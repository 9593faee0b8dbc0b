"""What the run is on, as JAX reports it."""
from __future__ import annotations

import sys


def devices_or_exit(chips, allow_cpu=False):
    """The first ``chips`` devices.  Without an accelerator, or with fewer
    chips than the cell asks for, the process exits non-zero and prints no
    result (a rehearsal may pass ``allow_cpu``; it never reports a device
    metric)."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.stderr.write("chipbench: no backend: %s\n" % e)
        raise SystemExit(3)
    if devs[0].platform == "cpu" and not allow_cpu:
        sys.stderr.write("chipbench: JAX found no accelerator (platform cpu);"
                         " a cell is measured on the chip or not at all\n")
        raise SystemExit(3)
    if len(devs) < chips:
        sys.stderr.write("chipbench: the cell asks for %d chips, JAX has %d\n"
                         % (chips, len(devs)))
        raise SystemExit(3)
    return devs[:chips]


def describe(devs, memory_peak_bytes):
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs),
            "memory_peak_bytes": int(memory_peak_bytes)}


def memory_peak_bytes(devs):
    """Peak bytes on the fullest chip.  On this runtime ``peak_bytes_in_use``
    counts live arrays only; a program's temporaries show up under
    ``peak_bytes_reserved`` (PERF.md, PR 23), so the larger of the two is
    the peak.  0 where the backend reports nothing (CPU)."""
    peak = 0
    for d in devs:
        s = d.memory_stats() or {}
        peak = max(peak, s.get("peak_bytes_in_use", 0),
                   s.get("peak_bytes_reserved", 0))
    return peak
