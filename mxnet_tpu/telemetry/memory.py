"""Memory observability: HBM attribution, live accounting, OOM forensics.

The reference framework devoted a whole layer to memory (the storage
allocator + NNVM memory planning, PAPER.md) and shipped a graph memory
profiler; on TPUs HBM — not FLOPs — is the resource that gates replica
density, donated whole-step buffers and prefetch depth. This module is
the third axis of the telemetry spine (time = tracing, compute = flops,
memory = here), in three parts:

  * **per-executable attribution** — at the unified executable registry's
    single fill hook (`mxnet_tpu.compile.registry`, exactly where FLOP
    pricing lives), every AOT compile captures
    `Compiled.memory_analysis()`: argument / output / temp / generated-
    code / aliased bytes. The figures are recorded in a process-wide
    table (`record_executable`), persisted in the ``MXTPUEXE1`` artifact
    header, and read back on a persistent-tier hit — a zero-compile cold
    start still knows every executable's footprint. The serving layer
    brackets its per-bucket warm with `recorded_mark`/`recorded_since`
    to price each padding bucket (`model_footprint`), which is what the
    ``MXTPU_SERVE_MEMORY_BUDGET`` admission check enforces.
  * **live accounting** — device gauges polled from jax
    ``memory_stats()`` (graceful None on CPU), process RSS/VmHWM from
    ``/proc/self/status`` (real numbers even where the backend reports
    nothing), NDArray live-count/live-bytes maintained at construction /
    ``__del__`` (ndarray.py hooks), and a per-step peak-delta histogram
    (`observe_step_delta`) so a trace exemplar can name the step that
    spiked.
  * **forensics** — `snapshot()` is the flight recorder's memory block:
    gauge values, the last polled device stats, and the top-N
    executables by temp bytes. It is SIGNAL-SAFE by construction (plain
    dict reads, one /proc file read, no jax, no locks, no logging) and
    is walked by mxlint's signal-safety checker. The **donation
    verifier** (`verify_donation`, called from the fill hook for keys
    that declare donated arguments) checks from memory_analysis that the
    fused trainer step actually aliases its donated param/optimizer
    buffers — ROADMAP item 1's key invariant as a checked metric
    (`mxtpu_donation_alias_bytes` vs `mxtpu_donation_declared_bytes`)
    instead of a hope.

Pure stdlib on every always-on path; jax is touched only from
`sample_devices` (never from the signal path — the dump reads the cached
last sample). ``MXTPU_TELEMETRY=0`` turns everything into no-ops.
"""
from __future__ import annotations

import collections
import os
import sys
import threading
import time

try:  # imported at module load, NOT from the signal path (import lock)
    import resource as _resource
except ImportError:  # non-POSIX
    _resource = None

from .. import env as _env
from . import core

__all__ = [
    "enabled", "from_compiled", "record_executable", "lookup_key",
    "recorded_mark", "recorded_since", "executables_top", "sum_figures",
    "bucket_figures", "footprint_bytes", "verify_donation",
    "last_donation_report", "read_process_memory", "sample_devices",
    "sample", "observe_step_delta", "snapshot", "ndarray_created",
    "ndarray_freed", "ndarray_resized", "ndarray_live", "parse_bytes",
    "serve_memory_budget", "model_footprint", "ensure_poller",
]

# memory_analysis attribute -> short figure key (the artifact-header and
# snapshot spelling; host_* variants are ignored — device memory is the
# scarce resource this module exists for)
_FIGURES = (
    ("argument_size_in_bytes", "arguments"),
    ("output_size_in_bytes", "outputs"),
    ("temp_size_in_bytes", "temp"),
    ("generated_code_size_in_bytes", "generated_code"),
    ("alias_size_in_bytes", "alias"),
)


def enabled():
    """Memory accounting rides the master telemetry switch — there is no
    separate gate: every always-on path is a handful of plain adds."""
    return core._STATE.enabled


# ---------------------------------------------------------------------------
# per-executable attribution (fed by mxnet_tpu.compile.registry)
# ---------------------------------------------------------------------------

class _MemState:
    def __init__(self):
        # executable table: insertion-ordered digest/label -> figures
        # (plain dict: GIL-atomic reads keep snapshot() signal-safe)
        self.executables = {}
        # PER-THREAD attribution log (same discipline as the registry's
        # per-thread fill log): a warm brackets its own thread's records
        # with recorded_mark/_since, so a concurrent load or live batcher
        # traffic on another thread never inflates a bucket's figures —
        # and each thread's log is a BOUNDED deque, so a long-lived
        # serving worker can't leak through its own telemetry
        self.log_local = threading.local()
        self.nd_live = [0, 0]    # [count, bytes] — ndarray.py hooks
        self.devices = None      # last sample_devices() result (cached
        #                          for the signal-safe snapshot)
        self.devices_ts = None
        self.caps = None         # does the backend report memory_stats?
        self.step_peak = None    # peak bytes at the last observe_step
        self.step_peak_ts = 0.0  # monotonic time of that probe
        self.last_donation = None
        self.poller = None
        self.poller_decided = False


_STATE = _MemState()
_MAX_EXECUTABLES = 4096  # runaway-shape backstop, same order as the LRU
_MAX_LOG = 4096          # per-thread attribution-log bound
# serializes ensure_poller's cold path only (same double-checked shape as
# core._DECIDE_LOCK): an unlocked decided-flag race could start 2 pollers
_DECIDE_LOCK = threading.Lock()


def _reset_after_fork():
    st = _MemState()
    st.executables = dict(_STATE.executables)  # attribution is still true
    # inherited NDArrays are alive in the child and their __del__ will
    # decrement — the counts must carry over or the gauges go negative
    st.nd_live = list(_STATE.nd_live)
    globals()["_STATE"] = st


def _thread_log():
    """(seq_counter_ref, entries deque) for the calling thread. Entries
    are (seq, entry_key) pairs; the deque bound means a cursor older than
    the window simply sees fewer entries, never wrong ones."""
    local = _STATE.log_local
    entries = getattr(local, "entries", None)
    if entries is None:
        entries = local.entries = collections.deque(maxlen=_MAX_LOG)
        local.seq = 0
    return local, entries


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reset_after_fork)


def from_compiled(compiled):
    """Figures dict from a jax ``Compiled``'s ``memory_analysis()``, or
    None when the backend doesn't support it (never raises — attribution
    is best-effort, exactly like FLOP pricing)."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for attr, name in _FIGURES:
        v = getattr(ma, attr, None)
        if v is None and isinstance(ma, dict):
            v = ma.get(attr)
        if v is not None:
            out[name] = int(v)
    return out or None


def record_executable(kind, label, digest, figures, key=None):
    """Record one executable's memory figures into the process table (and
    the bracketing log). ``key`` (the registry's `ExecutableKey`) indexes
    the entry so later MEMORY-TIER HITS can still be attributed — a
    reload of an already-resident model fills nothing, but its warm still
    touches the keys (`lookup_key`). Safe with figures=None (no-op)."""
    if not figures or not enabled():
        return
    local, entries = _thread_log()
    entry_key = key if key is not None else (
        digest or "%s:%s:%d" % (kind, label, local.seq))
    entry = {"kind": kind, "label": label, "digest": digest}
    entry.update(figures)
    if len(_STATE.executables) >= _MAX_EXECUTABLES \
            and entry_key not in _STATE.executables:
        _STATE.executables.pop(next(iter(_STATE.executables)), None)
    _STATE.executables[entry_key] = entry
    local.seq += 1
    entries.append((local.seq, entry_key))


def lookup_key(key):
    """Figures entry recorded under a registry `ExecutableKey`, or None."""
    return _STATE.executables.get(key)


def recorded_mark():
    """Cursor into THIS THREAD's attribution log — bracket a load/warm
    with `recorded_mark()` / `recorded_since()` to learn which
    executables' figures it contributed (the serving per-bucket
    footprint). Fills on other threads never leak into the bracket."""
    local, _ = _thread_log()
    return local.seq


def recorded_since(cursor):
    """This thread's figure entries recorded since ``cursor``
    (deduplicated, in fill order)."""
    _, entries = _thread_log()
    seen, out = set(), []
    for seq, k in entries:
        if seq <= cursor or k in seen:
            continue
        seen.add(k)
        entry = _STATE.executables.get(k)
        if entry is not None:
            out.append(entry)
    return out


def executables_top(n=10, by="temp"):
    """Top-``n`` recorded executables by one figure (default temp bytes —
    the live-working-set contribution). Plain dict reads: signal-safe."""
    rows = [e for e in list(_STATE.executables.values()) if e.get(by)]
    rows.sort(key=lambda e: e.get(by, 0), reverse=True)
    return rows[:n]


def sum_figures(entries):
    """Combine several executables' figure dicts into one (the serving
    per-bucket roll-up: a bucket warm may fill forward + helper
    executables). {} when nothing was recorded."""
    out = {}
    for entry in entries:
        for _, name in _FIGURES:
            v = entry.get(name)
            if v is not None:
                out[name] = out.get(name, 0) + int(v)
    return out


def bucket_figures(touched_keys, recorded_entries):
    """One bucket warm's combined figures: the entries its FILLS recorded
    (`recorded_since`) plus table entries for the keys it merely TOUCHED
    (memory-tier hits on an already-resident executable — the reload
    path), each executable counted once."""
    seen, entries = set(), []
    for e in recorded_entries:
        if id(e) not in seen:
            seen.add(id(e))
            entries.append(e)
    for k in touched_keys:
        e = _STATE.executables.get(k)
        if e is not None and id(e) not in seen:
            seen.add(id(e))
            entries.append(e)
    return sum_figures(entries)


def footprint_bytes(figures):
    """One executable's device-footprint contribution: arguments +
    outputs + temps + generated code, minus aliased (donated) bytes that
    arguments and outputs double-count."""
    if not figures:
        return 0
    return max(0, figures.get("arguments", 0) + figures.get("outputs", 0)
               + figures.get("temp", 0) + figures.get("generated_code", 0)
               - figures.get("alias", 0))


def model_footprint(per_bucket):
    """Total footprint of a served model from its per-bucket figures
    (``{bucket: figures}``). Buckets SHARE weights (the argument bytes
    are dominated by one weight copy per model, `predict._clone_with`),
    so the total counts the largest bucket's argument bytes once plus
    every bucket's private outputs/temps/code."""
    if not per_bucket:
        return None
    args = max((f.get("arguments", 0) for f in per_bucket.values()),
               default=0)
    private = sum(f.get("outputs", 0) + f.get("temp", 0)
                  + f.get("generated_code", 0)
                  for f in per_bucket.values())
    return args + private


# ---------------------------------------------------------------------------
# donation verifier
# ---------------------------------------------------------------------------

def _leaf_nbytes(x):
    nb = getattr(x, "nbytes", None)
    if nb is not None:
        return int(nb)
    if isinstance(x, (list, tuple)):
        return sum(_leaf_nbytes(e) for e in x)
    if isinstance(x, dict):
        return sum(_leaf_nbytes(v) for v in x.values())
    # aval-only example args (jax.ShapeDtypeStruct): size from shape/dtype
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        n = 1
        for d in shape:
            n *= int(d)
        return n * int(getattr(dtype, "itemsize", 0) or 0)
    return 0


# the decode engine's executables (serving/generate.py): what they donate
# is the whole KV pool, which they must also update IN PLACE
_KV_POOL_KINDS = ("lm_prefill", "lm_decode")


def verify_donation(key, example_args, figures, threshold=0.5):
    """Check, from an executable's memory figures, that the buffers its
    key DECLARES donated (``key.donation`` argnums) were actually aliased
    by XLA (``alias`` bytes ≈ donated bytes). Publishes
    ``mxtpu_donation_declared_bytes`` / ``mxtpu_donation_alias_bytes``
    gauges (labeled by key kind) and a ``donation_unaliased`` flight-
    recorder event when the aliased fraction falls under ``threshold`` —
    a fused trainer step that silently stopped donating is an extra
    whole-model allocation, exactly the regression ROADMAP item 1 cannot
    afford. Returns the report dict (also kept for
    `last_donation_report`), or None when unverifiable.

    For the decode engine's executables the donated bytes are the KV
    pool, and aliasing alone does not say the pool stayed where it lies:
    a program can alias its output and still copy, slice or pad the pool
    on the way. Its temporaries do say it, so they are published as
    ``mxtpu_serve_exe_temp_bytes{kind}``, and temporaries over a quarter
    of the pool record a ``kv_pool_not_in_place`` event."""
    if not enabled() or not key.donation or figures is None \
            or figures.get("alias") is None:
        return None
    declared = 0
    for i in key.donation:
        try:
            declared += _leaf_nbytes(example_args[int(i)])
        except (IndexError, TypeError, ValueError):
            return None
    if not declared:
        return None
    alias = int(figures.get("alias", 0))
    report = {
        "kind": key.kind,
        "declared_bytes": int(declared),
        "alias_bytes": alias,
        "aliased_fraction": alias / float(declared),
        "ok": alias >= threshold * declared,
    }
    _STATE.last_donation = report
    labels = {"kind": key.kind}
    core.gauge("mxtpu_donation_declared_bytes", labels).set(declared)
    core.gauge("mxtpu_donation_alias_bytes", labels).set(alias)
    from . import recorder

    if not report["ok"]:
        recorder.record_event(
            "donation_unaliased", key_kind=key.kind,
            declared_bytes=int(declared), alias_bytes=alias,
            aliased_fraction=round(report["aliased_fraction"], 4))
    if key.kind in _KV_POOL_KINDS and figures.get("temp") is not None:
        temp = report["temp_bytes"] = int(figures["temp"])
        core.gauge("mxtpu_serve_exe_temp_bytes", labels).set(temp)
        if temp > declared // 4:
            recorder.record_event(
                "kv_pool_not_in_place", key_kind=key.kind,
                temp_bytes=temp, kv_bytes=int(declared))
    return report


def last_donation_report():
    """The most recent `verify_donation` report (bench evidence reads
    this after one trainer step), or None."""
    return _STATE.last_donation


# ---------------------------------------------------------------------------
# live accounting: process / device / NDArray
# ---------------------------------------------------------------------------

def read_process_memory():
    """{'rss': bytes, 'vmhwm': bytes} from ``/proc/self/status`` (stdlib,
    ~50µs), or None off-Linux. Kernels that hide ``VmHWM`` (sandboxed
    containers) fall back to ``getrusage`` ru_maxrss for the high-water
    mark. Works where ``memory_stats()`` returns None — CPU boxes get
    real numbers. Signal-safe: one file read + one syscall."""
    out = {}
    try:
        with open("/proc/self/status") as f:
            text = f.read()
    except OSError:
        text = ""
    for line in text.splitlines():
        if line.startswith("VmRSS:"):
            out["rss"] = int(line.split()[1]) * 1024
        elif line.startswith("VmHWM:"):
            out["vmhwm"] = int(line.split()[1]) * 1024
    if "vmhwm" not in out and _resource is not None:
        try:
            out["vmhwm"] = _resource.getrusage(
                _resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            pass
    return out or None


def sample_devices():
    """Poll ``memory_stats()`` on every local device into per-device
    dicts (bytes_in_use / peak_bytes_in_use / bytes_limit, whichever the
    backend reports). Returns None on backends without stats (CPU) —
    gracefully, once (the capability is cached). NEVER called from the
    signal path (the dump reads the cached last sample), and NEVER the
    first thing to touch the backend: a telemetry flusher/scrape thread
    must not initialize XLA (and with it claim the chip), so sampling
    waits until some real computation has already brought the backend
    up."""
    if _STATE.caps is False or not enabled():
        return _STATE.devices if _STATE.caps else None
    if "jax" not in sys.modules:
        return None
    try:
        import jax
        from jax._src import xla_bridge as _xb

        if not getattr(_xb, "_backends", None):
            return None  # backend not initialized — do not dial from here
        devs = jax.local_devices()
    except Exception:
        return None
    out = {}
    for d in devs:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out[str(getattr(d, "id", len(out)))] = {
            k: int(v) for k, v in stats.items()
            if isinstance(v, (int, float)) and k in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_free_block_bytes", "bytes_reserved")}
    # last-sample cache, lock-free BY DESIGN: the flight recorder's
    # signal-context snapshot() reads these fields, so no lock may ever
    # guard them (poller/flusher/scrape racers each publish a complete
    # sample; a reader sees one sample or the other, never a crash)
    if not out:
        _STATE.caps = False  # mxlint: gil-atomic — signal-safe cache
        return None
    _STATE.caps = True  # mxlint: gil-atomic — signal-safe cache
    _STATE.devices = out  # mxlint: gil-atomic — signal-safe cache
    _STATE.devices_ts = time.time()  # mxlint: gil-atomic — signal-safe cache
    for dev_id, stats in out.items():
        labels = {"device": dev_id}
        if "bytes_in_use" in stats:
            core.gauge("mxtpu_device_bytes_in_use", labels).set(
                stats["bytes_in_use"])
        if "peak_bytes_in_use" in stats:
            core.gauge("mxtpu_device_bytes_peak", labels).set(
                stats["peak_bytes_in_use"])
        if "bytes_limit" in stats:
            core.gauge("mxtpu_device_bytes_limit", labels).set(
                stats["bytes_limit"])
    return out


def ndarray_created(nbytes):
    """NDArray construction hook (ndarray.py): plain list adds — this is
    the imperative hot path."""
    st = _STATE.nd_live
    st[0] += 1
    st[1] += nbytes


def ndarray_freed(nbytes):
    """NDArray ``__del__`` hook. Must never raise: interpreter shutdown
    may have torn half the module down already."""
    try:
        st = _STATE.nd_live
        st[0] -= 1
        st[1] -= nbytes
    except Exception:
        pass


def ndarray_resized(delta):
    """`_set_data` swapped in a different-sized buffer."""
    _STATE.nd_live[1] += delta


def ndarray_live():
    """(live_count, live_bytes) of NDArray handles this process holds."""
    return _STATE.nd_live[0], _STATE.nd_live[1]


def sample(devices=True):
    """Refresh every memory gauge: process RSS/VmHWM, NDArray live
    count/bytes, and (``devices=True``) the per-device stats. Called from
    the JSONL flush, the Prometheus scrape, the optional poller thread
    (``MXTPU_MEMORY_POLL_MS``) and per-step. Cheap: one /proc read plus
    plain gauge stores."""
    if not enabled():
        return None
    proc = read_process_memory()
    if proc is not None:
        if "rss" in proc:
            core.gauge("mxtpu_process_rss_bytes").set(proc["rss"])
        if "vmhwm" in proc:
            core.gauge("mxtpu_process_vmhwm_bytes").set(proc["vmhwm"])
    live, live_bytes = ndarray_live()
    core.gauge("mxtpu_ndarray_live").set(live)
    core.gauge("mxtpu_ndarray_live_bytes").set(live_bytes)
    if devices:
        sample_devices()
    return proc


def _peak_bytes():
    """The process's best peak-memory signal: device peak when the
    backend reports one (HBM is what OOMs), else the RSS high-water
    mark. This sits on the per-step hot path, so the host fallback is
    ONE getrusage syscall — never a /proc read (~200µs on sandboxed
    kernels, which a <2%-overhead budget cannot afford)."""
    if _STATE.caps is not False:
        devs = sample_devices()
        if devs:
            return sum(s.get("peak_bytes_in_use", 0) for s in devs.values())
    if _resource is not None:
        try:
            return _resource.getrusage(_resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            pass
    proc = read_process_memory()
    if proc is None:
        return None
    return proc.get("vmhwm") or proc.get("rss")


_STEP_PROBE_MIN_S = 0.1  # rate limit: the peak probe is a syscall (and
#                          sandboxed kernels make getrusage ~15µs); steps
#                          faster than this share one probe window — the
#                          <2% per-step overhead contract stands, and
#                          fast steps barely move the peak anyway


def observe_step_delta(exemplar=None, force=False):
    """Per-step peak-memory growth: how much the peak (device, else
    VmHWM) moved since the previous probe, into the
    ``mxtpu_step_peak_bytes_delta`` histogram — with the step's trace id
    as exemplar, so the step that spiked memory names a renderable
    trace. Called from `telemetry.observe_step`; probed at most every
    ``_STEP_PROBE_MIN_S`` (``force=True`` bypasses — tests)."""
    if not enabled():
        return
    now = time.monotonic()
    if not force and now - _STATE.step_peak_ts < _STEP_PROBE_MIN_S:
        return
    _STATE.step_peak_ts = now
    peak = _peak_bytes()
    if peak is None:
        return
    prev = _STATE.step_peak
    _STATE.step_peak = peak
    if prev is None:
        return
    core.histogram("mxtpu_step_peak_bytes_delta",
                   bounds=core.BYTE_BOUNDS).observe(
        max(0, peak - prev), exemplar=exemplar)


# ---------------------------------------------------------------------------
# poller
# ---------------------------------------------------------------------------

def _poller_loop(period_s):
    while True:
        time.sleep(period_s)
        if os.getpid() != core._STATE.owner_pid:
            return
        sample()


def ensure_poller():
    """Start the background gauge poller once if ``MXTPU_MEMORY_POLL_MS``
    asks for one (default off — the flush/scrape/step sampling is enough
    for most runs; long forwards between steps are what the poller is
    for). Env decision cached, same discipline as the flusher."""
    if _STATE.poller_decided:
        return
    with _DECIDE_LOCK:  # double-checked: only the cold path locks
        if _STATE.poller_decided:
            return
        _STATE.poller_decided = True
        if not enabled():
            return
        period_ms = _env.get("MXTPU_MEMORY_POLL_MS")
        if not period_ms or period_ms <= 0:
            return
        t = threading.Thread(target=_poller_loop,
                             args=(max(0.01, period_ms / 1e3),),
                             name="mxtpu-memory-poll", daemon=True)
        _STATE.poller = t
        t.start()


# ---------------------------------------------------------------------------
# forensics snapshot (flight-recorder dump block — SIGNAL-SAFE)
# ---------------------------------------------------------------------------

def snapshot(top_n=10):
    """The flight recorder's memory block: process RSS/VmHWM (read fresh
    — one /proc read), the LAST polled device stats (never a fresh jax
    call from a signal context), NDArray live accounting, the top-N
    executables by temp bytes, and the last donation report. Every hang/
    OOM dump says what was resident. Walked by mxlint signal-safety."""
    return {
        "process": read_process_memory(),
        "devices": _STATE.devices,
        "devices_sampled_ago_s":
            None if _STATE.devices_ts is None
            else round(time.time() - _STATE.devices_ts, 1),
        "ndarray": {"live": _STATE.nd_live[0],
                    "live_bytes": _STATE.nd_live[1]},
        "executables_by_temp": executables_top(top_n),
        "donation": _STATE.last_donation,
    }


# ---------------------------------------------------------------------------
# serving memory budget
# ---------------------------------------------------------------------------

_SUFFIX = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40}


def parse_bytes(text):
    """``"1073741824"`` / ``"512M"`` / ``"1.5G"`` -> bytes (int), or None
    on a value that parses to nothing."""
    s = str(text).strip().lower()
    if not s:
        return None
    mult = 1
    if s[-1] in _SUFFIX:
        mult = _SUFFIX[s[-1]]
        s = s[:-1]
    try:
        return int(float(s) * mult)
    except ValueError:
        return None


def serve_memory_budget():
    """The serving memory budget from ``MXTPU_SERVE_MEMORY_BUDGET``:
    ``(limit_bytes, warn_only)`` or ``(None, False)`` when unset. A
    ``warn:`` prefix turns rejection into a logged warning (canary
    posture); a malformed value disables the check (never blocks a
    load)."""
    raw = _env.raw("MXTPU_SERVE_MEMORY_BUDGET") or ""
    warn = False
    if raw.lower().startswith("warn:"):
        warn = True
        raw = raw[5:]
    limit = parse_bytes(raw) if raw else None
    return limit, warn
