"""mxnet_tpu.telemetry — always-on runtime metrics + distributed flight
recorder.

One coherent telemetry spine for the framework (docs/observability.md):

  * `counter` / `gauge` / `histogram` — lock-free per-process metrics with
    periodic JSONL flush (``MXTPU_TELEMETRY_DIR``) and an optional
    Prometheus text endpoint (``MXTPU_TELEMETRY_PORT``) — core.py;
  * `record_event` / `record_step` / `dump` — a ring buffer of recent
    events plus a hang watchdog (``MXTPU_WATCHDOG_TIMEOUT``) and SIGUSR1
    stack dumps — recorder.py;
  * `observe_step` — the single call every trainer step makes: step wall
    time, examples/sec, achieved MFU (when per-step FLOPs are known), and
    the watchdog heartbeat.

Zero hard dependencies (pure stdlib; jax is only touched lazily for the MFU
peak-FLOPs lookup), metrics default ON, exporters default OFF.
"""
from __future__ import annotations


from .. import env as _env
from .core import (  # noqa: F401
    BYTE_BOUNDS, LATENCY_BOUNDS, counter, enabled, flush, gauge,
    get_registry, histogram, prometheus_text, rank, restart_generation,
    set_enabled, snapshot, start_http_server, telemetry_dir,
)
from .recorder import (  # noqa: F401
    dump, dump_path, events, install_signal_handler, last_step, record_event,
    record_step,
)
from . import core as _core
from . import flops  # noqa: F401  (automatic FLOP accounting)
from . import goodput  # noqa: F401  (per-step stall attribution)
from . import memory  # noqa: F401  (HBM/RSS attribution + live gauges)
from . import slo  # noqa: F401  (windowed SLO engine + /statusz)
from . import tracing  # noqa: F401  (distributed request/step spans)

__all__ = [
    "counter", "gauge", "histogram", "enabled", "set_enabled", "snapshot",
    "prometheus_text", "flush", "start_http_server", "get_registry",
    "record_event", "record_step", "events", "dump", "dump_path",
    "last_step", "install_signal_handler", "observe_step", "set_step_flops",
    "rank", "restart_generation", "telemetry_dir", "tracing", "flops",
    "goodput", "memory", "slo", "LATENCY_BOUNDS", "BYTE_BOUNDS",
]


# ---------------------------------------------------------------------------
# step-level instrumentation (shared by gluon.Trainer, DistributedTrainer,
# PipelineTrainer and the module.fit loop)
# ---------------------------------------------------------------------------

_STEP_FLOPS = [None]     # model FLOPs per optimizer step (fwd+bwd), if known
_PEAK_FLOPS = [False]    # False = not yet resolved; None = CPU (no MFU)


def set_step_flops(flops):
    """Declare the model's FLOPs per training step so `observe_step` can
    publish achieved MFU against `runtime.chip_peak_tflops`. Benchmarks and
    training scripts that know their FLOP count call this once;
    ``MXTPU_STEP_FLOPS`` is the env spelling."""
    _STEP_FLOPS[0] = float(flops) if flops else None


if _env.is_set("MXTPU_STEP_FLOPS"):
    _step_flops_env = _env.get("MXTPU_STEP_FLOPS")
    if _step_flops_env is not None:  # malformed value falls back to unset
        set_step_flops(_step_flops_env)


def _peak_flops():
    """Aggregate peak bf16 FLOP/s of the local devices (cached). None on
    the CPU backend, which has no MFU; an accelerator whose `device_kind`
    is missing from `runtime.PEAK_BF16_TFLOPS` raises."""
    if _PEAK_FLOPS[0] is False:
        import jax

        from .. import runtime

        devs = jax.devices()
        if devs[0].platform == "cpu":
            _PEAK_FLOPS[0] = None
        else:
            _PEAK_FLOPS[0] = (runtime.chip_peak_tflops(devs[0]) * 1e12
                              * len(devs))
    return _PEAK_FLOPS[0]


_STEP_METRICS = {}  # kind -> (hist, steps, examples, eps, mfu) — the per-
                    # step path must not pay 4 registry lookups per call


def _step_metrics(kind):
    m = _STEP_METRICS.get(kind)
    if m is None:
        labels = {"kind": kind}
        m = (_core._REGISTRY.histogram("mxtpu_step_seconds", labels),
             _core._REGISTRY.counter("mxtpu_steps_total", labels),
             _core._REGISTRY.counter("mxtpu_examples_total", labels),
             _core._REGISTRY.gauge("mxtpu_examples_per_sec", labels),
             _core._REGISTRY.gauge("mxtpu_step_mfu", labels),
             _core._REGISTRY.gauge("mxtpu_step_flops_auto", labels))
        _STEP_METRICS[kind] = m
    return m


def observe_step(duration_s, examples=None, step=None, kind="train"):
    """Record one completed training step: latency histogram (with a
    trace-id exemplar when the step is traced), step/example counters,
    examples/sec gauge, achieved-MFU gauge, plus the flight-recorder
    heartbeat that feeds the hang watchdog. Step FLOPs for the MFU come
    from `set_step_flops`/``MXTPU_STEP_FLOPS`` when declared, else from
    the automatic cost-analysis accounting (`telemetry.flops`) — the
    FLOPs instrumented executables actually ran since the last step."""
    if not _core._STATE.enabled:
        return
    # first step of each trainer kind registers its optional SLO
    # objectives (step-time ceiling / MFU floor / staleness — only the
    # knobs that are set); later steps pay one set-membership check
    if kind not in slo._STATE.wired_train:
        slo.wire_training(kind)
    hist, c_steps, c_examples, g_eps, g_mfu, g_auto = _step_metrics(kind)
    trace_id = tracing.current_trace_id()
    hist.observe(duration_s, exemplar=trace_id)
    # per-step peak-memory growth (device peak or VmHWM), exemplared with
    # the step's trace so a memory spike names a renderable trace
    memory.observe_step_delta(exemplar=trace_id)
    memory.ensure_poller()
    c_steps.inc()
    if examples is not None and duration_s > 0:
        c_examples.inc(int(examples))
        g_eps.set(examples / duration_s)
    auto = flops.take_step_delta() if flops.enabled() else 0.0
    step_flops = _STEP_FLOPS[0] or auto
    if step_flops and duration_s > 0:
        if auto and not _STEP_FLOPS[0]:
            g_auto.set(auto)
        peak = _peak_flops()
        if peak:
            g_mfu.set((step_flops / duration_s) / peak)
    record_step(step)


