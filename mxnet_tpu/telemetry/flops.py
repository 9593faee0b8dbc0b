"""Automatic FLOP accounting from JAX's lowered-HLO cost analysis.

MFU was the one telemetry number that still needed hand-feeding
(`set_step_flops` / `MXTPU_STEP_FLOPS`); every ROADMAP perf item stalls on
it. This module closes the loop: at jit-cache-fill time — the moment an
executable is built for a new (op, attrs, shapes) signature — the call
site asks XLA's HLO cost analysis how many FLOPs one execution costs
(`jax.stages.Lowered.cost_analysis()`, a trace+lower with NO backend
compile), remembers it, and every execution accumulates into a process-
wide counter. `observe_step` reads the per-step delta, so
`mxtpu_step_mfu` publishes with zero manual declarations, and the serving
layer prices each padding bucket (`mxtpu_serve_bucket_flops`) the same
way.

Accounting is wired at ONE place: the unified executable registry's fill
hook (`mxnet_tpu.compile.registry`), which every factory resolves
through — eager ops, autograd backward, Executor forward/backward,
gluon CachedOp, the sharded trainers, and via the Executor serving
bucket warm. Concrete fills price the executable once from the compile's
own `Lowered` (stored in persistent-tier artifact headers, so pricing
survives a zero-compile cold start); lazy fills use `instrument`'s
per-shape memo below. The cost: one extra trace+lower per NEW shape
signature (amortized to zero in steady state) and one float add per
execution. `MXTPU_TRACE_FLOPS=0` turns all of it off. Cost analysis can
fail (exotic primitives, missing backend support); every entry point
degrades to "unknown" (None) rather than ever breaking dispatch.

Jax is only imported lazily, from call sites that already did.
"""
from __future__ import annotations

from .. import env as _env
from . import core

__all__ = ["enabled", "accumulate", "total", "take_step_delta",
           "cost_analysis_flops", "measure", "PerShapeFlops"]


class _FlopState:
    def __init__(self):
        self.enabled = None     # None = read env lazily, cache after
        self.total = 0.0        # FLOPs executed since process start
        self.step_mark = 0.0    # total at the last observe_step
        self.last_step = None   # FLOPs attributed to the last step


_STATE = _FlopState()


def enabled():
    """Is automatic accounting on? (``MXTPU_TRACE_FLOPS``, default on;
    cached — flip it before the first compile, not mid-run.)"""
    if _STATE.enabled is None:
        _STATE.enabled = bool(core._STATE.enabled
                              and _env.get("MXTPU_TRACE_FLOPS"))
    return _STATE.enabled


def accumulate(flops):
    """Record one execution of an executable costing ``flops``. Plain
    float add — lock-free, same torn-sample trade as the metrics layer."""
    if flops:
        _STATE.total += flops


def total():
    """FLOPs executed by instrumented executables since process start.
    Serving warm brackets this to price each padding bucket."""
    return _STATE.total


def take_step_delta():
    """FLOPs executed since the previous call — the automatic per-step
    FLOP count `observe_step` uses when no manual value is declared.
    (Work between steps — eval forwards, serving traffic — lands in the
    next step's delta; steady-state training attributes cleanly.)"""
    t = _STATE.total
    delta = t - _STATE.step_mark
    _STATE.step_mark = t
    if delta > 0:
        _STATE.last_step = delta
    return delta


def last_step_flops():
    """The most recent nonzero per-step FLOP attribution."""
    return _STATE.last_step


def cost_analysis_flops(analysis):
    """Pull the ``flops`` figure out of a jax cost-analysis result, which
    is a dict in some jax versions and a per-computation list of dicts in
    others. Returns float or None."""
    if isinstance(analysis, (list, tuple)):
        vals = [d.get("flops") for d in analysis if isinstance(d, dict)]
        vals = [v for v in vals if v is not None and v >= 0]
        return float(sum(vals)) if vals else None
    if isinstance(analysis, dict):
        v = analysis.get("flops")
        return float(v) if v is not None and v >= 0 else None
    return None


def measure(jitted, args, kwargs=None):
    """FLOPs of one execution of ``jitted`` on ``args``: trace + lower
    (cheap; no backend compile) and run HLO cost analysis. None when
    accounting is off or analysis is unavailable for this computation."""
    if not enabled():
        return None
    try:
        lowered = jitted.lower(*args, **(kwargs or {}))
        return cost_analysis_flops(lowered.cost_analysis())
    except Exception:
        return None


def _shape_sig(x):
    """Hashable shape/dtype signature of a (possibly nested) argument."""
    if isinstance(x, (tuple, list)):
        return tuple(_shape_sig(e) for e in x)
    if isinstance(x, dict):
        return tuple(sorted((str(k), _shape_sig(v)) for k, v in x.items()))
    shape = getattr(x, "shape", None)
    if shape is None:
        return (type(x).__name__,)
    return (tuple(shape), str(getattr(x, "dtype", "")))


class PerShapeFlops:
    """Per-shape-signature FLOP memo for ONE jitted callable (whose jax-
    side cache is keyed by shapes the wrapper can't see). First call with
    a new signature pays one lower+cost-analysis; later calls are a dict
    lookup + float add."""

    __slots__ = ("_jitted", "_by_sig")

    def __init__(self, jitted):
        self._jitted = jitted
        self._by_sig = {}

    def observe(self, args):
        sig = _shape_sig(args)
        flops = self._by_sig.get(sig, -1.0)
        if flops == -1.0:
            flops = measure(self._jitted, args)
            self._by_sig[sig] = flops
        if flops:
            _STATE.total += flops


def instrument(jitted):
    """Wrap a jitted callable so every execution feeds the accumulator
    (per-shape memo as above). Returns ``jitted`` unchanged when
    accounting is off — zero overhead."""
    if not enabled():
        return jitted
    memo = PerShapeFlops(jitted)

    def call(*args):
        memo.observe(args)
        return jitted(*args)

    call._flops_memo = memo  # introspection for tests
    return call
