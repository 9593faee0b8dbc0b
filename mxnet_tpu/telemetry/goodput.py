"""The phase accountant of the hot loops: per-step stall attribution and
cumulative phase totals (docs/observability.md §Goodput, §Lap phases).

Every training step — gluon ``Trainer.step``, ``DistributedTrainer``/
``ShardedTrainer``/``PipelineTrainer.step``, ``module.fit`` — and every lap
of the decode scheduler (``serving.generate.GenerateScheduler``, kind
``serve``, phases :data:`SERVE_PHASES`) brackets itself with
:func:`step_start` / :func:`step_end` and attributes slices of its wall
time to exhaustive, non-overlapping phases. A training step's are:

``data_wait``
    iterator ``next()`` / ``device_put`` / batch-shard blocking
``host_dispatch``
    Python between step entry and the executable launch
    (:func:`mark_launch`) that no finer phase claimed
``compile``
    executable-cache miss time (``compile.registry`` attributes its whole
    miss path — persistent-tier loads and true fills)
``compute``
    the device step itself
``checkpoint_stall``
    sync save + async-writer submit blocking
    (``parallel.resilience`` forwards its ``mxtpu_checkpoint_stall_seconds``
    observations here)
``collective``
    gradient allreduce outside the fused step
``other``
    the honest remainder — ``wall - sum(attributed)``, never negative

Per-step phases land in ``mxtpu_step_phase_seconds{phase=}`` histograms
(with trace-id exemplars when the step's root span is sampled) and
cumulative ``mxtpu_goodput_phase_seconds_total{phase=}`` counters; a
rolling window of the last ``MXTPU_GOODPUT_WINDOW_STEPS`` steps feeds the
``mxtpu_goodput_fraction`` gauge (windowed compute ÷ wall) and the
``/statusz`` ``training`` block. Time between steps (the training loop
doing neither) accumulates in the cumulative-only ``between_steps``
phase — it has no per-step histogram because it is not part of any step —
minus whatever out-of-step attribution (e.g. a checkpoint stall between
steps) already claimed. ``tools/goodput_report.py`` joins these counters
from each rank's final telemetry flush with the launcher's
``launcher-events.jsonl`` generation/downtime ledger into the whole-job
decomposition.

Every bracket and every phase inside one is also an annotation in the
profiler's trace (``mxtpu.<kind>.step`` / ``mxtpu.serve.lap``,
``mxtpu.<kind>.<phase>``), stamped by ``jax.profiler`` on the clock it
stamps device operations with, so an idle gap of the device lies inside a
named phase of the host. jax is used only if the process has already
loaded it. Every closed bracket leaves a whole record in a bounded ring
per kind (:func:`window`).

What happens before the first step or lap — import, artifact IO, engine
and trainer construction, each program's trace / lower / backend compile
and its first run — is a third ring, ``window("startup")``, of whole
:class:`span` records (docs/observability.md §Start-up); nothing is written
into it by a step or a lap after the first.

Accounting state is thread-local: concurrent trainers (tests, serving +
training in one process) never cross-attribute. All read paths used by
signal handlers (:func:`snapshot`, :func:`statusz_block`) are lock-free
and allocation-light — mxlint's signal-safety checker walks them.
"""
import atexit
import collections
import itertools
import sys
import threading
import time

from .. import env as _env
from . import core as _core
from . import tracing as _tracing

# step-internal phases (each has a per-step histogram series);
# ``between_steps`` additionally exists as a cumulative-only counter label
PHASES = ("data_wait", "host_dispatch", "compile", "compute",
          "checkpoint_stall", "collective", "other")

# the decode scheduler's lap (kind ``serve``): ``admit`` is queue, deadline
# and page housekeeping; ``prefill_host`` / ``decode_dispatch`` run until
# the executable call returns, ``prefill_wait`` / ``decode_wait`` are
# blocked on the device; ``build`` is the numpy batch build; ``retire`` the
# per-sequence loop
SERVE_PHASES = ("admit", "prefill_host", "prefill_wait", "build",
                "decode_dispatch", "decode_wait", "retire", "other")
_KIND_PHASES = {"serve": SERVE_PHASES}   # every other kind: PHASES

_TLS = threading.local()

# kind -> ring of whole records of closed brackets: t0, t1 (perf_counter),
# phases, traced, cpu_s, step and what the bracket's owner handed step_end
_RING_LEN = 4096
_RINGS = {}
_LAST_TRAIN_KIND = None   # whose ring feeds the gauge and /statusz
_GAUGE_STEPS = None       # MXTPU_GOODPUT_WINDOW_STEPS, read at first step

_FIRST_STEP_STARTUP_S = None  # first span's start -> first step's start

# the start-up account (ring ``startup``): jax's own compile stages by the
# monitoring event that carries each, and what its cache events say of a
# backend compile
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile"}
_JAX_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
              "/jax/compilation_cache/cache_misses": "miss"}
_JAX_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_ARMED = False     # the listeners are registered once a process
_JAX_NOT_TRACING = None  # jax's own "no trace is in progress", if it has one
_SPAN_IDS = itertools.count(1)
_STARTUP_T0 = None     # start of the first span written (perf_counter)
_READY = None          # the first ``ready`` mark's record
_STARTUP = None        # what the first ``ready`` published (/statusz)

_METRICS = None  # (hist_by_phase, ctr_by_phase, wall_ctr, frac_gauge)

_ATEXIT_REGISTERED = False


def _enabled():
    return _core._STATE.enabled and _env.get("MXTPU_GOODPUT")


def _metrics():
    global _METRICS, _GAUGE_STEPS
    m = _METRICS
    if m is None:
        hists = {p: _core.histogram("mxtpu_step_phase_seconds",
                                    {"phase": p}) for p in PHASES}
        ctrs = {p: _core.counter("mxtpu_goodput_phase_seconds_total",
                                 {"phase": p})
                for p in PHASES + ("between_steps",)}
        m = _METRICS = (hists, ctrs,
                        _core.counter("mxtpu_goodput_wall_seconds_total"),
                        _core.gauge("mxtpu_goodput_fraction"))
    if _GAUGE_STEPS is None:
        # mxlint: gil-atomic — one-time sizing latch
        _GAUGE_STEPS = max(8, int(_env.get("MXTPU_GOODPUT_WINDOW_STEPS")))
    return m


def _trace_me():
    """``jax.profiler.TraceAnnotation`` if this process has loaded jax (the
    telemetry package stays importable without it), else None."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


def _acct():
    return getattr(_TLS, "acct", None)


def step_start(kind="train", t0=None, step=None):
    """Open a step accounting bracket; ``kind`` selects its phase set
    (``serve``: :data:`SERVE_PHASES`; any other: :data:`PHASES`) and names
    its annotations. ``t0`` back-dates the step start (``module.fit`` opens
    the bracket only after a successful iterator ``next()`` so
    StopIteration leaves no dangling bracket, but the wait itself belongs
    to the step); ``step`` numbers a training step in the profiler's
    trace. A bracket left open by a step that raised is silently
    discarded — no trainer nests one step inside another, so an open
    bracket here can only be stale."""
    if not _enabled():
        return
    stale = _acct()
    if stale is not None and stale["ann"] is not None:
        stale["ann"].__exit__(None, None, None)
    now = time.perf_counter()
    t0 = now if t0 is None else t0
    names = _KIND_PHASES.get(kind, PHASES)
    if names is PHASES:
        # idle time since the previous step's end that no out-of-step
        # add() claimed: the training loop doing neither compute nor a
        # named stall
        last_end = getattr(_TLS, "last_end", None)
        if last_end is not None and t0 > last_end:
            claimed = getattr(_TLS, "gap_attr", 0.0)
            gap = max(0.0, (t0 - last_end) - claimed)
            if gap > 0.0:
                _metrics()[1]["between_steps"].inc(gap)
        _TLS.gap_attr = 0.0
    tm = _trace_me()
    ann, traced = None, False
    if tm is not None:
        traced = tm.is_enabled()
        if names is PHASES:
            if step is None:
                step = len(_RINGS.get(kind, ())) + 1
            # TraceMe's `_r=1` is what makes an annotation a step
            ann = tm("mxtpu.%s.step" % kind, _r=1, step_num=step)
        else:
            ann = tm("mxtpu.%s.lap" % kind)
        ann.__enter__()
    _TLS.acct = {"kind": kind, "t0": t0, "phases": {}, "launched": False,
                 "names": names, "tm": tm, "ann": ann, "traced": traced,
                 "step": step, "cpu0": time.thread_time()}
    global _ATEXIT_REGISTERED
    if not _ATEXIT_REGISTERED:
        _ATEXIT_REGISTERED = True  # mxlint: gil-atomic — one-time latch
        # Registered at first step (AFTER core registered its final flush),
        # so LIFO atexit publishes the abandoned bracket before the flush.
        atexit.register(finalize)


def add(phase, seconds):
    """Attribute ``seconds`` to ``phase``. Inside an open bracket whose
    kind has the phase the time joins the current step; otherwise a
    training phase (async checkpoint submit between steps, compile at
    trainer construction or inside a scheduler's lap) goes straight to the
    cumulative counter and reduces the next ``between_steps`` gap, and any
    other name is dropped."""
    if seconds <= 0.0:
        return
    a = _acct()
    if a is not None and phase in a["names"]:
        ph = a["phases"]
        ph[phase] = ph.get(phase, 0.0) + seconds
        return
    if phase not in PHASES or not _enabled():
        return
    _metrics()[1][phase].inc(seconds)
    _TLS.gap_attr = getattr(_TLS, "gap_attr", 0.0) + seconds


class phase:
    """``with goodput.phase("compute"):`` — attribute the block's elapsed
    time, MINUS whatever finer-grained attribution happened inside the
    block (an op resolving through the compile registry mid-step adds
    ``compile`` seconds; they must not also count as ``compute``; the
    scheduler's ``prefill_host`` holds the engine's ``prefill_wait``).
    Keeps phases non-overlapping by construction. Inside a bracket the
    block is also the annotation ``mxtpu.<kind>.<phase>`` of the
    profiler's trace. ``t0`` (perf_counter) and ``elapsed`` (the whole
    block, nothing subtracted) stay readable after the block, so its owner
    needs no stamps of its own. Cheap no-op when disabled."""

    __slots__ = ("_name", "t0", "elapsed", "_nested0", "_ann")

    def __init__(self, name):
        self._name = name

    def __enter__(self):
        a = _acct()
        self._ann = None
        if a is None:
            self._nested0 = None
        else:
            self._nested0 = sum(a["phases"].values())
            if a["tm"] is not None:
                self._ann = a["tm"]("mxtpu.%s.%s" % (a["kind"], self._name))
                self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        elapsed = self.elapsed = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        a = _acct()
        if a is not None and self._nested0 is not None:
            elapsed -= sum(a["phases"].values()) - self._nested0
        add(self._name, elapsed)
        return False


def mark_launch():
    """Stamp the executable-launch point: everything since step start that
    no finer phase claimed becomes ``host_dispatch`` (argument wrapping,
    cache lookups, Python glue before the device gets work)."""
    a = _acct()
    if a is None or a["launched"]:
        return
    a["launched"] = True
    elapsed = time.perf_counter() - a["t0"]
    ph = a["phases"]
    add("host_dispatch", elapsed - sum(ph.values()))


def step_end(step=None, **fields):
    """Close the bracket: fill ``other`` with the unattributed remainder,
    append the step's whole record (``step`` and ``fields`` with it) to
    its kind's ring and, for a training step, publish per-phase histograms
    (exemplar = the step's sampled trace id, if any) + cumulative
    counters and advance the ``mxtpu_goodput_fraction`` gauge; the
    scheduler publishes its lap's phases under its own model label.
    Returns the step's phase dict (plus ``wall``) — tests assert
    exhaustiveness on it."""
    a = _acct()
    if a is None:
        return None
    _TLS.acct = None
    now = time.perf_counter()
    cpu_s = time.thread_time() - a["cpu0"]
    traced = a["traced"]
    if a["ann"] is not None:
        a["ann"].__exit__(None, None, None)
        # recorded whole only if a session was on at both ends
        traced = traced and a["tm"].is_enabled()
    wall = max(0.0, now - a["t0"])
    ph = a["phases"]
    attributed = sum(ph.values())
    if attributed < wall:
        ph["other"] = ph.get("other", 0.0) + (wall - attributed)
    rec = {"t0": a["t0"], "t1": now, "phases": ph, "traced": traced,
           "cpu_s": cpu_s, "step": a["step"] if step is None else step}
    rec.update(fields)
    kind = a["kind"]
    _ring(kind).append(rec)  # mxlint: gil-atomic — signal-safe ring
    if a["names"] is PHASES:
        _publish_training(kind, ph, wall, now)
    out = dict(ph)
    out["wall"] = wall
    return out


def _ring(kind):
    ring = _RINGS.get(kind)
    if ring is None:
        ring = _RINGS.setdefault(kind, collections.deque(maxlen=_RING_LEN))
    return ring


def _publish_training(kind, ph, wall, now):
    global _LAST_TRAIN_KIND, _FIRST_STEP_STARTUP_S
    _TLS.last_end = now
    _LAST_TRAIN_KIND = kind  # mxlint: gil-atomic — plain store
    hists, ctrs, wall_ctr, frac = _metrics()
    tid = _tracing.current_trace_id()
    for p, v in ph.items():
        if v > 0.0:
            hists[p].observe(v, exemplar=tid)
            ctrs[p].inc(v)
    wall_ctr.inc(wall)
    w_wall = w_compute = 0.0
    for e in _win_steps(kind):
        w_wall += e["t1"] - e["t0"]
        w_compute += e["phases"].get("compute", 0.0)
    if w_wall > 0.0:
        frac.set(w_compute / w_wall)

    if _FIRST_STEP_STARTUP_S is None:
        # the launcher ledger joins this against generation start to price
        # restart cost (rendezvous + restore + first-step compile).
        # ``startup_s`` runs from the start-up account's first span (the
        # package import's first line) → first step START (the step
        # itself is already phase-attributed — no double counting);
        # ``step_wall_s`` lets tools/goodput_report.py anchor the
        # attributed window's wall-clock start at ``ts - step_wall_s``.
        # Lazy import: recorder imports goodput for dumps, not the reverse.
        from . import recorder as _recorder

        # mxlint: gil-atomic — one-time stamp
        _FIRST_STEP_STARTUP_S = 0.0 if _STARTUP_T0 is None \
            else max(0.0, now - wall - _STARTUP_T0)
        _recorder.record_event(
            "goodput_first_step", trainer=kind,
            generation=_core.restart_generation(),
            startup_s=round(_FIRST_STEP_STARTUP_S, 3),
            step_wall_s=round(wall, 4))


def finalize():
    """Salvage an abandoned step bracket at process exit: a SIGTERM mid-
    step unwinds through ``phase.__exit__`` (so e.g. the seconds blocked
    in a dead peer's allreduce DID land in the bracket's ``collective``
    slot) but never reaches :func:`step_end`. Publish those accumulated
    phases to the cumulative counters so the rank's final telemetry flush
    carries them — registered at the first :func:`step_start` so LIFO
    atexit runs it before core's final flush. Reads the CALLING thread's
    bracket (atexit → main thread, where training loops run); a bracket
    open on another thread at exit is lost, which only widens the
    report's honest ``shutdown`` remainder."""
    a = _acct()
    if a is None or a["names"] is not PHASES or not _enabled():
        return
    _TLS.acct = None
    ph = a["phases"]
    attributed = sum(ph.values())
    if attributed <= 0.0:
        return
    _, ctrs, wall_ctr, _ = _metrics()
    for p, v in ph.items():
        if v > 0.0:
            ctrs[p].inc(v)
    # wall advances only by what was attributed: the tail between the
    # last phase exit and interpreter death is exit handling, not step
    # time — the report prices it from launcher timestamps instead.
    wall_ctr.inc(attributed)


# -- the start-up account ------------------------------------------------------

def _open_spans():
    stack = getattr(_TLS, "spans", None)
    if stack is None:
        stack = _TLS.spans = []
    return stack


def _write_span(name, t0, t1, fields, span_id=None, back_dated=False):
    """Append one whole span record to the ``startup`` ring; its ``parent``
    is the span open on this thread. A back-dated span (one of jax's
    stages, written when it ends; the package import) adopts what this
    thread wrote inside its interval under the same parent, so that
    ``parent`` is the nesting whichever way a span came to be written."""
    global _STARTUP_T0
    stack = _open_spans()
    rec = {"name": name, "t0": t0, "t1": t1,
           "id": next(_SPAN_IDS) if span_id is None else span_id,
           "parent": stack[-1] if stack else None,
           "after_ready": _READY is not None}
    rec.update(fields)
    recent = getattr(_TLS, "written", None)
    if recent is None:
        recent = _TLS.written = collections.deque(maxlen=256)
    if back_dated:
        for inner in reversed(recent):
            if inner["t0"] < t0:
                break
            if inner["parent"] == rec["parent"]:
                inner["parent"] = rec["id"]
    recent.append(rec)
    if _STARTUP_T0 is None or t0 < _STARTUP_T0:
        _STARTUP_T0 = t0  # mxlint: gil-atomic — plain store
    _ring("startup").append(rec)  # mxlint: gil-atomic — signal-safe ring
    return rec


class span:
    """``with goodput.span("artifact_read", bytes=n) as sp:`` — one record
    of the start-up account (ring ``startup``): ``name``, ``t0``/``t1`` on
    ``time.perf_counter()``, ``id``, ``parent`` (the id of the span open on
    this thread when it began, else None), ``after_ready`` and the owner's
    ``fields`` (writable until the block ends). Usable outside any step
    bracket; spans nest, nothing is subtracted when one is written, and a
    reader takes self time as duration less children. Where a profiler
    session is on the block is also the annotation
    ``mxtpu.startup.<name>``. ``t0`` back-dates the start (the package
    import's first line). ``t0`` and ``elapsed`` stay readable after the
    block, with the accountant off too; nothing is written then."""

    __slots__ = ("name", "fields", "t0", "elapsed", "_id", "_ann",
                 "_back_dated")

    def __init__(self, name, t0=None, **fields):
        self.name, self.fields, self.t0 = name, fields, t0
        self._back_dated = t0 is not None

    def __enter__(self):
        self._id = self._ann = None
        if _enabled():
            _arm_jax()
            self._id = next(_SPAN_IDS)
            tm = _trace_me()
            if tm is not None and tm.is_enabled():
                self._ann = tm("mxtpu.startup." + self.name)
                self._ann.__enter__()
        if self.t0 is None:
            self.t0 = time.perf_counter()
        if self._id is not None:
            _open_spans().append(self._id)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.elapsed = t1 - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._id is not None:
            _open_spans().pop()
            _write_span(self.name, self.t0, t1, self.fields, self._id,
                        self._back_dated)
        return False


def ready(**fields):
    """Mark, with a span of zero length, that the process can do what it
    was started for: a model is published, the first training step has
    returned. The first mark ends the start-up a reader accounts for and
    publishes it (``mxtpu_startup_phase_seconds{phase}``, the ``/statusz``
    ``startup`` block); spans go on being written after it, marked
    ``after_ready``."""
    global _READY
    if not _enabled():
        return
    now = time.perf_counter()
    rec = _write_span("ready", now, now, fields)
    if _READY is None:
        _READY = rec  # mxlint: gil-atomic — one-time latch
        _publish_startup(rec)


def self_seconds(spans):
    """``{name: seconds}`` over ``spans``: each span's duration less that
    of the spans whose ``parent`` names it, summed by name."""
    held = {}
    for r in spans:
        if r["parent"] is not None:
            held[r["parent"]] = held.get(r["parent"], 0.0) \
                + (r["t1"] - r["t0"])
    out = {}
    for r in spans:
        own = (r["t1"] - r["t0"]) - held.get(r["id"], 0.0)
        out[r["name"]] = out.get(r["name"], 0.0) + max(0.0, own)
    return out


def _publish_startup(mark):
    global _STARTUP
    spans = [r for r in window("startup")
             if r["t1"] <= mark["t1"] and r is not mark]
    phases = self_seconds(spans)
    phases["total"] = mark["t1"] - _STARTUP_T0
    for name, seconds in phases.items():
        _core.gauge("mxtpu_startup_phase_seconds",
                    {"phase": name}).set(seconds)
    _STARTUP = {"ready": True, "spans": len(spans),
                "phases": {k: round(v, 3) for k, v in phases.items()}}


def startup_block():
    """The ``/statusz`` ``startup`` block: self seconds by span name from
    the first span's start to the first ``ready`` mark (``total``: all of
    it), as that mark published them; before it, how many spans there are.
    A stored dict — signal-safe."""
    if _STARTUP is None:
        return {"ready": False, "spans": len(_RINGS.get("startup", ()))}
    return _STARTUP


def _arm_jax():
    """Register the listeners that write jax's compile stages into the
    account, once, when the accountant first sees jax loaded. jax calls
    them on the compiling thread, on a compile and never on an
    execution."""
    global _JAX_ARMED, _JAX_NOT_TRACING
    if _JAX_ARMED:
        return
    mon = getattr(sys.modules.get("jax"), "monitoring", None)
    if mon is None:
        return
    _JAX_ARMED = True  # mxlint: gil-atomic — one-time latch
    _JAX_NOT_TRACING = getattr(sys.modules.get("jax._src.core"),
                               "trace_state_clean", None)
    mon.register_event_listener(_on_jax_event)
    mon.register_event_duration_secs_listener(_on_jax_duration)


def _on_jax_event(event, **_):
    verdict = _JAX_CACHE.get(event)
    if verdict is not None:
        _TLS.jax_cache = verdict    # of the backend compile now running


def _on_jax_duration(event, duration, fun_name=None, **_):
    name = _JAX_STAGES.get(event)
    if name is None:
        if event == _JAX_RETRIEVAL:
            _TLS.jax_retrieval_s = duration
        return
    if name == "trace" and _JAX_NOT_TRACING is not None \
            and not _JAX_NOT_TRACING():
        # a jitted function traced inside another's trace (every jnp
        # call): its seconds are the outer trace's, hundreds a program
        return
    fields = {"fun_name": fun_name}
    if name == "backend_compile":
        # neither event: jax's cache took no part (not armed, or the
        # program under its thresholds)
        fields["cache"] = getattr(_TLS, "jax_cache", None) or "off"
        retrieval = getattr(_TLS, "jax_retrieval_s", None)
        if retrieval is not None:
            fields["retrieval_s"] = retrieval
        _TLS.jax_cache = _TLS.jax_retrieval_s = None
    if not _enabled():
        return
    # jax stamps its stages on time.time(): the end is now, on the rings'
    # clock, and the start that long before
    t1 = time.perf_counter()
    _write_span(name, t1 - duration, t1, fields, back_dated=True)


def window(kind):
    """A copy of ``kind``'s ring: the whole records of its last (up to
    4096) closed brackets, oldest first. Each holds ``t0``/``t1`` on
    ``time.perf_counter()``, ``phases``, ``traced`` (a profiler session
    was recording at the bracket's start and at its end), ``cpu_s``
    (``time.thread_time()`` of the bracket's thread across it: wall minus
    device wait minus this is time spent waiting for the GIL or a lock),
    ``step`` and the owner's fields (a lap's ``n``, ``bucket``,
    ``prefills``, ``admitted``, ``queue_wait_s``). Kind ``startup`` holds
    the start-up account's :class:`span` records instead. Same retry discipline
    as core._win_entries — an append during a signal-context read raises
    RuntimeError."""
    ring = _RINGS.get(kind)
    if ring is None:
        return []
    for _ in range(4):
        try:
            return list(ring)
        except RuntimeError:
            continue
    return []


def _win_steps(kind):
    """The last ``MXTPU_GOODPUT_WINDOW_STEPS`` records of ``kind``, newest
    first: the rolling window behind the gauge and the ``/statusz`` block
    (read from the ring's end, so a step does not copy 4096 records; same
    retry discipline as :func:`window`)."""
    ring = _RINGS.get(kind)
    if ring is None:
        return []
    n = _GAUGE_STEPS or 128
    for _ in range(4):
        out = []
        try:
            for rec in reversed(ring):
                out.append(rec)
                if len(out) == n:
                    break
            return out
        except RuntimeError:
            continue
    return []


def totals():
    """Cumulative attributed seconds per phase (including
    ``between_steps``) + total step wall. Plain value reads —
    signal-safe."""
    m = _METRICS
    if m is None:
        return {"phases": {}, "wall": 0.0}
    return {"phases": {p: c._value for p, c in m[1].items() if c._value},
            "wall": m[2]._value}


def statusz_block():
    """The `/statusz` ``training`` block: windowed goodput fraction, top
    stall phase over the window, cumulative totals, startup cost."""
    entries = _win_steps(_LAST_TRAIN_KIND)
    w_wall = w_compute = 0.0
    stalls = {}
    for e in entries:
        w_wall += e["t1"] - e["t0"]
        w_compute += e["phases"].get("compute", 0.0)
        # the step's largest stall: its longest phase that is not compute
        stall_phase, stall_s = None, 0.0
        for p, v in e["phases"].items():
            if p != "compute" and v > stall_s:
                stall_phase, stall_s = p, v
        if stall_phase is not None:
            stalls[stall_phase] = stalls.get(stall_phase, 0.0) + stall_s
    top = max(stalls.items(), key=lambda kv: kv[1]) if stalls else None
    block = {
        "enabled": bool(_enabled()),
        "window_steps": len(entries),
        "goodput_fraction": round(w_compute / w_wall, 4) if w_wall else None,
        "top_stall_phase": top[0] if top else None,
        "top_stall_seconds": round(top[1], 4) if top else 0.0,
        "totals": totals(),
    }
    if _FIRST_STEP_STARTUP_S is not None:
        block["first_step_startup_s"] = round(_FIRST_STEP_STARTUP_S, 3)
    return block


def snapshot():
    """Flight-recorder dump payload: statusz block shape (signal-safe)."""
    return statusz_block()


def _reset_for_tests():
    global _METRICS, _FIRST_STEP_STARTUP_S, _LAST_TRAIN_KIND, _GAUGE_STEPS
    global _STARTUP_T0, _READY, _STARTUP
    _RINGS.clear()
    _LAST_TRAIN_KIND = None
    _GAUGE_STEPS = None
    _METRICS = None
    _FIRST_STEP_STARTUP_S = None
    _STARTUP_T0 = _READY = _STARTUP = None
    _TLS.spans, _TLS.written = [], None
    stale = _acct()
    if stale is not None and stale["ann"] is not None:
        stale["ann"].__exit__(None, None, None)
    _TLS.acct = None
    _TLS.last_end = None
    _TLS.gap_attr = 0.0
