"""The unified executable registry: one cache every jit factory resolves
through.

Two tiers:

  * **memory** — an LRU table of live executables, capacity
    ``MXTPU_COMPILE_CACHE_ENTRIES``. A hit is a dict lookup; eviction
    drops the oldest-touched entry (its per-shape XLA executables go with
    it).
  * **persistent** (opt-in via ``MXTPU_COMPILE_CACHE``, persist.py) —
    serialized compiled executables on disk. A memory miss checks the
    disk tier before compiling: a hit deserializes the executable and
    NEVER traces or compiles (no ``jit_compile`` event), which is what
    lets a restarted serving replica or elastic-restart generation reach
    ready with zero recompiles.

Fill telemetry (the single hook that replaced per-site wrappers):

  * ``mxtpu_jit_cache_lookup_total`` — one per registry lookup;
  * ``mxtpu_compile_cache_hit_total`` — memory hits;
  * ``mxtpu_jit_cache_miss_total`` + a ``jit_compile`` flight-recorder
    event + a ``compile.fill`` span — true fills (trace + compile);
  * ``mxtpu_compile_cache_persist_hit_total`` / ``_store_total`` /
    ``_bad_total`` — disk-tier traffic (bad = present but corrupt/stale);
  * ``mxtpu_compile_cache_evict_total`` + ``mxtpu_compile_cache_entries``
    — capacity behavior.

FLOP accounting also rides the fill hook: concrete fills capture
`Lowered.cost_analysis()` once at compile (or read it back from the
artifact header), lazy fills wrap the jitted callable in the per-shape
memo (`telemetry.flops.instrument`) exactly as the call sites used to.
"""
from __future__ import annotations

import collections
import itertools
import os
import threading

from .. import env as _env
from ..telemetry import core as _tm_core
from ..telemetry import flops as _tm_flops
from ..telemetry import memory as _tm_memory
from ..telemetry import recorder as _tm_rec
from ..telemetry import tracing as _tracing
from . import persist as _persist

__all__ = ["Registry", "registry", "get_or_build", "lookup", "invalidate_tag",
           "reset", "stats", "compiled", "mark", "keys_since",
           "prefetch_paths",
           "clear_staged", "instance_token", "begin_touch_log",
           "end_touch_log"]


# lazily-resolved counters: a process that starts MXTPU_TELEMETRY=0 and
# enables telemetry later must record real counts (never cache the null
# metric) — the ops-dispatch pattern, now in one place
_TM = {}


def _counter(name):
    c = _TM.get(name)
    if c is None:
        if not _tm_core._STATE.enabled:
            return _tm_core._NULL
        c = _tm_core.counter(name)
        _TM[name] = c
    return c


def _entries_gauge():
    return _counter_gauge("mxtpu_compile_cache_entries")


def _counter_gauge(name):
    g = _TM.get(name)
    if g is None:
        if not _tm_core._STATE.enabled:
            return _tm_core._NULL
        g = _tm_core.gauge(name)
        _TM[name] = g
    return g


class _FixedFlops:
    """AOT-compiled executable wrapper: every execution accumulates the
    compile-time cost-analysis FLOPs (no per-call lowering). Carries a
    one-shot ``rebuild`` escape hatch: if the compiled executable rejects
    a call (a deserialized artifact this process can't drive — device
    placement/layout skew the key can't see), the wrapper recompiles
    through the plain jit path, COUNTS the fill honestly (miss +
    ``jit_compile`` event), swaps itself over, and retries — a stale
    artifact costs one recompile, it never bricks the entry."""

    __slots__ = ("_fn", "_flops", "_rebuild")
    _mxtpu_aot = True

    def __init__(self, fn, flops, rebuild=None):
        self._fn = fn
        self._flops = flops
        self._rebuild = rebuild

    def __call__(self, *args):
        if self._rebuild is None:
            if self._flops:
                _tm_flops.accumulate(self._flops)
            return self._fn(*args)
        try:
            if self._flops:
                _tm_flops.accumulate(self._flops)
            return self._fn(*args)
        except Exception:
            # executables are pure: a retry through a fresh compile is
            # safe, and a real input error will re-raise from it
            self._fn = self._rebuild()
            self._flops = None  # the instrumented fallback prices itself
            self._rebuild = None
            return self._fn(*args)


class _LazyPerShape:
    """Per-shape wrapper stored under a LAZY key when the persistent tier
    is armed: each NEW shape signature resolves through the concrete-fill
    path (disk hit or AOT compile + store), so eager-op and autograd
    executables persist per shape. When an AOT-loaded executable rejects
    a call (device/weak-type skew the shape signature can't see), the
    signature falls back to the plain jitted callable permanently."""

    __slots__ = ("_registry", "_key", "_jitted", "_label", "_by_sig",
                 "_fallback")

    def __init__(self, registry, key, jitted, label):
        self._registry = registry
        self._key = key
        self._jitted = jitted
        self._label = label
        self._by_sig = {}
        self._fallback = None

    def _fallback_fn(self):
        """The plain jitted path for a signature the AOT route can't
        serve. Counted as a true fill — the jax.jit beneath really will
        trace+compile this signature, and the zero-compile acceptance
        signals must not be blind to the degraded path."""
        self._registry._count_fill(self._label, None, None)
        if self._fallback is None:
            self._fallback = _tm_flops.instrument(self._jitted)
        return self._fallback

    def __call__(self, *args):
        sig = _tm_flops._shape_sig(args)
        fn = self._by_sig.get(sig)
        if fn is None:
            try:
                fn = self._registry._fill_concrete(
                    self._key.with_shapes(sig), lambda: self._jitted, args,
                    self._label, None, None)
            except Exception:
                fn = self._fallback_fn()
            self._by_sig[sig] = fn
        try:
            return fn(*args)
        except Exception:
            if getattr(fn, "_mxtpu_aot", False):
                # a deserialized executable this process can't drive:
                # recompile through the normal jit path and remember that
                fn = self._fallback_fn()
                self._by_sig[sig] = fn
                return fn(*args)
            raise


class Registry:
    """LRU executable table + persistent-tier front end (one process-wide
    instance via `registry()`; tests build private ones)."""

    def __init__(self, capacity=None, persist_dir=None):
        self._lock = threading.Lock()   # guards insert/evict/invalidate;
        #                                 the HIT path is lock-free (below)
        self._table = {}     # ExecutableKey -> value (plain dict: GIL-
        #                      atomic get keeps per-op dispatch lock-free)
        self._stamps = {}    # ExecutableKey -> recency stamp (LRU order)
        self._clock = itertools.count(1)
        self._capacity = capacity
        self._persist_dir = persist_dir  # None = resolve from env per miss
        self._staged = {}    # digest -> (callable, flops, memory_figures)
        #                      manifest prefetch staging
        # per-THREAD fill log: loads/warms bracket their own thread's
        # fills with mark()/keys_since(), so concurrent model loads (and
        # live traffic on batcher threads) never pollute each other's
        # warmup manifests
        self._fill_local = threading.local()
        # per-THREAD touch log (armed only between begin_touch_log/
        # end_touch_log): which keys a warm LOOKED UP, hit or miss — the
        # memory-attribution bracket needs this because a reload of an
        # already-resident model fills nothing (telemetry.memory)
        self._touch_local = threading.local()

    # -- config ------------------------------------------------------------
    def capacity(self):
        if self._capacity is not None:
            return self._capacity
        return max(1, _env.get("MXTPU_COMPILE_CACHE_ENTRIES"))

    def _dir(self, key):
        """Persistent-tier directory for this key, or None (tier off, or
        the key cannot persist: process-local fingerprints/callbacks, and
        sharded executables that carry NO topology fingerprint — without
        one, a serialized sharded step could resurrect onto a different
        mesh geometry; keys that declare their topology (the
        ShardedTrainer promoted path) persist like any other)."""
        if key.no_persist or (key.sharded and key.topology is None):
            return None
        if self._persist_dir is not None:
            return self._persist_dir or None
        return _persist.cache_dir()

    # -- core --------------------------------------------------------------
    def _fill_log(self):
        log = getattr(self._fill_local, "entries", None)
        if log is None:
            log = self._fill_local.entries = []
        return log

    def _log_fill(self, key, digest):
        self._fill_log().append((key, digest))

    def lookup(self, key):
        """Memory-tier probe (counts a lookup; None on miss). LOCK-FREE:
        dict get + a recency-stamp store, both GIL-atomic — eager-op
        dispatch from N serving/predictor threads never contends on a
        mutex (the eviction path under the lock tolerates the benign
        stamp races this allows)."""
        _counter("mxtpu_jit_cache_lookup_total").inc()
        touches = getattr(self._touch_local, "log", None)
        if touches is not None:  # armed only inside a warm bracket
            touches.append(key)
        value = self._table.get(key)
        if value is not None:
            # lock-free hit path by design: a torn/raced stamp only skews
            # LRU recency by one touch, never correctness
            self._stamps[key] = next(self._clock)  # mxlint: gil-atomic — LRU stamp
            _counter("mxtpu_compile_cache_hit_total").inc()
        return value

    def get_or_build(self, key, build, label=None, example_args=None,
                     on_fill=None, event_fields=None):
        """THE factory entry point. ``build()`` returns a jax.jit callable
        (never called on a hit). With ``example_args`` the key is filled
        as ONE concrete executable (AOT + persistent tier when armed);
        without, the entry is a per-shape callable (plain jitted wrapper,
        or the per-shape persist wrapper when armed). ``example_args``
        may be a zero-arg THUNK returning the tuple — evaluated only on
        a true fill, so hot call sites (the trainers' per-step
        resolution) pay nothing on a hit. ``on_fill`` runs only on a
        true fill (site-specific build counters); ``event_fields`` joins
        the ``jit_compile`` event."""
        value = self.lookup(key)
        if value is not None:
            return value
        # the whole miss path — persistent-tier loads and true fills alike —
        # is compile time the training step did not spend on the device;
        # the goodput accountant attributes it whether or not a step
        # bracket is open (warmup compiles land on the cumulative counter)
        # — and one ``program`` span of the start-up account, under which
        # jax's own trace / lower / backend compile of this key land
        from ..telemetry import goodput as _goodput

        label = label or key.fingerprint
        sp = _goodput.span("program", label=label, kind=key.kind,
                           tier="memory_miss")
        try:
            with sp:
                if callable(example_args):
                    example_args = example_args()
                if key.concrete and example_args is not None:
                    value = self._fill_concrete(key, build, example_args,
                                                label, on_fill, event_fields,
                                                span=sp)
                else:
                    value = self._fill_lazy(key, build, label, on_fill,
                                            event_fields)
                return self._insert(key, value)
        finally:
            _goodput.add("compile", sp.elapsed)

    def _insert(self, key, value):
        with self._lock:
            existing = self._table.get(key)
            if existing is not None:   # racing fill: first one wins
                self._stamps[key] = next(self._clock)
                return existing
            self._table[key] = value
            self._stamps[key] = next(self._clock)
            cap = self.capacity()
            while len(self._table) > cap:
                old_key = min(self._table,
                              key=lambda k: self._stamps.get(k, 0))
                del self._table[old_key]
                self._stamps.pop(old_key, None)
                _counter("mxtpu_compile_cache_evict_total").inc()
                _tm_rec.record_event("compile_evict", key_kind=old_key.kind,
                                     fingerprint=old_key.fingerprint[:32])
            if len(self._stamps) > 2 * len(self._table):
                # prune stamps orphaned by lock-free hit races
                for k in list(self._stamps):
                    if k not in self._table:
                        del self._stamps[k]
            _entries_gauge().set(len(self._table))
        return value

    def _fill_lazy(self, key, build, label, on_fill, event_fields):
        """Fill a lazy (shapes-unknown) entry: the jitted callable keeps
        its internal per-shape cache; armed persistence upgrades it to the
        per-shape AOT wrapper. The jit_compile event fires here (one per
        signature family, matching the historical per-(op, attrs) event)
        unless the armed wrapper will emit per-shape events instead."""
        jitted = build()
        if self._dir(key) is not None:
            # per-shape wrapper: fills (and their events) happen per shape
            return _LazyPerShape(self, key, jitted, label)
        self._count_fill(label, on_fill, event_fields)
        return _tm_flops.instrument(jitted)

    def _fill_concrete(self, key, build, args, label, on_fill, event_fields,
                       span=None):
        """Fill ONE executable for pinned shapes: disk hit (no compile) or
        AOT trace+compile (+ store when armed). Sharded/donating keys the
        persistent tier refuses (topology-less sharded steps) still take
        the AOT path when memory accounting is on, so their memory figures
        — and the donation verifier — come from the compile the fill pays
        anyway. ``span`` is the miss path's ``program`` span, told here
        which tier served it."""
        directory = self._dir(key)
        if directory is not None:
            loaded = self._load_persisted(directory, key, label, build)
            if loaded is not None:
                if span is not None:
                    span.fields["tier"] = "persist_hit"
                return loaded
        with _tracing.span("compile.fill",
                           attrs={"kind": key.kind, "label": label}):
            jitted = build()
            value = None
            if directory is not None:
                value = self._aot_store(directory, key, jitted, args, label)
            elif (key.sharded or key.donation) and _tm_memory.enabled():
                value = self._aot_capture(key, jitted, args, label)
            if value is None:
                value = _tm_flops.instrument(jitted)
        self._count_fill(label, on_fill, event_fields)
        return value

    def _count_fill(self, label, on_fill, event_fields):
        _counter("mxtpu_jit_cache_miss_total").inc()
        _tm_rec.record_event("jit_compile", op=label, **(event_fields or {}))
        if on_fill is not None:
            on_fill()

    def _rebuilder(self, build, label):
        """The execution-failure escape hatch handed to `_FixedFlops`:
        rebuild through plain jit, counting the fill honestly."""
        def rebuild():
            self._count_fill(label, None, None)
            return _tm_flops.instrument(build())

        return rebuild

    def _compile_aot(self, key, jitted, args, label):
        """Shared AOT front half: lower + compile, price FLOPs from the
        lowering and memory figures from the compile (recorded into the
        attribution table; donating keys run the donation verifier).
        Returns (compiled, flops, mem) or None when this executable
        can't take the AOT path."""
        try:
            lowered = jitted.lower(*args)
            flops = None
            if _tm_flops.enabled():
                try:
                    flops = _tm_flops.cost_analysis_flops(
                        lowered.cost_analysis())
                except Exception:
                    flops = None
            compiled = lowered.compile()
        except Exception:
            return None
        mem = _tm_memory.from_compiled(compiled)
        if key.donation:
            _tm_memory.verify_donation(key, args, mem)
        return compiled, flops, mem

    def _aot_capture(self, key, jitted, args, label):
        """Memory-tier-only AOT fill (sharded/donating keys): same
        compile the jit would pay on first call, but through `lower()`+
        `compile()` so `memory_analysis()` is attributable. The compiled
        executable is used directly (no second compile), with the
        standard rebuild escape hatch."""
        res = self._compile_aot(key, jitted, args, label)
        if res is None:
            return None
        compiled, flops, mem = res
        _tm_memory.record_executable(key.kind, label, None, mem, key=key)
        return _FixedFlops(compiled, flops,
                           rebuild=self._rebuilder(lambda: jitted, label))

    def _aot_store(self, directory, key, jitted, args, label):
        """Lower+compile ahead of time, capture cost-analysis FLOPs +
        memory figures, and serialize into the persistent tier (figures
        ride the artifact header, so a zero-compile cold start still
        knows its footprint). None when this executable can't take the
        AOT path (caller falls back to plain jit)."""
        res = self._compile_aot(key, jitted, args, label)
        if res is None:
            return None
        compiled, flops, mem = res
        digest = _persist.store(directory, key, compiled, label=label,
                                flops=flops, memory=mem)
        if digest is not None:
            _counter("mxtpu_compile_cache_persist_store_total").inc()
            self._log_fill(key, digest)
        _tm_memory.record_executable(key.kind, label, digest, mem, key=key)
        return _FixedFlops(compiled, flops,
                           rebuild=self._rebuilder(lambda: jitted, label))

    def _load_persisted(self, directory, key, label, build):
        """Disk/staged probe for a concrete key. A hit deserializes the
        executable — no trace, no compile, no ``jit_compile`` event."""
        import jax

        digest = key.digest(jax.default_backend(), jax.__version__)
        with self._lock:
            staged = self._staged.pop(digest, None)
        if staged is not None:
            fn, flops, mem = staged
        else:
            path = _persist.artifact_path(directory, digest)
            if not os.path.exists(path):
                return None
            fn, flops, mem = _persist.load_path(path)
            if fn is None:
                _counter("mxtpu_compile_cache_persist_bad_total").inc()
                _tm_rec.record_event("compile_persist_bad", op=label)
                return None
        _counter("mxtpu_compile_cache_persist_hit_total").inc()
        _tm_rec.record_event("compile_persist_hit", op=label)
        self._log_fill(key, digest)
        # the header figures keep attribution alive across a zero-compile
        # cold start (the memory_analysis ran in the process that stored)
        _tm_memory.record_executable(key.kind, label, digest, mem, key=key)
        return _FixedFlops(fn, flops, rebuild=self._rebuilder(build, label))

    # -- invalidation ------------------------------------------------------
    def invalidate_tag(self, tag):
        """Drop every memory entry whose key carries ``tag`` (custom-op
        re-registration). Returns how many entries were dropped."""
        with self._lock:
            doomed = [k for k in self._table if tag in k.tags]
            for k in doomed:
                del self._table[k]
                self._stamps.pop(k, None)
            _entries_gauge().set(len(self._table))
        return len(doomed)

    def reset(self):
        """Clear the memory tier + staging (tests, fork children). The
        persistent tier is untouched. (Fill logs are per-thread; this
        clears the calling thread's.)"""
        with self._lock:
            self._table.clear()
            self._stamps.clear()
            self._staged.clear()
            self._fill_local.entries = []
            self._touch_local.log = None
            _entries_gauge().set(0)

    # -- touch bracketing (memory attribution) -----------------------------
    def begin_touch_log(self):
        """Arm this thread's touch log: every registry lookup (hit or
        miss) records its key until `end_touch_log`. The serving warm
        brackets each bucket with it so memory attribution survives the
        all-hits reload path (docs/observability.md §Memory)."""
        self._touch_local.log = []

    def end_touch_log(self):
        """Disarm and return this thread's touched keys (in order)."""
        log = getattr(self._touch_local, "log", None)
        self._touch_local.log = None
        return log or []

    # -- warmup manifests --------------------------------------------------
    def mark(self):
        """Cursor into THIS THREAD's persistable-fill log (bracket a
        load+warm with mark()/keys_since() to learn a model's executable
        key-set; fills on other threads — a concurrent load, live
        traffic — never leak into the bracket)."""
        return len(self._fill_log())

    def keys_since(self, cursor):
        """This thread's (key, digest) pairs persisted/loaded since
        ``cursor``."""
        return list(self._fill_log()[cursor:])

    def clear_staged(self):
        """Drop staged prefetch entries the warm never claimed (stale
        manifest rows — shrunk geometry, changed dtypes): a long-lived
        worker must not pin deserialized executables forever. Returns
        how many were dropped; call after warm completes."""
        with self._lock:
            n = len(self._staged)
            self._staged.clear()
        return n

    def prefetch_paths(self, paths):
        """Deserialize artifact files into the staging table BEFORE the
        executables are requested (replica warmup-manifest prefetch).
        Returns how many loaded; unreadable entries are skipped."""
        n = 0
        for path in paths:
            header = _persist.read_header(path)
            if header is None or not header.get("digest"):
                _counter("mxtpu_compile_cache_persist_bad_total").inc()
                continue
            fn, flops, mem = _persist.load_path(path)
            if fn is None:
                _counter("mxtpu_compile_cache_persist_bad_total").inc()
                continue
            with self._lock:
                self._staged[header["digest"]] = (fn, flops, mem)
            n += 1
        return n

    # -- introspection -----------------------------------------------------
    def compiled(self, key):
        """The `jax.stages.Compiled` behind an AOT-filled memory entry
        (sharded/donating fills, persistent-tier stores and loads), or
        None — for callers that read the program itself (`as_text()`:
        which kernels and collectives the compiler put in)."""
        value = self._table.get(key)
        if getattr(value, "_mxtpu_aot", False):
            return value._fn
        return None

    def stats(self):
        with self._lock:
            return {
                "entries": len(self._table),
                "capacity": self.capacity(),
                "staged": len(self._staged),
                "kinds": collections.Counter(k.kind for k in self._table),
            }


_REGISTRY = None
_REGISTRY_LOCK = threading.Lock()


def registry():
    """The process-wide registry singleton."""
    global _REGISTRY
    if _REGISTRY is None:
        with _REGISTRY_LOCK:
            if _REGISTRY is None:
                _REGISTRY = Registry()
    return _REGISTRY


def _reset_after_fork():
    # forked children must not call into jax executables compiled by the
    # parent (the jax runtime is not fork-safe); drop every live entry so
    # first use rebuilds in the child (DataLoader workers never get here —
    # HOST_ARRAY_MODE keeps them off the jit path entirely)
    if _REGISTRY is not None:
        _REGISTRY.reset()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reset_after_fork)


# module-level conveniences (the call-site surface)

def get_or_build(key, build, label=None, example_args=None, on_fill=None,
                 event_fields=None):
    return registry().get_or_build(key, build, label=label,
                                   example_args=example_args,
                                   on_fill=on_fill,
                                   event_fields=event_fields)


def lookup(key):
    return registry().lookup(key)


def invalidate_tag(tag):
    return registry().invalidate_tag(tag)


def reset():
    registry().reset()


def stats():
    return registry().stats()


def compiled(key):
    return registry().compiled(key)


def mark():
    return registry().mark()


def keys_since(cursor):
    return registry().keys_since(cursor)


def prefetch_paths(paths):
    return registry().prefetch_paths(paths)


def clear_staged():
    return registry().clear_staged()


def begin_touch_log():
    registry().begin_touch_log()


def end_touch_log():
    return registry().end_touch_log()


_TOKENS = itertools.count()


def instance_token(prefix):
    """A process-unique fingerprint for executables keyed to a LIVE
    python object (gluon CachedOp, the sharded trainers): stable for the
    object's lifetime, never reused (unlike ``id()``), and obviously
    process-local — such keys must also set ``no_persist``."""
    return "%s#%d" % (prefix, next(_TOKENS))
