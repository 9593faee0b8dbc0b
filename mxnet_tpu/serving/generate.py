"""Continuous-batching autoregressive decode with a paged KV cache.

The serving stack through PR 9 (replica pools, failover, compile cache,
memory-budget admission) serves batch-synchronous classification — the
wrong shape for sequence generation, where requests FINISH AT DIFFERENT
LENGTHS: a batch-synchronous batcher holds every finished sequence
hostage to the longest one, and a naive contiguous KV cache reserves
max-length memory per sequence. This module rebuilds the two techniques
that fixed decode serving at scale, TPU-natively on the machinery the
repo already has:

  * **token-level continuous batching** (Orca, OSDI'22):
    `GenerateScheduler` admits and retires requests at STEP granularity —
    each scheduler lap first prefills any waiting requests that fit
    (pages + batch slots), then runs ONE decode step for the whole active
    set, padded to a power-of-two batch bucket. Prefill and decode are
    separate executables, each resolved through the `mxnet_tpu.compile`
    registry — one cached decode executable per (batch bucket, KV page
    geometry), so steady-state decode is zero-compile and a late joiner
    never restarts the running batch.
  * **paged KV cache** (PagedAttention, SOSP'23): `KVPageAllocator` hands
    out fixed-size pages from a free list; each sequence owns a page
    table, pages return to the pool the step the sequence finishes. The
    whole pool is allocated at load and priced into the model footprint,
    so `MXTPU_SERVE_MEMORY_BUDGET` admission 507s a load whose KV pool
    cannot fit BEFORE it can OOM the device mid-decode
    (`mxtpu_serve_kv_pages_{total,used}` gauges track occupancy).
    Admission reserves a sequence's worst-case pages up front
    (prompt + max_new_tokens), so a running batch can never deadlock on
    the pool. A model with sliding-window layers has a SECOND group of
    page ids for them, in which a sequence holds a bounded ring of pages
    beside the growing pages of its full-attention layers.
  * **decode attention** runs the flash-decode Pallas kernel
    (`ops/pallas_kernels.paged_attention` — a work list of live blocks
    of pages, online softmax over the streamed blocks) on TPU, the dense-
    gather jnp fallback elsewhere (`MXTPU_PALLAS_DECODE`).
  * **sampling** (greedy / temperature / top-k / top-p) is folded into
    the decode executable with PER-ROW parameter arrays
    (`ops/random_ops.sample_token_logits`), so a mixed batch of greedy
    and stochastic requests stays one executable, which branches on the
    device on what its rows ask for (an all-greedy batch takes the argmax
    and sorts nothing); every step consumes one threefry subkey from the
    global chain.

`TransformerLMEngine` runs a `gluon.model_zoo.transformer.TransformerLM`
(decoder-only, tied embedding head) in incremental form: the pure-jax
prefill/decode functions here compute exactly the block's full-sequence
forward (tests/test_generate.py proves logits parity and greedy-sequence
equality), with parameters passed as executable arguments so two models
with one geometry share executables.

`ServedLM` is the repository-facing model: in-process it owns a
scheduler; with ``replicas=N`` it routes requests through a
`ReplicaPool` in generate mode (each replica worker runs its own
scheduler — continuous batching happens replica-side, request routing
router-side) over the existing supervisor wire protocol.
"""
from __future__ import annotations

import collections
import contextlib
import hashlib
import itertools
import json
import logging
import math
import os
import statistics
import threading
import time

import numpy as _np

from .. import compile as _compile
from .. import env as _env
from .. import random as _random
from .. import telemetry
from ..telemetry import goodput as _goodput
from ..telemetry import slo as _slo
from ..base import MXNetError
from ..telemetry import tracing as _tracing
from .batcher import (DeadlineExceededError, DrainingError, QueueFullError,
                      ServingError, bucket_for, drain_timeout_s,
                      power_of_two_buckets)

__all__ = ["KVPageAllocator", "StateSlotPool", "GenRequest", "GenerateScheduler",
           "TransformerLMEngine", "ServedLM", "save_lm", "load_lm"]

_LOG = logging.getLogger("mxnet_tpu.serving.generate")

_LM_FORMAT = "mxtpu-lm-v1"
# query rows of one block of a latent layer's prefill attention: 64 heads x
# 256 rows x 4096 keys of float32 scores are 268 MB, where the whole
# (H, L, L) would be 4.3 GB
_PREFILL_Q_BLOCK = 256
# rows an expert layer takes at a time: a longer prompt goes in equal chunks
# (`_lm_experts`); every bucket of the accepted cells is at most this
_MOE_ROWS = 4096


# ---------------------------------------------------------------------------
# KV page allocator
# ---------------------------------------------------------------------------

class KVPageAllocator:
    """Free-list allocator over a fixed pool of KV-cache pages.

    Pages are identity-only here (integers 0..num_pages-1); the device
    arrays they index live in the engine. Allocation is all-or-nothing
    (`alloc` returns None rather than a partial grant) and O(n) in the
    grant size; `free` returns pages for immediate reuse — a completed
    sequence's pages serve the next admission the same scheduler lap.
    Occupancy rides the `mxtpu_serve_kv_pages_{total,used}` gauges.

    One allocator is one GROUP of page ids. Every model has the group of
    its growing pages (a sequence holds ``pages_for(prompt + max_new)`` of
    them, one table for all its full-attention layers); a model with
    sliding-window layers has a second, ``group="window"``, whose pages a
    sequence holds as a ring (`TransformerLMEngine.ring_pages` at most) and
    whose gauges are `mxtpu_serve_kv_window_pages_{total,used}` and
    `mxtpu_serve_kv_window_occupancy`.
    """

    def __init__(self, num_pages, page_size, name="default", group=None):
        if num_pages < 1 or page_size < 1:
            raise MXNetError("KV pool needs >=1 pages of >=1 tokens, got "
                             "%d x %d" % (num_pages, page_size))
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        # LIFO free list: recently-freed pages are re-issued first (their
        # cache lines / artifact pages are warmest)
        self._free = list(range(self.num_pages - 1, -1, -1))
        labels = {"model": name}
        # used/total as one ratio gauge too: the SLO occupancy-ceiling
        # objective and /statusz read a single windowed series
        if group == "window":
            self._m_total = telemetry.gauge(
                "mxtpu_serve_kv_window_pages_total", labels)
            self._m_used = telemetry.gauge(
                "mxtpu_serve_kv_window_pages_used", labels)
            self._m_occ = telemetry.gauge(
                "mxtpu_serve_kv_window_occupancy", labels)
        elif group is None:
            self._m_total = telemetry.gauge("mxtpu_serve_kv_pages_total",
                                            labels)
            self._m_used = telemetry.gauge("mxtpu_serve_kv_pages_used",
                                           labels)
            self._m_occ = telemetry.gauge("mxtpu_serve_kv_occupancy", labels)
        else:
            raise MXNetError("a page group is None or 'window', not %r"
                             % (group,))
        self._m_total.set(self.num_pages)
        self._m_used.set(0)
        self._m_occ.set(0.0)

    def pages_for(self, tokens):
        """Pages needed to hold ``tokens`` tokens."""
        return -(-int(tokens) // self.page_size)

    @property
    def free_pages(self):
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self):
        return self.num_pages - self.free_pages

    def alloc(self, n):
        """Grant ``n`` pages, or None when the pool cannot serve them
        (callers keep the request queued — backpressure, not failure)."""
        n = int(n)
        with self._lock:
            if n < 0 or n > len(self._free):
                return None
            pages = self._free[-n:][::-1] if n else []
            del self._free[len(self._free) - n:]
            used = self.num_pages - len(self._free)
            self._m_used.set(used)
            self._m_occ.set(used / float(self.num_pages))
        return pages

    def free(self, pages):
        """Return a grant to the pool (double-free is a bug upstream and
        raises — a page owned by two sequences corrupts both)."""
        with self._lock:
            live = set(self._free)
            for p in pages:
                if p in live or not (0 <= p < self.num_pages):
                    raise MXNetError("double-free/corrupt KV page %r" % (p,))
            self._free.extend(pages)
            used = self.num_pages - len(self._free)
            self._m_used.set(used)
            self._m_occ.set(used / float(self.num_pages))


class StateSlotPool:
    """Free list of fixed per-sequence state slots: the second kind of
    state a sequence can own beside its KV pages. A model with recurrent
    layers (the engine's short convolutions) keeps one row a layer for every
    sequence that can be active; a slot is taken at admission, written by
    the prefill, read and rewritten by every decode step, and returned the
    step the sequence leaves (retire, abort, deadline, prefill failure
    alike). Slots do not grow, so unlike pages one slot is the whole grant.
    Occupancy rides `mxtpu_serve_state_slots_{total,used}`."""

    def __init__(self, num_slots, name="default"):
        self.num_slots = int(num_slots)
        self._lock = threading.Lock()
        self._free = list(range(self.num_slots - 1, -1, -1))
        labels = {"model": name}
        telemetry.gauge("mxtpu_serve_state_slots_total",
                        labels).set(self.num_slots)
        self._m_used = telemetry.gauge("mxtpu_serve_state_slots_used",
                                       labels)
        self._m_used.set(0)

    @property
    def used_slots(self):
        with self._lock:
            return self.num_slots - len(self._free)

    def alloc(self):
        """One slot, or None when every slot is taken."""
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self._m_used.set(self.num_slots - len(self._free))
        return slot

    def free(self, slot):
        with self._lock:
            if slot in self._free or not (0 <= slot < self.num_slots):
                raise MXNetError("double-free/corrupt state slot %r"
                                 % (slot,))
            self._free.append(slot)
            self._m_used.set(self.num_slots - len(self._free))


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

class GenRequest:
    """One admitted generation request. ``wait()`` yields the generated
    token list (prompt excluded); `finish_reason` is ``eos`` / ``length``
    after a normal completion."""

    __slots__ = ("tokens", "max_new_tokens", "temperature", "top_k",
                 "top_p", "deadline", "outputs", "finish_reason", "error",
                 "trace", "retried", "tag", "on_complete", "queue_seconds",
                 "_event", "_rlock", "_t_submit")

    def __init__(self, tokens, max_new_tokens, temperature=0.0, top_k=0,
                 top_p=1.0, deadline=None, trace=None):
        self.tokens = [int(t) for t in tokens]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.deadline = deadline
        self.outputs = None
        self.finish_reason = None
        self.error = None
        self.queue_seconds = None
        self.retried = False     # pooled failover: one retry per request
        self.tag = None          # wire id (pooled mode)
        self.on_complete = None  # worker-side completion hook
        self.trace = trace if trace is not None else _tracing.capture()
        self._event = threading.Event()
        self._rlock = threading.Lock()
        self._t_submit = time.perf_counter()

    def done(self):
        return self._event.is_set()

    def wait(self, timeout=None):
        self._event.wait(timeout)
        if not self._event.is_set():
            raise DeadlineExceededError(
                "generation expired after %.0f ms"
                % ((time.perf_counter() - self._t_submit) * 1e3))
        if self.error is not None:
            raise self.error
        return self.outputs

    def _resolve(self, outputs=None, finish_reason=None, error=None):
        # first resolution wins, atomically (scheduler thread, pooled
        # dispatch thread, abort paths and deadline expiry can race)
        with self._rlock:
            if self._event.is_set():
                return
            self.outputs = outputs
            self.finish_reason = finish_reason
            self.error = error
            self._event.set()
            cb = self.on_complete
        if cb is not None:
            try:
                cb(self)
            except Exception as e:  # a dead socket must not kill the
                _LOG.warning("generate completion hook failed: %r", e)


# ---------------------------------------------------------------------------
# the continuous-batching scheduler
# ---------------------------------------------------------------------------

class _Sequence:
    """Scheduler-internal state of one RUNNING sequence."""

    __slots__ = ("req", "pages", "page_row", "slot", "ring", "ring_row",
                 "pos", "generated", "t_last", "n_steps")

    def __init__(self, req, pages, page_row, pos, first_token, slot=None,
                 ring=None, ring_row=None):
        self.req = req
        self.pages = pages
        self.page_row = page_row
        self.slot = slot          # state slot (models with recurrent layers)
        # the window group's pages (models with sliding-window layers):
        # token t in ring_row[(t // page_size) % ring_pages]
        self.ring = ring
        self.ring_row = ring_row
        self.pos = pos            # position of the NEXT token to feed
        self.generated = [first_token]
        self.t_last = time.perf_counter()
        self.n_steps = 0


_SCHED_SEQ = itertools.count()


class GenerateScheduler:
    """Token-level continuous batching over one decode engine.

    One worker thread (``mxtpu-decode-<name>``) owns the engine, the
    active set and the page allocator's grants. Each lap:

      1. **admit**: pop waiting requests while batch slots AND worst-case
         pages (of both page groups, where the model has window layers)
         are available; run one PREFILL each (its own bucketed
         executable), which also samples the first token.
      2. **decode**: one step for the whole active set, padded to the
         smallest power-of-two batch bucket — one cached executable per
         bucket, zero-compile steady state.
      3. **retire**: sequences hitting EOS / ``max_new_tokens`` / their
         deadline resolve immediately and return their pages — the next
         lap's admissions reuse them. Requests join and leave at step
         granularity; nobody waits for the longest sequence in the batch.

    Every lap is one ``serve`` bracket of the phase accountant
    (`telemetry.goodput`, phases `goodput.SERVE_PHASES`): its phases are
    annotations in the profiler's trace and histograms here, its whole
    record joins ``goodput.window("serve")``, and a lap that stalls
    writes itself down (docs/observability.md §Lap phases). The ``cv.wait``
    with nothing queued and nothing active is outside every lap. Durations
    are stamped on ``time.perf_counter()``, the accountant's clock;
    deadlines are the callers', on ``time.monotonic()``.

    The engine must be single-threaded-driven; only the worker thread
    (plus `close` after joining it) touches it.
    """

    def __init__(self, engine, name="default", queue_depth=None, warm=True):
        self.engine = engine
        self.name = str(name)
        self.buckets = sorted(int(b) for b in engine.buckets)
        self.max_active = self.buckets[-1]
        if queue_depth is None:
            queue_depth = _env.get("MXTPU_SERVE_QUEUE_DEPTH")
        self.queue_depth = max(1, int(queue_depth))
        self.allocator = KVPageAllocator(engine.num_pages, engine.page_size,
                                         name=self.name)
        # the second page group: a ring a sequence, only for an engine
        # whose model has sliding-window layers
        n_window = getattr(engine, "window_pages", 0)
        self.window_allocator = KVPageAllocator(
            n_window, engine.page_size, name=self.name, group="window") \
            if n_window else None
        # the second kind of state: one fixed slot a sequence, only for an
        # engine whose model has recurrent layers
        n_slots = getattr(engine, "state_slots", 0)
        self.slots = StateSlotPool(n_slots, name=self.name) \
            if n_slots else None

        self._cv = threading.Condition()
        self._queue = collections.deque()
        self._active = []     # _Sequence list; mutated under _cv
        self._stop = False
        self._draining = False

        labels = {"model": self.name}
        self._m_queue = telemetry.gauge("mxtpu_serve_queue_depth", labels)
        self._m_reqs = telemetry.counter("mxtpu_serve_requests_total", labels)
        self._m_active = telemetry.gauge("mxtpu_serve_active_sequences",
                                         labels)
        self._m_steps = telemetry.counter("mxtpu_serve_decode_steps_total",
                                          labels)
        self._m_tokens = telemetry.counter(
            "mxtpu_serve_generated_tokens_total", labels)
        # decode steps by what the sampler had to do: `greedy` steps take
        # the argmax alone, `sampled` ones hold a row with a temperature
        self._m_sampler = {
            path: telemetry.counter("mxtpu_serve_sampler_steps_total",
                                    {"model": self.name, "path": path})
            for path in ("greedy", "sampled")}
        self._m_rej_full = telemetry.counter(
            "mxtpu_serve_rejected_total",
            {"model": self.name, "reason": "queue_full"})
        self._m_rej_dead = telemetry.counter(
            "mxtpu_serve_rejected_total",
            {"model": self.name, "reason": "deadline"})
        # in-flight expiry (admitted, partially decoded, then timed out)
        # is NOT an admission rejection: a dashboard alerting on
        # rejected_total must not fire during slow-decode incidents
        self._m_expired = telemetry.counter(
            "mxtpu_serve_rejected_total",
            {"model": self.name, "reason": "decode_expired"})
        # inter-token latency IS decode serving latency: its p99 is the
        # built-in generation objective (MXTPU_SLO_INTERTOKEN_P99_MS)
        self._m_intertoken = telemetry.histogram(
            "mxtpu_serve_intertoken_seconds", labels,
            bounds=(.001, .0025, .005, .01, .025, .05, .1, .25, .5, 1., 2.5))
        self._m_prefill = telemetry.histogram("mxtpu_serve_prefill_seconds",
                                              labels)
        self._m_decode = telemetry.histogram(
            "mxtpu_serve_decode_step_seconds", labels)
        self._m_queue_s = telemetry.histogram("mxtpu_serve_queue_seconds",
                                              labels)
        # where a lap goes; a histogram's sum is the phase's cumulative
        # seconds
        self._m_lap = {
            p: telemetry.histogram("mxtpu_serve_lap_phase_seconds",
                                   {"model": self.name, "phase": p})
            for p in _goodput.SERVE_PHASES}
        self._lap = None             # the open lap's counts
        self._slow_lap_logged = 0.0  # perf_counter of the last warning
        # built-in generation SLOs: inter-token p99 + KV-occupancy
        # ceiling + admission-queue ceiling (docs/observability.md §SLOs)
        _slo.wire_generate_objectives(self.name,
                                      queue_depth=self.queue_depth)

        # the RNG chain is thread-local (mxnet_tpu/random.py) and the
        # worker thread would otherwise lazily seed itself with the
        # DEFAULT seed — every replica/restart drawing one identical
        # "random" stream, deaf to mx.random.seed(). Derive the worker
        # chain from the CONSTRUCTING thread's seed (so an in-process
        # seed() before load stays reproducible) folded with the pid and
        # a per-process scheduler index (so co-located replicas and
        # restarted workers decorrelate).
        self._rng_seed = (_random.current_seed() * 1000003
                          + os.getpid() * 10007
                          + next(_SCHED_SEQ)) % (1 << 31)

        self.warm_seconds = None
        if warm:
            self.warm_seconds = engine.warm()
        self._worker = threading.Thread(
            target=self._loop, name="mxtpu-decode-%s" % self.name,
            daemon=True)
        self._worker.start()

    # -- admission ---------------------------------------------------------
    def submit(self, tokens, max_new_tokens=None, temperature=0.0, top_k=0,
               top_p=1.0, deadline=None, trace=None, on_complete=None):
        """Admit one generation request; returns a `GenRequest`.
        ``on_complete`` (optional) fires on EVERY resolution — success,
        expiry or abort (the replica worker's reply hook)."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise MXNetError("generation needs at least one prompt token")
        if len(tokens) > self.engine.max_prompt:
            raise MXNetError(
                "prompt has %d tokens; this model admits up to %d "
                "(MXTPU_SERVE_MAX_PROMPT)" % (len(tokens),
                                              self.engine.max_prompt))
        vocab = self.engine.vocab_size
        if any(t < 0 or t >= vocab for t in tokens):
            raise MXNetError("prompt token out of range [0, %d)" % vocab)
        cap = self.engine.max_new_tokens
        if max_new_tokens is None:
            max_new_tokens = cap
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1 or max_new_tokens > cap:
            raise MXNetError(
                "max_new_tokens must be in 1..%d (MXTPU_SERVE_MAX_NEW_"
                "TOKENS), got %d" % (cap, max_new_tokens))
        req = GenRequest(tokens, max_new_tokens, temperature=temperature,
                         top_k=top_k, top_p=top_p, deadline=deadline,
                         trace=trace)
        req.on_complete = on_complete
        with self._cv:
            if self._stop or self._draining:
                raise DrainingError("model %r is draining" % self.name)
            if len(self._queue) >= self.queue_depth:
                self._m_rej_full.inc()
                raise QueueFullError(
                    "generation queue for %r is full (%d requests; "
                    "MXTPU_SERVE_QUEUE_DEPTH)" % (self.name,
                                                  self.queue_depth))
            self._queue.append(req)
            self._m_queue.set(len(self._queue))
            self._m_reqs.inc()
            self._cv.notify()
        return req

    def pending(self):
        with self._cv:
            return len(self._queue) + len(self._active)

    # -- shutdown ----------------------------------------------------------
    def drain(self, timeout=None):
        """Stop admitting; let running sequences finish. Bounded."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()
        if timeout is None:
            timeout = drain_timeout_s()
        deadline = time.monotonic() + timeout
        while self.pending():
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.005)
        return True

    def abort_pending(self, error=None):
        """Force-resolve every queued and RUNNING request (bounded-drain
        escape hatch). Running sequences' pages are reclaimed by the
        worker on its next lap (or by `close` once the worker is joined);
        first-resolution-wins makes the race benign."""
        if error is None:
            error = DrainingError(
                "model %r drain timed out; generation force-completed"
                % self.name)
        with self._cv:
            victims = list(self._queue) + [s.req for s in self._active
                                           if not s.req.done()]
            self._queue.clear()
            self._m_queue.set(0)
        for req in victims:
            req._resolve(error=error)
        return len(victims)

    def close(self, drain=True, timeout=None):
        drained = self.drain(timeout) if drain else False
        with self._cv:
            self._stop = True
            self._draining = True
            self._cv.notify_all()
        self._worker.join(timeout=10.0)
        self.abort_pending(DrainingError(
            "model %r shut down before this generation ran" % self.name))
        if not self._worker.is_alive():
            # the worker is gone: reclaim whatever the aborted sequences
            # still held so the used gauge reads 0 after shutdown
            with self._cv:
                leftovers, self._active = self._active, []
            for seq in leftovers:
                self._release(seq)
            self._m_active.set(0)
        # verdicts for a gone model are noise on /statusz
        _slo.unregister_model(self.name)
        return drained

    # -- the worker --------------------------------------------------------
    def _loop(self):
        _random.seed(self._rng_seed)   # this thread's sampling chain
        while True:
            with self._cv:
                while not self._queue and not self._active:
                    if self._stop:
                        return
                    self._cv.wait(0.05)
                if self._stop:
                    return
            _goodput.step_start(kind="serve")
            self._lap = {"n": 0, "bucket": 0, "sampled": 0, "prefills": 0,
                         "admitted": 0, "queue_wait_s": 0.0,
                         "prefill_tokens": 0}
            try:
                with _goodput.phase("admit"):
                    self._admit()
                if self._active:
                    self._step()
            except Exception as e:  # the lone decode worker must not die
                telemetry.record_event("serve_decode_error",
                                       model=self.name, error=repr(e))
                _LOG.exception("decode loop error on %r", self.name)
                err = ServingError("decode loop for %r failed: %r"
                                   % (self.name, e))
                err.__cause__ = e
                with self._cv:
                    dead, self._active = self._active, []
                for seq in dead:
                    self._release(seq)
                    seq.req._resolve(error=err)
                self._m_active.set(0)
            lap = _goodput.step_end(model=self.name, **self._lap)
            if lap is not None:
                self._observe_lap(lap)

    def _observe_lap(self, lap):
        """Publish a closed lap's phases, and write a stalled one down: a
        lap longer than both 1 s and five times the ring's median lap is
        one event and one log line (at most a line a second), so a run that
        holds a stall says which phase of which lap it was."""
        wall = lap.pop("wall")
        for p, v in lap.items():
            self._m_lap[p].observe(v)
        if wall <= 1.0:
            return
        ring = _goodput.window("serve")
        median = statistics.median(r["t1"] - r["t0"] for r in ring)
        now = time.perf_counter()
        if wall <= 5.0 * median or now - self._slow_lap_logged < 1.0:
            return
        self._slow_lap_logged = now
        cpu_s = next(r["cpu_s"] for r in reversed(ring)
                     if r["model"] == self.name)
        fields = dict(self._lap, lap_s=round(wall, 4),
                      median_lap_s=round(median, 4), cpu_s=round(cpu_s, 4),
                      phases={p: round(v, 4) for p, v in lap.items()})
        telemetry.record_event("serve_slow_lap", model=self.name, **fields)
        _LOG.warning("slow decode lap on %r: %s", self.name, fields)

    def _admit(self):
        """Pop waiting requests while batch slots + worst-case pages are
        available and run their prefill — the join-mid-decode half of
        continuous batching."""
        lap = self._lap
        while len(self._active) < self.max_active:
            with self._cv:
                if not self._queue:
                    break
                req = self._queue[0]
                if req.deadline is not None \
                        and time.monotonic() >= req.deadline:
                    self._queue.popleft()
                    self._m_queue.set(len(self._queue))
                    self._m_rej_dead.inc()
                    req._resolve(error=DeadlineExceededError(
                        "deadline expired after %.0f ms in queue"
                        % ((time.perf_counter() - req._t_submit) * 1e3)))
                    continue
                if req.done():       # externally aborted while queued
                    self._queue.popleft()
                    self._m_queue.set(len(self._queue))
                    continue
                held = self._reserve(req)
                if held is None:
                    break            # pool pressure: stays queued
                pages, ring, slot = held
                self._queue.popleft()
                self._m_queue.set(len(self._queue))
            req.queue_seconds = time.perf_counter() - req._t_submit
            trace = req.trace
            exemplar = None if trace is None else trace.trace_id
            self._m_queue_s.observe(req.queue_seconds, exemplar=exemplar)
            lap["admitted"] += 1
            lap["queue_wait_s"] += req.queue_seconds
            page_row = _np.zeros(self.engine.max_pages_per_seq, _np.int32)
            page_row[:len(pages)] = pages
            state, ring_row = {} if slot is None else {"slot": slot}, None
            if ring is not None:
                ring_row = state["ring_row"] = _np.zeros(
                    self.engine.ring_pages, _np.int32)
                ring_row[:len(ring)] = ring
            try:
                # the engine claims `prefill_wait` inside this phase
                with _goodput.phase("prefill_host") as prefill:
                    first = self.engine.prefill(
                        req.tokens, page_row,
                        (req.temperature, req.top_k, req.top_p),
                        _random.next_key(), **state)
            except Exception as e:  # bad prompt/model: answer, free state
                self._unreserve(pages, ring, slot)
                err = ServingError("prefill on %r failed: %r"
                                   % (self.name, e))
                err.__cause__ = e
                telemetry.record_event("serve_decode_error",
                                       model=self.name, error=repr(e))
                req._resolve(error=err)
                continue
            lap["prefills"] += 1
            lap["prefill_tokens"] += len(req.tokens)
            # as `last_moe` below: a stub engine of the tests has neither
            pairs = getattr(self.engine, "last_prefill_moe_pairs", None)
            if pairs is not None:
                lap["prefill_moe_pairs"] = lap.get("prefill_moe_pairs", 0) \
                    + pairs
            self._m_prefill.observe(prefill.elapsed, exemplar=exemplar)
            if trace is not None and trace.recorded:
                t0_wall = time.time() - (time.perf_counter() - prefill.t0)
                _tracing.emit_span(
                    "serve.queue", t0_wall - req.queue_seconds,
                    req.queue_seconds, trace, component="decode")
                _tracing.emit_span(
                    "decode.prefill", t0_wall, prefill.elapsed, trace,
                    component="decode",
                    attrs={"prompt": len(req.tokens), "pages": len(pages)})
            self._m_tokens.inc()
            seq = _Sequence(req, pages, page_row, len(req.tokens), first,
                            slot, ring, ring_row)
            with _goodput.phase("retire"):
                done = self._finish_if_done(seq)
            if not done:
                with self._cv:
                    self._active.append(seq)
            self._m_active.set(len(self._active))

    def _reserve(self, req):
        """A request's worst-case state, all or nothing: (growing pages,
        window group's ring or None, state slot or None), or None while any
        of the three is short. Worst case is prompt + max_new tokens: pages
        are granted up front so a RUNNING sequence can never stall
        mid-decode waiting for a pool (no deadlock, no mid-flight
        eviction). A window layer keeps the last window of tokens alone, so
        the second group's grant is bounded by the ring."""
        need = self.allocator.pages_for(len(req.tokens) + req.max_new_tokens)
        pages = self.allocator.alloc(need)
        if pages is None:
            return None
        ring = slot = None
        if self.window_allocator is not None:
            ring = self.window_allocator.alloc(
                min(need, self.engine.ring_pages))
            if ring is None:
                self._unreserve(pages, None, None)
                return None
        if self.slots is not None:
            slot = self.slots.alloc()
            if slot is None:         # never while active < max_active
                self._unreserve(pages, ring, None)
                return None
        return pages, ring, slot

    def _unreserve(self, pages, ring, slot):
        self.allocator.free(pages)
        if ring is not None:
            self.window_allocator.free(ring)
        if slot is not None:
            self.slots.free(slot)

    def _step(self):
        """One decode step for the whole active set, padded to the
        smallest batch bucket; then retire finished sequences."""
        # sequences resolved externally (abort, expired deadline) retire
        # first — never spend a step on an answer nobody is waiting for
        with _goodput.phase("retire"):
            now = time.monotonic()
            live = []
            for seq in self._active:
                if seq.req.done():
                    self._release(seq)
                elif seq.req.deadline is not None \
                        and now >= seq.req.deadline:
                    self._retire(seq, None, error=DeadlineExceededError(
                        "deadline expired after %d generated token(s)"
                        % len(seq.generated)))
                else:
                    live.append(seq)
            if len(live) != len(self._active):
                with self._cv:
                    self._active = live
                self._m_active.set(len(live))
        if not live:
            return
        with _goodput.phase("build"):
            n = len(live)
            bucket = bucket_for(n, self.buckets)
            ps = self.engine.page_size
            nump = self.engine.num_pages
            tokens = _np.zeros(bucket, _np.int32)
            positions = _np.zeros(bucket, _np.int32)
            dest_pages = _np.full(bucket, nump, _np.int32)  # OOB = dropped
            dest_slots = _np.zeros(bucket, _np.int32)
            tables = _np.zeros((bucket, self.engine.max_pages_per_seq),
                               _np.int32)
            lengths = _np.zeros(bucket, _np.int32)
            temps = _np.zeros(bucket, _np.float32)
            top_ks = _np.zeros(bucket, _np.int32)
            top_ps = _np.ones(bucket, _np.float32)
            extra = {}
            if self.slots is not None:
                # a padding row names the row past the inert one: it reads
                # the inert row and its write drops
                seq_slots = extra["seq_slots"] = _np.full(
                    bucket, self.slots.num_slots + 1, _np.int32)
                seq_slots[:n] = [seq.slot for seq in live]
            if self.window_allocator is not None:
                ring = self.engine.ring_pages
                # a padding row's write drops past the window group's pages
                ring_dest = extra["ring_dest"] = _np.full(
                    bucket, self.engine.window_pages, _np.int32)
                ring_tables = extra["ring_tables"] = _np.zeros(
                    (bucket, ring), _np.int32)
                for i, seq in enumerate(live):
                    ring_dest[i] = seq.ring_row[(seq.pos // ps) % ring]
                    ring_tables[i] = seq.ring_row
            for i, seq in enumerate(live):
                tokens[i] = seq.generated[-1]
                positions[i] = seq.pos
                dest_pages[i] = seq.page_row[seq.pos // ps]
                dest_slots[i] = seq.pos % ps
                tables[i] = seq.page_row
                lengths[i] = seq.pos + 1
                temps[i] = seq.req.temperature
                top_ks[i] = seq.req.top_k
                top_ps[i] = seq.req.top_p
            sampled = int((temps > 0).sum())
        # the engine claims `decode_wait` inside this phase
        with _goodput.phase("decode_dispatch") as step:
            nxt = self.engine.decode_step(tokens, positions, dest_pages,
                                          dest_slots, tables, lengths, temps,
                                          top_ks, top_ps, _random.next_key(),
                                          **extra)
        self._lap.update(n=n, bucket=bucket, sampled=sampled,
                         context_tokens=int(lengths.sum()))
        if self.window_allocator is not None:
            # what the window layers' kernel streams, beside what the full
            # layers' does; and the second group's occupancy
            self._lap.update(
                window_tokens=int(_np.minimum(
                    lengths, self.engine.window).sum()),
                ring_pages=self.window_allocator.used_pages)
        if self.slots is not None:
            self._lap["state_slots"] = self.slots.used_slots
        moe = getattr(self.engine, "last_moe", None)
        if moe is not None:
            self._lap.update(moe_pairs=moe[0], moe_experts_hit=moe[1],
                             moe_load_max=moe[2])
        self._m_steps.inc()
        self._m_sampler["sampled" if sampled else "greedy"].inc()
        self._m_decode.observe(step.elapsed)
        with _goodput.phase("retire"):
            now = time.perf_counter()
            t0_wall = time.time() - (now - step.t0)
            still = []
            for i, seq in enumerate(live):
                seq.pos += 1
                seq.n_steps += 1
                seq.generated.append(int(nxt[i]))
                self._m_tokens.inc()
                trace = seq.req.trace
                self._m_intertoken.observe(
                    now - seq.t_last,
                    exemplar=None if trace is None else trace.trace_id)
                seq.t_last = now
                # request tracing: only a sequence whose request carries a
                # recorded trace costs a call
                if trace is not None and trace.recorded:
                    _tracing.emit_span(
                        "decode.step", t0_wall, step.elapsed, trace,
                        component="decode",
                        attrs={"bucket": bucket, "n": n,
                               "step": seq.n_steps})
                if not self._finish_if_done(seq):
                    still.append(seq)
            with self._cv:
                self._active = still
            self._m_active.set(len(still))

    def _finish_if_done(self, seq):
        """Retire a sequence that hit EOS or its token budget."""
        eos = self.engine.eos_id
        if eos is not None and seq.generated[-1] == eos:
            self._retire(seq, "eos")
            return True
        if len(seq.generated) >= seq.req.max_new_tokens:
            self._retire(seq, "length")
            return True
        return False

    def _release(self, seq):
        """Return everything a sequence holds: its pages of both groups and
        its slot."""
        self._unreserve(seq.pages, seq.ring, seq.slot)

    def _retire(self, seq, finish_reason, error=None):
        self._release(seq)
        if error is not None:
            self._m_expired.inc()
            seq.req._resolve(error=error)
        else:
            seq.req._resolve(outputs=list(seq.generated),
                             finish_reason=finish_reason)


# ---------------------------------------------------------------------------
# the decoder-LM engine: one per-layer description, two programs
# ---------------------------------------------------------------------------

def transformer_lm_description(config):
    """The per-layer description of `model_zoo.transformer.TransformerLM`:
    learned positions, a LayerNorm after the embeddings and after each
    residual add (post-LN), biased projections, one K and V a query head, an
    erf-GELU feed-forward, the head tied to the embedding, float32."""
    heads = int(config["num_heads"])
    return {"arch": "transformer_lm", "dtype": "float32",
            "units": int(config["units"]),
            "vocab_size": int(config["vocab_size"]),
            "max_length": int(config["max_length"]),
            "norm": "layer", "norm_at": "post", "norm_eps": 1e-5,
            "positions": "learned", "embed_norm": True, "final_norm": False,
            "head": "tied", "heads": heads, "kv_heads": heads,
            "head_dim": int(config["units"]) // heads, "qk_norm": False,
            "ffn": "gelu",
            "layers": [{"operator": "attention", "ffn": "dense"}
                       for _ in range(int(config["num_layers"]))]}


def _lm_norm(desc, x, p):
    from ..ops import nn as _opsnn

    if desc["norm"] == "rms":
        return _opsnn.rms_norm(x, p["g"], eps=desc["norm_eps"])
    return _opsnn.layer_norm(x, p["g"], p["b"])


def _lm_dense(x, p):
    from ..ops import nn as _opsnn

    y = _opsnn.matmul_nt(x, p["w"])
    return y + p["b"] if "b" in p else y


def _lm_attn_scope(spec):
    """A model that says its layers' windows names the two kinds of
    attention in the device trace; any other model's trace is as it was."""
    import jax

    if "window" not in spec:
        return contextlib.nullcontext()
    return jax.named_scope("mxtpu.lm.attn.window" if spec["window"]
                           else "mxtpu.lm.attn.full")


def _write_pages(pool, rows, table, length, ring=0):
    """A prompt's rows (lp, lanes) into ``pool`` (pages, page_size, Cp) at
    the pool's full width and whole pages at a time (a write of a part of a
    row's lane tiles, or a scatter of rows, is a serial loop on the chip:
    3 us a row, 12 ms a layer at 4096); the lanes and rows of the padding are
    zeros, and no step reads a page's row past the prompt's length before
    writing it. Page p of the prompt goes to ``table[p]``, or with ``ring``
    > 0 to ``table[p % ring]`` if it is one of the last ``ring`` pages the
    prompt's ``length`` reaches: the rows a ring still holds."""
    import jax.numpy as jnp

    lp, lanes = rows.shape
    ps = pool.shape[1]
    n_pg = -(-lp // ps)
    wide = jnp.pad(rows, ((0, n_pg * ps - lp), (0, pool.shape[-1] - lanes))) \
        .astype(pool.dtype)
    page = jnp.arange(n_pg)
    live = page * ps < length
    if ring:
        live &= page > (length - 1) // ps - ring
        dest = table[page % ring]
    else:
        dest = table[:n_pg]
    return pool.at[jnp.where(live, dest, pool.shape[0])].set(
        wide.reshape(n_pg, ps, -1), mode="drop")


def _lm_experts(ex, layer, r, valid, router_rows):
    """The expert feed-forward of rows ``r`` (n, C) -> (rows, stats). More
    than `_MOE_ROWS` rows (a long prompt) go through the layer in equal
    chunks of at most that many, one after the other: routing is a row's
    own, so the rows are the same, and the routed copies of the rows, which
    are ``per_token`` times the rows in float32, never exist for more than a
    chunk (2 GB at 12288 rows of 2560 for 6 experts a token). The chunks'
    stats are summed (pairs) and their largest taken (experts hit, busiest
    expert): a prefill reads the pairs alone."""
    import jax
    import jax.numpy as jnp

    from ..ops.contrib import sigmoid_topk_moe

    def experts(rows, live, router=None):
        return sigmoid_topk_moe(
            rows, layer["gate"], layer.get("expert_bias"), layer["ew1"],
            layer["ew3"], layer["ew2"], router, k=ex["per_token"],
            expert_offset=ex["offset"], valid=live,
            routed_scaling_factor=ex["scaling"],
            norm_topk_prob=ex["norm_topk"], n_group=ex.get("groups", 1),
            topk_group=ex.get("topk_groups", 1),
            gate_eps=ex.get("gate_eps", 1e-6),
            scores=ex.get("scores", "sigmoid"),
            activation=ex.get("activation", "silu"))

    n = r.shape[0]
    if n <= _MOE_ROWS:
        return experts(r, valid, router_rows)
    chunks = next(c for c in range(-(-n // _MOE_ROWS), n + 1) if n % c == 0)
    parts = tuple(a.reshape((chunks, n // chunks) + a.shape[1:])
                  for a in (r, valid, router_rows) if a is not None)
    f, st = jax.lax.map(lambda a: experts(*a), parts)
    return f.reshape(r.shape), jnp.concatenate(
        [jnp.sum(st[:, :1], axis=0), jnp.max(st[:, 1:], axis=0)])


def _lm_layers(desc, params, x, positions, valid, kv, slots, attention, conv):
    """The layers of a decoder LM over rows ``x`` (n, C), one row a token at
    ``positions`` (n,): the block both of the engine's programs run. What
    differs between them is where an operator keeps its state, so the two
    stateful steps are handed in: ``attention((K, V) pages of the layer, q,
    k, v, window) -> (attended, new pages)`` (store k and v, attend;
    ``window`` the layer's sliding window or None, which says which of the
    two page groups the layer's pages belong to), or for a
    latent model ``attention(pages of the layer, q, row, layer)`` (store the
    compressed row, attend through ``kv_b``: `_lm_latent`), and
    ``conv(slots of the layer, r, layer) -> (o, new slots)`` (the gated
    short convolution over its slot); ``kv`` and ``slots`` hold one entry an
    attention / convolution layer. Rows with ``valid`` false are a bucket's
    padding: they are computed like any row and route to no expert.

    What is per layer in a description's ``layers`` entry beside the
    operator and the feed-forward: ``window`` (keys (i - window, i] are
    live; absent or None: the whole context) and ``rotary`` (whether q and k
    are rotated; absent: as the model's ``positions`` says, so a layer with
    ``rotary`` false in a rotary model has no positional encoding at all).
    ``experts`` may say ``router_rows: "operator"`` (the router reads the
    operator's normed input rows, the experts the feed-forward's),
    ``scores`` and ``activation`` (`ops.contrib.sigmoid_topk_moe`). Returns
    (rows, per-expert-layer stats, new kv, new slots)."""
    import jax

    from ..ops import nn as _opsnn

    n = x.shape[0]
    pre = desc["norm_at"] == "pre"
    latent = desc.get("attention") == "latent"
    h, kvh, dh = desc["heads"], desc.get("kv_heads"), desc.get("head_dim")
    stats, new_kv, new_slots = [], [], []
    for layer, spec in zip(params["layers"], desc["layers"]):
        r = r_operator = _lm_norm(desc, x, layer["attn_norm"]) if pre else x
        if spec["operator"] == "attention" and latent:
            with jax.named_scope("mxtpu.lm.attn"):
                o, pages = _lm_latent(desc, layer, r, positions,
                                      kv[len(new_kv)], attention)
                new_kv.append(pages)
        elif spec["operator"] == "attention":
            with jax.named_scope("mxtpu.lm.attn"):
                q = _lm_dense(r, layer["q"]).reshape(n, h, dh)
                k = _lm_dense(r, layer["k"]).reshape(n, kvh, dh)
                v = _lm_dense(r, layer["v"]).reshape(n, kvh, dh)
                if desc["qk_norm"]:
                    q = _opsnn.rms_norm(q, layer["q_norm"]["g"],
                                        eps=desc["norm_eps"])
                    k = _opsnn.rms_norm(k, layer["k_norm"]["g"],
                                        eps=desc["norm_eps"])
                if spec.get("rotary", desc["positions"] == "rotary"):
                    q = _opsnn.rope(q, positions, desc["rope_theta"])
                    k = _opsnn.rope(k, positions, desc["rope_theta"])
                with _lm_attn_scope(spec):
                    att, pages = attention(kv[len(new_kv)], q, k, v,
                                           spec.get("window"))
                new_kv.append(pages)
                o = _lm_dense(att.astype(x.dtype).reshape(n, h * dh),
                              layer["o"])
        else:
            with jax.named_scope("mxtpu.lm.conv"):
                o, slot = conv(slots[len(new_slots)], r, layer)
                new_slots.append(slot)
        x = x + o
        if not pre:
            x = _lm_norm(desc, x, layer["attn_norm"])
        r = _lm_norm(desc, x, layer["ffn_norm"]) if pre else x
        if spec["ffn"] == "experts":
            ex = desc["experts"]
            f, st = _lm_experts(
                ex, layer, r, valid,
                r_operator if ex.get("router_rows") == "operator" else None)
            stats.append(st)
            if ex.get("shared"):
                # the shared expert: whole on every holder, every token
                with jax.named_scope("mxtpu.lm.moe.shared"):
                    f = f + _opsnn.swiglu_ffn(r, layer["sw1"], layer["sw3"],
                                              layer["sw2"])
        elif desc["ffn"] == "swiglu":
            f = _opsnn.swiglu_ffn(r, layer["w1"], layer["w3"], layer["w2"])
        else:
            f = _lm_dense(jax.nn.gelu(_lm_dense(r, layer["ffn1"]),
                                      approximate=False), layer["ffn2"])
        x = x + f
        if not pre:
            x = _lm_norm(desc, x, layer["ffn_norm"])
    return x, stats, tuple(new_kv), tuple(new_slots)


def _lm_latent(desc, layer, r, positions, pages, attention):
    """A latent (MLA) attention operator over rows ``r`` (n, C): the query
    through its low-rank pair, the token's ONE cached row (the RMS-normed
    compressed KV, then the rotary key all heads share), and ``attention(
    pages, q, row, layer) -> ((n, H, v) attended, new pages)``, which stores
    the row and attends in the program's own way: prefill expands K and V of
    every head from the rows, decode absorbs ``kv_b`` into the query and the
    output and reads the pages as they lie."""
    import jax
    import jax.numpy as jnp

    from ..ops import nn as _opsnn

    lat, h, n = desc["latent"], desc["heads"], r.shape[0]
    yarn = _opsnn.yarn_args(desc["rope_scaling"])
    with jax.named_scope("mxtpu.lm.mla.q"):
        c_q = _opsnn.rms_norm(_lm_dense(r, layer["q_a"]),
                              layer["q_a_norm"]["g"], eps=desc["norm_eps"])
        q = _opsnn.rope(
            _lm_dense(c_q, layer["q_b"]).reshape(n, h, lat["nope"]
                                                 + lat["rope"]),
            positions, desc["rope_theta"], start=lat["nope"], yarn=yarn)
    with jax.named_scope("mxtpu.lm.mla.kv"):
        ckv = _lm_dense(r, layer["kv_a"])
        c_kv = _opsnn.rms_norm(ckv[:, :lat["kv_rank"]],
                               layer["kv_a_norm"]["g"], eps=desc["norm_eps"])
        k_rot = _opsnn.rope(ckv[:, None, lat["kv_rank"]:], positions,
                            desc["rope_theta"], yarn=yarn)[:, 0]
        row = jnp.concatenate([c_kv, k_rot], axis=-1)
    with jax.named_scope("mxtpu.lm.mla.attend"):
        att, pages = attention(pages, q, row, layer)
    return _lm_dense(att.astype(r.dtype).reshape(n, h * lat["v"]),
                     layer["o"]), pages


def _latent_scale(desc):
    from ..ops.nn import latent_softmax_scale

    return latent_softmax_scale(desc["latent"]["nope"],
                                desc["latent"]["rope"], desc["rope_scaling"])


def _lm_embed(desc, params, tokens, positions):
    x = params["word"][tokens]
    if desc["positions"] == "learned":
        x = x + params["pos"][positions]
    if desc["embed_norm"]:
        x = _lm_norm(desc, x, params["embed_norm"])
    return x


def _lm_logits(desc, params, x):
    """Rows -> float32 logits over the vocabulary."""
    from ..ops import nn as _opsnn

    if desc["final_norm"]:
        x = _lm_norm(desc, x, params["final_norm"])
    head = params["word"] if desc["head"] == "tied" else params["head"]
    return _opsnn.matmul_nt(x, head, "float32")


class TransformerLMEngine:
    """Incremental execution of a decoder LM through paged KV and state
    slots.

    The model is a per-layer description (`transformer_lm_description`, or a
    zoo block's ``description()``, carried by a `save_lm` artifact's header):
    each layer an operator (attention | short convolution) and a
    feed-forward (dense | experts), with the norm (layer | RMS, post | pre),
    the positions (learned | rotary) and the head (tied | own) of the whole
    model. Prefill and decode are both built from it over the same layer
    function (`_lm_layers`); they differ only in where an operator keeps
    its state:

    * an **attention** layer owns one (K, V) pair of arrays (`_kv`), each
      ``(pages of its group, page_size, Cp)``; a page id names the same page
      in every layer of a group, so a sequence has one page table a GROUP,
      not a layer. A full-attention layer is of the growing group
      (``num_pages``; a sequence holds pages for prompt + max_new tokens); a
      **sliding-window** layer (its entry of the description's ``layers``
      says ``window``) is of the window group (``window_pages``), in which
      a sequence holds a ring of ``ring_pages`` = ceil((window - 1) /
      page_size) + 1 pages at most and token t lies in ring entry
      ``(t // page_size) % ring_pages``: the window layers' bytes a
      sequence stop growing at the window. A page is page_size rows, a
      row one token's values of all KV heads side by side (KV head h in
      columns [h*Dh, (h+1)*Dh)), Cp = kv_heads*head_dim rounded up to a
      multiple of 128 at allocation. Grouped-query models keep kv_heads <
      heads rows wide; nothing is repeated per query head. Prefill computes
      the causal forward of a padded prompt bucket and scatters every live
      token's K/V to ``[page, slot]`` (a model with window layers attends in
      query blocks, window layers in a band, and writes whole pages: to a
      ring only the rows it still holds); a decode step appends one row a
      sequence and attends over the page table of the layer's group
      (`ops/pallas_kernels.paged_attention`; a window layer's call names
      each sequence's first live key).
    * a **latent attention** layer (description ``"attention": "latent"``)
      owns ONE array of the pool, ``(num_pages, page_size, Cp)``: a row is a
      token's compressed KV (``kv_rank`` lanes, RMS-normed) and the rotary
      key all heads share, Cp their sum rounded up to a multiple of 128.
      Prefill expands every head's K and V from the rows through ``kv_b`` and
      attends causally in query blocks (no (H, L, L) array); a decode step
      absorbs ``kv_b`` into the query and the output and attends over the
      pages as they lie (`ops/pallas_kernels.paged_latent_attention`).
    * a **short-convolution** layer owns one array of state slots
      (`_slots`), ``(state_slots + 1, taps - 1, C)``: the gated input of a
      sequence's last taps-1 positions. Prefill writes the slot at the
      prompt's own length (not the padded bucket's), every decode step reads
      and rewrites it; rows of a bucket's padding read the last, inert row
      and their writes drop.

    Parameters, pages and slots are kept in the description's dtype; matrix
    products accumulate in float32; norm statistics, router scores, softmax
    and logits are float32. Both programs are pure functions of (params,
    state, inputs) resolved through the `mxnet_tpu.compile` registry —
    parameters ride as arguments, so the executables are keyed purely by
    description and geometry — and the state (pool and slots) is donated
    whole to every call. Single-threaded: only the scheduler worker may
    drive an engine (docs/serving.md §Generation).
    """

    def __init__(self, lm=None, params=None, config=None, num_pages=None,
                 page_size=None, max_prompt=None, max_new_tokens=None,
                 max_batch=None, decode_buckets=None, prefill_buckets=None,
                 eos_id=None, kv_dtype=None, description=None,
                 window_pages=None):
        import jax

        # the parameters into the engine's layout, the page pool and the
        # slots: one span of the start-up account
        with _goodput.span("engine_build") as build:
            if lm is not None:
                config = lm.config
                params = lm.decode_params()
                if description is None and hasattr(lm, "description"):
                    description = lm.description()
            if config is None or params is None:
                raise MXNetError("TransformerLMEngine needs an lm= block or "
                                 "params= + config=")
            if description is None:
                description = transformer_lm_description(config)
            self.config = dict(config)
            self.description = desc = dict(description)
            self.vocab_size = int(desc["vocab_size"])
            self.units = int(desc["units"])
            self.num_heads = int(desc["heads"])
            # a latent model caches one row a token for all heads: the
            # compressed KV and the rotary key beside it
            self.latent = desc.get("attention") == "latent"
            if self.latent:
                self.kv_heads, self.head_dim = 1, int(
                    desc["latent"]["kv_rank"] + desc["latent"]["rope"])
            else:
                self.kv_heads = int(desc["kv_heads"])
                self.head_dim = int(desc["head_dim"])
            self.num_layers = len(desc["layers"])
            self.attn_layers = sum(1 for l in desc["layers"]
                                   if l["operator"] == "attention")
            self.conv_layers = self.num_layers - self.attn_layers
            self.has_experts = any(l["ffn"] == "experts"
                                   for l in desc["layers"])
            self.eos_id = None if eos_id is None else int(eos_id)
            self.page_size = int(page_size if page_size is not None
                                 else _env.get("MXTPU_SERVE_KV_PAGE_SIZE"))
            self.num_pages = int(num_pages if num_pages is not None
                                 else _env.get("MXTPU_SERVE_KV_PAGES"))
            self.max_prompt = int(max_prompt if max_prompt is not None
                                  else _env.get("MXTPU_SERVE_MAX_PROMPT"))
            self.max_new_tokens = int(
                max_new_tokens if max_new_tokens is not None
                else _env.get("MXTPU_SERVE_MAX_NEW_TOKENS"))
            max_total = self.max_prompt + self.max_new_tokens
            if max_total > int(desc["max_length"]):
                raise MXNetError(
                    "max_prompt + max_new_tokens = %d exceeds the model's "
                    "position table (max_length=%d)"
                    % (max_total, desc["max_length"]))
            self.max_pages_per_seq = -(-max_total // self.page_size)
            if self.max_pages_per_seq > self.num_pages:
                raise MXNetError(
                    "one sequence can need %d pages but the pool has only %d "
                    "(MXTPU_SERVE_KV_PAGES)" % (self.max_pages_per_seq,
                                                self.num_pages))
            if decode_buckets is None:
                if max_batch is None:
                    max_batch = _env.get("MXTPU_SERVE_MAX_BATCH")
                decode_buckets = power_of_two_buckets(max_batch)
            self.buckets = sorted(int(b) for b in decode_buckets)
            if prefill_buckets is None:
                lo = min(8, self.max_prompt)
                prefill_buckets = [b for b in
                                   power_of_two_buckets(self.max_prompt)
                                   if b >= lo]
            self.prefill_buckets = sorted(int(b) for b in prefill_buckets)
            self.dtype = str(desc["dtype"])
            self.kv_dtype = str(kv_dtype if kv_dtype is not None
                                else self.dtype)
            # one state slot a sequence that can be active, for every
            # short-convolution layer; 0 where the model has none
            self.state_slots = self.buckets[-1] if self.conv_layers else 0
            # sliding-window layers: one window for all of them, a ring of
            # pages a sequence in the second page group
            # (one entry an attention layer)
            windows = self._windows = tuple(
                l.get("window") for l in desc["layers"]
                if l["operator"] == "attention")
            if len({w for w in windows if w}) > 1 \
                    or (self.latent and any(windows)):
                raise MXNetError("window layers share one window and cache "
                                 "K and V, got %r" % (windows,))
            self.window = int(max((w or 0 for w in windows), default=0))
            self.ring_pages = min(self.max_pages_per_seq, -(
                -(self.window - 1) // self.page_size) + 1) \
                if self.window else 0
            # every sequence of the widest batch at its whole ring, unless
            # told: more could never be held
            self.window_pages = 0 if not self.window else int(
                window_pages if window_pages is not None
                else self.buckets[-1] * self.ring_pages)
            if self.window_pages < self.ring_pages:
                raise MXNetError(
                    "one sequence's ring is %d pages but the window group "
                    "has only %d" % (self.ring_pages, self.window_pages))

            dtype = jax.numpy.dtype(self.dtype)
            self._params = jax.tree_util.tree_map(
                lambda a: jax.numpy.asarray(a, dtype), params)
            self._param_bytes = int(sum(
                a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(self._params)))
            # rows padded to the 128-lane tile HERE, once — never per call
            self._kv_lanes = self.kv_heads * self.head_dim
            leaf = (self.num_pages, self.page_size,
                    -(-self._kv_lanes // 128) * 128)
            # a layer's leaf: a (K, V) pair of its group's pages, or a
            # latent layer's one array
            self._kv = tuple(
                jax.numpy.zeros(leaf, dtype=self.kv_dtype) if self.latent
                else tuple(jax.numpy.zeros(
                    (self.window_pages,) + leaf[1:] if w else leaf,
                    dtype=self.kv_dtype) for _ in "kv")
                for w in windows)
            # row `state_slots` is the inert one that padding rows read
            self._slots = tuple(
                jax.numpy.zeros((self.state_slots + 1,
                                 int(desc["conv_taps"]) - 1, self.units),
                                dtype=self.kv_dtype)
                for _ in range(self.conv_layers))
            self.last_moe = None    # (pairs, experts hit, busiest) of a step
            # (token, expert) pairs the last prefill computed here, summed over
            # the expert layers: known from the prompt's length where every
            # expert is held, else it rides out of the program with the token
            self.last_prefill_moe_pairs = None
            ex = desc.get("experts") or {}
            self._share_held = self.has_experts and ex["held"] < ex["total"]
            self._pairs_per_token = int(ex.get("per_token", 0)) * sum(
                1 for l in desc["layers"] if l["ffn"] == "experts")
            # executable identity: description + geometry (params are args,
            # so two engines with one geometry share executables)
            ident = {"config": self.config, "pages": self.num_pages,
                     "page_size": self.page_size,
                     "maxp": self.max_pages_per_seq, "kv": self.kv_dtype}
            if desc["arch"] != "transformer_lm":
                ident["description"] = desc
                ident["slots"] = self.state_slots
            if self.window:
                ident["window_pages"] = self.window_pages
            self._fingerprint = hashlib.sha256(json.dumps(
                ident, sort_keys=True).encode()).hexdigest()[:32]
            build.fields["pool_bytes"] = self.kv_bytes()
            if self.window:
                build.fields.update(window_pages=self.window_pages,
                                    window_pool_bytes=self.window_kv_bytes())
            # how the decode attention walks the pool at the widest bucket
            # (a table of the growing group's width, or of the ring's where
            # every attention layer has the window): static for the engine,
            # so geometry() and this span carry it
            from ..ops.pallas_kernels import decode_attention_form
            build.fields["kernel_form"] = self.kernel_form = \
                decode_attention_form(
                    self.latent, self.head_dim, self.page_size, leaf[-1],
                    self.kv_dtype, self.num_heads // self.kv_heads,
                    self.buckets[-1], self.ring_pages if windows
                    and all(windows) else self.max_pages_per_seq)

    # -- sizing ------------------------------------------------------------
    def kv_bytes(self):
        """Device bytes of the page pool and the state slots (allocated in
        full at load — the figure `MXTPU_SERVE_MEMORY_BUDGET` admission
        prices)."""
        import jax

        return int(sum(a.size * a.dtype.itemsize for a in
                       jax.tree_util.tree_leaves((self._kv, self._slots))))

    def window_kv_bytes(self):
        """The window group's share of `kv_bytes`: the (K, V) arrays of the
        sliding-window layers."""
        return int(sum(a.size * a.dtype.itemsize
                       for pair, w in zip(self._kv, self._windows) if w
                       for a in pair))

    def param_bytes(self):
        return self._param_bytes

    def geometry(self):
        return {"num_pages": self.num_pages, "page_size": self.page_size,
                "max_pages_per_seq": self.max_pages_per_seq,
                "max_prompt": self.max_prompt,
                "max_new_tokens": self.max_new_tokens,
                "decode_buckets": list(self.buckets),
                "prefill_buckets": list(self.prefill_buckets),
                "kv_dtype": self.kv_dtype,
                "state_slots": self.state_slots,
                "kv_bytes": self.kv_bytes(),
                "param_bytes": self.param_bytes(),
                "kernel_form": dict(self.kernel_form),
                # the second page group (zeros: no window layers)
                "window": self.window, "ring_pages": self.ring_pages,
                "window_pages": self.window_pages,
                "window_kv_bytes": self.window_kv_bytes()}

    # -- executables -------------------------------------------------------
    def _key(self, kind, shape_sig):
        # no_persist: plain memory-tier entries (the decode loop's hit
        # path is a dict get; serializing pallas/jnp decode graphs buys
        # little and the artifact trust story nothing)
        # donation=(1,): every executable minted through this key (prefill
        # AND per-bucket decode) donates the state (KV pool, slots) at
        # argnum 1, and the fill-hook donation verifier
        # (telemetry.memory.verify_donation) only audits keys that declare it
        return _compile.ExecutableKey(
            kind, self._fingerprint, shapes=shape_sig, donation=(1,),
            static=(("pages", self.num_pages),
                    ("page_size", self.page_size),
                    ("maxp", self.max_pages_per_seq),
                    ("kv", self.kv_dtype))
            + ((("window_pages", self.window_pages),) if self.window
               else ()),
            no_persist=True)

    def _state(self):
        """What every program is handed at argnum 1, donated: the page pool,
        with the state slots beside it where the model has any."""
        return (self._kv, self._slots) if self.conv_layers else self._kv

    def _set_state(self, state):
        if self.conv_layers:
            self._kv, self._slots = state
        else:
            self._kv = state

    def _build_prefill(self, lp, logits_out=False):
        import jax
        import jax.numpy as jnp

        from ..ops import nn as _opsnn
        from ..ops.pallas_kernels import prompt_attention
        from ..ops.random_ops import sample_token_logits

        desc, ps, nump = self.description, self.page_size, self.num_pages
        lanes, stateful = self._kv_lanes, bool(self.conv_layers)
        latent, share_held = self.latent, self._share_held
        windowed, ring = bool(self.window), self.ring_pages
        rank = desc["latent"]["kv_rank"] if latent else None
        scale = _latent_scale(desc) if latent \
            else 1.0 / math.sqrt(self.head_dim)

        def fn(params, state, tokens, length, page_row, temp, top_k, top_p,
               key, *held):
            # tokens (lp,) int32 padded; length () int32; page_row (maxp,);
            # then slot () int32, the sequence's state slot (stateful
            # models), and ring_row (ring_pages,), its pages of the window
            # group (models with window layers)
            held = list(held)
            slot = held.pop(0) if stateful else None
            ring_row = held.pop(0) if windowed else None
            kv, slots = state if stateful else (state, ())
            t_idx = jnp.arange(lp)
            x = _lm_embed(desc, params, tokens, t_idx)           # (lp, C)
            tpage = jnp.where(t_idx < length, page_row[t_idx // ps], nump)
            tslot = t_idx % ps

            def attention(pages, q, k, v, window):
                kp, vp = pages
                if windowed:
                    # a model with window layers: whole pages, to a ring
                    # only those it still holds; attention a tile of
                    # scores at a time (28 heads x 12288^2 float32 scores
                    # would be 17 GB), a window layer's over its band alone
                    table, r = (ring_row, ring) if window else (page_row, 0)
                    pages = tuple(
                        _write_pages(pool, x.reshape(lp, lanes), table,
                                     length, r)
                        for pool, x in ((kp, k), (vp, v)))
                    return prompt_attention(q, k, v, scale, window), pages
                # one row of contiguous values a token, in place
                pages = (kp.at[tpage, tslot, :lanes].set(
                             k.reshape(lp, lanes).astype(kp.dtype),
                             mode="drop"),
                         vp.at[tpage, tslot, :lanes].set(
                             v.reshape(lp, lanes).astype(vp.dtype),
                             mode="drop"))
                return _opsnn.causal_attention(q, k, v, scale), pages

            def latent_attention(pages, q, row, layer):
                # the prompt's rows in place, whole pages at a time.  K and V
                # of every head expanded from the rows, queries
                # `_PREFILL_Q_BLOCK` at a time
                pages = _write_pages(pages, row, page_row, length)
                k_nope = jnp.einsum("lr,hdr->lhd", row[:, :rank],
                                    layer["kvb_k"],
                                    preferred_element_type=jnp.float32)
                k = jnp.concatenate(
                    [k_nope.astype(row.dtype), jnp.broadcast_to(
                        row[:, None, rank:], (lp, q.shape[1],
                                              lanes - rank))], axis=-1)
                v = jnp.einsum("lr,hdr->lhd", row[:, :rank], layer["kvb_v"],
                               preferred_element_type=jnp.float32)
                return _opsnn.causal_attention(
                    q, k, v.astype(row.dtype), scale,
                    block=_PREFILL_Q_BLOCK), pages

            def conv(held, r, layer):
                # the slot holds the gated input of the prompt's own last
                # positions, whatever the bucket's padding computes after
                o, st = _opsnn.gated_short_conv(
                    r, layer["in"]["w"], layer["conv"], layer["out"]["w"],
                    None, length)
                return o, held.at[slot].set(st.astype(held.dtype),
                                            mode="drop")

            x, stats, kv, slots = _lm_layers(
                desc, params, x, t_idx, t_idx < length, kv, slots,
                latent_attention if latent else attention, conv)
            new = (kv, slots) if stateful else kv
            if logits_out:
                return _lm_logits(desc, params, x), new          # (lp, V)
            logits = _lm_logits(desc, params, x[length - 1])     # (V,)
            tok = sample_token_logits(key, logits[None], temp, top_k,
                                      top_p)
            if share_held:
                # which of the prompt's pairs fall to the experts held here
                # is the router's answer: their count rides out with the
                # token
                return jnp.stack([tok[0], jnp.sum(jnp.stack(stats)[:, 0])
                                  .astype(tok.dtype)]), new
            return tok[0], new

        # the state is DONATED: without it every call materializes a
        # second full pool for the output (transient 2x kv_bytes — the
        # exact OOM the load-time budget admission promises to preclude)
        return lambda: jax.jit(fn, donate_argnums=(1,))

    def _build_decode(self, bucket, logits_out=False):
        import jax
        import jax.numpy as jnp

        from ..ops import nn as _opsnn
        from ..ops.pallas_kernels import (paged_attention,
                                          paged_latent_attention)
        from ..ops.random_ops import sample_token_logits

        desc, lanes, kvh = self.description, self._kv_lanes, self.kv_heads
        stateful, latent = bool(self.conv_layers), self.latent
        windowed = bool(self.window)
        scale = _latent_scale(desc) if latent \
            else 1.0 / math.sqrt(self.head_dim)

        def fn(params, state, tokens, positions, dest_pages, dest_slots,
               tables, lengths, temp, top_k, top_p, key, *held):
            # then seq_slots (b,) int32: each row's state slot; a padding
            # row names the row past the inert one, so it reads the inert
            # row and its write drops (stateful models); and ring_dest (b,),
            # ring_tables (b, ring_pages): where the row's K/V goes in the
            # window group, and its ring (models with window layers)
            held = list(held)
            seq_slots = held.pop(0) if stateful else None
            ring_dest, ring_tables = held if windowed else (None, None)
            b = tokens.shape[0]
            kv, slots = state if stateful else (state, ())
            x = _lm_embed(desc, params, tokens, positions)       # (b, C)

            def attention(pages, q, k, v, window):
                kp, vp = pages
                # a window layer: the ring's pages, and each row's first
                # live key
                dest, tbl, starts = (
                    ring_dest, ring_tables, jnp.maximum(lengths - window, 0)
                ) if window else (dest_pages, tables, None)
                kp = kp.at[dest, dest_slots, :lanes].set(
                    k.reshape(b, lanes).astype(kp.dtype), mode="drop")
                vp = vp.at[dest, dest_slots, :lanes].set(
                    v.reshape(b, lanes).astype(vp.dtype), mode="drop")
                return paged_attention(
                    q, kp, vp, tbl, lengths, sm_scale=scale, kv_heads=kvh,
                    starts=starts), (kp, vp)

            def latent_attention(pages, q, row, layer):
                # absorbed: the key up-projection folded into the query, the
                # value up-projection applied to the attended rows; a page
                # is read once for both products
                rank, nope = desc["latent"]["kv_rank"], desc["latent"]["nope"]
                pages = pages.at[dest_pages, dest_slots].set(
                    jnp.pad(row, ((0, 0), (0, pages.shape[-1] - lanes)))
                    .astype(pages.dtype), mode="drop")
                q_lat = jnp.einsum("bhd,hdr->bhr", q[..., :nope],
                                   layer["kvb_k"])
                o_lat = paged_latent_attention(
                    jnp.concatenate([q_lat.astype(q.dtype), q[..., nope:]],
                                    axis=-1),
                    pages, tables, lengths, scale, rank)
                return jnp.einsum("bhr,hdr->bhd", o_lat, layer["kvb_v"],
                                  preferred_element_type=jnp.float32), pages

            def conv(held, r, layer):
                o, st = _opsnn.gated_short_conv(
                    r[:, None], layer["in"]["w"], layer["conv"],
                    layer["out"]["w"], held.at[seq_slots].get(mode="clip"))
                return o[:, 0], held.at[seq_slots].set(
                    st.astype(held.dtype), mode="drop")

            x, stats, kv, slots = _lm_layers(
                desc, params, x, positions, lengths > 0, kv, slots,
                latent_attention if latent else attention, conv)
            new = (kv, slots) if stateful else kv
            logits = _lm_logits(desc, params, x)                 # (b, V)
            if logits_out:
                return logits, new
            tok = sample_token_logits(key, logits, temp, top_k, top_p)
            if stats:
                # the step's expert counts ride out with its tokens: pairs
                # computed and experts hit, summed over the expert layers,
                # and the busiest expert's pairs
                st = jnp.stack(stats)
                tok = jnp.concatenate([tok, jnp.stack(
                    [jnp.sum(st[:, 0]), jnp.sum(st[:, 1]),
                     jnp.max(st[:, 2])]).astype(tok.dtype)])
            return tok, new

        # state donated: the per-step update must alias, not copy, the pool
        return lambda: jax.jit(fn, donate_argnums=(1,))

    def _prefill_exe(self, lp, example_args=None, logits_out=False):
        # example_args routes a miss through the registry's AOT fill, so
        # the donation verifier actually audits the declared KV-pool
        # donation at fill time (misses only; hits never evaluate it)
        kind = "lm_prefill_logits" if logits_out else "lm_prefill"
        return _compile.get_or_build(
            self._key(kind, ("prompt", lp)),
            self._build_prefill(lp, logits_out), label="%s:l%d" % (kind, lp),
            example_args=example_args)

    def _decode_exe(self, bucket, example_args=None, logits_out=False):
        kind = "lm_decode_logits" if logits_out else "lm_decode"
        return _compile.get_or_build(
            self._key(kind, ("batch", bucket)),
            self._build_decode(bucket, logits_out),
            label="%s:b%d" % (kind, bucket), example_args=example_args)

    # -- driving -----------------------------------------------------------
    def _prefill_args(self, tokens, page_row, sampling, key, slot,
                      ring_row):
        lp = bucket_for(len(tokens), self.prefill_buckets)
        if lp is None:
            raise MXNetError("prompt of %d tokens overflows the prefill "
                             "buckets %s" % (len(tokens),
                                             self.prefill_buckets))
        padded = _np.zeros(lp, _np.int32)
        padded[:len(tokens)] = tokens
        temp, top_k, top_p = sampling
        args = (self._params, self._state(), padded,
                _np.int32(len(tokens)), _np.asarray(page_row, _np.int32),
                _np.float32([temp]), _np.int32([top_k]),
                _np.float32([top_p]), key)
        if self.conv_layers:
            # no slot named (warm-up): the row past the inert one, dropped
            args += (_np.int32(self.state_slots + 1 if slot is None
                               else slot),)
        if self.window:
            # no ring named (warm-up): page 0, which nobody holds yet
            args += (_np.zeros(self.ring_pages, _np.int32) if ring_row is None
                     else _np.asarray(ring_row, _np.int32),)
        return lp, args

    def prefill(self, tokens, page_row, sampling, key, slot=None,
                ring_row=None):
        """Run one prompt through its padded prefill bucket; writes the
        prompt's K/V into `page_row`'s pages (a window layer's, what its
        ring still holds of them, into `ring_row`'s) and its convolution
        state into state slot `slot`, and returns the sampled first token
        (int)."""
        lp, args = self._prefill_args(tokens, page_row, sampling, key, slot,
                                      ring_row)
        tok, state = self._prefill_exe(lp, lambda: args)(*args)
        self._set_state(state)
        with _goodput.phase("prefill_wait"):    # blocked on the device
            out = _np.asarray(tok).reshape(-1)
        if self.has_experts:
            self.last_prefill_moe_pairs = int(out[1]) if self._share_held \
                else len(tokens) * self._pairs_per_token
        return int(out[0])

    def _decode_args(self, tokens, positions, dest_pages, dest_slots, tables,
                     lengths, temps, top_ks, top_ps, key, seq_slots,
                     ring_dest, ring_tables):
        args = (self._params, self._state(), tokens, positions, dest_pages,
                dest_slots, tables, lengths, temps, top_ks, top_ps, key)
        if self.conv_layers:
            # no slots named (warm-up): the row past the inert one, dropped
            if seq_slots is None:
                seq_slots = _np.full(len(tokens), self.state_slots + 1,
                                     _np.int32)
            args += (_np.asarray(seq_slots, _np.int32),)
        if self.window:
            # no rings named (warm-up): writes past the group's pages, dropped
            if ring_dest is None:
                ring_dest = _np.full(len(tokens), self.window_pages, _np.int32)
                ring_tables = _np.zeros((len(tokens), self.ring_pages),
                                        _np.int32)
            args += (_np.asarray(ring_dest, _np.int32),
                     _np.asarray(ring_tables, _np.int32))
        return args

    def decode_step(self, tokens, positions, dest_pages, dest_slots,
                    tables, lengths, temps, top_ks, top_ps, key,
                    seq_slots=None, ring_dest=None, ring_tables=None):
        """One token for every row (rows with length 0 are inert padding:
        their K/V and state writes drop and their sampled token is
        discarded). ``dest_pages`` and ``tables`` are of the growing page
        group; a model with window layers is also told each row's page of
        the window group to write (``ring_dest``) and its ring
        (``ring_tables``, (b, ring_pages)). Returns an int32 numpy array of
        next tokens; a model with expert layers leaves the step's (pairs,
        experts hit, busiest expert's pairs) in ``last_moe``."""
        args = self._decode_args(tokens, positions, dest_pages, dest_slots,
                                 tables, lengths, temps, top_ks, top_ps, key,
                                 seq_slots, ring_dest, ring_tables)
        out, state = self._decode_exe(len(tokens), lambda: args)(*args)
        self._set_state(state)
        with _goodput.phase("decode_wait"):     # blocked on the device
            out = _np.asarray(out)
        if self.has_experts:
            self.last_moe = tuple(int(v) for v in out[len(tokens):])
            out = out[:len(tokens)]
        return out

    # test-only: the logits both programs sample from, through the same
    # pages and slots (tests/test_lfm2.py compares them with a reference)
    def prefill_logits(self, tokens, page_row, slot=None, ring_row=None):
        """(len(tokens), V) float32 logits of a prompt's every position;
        writes pages and slot as `prefill` does."""
        lp, args = self._prefill_args(tokens, page_row, (0.0, 0, 1.0),
                                      _random.next_key(), slot, ring_row)
        logits, state = self._prefill_exe(lp, lambda: args, True)(*args)
        self._set_state(state)
        return _np.asarray(logits)[:len(tokens)]

    def decode_logits(self, tokens, positions, dest_pages, dest_slots,
                      tables, lengths, seq_slots=None, ring_dest=None,
                      ring_tables=None):
        """(b, V) float32 logits of one decode step; writes as
        `decode_step` does."""
        b = len(tokens)
        args = self._decode_args(
            tokens, positions, dest_pages, dest_slots, tables, lengths,
            _np.zeros(b, _np.float32), _np.zeros(b, _np.int32),
            _np.ones(b, _np.float32), _random.next_key(), seq_slots,
            ring_dest, ring_tables)
        logits, state = self._decode_exe(b, lambda: args, True)(*args)
        self._set_state(state)
        return _np.asarray(logits)

    def warm(self):
        """Compile every prefill + decode bucket (dummy data, dropped
        writes) so steady-state generation is zero-compile. Each bucket's
        call, from dispatch to the fetched result, is a ``first_run`` span
        of the start-up account (the ``program`` span of a miss lies
        inside it). Returns seconds."""
        t0 = time.monotonic()
        maxp = self.max_pages_per_seq
        for lp in self.prefill_buckets:
            # a full-bucket prompt so EVERY prefill bucket compiles (a
            # 1-token prompt would only ever warm the smallest)
            with _goodput.span("first_run", label="lm_prefill:l%d" % lp):
                self.prefill([1] * lp, _np.zeros(maxp, _np.int32),
                             (0.0, 0, 1.0), _random.next_key())
        for b in self.buckets:
            with _goodput.span("first_run", label="lm_decode:b%d" % b):
                self.decode_step(
                    _np.zeros(b, _np.int32), _np.zeros(b, _np.int32),
                    _np.full(b, self.num_pages, _np.int32),
                    _np.zeros(b, _np.int32),
                    _np.zeros((b, maxp), _np.int32),
                    _np.zeros(b, _np.int32), _np.zeros(b, _np.float32),
                    _np.zeros(b, _np.int32), _np.ones(b, _np.float32),
                    _random.next_key())
        return time.monotonic() - t0


# ---------------------------------------------------------------------------
# artifact IO — <prefix>-lmconfig.json + <prefix>-lm.params
# ---------------------------------------------------------------------------

# zoo blocks a generation artifact can hold, by the header's "arch"
_LM_ARCHS = {
    "transformer_lm": ("mxnet_tpu.gluon.model_zoo.transformer",
                       "TransformerLM"),
    "lfm2": ("mxnet_tpu.gluon.model_zoo.lfm2", "Lfm2LM"),
    "gigachat3": ("mxnet_tpu.gluon.model_zoo.gigachat3", "GigaChat3LM"),
    "smallthinker": ("mxnet_tpu.gluon.model_zoo.smallthinker",
                     "SmallThinkerLM"),
}
# dtypes numpy's .npy cannot name are written as these views of their bits
_STORED_AS = {"bfloat16": "uint16"}


def _lm_arch(lm):
    for arch, (_, cls) in _LM_ARCHS.items():
        if type(lm).__name__ == cls:
            return arch
    raise MXNetError("%s is no zoo block a generation artifact can hold "
                     "(have %s)" % (type(lm).__name__, sorted(_LM_ARCHS)))


def _write_params(lm, path, dtype):
    """The parameters as an npz in ``dtype``, one array at a time (the host
    never holds more than the largest), bfloat16 as its bits."""
    import zipfile

    import jax

    from ..base import atomic_writer

    view = _STORED_AS.get(dtype)
    with atomic_writer(path, "wb") as f:
        with zipfile.ZipFile(f, "w", zipfile.ZIP_STORED,
                             allowZip64=True) as z:
            for name, p in lm._collect_params_with_prefix().items():
                a = p.data().asnumpy().astype(jax.numpy.dtype(dtype),
                                              copy=False)
                with z.open(name + ".npy", "w", force_zip64=True) as h:
                    _np.lib.format.write_array(
                        h, a.view(view) if view else a, allow_pickle=False)


def _adopt_params(lm, path, dtype):
    """Read an npz written by `_write_params` onto the device one array at
    a time; each parameter adopts its array (no initializer, no second
    copy: a model that fills most of the device loads)."""
    import jax

    view = _STORED_AS.get(dtype)
    with _np.load(path, allow_pickle=False) as f:
        for name, p in lm._collect_params_with_prefix().items():
            if name not in f.files:
                raise MXNetError("Parameter %s missing in %s" % (name, path))
            a = f[name]
            if view:
                a = a.view(jax.numpy.dtype(dtype))
            p.adopt(jax.device_put(a))


def _artifact_fields(lm, prefix):
    """What an artifact span says of its work: the parameter file's bytes
    and how many arrays it holds."""
    return {"bytes": os.path.getsize(prefix + "-lm.params"),
            "arrays": len(lm.collect_params())}


def save_lm(lm, prefix):
    """Write a generation-serving artifact: the header (which zoo block, its
    constructor arguments, and the per-layer description the engine builds
    its programs from) and the parameters. This is what `tools/serve.py
    --model name=PREFIX@generate` and replica workers load."""
    from .. import nd
    from ..base import atomic_writer

    prefix = os.fspath(prefix)
    arch = _lm_arch(lm)
    with _goodput.span("artifact_write") as sp:
        if any(p._data is None for p in lm.collect_params().values()):
            # deferred Dense/LayerNorm shapes materialize on first forward
            lm(nd.array([[0]], dtype="int32"))
        description = lm.description() if hasattr(lm, "description") \
            else transformer_lm_description(lm.config)
        with atomic_writer(prefix + "-lmconfig.json", "w") as f:
            json.dump({"format": _LM_FORMAT, "arch": arch,
                       "config": lm.config, "description": description},
                      f, indent=1)
        if arch == "transformer_lm":
            lm.save_parameters(prefix + "-lm.params")
        else:
            _write_params(lm, prefix + "-lm.params", description["dtype"])
        sp.fields.update(_artifact_fields(lm, prefix))
    return prefix


def load_lm(prefix):
    """Rebuild the zoo block of a `save_lm` artifact: the header's "arch"
    names it (a header without one is a `TransformerLM`'s)."""
    import importlib

    prefix = os.fspath(prefix)
    cfg_path = prefix + "-lmconfig.json"
    if not os.path.exists(cfg_path):
        raise MXNetError("no generation artifact at %r (expected %s)"
                         % (prefix, cfg_path))
    with open(cfg_path) as f:
        header = json.load(f)
    if header.get("format") != _LM_FORMAT:
        raise MXNetError("%s: unknown LM artifact format %r"
                         % (cfg_path, header.get("format")))
    arch = header.get("arch", "transformer_lm")
    if arch not in _LM_ARCHS:
        raise MXNetError("%s: unknown LM architecture %r (have %s)"
                         % (cfg_path, arch, sorted(_LM_ARCHS)))
    module, cls = _LM_ARCHS[arch]
    with _goodput.span("artifact_read") as sp:
        lm = getattr(importlib.import_module(module),
                     cls)(**header["config"])
        if arch == "transformer_lm":
            lm.load_parameters(prefix + "-lm.params")
        else:
            _adopt_params(lm, prefix + "-lm.params",
                          header["description"]["dtype"])
        sp.fields.update(_artifact_fields(lm, prefix))
    return lm


# ---------------------------------------------------------------------------
# the repository-facing served model
# ---------------------------------------------------------------------------

class ServedLM:
    """One served generation model (`ModelRepository` duck type).

    In-process it owns a `GenerateScheduler`; pooled (``replicas >= 1``)
    it routes each request to a replica worker over the supervisor wire
    protocol — every worker runs its own scheduler, so continuous
    batching happens replica-side while routing, failover (exactly-once
    re-dispatch) and health checks stay router-side.
    """

    def __init__(self, name, version, scheduler=None, pool=None, info=None,
                 meta=None):
        self.name = str(name)
        self.version = int(version)
        self._scheduler = scheduler
        self._pool = pool
        self.meta = dict(meta or {})
        self.loaded_at = time.time()
        # autoscaling policy (docs/serving.md §Autoscaling)
        self.min_replicas = None
        self.max_replicas = None
        self.pinned = False
        self.warmed = True
        if scheduler is not None:
            self.generate_info = dict(scheduler.engine.geometry())
            self.warm_seconds = scheduler.warm_seconds
        else:
            self.generate_info = dict((info or {}).get("generate") or {})
            self.warm_seconds = (info or {}).get("warm_seconds")
        self.memory_bytes = (
            (self.generate_info.get("kv_bytes") or 0)
            + (self.generate_info.get("param_bytes") or 0)) or None
        if self.effective_memory_bytes:
            telemetry.gauge("mxtpu_serve_model_memory_bytes",
                            {"model": "%s/%d" % (self.name, self.version)}
                            ).set(self.effective_memory_bytes)

    # -- construction ------------------------------------------------------
    @staticmethod
    def load(name, version, prefix, replicas=0, queue_depth=None,
             worker_args=None, pool_kwargs=None, **engine_kwargs):
        """Load a `save_lm` artifact as a served generation model.

        ``replicas`` = 0 runs the scheduler in-process; N >= 1 spawns a
        supervised `ReplicaPool` in generate mode (``engine_kwargs`` with
        geometry meaning — kv pages/page size/max batch — are forwarded
        to the workers as argv so router and replicas agree)."""
        version = int(version)
        if replicas and replicas > 0:
            from .replica_pool import ReplicaPool

            if worker_args is None:
                if prefix is None:
                    raise MXNetError("pooled ServedLM.load needs an "
                                     "artifact prefix (or worker_args)")
                worker_args = ["--generate", os.fspath(prefix)]
                flag_for = {"num_pages": "--kv-pages",
                            "page_size": "--kv-page-size",
                            "window_pages": "--kv-window-pages",
                            "max_prompt": "--max-prompt",
                            "max_new_tokens": "--max-new-tokens",
                            "max_batch": "--max-batch"}
                for k, flag in flag_for.items():
                    if engine_kwargs.get(k) is not None:
                        worker_args += [flag, str(engine_kwargs[k])]
            pool = ReplicaPool("%s/%d" % (name, version), worker_args,
                               replicas, generate=True,
                               gen_queue_depth=queue_depth,
                               **(pool_kwargs or {}))
            try:
                info = pool.wait_ready()
            except Exception:
                pool.close()
                raise
            # router-side SLOs over the pool's own admission→resolution
            # latency/volume series (the workers' scheduler objectives
            # are per-replica-process): THE breach signal the autoscaler
            # reads for pooled LMs (docs/serving.md §Autoscaling).
            # queue_depth=None: the router has no queue-depth gauge
            _slo.wire_serving_objectives("%s/%d" % (name, version))
            return ServedLM(name, version, pool=pool, info=info,
                            meta={"artifact": "generate",
                                  "path": None if prefix is None
                                  else os.fspath(prefix),
                                  "replicas": int(replicas)})
        engine = TransformerLMEngine(lm=load_lm(prefix), **engine_kwargs)
        sched = GenerateScheduler(engine,
                                  name="%s/%d" % (name, version),
                                  queue_depth=queue_depth)
        return ServedLM(name, version, scheduler=sched,
                        meta={"artifact": "generate",
                              "path": os.fspath(prefix)})

    # -- serving surface ---------------------------------------------------
    @property
    def pool(self):
        return self._pool

    @property
    def scheduler(self):
        return self._scheduler

    @property
    def resident_copies(self):
        # live pool size, so budget math tracks autoscaler resizes
        if self._pool is not None:
            return max(1, int(self._pool.size))
        try:
            return max(1, int(self.meta.get("replicas") or 1))
        except (TypeError, ValueError):
            return 1

    @property
    def effective_memory_bytes(self):
        if not self.memory_bytes:
            return None
        return self.memory_bytes * self.resident_copies

    def generate(self, tokens, max_new_tokens=None, temperature=0.0,
                 top_k=0, top_p=1.0, timeout_ms=None):
        """Admit one generation request and wait for it: returns
        ``{"tokens": [...], "finish_reason": ...}``. Raises the typed
        admission errors (429/503/504/400 mapping) like predict."""
        if timeout_ms is None:
            timeout_ms = _env.get("MXTPU_SERVE_TIMEOUT_MS")
        deadline = None
        if timeout_ms and timeout_ms > 0:
            deadline = time.monotonic() + float(timeout_ms) / 1e3
        if self._pool is not None:
            req = self._make_pool_request(tokens, max_new_tokens,
                                          temperature, top_k, top_p,
                                          deadline)
            self._pool.submit_generate(req)
        else:
            req = self._scheduler.submit(
                tokens, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                deadline=deadline)
        timeout = None if deadline is None \
            else max(0.0, deadline - time.monotonic())
        out = req.wait(timeout)
        return {"tokens": out, "finish_reason": req.finish_reason}

    def _make_pool_request(self, tokens, max_new_tokens, temperature,
                           top_k, top_p, deadline):
        """Router-side validation mirrors the scheduler's (the worker
        re-validates, but a malformed request should 400 here, not ride
        the wire)."""
        tokens = [int(t) for t in tokens]
        gi = self.generate_info
        if not tokens:
            raise MXNetError("generation needs at least one prompt token")
        if gi.get("max_prompt") and len(tokens) > gi["max_prompt"]:
            raise MXNetError(
                "prompt has %d tokens; this model admits up to %d"
                % (len(tokens), gi["max_prompt"]))
        cap = gi.get("max_new_tokens") \
            or _env.get("MXTPU_SERVE_MAX_NEW_TOKENS")
        if max_new_tokens is None:
            max_new_tokens = cap
        if int(max_new_tokens) < 1 or int(max_new_tokens) > cap:
            raise MXNetError("max_new_tokens must be in 1..%d, got %s"
                             % (cap, max_new_tokens))
        return GenRequest(tokens, max_new_tokens, temperature=temperature,
                          top_k=top_k, top_p=top_p, deadline=deadline)

    # -- repository lifecycle ---------------------------------------------
    def pending(self):
        if self._scheduler is not None:
            return self._scheduler.pending()
        return self._pool.generate_pending()

    def drain(self, timeout=None):
        if self._scheduler is not None:
            return self._scheduler.drain(timeout)
        return self._pool.drain_generate(timeout)

    def abort_pending(self, error=None):
        if self._scheduler is not None:
            return self._scheduler.abort_pending(error)
        return self._pool.abort_generate(error)

    def close(self, drain=True, timeout=None):
        drained = False
        if self._scheduler is not None:
            drained = self._scheduler.close(drain=drain, timeout=timeout)
        if self._pool is not None:
            if drain:
                drained = self._pool.drain_generate(timeout)
            self._pool.close()
            # retire the router-side objectives wired at pooled load —
            # verdicts for a gone model are noise on /statusz
            _slo.unregister_model("%s/%d" % (self.name, self.version))
        return drained

    def describe(self):
        out = {
            "name": self.name,
            "version": self.version,
            "kind": "generate",
            "generate": dict(self.generate_info),
            "warmed": self.warmed,
            "warm_seconds": self.warm_seconds,
            "pending": self.pending(),
            "loaded_at": self.loaded_at,
            "meta": self.meta,
            "memory": {"total_bytes": self.memory_bytes,
                       "copies": self.resident_copies,
                       "effective_bytes": self.effective_memory_bytes},
        }
        if self._scheduler is not None:
            alloc = self._scheduler.allocator
            out["kv"] = {"pages_total": alloc.num_pages,
                         "pages_used": alloc.used_pages,
                         "page_size": alloc.page_size}
            ring = self._scheduler.window_allocator
            if ring is not None:
                out["kv"].update(window_pages_total=ring.num_pages,
                                 window_pages_used=ring.used_pages)
        if self._pool is not None:
            out["pool"] = self._pool.describe()
        return out
