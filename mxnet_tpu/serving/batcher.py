"""Dynamic micro-batcher: the request queue at the heart of the serving
subsystem (docs/serving.md).

The predict API (`predict.py`) was built for the one-caller-one-forward
case; between requests the accelerator idles. This module closes that gap
with the request/micro-batch design of clipper/triton-style model servers:

  * concurrent requests land in a bounded queue (admission control:
    ``MXTPU_SERVE_QUEUE_DEPTH``, overflow is rejected immediately — the
    HTTP layer maps that to 429);
  * a single worker thread coalesces them into one batch, closing it when
    it reaches ``MXTPU_SERVE_MAX_BATCH`` examples or when the oldest
    admitted request has waited ``MXTPU_SERVE_MAX_DELAY_MS``;
  * the batch is padded up to a POWER-OF-TWO bucket so every bucket maps
    to exactly one cached XLA executable (the Executor caches one
    compiled forward per input signature) — steady state never
    recompiles, whatever batch sizes arrive;
  * results are unpadded and split back per request (the shared
    `base.unpad_outputs` helper — same code path as module predict's
    last-batch unpad).

One worker thread per batcher means the underlying predictor is only ever
driven single-threaded — executor forward needs no locking — while any
number of frontend threads block cheaply on their request's event.

Everything here is framework-agnostic: the ``runner`` callable owns the
model; numpy in, numpy out.
"""
from __future__ import annotations

import collections
import logging
import threading
import time

import numpy as _np

from .. import env as _env
from .. import telemetry
from ..telemetry import slo as _slo
from ..telemetry import tracing as _tracing
from ..base import MXNetError, unpad_outputs

__all__ = [
    "ServingError", "QueueFullError", "DeadlineExceededError",
    "ModelUnavailableError", "DrainingError", "OverloadedError",
    "MemoryBudgetError",
    "power_of_two_buckets", "bucket_for", "pad_batch", "DynamicBatcher",
    "drain_timeout_s",
]

_LOG = logging.getLogger("mxnet_tpu.serving.batcher")
_warned_drain_s = False


def drain_timeout_s():
    """Effective graceful-drain budget in seconds: the
    `MXTPU_SERVE_DRAIN_TIMEOUT_MS` default, honoring the deprecated
    seconds-typed `MXTPU_SERVE_DRAIN_TIMEOUT_S` (with a one-time warning)
    when only the old name is set — one fallback shared by every drain
    reader, so a deployment's configured budget survives the rename no
    matter which drain path runs."""
    global _warned_drain_s
    timeout = _env.get("MXTPU_SERVE_DRAIN_TIMEOUT_MS") / 1e3
    if not _env.is_set("MXTPU_SERVE_DRAIN_TIMEOUT_MS") \
            and _env.is_set("MXTPU_SERVE_DRAIN_TIMEOUT_S"):
        timeout = _env.get("MXTPU_SERVE_DRAIN_TIMEOUT_S")
        if not _warned_drain_s:
            _warned_drain_s = True
            _LOG.warning(
                "MXTPU_SERVE_DRAIN_TIMEOUT_S is deprecated; set "
                "MXTPU_SERVE_DRAIN_TIMEOUT_MS=%d instead (honoring the "
                "old value as %.0fs)", int(timeout * 1e3), timeout)
    return timeout


class ServingError(MXNetError):
    """Base serving-layer error; `status` is the HTTP mapping and
    `retry_after` (seconds, optional) becomes a ``Retry-After`` header."""

    status = 500
    retry_after = None


class QueueFullError(ServingError):
    """Admission control: the bounded request queue is full."""

    status = 429
    retry_after = 1


class DeadlineExceededError(ServingError):
    """The request's deadline expired before a result was produced."""

    status = 504


class ModelUnavailableError(ServingError):
    """No such model/version (or it has been unloaded)."""

    status = 404


class DrainingError(ServingError):
    """The server/model is draining and admits no new work."""

    status = 503


class OverloadedError(ServingError):
    """Deterministic load shedding: the model's replica pool is degraded
    and taking this request would queue it into a black hole. The reply
    carries ``Retry-After`` scaled to the healthy-replica count
    (docs/serving.md resilience section)."""

    status = 503

    def __init__(self, msg, retry_after=1):
        super().__init__(msg)
        self.retry_after = max(1, int(retry_after))


class MemoryBudgetError(ServingError):
    """A model load's computed device footprint (per-executable
    `memory_analysis()` figures, docs/observability.md §Memory) exceeds
    ``MXTPU_SERVE_MEMORY_BUDGET``: the load is rejected BEFORE publish —
    at admission time, deterministically — instead of letting the
    process OOM under traffic. 507 Insufficient Storage.

    ``details`` carries the machine-readable footprint breakdown
    (requested bytes, per-resident-model ``effective_memory_bytes``,
    budget, headroom, shortfall) so an operator can see WHAT to evict;
    the HTTP layer ships it in the 507 body."""

    status = 507

    def __init__(self, msg, details=None):
        super().__init__(msg)
        self.details = details


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

def power_of_two_buckets(max_batch):
    """The padding buckets for a given max batch: every power of two below
    ``max_batch``, plus ``max_batch`` itself as the terminal bucket (so a
    non-power-of-two max still gets exactly one executable)."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise MXNetError("max_batch must be >= 1, got %d" % max_batch)
    buckets, b = [], 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return buckets


def bucket_for(n, buckets):
    """Smallest bucket holding ``n`` examples (None when n overflows)."""
    for b in buckets:
        if n <= b:
            return b
    return None


def pad_batch(batch, total, buckets):
    """Concatenate the requests' input arrays and zero-pad up to the
    smallest bucket holding ``total`` examples. Returns ``(padded_arrays,
    bucket)``. Shared by the inline runner path and the replica-pool
    dispatchers (each pads in its own thread)."""
    bucket = bucket_for(total, buckets)
    names = batch[0].arrays.keys()
    padded = {}
    for name in names:
        parts = [r.arrays[name] for r in batch]
        a = parts[0] if len(parts) == 1 else _np.concatenate(parts)
        if a.shape[0] < bucket:
            pad = _np.zeros((bucket - a.shape[0],) + a.shape[1:],
                            dtype=a.dtype)
            a = _np.concatenate([a, pad])
        padded[name] = a
    return padded, bucket


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

class ServeRequest:
    """One admitted inference request: ``arrays`` is a dict of input name ->
    numpy array whose leading dim is this request's example count."""

    __slots__ = ("arrays", "n", "deadline", "outputs", "error", "bucket",
                 "_event", "_rlock", "_t_submit", "queue_seconds",
                 "compute_seconds", "retried", "trace")

    def __init__(self, arrays, n, deadline):
        self.arrays = arrays
        self.n = n
        self.deadline = deadline
        self.outputs = None
        self.error = None
        self.bucket = None
        self.queue_seconds = None
        self.compute_seconds = None
        self.retried = False  # failover re-enqueue happened (exactly once)
        # span context captured at admission (the HTTP handler's request
        # span); every later phase — whichever thread or process runs it —
        # parents its spans here, so one trace follows the request
        self.trace = _tracing.capture()
        self._event = threading.Event()
        self._rlock = threading.Lock()
        self._t_submit = time.monotonic()

    def done(self):
        return self._event.is_set()

    def wait(self, timeout=None):
        """Block until the batcher resolves this request (or the wait times
        out / the deadline passes). Returns the per-request output list or
        raises the ServingError the batcher recorded."""
        self._event.wait(timeout)
        if not self._event.is_set():
            raise DeadlineExceededError(
                "request expired after %.0f ms in queue"
                % ((time.monotonic() - self._t_submit) * 1e3))
        if self.error is not None:
            raise self.error
        return self.outputs

    def _resolve(self, outputs=None, error=None):
        # first resolution wins, ATOMICALLY: a replica dispatch thread, the
        # drain thread's abort_pending and the worker's expiry path can race
        # here, and an unlocked check-then-act could interleave their writes
        # so a waiter wakes to outputs=None, error=None
        with self._rlock:
            if self._event.is_set():
                return  # a late error must not clobber a result a waiter
                #         may already be reading
            self.outputs = outputs
            self.error = error
            self._event.set()


# ---------------------------------------------------------------------------
# the batcher
# ---------------------------------------------------------------------------

class DynamicBatcher:
    """Coalesce concurrent requests into padded, bucketed batches.

    Parameters
    ----------
    runner : callable(batch_arrays, bucket, n) -> list of numpy arrays
        Runs one padded batch (leading dim == bucket) and returns the model
        outputs, each with leading dim == bucket. Called only from the
        batcher's single worker thread.
    buckets : list of int
        Ascending padding buckets; the last is the max coalesced batch.
    max_delay_ms / queue_depth : admission + coalescing knobs
        Default to ``MXTPU_SERVE_MAX_DELAY_MS`` / ``MXTPU_SERVE_QUEUE_DEPTH``.
    name : str
        Telemetry label (``model="<name>"`` on every serving metric).
    dispatcher : callable(batch, total), optional
        Takes over batch execution (the replica pool's hook): called from
        the worker thread with an assembled, expiry-filtered batch; the
        dispatcher must eventually route every request through
        `resolve_batch` / `fail_batch` / `requeue` so in-flight accounting
        closes. When None (default), batches run inline on ``runner``.
    admission_gate : callable(queued_len) -> ServingError or None, optional
        Consulted under the queue lock on every submit BEFORE the depth
        check — the replica pool sheds load here when degraded (an error
        return is raised to the caller; the request never queues).
    """

    def __init__(self, runner, buckets, max_delay_ms=None, queue_depth=None,
                 name="default", dispatcher=None, admission_gate=None):
        self._runner = runner
        self._dispatcher = dispatcher
        self._admission_gate = admission_gate
        self.buckets = sorted(int(b) for b in buckets)
        if not self.buckets:
            raise MXNetError("need at least one bucket")
        self.max_batch = self.buckets[-1]
        if max_delay_ms is None:
            max_delay_ms = _env.get("MXTPU_SERVE_MAX_DELAY_MS")
        if queue_depth is None:
            queue_depth = _env.get("MXTPU_SERVE_QUEUE_DEPTH")
        self.max_delay_s = max(0.0, float(max_delay_ms)) / 1e3
        self.queue_depth = max(1, int(queue_depth))
        self.name = name

        self._queue = collections.deque()
        self._cv = threading.Condition()
        self._stop = False
        self._draining = False
        # requests popped but not yet resolved — a SET (not a count) so a
        # forced drain can resolve work stuck inside a wedged runner
        self._inflight = set()

        labels = {"model": name}
        self._m_queue = telemetry.gauge("mxtpu_serve_queue_depth", labels)
        self._m_reqs = telemetry.counter("mxtpu_serve_requests_total", labels)
        self._m_examples = telemetry.counter("mxtpu_serve_examples_total",
                                             labels)
        self._m_batches = telemetry.counter("mxtpu_serve_batches_total",
                                            labels)
        self._m_rej_full = telemetry.counter(
            "mxtpu_serve_rejected_total", {"model": name, "reason": "queue_full"})
        self._m_rej_dead = telemetry.counter(
            "mxtpu_serve_rejected_total", {"model": name, "reason": "deadline"})
        self._m_rej_shed = telemetry.counter(
            "mxtpu_serve_rejected_total", {"model": name, "reason": "shed"})
        # how full each dispatched bucket was (n / bucket)
        self._m_occupancy = telemetry.histogram(
            "mxtpu_serve_batch_occupancy", labels,
            bounds=tuple(i / 10.0 for i in range(1, 11)))
        self._m_batch_size = telemetry.histogram(
            "mxtpu_serve_batch_size", labels,
            bounds=tuple(float(b) for b in self.buckets))
        # queue-wait vs compute split per request — the first thing to read
        # when serving latency is off (is it admission or the model?)
        self._m_queue_s = telemetry.histogram("mxtpu_serve_queue_seconds",
                                              labels)
        self._m_compute_s = telemetry.histogram("mxtpu_serve_compute_seconds",
                                                labels)
        # end-to-end admission→resolution latency per request: THE serving
        # SLO figure (the built-in p99 objective and /statusz windowed
        # rates read it), with trace-id exemplars so a breach names a
        # renderable trace
        self._m_request_s = telemetry.histogram("mxtpu_serve_request_seconds",
                                                labels)
        # built-in SLOs for this model: p99 / availability / queue-depth
        # ceiling (docs/observability.md §SLOs); dropped again in close()
        _slo.wire_serving_objectives(name, queue_depth=self.queue_depth)

        self._worker = threading.Thread(
            target=self._loop, name="mxtpu-serve-batcher-%s" % name,
            daemon=True)
        self._worker.start()

    # -- admission ---------------------------------------------------------
    def submit(self, arrays, deadline=None):
        """Admit one request. ``arrays``: dict name -> numpy array, leading
        dim = example count (1..max_batch). Returns a `ServeRequest` whose
        ``wait()`` yields the unpadded per-request outputs."""
        ns = {int(a.shape[0]) for a in arrays.values()}
        if not ns:
            raise MXNetError("request carries no input arrays")
        if len(ns) != 1:
            raise MXNetError("inconsistent leading dims across inputs: %s"
                             % sorted(ns))
        n = ns.pop()
        if n < 1 or n > self.max_batch:
            raise MXNetError(
                "request carries %d examples; this model serves 1..%d per "
                "request (MXTPU_SERVE_MAX_BATCH)" % (n, self.max_batch))
        req = ServeRequest(arrays, n, deadline)
        with self._cv:
            if self._stop or self._draining:
                raise DrainingError("model %r is draining" % self.name)
            if self._admission_gate is not None:
                err = self._admission_gate(len(self._queue))
                if err is not None:
                    self._m_rej_shed.inc()
                    raise err
            if len(self._queue) >= self.queue_depth:
                self._m_rej_full.inc()
                raise QueueFullError(
                    "queue for model %r is full (%d requests; "
                    "MXTPU_SERVE_QUEUE_DEPTH)" % (self.name, self.queue_depth))
            self._queue.append(req)
            self._m_queue.set(len(self._queue))
            self._m_reqs.inc()
            self._cv.notify()
        return req

    def pending(self):
        """Queued + in-flight request count (drain progress)."""
        with self._cv:
            return len(self._queue) + len(self._inflight)

    # -- shutdown ----------------------------------------------------------
    def drain(self, timeout=None):
        """Stop admitting, let the worker finish everything queued, and wait
        up to ``timeout`` seconds (default `MXTPU_SERVE_DRAIN_TIMEOUT_MS` —
        a wedged model must not hang shutdown forever). Returns True when
        fully drained."""
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
        """Force-resolve every queued AND in-flight request (bounded-drain
        escape hatch: a wedged runner must not strand its waiters — they
        get a deterministic 503 instead of a connection reset when the
        process exits). Safe against late runner completion: first
        resolution wins. Returns how many requests were force-resolved."""
        if error is None:
            error = DrainingError(
                "model %r drain timed out; request force-completed"
                % self.name)
        with self._cv:
            victims = [r for r in self._queue] + \
                [r for r in self._inflight if not r.done()]
            self._queue.clear()
            self._inflight.clear()
            self._m_queue.set(0)
        for req in victims:
            req._resolve(error=error)
        return len(victims)

    def close(self, drain=True, timeout=None):
        """Drain (optionally) then stop the worker thread."""
        drained = self.drain(timeout) if drain else False
        with self._cv:
            self._stop = True
            self._draining = True
            self._cv.notify_all()
        self._worker.join(timeout=5.0)
        # anything still queued after a failed/skipped drain gets an answer
        self.abort_pending(DrainingError(
            "model %r shut down before this request ran" % self.name))
        # verdicts for a gone model are noise on /statusz
        _slo.unregister_model(self.name)
        return drained

    # -- the worker --------------------------------------------------------
    def _pop_live(self, max_n=None):
        """Pop the next request that is still live (expired ones are
        resolved 504 on the spot) AND fits within ``max_n`` examples — the
        fit check must be applied to the request actually popped, not the
        pre-expiry queue head. Returns None when the queue is empty or the
        next live request would overflow. Caller holds _cv."""
        now = time.monotonic()
        while self._queue:
            req = self._queue[0]
            if req.deadline is not None and now >= req.deadline:
                self._queue.popleft()
                self._m_queue.set(len(self._queue))
                self._expire(req, now)
                continue
            if max_n is not None and req.n > max_n:
                return None  # stays queued for the next batch
            self._queue.popleft()
            self._m_queue.set(len(self._queue))
            self._inflight.add(req)
            return req
        return None

    def _expire(self, req, now=None):
        """Resolve one request 504. In-flight accounting is the CALLER's
        job (close it under ``_cv`` before calling): the old ``locked=``
        parameter made this method's locking depend on caller-supplied
        control flow, which the lock-discipline/lock-order checkers
        rightly cannot prove safe — and neither could a reviewer."""
        if now is None:
            now = time.monotonic()
        self._m_rej_dead.inc()
        req._resolve(error=DeadlineExceededError(
            "deadline expired after %.0f ms in queue"
            % ((now - req._t_submit) * 1e3)))

    def _prune_expired(self, batch):
        """Drop (and 504) every already-expired request from an assembled
        batch — the batch may have aged in the coalescing window or a
        dispatcher queue since its members were popped live. Returns the
        still-live remainder. Spending executor time on an answer nobody is
        waiting for is exactly the work a degraded pool cannot afford."""
        now = time.monotonic()
        live, dead = [], []
        for req in batch:
            if req.deadline is not None and now >= req.deadline \
                    and not req.done():
                dead.append(req)
            elif not req.done():
                live.append(req)
        if dead:
            with self._cv:
                self._inflight.difference_update(dead)
            for req in dead:
                self._expire(req, now)
        return live

    def _loop(self):
        while True:
            batch = []
            total = 0
            with self._cv:
                while not self._queue:
                    if self._stop:
                        return
                    self._cv.wait(0.05)
                first = self._pop_live()
            if first is None:
                continue
            batch.append(first)
            total = first.n
            t_assembly = time.monotonic()
            close_at = t_assembly + self.max_delay_s
            # coalesce until the bucket ceiling or the delay window closes;
            # when draining, take whatever is queued without waiting
            while total < self.max_batch:
                with self._cv:
                    req = self._pop_live(self.max_batch - total)
                    if req is None:
                        if self._queue:
                            break  # live head would overflow: next batch's
                        if self._draining or self._stop:
                            break
                        remaining = close_at - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cv.wait(min(remaining, 0.05))
                        continue
                batch.append(req)
                total += req.n
            # assembly-time expiry: members can age out during the
            # coalescing window (or while queued behind a long batch) —
            # 504 them NOW instead of spending executor time on answers
            # nobody is waiting for
            batch = self._prune_expired(batch)
            total = sum(r.n for r in batch)
            if not batch:
                continue
            # the coalescing window, per traced request (retroactive span:
            # only the window's end knows the batch composition)
            assembly_s = time.monotonic() - t_assembly
            assembly_wall = time.time() - assembly_s
            for req in batch:
                _tracing.emit_span("serve.assembly", assembly_wall,
                                   assembly_s, req.trace, component="router",
                                   attrs={"batch": len(batch), "n": total})
            try:
                if self._dispatcher is not None:
                    self._dispatcher(batch, total)
                else:
                    self._dispatch(batch, total)
            except Exception as e:  # the lone worker must NEVER die
                telemetry.record_event("serve_batcher_error",
                                       model=self.name, error=repr(e))
                self.fail_batch(batch, ServingError(
                    "batcher for %r failed: %r" % (self.name, e)))

    # -- batch resolution (shared by the inline path and pool dispatchers) -
    def resolve_batch(self, batch, outputs, bucket, total, compute_s):
        """Unpad `outputs` (leading dim == bucket), split them back per
        request, resolve every request, and close in-flight accounting.
        `batch` must be the exact request list the outputs were computed
        for (order preserved)."""
        now = time.monotonic()
        t_unpad = time.perf_counter()
        unpad_wall = time.time()
        outs = unpad_outputs(outputs, bucket - total)
        offset = 0
        splits = []
        for req in batch:
            req.bucket = bucket
            req.queue_seconds = max(0.0, now - compute_s - req._t_submit)
            req.compute_seconds = compute_s
            trace_id = req.trace.trace_id if req.trace is not None else None
            self._m_queue_s.observe(req.queue_seconds, exemplar=trace_id)
            self._m_request_s.observe(max(0.0, now - req._t_submit),
                                      exemplar=trace_id)
            # queue-phase span, start rebased to the request's submit time
            # (wall clock = now minus the monotonic elapsed)
            _tracing.emit_span(
                "serve.queue", unpad_wall - (now - req._t_submit),
                req.queue_seconds, req.trace, component="router")
            per_req = [o[offset:offset + req.n].copy() for o in outs]
            offset += req.n
            splits.append((req, per_req))
        unpad_s = time.perf_counter() - t_unpad
        for req, per_req in splits:
            _tracing.emit_span("serve.unpad", unpad_wall, unpad_s,
                               req.trace, component="router",
                               attrs={"bucket": bucket,
                                      "pad": bucket - total})
            req._resolve(outputs=per_req)
        with self._cv:
            self._inflight.difference_update(batch)
        self._m_examples.inc(total)
        self._m_batches.inc()
        self._m_batch_size.observe(total)
        if bucket:
            self._m_occupancy.observe(total / float(bucket))
        self._m_compute_s.observe(
            compute_s, exemplar=next(
                (r.trace.trace_id for r in batch
                 if r.trace is not None and r.trace.recorded), None))

    def fail_batch(self, batch, error, compute_s=None):
        """Resolve every request in `batch` with `error` and close
        accounting (already-resolved members are left alone). Failed
        batches still count toward the dispatch-volume metrics —
        batches/examples flatlining during an incident would read as "no
        traffic" on a dashboard, and compute burned on batches that then
        error must stay visible (occupancy is success-only: the bucket
        is not always known on the failure path)."""
        for req in batch:
            req._resolve(error=error)
        with self._cv:
            self._inflight.difference_update(batch)
        total = sum(r.n for r in batch)
        self._m_examples.inc(total)
        self._m_batches.inc()
        self._m_batch_size.observe(total)
        if compute_s is not None:
            self._m_compute_s.observe(compute_s)

    def requeue(self, batch):
        """Failover path: push a dead replica's in-flight batch back to the
        FRONT of the queue, EXACTLY ONCE per request (predict is
        idempotent, so one retry is safe; unbounded retries could double
        work without bound). Expired members are 504ed; members that
        already failed over once get a retryable 503 instead of a second
        ride. Returns the number of requests actually requeued."""
        now = time.monotonic()
        requeued = 0
        # requests requeued by THIS call — `req.retried` alone cannot tell
        # "just went back on the queue" from "already used its one retry
        # on an earlier failover" (the latter must get the 503 below, not
        # be skipped unresolved)
        taken = set()
        with self._cv:
            self._inflight.difference_update(batch)
            accept = not (self._stop or self._draining)
            for req in reversed(batch):
                if req.done():
                    continue
                if req.deadline is not None and now >= req.deadline:
                    continue  # expired: resolved below, outside the lock
                if req.retried or not accept:
                    continue
                req.retried = True
                taken.add(req)
                self._queue.appendleft(req)
                requeued += 1
            self._m_queue.set(len(self._queue))
            if requeued:
                self._cv.notify()
        for req in batch:
            if req in taken or req.done():
                continue
            if req.deadline is not None and now >= req.deadline:
                self._expire(req, now)
            elif req.retried:
                # second replica death under the same request: answer a
                # retryable 503 rather than loop the failover
                req._resolve(error=OverloadedError(
                    "request already failed over once on model %r"
                    % self.name))
            else:
                # never retried, but the batcher stopped accepting: the
                # 503 is about draining, not a failover the request never
                # had
                req._resolve(error=OverloadedError(
                    "model %r is draining; in-flight request not retried"
                    % self.name))
        return requeued

    def _dispatch(self, batch, total):
        t0 = time.monotonic()
        try:
            padded, bucket = pad_batch(batch, total, self.buckets)
            t_run = time.monotonic()
            run_wall = time.time()
            outs = self._runner(padded, bucket, total)
            compute_s = time.monotonic() - t_run
            for req in batch:
                _tracing.emit_span("serve.compute", run_wall, compute_s,
                                   req.trace, component="worker",
                                   attrs={"bucket": bucket, "n": total})
            self.resolve_batch(batch, outs, bucket, total,
                               time.monotonic() - t0)
        except ServingError as e:
            self.fail_batch(batch, e, compute_s=time.monotonic() - t0)
        except Exception as e:  # a model failure answers 500, never hangs
            err = ServingError("model %r failed: %r" % (self.name, e))
            err.__cause__ = e
            telemetry.record_event("serve_batch_error", model=self.name,
                                   error=repr(e))
            self.fail_batch(batch, err, compute_s=time.monotonic() - t0)
