"""Versioned multi-model registry for the serving subsystem.

A repository maps ``name/version`` to a `ServedModel`: a loaded inference
artifact plus its per-bucket executables and its own `DynamicBatcher`
(one worker thread per served model — the executor is only ever driven
single-threaded; any number of HTTP threads block on their request event).

Two artifact kinds load (the same two the deployment layer produces):

  * ``prefix`` -> ``prefix-symbol.json`` + ``prefix-%04d.params``
    (`HybridBlock.export` / `model.save_checkpoint`): a live `Predictor`
    is bound per padding bucket, every clone SHARING the prototype's
    device weight buffers (the `predict._clone_with` mechanism — the
    reference's MXPredCreateMultiThread semantics) so N buckets cost one
    copy of the weights plus N small IO buffers.
  * ``*.mxc`` / ``MXTPUAOT1`` blobs (`Predictor.export_compiled`): a
    `CompiledPredictor` whose geometry is frozen at build — its frozen
    batch size is the single padding bucket.

Loading WARMS every bucket (one forward of zeros per bucket) before the
model is published, so the executable cache is fully populated and steady-
state traffic never sees a compile. Unloading drains the model's queue
and in-flight work before dropping it (hot load/unload).
"""
from __future__ import annotations

import os
import re
import threading
import time

import numpy as _np

from .. import compile as _compile
from .. import env as _env
from .. import telemetry
from ..base import MXNetError
from ..parallel import resilience as _resilience
from ..telemetry import memory as _tm_memory
from .batcher import (DynamicBatcher, MemoryBudgetError,
                      ModelUnavailableError, drain_timeout_s,
                      power_of_two_buckets)

__all__ = ["ServedModel", "ModelRepository", "build_runner"]


def _resolved_max_batch(max_batch):
    """The max_batch that actually shapes the bucket set (env default
    applied) — warmup-manifest ids key on THIS value on both the
    repository and replica-worker sides, so a geometry change cleanly
    partitions manifests (docs/compile_cache.md)."""
    if max_batch is not None:
        return int(max_batch)
    return _env.get("MXTPU_SERVE_MAX_BATCH")


class ServedModel:
    """One ``name/version``: bucketed forward + dynamic batcher.

    ``runner(batch_arrays, bucket, n) -> [numpy outputs]`` owns the actual
    model; the constructors below build it from deployment artifacts, and
    tests may inject a stub (the repository only needs this interface).
    """

    def __init__(self, name, version, runner, buckets, example_shapes,
                 input_dtypes=None, meta=None, max_delay_ms=None,
                 queue_depth=None, pool=None):
        self.name = str(name)
        self.version = int(version)
        self.example_shapes = {k: tuple(v) for k, v in example_shapes.items()}
        self.input_dtypes = {k: _np.dtype(input_dtypes[k])
                             if input_dtypes and k in input_dtypes
                             else _np.dtype(_np.float32)
                             for k in self.example_shapes}
        self.meta = dict(meta or {})
        self.loaded_at = time.time()
        # autoscaling policy (docs/serving.md §Autoscaling): None defers
        # to the MXTPU_AUTOSCALE_{MIN,MAX}_REPLICAS defaults; `pinned`
        # exempts the model from budget-pressure eviction
        self.min_replicas = None
        self.max_replicas = None
        self.pinned = False
        self.warmed = False
        self.warm_seconds = None
        self.manifest_id = None     # warmup-manifest id (artifact models)
        self.compile_digests = []   # executable-cache digests the warm
        #                             filled/loaded (docs/compile_cache.md)
        self.bucket_flops = {}  # bucket -> FLOPs per batch (warm-time
        #                         cost analysis; {} when unavailable)
        self.bucket_memory = {}  # bucket -> memory_analysis figures of
        #                          the executables the bucket warm
        #                          filled/loaded ({} when unavailable)
        self.memory_bytes = None  # model device footprint from the
        #                           figures (docs/observability.md §Memory)
        self._runner = runner
        self._pool = pool
        if pool is not None:
            # resilient mode: batches are dispatched to the replica pool's
            # worker processes; admission runs through the pool's
            # load-shedding gate (docs/serving.md §resilience)
            self._batcher = DynamicBatcher(
                None, buckets, max_delay_ms=max_delay_ms,
                queue_depth=queue_depth,
                name="%s/%d" % (self.name, self.version),
                dispatcher=pool.dispatch_batch,
                admission_gate=pool.admission_gate)
            pool.bind(self._batcher)
        else:
            self._batcher = DynamicBatcher(
                runner, buckets, max_delay_ms=max_delay_ms,
                queue_depth=queue_depth,
                name="%s/%d" % (self.name, self.version))

    # -- construction from artifacts --------------------------------------
    @staticmethod
    def pooled(name, version, path, replicas, input_shapes=None,
               input_dtypes=None, max_batch=None, max_delay_ms=None,
               queue_depth=None, heartbeat_ms=None, backoff_ms=None,
               extra_env=None, spawn_timeout_s=120.0, teardown_grace=None,
               worker_args=None, wedge_timeout_ms=None):
        """Serve an artifact through a supervised `ReplicaPool` of
        ``replicas`` worker processes (docs/serving.md §resilience).
        ``worker_args`` overrides the artifact argv entirely (tests pass
        ``--stub`` specs). The pool spawns, loads and warms every replica
        BEFORE the model is returned — a half-warm pool never publishes."""
        from .replica_pool import ReplicaPool

        if worker_args is None:
            if path is None:
                raise MXNetError("pooled() needs an artifact path (or "
                                 "explicit worker_args)")
            worker_args = ["--artifact", os.fspath(path)]
            for iname, dims in (input_shapes or {}).items():
                spec = "%s=%s" % (iname, "x".join(str(d) for d in dims))
                if input_dtypes and iname in input_dtypes:
                    spec += ":%s" % input_dtypes[iname]
                worker_args += ["--input", spec]
            if max_batch is not None:
                worker_args += ["--max-batch", str(max_batch)]
        pool = ReplicaPool("%s/%d" % (name, int(version)), worker_args,
                           replicas, heartbeat_ms=heartbeat_ms,
                           backoff_ms=backoff_ms, extra_env=extra_env,
                           spawn_timeout_s=spawn_timeout_s,
                           teardown_grace=teardown_grace,
                           wedge_timeout_ms=wedge_timeout_ms)
        try:
            info = pool.wait_ready(spawn_timeout_s)
        except Exception:
            pool.close()
            raise
        model = ServedModel(
            name, version, None, info["buckets"], info["example_shapes"],
            input_dtypes=info.get("input_dtypes"),
            meta={"artifact": "pooled", "path": None if path is None
                  else os.fspath(path), "replicas": int(replicas)},
            max_delay_ms=max_delay_ms, queue_depth=queue_depth, pool=pool)
        # every replica warmed its buckets before reporting ready
        model.warmed = True
        model.warm_seconds = info.get("warm_seconds")
        if info.get("bucket_flops"):
            model.set_bucket_flops(info["bucket_flops"])
        if info.get("bucket_memory"):
            # figures computed replica-side during its warm (ready frame)
            model.set_bucket_memory(info["bucket_memory"])
        # the replica's executable key-set (it wrote the warmup manifest
        # worker-side, next to the artifacts it filled/loaded)
        if path is not None:
            model.manifest_id = _compile.model_manifest_id(
                path, _resolved_max_batch(max_batch), input_shapes)
        model.compile_digests = sorted(info.get("compile_digests") or [])
        return model

    @staticmethod
    def from_path(name, version, path, input_shapes=None, input_dtypes=None,
                  ctx=None, max_batch=None, max_delay_ms=None,
                  queue_depth=None):
        """Load a deployment artifact: a ``*.mxc``/``MXTPUAOT1`` compiled
        blob, or an export ``prefix`` (with ``input_shapes`` = per-example
        shapes, batch dim EXCLUDED)."""
        kind, parts = _resolve_artifact(path)
        if kind == "compiled":
            model = ServedModel._from_compiled(
                name, version, parts, max_delay_ms=max_delay_ms,
                queue_depth=queue_depth)
        else:
            symbol_file, param_file = parts
            model = ServedModel._from_symbol(
                name, version, symbol_file, param_file,
                input_shapes=input_shapes, input_dtypes=input_dtypes,
                ctx=ctx, max_batch=max_batch, max_delay_ms=max_delay_ms,
                queue_depth=queue_depth)
        # ties this artifact + geometry to its warmup manifest (the SAME
        # id a replica worker derives from its argv — manifest.py)
        model.manifest_id = _compile.model_manifest_id(
            path, _resolved_max_batch(max_batch), input_shapes)
        return model

    @staticmethod
    def _from_symbol(name, version, symbol_file, param_file, input_shapes,
                     input_dtypes=None, ctx=None, max_batch=None,
                     max_delay_ms=None, queue_depth=None):
        runner, buckets, example_shapes, dtypes, meta = _symbol_runner(
            symbol_file, param_file, input_shapes,
            input_dtypes=input_dtypes, ctx=ctx, max_batch=max_batch)
        return ServedModel(name, version, runner, buckets, example_shapes,
                           input_dtypes=dtypes, meta=meta,
                           max_delay_ms=max_delay_ms,
                           queue_depth=queue_depth)

    @staticmethod
    def _from_compiled(name, version, path, max_delay_ms=None,
                       queue_depth=None):
        runner, buckets, example_shapes, dtypes, meta = \
            _compiled_runner(path)
        return ServedModel(name, version, runner, buckets, example_shapes,
                           input_dtypes=dtypes, meta=meta,
                           max_delay_ms=max_delay_ms,
                           queue_depth=queue_depth)

    # -- serving surface ---------------------------------------------------
    @property
    def pool(self):
        """The model's `ReplicaPool` (None when served in-process)."""
        return self._pool

    @property
    def buckets(self):
        return list(self._batcher.buckets)

    @property
    def max_batch(self):
        return self._batcher.max_batch

    def validate(self, arrays):
        """Check names/shapes/dtypes against the model signature; returns
        the (cast) arrays. Raises MXNetError on mismatch (HTTP 400)."""
        want = set(self.example_shapes)
        got = set(arrays)
        if want != got:
            raise MXNetError("inputs %s != model inputs %s"
                             % (sorted(got), sorted(want)))
        out = {}
        for k, a in arrays.items():
            a = _np.asarray(a, dtype=self.input_dtypes[k])
            if tuple(a.shape[1:]) != self.example_shapes[k]:
                raise MXNetError(
                    "input %r per-example shape %s != declared %s"
                    % (k, tuple(a.shape[1:]), self.example_shapes[k]))
            out[k] = a
        return out

    def predict(self, arrays, timeout_ms=None):
        """Validate, admit, and wait: returns the list of per-request
        output arrays. Raises QueueFullError / DeadlineExceededError /
        DrainingError per the admission-control contract."""
        arrays = self.validate(arrays)
        if timeout_ms is None:
            timeout_ms = _env.get("MXTPU_SERVE_TIMEOUT_MS")
        deadline = None
        if timeout_ms and timeout_ms > 0:
            deadline = time.monotonic() + float(timeout_ms) / 1e3
        req = self._batcher.submit(arrays, deadline)
        timeout = None if deadline is None \
            else max(0.0, deadline - time.monotonic())
        return req.wait(timeout)

    def record_compile_entries(self, entries):
        """Record the executable key-set the load+warm filled or loaded
        from the persistent tier (``(ExecutableKey, digest)`` pairs from
        `compile.keys_since`), and publish it as this model's warmup
        manifest so a future cold start prefetches instead of compiling
        (docs/compile_cache.md)."""
        self.compile_digests = sorted({d for _, d in entries})
        directory = _compile.cache_dir()
        if directory and self.manifest_id and entries:
            _compile.write_manifest(directory, self.manifest_id, entries,
                                    model=self.name, version=self.version)

    def set_bucket_flops(self, bucket_flops):
        """Publish per-bucket FLOP cost (from warm-time cost analysis) as
        ``mxtpu_serve_bucket_flops`` gauges — the serving arm of the
        automatic FLOP accounting (docs/observability.md)."""
        self.bucket_flops = {int(b): f for b, f in bucket_flops.items() if f}
        for b, f in self.bucket_flops.items():
            telemetry.gauge("mxtpu_serve_bucket_flops",
                            {"model": "%s/%d" % (self.name, self.version),
                             "bucket": str(b)}).set(f)

    @property
    def resident_copies(self):
        """How many full copies of the model are resident: each replica
        worker process warms its own weights + executables, so a pooled
        model costs N× its single-copy footprint. Read LIVE from the
        pool so budget math tracks autoscaler resizes, not the size the
        model loaded with."""
        if self._pool is not None:
            return max(1, int(self._pool.size))
        try:
            return max(1, int(self.meta.get("replicas") or 1))
        except (TypeError, ValueError):
            return 1

    @property
    def effective_memory_bytes(self):
        """Single-copy footprint × resident copies — what the
        ``MXTPU_SERVE_MEMORY_BUDGET`` admission check charges."""
        if not self.memory_bytes:
            return None
        return self.memory_bytes * self.resident_copies

    def set_bucket_memory(self, bucket_memory):
        """Record per-bucket memory figures (summed `memory_analysis()`
        of the executables each bucket warm filled or loaded from the
        persistent tier), derive the model's single-copy device
        footprint, and publish the EFFECTIVE (× replicas) figure as
        ``mxtpu_serve_model_memory_bytes`` — the number the
        ``MXTPU_SERVE_MEMORY_BUDGET`` admission check enforces and the
        answer to "how many replicas of this model fit on a chip"."""
        self.bucket_memory = {int(b): dict(f)
                              for b, f in bucket_memory.items() if f}
        self.memory_bytes = _tm_memory.model_footprint(self.bucket_memory)
        if self.effective_memory_bytes:
            telemetry.gauge("mxtpu_serve_model_memory_bytes",
                            {"model": "%s/%d" % (self.name, self.version)}
                            ).set(self.effective_memory_bytes)

    def warm(self):
        """One zeros-forward per bucket: populates the executable cache so
        steady-state traffic never compiles, and — with automatic FLOP
        accounting on — prices each bucket's executable from the compile's
        cost analysis. Emits one ``serve_bucket_warm`` event per bucket."""
        from ..telemetry import flops as _flops

        if self._pool is not None:
            # pooled models warm replica-side before each replica reports
            # ready (supervisor.worker_main) — nothing to do here
            self.warmed = True
            return self.warm_seconds
        t_all = time.monotonic()
        bucket_flops = {}
        bucket_memory = {}
        for b in self._batcher.buckets:
            zeros = {k: _np.zeros((b,) + s, dtype=self.input_dtypes[k])
                     for k, s in self.example_shapes.items()}
            t0 = time.monotonic()
            f0 = _flops.total()
            m0 = _tm_memory.recorded_mark()
            _compile.begin_touch_log()
            try:
                self._runner(zeros, b, b)
            finally:
                touched = _compile.end_touch_log()
            bucket_flops[b] = _flops.total() - f0
            # memory figures of the executables THIS bucket's warm filled,
            # deserialized (zero-compile cold starts read them from the
            # artifact headers) or merely TOUCHED as memory-tier hits (the
            # reload path) — docs/observability.md §Memory
            bucket_memory[b] = _tm_memory.bucket_figures(
                touched, _tm_memory.recorded_since(m0))
            telemetry.record_event(
                "serve_bucket_warm", model=self.name, version=self.version,
                bucket=b, seconds=round(time.monotonic() - t0, 4),
                flops=bucket_flops[b] or None,
                memory_bytes=_tm_memory.footprint_bytes(bucket_memory[b])
                or None)
        self.set_bucket_flops(bucket_flops)
        self.set_bucket_memory(bucket_memory)
        self.warm_seconds = time.monotonic() - t_all
        self.warmed = True
        return self.warm_seconds

    def pending(self):
        return self._batcher.pending()

    def drain(self, timeout=None):
        return self._batcher.drain(timeout)

    def abort_pending(self, error=None):
        """Force-complete every queued + in-flight request (bounded-drain
        escape hatch); returns how many were force-resolved."""
        return self._batcher.abort_pending(error)

    def close(self, drain=True, timeout=None):
        drained = self._batcher.close(drain=drain, timeout=timeout)
        if self._pool is not None:
            self._pool.close()
        return drained

    def describe(self):
        out = {
            "name": self.name,
            "version": self.version,
            "buckets": self.buckets,
            "max_batch": self.max_batch,
            "inputs": {k: {"shape": list(s),
                           "dtype": self.input_dtypes[k].name}
                       for k, s in self.example_shapes.items()},
            "warmed": self.warmed,
            "warm_seconds": self.warm_seconds,
            "pending": self.pending(),
            "loaded_at": self.loaded_at,
            "meta": self.meta,
            "compile": {"manifest": self.manifest_id,
                        "digests": list(self.compile_digests)},
            "memory": {"total_bytes": self.memory_bytes,
                       "copies": self.resident_copies,
                       "effective_bytes": self.effective_memory_bytes,
                       "per_bucket": {str(b): f for b, f in
                                      sorted(self.bucket_memory.items())}},
        }
        if self._pool is not None:
            out["pool"] = self._pool.describe()
        return out


# ---------------------------------------------------------------------------
# artifact loading — shared by ServedModel (in-process) and the replica
# worker (mxnet_tpu/serving/supervisor.py), which needs a bucketed runner
# WITHOUT a batcher attached
# ---------------------------------------------------------------------------

def build_runner(path, input_shapes=None, input_dtypes=None, ctx=None,
                 max_batch=None):
    """Load a deployment artifact into a bucketed ``runner(arrays, bucket,
    n) -> [numpy outputs]``. Returns ``(runner, buckets, example_shapes,
    input_dtypes, meta)``."""
    kind, parts = _resolve_artifact(path)
    if kind == "compiled":
        return _compiled_runner(parts)
    symbol_file, param_file = parts
    return _symbol_runner(symbol_file, param_file, input_shapes,
                          input_dtypes=input_dtypes, ctx=ctx,
                          max_batch=max_batch)


def _symbol_runner(symbol_file, param_file, input_shapes, input_dtypes=None,
                   ctx=None, max_batch=None):
    from ..predict import Predictor, _clone_with

    if not input_shapes:
        raise MXNetError(
            "symbol/params models need input_shapes (per-example, "
            "batch dim excluded), e.g. {'data': (8,)}")
    example_shapes = {k: tuple(v) for k, v in input_shapes.items()}
    if max_batch is None:
        max_batch = _env.get("MXTPU_SERVE_MAX_BATCH")
    buckets = power_of_two_buckets(max_batch)

    def shapes_at(b):
        return {k: (b,) + s for k, s in example_shapes.items()}

    # one Predictor per bucket, all sharing the prototype's device
    # weight buffers — N buckets cost one weight copy + N IO buffers
    proto = Predictor(symbol_file, param_file, ctx=ctx,
                      input_shapes=shapes_at(buckets[-1]),
                      input_dtypes=input_dtypes)
    by_bucket = {buckets[-1]: proto}
    for b in buckets[:-1]:
        by_bucket[b] = _clone_with(proto, shapes_at(b), shared=proto)
    num_outputs = proto.num_outputs

    def runner(arrays, bucket, n):
        pred = by_bucket[bucket]
        pred.forward(**arrays)
        return [pred.get_output(i).asnumpy() for i in range(num_outputs)]

    meta = {"artifact": "symbol", "symbol_file": str(symbol_file),
            "param_file": str(param_file)}
    return runner, buckets, example_shapes, input_dtypes, meta


def _compiled_runner(path):
    from ..predict import CompiledPredictor

    comp = CompiledPredictor.load(path)
    shapes = comp._input_shapes
    batches = {s[0] for s in shapes.values() if s}
    if len(batches) != 1:
        raise MXNetError(
            "compiled artifact has ambiguous batch dim across inputs: "
            "%s" % shapes)
    frozen = batches.pop()
    example_shapes = {k: tuple(s[1:]) for k, s in shapes.items()}
    dtypes = {k: comp._input_dtypes.get(k, _np.dtype(_np.float32))
              for k in shapes}

    def runner(arrays, bucket, n):
        comp.forward(**arrays)
        return [comp.get_output(i).asnumpy()
                for i in range(comp.num_outputs)]

    # geometry is frozen at build (TensorRT-engine semantics): the
    # frozen batch is the one and only padding bucket
    meta = {"artifact": "compiled", "path": str(path),
            "platforms": list(comp.platforms)}
    return runner, [frozen], example_shapes, dtypes, meta


# ---------------------------------------------------------------------------
# artifact resolution
# ---------------------------------------------------------------------------

_PARAMS_RE = re.compile(r"-(\d{4})\.params$")


def _resolve_artifact(path):
    """Classify ``path``: ('compiled', file) for .mxc/MXTPUAOT blobs,
    ('symbol', (symbol_json, params)) for an export prefix."""
    from ..predict import _MXC_MAGIC

    path = os.fspath(path)
    if os.path.isfile(path):
        with open(path, "rb") as f:
            magic = f.read(len(_MXC_MAGIC))
        if magic == _MXC_MAGIC:
            return "compiled", path
        if path.endswith("-symbol.json"):
            path = path[:-len("-symbol.json")]  # accept the json itself
        else:
            raise MXNetError(
                "%r is neither a compiled (.mxc) artifact nor a "
                "*-symbol.json / export prefix" % path)
    symbol_file = path + "-symbol.json"
    if not os.path.exists(symbol_file):
        raise MXNetError("no artifact at %r (expected %s or a compiled "
                         ".mxc file)" % (path, symbol_file))
    directory, base = os.path.split(path)
    candidates = []
    for fn in os.listdir(directory or "."):
        if fn.startswith(base + "-"):
            m = _PARAMS_RE.search(fn)
            if m and fn == "%s-%s.params" % (base, m.group(1)):
                candidates.append((int(m.group(1)), fn))
    if not candidates:
        raise MXNetError("no %s-NNNN.params next to %s" % (base, symbol_file))
    _, newest = max(candidates)
    return "symbol", (symbol_file, os.path.join(directory, newest))


# ---------------------------------------------------------------------------
# the repository
# ---------------------------------------------------------------------------

class ModelRepository:
    """name/version -> ServedModel, with hot load/unload.

    Loading warms before publishing (a half-warm model never serves);
    unloading marks the version draining, waits for queued + in-flight
    work, then drops it. `get` resolves ``version=None`` to the highest
    published version.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._models = {}   # name -> {version: ServedModel}
        self._loading = set()  # (name, version) reservations mid-load
        self._m_loaded = telemetry.gauge("mxtpu_serve_models_loaded")

    def load(self, name, path, version=None, input_shapes=None,
             input_dtypes=None, ctx=None, max_batch=None, max_delay_ms=None,
             queue_depth=None, warm=True, replicas=0, generate=False,
             generate_opts=None, min_replicas=None, max_replicas=None,
             pinned=False, **pool_kwargs):
        """Load an artifact as ``name/version`` (auto-increment when
        ``version`` is None) and publish it after warmup. The version is
        RESERVED for the whole load, so two concurrent loads of the same
        name never collide after both paid bind+warm; a failed load tears
        its half-built model (and batcher thread) down.

        ``replicas`` > 0 serves the model through a supervised replica
        pool (`ServedModel.pooled`; ``pool_kwargs`` — heartbeat_ms,
        backoff_ms, extra_env, spawn_timeout_s, teardown_grace — pass
        through) instead of in-process.

        ``generate=True`` loads ``path`` as a generation LM artifact
        (`generate.save_lm` prefix) served through the continuous-
        batching decode scheduler instead of the DynamicBatcher
        (docs/serving.md §Generation; ``generate_opts`` forwards KV/
        bucket geometry to `TransformerLMEngine`). The KV page pool is
        part of the model footprint, so the memory-budget admission in
        `add` 507s a load whose pages cannot fit.

        ``min_replicas`` / ``max_replicas`` bound the autoscaler for
        this model (None = the ``MXTPU_AUTOSCALE_{MIN,MAX}_REPLICAS``
        defaults); ``pinned=True`` exempts it from budget-pressure
        eviction. A load that would overflow the memory budget first
        tries to reclaim residency (shrink cold pools, evict
        idle-beyond-TTL unpinned models) before 507ing
        (docs/serving.md §Autoscaling)."""
        with self._lock:
            have = self._models.get(name, {})
            reserved = [v for (n, v) in self._loading if n == name]
            if version is None:
                version = max(list(have) + reserved, default=0) + 1
            version = int(version)
            if version in have or (name, version) in self._loading:
                raise MXNetError("model %s/%d is already loaded"
                                 % (name, version))
            self._loading.add((name, version))
        try:
            if generate:
                from .generate import ServedLM

                # predict-only knobs must not be silently ignored: a
                # caller passing them believes they took effect
                if input_shapes or input_dtypes or ctx is not None \
                        or max_delay_ms is not None or not warm:
                    raise MXNetError(
                        "generate=True loads take geometry through "
                        "generate_opts (and always warm); input_shapes/"
                        "input_dtypes/ctx/max_delay_ms/warm=False do "
                        "not apply")
                opts = dict(generate_opts or {})
                if max_batch is not None:
                    opts.setdefault("max_batch", max_batch)
                model = ServedLM.load(
                    name, version, path, replicas=int(replicas or 0),
                    queue_depth=queue_depth, pool_kwargs=pool_kwargs,
                    **opts)
                model.min_replicas = min_replicas
                model.max_replicas = max_replicas
                model.pinned = bool(pinned)
                try:
                    return self._add_with_reclaim(model)
                except Exception:
                    model.close(drain=False, timeout=0)
                    raise
            if replicas and replicas > 0:
                model = ServedModel.pooled(
                    name, version, path, replicas,
                    input_shapes=input_shapes, input_dtypes=input_dtypes,
                    max_batch=max_batch, max_delay_ms=max_delay_ms,
                    queue_depth=queue_depth, **pool_kwargs)
            else:
                # warmup-manifest prefetch BEFORE binding: with the
                # persistent tier armed and a previous publish of this
                # artifact, every executable deserializes instead of
                # compiling (cold start, warm cache — docs/compile_cache.md)
                _compile.prefetch(_compile.model_manifest_id(
                    path, _resolved_max_batch(max_batch), input_shapes))
                cursor = _compile.mark()
                model = ServedModel.from_path(
                    name, version, path, input_shapes=input_shapes,
                    input_dtypes=input_dtypes, ctx=ctx, max_batch=max_batch,
                    max_delay_ms=max_delay_ms, queue_depth=queue_depth)
            try:
                if warm:
                    model.warm()
                if model.pool is None:
                    model.record_compile_entries(_compile.keys_since(cursor))
                    # drop staged prefetch entries the warm never claimed
                    # (stale manifest rows must not stay pinned)
                    _compile.clear_staged()
                model.min_replicas = min_replicas
                model.max_replicas = max_replicas
                model.pinned = bool(pinned)
                # memory-budget admission happens inside add(), under the
                # repository lock; a short load first reclaims cold
                # residency (shrink/evict) before the 507 stands
                return self._add_with_reclaim(model)
            except Exception:
                model.close(drain=False, timeout=0)  # no thread/weight leak
                raise
        finally:
            with self._lock:
                self._loading.discard((name, version))

    def _check_memory_budget_locked(self, model):
        """The ``MXTPU_SERVE_MEMORY_BUDGET`` admission check, evaluated
        UNDER the repository lock so two concurrent loads cannot both
        pass against the same headroom: already-published models'
        footprints plus this one must fit the budget. Returns the
        over-budget message for warn-only mode, raises `MemoryBudgetError`
        (HTTP 507) otherwise; unknown footprints (no figures recorded —
        accounting off, or a backend without memory_analysis) never
        block a load.

        The rejection carries a full footprint breakdown — requested
        bytes, every resident model's ``effective_memory_bytes``, the
        budget, headroom and shortfall — in the message AND a
        machine-readable ``details`` dict the HTTP 507 body ships, so an
        operator can see WHAT to evict, not just that nothing fit."""
        limit, warn_only = _tm_memory.serve_memory_budget()
        needed = model.effective_memory_bytes  # N replicas = N copies
        if not limit or not needed:
            return None
        resident = 0
        resident_models = []
        for vs in self._models.values():
            for m in vs.values():
                eff = m.effective_memory_bytes or 0
                resident += eff
                resident_models.append({
                    "model": "%s/%d" % (m.name, m.version),
                    "effective_bytes": eff or None,
                    "copies": m.resident_copies,
                    "pinned": bool(getattr(m, "pinned", False)),
                })
        total = resident + needed
        if total <= limit:
            return None
        telemetry.record_event(
            "serve_memory_budget", model=model.name, version=model.version,
            footprint_bytes=needed, copies=model.resident_copies,
            resident_bytes=resident, budget_bytes=limit,
            action="warn" if warn_only else "reject")
        details = {
            "requested_bytes": needed,
            "per_copy_bytes": model.memory_bytes,
            "copies": model.resident_copies,
            "budget_bytes": limit,
            "resident_bytes": resident,
            "headroom_bytes": max(0, limit - resident),
            "shortfall_bytes": total - limit,
            "resident_models": resident_models,
        }
        msg = ("loading %s/%d needs %d bytes (%d bytes/copy x %d "
               "replica(s)); budget MXTPU_SERVE_MEMORY_BUDGET=%d has %d "
               "bytes headroom (%d resident), short %d bytes — resident: "
               "%s"
               % (model.name, model.version, needed, model.memory_bytes,
                  model.resident_copies, limit, details["headroom_bytes"],
                  resident, details["shortfall_bytes"],
                  ", ".join("%s=%s bytes (x%d%s)"
                            % (r["model"], r["effective_bytes"],
                               r["copies"],
                               ", pinned" if r["pinned"] else "")
                            for r in resident_models) or "nothing"))
        if not warn_only:
            raise MemoryBudgetError(msg, details=details)
        return msg

    def reclaim_memory(self, needed_bytes, exclude=None, reason="load",
                       now=None):
        """Budget-pressure bin-packing (docs/serving.md §Autoscaling):
        try to free at least ``needed_bytes`` of budgeted residency so a
        new load (or an autoscaler scale-up) fits, instead of answering
        a flat 507 while cold models pin HBM. Two phases, coldest first
        (LRU by the windowed request-rate staleness of each model's
        request counters):

          1. **shrink** idle pooled models toward their ``min_replicas``
             (`ReplicaPool.remove_replica(drain=True)` — zero request
             loss, each removal frees one ``memory_bytes`` copy);
          2. **evict** whole models that are unpinned and idle beyond
             ``MXTPU_AUTOSCALE_EVICT_TTL_S`` (a drained `unload`; the
             model's warmup manifest persists, so a future reload warms
             in seconds).

        Emits ``autoscale_down`` / ``autoscale_evict`` decisions. Never
        touches ``exclude`` (the model being admitted) and never runs
        under the repository lock — drains block. Returns bytes freed."""
        from . import autoscaler as _asc

        needed = int(needed_bytes or 0)
        if needed <= 0:
            return 0
        if now is None:
            now = time.time()
        idle_s = _env.get("MXTPU_AUTOSCALE_IDLE_S")
        ttl_s = _env.get("MXTPU_AUTOSCALE_EVICT_TTL_S")
        freed = 0
        candidates = [m for m in self.models()
                      if "%s/%d" % (m.name, m.version) != exclude]
        # coldest first: the model whose request counters have been
        # still the longest gives up residency first
        candidates.sort(key=lambda m: -_asc.idle_age_s(m, now))
        for m in candidates:
            if freed >= needed:
                break
            pool = getattr(m, "pool", None)
            per_copy = getattr(m, "memory_bytes", None)
            if pool is None or not per_copy:
                continue
            if _asc.idle_age_s(m, now) < idle_s:
                continue  # hot pools keep their replicas
            label = "%s/%d" % (m.name, m.version)
            floor = _asc.min_replicas(m)
            while pool.size > floor and freed < needed:
                try:
                    # floor re-checked atomically inside remove_replica:
                    # a concurrent autoscaler drain racing this loop
                    # must not shrink below the model's min_replicas
                    # (and the loser's MXNetError must not escape as a
                    # 400 where the caller expects the enriched 507)
                    replica = pool.remove_replica(drain=True, floor=floor)
                except MXNetError:
                    break  # lost the race: this pool is done shrinking
                freed += per_copy
                _asc.record_decision(
                    "down", label, reason="budget_pressure",
                    trigger=reason, replica=replica, size=pool.size,
                    freed_bytes=per_copy)
        for m in candidates:
            if freed >= needed:
                break
            if getattr(m, "pinned", False):
                continue
            eff = getattr(m, "effective_memory_bytes", None)
            if not eff:
                continue
            age = _asc.idle_age_s(m, now)
            if age < ttl_s:
                continue
            label = "%s/%d" % (m.name, m.version)
            try:
                self.unload(m.name, m.version)
            except ModelUnavailableError:
                continue  # a concurrent unload beat us to it
            freed += eff
            _asc.record_decision(
                "evict", label, reason=reason, idle_s=round(age, 3),
                freed_bytes=eff)
        return freed

    def _add_with_reclaim(self, model):
        """Publish, and on a budget rejection try to reclaim the
        shortfall (shrink cold pools / evict idle models) ONCE before
        retrying — the retry's admission check runs fresh under the
        lock, so concurrent loads stay consistent. A load that still
        cannot fit raises the enriched 507 and records an
        ``autoscale_blocked`` decision."""
        from . import autoscaler as _asc

        label = "%s/%d" % (model.name, model.version)
        try:
            return self.add(model)
        except MemoryBudgetError as e:
            details = getattr(e, "details", None) or {}
            shortfall = details.get("shortfall_bytes") \
                or model.effective_memory_bytes or 0
            freed = self.reclaim_memory(shortfall, exclude=label,
                                        reason="load")
            if freed > 0:
                try:
                    return self.add(model)
                except MemoryBudgetError as e2:
                    _asc.record_decision(
                        "blocked", label, reason="load_budget",
                        freed_bytes=freed,
                        shortfall_bytes=(getattr(e2, "details", None)
                                         or {}).get("shortfall_bytes"))
                    raise
            _asc.record_decision(
                "blocked", label, reason="load_budget", freed_bytes=0,
                shortfall_bytes=shortfall)
            raise

    def add(self, model):
        """Publish an already-built ServedModel (tests inject stubs here).
        The memory-budget admission check runs here, under the lock —
        a rejected model raises `MemoryBudgetError` and is never
        published (`load` tears it down)."""
        with self._lock:
            if model.version in self._models.get(model.name, {}):
                raise MXNetError("model %s/%d is already loaded"
                                 % (model.name, model.version))
            # raises BEFORE any mutation: a rejected name never appears
            # half-registered in names()/describe()
            over_budget = self._check_memory_budget_locked(model)
            self._models.setdefault(model.name, {})[model.version] = model
            self._m_loaded.set(sum(len(v) for v in self._models.values()))
        if over_budget:
            import logging

            logging.getLogger("mxnet_tpu.serving").warning(
                "%s (warn-only budget: publishing anyway)", over_budget)
        telemetry.record_event("serve_model_load", model=model.name,
                               version=model.version)
        # the start-up account's mark: what a reader of set-up sums ends
        # at the first one (docs/observability.md §Start-up)
        telemetry.goodput.ready(model="%s/%d" % (model.name, model.version))
        # chaos hook: a `load_surge@` MXTPU_FAULT_INJECT entry arms a
        # synthetic open-loop burst against this model's admission queue
        # (docs/fault_tolerance.md §5 — the autoscaler test vector)
        _resilience.maybe_inject_load_surge(model)
        return model

    def get(self, name, version=None):
        with self._lock:
            versions = self._models.get(name)
            if not versions:
                raise ModelUnavailableError("no model named %r" % (name,))
            if version is None:
                return versions[max(versions)]
            model = versions.get(int(version))
            if model is None:
                raise ModelUnavailableError(
                    "model %r has no version %s (have %s)"
                    % (name, version, sorted(versions)))
            return model

    def unload(self, name, version=None, timeout=None):
        """Drain and drop ``name/version`` (newest when None). Returns True
        when the drain completed within ``timeout``."""
        model = self.get(name, version)
        with self._lock:
            versions = self._models.get(name, {})
            versions.pop(model.version, None)
            if not versions:
                self._models.pop(name, None)
            self._m_loaded.set(sum(len(v) for v in self._models.values()))
        if timeout is None:
            timeout = drain_timeout_s()
        drained = model.close(drain=True, timeout=timeout)
        telemetry.record_event("serve_model_unload", model=model.name,
                               version=model.version, drained=drained)
        return drained

    def names(self):
        with self._lock:
            return sorted(self._models)

    def models(self):
        """Flat list of every published ServedModel."""
        with self._lock:
            return [m for vs in self._models.values()
                    for _, m in sorted(vs.items())]

    def describe(self):
        return {"models": [m.describe() for m in self.models()]}

    def pending(self):
        return sum(m.pending() for m in self.models())

    def drain_all(self, timeout=None):
        """Drain every model (graceful-shutdown path). Returns True when
        everything finished in time."""
        if timeout is None:
            timeout = drain_timeout_s()
        deadline = time.monotonic() + timeout
        ok = True
        for m in self.models():
            ok = m.drain(max(0.0, deadline - time.monotonic())) and ok
        return ok

    def abort_pending(self):
        """Force-complete every model's stranded requests (the bounded
        SIGTERM drain's escape hatch). Returns the total force-resolved."""
        return sum(m.abort_pending() for m in self.models())
