"""Gluon Trainer.

Reference: python/mxnet/gluon/trainer.py:27 — `step` :298 = allreduce grads
across device copies (:327, via kvstore) + optimizer update per copy (:359).

TPU-native: for the single-process multi-device case the grad reduction is a
kvstore('device') push/pull which lowers onto one XLA add over device buffers;
the *scaled* path is mxnet_tpu.parallel.DistributedTrainer, which keeps ONE
sharded copy of each parameter on the mesh and lets XLA insert the
all-reduces inside the compiled step (SURVEY §2.3 row 1).

Promotion (`sharded=True` + ``block=``/``loss=``, or fleet-wide via
``MXTPU_SHARDED_STEP`` when a block is supplied): the trainer internally
becomes a `parallel.ShardedTrainer` — forward + loss + backward + optimizer
update run as ONE compiled executable with donated param/state buffers, and
``step_batch(data, label)`` replaces the record/backward/step() triplet
(the loss scalar stays on device until the caller asks). Promoted
executables persist across processes (docs/sharded_training.md)."""
from __future__ import annotations

import time

from .. import env as _env
from ..base import MXNetError
from .. import optimizer as opt
from .. import telemetry
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None, sharded=None,
                 block=None, loss=None, mesh=None, sharding_rules=None,
                 amp_dtype=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be list/dict of Parameters")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError("invalid parameter %r" % (p,))
            self._params.append(p)
            self._param2idx[p.name] = i
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        # -- promotion to the fused sharded step -------------------------
        # sharded=None defers to MXTPU_SHARDED_STEP (armed fleet-wide by
        # tools/launch.py --sharded-step), which only promotes when the
        # caller supplied the block — op-by-op callers are untouched
        if sharded is None:
            sharded = block is not None and _env.get("MXTPU_SHARDED_STEP")
        self._sharded = None
        if sharded:
            if block is None:
                raise MXNetError(
                    "Trainer(sharded=True) needs block= (and usually "
                    "loss=): the fused step traces the block's forward — "
                    "see docs/sharded_training.md")
            from ..parallel.sharded_trainer import ShardedTrainer

            self._sharded = ShardedTrainer(
                block, optimizer, optimizer_params=optimizer_params,
                loss=loss, mesh=mesh, rules=sharding_rules,
                amp_dtype=amp_dtype)
            self._optimizer = self._sharded.optimizer
            self._scale = self._optimizer.rescale_grad
            self._kvstore_type = None
            self._kvstore = None
            self._kv_initialized = True
            self._update_on_kvstore = None
            self._step_count = 0
            return
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = self._optimizer.rescale_grad
        self._kvstore_type = kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._update_on_kvstore = update_on_kvstore
        # completed-update cursor: drives the fault-injection hook and is
        # saved/restored with the optimizer states so an auto-resumed run
        # keeps a monotonically correct step count (parallel/resilience.py)
        self._step_count = 0

    @property
    def sharded(self):
        """The promoted `parallel.ShardedTrainer` (None on the op-by-op
        path)."""
        return self._sharded

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx()
            assert contexts is None or contexts == ctx, \
                "All Parameters must be initialized on the same set of contexts"
            contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be empty when optimizer is an instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)
                          for _ in self._contexts]

    def _is_dist_kvstore(self):
        """Rank-spanning kvstore? (needs grad sync even with ONE local
        device — the reference's standard 1-GPU-per-worker mode,
        trainer.py:169 `'dist' in kvstore.type`)."""
        kt = self._kvstore_type
        if isinstance(kt, str):
            # every name kvstore.create() maps to _DistKVStore
            return "dist" in kt or kt in ("horovod", "tpu")
        return getattr(kt, "num_workers", 1) > 1

    def _init_kvstore(self):
        """Lazily create the kvstore (reference: trainer.py:169)."""
        self._kv_initialized = True
        if not self._kvstore_type or (len(self._contexts) < 2
                                      and not self._is_dist_kvstore()):
            self._kvstore = None
            return
        from .. import kvstore as kvs

        kv = kvs.create(self._kvstore_type) if isinstance(self._kvstore_type, str) \
            else self._kvstore_type
        self._kvstore = kv
        if self._compression_params:
            kv.set_gradient_compression(self._compression_params)
        dist = self._is_dist_kvstore()
        for i, param in enumerate(self._params):
            if param._data is not None:
                kv.init(i, param.list_data()[0])
                if dist:
                    # adopt the group-authoritative (rank 0) initial value
                    # so every rank trains the same replica
                    kv.pull(i, out=param.list_data())

    @property
    def learning_rate(self):
        return self._optimizer.lr_scheduler(self._optimizer.num_update) \
            if self._optimizer.lr_scheduler else self._optimizer.lr

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    @property
    def step_count(self):
        """Number of completed step() calls (survives save/load_states)."""
        if self._sharded is not None:
            return self._sharded._step_count
        return self._step_count

    def step_batch(self, data, label=None):
        """The promoted hot path: one fused forward+loss+backward+update
        over the batch, returning the (device-resident) scalar loss
        NDArray — no host sync happens unless the caller asks for one.
        Requires promotion (``sharded=True``/``MXTPU_SHARDED_STEP``)."""
        if self._sharded is None:
            raise MXNetError(
                "step_batch() needs a promoted trainer: construct with "
                "sharded=True, block= and loss= (docs/sharded_training.md)")
        return self._sharded.step(data, label)

    def prefetch(self, it, depth=None):
        """Wrap `it` in a mesh-aware `data.DevicePrefetcher` so step_batch
        consumes already-sharded device batches (promoted path only)."""
        if self._sharded is None:
            raise MXNetError(
                "prefetch() needs a promoted trainer: construct with "
                "sharded=True, block= and loss= (docs/sharded_training.md)")
        return self._sharded.prefetch(it, depth=depth)

    def sync_params(self):
        """Copy mesh-trained values back into the block's Parameters (the
        promoted path keeps ONE sharded copy per param; call this before
        save_parameters/export). No-op on the op-by-op path, where the
        Parameters themselves are the training copies."""
        if self._sharded is not None:
            self._sharded.sync_params()

    def step(self, batch_size, ignore_stale_grad=False):
        """Allreduce grads + update (reference: trainer.py:298)."""
        if self._sharded is not None:
            raise MXNetError(
                "this Trainer is promoted to the fused sharded step "
                "(sharded=True/MXTPU_SHARDED_STEP): the parameters live on "
                "the mesh and forward+backward+update run as one "
                "executable — drive it with step_batch(data, label) "
                "instead of record()/backward()/step() "
                "(docs/sharded_training.md)")
        t0 = time.perf_counter()
        telemetry.goodput.step_start(kind="train", t0=t0,
                                     step=self._step_count + 1)
        # distributed tracing: a sampled step records allreduce/optimizer
        # phase spans (no-op span when tracing is unarmed)
        with telemetry.tracing.root("train.step", component="train",
                                    attrs={"step": self._step_count + 1}):
            if not self._kv_initialized:
                self._init_kvstore()
            self._optimizer.rescale_grad = self._scale / batch_size
            with telemetry.tracing.span("train.allreduce"), \
                    telemetry.goodput.phase("collective"):
                self._allreduce_grads()
            telemetry.goodput.mark_launch()
            with telemetry.tracing.span("train.optimizer"), \
                    telemetry.goodput.phase("compute"):
                self._update(ignore_stale_grad)
            self._step_count += 1
            # always-on telemetry: step wall time, examples/sec, MFU (auto
            # cost-analysis FLOPs, or set_step_flops when declared) + the
            # flight-recorder/watchdog heartbeat
            telemetry.observe_step(time.perf_counter() - t0,
                                   examples=batch_size,
                                   step=self._step_count)
            telemetry.goodput.step_end(step=self._step_count)
        # step-boundary fault hook; the env guard keeps the hot path free
        # of even the import lookup when injection is unarmed
        if _env.is_set("MXTPU_FAULT_INJECT"):
            from ..parallel import resilience

            resilience.maybe_inject_fault(self._step_count)

    def allreduce_grads(self):
        if self._sharded is not None:
            return  # the fused step's psum already reduced (in-graph)
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if len(self._contexts) < 2 and self._kvstore is None:
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            grads = param.list_grad()
            if self._kvstore is not None:
                self._kvstore.push(i, grads)
                self._kvstore.pull(i, out=grads)
            else:
                total = grads[0]
                for g in grads[1:]:
                    total = total + g.as_in_context(total.context)
                for g in grads:
                    g._set_data(total.as_in_context(g.context)._data)

    def update(self, batch_size, ignore_stale_grad=False):
        if self._sharded is not None:
            raise MXNetError(
                "promoted Trainer: the optimizer update is fused into "
                "step_batch() — there is no separate update() phase")
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if not ignore_stale_grad:
                for data in param.list_data():
                    if not data._fresh_grad:
                        raise MXNetError(
                            "Gradient of Parameter `%s` on context %s has not been "
                            "updated by backward since last step. Set "
                            "ignore_stale_grad=True to suppress" % (param.name, data.context))
            for upd, arr, grad in zip(self._updaters, param.list_data(),
                                      param.list_grad()):
                if getattr(param, "_grad_stype", "default") == "row_sparse" \
                        and getattr(self._optimizer, "supports_sparse", False):
                    # tape grads are dense; cast to row_sparse so the
                    # optimizer takes the lazy row-update path (reference:
                    # parameter.py grad_stype + sparse optimizer kernels).
                    # Optimizers without a sparse kernel stay dense, like the
                    # reference's storage-fallback wrappers (common/exec_utils.h)
                    grad = grad.tostype("row_sparse")
                upd(i, grad, arr)
                arr._fresh_grad = False

    def save_states(self, fname):
        """reference: trainer.py:429 — extended with the step cursor so an
        auto-resumed run (parallel/resilience.py) continues the schedule,
        and written atomically (temp + fsync + rename) so a kill mid-save
        never truncates the states file."""
        import pickle

        from ..base import atomic_writer

        if self._sharded is not None:
            self._sharded.save_states(fname)
            return
        assert self._optimizer is not None
        blob = {"__mxtpu_trainer_states__": 1,
                "updater": self._updaters[0].get_states(dump_optimizer=True),
                "step_count": self._step_count}
        with atomic_writer(fname, "wb") as f:
            pickle.dump(blob, f)

    def _require_sharded(self, what):
        if self._sharded is None:
            raise MXNetError(
                "%s needs the promoted sharded trainer (sharded=True + "
                "block=, or MXTPU_SHARDED_STEP=1); op-by-op trainers "
                "checkpoint via save_states/load_states" % what)
        return self._sharded

    def save_sharded_checkpoint(self, manager, step=None, meta=None):
        """This rank's shard of an async sharded checkpoint
        (parallel.resilience.CheckpointManager.save_sharded_async);
        promoted trainers only."""
        return self._require_sharded("save_sharded_checkpoint").\
            save_sharded_checkpoint(manager, step=step, meta=meta)

    def emergency_sharded_checkpoint(self, manager, meta=None):
        """Solo synchronous preemption checkpoint (flushes the async
        writer first); promoted trainers only."""
        return self._require_sharded("emergency_sharded_checkpoint").\
            emergency_sharded_checkpoint(manager, meta=meta)

    def restore_sharded_checkpoint(self, manager, step=None):
        """Restore the newest sharded checkpoint onto the current mesh,
        resharding elastically when the topology changed; promoted
        trainers only. Returns the manifest header or None."""
        return self._require_sharded("restore_sharded_checkpoint").\
            restore_sharded_checkpoint(manager, step=step)

    def load_states(self, fname):
        """reference: trainer.py:458 (legacy raw updater blobs still load)."""
        import pickle

        if self._sharded is not None:
            self._sharded.load_states(fname)
            return
        with open(fname, "rb") as f:
            raw = f.read()
        states = raw
        try:
            blob = pickle.loads(raw)
        except Exception:
            blob = None
        if isinstance(blob, dict) and "__mxtpu_trainer_states__" in blob:
            states = blob["updater"]
            self._step_count = int(blob.get("step_count", 0))
        for updater in self._updaters:
            updater.set_states(states)
            updater.optimizer = self._optimizer
