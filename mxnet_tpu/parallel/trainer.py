"""DistributedTrainer — the scaled training path.

The reference scales by copying parameters per device and reducing grads
through a kvstore (gluon/trainer.py:27 + kvstore_dist.h / kvstore_nccl.h).
The TPU-native model compiles ONE training step over the whole mesh:

  * each parameter is a single logical jax.Array laid out by a
    PartitionSpec (sharding.ShardingRules);
  * the batch is sharded over the data axes;
  * forward + loss + backward + optimizer update are ONE jit-compiled
    function with donated param/state buffers — XLA inserts the grad
    all-reduces (psum over dp), the fsdp all-gathers/reduce-scatters and
    the tp collectives, and they ride ICI;
  * any registered mxnet_tpu.optimizer.Optimizer works: its `update()` is
    traced into the step (the fused optimizer ops are pure functions, see
    ops/optimizer_ops.py), with the update count `t` and scheduled `lr`
    fed in as device scalars so one executable serves every step.

This subsumes the reference's dist_sync kvstore semantics (synchronous
data parallelism); dist_async is intentionally not reproduced (SURVEY
§2.3 divergence note).
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from .. import optimizer as opt_mod
from .mesh import current_mesh
from .sharding import ShardingRules, batch_spec, named_sharding

__all__ = ["DistributedTrainer"]


def _tree_map(fn, *trees):
    """tree_map over optimizer-state pytrees. NDArray is not a registered
    pytree node, so mark it (and any non-container) as a leaf explicitly."""
    import jax

    return jax.tree_util.tree_map(
        fn, *trees,
        is_leaf=lambda x: x is not None and not isinstance(x, (list, tuple, dict)))


def _host_lr(optimizer):
    """Current learning rate resolved on the host (scheduler included)."""
    o = optimizer
    return float(o.lr_scheduler(max(o.num_update, 1))) if o.lr_scheduler \
        else o.lr


def _traced_update(optimizer, ctx, keys, weights, grads, states, t, lr):
    """Trace optimizer.update() for each weight key with the update count
    and learning rate fed as device scalars, so ONE executable serves every
    step (no per-step recompile from e.g. Adam's bias correction). The
    optimizer's host-side counters/scheduler are stubbed out for the trace
    and restored after. Shared by DistributedTrainer and PipelineTrainer."""
    from ..ndarray import NDArray

    o = optimizer
    saved = (o._index_update_count.copy(), o.num_update, o.lr,
             o.lr_scheduler, o._update_count)
    try:
        o._index_update_count = {i: t for i in keys}
        o._update_count = lambda index: None
        o.lr_scheduler = None
        o.lr = lr
        new_w, new_s = [], []
        for k, i in enumerate(keys):
            w = NDArray(weights[k], ctx=ctx)
            g = NDArray(grads[k], ctx=ctx)
            s = _tree_map(lambda a: NDArray(a, ctx=ctx), states[k])
            o.update_multi_precision(i, w, g, s)
            new_w.append(w._data)
            new_s.append(_tree_map(lambda nd_: nd_._data, s))
        return new_w, new_s
    finally:
        (o._index_update_count, o.num_update, o.lr, o.lr_scheduler,
         o._update_count) = saved


class DistributedTrainer:
    """Compiled sharded training over a mesh.

    Parameters
    ----------
    block : gluon.Block — initialized (single context); its parameters are
        moved onto the mesh and updated functionally. Call `sync_params()`
        to copy trained values back into the block for save/export.
    optimizer : str or Optimizer
    loss : gluon loss Block / callable(pred, label) -> per-sample loss.
    mesh : jax.sharding.Mesh (default: parallel.current_mesh())
    rules : ShardingRules for parameter layout (default heuristics).
    loss_inputs : what a multi-output model feeds the loss —
        "pred" (first output only), "outputs" (the full output tuple, for
        auxiliary terms like MoE load-balance/z-loss), or None (default):
        gluon loss Blocks get "pred", plain callables get "outputs" when
        the model returns several values. Single-output models always
        behave as "pred".
    """

    def __init__(self, block, optimizer, optimizer_params=None, loss=None,
                 mesh=None, rules=None, amp_dtype=None, loss_inputs=None):
        import jax

        self._block = block
        self._mesh = mesh or current_mesh()
        self._rules = rules or ShardingRules()
        self._loss = loss
        if loss_inputs not in (None, "pred", "outputs"):
            raise MXNetError("loss_inputs must be None, 'pred' or 'outputs'")
        self._loss_inputs = loss_inputs
        # mixed precision: compute forward/backward in `amp_dtype`
        # (bfloat16 — the MXU's native dtype) while parameters, gradients
        # as accumulated through the cast's vjp, and the optimizer update
        # stay fp32 (master weights; reference analogue: multi_precision)
        self._amp_dtype = amp_dtype

        param_items = sorted(block.collect_params().items())
        if not param_items:
            raise MXNetError("block has no parameters; initialize() it first")
        self._param_names = [n for n, _ in param_items]
        self._params = [p for _, p in param_items]
        # NDArray views (one per param, on the block's context) — these are
        # the objects whose buffers get swapped during tracing
        ctx = self._params[0].list_ctx()[0]
        self._param_nds = [p.data(ctx) for p in self._params]
        self._trainable = [i for i, p in enumerate(self._params)
                           if p.grad_req != "null"]
        self._aux = [i for i, p in enumerate(self._params) if p.grad_req == "null"]

        optimizer_params = optimizer_params or {}
        if isinstance(optimizer, opt_mod.Optimizer):
            self._optimizer = optimizer
        else:
            self._optimizer = opt_mod.create(optimizer, **optimizer_params)
        self._optimizer.param_dict = {i: self._params[i] for i in self._trainable}

        # -- move parameters onto the mesh ---------------------------------
        self._shardings = []
        self._arrays = []
        for name, p, nd_ in zip(self._param_names, self._params, self._param_nds):
            sh = self._rules.sharding_for(name, nd_.shape, self._mesh)
            self._shardings.append(sh)
            # fresh device-side copy: device_put may alias a matching
            # shard with the block's live buffer, and step()'s donation
            # would then delete the param out from under the block
            import jax.numpy as jnp

            self._arrays.append(jax.device_put(
                jnp.array(nd_._data, copy=True), sh))

        # -- optimizer state pytree (sharded like its weight) --------------
        self._states = []
        self._state_shardings = []
        for i in self._trainable:
            st = self._optimizer.create_state_multi_precision(
                i, self._param_nds[i])
            sh = self._shardings[i]
            self._states.append(_tree_map(
                lambda s: jax.device_put(s._data, sh), st))
            self._state_shardings.append(_tree_map(lambda s: sh, st))

        self._step_count = 0
        # executables resolve through mxnet_tpu.compile (keyed by this
        # process-local token x batch signature); the local dict only
        # carries forward's trace-time aux ordering metadata
        from .. import compile as _compile

        self._compile_token = _compile.instance_token("DistributedTrainer")
        self._fwd_compiled = {}

    # ------------------------------------------------------------------
    @property
    def optimizer(self):
        return self._optimizer

    @property
    def mesh(self):
        return self._mesh

    @property
    def learning_rate(self):
        return self._host_lr()

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _host_lr(self):
        return _host_lr(self._optimizer)

    # ------------------------------------------------------------------
    def _trace_forward(self, batch_arrays, param_arrays, key, is_train):
        """Run the block's eager forward with traced buffers swapped in.
        Same mechanism as HybridBlock._build_cache (gluon/block.py)."""
        from .. import autograd, random as _random
        from ..ndarray import NDArray
        from ..gluon import block as block_mod

        ctx = self._params[0].list_ctx()[0]
        # mxlint: trace-pure — routes the traced step key through the
        # RNG chain for the trace's duration; restored in finally
        prev_key = _random.push_trace_key(key)
        saved = [(nd_, nd_._data, nd_._version) for nd_ in self._param_nds]
        block_mod._TRACING.flag = True
        try:
            for nd_, arr in zip(self._param_nds, param_arrays):
                nd_._data = arr
            call_args = [NDArray(a, ctx=ctx) for a in batch_arrays]
            # enter the params' ctx: ops that create fresh arrays mid-forward
            # (arange position ids, masks) must land on the same ctx or
            # sub-blocks fed by them request params on the ambient default
            with ctx:
                with autograd._scope(recording=False, training=is_train):
                    out = self._block(*call_args)
            aux_updates = {}
            for i in self._aux:
                if self._param_nds[i]._data is not param_arrays[i]:
                    aux_updates[i] = self._param_nds[i]._data
            return out, aux_updates
        finally:
            for nd_, old, ver in saved:
                nd_._data = old
                nd_._version = ver
            block_mod._TRACING.flag = False
            _random.pop_trace_key(prev_key)  # mxlint: trace-pure — see push

    def _traced_update(self, weights, grads, states, t, lr):
        return _traced_update(self._optimizer, self._params[0].list_ctx()[0],
                              self._trainable, weights, grads, states, t, lr)

    # -- executable identity (ShardedTrainer overrides) -----------------
    def _step_key(self, sig):
        """Cache key for the fused step at one batch signature. The base
        trainer's fingerprint is a process-local instance token, so the
        key is quarantined from the persistent tier (no_persist);
        ShardedTrainer substitutes a stable cross-process fingerprint +
        topology and drops the quarantine."""
        from .. import compile as _compile

        return _compile.ExecutableKey("dist_step", self._compile_token,
                                      shapes=sig, sharded=True,
                                      donation=(3, 4), no_persist=True)

    def _forward_key(self, sig):
        from .. import compile as _compile

        return _compile.ExecutableKey("dist_forward", self._compile_token,
                                      shapes=sig, sharded=True,
                                      no_persist=True)

    def _resolve(self, key, build, **kw):
        """Registry resolution hook: ShardedTrainer brackets this with
        manifest prefetch/record so its fills land in a warmup manifest."""
        from .. import compile as _compile

        return _compile.get_or_build(key, build, **kw)

    def _build_step(self, batch_shapes):
        import jax
        import jax.numpy as jnp

        trainable, aux = self._trainable, self._aux
        loss_blk = self._loss

        amp = self._amp_dtype

        def maybe_cast(a):
            if amp is not None and jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(amp)
            return a

        def step(key, t, lr, arrays, states, *batch):
            train_arrays = [arrays[i] for i in trainable]
            other = list(arrays)

            def loss_fn(train_arrs):
                full = list(other)
                for k, i in enumerate(trainable):
                    # cast INSIDE the grad closure: the cast's vjp returns
                    # fp32 cotangents, i.e. grads accumulate at full precision
                    full[i] = maybe_cast(train_arrs[k])
                fwd_in = batch[:-1] if loss_blk is not None else batch
                fwd_in = tuple(maybe_cast(b) for b in fwd_in)
                out, aux_up = self._trace_forward(fwd_in, full, key, True)
                pred = out[0] if isinstance(out, (list, tuple)) else out
                # aux states (BatchNorm stats) keep their stored dtype
                aux_up = {i: u.astype(arrays[i].dtype)
                          for i, u in aux_up.items()}
                if loss_blk is not None:
                    # mxlint: trace-pure — per-trainer statics: the params'
                    # ctx and the loss-input mode deliberately specialize
                    # this executable (fixed for the trainer's lifetime)
                    label_nd = pred.__class__(batch[-1],
                                              ctx=self._params[0].list_ctx()[0])
                    mode = self._loss_inputs  # mxlint: trace-pure — see above
                    if mode is None:
                        # default: gluon loss Blocks keep the (pred, label)
                        # contract; plain callables see the whole output so
                        # auxiliary terms (MoE load-balance/z-loss, deep
                        # supervision heads) can fold into the objective.
                        # Pass loss_inputs="pred" to pin the old behavior.
                        from ..gluon.loss import Loss as _GluonLoss
                        mode = ("pred" if isinstance(loss_blk, _GluonLoss)
                                else "outputs")
                    if (mode == "outputs"
                            and isinstance(out, (list, tuple))
                            and len(out) > 1):
                        l = loss_blk(tuple(out), label_nd)
                    else:
                        l = loss_blk(pred, label_nd)
                    lval = jnp.mean(l._data.astype(jnp.float32))
                else:
                    lval = jnp.mean(pred._data.astype(jnp.float32))
                return lval, aux_up

            (loss_val, aux_up), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(train_arrays)
            new_w, new_s = self._traced_update(train_arrays, list(grads),
                                               states, t, lr)
            new_arrays = list(arrays)
            for k, i in enumerate(trainable):
                new_arrays[i] = new_w[k]
            for i in aux:
                if i in aux_up:
                    new_arrays[i] = aux_up[i]
            return loss_val, new_arrays, new_s

        from jax.sharding import PartitionSpec

        data_sh = [named_sharding(self._mesh, batch_spec(self._mesh, len(s)))
                   for s in batch_shapes]
        repl = named_sharding(self._mesh, PartitionSpec())
        out_shardings = (repl, list(self._shardings), list(self._state_shardings))
        # NOTE: donated buffers make a post-hoc lower() on live args
        # unsafe-looking but fine — lower() only traces avals, it never
        # executes or donates; cost analysis (now at the registry fill
        # hook, mxnet_tpu.compile.registry) happens on abstract values
        return jax.jit(
            step,
            in_shardings=(repl, repl, repl, list(self._shardings),
                          list(self._state_shardings), *data_sh),
            out_shardings=out_shardings,
            donate_argnums=(3, 4),
        )

    # ------------------------------------------------------------------
    def step(self, data, label=None, batch_size=None):
        """One synchronous sharded training step; returns the (replicated)
        scalar loss as an NDArray. Reference semantics: trainer.py:298
        step = allreduce + update, here fused into one executable."""
        import jax.numpy as jnp

        from .. import random as _random
        from ..ndarray import NDArray

        import time as _time

        t0 = _time.perf_counter()
        from .. import telemetry as _telemetry

        _telemetry.goodput.step_start(kind="dist", t0=t0,
                                      step=self._step_count + 1)
        if self._loss is not None and label is None:
            raise MXNetError("this trainer was built with a loss that takes "
                             "(pred, label); step() needs a label argument")
        batch = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                 for a in ([data] if label is None else [data, label])]
        # the step's loss is jnp.mean over the (global) batch, so gradients
        # are already batch-means — unlike gluon.Trainer.step, which divides
        # summed grads by batch_size via rescale_grad. Leave rescale at the
        # optimizer's own value.

        sig = tuple((tuple(b.shape), str(b.dtype)) for b in batch)
        from .. import telemetry

        # the step's RNG key is minted BEFORE the executable fill: the AOT
        # lower below traces _trace_forward, and the global RNG chain must
        # be initialized eagerly — a lazy first _get() inside a trace would
        # store a tracer into process state (UnexpectedTracerError later)
        key = _random.next_key()
        # aval-only example args (ShapeDtypeStruct — committed host arrays
        # would fail the lower's sharding validation), passed as a THUNK
        # so a steady-state step pays nothing: on a true fill they let the
        # registry capture memory_analysis figures and run the donation
        # verifier on the fused step (telemetry.memory,
        # docs/observability.md §Memory)
        def example_avals():
            import jax

            aval = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
            return (aval(key), jax.ShapeDtypeStruct((), "float32"),
                    jax.ShapeDtypeStruct((), "float32"),
                    [aval(a) for a in self._arrays],
                    jax.tree_util.tree_map(aval, list(self._states)),
                    *[aval(b) for b in batch])

        fn = self._resolve(
            self._step_key(sig),
            lambda: self._build_step([b.shape for b in batch]),
            label="dist_trainer_step",
            example_args=example_avals,
            on_fill=lambda: telemetry.counter(
                "mxtpu_executor_build_total", {"what": "dist_step"}).inc(),
            event_fields={"batch_sig": str(sig)})

        with _telemetry.goodput.phase("data_wait"):
            batch = [self._shard_batch(b) for b in batch]
        # host-side schedule: the real step count advances here (only after
        # the batch sharded successfully, so a failed step doesn't skew the
        # update schedule); the traced update consumes it (and the scheduled
        # lr) as device scalars
        self._step_count += 1
        o = self._optimizer
        o.num_update = max(self._step_count + o.begin_num_update, o.num_update)
        lr = self._host_lr()
        t = jnp.asarray(self._step_count, dtype=jnp.float32)
        from .. import telemetry

        with telemetry.tracing.root("train.step", component="train",
                                    attrs={"step": self._step_count,
                                           "kind": "dist"}):
            telemetry.goodput.mark_launch()
            with telemetry.tracing.span("train.fused_step"), \
                    telemetry.goodput.phase("compute"):
                loss_val, self._arrays, self._states = fn(
                    key, t, jnp.asarray(lr, dtype=jnp.float32),
                    self._arrays, self._states, *batch)
            ctx = self._params[0].list_ctx()[0]
            # global-batch examples/sec: the leading dim of the (global)
            # batch
            examples = None
            if batch and getattr(batch[0], "ndim", 0) > 0:
                examples = int(batch[0].shape[0])
            telemetry.observe_step(_time.perf_counter() - t0,
                                   examples=examples,
                                   step=self._step_count, kind="dist")
            telemetry.goodput.step_end(step=self._step_count)
        from . import resilience

        # step-boundary fault hook (no-op unless MXTPU_FAULT_INJECT is set)
        resilience.maybe_inject_fault(self._step_count)
        return NDArray(loss_val, ctx=ctx)

    def _shard_batch(self, arr):
        import jax

        return jax.device_put(arr, named_sharding(
            self._mesh, batch_spec(self._mesh, arr.ndim)))

    def prefetch(self, it, depth=None):
        """Wrap a data iterator in a `data.DevicePrefetcher` bound to this
        trainer's mesh: batches arrive on-device already laid out as
        `batch_spec` shardings, so step()'s `_shard_batch` is a no-op and
        the host→device copy overlaps the previous step's compute
        (docs/data_pipeline.md)."""
        from ..data import DevicePrefetcher

        return DevicePrefetcher(it, depth=depth, mesh=self._mesh,
                                src="sharded")

    def forward(self, data, is_train=False):
        """Compiled sharded inference over the mesh."""
        import jax
        import jax.numpy as jnp

        from .. import random as _random
        from ..ndarray import NDArray

        x = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        # minted before the fill: the AOT lower must never initialize the
        # RNG chain inside its trace (see step())
        key = _random.next_key()
        sig = (tuple(x.shape), str(x.dtype), is_train)
        entry = self._fwd_compiled.get(sig)
        if entry is None:
            aux_order = []   # aux indices whose updates the trace emits
                             # (filled at trace time; stable thereafter)

            def build():
                def fwd(key, arrays, batch):
                    out, aux_up = self._trace_forward((batch,), arrays, key,
                                                      is_train)
                    pred = out[0] if isinstance(out, (list, tuple)) else out
                    # mxlint: trace-pure — aux_order is the trace's own
                    # output-ordering record (see decl above): filled once at
                    # trace time, read eagerly after resolve, stable after
                    aux_order.clear()
                    aux_order.extend(sorted(aux_up))  # mxlint: trace-pure
                    return pred._data, [aux_up[i] for i in aux_order]

                from jax.sharding import PartitionSpec

                return jax.jit(fwd, in_shardings=(
                    named_sharding(self._mesh, PartitionSpec()),
                    list(self._shardings),
                    named_sharding(self._mesh, batch_spec(self._mesh, x.ndim))))

            fn = self._resolve(
                self._forward_key(sig),
                build, label="dist_trainer_forward",
                example_args=lambda: (
                    jax.ShapeDtypeStruct(key.shape, key.dtype),
                    [jax.ShapeDtypeStruct(a.shape, a.dtype)
                     for a in self._arrays],
                    jax.ShapeDtypeStruct(x.shape, x.dtype)))
            entry = (fn, aux_order)
            self._fwd_compiled[sig] = entry
        fn, aux_order = entry
        out, aux_new = fn(key, self._arrays, self._shard_batch(x))
        # train-mode forward advances BatchNorm running stats (gluon
        # semantics); write the updates back into the mesh param set
        for i, arr in zip(aux_order, aux_new):
            self._arrays[i] = jax.device_put(arr, self._shardings[i])
        ctx = self._params[0].list_ctx()[0]
        return NDArray(out, ctx=ctx)

    # ------------------------------------------------------------------
    def sync_params(self):
        """Copy trained values back into the block's Parameters (for
        save_parameters/export — reference checkpoint flow §5.4)."""
        import jax

        for p, nd_, arr in zip(self._params, self._param_nds, self._arrays):
            host = np.asarray(jax.device_get(arr))
            p.set_data(nd_.__class__(host, ctx=p.list_ctx()[0]))
            nd_._data = p.data(p.list_ctx()[0])._data

    def save_checkpoint(self, directory, step=0):
        """Sharded checkpoint of parameters + optimizer state via orbax
        (tensorstore-backed). SURVEY §5.4: the reference's formats are
        single-file rank-0 writes; on a pod each host writes only its
        addressable shards, and restore re-shards onto the current mesh —
        no full gather through one host. Reference analogue:
        Trainer.save_states (trainer.py:429) + save_checkpoint
        (model.py:394)."""
        import os

        import orbax.checkpoint as ocp

        import jax

        path = os.path.abspath(os.fspath(directory))
        # optimizer states are arbitrary pytrees; store them as flat leaf
        # dicts (orbax normalizes tuple/list containers) and unflatten with
        # the live treedef on restore
        states = {}
        for i, st in zip(self._trainable, self._states):
            leaves = jax.tree_util.tree_leaves(st)
            states[str(i)] = {str(j): leaf for j, leaf in enumerate(leaves)}
        tree = {
            "params": dict(zip(self._param_names, self._arrays)),
            "states": states,
            "meta": {"step": self._step_count,
                     "num_update": self._optimizer.num_update},
        }
        with ocp.PyTreeCheckpointer() as ckptr:
            ckptr.save(os.path.join(path, "step_%08d" % step), tree,
                       force=True)

    def load_checkpoint(self, directory, step=0):
        """Restore a save_checkpoint directory, placing every array directly
        onto its mesh sharding (each host reads only its shards)."""
        import os

        import jax
        import orbax.checkpoint as ocp

        path = os.path.join(os.path.abspath(os.fspath(directory)),
                            "step_%08d" % step)

        param_args = {n: ocp.ArrayRestoreArgs(sharding=sh)
                      for n, sh in zip(self._param_names, self._shardings)}
        state_args = {}
        for i, shs in zip(self._trainable, self._state_shardings):
            leaves = jax.tree_util.tree_leaves(shs)
            state_args[str(i)] = {
                str(j): ocp.ArrayRestoreArgs(sharding=sh)
                for j, sh in enumerate(leaves)}
        with ocp.PyTreeCheckpointer() as ckptr:
            restored = ckptr.restore(
                path,
                restore_args={
                    "params": param_args,
                    "states": state_args,
                    "meta": {"step": ocp.RestoreArgs(),
                             "num_update": ocp.RestoreArgs()},
                })
        self._arrays = [restored["params"][n] for n in self._param_names]
        new_states = []
        for i, st in zip(self._trainable, self._states):
            treedef = jax.tree_util.tree_structure(st)
            flat = restored["states"][str(i)]
            leaves = [flat[str(j)] for j in range(len(flat))]
            new_states.append(jax.tree_util.tree_unflatten(treedef, leaves))
        self._states = new_states
        self._step_count = int(restored["meta"]["step"])
        self._optimizer.num_update = int(restored["meta"]["num_update"])

    def save_states(self, fname):
        import pickle

        import jax

        from ..base import atomic_writer

        states = _tree_map(lambda a: np.asarray(jax.device_get(a)),
                           self._states)
        # atomic (temp + fsync + rename): a preempted pod mid-save keeps the
        # previous complete states file intact (parallel/resilience.py)
        with atomic_writer(fname, "wb") as f:
            pickle.dump({"states": states, "step": self._step_count,
                         "num_update": self._optimizer.num_update}, f)

    def load_states(self, fname):
        import pickle

        import jax

        with open(fname, "rb") as f:
            blob = pickle.load(f)
        self._step_count = blob["step"]
        self._optimizer.num_update = blob["num_update"]
        loaded = blob["states"]
        self._states = [
            _tree_map(lambda a, sh: jax.device_put(a, sh), st, shs)
            for st, shs in zip(loaded, self._state_shardings)]

    # -- per-rank sharded checkpoints (parallel.resilience format) ---------
    def shard_snapshot(self):
        """Host snapshot of THIS process's shards of every parameter and
        optimizer-state leaf — the only work the training thread pays on
        the async checkpoint path. Each array is recorded as its global
        shape/dtype plus the addressable pieces keyed by normalized
        (start, stop)-per-dim index, so `_install_shard_payloads` can
        either place pieces directly (same topology) or reassemble the
        global array and reshard it (elastic resume). Replicated shards
        (identical index on several local devices) are deduplicated."""
        import jax

        def entry(arr):
            shape = tuple(int(d) for d in arr.shape)
            pieces, seen = [], set()
            for s in arr.addressable_shards:
                key = tuple(sl.indices(dim)[:2]
                            for sl, dim in zip(s.index, shape))
                if key in seen:
                    continue
                seen.add(key)
                # np.array (copy), NOT np.asarray: on the CPU backend
                # device_get is zero-copy, and the fused step DONATES these
                # buffers — a view would dangle the moment the next step
                # runs, corrupting (or segfaulting) the background write
                pieces.append((key, np.array(jax.device_get(s.data))))
            return {"shape": shape, "dtype": str(arr.dtype),
                    "pieces": pieces}

        return {
            "params": {n: entry(a)
                       for n, a in zip(self._param_names, self._arrays)},
            "states": [[entry(leaf)
                        for leaf in jax.tree_util.tree_leaves(st)]
                       for st in self._states],
            "step": self._step_count,
            "num_update": self._optimizer.num_update,
        }

    def _install_shard_payloads(self, payloads, header):
        """`CheckpointManager.restore_sharded` loader: install parameters,
        optimizer state and the step/num_update cursors from shard
        payloads. Fast path gets only this rank's payload and places each
        piece verbatim; the elastic path gets EVERY saved shard,
        reassembles each global array (erroring on coverage holes) and
        reshards it onto the current mesh via make_array_from_callback —
        each process materializes only its addressable indices."""
        import jax
        import jax.numpy as jnp

        def materialize(entries, sharding, what):
            shape = tuple(entries[0]["shape"])
            dtype = entries[0]["dtype"]
            pieces = {}
            for e in entries:
                if tuple(e["shape"]) != shape or e["dtype"] != dtype:
                    raise MXNetError(
                        "sharded checkpoint: %s changed shape/dtype "
                        "(saved %r/%s, shard disagrees with %r/%s)"
                        % (what, tuple(e["shape"]), e["dtype"], shape,
                           dtype))
                for key, data in e["pieces"]:
                    pieces[tuple(tuple(p) for p in key)] = data
            cache = {}

            def full():
                if "a" not in cache:
                    out = np.zeros(shape, dtype)
                    cover = np.zeros(shape, bool)
                    for key, data in pieces.items():
                        slc = tuple(slice(a, b) for a, b in key)
                        out[slc] = data
                        cover[slc] = True
                    if not cover.all():
                        raise MXNetError(
                            "sharded checkpoint: the shard set does not "
                            "cover %s — an elastic resume needs every "
                            "saved rank's shard (a solo emergency "
                            "checkpoint only covers fully-replicated "
                            "state)" % what)
                    cache["a"] = out
                return cache["a"]

            def cb(index):
                key = tuple(sl.indices(dim)[:2]
                            for sl, dim in zip(index, shape))
                hit = pieces.get(key)
                piece = hit if hit is not None else full()[index]
                # hand jax an XLA-OWNED device array, never the raw
                # pickle-loaded numpy buffer: the CPU client zero-copies
                # 64-byte-aligned host memory, and these arrays feed the
                # DONATING fused step — donating a buffer numpy still owns
                # corrupts the heap (flaky SIGSEGV in whatever allocates
                # next, only in resumed generations)
                return jnp.array(piece, copy=True)

            return jax.make_array_from_callback(shape, sharding, cb)

        plist = list(payloads.values())
        new_arrays = []
        for name, sh in zip(self._param_names, self._shardings):
            entries = [p["params"].get(name) for p in plist]
            if any(e is None for e in entries):
                raise MXNetError(
                    "sharded checkpoint: parameter %r missing from a "
                    "shard — the checkpoint was saved for a different "
                    "model" % name)
            new_arrays.append(materialize(entries, sh, "param %r" % name))
        new_states = []
        for k, (st, shs) in enumerate(zip(self._states,
                                          self._state_shardings)):
            per_payload = [p["states"][k] for p in plist]
            sh_leaves = jax.tree_util.tree_leaves(shs)
            n = len(sh_leaves)
            if any(len(pp) != n for pp in per_payload):
                raise MXNetError(
                    "sharded checkpoint: optimizer state %d leaf count "
                    "mismatch — saved with a different optimizer" % k)
            leaves = [materialize([pp[j] for pp in per_payload],
                                  sh_leaves[j], "state[%d][%d]" % (k, j))
                      for j in range(n)]
            new_states.append(jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(st), leaves))
        self._arrays = new_arrays
        self._states = new_states
        self._step_count = int(plist[0]["step"])
        self._optimizer.num_update = int(plist[0]["num_update"])

    def _shard_identity(self):
        import jax

        from .mesh import mesh_fingerprint

        return (jax.process_index(), jax.process_count(),
                mesh_fingerprint(self._mesh))

    def save_sharded_checkpoint(self, manager, step=None, meta=None):
        """Write this rank's shard of a sharded checkpoint through
        `manager` (parallel.resilience.CheckpointManager): snapshot on the
        calling thread, serialize+fsync+manifest-publish on the manager's
        background writer (MXTPU_CKPT_ASYNC). Every rank must call this at
        the same step boundary."""
        rank, world, topology = self._shard_identity()
        return manager.save_sharded_async(
            self._step_count if step is None else step,
            self.shard_snapshot(), rank=rank, world_size=world,
            topology=topology, meta=meta)

    def emergency_sharded_checkpoint(self, manager, meta=None):
        """SOLO synchronous checkpoint for the preemption path: flush any
        in-flight async save, then publish this rank's snapshot as a
        1-shard manifest (rank 0 of world 1) with no peer cooperation —
        the preempting agent only notified THIS rank, and the others may
        be wedged in a collective. Restoring it at any world size goes
        through the elastic path; it covers the full model whenever this
        process's shards do (pure data-parallel / single-host — a
        cross-process-partitioned model needs a group-wide `preempt`
        instead, and restore errors honestly on the coverage hole)."""
        _, _, topology = self._shard_identity()
        manager.flush()
        m = dict(meta or {})
        m.setdefault("preempt", True)
        return manager.save_sharded(
            self._step_count, self.shard_snapshot(), rank=0, world_size=1,
            topology=topology, meta=m)

    def restore_sharded_checkpoint(self, manager, step=None):
        """Restore the newest complete sharded checkpoint (or `step`) onto
        the CURRENT mesh; reshards when the saved topology/world size
        differs (the compile key's topology fingerprint then honestly
        misses once). Returns the manifest header, or None when there is
        nothing to restore."""
        rank, world, topology = self._shard_identity()
        return manager.restore_sharded(
            self._install_shard_payloads, step=step, rank=rank,
            world_size=world, topology=topology)
