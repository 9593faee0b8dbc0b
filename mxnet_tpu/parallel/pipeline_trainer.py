"""PipelineTrainer — pipeline-parallel training of real Gluon models.

The reference has no pipeline parallelism (SURVEY §2.3); `pipeline.py`
provides the collective GPipe loop for uniform stages. This module lifts
its constraints so an actual model — the in-tree BERT encoder stack — can
be pipelined through the Gluon API:

  * **non-uniform ends**: the embedding front (`prelude`) and the
    pooler/head back (`postlude`) run replicated on every pp device
    outside the loop; only the uniform transformer-layer stack is
    pipelined. For transformer models the ends are a few percent of the
    FLOPs, so replicating them costs almost nothing while removing the
    shape-preservation constraint where it doesn't hold.
  * **Gluon params, not hand-stacked pytrees**: the trainer collects each
    layer's Parameters, verifies the stack is homogeneous, and stacks
    them into (pp, layers_per_stage, ...) leaves sharded over the `pp`
    mesh axis — one stage's slice resident per device. `sync_params()`
    unstacks trained values back into the Blocks for save/export.
  * **one executable**: prelude → pipelined stack → postlude → loss →
    backward → optimizer update compile into a single donated-buffer XLA
    program, like DistributedTrainer. Any registered optimizer works
    (elementwise updates apply per stacked leaf).
  * **microbatch schedule control**: `num_microbatches` sets pipeline
    depth utilization (bubble fraction = (pp-1)/(m+pp-1));
    `remat=True` bounds live activations to stage inputs (the 1F1B
    peak-memory behavior, achieved functionally — pipeline.py docstring).

Masks (BERT `valid_length`) travel with their microbatch as pipeline
`extras`. A dp axis in the mesh composes: batch dims shard over dp while
stages shard over pp.

Usage (model side: BERTModel.pipeline_stages() — transformer.py):

    mesh = make_mesh([("pp", 4)])
    trainer = PipelineTrainer(model, "adam", {"learning_rate": 1e-4},
                              loss=SoftmaxCrossEntropyLoss(), mesh=mesh)
    loss = trainer.step(tokens, labels)
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from .. import optimizer as opt_mod
from .mesh import PP, current_mesh
from .pipeline import pipeline_apply
from .sharding import batch_spec, named_sharding
from .trainer import _host_lr, _traced_update, _tree_map

__all__ = ["PipelineTrainer"]


class PipelineTrainer:
    """Compiled pipeline-parallel training over the `pp` mesh axis.

    Parameters
    ----------
    block : gluon.Block — initialized. Must either implement
        ``pipeline_stages() -> (prelude, cells, postlude)`` (see
        BERTModel.pipeline_stages) or be accompanied by explicit
        `cells`/`prelude`/`postlude` arguments.
    optimizer : str or Optimizer
    optimizer_params : dict
    loss : gluon loss Block / callable(pred, label) -> per-sample loss
    cells : list of homogeneous HybridBlocks to pipeline (len divisible
        by the pp axis size); default block.pipeline_stages()[1]
    prelude : callable(*inputs) -> activation NDArray, or
        (activation, mask) pair; runs replicated before the pipeline.
        Default: identity on a single input.
    postlude : callable(activation NDArray) -> prediction NDArray (or
        tuple whose first element is the prediction); replicated after.
    mesh : jax.sharding.Mesh with a `pp` axis (default current_mesh())
    num_microbatches : int (default: pipeline depth)
    remat : bool — recompute stage interiors in backward (memory-optimal)
    amp_dtype : bf16 compute with fp32 master weights, as in
        DistributedTrainer
    """

    def __init__(self, block, optimizer, optimizer_params=None, loss=None,
                 cells=None, prelude=None, postlude=None, mesh=None,
                 axis_name=PP, num_microbatches=None, remat=False,
                 amp_dtype=None):
        import jax

        self._block = block
        self._mesh = mesh or current_mesh()
        self._axis = axis_name
        if axis_name not in self._mesh.shape:
            raise MXNetError("mesh has no '%s' axis (axes: %s)"
                             % (axis_name, tuple(self._mesh.shape)))
        self._pp = self._mesh.shape[axis_name]
        self._loss = loss
        self._amp_dtype = amp_dtype
        self._remat = remat

        if cells is None or prelude is None or postlude is None:
            if not hasattr(block, "pipeline_stages"):
                raise MXNetError(
                    "block does not implement pipeline_stages(); pass "
                    "cells=/prelude=/postlude= explicitly")
            d_pre, d_cells, d_post = block.pipeline_stages()
            cells = cells if cells is not None else d_cells
            prelude = prelude if prelude is not None else d_pre
            postlude = postlude if postlude is not None else d_post
        self._cells = list(cells)
        self._prelude = prelude or (lambda x: x)
        self._postlude = postlude or (lambda x: x)
        if len(self._cells) % self._pp:
            raise MXNetError("%d cells not divisible into %d pipeline "
                             "stages" % (len(self._cells), self._pp))
        self._cps = len(self._cells) // self._pp
        self._num_microbatches = num_microbatches

        # -- canonical per-cell parameter order; verify homogeneity --------
        def cell_items(cell):
            return sorted(cell.collect_params().items())

        first = cell_items(self._cells[0])
        self._cell_local_names = [self._strip(self._cells[0], n)
                                  for n, _ in first]
        sigs = []
        for cell in self._cells:
            items = cell_items(cell)
            sigs.append([(self._strip(cell, n), tuple(p.shape),
                          np.dtype(p.dtype).name, p.grad_req)
                         for n, p in items])
        if any(s != sigs[0] for s in sigs[1:]):
            raise MXNetError(
                "pipeline cells are not homogeneous (same local param "
                "names/shapes/dtypes required): %s vs %s"
                % (sigs[0], next(s for s in sigs if s != sigs[0])))
        if any(req == "null" for _, _, _, req in sigs[0]):
            raise MXNetError("pipeline cells with aux (grad_req='null') "
                             "state are not supported — running stats "
                             "cannot be carried through the stage loop")

        ctx = None
        all_items = sorted(block.collect_params().items())
        if not all_items:
            raise MXNetError("block has no parameters; initialize() it first")
        ctx = all_items[0][1].list_ctx()[0]
        self._ctx = ctx

        # -- split params: pipelined cell leaves vs outer (ends) -----------
        cell_param_names = set()
        self._cell_nds = []       # [cell][j] NDArray view, canonical order
        for cell in self._cells:
            items = cell_items(cell)
            cell_param_names.update(n for n, _ in items)
            self._cell_nds.append([p.data(ctx) for _, p in items])

        outer_items = [(n, p) for n, p in all_items
                       if n not in cell_param_names]
        self._outer_names = [n for n, _ in outer_items]
        self._outer_params = [p for _, p in outer_items]
        self._outer_nds = [p.data(ctx) for p in self._outer_params]
        self._outer_trainable = [i for i, p in enumerate(self._outer_params)
                                 if p.grad_req != "null"]
        self._outer_aux = [i for i, p in enumerate(self._outer_params)
                          if p.grad_req == "null"]

        # -- stacked cell leaves on the mesh: (pp, cps, *shape) ------------
        from jax.sharding import PartitionSpec as P

        self._pp_sharding = named_sharding(self._mesh, P(axis_name))
        self._repl = named_sharding(self._mesh, P())
        self._cell_leaves = []
        for j in range(len(first)):
            stacked = np.stack([np.asarray(jax.device_get(
                self._cell_nds[c][j]._data)) for c in range(len(self._cells))])
            stacked = stacked.reshape((self._pp, self._cps)
                                      + stacked.shape[1:])
            self._cell_leaves.append(
                jax.device_put(stacked, self._pp_sharding))

        # fresh device-side copy so the mesh array NEVER aliases the
        # block's live param buffer: device_put can reuse a matching shard
        # in place, and the step's buffer donation would then delete the
        # param out from under the block (breaking later eager use / a
        # second trainer)
        import jax.numpy as jnp

        self._outer_arrays = [
            jax.device_put(jnp.array(nd_._data, copy=True), self._repl)
            for nd_ in self._outer_nds]

        # -- optimizer + state (outer trainables then cell leaves) ---------
        optimizer_params = optimizer_params or {}
        if isinstance(optimizer, opt_mod.Optimizer):
            self._optimizer = optimizer
        else:
            self._optimizer = opt_mod.create(optimizer, **optimizer_params)

        from ..ndarray import NDArray

        self._states = []
        self._state_shardings = []
        self._weight_keys = ([("outer", i) for i in self._outer_trainable]
                             + [("cell", j)
                                for j in range(len(self._cell_leaves))])
        for k, (kind, i) in enumerate(self._weight_keys):
            if kind == "outer":
                w_nd, sh = self._outer_nds[i], self._repl
            else:
                w_nd = NDArray(self._cell_leaves[i], ctx=ctx)
                sh = self._pp_sharding
            st = self._optimizer.create_state_multi_precision(k, w_nd)
            self._states.append(_tree_map(
                lambda s: jax.device_put(s._data, sh), st))
            self._state_shardings.append(_tree_map(lambda s: sh, st))

        self._step_count = 0
        # executables resolve through mxnet_tpu.compile, keyed by this
        # process-local token x batch signature (memory tier only)
        from .. import compile as _compile

        self._compile_token = _compile.instance_token("PipelineTrainer")

    # ------------------------------------------------------------------
    @staticmethod
    def _strip(cell, name):
        pre = cell.prefix
        return name[len(pre):] if name.startswith(pre) else name

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def mesh(self):
        return self._mesh

    def _host_lr(self):
        return _host_lr(self._optimizer)

    # ------------------------------------------------------------------
    def _swap_all(self, outer_arrays):
        """Swap the outer (prelude/postlude) param buffers for traced
        arrays; cell buffers are swapped per-layer in _call_cell."""
        saved = [(nd_, nd_._data, nd_._version) for nd_ in self._outer_nds]
        for nd_, arr in zip(self._outer_nds, outer_arrays):
            nd_._data = arr
        return saved

    @staticmethod
    def _restore(saved):
        for nd_, old, ver in saved:
            nd_._data = old
            nd_._version = ver

    def _call_cell(self, leaves, act, mask, key):
        """Apply ONE layer: swap the template cell's param buffers with
        `leaves` (this layer's arrays) and run its Gluon forward under a
        per-layer RNG key (decorrelated dropout across layers/stages)."""
        from .. import random as _random
        from ..ndarray import NDArray

        cell = self._cells[0]
        nds = self._cell_nds[0]
        saved = [(nd_, nd_._data, nd_._version) for nd_ in nds]
        prev_key = _random.push_trace_key(key)
        try:
            for nd_, arr in zip(nds, leaves):
                nd_._data = arr
            a_nd = NDArray(act, ctx=self._ctx)
            if mask is None:
                out = cell(a_nd)
            else:
                out = cell(a_nd, NDArray(mask, ctx=self._ctx))
            return out._data
        finally:
            self._restore(saved)
            _random.pop_trace_key(prev_key)

    def _stage_fn(self, stage_leaves, act, *extras):
        """One pipeline stage = scan over this stage's cps layers.

        extras = (mask?, sample_ids): sample_ids is a per-sample int32
        array riding with each microbatch; folding its first element into
        the RNG key decorrelates dropout across microbatches (the loop
        body is traced once, so a static key would repeat per tick)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from .. import random as _random

        mask = extras[0] if len(extras) == 2 else None
        ids = extras[-1]
        base = jax.random.fold_in(_random.next_key(), ids[0])
        sidx = lax.axis_index(self._axis)

        def layer_body(a, xs):
            per_layer_leaves, li = xs
            key = jax.random.fold_in(jax.random.fold_in(base, sidx), li)
            return self._call_cell(per_layer_leaves, a, mask, key), None

        act, _ = lax.scan(layer_body, act,
                          (stage_leaves, jnp.arange(self._cps)))
        return act

    # ------------------------------------------------------------------
    def _traced_update(self, weights, grads, states, t, lr):
        return _traced_update(self._optimizer, self._ctx,
                              list(range(len(self._weight_keys))),
                              weights, grads, states, t, lr)


    def _run_model(self, batch_arrays, outer_full, cell_leaves, key,
                   is_train):
        """prelude -> pipelined stack -> postlude, eager-traced (buffers
        swapped) so Gluon code builds the jax computation."""
        import jax.numpy as jnp

        from .. import autograd, random as _random
        from ..gluon import block as block_mod
        from ..ndarray import NDArray

        # mxlint: trace-pure — routes the traced step key through the
        # RNG chain for the trace's duration; restored in finally
        prev_key = _random.push_trace_key(key)
        saved = self._swap_all(outer_full)
        block_mod._TRACING.flag = True
        try:
            call_args = [NDArray(a, ctx=self._ctx) for a in batch_arrays]
            with autograd._scope(recording=False, training=is_train):
                pre = self._prelude(*call_args)
                if isinstance(pre, (tuple, list)):
                    act_nd, mask_nd = pre[0], pre[1]
                else:
                    act_nd, mask_nd = pre, None
                mask_arr = None if mask_nd is None else mask_nd._data
                ids = jnp.arange(act_nd.shape[0], dtype=jnp.int32)
                extras = (ids,) if mask_arr is None else (mask_arr, ids)

                act = pipeline_apply(
                    self._stage_fn, cell_leaves, act_nd._data,
                    num_microbatches=self._num_microbatches,
                    axis_name=self._axis, mesh=self._mesh,
                    extras=extras, remat=self._remat)

                out = self._postlude(NDArray(act, ctx=self._ctx))
            pred = out[0] if isinstance(out, (list, tuple)) else out
            aux_up = {}
            for i in self._outer_aux:
                if self._outer_nds[i]._data is not outer_full[i]:
                    aux_up[i] = self._outer_nds[i]._data
            return pred._data, aux_up
        finally:
            self._restore(saved)
            block_mod._TRACING.flag = False
            _random.pop_trace_key(prev_key)  # mxlint: trace-pure — see push

    def _build_step(self, batch_shapes):
        import jax
        import jax.numpy as jnp

        trainable = self._outer_trainable
        aux = self._outer_aux
        loss_blk = self._loss
        amp = self._amp_dtype
        n_outer_t = len(trainable)

        def maybe_cast(a):
            if amp is not None and jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(amp)
            return a

        def step(key, t, lr, outer_arrays, cell_leaves, states, *batch):
            outer_t = [outer_arrays[i] for i in trainable]

            def loss_fn(wl):
                outer_w, cell_w = wl[:n_outer_t], wl[n_outer_t:]
                full = list(outer_arrays)
                for k, i in enumerate(trainable):
                    full[i] = maybe_cast(outer_w[k])
                cells_amp = [maybe_cast(c) for c in cell_w]
                fwd_in = batch[:-1] if loss_blk is not None else batch
                fwd_in = tuple(maybe_cast(b) if jnp.issubdtype(
                    b.dtype, jnp.floating) else b for b in fwd_in)
                pred_arr, aux_up = self._run_model(fwd_in, full, cells_amp,
                                                   key, True)
                aux_up = {i: u.astype(outer_arrays[i].dtype)
                          for i, u in aux_up.items()}
                from ..ndarray import NDArray

                if loss_blk is not None:
                    # mxlint: trace-pure — self._ctx is frozen per-trainer
                    # config; a rebuilt trainer resolves a fresh executable
                    pred_nd = NDArray(pred_arr, ctx=self._ctx)
                    label_nd = NDArray(batch[-1], ctx=self._ctx)  # mxlint: trace-pure — ditto
                    l = loss_blk(pred_nd, label_nd)
                    lval = jnp.mean(l._data.astype(jnp.float32))
                else:
                    lval = jnp.mean(pred_arr.astype(jnp.float32))
                return lval, aux_up

            weights = outer_t + list(cell_leaves)
            (loss_val, aux_up), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(weights)
            new_w, new_s = self._traced_update(weights, list(grads),
                                               states, t, lr)
            new_outer = list(outer_arrays)
            for k, i in enumerate(trainable):
                new_outer[i] = new_w[k]
            for i in aux:
                if i in aux_up:
                    new_outer[i] = aux_up[i]
            new_cells = new_w[n_outer_t:]
            return loss_val, new_outer, new_cells, new_s

        data_sh = [named_sharding(self._mesh,
                                  batch_spec(self._mesh, len(s)))
                   for s in batch_shapes]
        out_shardings = (self._repl,
                         [self._repl] * len(self._outer_arrays),
                         [self._pp_sharding] * len(self._cell_leaves),
                         list(self._state_shardings))
        return jax.jit(
            step,
            in_shardings=(self._repl, self._repl, self._repl,
                          [self._repl] * len(self._outer_arrays),
                          [self._pp_sharding] * len(self._cell_leaves),
                          list(self._state_shardings), *data_sh),
            out_shardings=out_shardings,
            donate_argnums=(3, 4, 5),
        )

    # ------------------------------------------------------------------
    def step(self, *batch):
        """One pipelined training step over (inputs..., label); returns
        the scalar loss NDArray."""
        import jax.numpy as jnp

        from .. import random as _random
        from ..ndarray import NDArray

        import time as _time

        t0 = _time.perf_counter()
        from .. import telemetry as _telemetry

        _telemetry.goodput.step_start(kind="pipeline", t0=t0,
                                      step=self._step_count + 1)
        if self._loss is not None and len(batch) < 2:
            raise MXNetError("step(*inputs, label) needs a label for the "
                             "configured loss")
        arrs = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                for a in batch]
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrs)
        from .. import compile as _compile

        # minted BEFORE the fill: the AOT lower below traces the model and
        # the RNG chain must never initialize inside a trace (trainer.py)
        key = _random.next_key()
        # aval-only example args as a thunk (see trainer.py): on a true
        # fill they let the registry capture memory_analysis figures and
        # run the donation verifier on the fused pipeline step
        def example_avals():
            import jax as _jax

            aval = lambda a: _jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
            return (aval(key), _jax.ShapeDtypeStruct((), "float32"),
                    _jax.ShapeDtypeStruct((), "float32"),
                    [aval(a) for a in self._outer_arrays],
                    [aval(a) for a in self._cell_leaves],
                    _jax.tree_util.tree_map(aval, list(self._states)),
                    *map(aval, arrs))

        fn = _compile.get_or_build(
            _compile.ExecutableKey("pipeline_step", self._compile_token,
                                   shapes=sig, sharded=True,
                                   donation=(3, 4, 5), no_persist=True),
            lambda: self._build_step([a.shape for a in arrs]),
            label="pipeline_trainer_step",
            example_args=example_avals)

        import jax

        with _telemetry.goodput.phase("data_wait"):
            arrs = [jax.device_put(a, named_sharding(
                self._mesh, batch_spec(self._mesh, a.ndim))) for a in arrs]
        self._step_count += 1
        o = self._optimizer
        o.num_update = max(self._step_count + o.begin_num_update,
                           o.num_update)
        lr = self._host_lr()
        t = jnp.asarray(self._step_count, dtype=jnp.float32)
        _telemetry.goodput.mark_launch()
        with _telemetry.goodput.phase("compute"):
            loss_val, self._outer_arrays, self._cell_leaves, self._states = \
                fn(key, t, jnp.asarray(lr, dtype=jnp.float32),
                   self._outer_arrays, self._cell_leaves, self._states,
                   *arrs)
        from .. import telemetry

        examples = int(arrs[0].shape[0]) if getattr(arrs[0], "ndim", 0) \
            else None
        telemetry.observe_step(_time.perf_counter() - t0, examples=examples,
                               step=self._step_count, kind="pipeline")
        _telemetry.goodput.step_end(step=self._step_count)
        return NDArray(loss_val, ctx=self._ctx)

    def forward(self, *batch, is_train=False):
        """Pipelined inference (for numerics checks vs the sequential
        model)."""
        import jax
        import jax.numpy as jnp

        from .. import random as _random
        from ..ndarray import NDArray

        arrs = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                for a in batch]
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrs) + (is_train,)

        def build():
            def fwd(key, outer_arrays, cell_leaves, *data):
                pred, _ = self._run_model(data, list(outer_arrays),
                                          list(cell_leaves), key, is_train)
                return pred

            data_sh = [named_sharding(self._mesh,
                                      batch_spec(self._mesh, a.ndim))
                       for a in arrs]
            return jax.jit(fwd, in_shardings=(
                self._repl, [self._repl] * len(self._outer_arrays),
                [self._pp_sharding] * len(self._cell_leaves), *data_sh))

        from .. import compile as _compile

        fn = _compile.get_or_build(
            _compile.ExecutableKey("pipeline_forward", self._compile_token,
                                   shapes=sig, sharded=True,
                                   no_persist=True),
            build, label="pipeline_trainer_forward")
        key = _random.next_key()
        arrs = [jax.device_put(a, named_sharding(
            self._mesh, batch_spec(self._mesh, a.ndim))) for a in arrs]
        out = fn(key, self._outer_arrays, self._cell_leaves, *arrs)
        return NDArray(out, ctx=self._ctx)

    # ------------------------------------------------------------------
    def sync_params(self):
        """Unstack trained leaves back into the Blocks' Parameters (for
        save_parameters/export — reference checkpoint flow §5.4)."""
        import jax

        for i, (p, nd_) in enumerate(zip(self._outer_params,
                                         self._outer_nds)):
            host = np.asarray(jax.device_get(self._outer_arrays[i]))
            p.set_data(nd_.__class__(host, ctx=p.list_ctx()[0]))
            nd_._data = p.data(p.list_ctx()[0])._data
        for j, leaf in enumerate(self._cell_leaves):
            host = np.asarray(jax.device_get(leaf))
            flat = host.reshape((len(self._cells),) + host.shape[2:])
            for c, cell in enumerate(self._cells):
                items = sorted(cell.collect_params().items())
                name, p = items[j]
                nd_ = self._cell_nds[c][j]
                p.set_data(nd_.__class__(flat[c], ctx=p.list_ctx()[0]))
                nd_._data = p.data(p.list_ctx()[0])._data
