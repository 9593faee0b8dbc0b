"""Pipeline parallelism over the `pp` mesh axis.

Absent from the reference (SURVEY §2.3: "Pipeline parallel — absent; closest
is manual model-parallel layer placement via group2ctx"). TPU-native design:
a GPipe-style microbatch schedule expressed as one `shard_map`-ped
`lax.fori_loop` — each pp device holds ONE stage's parameters; activations
hop to the next stage over `ppermute` (a single ICI neighbor transfer per
tick), so the schedule compiles to a static XLA program with no host
involvement per microbatch.

Constraints (the standard collective-pipeline formulation):
- stages are shape-preserving (activation in == activation out), the
  transformer-layer case pipelining exists for;
- per-stage params are stacked on a leading axis of size `pp` and sharded
  over it (one slice resident per device).

Non-uniform models (embeddings in front, heads behind) are handled by
`PipelineTrainer` (pipeline_trainer.py): prelude/postlude run replicated
outside the loop, only the uniform layer stack is pipelined.

Differentiable end-to-end: `ppermute` has an exact transpose, so
`jax.grad` through `pipeline_apply` yields the backward pipeline schedule
automatically — no hand-written backward pass. Memory control: GPipe's
weakness is storing every microbatch's stage activations for the backward
sweep; `remat=True` wraps the stage in `jax.checkpoint` so only stage
INPUTS are kept and the interior is recomputed during backward — the same
peak-activation bound 1F1B achieves by schedule, achieved functionally
(the XLA scheduler still overlaps the recompute with the ppermute hops).
"""
from __future__ import annotations

import functools

__all__ = ["pipeline_apply", "pipeline_stack_params"]


def pipeline_stack_params(param_list):
    """Stack a list of per-stage pytrees into one pytree with a leading
    stage axis (shard it over `pp` with PartitionSpec('pp', ...))."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_list)


def _pipeline_loop(stage_fn, params, xs, axis_name):
    """Runs inside shard_map: params are this device's stage slice
    (leading stage axis of size 1), xs = (x, *extras) — each a full
    (M, ...) microbatch stack. `extras` (e.g. an attention mask) travel
    with their microbatch through the permutes but are not transformed."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    squeeze = jax.tree_util.tree_map(lambda p: p[0], params)
    x = xs[0]
    m = x.shape[0]
    steps = m + n - 1

    state0 = tuple(jnp.zeros_like(a[0]) for a in xs)
    outs0 = jnp.zeros_like(x)

    def body(t, carry):
        state, outs = carry
        # stage 0 consumes microbatch t (while valid); later stages consume
        # what arrived from the left neighbor last tick
        feed = tuple(a[jnp.minimum(t, m - 1)] for a in xs)
        inp = tuple(jnp.where(idx == 0, f, s) for f, s in zip(feed, state))
        out = stage_fn(squeeze, *inp)
        # the last stage finishes microbatch t-(n-1) at tick t
        mb = t - (n - 1)
        valid = (idx == n - 1) & (mb >= 0)
        outs = lax.cond(
            valid,
            lambda o: o.at[jnp.maximum(mb, 0)].set(out),
            lambda o: o,
            outs)
        perm = [(i, (i + 1) % n) for i in range(n)]
        state = tuple(lax.ppermute(a, axis_name, perm)
                      for a in (out,) + inp[1:])
        return state, outs

    _, outs = lax.fori_loop(0, steps, body, (state0, outs0))
    # only the last stage holds real outputs; psum broadcasts them (every
    # other device contributes zeros)
    has = jnp.where(idx == n - 1, 1.0, 0.0)
    return lax.psum(outs * has.astype(outs.dtype), axis_name)


def pipeline_apply(stage_fn, stacked_params, x, num_microbatches=None,
                   axis_name="pp", mesh=None, extras=(), remat=False):
    """Run `stage_fn(params_i, act, *extras) -> act` as a `pp`-deep pipeline.

    stage_fn : callable(stage_params_pytree, activation, *extras) ->
        activation (shape-preserving in the activation).
    stacked_params : pytree with leading stage axis == mesh.shape[axis_name]
        (see pipeline_stack_params).
    x : (B, ...) global batch (replicated over pp; batch dim may be sharded
        over a dp axis of the same mesh); split into `num_microbatches`
        equal microbatches (default: pipeline depth).
    extras : per-sample arrays (B, ...) that accompany each microbatch
        untransformed (attention masks); they ride the same ppermute hops.
    remat : wrap the stage in jax.checkpoint — backward recomputes stage
        interiors instead of storing every microbatch's activations
        (the 1F1B peak-memory bound, achieved functionally).
    Returns (B, ...) outputs, numerically identical to applying the stages
    sequentially.
    """
    import jax

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    n = mesh.shape[axis_name]
    lead = {leaf.shape[0] for leaf in
            jax.tree_util.tree_leaves(stacked_params)}
    if lead != {n}:
        raise ValueError(
            "stacked_params leading (stage) axis %s must equal the '%s' "
            "mesh axis size %d — shard_map would silently truncate to one "
            "stage per device" % (sorted(lead), axis_name, n))
    b = x.shape[0]
    m = num_microbatches or n
    if b % m:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (b, m))
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    def mb_split(a):
        return a.reshape((m, b // m) + a.shape[1:])

    xs = tuple(mb_split(a) for a in (x,) + tuple(extras))

    # microbatch arrays are (M, mb, ...): ride any dp axis on the batch dim
    dp_axes = [ax for ax in ("dp", "fsdp") if ax in mesh.shape
               and mesh.shape[ax] > 1]
    data_spec = P(None, tuple(dp_axes) if dp_axes else None)

    pspec = jax.tree_util.tree_map(
        lambda _: P(axis_name), stacked_params)
    body = functools.partial(_pipeline_loop, fn, axis_name=axis_name)
    smapped = shard_map(body, mesh=mesh,
                        in_specs=(pspec, tuple(data_spec for _ in xs)),
                        out_specs=data_spec, check_vma=False)
    out = smapped(stacked_params, xs)
    return out.reshape((b,) + x.shape[1:])
