"""Ring attention — sequence/context parallelism over the `sp` mesh axis.

Not present in the reference (SURVEY §5.7: long sequences were handled by
bucketing only); this is the TPU-native long-context extension the build
plan calls for. Q/K/V are sharded on the sequence dimension across `sp`;
each device keeps its Q shard resident and the K/V shards rotate around
the ring via `ppermute` (one ICI hop per step), overlapping the transfer
with the local block's attention math. Softmax is accumulated online
(running max / running sum), so the result is exact — identical to full
attention — while no device ever materializes the full [L, L] score
matrix or the full K/V.
"""
from __future__ import annotations

import functools

__all__ = ["ring_attention", "ring_attention_sharded", "local_attention_block"]


def local_attention_block(q, k, v, o, m, l, causal, q_off, kv_off, scale):
    """One blockwise-attention accumulation step (online softmax).

    q: [B, Lq, H, D]; k, v: [B, Lk, H, D]; o: [B, Lq, H, D] accumulator;
    m, l: [B, H, Lq] running max / normalizer. Returns updated (o, m, l).
    """
    import jax.numpy as jnp

    # scores [B, H, Lq, Lk] — contraction on D via MXU
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        qpos = q_off + jnp.arange(lq)[:, None]
        kpos = kv_off + jnp.arange(lk)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows (all -inf): keep them at zero contribution
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = alpha * l + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Exact attention with K/V rotating around the `axis_name` ring.

    Call inside shard_map/pjit where q/k/v are the *local* sequence shards
    [B, L_local, H, D]. Returns the local output shard [B, L_local, H, D].
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    o0 = jnp.zeros((b, lq, h, d), jnp.float32)
    m0 = jnp.full((b, h, lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    q_off = idx * lq

    def body(step, carry):
        o, m, l, kc, vc = carry
        src = (idx - step) % n           # whose K/V shard we now hold
        kv_off = src * lk
        o, m, l = local_attention_block(q, kc, vc, o, m, l, causal,
                                        q_off, kv_off, scale)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return o, m, l, kc, vc

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh=None, axis_name="sp", causal=False,
                           scale=None, batch_axis="dp"):
    """Host-callable wrapper: shards [B, L, H, D] inputs over the mesh
    (batch on `dp`, sequence on `sp`) and runs ring_attention under
    shard_map. Jit-compatible."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    bat = batch_axis if batch_axis in mesh.axis_names else None
    seq = axis_name if axis_name in mesh.axis_names else None
    spec = P(bat, seq, None, None)

    body = functools.partial(ring_attention, axis_name=axis_name,
                             causal=causal, scale=scale)
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    if seq is None:
        raise ValueError(f"mesh {mesh.axis_names} has no '{axis_name}' axis")
    return fn(q, k, v)
