"""Ring attention: causal self-attention with the sequence sharded over a
mesh axis (context/sequence parallelism for long prompts).

Net-new relative to the reference — it has no sequence parallelism anywhere
(SURVEY.md §2.3, grep-verified); long-context prefill on TPU needs it so
one prompt's attention can use a whole slice's HBM and FLOPs.

Design (the TPU-idiomatic form of Ring Attention, Liu et al. 2023): each of
the ``sp`` devices holds a contiguous chunk of Q/K/V along the token axis.
Every device computes blockwise attention of its local queries against the
K/V chunk it currently holds, accumulating with an online (flash-style)
softmax, while `jax.lax.ppermute` rotates the K/V chunks one hop around the
ring — ``sp`` steps total, each overlapping ICI transfer with compute.
Chunks are identified by origin, so absolute positions (and the causal
mask) stay exact. The output is bit-stable under resharding because the
accumulation order per query is fixed by origin index, not arrival time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    return x if n_rep == 1 else jnp.repeat(x, n_rep, axis=-2)


def _ring_chunk(
    q: jax.Array,  # [Tl, H, D] local query chunk
    k: jax.Array,  # [Tl, KH, D] local key chunk
    v: jax.Array,  # [Tl, KH, D]
    *,
    sp: int,
    axis: str,
) -> jax.Array:
    Tl, H, D = q.shape
    n_rep = H // k.shape[1]
    idx = jax.lax.axis_index(axis)
    q_pos = idx * Tl + jnp.arange(Tl)  # absolute positions of local queries

    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    qf = q.astype(jnp.float32)
    acc = jnp.zeros((Tl, H, D), jnp.float32)
    m = jnp.full((H, Tl), NEG_INF, jnp.float32)  # running row max
    l = jnp.zeros((H, Tl), jnp.float32)  # running row sum

    perm = [(i, (i + 1) % sp) for i in range(sp)]
    kc, vc = k, v
    for step in range(sp):
        # after `step` rotations we hold the chunk originally on idx - step
        src = (idx - step) % sp
        k_pos = src * Tl + jnp.arange(Tl)
        kr = _repeat_kv(kc, n_rep).astype(jnp.float32)
        vr = _repeat_kv(vc, n_rep).astype(jnp.float32)
        logits = jnp.einsum("thd,shd->hts", qf, kr) * scale  # [H, Tl, Sl]
        mask = k_pos[None, :] <= q_pos[:, None]  # [Tl, Sl] causal
        logits = jnp.where(mask[None, :, :], logits, NEG_INF)
        # online softmax update (step 0 always contains the self-visible
        # diagonal, so m is finite from the first update onward)
        new_m = jnp.maximum(m, logits.max(axis=-1))
        corr = jnp.exp(m - new_m)  # [H, Tl]
        p = jnp.exp(logits - new_m[:, :, None])  # [H, Tl, Sl]
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr.T[:, :, None] + jnp.einsum("hts,shd->thd", p, vr)
        m = new_m
        if step < sp - 1:
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)

    out = acc / jnp.maximum(l.T[:, :, None], 1e-30)
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [T, H, D] (T divisible by mesh.shape[axis])
    k: jax.Array,  # [T, KH, D]
    v: jax.Array,  # [T, KH, D]
    *,
    mesh: Mesh,
    axis: str = "sp",
) -> jax.Array:
    """Causal self-attention, sequence sharded over ``mesh.shape[axis]``.

    Heads stay whole per device (compose with tp by head-sharding q/k/v
    outside). Padding must sit at the END of the sequence: padded keys have
    positions greater than every real query, so causality masks them.
    """
    sp = mesh.shape[axis]
    if sp == 1:
        from dynamo_tpu.ops.attention import causal_attention

        T = q.shape[0]
        return causal_attention(
            q, k, v, jnp.arange(T), jnp.asarray(T, jnp.int32)
        )
    if q.shape[0] % sp:
        raise ValueError(f"T={q.shape[0]} not divisible by {axis}={sp}")
    # compose with tensor parallelism: heads shard over "tp" (each GQA
    # group stays local), sequence over the ring axis
    tp = mesh.shape.get("tp", 1)
    head_axis = "tp" if tp > 1 and k.shape[1] % tp == 0 else None
    fn = partial(_ring_chunk, sp=sp, axis=axis)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis, head_axis, None),) * 3,
        out_specs=P(axis, head_axis, None),
        check_vma=False,
    )(q, k, v)
