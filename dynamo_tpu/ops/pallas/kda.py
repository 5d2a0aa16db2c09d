"""Pallas TPU kernels of Kimi Delta Attention (KDA, arXiv:2510.26692): the
gated delta rule with a decay a channel over a state ``S [dk, dv]`` a head
a sequence, kept in float32 between tokens.

    S' = diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Two programs, each chosen beside its XLA twin in ``ops/attention.py``:

- ``kda_step`` (decode): one call a layer a step over the burst's slots. A
  program of the grid takes one slot's row of the state POOL ``[layers,
  rows + 1, H, dk, dv]`` for a block of heads, found by the slot's entry in
  the scalar-prefetched ``rows`` (the trash row for a slot that owns none),
  applies the step and writes the block back in place: every live row is
  read once and written once, and that traffic IS the kernel (4 MiB a row a
  layer at 64 heads of 128 x 128; the arithmetic is a few passes of the
  vector unit over the block, hidden behind it). The state's ``dv`` lies on
  the lanes, so the quantities a key channel (alpha, k, q) come in as
  columns ``[dk, heads]`` (the caller transposes the step's few rows) and
  the quantities a value channel (v, the output) as rows. The slots' new
  convolution tails are written to their rows of the tails' pool in the
  same call, a head block's channels a program.
- ``kda_chunk`` (prefill, packed prefill, chunks): the chunkwise form's
  SEQUENTIAL part. What a block of 64 tokens needs that does not depend on
  the state (the decayed keys and queries, the triangular systems: ``ops/
  attention.kda_chunk_operands``) is plain batched XLA over all blocks at
  once; this kernel carries the state through a sequence's blocks in VMEM:
  ``U = U~ - W S; O = Q' S + B U; S = diag(gamma) S + K'^T U``, four matrix
  products a head a block, the state read from its row of the pool once
  before the first block (or zero at a sequence's start) and written back
  once after the last. No scan a token anywhere.

Matrix products are float32 at ``HIGHEST``: ``U`` is a difference of
values and the state's read-out of them, which cancels.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def head_block(heads: int, most: int) -> int:
    """Heads a program of either kernel takes: the largest divisor of
    ``heads`` no larger than ``most``."""
    return max(h for h in range(1, min(heads, most) + 1) if heads % h == 0)


# heads a program of kda_step holds: 16 x 64 KiB of state in and out, two
# buffers each, is 4 MiB of VMEM
STEP_HEADS = 16
# and of kda_chunk: 8 heads' operands of one block and their state
CHUNK_HEADS = 8


def _step_kernel(rows_ref, live_ref, qT_ref, kT_ref, aT_ref, bv_ref, bb_ref,
                 tail_ref, s_ref, c_ref, o_ref, s_out_ref, c_out_ref, *, hb: int):
    del rows_ref, c_ref  # the index maps' and the alias's alone
    b = pl.program_id(1)

    @pl.when(live_ref[b] == 0)
    def _():
        # a slot that owns no row: nothing is fetched for it (its blocks
        # are the slot's before it, so no index changes) and nothing
        # written; its output is defined
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[b] != 0)
    def _():
        for j in range(hb):
            a = aT_ref[:, j:j + 1]  # [dk, 1]: broadcast along the lanes
            k = kT_ref[:, j:j + 1]
            q = qT_ref[:, j:j + 1]
            sd = s_ref[j] * a  # decayed state [dk, dv]
            r = jnp.sum(sd * k, axis=0, keepdims=True)  # S'^T k: [1, dv]
            u = bv_ref[j:j + 1, :] - bb_ref[j:j + 1, :] * r
            sn = sd + k * u
            o_ref[j:j + 1, :] = jnp.sum(sn * q, axis=0, keepdims=True)
            s_out_ref[j] = sn
        c_out_ref[...] = tail_ref[...]  # this head block's convolution tail


def kda_step(
    pool: jax.Array,  # [L, rows + 1, H, dk, dv] float32 (aliased in place)
    conv: jax.Array,  # [L, rows + 1, taps - 1, 3, H * dk] (aliased in place)
    rows: jax.Array,  # [B] int32: each slot's row (the last = trash)
    q: jax.Array,  # [B, H, dk] float32: normalised and scaled
    k: jax.Array,  # [B, H, dk] float32: normalised
    v: jax.Array,  # [B, H, dv] float32
    alpha: jax.Array,  # [B, H, dk] float32: exp(g), the decay a channel
    beta: jax.Array,  # [B, H] float32
    tail: jax.Array,  # [B, taps - 1, 3, H * dk]: the slots' new tails
    *,
    layer: int,
    interpret: bool = False,
    scope: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step of every slot over layer ``layer`` of the state
    pool, the convolution tails shifted in the same call. Returns ``(o [B,
    H, dv] float32, pool, conv)``. A slot on the trash row (inactive, or
    its row missing) costs no state traffic: the grid walks the slots
    innermost, a block of heads at a time, and such a slot's blocks are
    mapped to the live slot's before it, whose index then does not
    change."""
    L, R1, H, dk, dv = pool.shape
    B = q.shape[0]
    hb = head_block(H, STEP_HEADS)
    nh = H // hb
    rows = rows.astype(jnp.int32)
    live = rows != R1 - 1
    # each slot's blocks: its own row's, or those of the last live slot
    # before it (the first live slot's for the leading ones)
    at = jax.lax.cummax(jnp.where(live, jnp.arange(B), -1))
    fetch = rows[jnp.where(at >= 0, at, jnp.argmax(live))]

    def cols(x):  # [B, H, dk] -> [B, H / hb, dk, hb]: a head a lane
        return x.astype(jnp.float32).reshape(B, nh, hb, dk).transpose(0, 1, 3, 2)

    bb = jnp.broadcast_to(beta.astype(jnp.float32)[..., None], (B, H, dv))
    bv = bb * v.astype(jnp.float32)
    col_spec = pl.BlockSpec(
        (None, None, dk, hb), lambda h, b, *_: (b, h, 0, 0))
    row_spec = pl.BlockSpec((None, hb, dv), lambda h, b, *_: (b, h, 0))
    tail_spec = pl.BlockSpec(
        (None,) + conv.shape[2:4] + (hb * dk,), lambda h, b, *_: (b, 0, 0, h))
    state_spec = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda h, b, fetch_, live_: (layer, fetch_[b], h, 0, 0),
    )
    conv_spec = pl.BlockSpec(
        (None, None) + conv.shape[2:4] + (hb * dk,),
        lambda h, b, fetch_, live_: (layer, fetch_[b], 0, 0, h),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nh, B),
        in_specs=[col_spec, col_spec, col_spec, row_spec, row_spec,
                  tail_spec, state_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[row_spec, state_spec, conv_spec],
    )
    # operands count the two scalar-prefetch arguments: 8 = the state pool
    # -> output 1, 9 = the tails' pool -> output 2
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        o, pool, conv = pl.pallas_call(
            functools.partial(_step_kernel, hb=hb),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                jax.ShapeDtypeStruct(conv.shape, conv.dtype),
            ],
            input_output_aliases={8: 1, 9: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(fetch, live.astype(jnp.int32), cols(q), cols(k), cols(alpha), bv,
          bb, tail.astype(conv.dtype), pool, conv)
    return o, pool, conv


def _chunk_kernel(rows_ref, fresh_ref, ut_ref, w_ref, qd_ref, b_ref, kx_ref,
                  s0_ref, o_ref, s_ref, *, hb: int, block: int):
    del rows_ref
    keep = (fresh_ref[pl.program_id(0)] == 0).astype(jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...] * keep

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32,
                       precision=_HI)

    for j in range(hb):
        s = s_ref[j]  # [dk, dv]
        u = ut_ref[j] - dot(w_ref[j], s)  # [C, dv]
        o_ref[j] = dot(qd_ref[j], s) + dot(b_ref[j], u)
        kx = kx_ref[j]  # [dk, 2C]: K'^T, then gamma on every lane
        s_ref[j] = kx[:, block:block + 1] * s + dot(kx[:, :block], u)


def kda_chunk_scan(
    ut: jax.Array,  # [N, H, nb, C, dv] float32: T^-1 (beta v)
    w: jax.Array,  # [N, H, nb, C, dk]: T^-1 (beta k decayed from the start)
    qd: jax.Array,  # [N, H, nb, C, dk]: q decayed from the block's start
    b: jax.Array,  # [N, H, nb, C, C]: decayed q . k, causal
    kx: jax.Array,  # [N, H, nb, dk, 2C]: (k decayed to the block's end)^T,
    # then the block's whole decay gamma [dk] repeated C times
    pool: jax.Array,  # [L, rows + 1, H, dk, dv] float32 (aliased in place)
    rows: jax.Array,  # [N] int32: each sequence's row (the last = trash)
    fresh: jax.Array,  # [N] bool: start from a zero state
    *,
    layer: int,
    interpret: bool = False,
    scope: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The state carried through each sequence's blocks, from and to its
    row of the pool: read once before the first block (or zero), written
    once after the last. Returns ``(o [N, H, nb, C, dv], pool)``. A state
    that is not finite times zero would not be zero: a pool never holds
    one (a row is written by this kernel and ``kda_step`` alone)."""
    N, H, nb, C, dv = ut.shape
    dk = w.shape[-1]
    hb = head_block(H, CHUNK_HEADS)

    def blocked(last2):
        return pl.BlockSpec(
            (None, hb, None) + last2, lambda n, h, i, *_: (n, h, i, 0, 0))

    state_spec = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda n, h, i, rows_, fresh_: (layer, rows_[n], h, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N, H // hb, nb),
        in_specs=[
            blocked((C, dv)), blocked((C, dk)), blocked((C, dk)),
            blocked((C, C)), blocked((dk, 2 * C)), state_spec,
        ],
        out_specs=[blocked((C, dv)), state_spec],
    )
    # operands count the two scalar-prefetch arguments: 7 = the pool
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        o, pool = pl.pallas_call(
            functools.partial(_chunk_kernel, hb=hb, block=C),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((N, H, nb, C, dv), jnp.float32),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            ],
            input_output_aliases={7: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(rows.astype(jnp.int32), fresh.astype(jnp.int32), ut, w, qd, b, kx,
          pool)
    return o, pool
