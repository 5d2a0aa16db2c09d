"""Pallas TPU kernel of the Mamba-1 (selective scan, arXiv:2312.00752)
mixer's decode step: a state ``S [N, C]`` a sequence (``N`` states a
channel, ``C`` channels), kept in float32 between tokens, under a decay
that differs in EVERY element, with B and C shared by the channels:

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n S_t[n, c] C_t[n]

``scan_step`` is one call a layer a step over the burst's slots, chosen
beside its XLA twin in ``ops/attention.scan_decode_step``. A program of
the grid takes one slot's row of the state POOL ``[layers, rows + 1, N,
C]``, found by the slot's entry in the scalar-prefetched ``rows`` (the
trash row for a slot that owns none), applies the step and writes the row
back in place: every live row is read once and written once, and that
traffic IS the kernel (320 KiB a row a layer at 16 x 5,120; the
arithmetic is one exponential and three passes of the vector unit an
element and a reduction over 16 sublanes, hidden behind it). ``ssd_step``'s
layout (the states on the lanes) wastes seven lanes of eight at N = 16:
here the CHANNELS lie on the lanes, so the quantities a channel (``dt``,
``dt x``, the output) come in as rows ``[1, C]``, B and C as columns ``[N,
1]``, and ``A`` whole ``[N, C]``, fetched once a call (its block never
changes). The slots' new convolution tails are written to their rows of
the tails' pool in the same call. The prefill's chunk form is plain XLA
(``ops/attention.scan_chunk_prefill``).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _step_kernel(rows_ref, live_ref, dt_ref, dx_ref, b_ref, c_ref, a_ref,
                 tail_ref, s_ref, conv_ref, y_ref, s_out_ref, conv_out_ref):
    del rows_ref, conv_ref  # the index maps' and the alias's alone
    slot = pl.program_id(0)

    @pl.when(live_ref[slot] == 0)
    def _():
        # a slot that owns no row: nothing is fetched for it (its blocks
        # are the slot's before it, so no index changes) and nothing
        # written; its output is defined
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live_ref[slot] != 0)
    def _():
        # [1, C] rows broadcast along the sublanes, [N, 1] columns along
        # the lanes
        s = s_ref[...] * jnp.exp(dt_ref[...] * a_ref[...]) + (
            dx_ref[...] * b_ref[...])
        y_ref[...] = jnp.sum(s * c_ref[...], axis=0, keepdims=True)
        s_out_ref[...] = s
        conv_out_ref[...] = tail_ref[...]


def scan_step(
    pool: jax.Array,  # [L, rows + 1, N, C] float32 (aliased in place)
    conv: jax.Array,  # [L, rows + 1, taps - 1, C] (aliased in place)
    rows: jax.Array,  # [B] int32: each slot's row (the last = trash)
    dt: jax.Array,  # [B, C] float32, softplus applied
    dx: jax.Array,  # [B, C] float32: dt x
    a: jax.Array,  # [N, C] float32: -exp(A_log), the states leading
    b: jax.Array,  # [B, N] float32
    c: jax.Array,  # [B, N] float32
    tail: jax.Array,  # [B, taps - 1, C]: the slots' new tails
    *,
    layer: int,
    interpret: bool = False,
    scope: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step of every slot over layer ``layer`` of the state
    pool, the convolution tails replaced in the same call. Returns ``(y
    [B, C] float32 = sum_n S_t C_t, pool, conv)``. A slot on the trash row
    (inactive, or its row missing) costs no state traffic: such a slot's
    blocks are mapped to the live slot's before it, whose index then does
    not change (``ssd_step``'s scheme)."""
    L, R1, N, C = pool.shape
    B = rows.shape[0]
    rows = rows.astype(jnp.int32)
    live = rows != R1 - 1
    # each slot's blocks: its own row's, or those of the last live slot
    # before it (the first live slot's for the leading ones)
    at = jax.lax.cummax(jnp.where(live, jnp.arange(B), -1))
    fetch = rows[jnp.where(at >= 0, at, jnp.argmax(live))]
    f32 = jnp.float32

    row_spec = pl.BlockSpec((None, 1, C), lambda s, *_: (s, 0, 0))
    col_spec = pl.BlockSpec((None, N, 1), lambda s, *_: (s, 0, 0))
    tail_spec = pl.BlockSpec(
        (None, conv.shape[2], C), lambda s, *_: (s, 0, 0))
    state_spec = pl.BlockSpec(
        (None, None, N, C), lambda s, fetch_, live_: (layer, fetch_[s], 0, 0))
    conv_spec = pl.BlockSpec(
        (None, None, conv.shape[2], C),
        lambda s, fetch_, live_: (layer, fetch_[s], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row_spec, row_spec, col_spec, col_spec,
                  pl.BlockSpec((N, C), lambda s, *_: (0, 0)), tail_spec,
                  state_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[row_spec, state_spec, conv_spec],
    )
    # operands count the two scalar-prefetch arguments: 8 = the state pool
    # -> output 1, 9 = the tails' pool -> output 2
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        y, pool, conv = pl.pallas_call(
            _step_kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, 1, C), f32),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                jax.ShapeDtypeStruct(conv.shape, conv.dtype),
            ],
            input_output_aliases={8: 1, 9: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(fetch, live.astype(jnp.int32), dt.astype(f32)[:, None, :],
          dx.astype(f32)[:, None, :], b.astype(f32)[:, :, None],
          c.astype(f32)[:, :, None], a.astype(f32),
          tail.astype(conv.dtype), pool, conv)
    return y[:, 0], pool, conv
