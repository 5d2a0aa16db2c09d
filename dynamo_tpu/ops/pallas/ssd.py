"""Pallas TPU kernel of the Mamba-2 (SSD, arXiv:2405.21060) mixer's decode
step: a state ``H [P, N]`` a head a sequence, kept in float32 between
tokens, under a scalar decay a head, with B and C shared by the heads of a
group.

    H_t = a_t H_{t-1} + (dt_t x_t) (outer) B_t        a_t = exp(dt_t A)
    y_t = H_t C_t

``ssd_step`` is one call a layer a step over the burst's slots, chosen
beside its XLA twin in ``ops/attention.ssd_decode_step``. A program of the
grid takes one slot's row of the state POOL ``[layers, rows + 1, H, P, N]``
for a block of heads of one group, found by the slot's entry in the
scalar-prefetched ``rows`` (the trash row for a slot that owns none),
applies the step and writes the block back in place: every live row is
read once and written once, and that traffic IS the kernel (4 MiB a row a
layer at 32 heads of 128 x 256; the arithmetic is three passes of the
vector unit over the block and a reduction along the lanes, hidden behind
it). The state's ``N`` lies on the lanes, so B and C come in as rows ``[1,
N]`` and the quantities a channel of the head (the decay, ``dt x``, the
output) as columns ``[P, heads]``: the caller transposes the step's few
rows. The slots' new convolution tails are written to their rows of the
tails' pool in the same call, a head block's share of the channels a
program. The prefill's chunk form is plain XLA (``ops/attention.
ssd_chunk_prefill``).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.kda import head_block

# heads a program holds: 8 x 128 KiB of state in and out, two buffers each,
# is 4 MiB of VMEM
STEP_HEADS = 8


def _step_kernel(rows_ref, live_ref, dxT_ref, aT_ref, b_ref, c_ref, tail_ref,
                 s_ref, conv_ref, yT_ref, s_out_ref, conv_out_ref, *, hb: int):
    del rows_ref, conv_ref  # the index maps' and the alias's alone
    slot = pl.program_id(1)

    @pl.when(live_ref[slot] == 0)
    def _():
        # a slot that owns no row: nothing is fetched for it (its blocks
        # are the slot's before it, so no index changes) and nothing
        # written; its output is defined
        yT_ref[...] = jnp.zeros_like(yT_ref)

    @pl.when(live_ref[slot] != 0)
    def _():
        b, c = b_ref[...], c_ref[...]  # [1, N]: broadcast along sublanes
        for j in range(hb):
            a = aT_ref[:, j:j + 1]  # [P, 1]: broadcast along the lanes
            dx = dxT_ref[:, j:j + 1]
            h = s_ref[j] * a + dx * b  # [P, N]
            yT_ref[:, j:j + 1] = jnp.sum(h * c, axis=1, keepdims=True)
            s_out_ref[j] = h
        conv_out_ref[...] = tail_ref[...]  # this program's share of the tail


def ssd_step(
    pool: jax.Array,  # [L, rows + 1, H, P, N] float32 (aliased in place)
    conv: jax.Array,  # [L, rows + 1, taps - 1, channels] (aliased in place)
    rows: jax.Array,  # [B] int32: each slot's row (the last = trash)
    dx: jax.Array,  # [B, H, P] float32: dt x
    decay: jax.Array,  # [B, H] float32: exp(dt A)
    b: jax.Array,  # [B, G, N] float32
    c: jax.Array,  # [B, G, N] float32
    tail: jax.Array,  # [B, taps - 1, channels]: the slots' new tails
    *,
    layer: int,
    interpret: bool = False,
    scope: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step of every slot over layer ``layer`` of the state
    pool, the convolution tails replaced in the same call. Returns ``(y
    [B, H, P] float32 = H_t C_t, pool, conv)``. A slot on the trash row
    (inactive, or its row missing) costs no state traffic: the grid walks
    the slots innermost, a block of heads at a time, and such a slot's
    blocks are mapped to the live slot's before it, whose index then does
    not change."""
    L, R1, H, P, N = pool.shape
    B, G = b.shape[:2]
    hb = head_block(H // G, STEP_HEADS)  # a block's heads share a group
    nh = H // hb
    channels = conv.shape[-1]
    # a program's share of the tail's channels (all of them where they do
    # not divide: every program of a slot then writes the same tail)
    split = channels % nh == 0
    cw = channels // nh if split else channels
    rows = rows.astype(jnp.int32)
    live = rows != R1 - 1
    # each slot's blocks: its own row's, or those of the last live slot
    # before it (the first live slot's for the leading ones)
    at = jax.lax.cummax(jnp.where(live, jnp.arange(B), -1))
    fetch = rows[jnp.where(at >= 0, at, jnp.argmax(live))]

    def cols(x):  # [B, H, P] -> [B, H / hb, P, hb]: a head a lane
        return x.astype(jnp.float32).reshape(B, nh, hb, P).transpose(0, 1, 3, 2)

    col_spec = pl.BlockSpec((None, None, P, hb), lambda h, s, *_: (s, h, 0, 0))
    group_spec = pl.BlockSpec(
        (None, None, 1, N), lambda h, s, *_: (s, h * hb * G // H, 0, 0))
    tail_spec = pl.BlockSpec(
        (None, conv.shape[2], cw),
        lambda h, s, *_: (s, 0, h if split else 0))
    state_spec = pl.BlockSpec(
        (None, None, hb, P, N),
        lambda h, s, fetch_, live_: (layer, fetch_[s], h, 0, 0),
    )
    conv_spec = pl.BlockSpec(
        (None, None, conv.shape[2], cw),
        lambda h, s, fetch_, live_: (layer, fetch_[s], 0, h if split else 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nh, B),
        in_specs=[col_spec, col_spec, group_spec, group_spec, tail_spec,
                  state_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[col_spec, state_spec, conv_spec],
    )
    # operands count the two scalar-prefetch arguments: 7 = the state pool
    # -> output 1, 8 = the tails' pool -> output 2
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        yT, pool, conv = pl.pallas_call(
            functools.partial(_step_kernel, hb=hb),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, nh, P, hb), jnp.float32),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                jax.ShapeDtypeStruct(conv.shape, conv.dtype),
            ],
            input_output_aliases={7: 1, 8: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(fetch, live.astype(jnp.int32), cols(dx),
          cols(jnp.broadcast_to(decay[..., None], dx.shape)),
          b.astype(jnp.float32)[:, :, None, :],
          c.astype(jnp.float32)[:, :, None, :],
          tail.astype(conv.dtype), pool, conv)
    return yT.transpose(0, 1, 3, 2).reshape(B, H, P), pool, conv
