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
  layer at 64 heads of 128 x 128). It takes the layer's projections as the
  matmuls leave them (q | k | v apart, ``[B, 3, H dk]``) and the slot's
  block of the tails' pool, and does in VMEM what lies between them and
  the state: the causal convolution over [the tail's rows; the new row] in
  float32, SiLU, q's and k's norm a head, ``beta v``. The state's ``dv``
  lies on the lanes, so the quantities a key channel (alpha, k, q) are
  needed as columns ``[dk, heads]``: ONE transpose of a lane tile that
  stacks the block's heads' rows of the three gives them all. The tail
  shifted by the new row goes back to the slot's block of the tails' pool
  in the same call. ``_step_kernel`` is at the file's end.
- ``kda_chunk`` (prefill, packed prefill, chunks): the chunkwise form
  WHOLE, a grid step a (sequence, block of heads, block of 64 tokens). What
  a block needs that does not depend on the state (the decayed keys and
  queries, the causal products, the triangular system: the terms of ``ops/
  attention.kda_chunk_operands``, its XLA twin) is formed in VMEM from the
  layer's operands as they are; then the state is carried through the
  block, read from its row of the pool once before a sequence's first
  block (or zero at its start) and written back once after the last.
  Nothing of a block's operands crosses HBM. No scan a token anywhere.

Matrix products are float32 at ``HIGHEST``: ``U`` is a difference of
values and the state's read-out of them, which cancels.

The lines of ``_head_operands``, ``_chunk_kernel`` and ``kda_chunk`` are
part of the prefill programs' compile-cache keys (a Mosaic body serializes
them): what is added goes after them, or into the room above them.
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
# and of kda_chunk: 8 heads' q, k, v, g, o of one block and their state
CHUNK_HEADS = 8


def kda_step(
    pool: jax.Array,  # [L, rows + 1, H, dk, dv] float32 (aliased in place)
    conv: jax.Array,  # [L, rows + 1, taps - 1, 3, H * dk] (aliased in place)
    rows: jax.Array,  # [B] int32: each slot's row (the last = trash)
    x: jax.Array,  # [B, 3, H * dk]: the q | k | v projections of the token
    taps: jax.Array,  # [taps, 3, H * dk]: the layer's convolutions
    alpha: jax.Array,  # [B, H, dk] float32: exp(g), the decay a channel
    beta: jax.Array,  # [B, H] float32
    *,
    layer: int,
    interpret: bool = False,
    scope: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step of every slot over layer ``layer`` of the state
    pool, from the layer's projections: a program (block of heads, slot)
    reads the slot's block of the tails' pool and of ``x``, convolves,
    normalises, steps the state and writes state and shifted tail back
    (``_step_kernel``). Returns ``(o [B, H, dv] float32, pool, conv)``. A
    slot on the trash row (inactive, or its row missing) costs no state
    or tail traffic: the grid walks the slots innermost, a block of heads
    at a time, and such a slot's blocks are mapped to the live slot's
    before it, whose index then does not change."""
    L, R1, H, dk, dv = pool.shape
    B = x.shape[0]
    hb = head_block(H, STEP_HEADS)
    assert dk == dv and 3 * hb <= _LANES, (dk, dv, hb)
    rows = rows.astype(jnp.int32)
    live = rows != R1 - 1
    # each slot's blocks: its own row's, or those of the last live slot
    # before it (the first live slot's for the leading ones)
    at = jax.lax.cummax(jnp.where(live, jnp.arange(B), -1))
    fetch = rows[jnp.where(at >= 0, at, jnp.argmax(live))]
    state_spec = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda h, b, fetch_, live_: (layer, fetch_[b], h, 0, 0),
    )
    conv_spec = pl.BlockSpec(
        (None, None) + conv.shape[2:4] + (hb * dk,),
        lambda h, b, fetch_, live_: (layer, fetch_[b], 0, 0, h),
    )
    row_spec = pl.BlockSpec((None, hb, dv), lambda h, b, *_: (b, h, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // hb, B),
        in_specs=[
            pl.BlockSpec((None, 3, hb * dk), lambda h, b, *_: (b, 0, h)),
            pl.BlockSpec(taps.shape[:2] + (hb * dk,),
                         lambda h, b, *_: (0, 0, h)),
            row_spec,
            pl.BlockSpec((None, 1, H), lambda h, b, *_: (b, 0, 0)),
            state_spec, conv_spec,
        ],
        out_specs=[row_spec, state_spec, conv_spec],
        # the block's heads' q, k and alpha rows, a lane tile to transpose
        scratch_shapes=[pltpu.VMEM((_LANES, dk), jnp.float32)],
    )
    # operands count the two scalar-prefetch arguments: 6 = the state pool
    # -> output 1, 7 = the tails' pool -> output 2
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        o, pool, conv = pl.pallas_call(
            functools.partial(_step_kernel, hb=hb),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                jax.ShapeDtypeStruct(conv.shape, conv.dtype),
            ],
            input_output_aliases={6: 1, 7: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(fetch, live.astype(jnp.int32), x, taps,
          alpha.astype(jnp.float32),
          beta.astype(jnp.float32).reshape(B, 1, H), pool, conv)
    return o, pool, conv


def _step_rows(x_ref, taps_ref, c_ref, c_out_ref):
    """``_step_kernel``'s first half: the causal convolution over [the
    slot's tail; the new row] in float32, in the order of the taps, then
    SiLU: the token's q | k | v rows ``[3, hb dk]`` float32. The tail the
    token leaves (the old rows but the first, then the new row) goes to
    ``c_out_ref``. x_ref: [3, hb dk]; taps_ref: [taps, 3, hb dk]; c_ref,
    c_out_ref: [taps - 1, 3, hb dk]."""
    f32 = jnp.float32
    n = c_ref.shape[0]
    x = x_ref[...].astype(c_ref.dtype)
    # models/llama.py: _causal_taps' sum, tap 0 on the oldest row
    conv = taps_ref[0].astype(f32) * c_ref[0].astype(f32)
    for i in range(1, n):
        conv = conv + taps_ref[i].astype(f32) * c_ref[i].astype(f32)
    conv = conv + taps_ref[n].astype(f32) * x.astype(f32)
    for i in range(n - 1):
        c_out_ref[i] = c_ref[i + 1]
    c_out_ref[n - 1] = x
    return jax.nn.silu(conv)


_LANES = 128


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=_HI)


def _dot_nt(a, b):  # a b^T
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI)


def _head_operands(q, k, v, g, beta, *, sub: int):
    """The state-independent half of one head's block, in VMEM:
    ``ops/attention.kda_chunk_operands`` term for term. q, k, g: [C, dk];
    v: [C, dv]; beta: [C, 1]. Returns ``(ut [C, dv], w [C, dk], qd [C,
    dk], b [C, C], kend [C, dk], G [C, dk])``."""
    C, dk = q.shape
    dv = v.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    # the decay summed from the block's start: shifted sums down the
    # tokens, doubling the shift (float32 additions, no product)
    G, shift = g, 1
    while shift < C:
        G = G + jnp.where(tok >= shift, pltpu.roll(G, shift, 0), 0.0)
        shift *= 2
    eg = jnp.exp(G)
    a_rows, b_rows = [], []
    for r0 in range(0, C, sub):
        # the sub-block's reference: the decay up to the token before it
        ref = G[r0 - 1:r0] if r0 else jnp.zeros((1, dk), jnp.float32)
        e_in = jnp.exp(G[r0:r0 + sub] - ref)  # <= 1
        lhs = jnp.concatenate(
            [k[r0:r0 + sub] * e_in, q[r0:r0 + sub] * e_in], axis=0)
        # the keys up to the sub-block's end, brought to the reference:
        # those of the sub-block itself back (FLA's e^80 bound), those
        # before it forward (<= 1)
        n = r0 + sub
        cap = jnp.where(tok[:n] >= r0, 80.0, 0.0)
        both = _dot_nt(lhs, k[:n] * jnp.exp(jnp.minimum(ref - G[:n], cap)))
        if n < C:
            both = jnp.concatenate(
                [both, jnp.zeros((2 * sub, C - n), jnp.float32)], axis=1)
        a_rows.append(both[:sub])
        b_rows.append(both[sub:])
    a = jnp.where(col < row, jnp.concatenate(a_rows, axis=0), 0.0)
    b = jnp.where(col <= row, jnp.concatenate(b_rows, axis=0), 0.0)
    # (I + beta a)^-1 [beta v | beta k e^G] by forward substitution in the
    # recurrence's order: a sub-block's unit lower system a token at a
    # time on the vector unit, the sub-blocks beneath it by a product
    tm = beta * a
    rhs = beta * jnp.concatenate([v, k * eg], axis=1)
    done = []
    for r0 in range(0, C, sub):
        x = rhs[r0:r0 + sub]
        if r0:
            solved = jnp.concatenate(
                done + [jnp.zeros((C - r0, dv + dk), jnp.float32)], axis=0)
            x = x - _dot(tm[r0:r0 + sub], solved)
        for s in range(sub - 1):
            # row s is final: take it out of the rows beneath (tm is
            # strictly lower, so the rows above get nothing)
            x = x - tm[r0:r0 + sub, r0 + s:r0 + s + 1] * x[s:s + 1]
        done.append(x)
    sol = jnp.concatenate(done, axis=0)
    return (sol[:, :dv], sol[:, dv:], q * eg, b,
            k * jnp.exp(G[C - 1:C] - G), G)


def _chunk_kernel(rows_ref, fresh_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                  s0_ref, o_ref, s_ref, *, sub: int):
    del rows_ref
    hb, dk, dv = s_ref.shape
    keep = (fresh_ref[pl.program_id(0)] == 0).astype(jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...] * keep

    C = q_ref.shape[0]
    betas = beta_ref[...]  # [C, H]: every head's
    lane = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    first = pl.program_id(1) * hb
    # the heads' chains are independent: unrolled, the scheduler overlaps
    # one's substitution steps with another's products
    for j in range(hb):
        at_k, at_v = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        beta = jnp.sum(
            jnp.where(lane == first + j, betas, 0.0), axis=1, keepdims=True)
        ut, w, qd, b, kend, G = _head_operands(
            q_ref[:, at_k], k_ref[:, at_k], v_ref[:, at_v], g_ref[:, at_k],
            beta, sub=sub)
        s = s_ref[j]  # [dk, dv]
        ws = _dot(jnp.concatenate([w, qd], axis=0), s)
        u = ut - ws[:C]  # [C, dv]
        o_ref[:, at_v] = ws[C:] + _dot(b, u)
        # one transpose (of a whole lane tile of rows) gives K'^T and,
        # from G's last row, the block's whole decay a key channel as a
        # column
        stack = [kend, G]
        if 2 * C < _LANES:
            stack.append(jnp.zeros((_LANES - 2 * C, dk), jnp.float32))
        kg = jnp.concatenate(stack, axis=0).T
        s_ref[j] = (jnp.exp(kg[:, 2 * C - 1:2 * C]) * s
                    + _dot(kg[:, :C], u))


def kda_chunk(
    q: jax.Array,  # [N, T, H dk] float32: normalised and scaled
    k: jax.Array,  # [N, T, H dk] float32: normalised
    v: jax.Array,  # [N, T, H dv] float32
    g: jax.Array,  # [N, T, H dk] float32: the log decay a channel, <= 0
    beta: jax.Array,  # [N, T, H] float32
    pool: jax.Array,  # [L, rows + 1, H, dk, dv] float32 (aliased in place)
    rows: jax.Array,  # [N] int32: each sequence's row (the last = trash)
    fresh: jax.Array,  # [N] bool: start from a zero state
    *,
    layer: int,
    block: int,
    sub: int,
    interpret: bool = False,
    scope: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The chunkwise form whole, from and to each sequence's row of the
    pool: read once before the first block (or zero), written once after
    the last. It takes the layer's operands as they are (a head is a lane
    tile of a block) and forms a block's state-independent half in VMEM at
    the grid step that consumes it, term for term as ``ops/attention.
    kda_chunk_operands`` does: the decay summed down the block (float32
    additions), keys and queries brought to their sub-block's reference
    (every exponent non-positive but inside a sub-block of ``sub``), the
    causal products A and B, ``T^-1 [beta v | beta k e^G]`` by forward
    substitution in the recurrence's order. Then ``U = U~ - W S; O = Q' S
    + B U; S = diag(gamma) S + K'^T U``. No solver's custom call, no
    re-layout: 8 heads' operands of a block are 256 KiB each in VMEM (two
    buffers), their state 512 KiB in and out. T is a multiple of
    ``block``, ``block`` of ``sub``. Returns ``(o [N, T, H dv] float32,
    pool)``. A state that is not finite times zero would not be zero: a
    pool never holds one (a row is written by this kernel and ``kda_step``
    alone)."""
    _, _, H, dk, dv = pool.shape
    N, T, _ = q.shape
    hb = head_block(H, CHUNK_HEADS)

    def blocked(width):
        return pl.BlockSpec(
            (None, block, width), lambda n, h, i, *_: (n, i, h))

    state_spec = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda n, h, i, rows_, fresh_: (layer, rows_[n], h, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N, H // hb, T // block),
        in_specs=[
            blocked(hb * dk), blocked(hb * dk), blocked(hb * dv),
            blocked(hb * dk),
            pl.BlockSpec((None, block, H), lambda n, h, i, *_: (n, i, 0)),
            state_spec,
        ],
        out_specs=[blocked(hb * dv), state_spec],
    )
    # operands count the two scalar-prefetch arguments: 7 = the pool
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        o, pool = pl.pallas_call(
            functools.partial(_chunk_kernel, sub=sub),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((N, T, H * dv), jnp.float32),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            ],
            input_output_aliases={7: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(rows.astype(jnp.int32), fresh.astype(jnp.int32), q, k, v, g, beta,
          pool)
    return o, pool


def _step_kernel(rows_ref, live_ref, x_ref, taps_ref, a_ref, beta_ref, s_ref,
                 c_ref, o_ref, s_out_ref, c_out_ref, cols_ref, *, hb: int):
    """``kda_step``'s program: one slot's block of ``hb`` heads. a_ref:
    [hb, dk]; beta_ref: [1, H] (every head's); s_ref, s_out_ref: [hb, dk,
    dv]; o_ref: [hb, dv]; cols_ref: [128, dk] scratch; the rest as
    ``_step_rows`` takes them."""
    del rows_ref  # the index maps' alone
    b = pl.program_id(1)
    first = pl.program_id(0) * hb  # the block's first head

    @pl.when(live_ref[b] == 0)
    def _():
        # a slot that owns no row: nothing is fetched for it (its blocks
        # are the slot's before it, so no index changes) and nothing
        # written; its output is defined
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[b] != 0)
    def _():
        dk, dv = s_ref.shape[1:]
        conv = _step_rows(x_ref, taps_ref, c_ref, c_out_ref)
        # q's and k's channels a head as ROWS of one lane tile, normalised
        # a head; alpha's rows beneath them
        for j in range(hb):
            at = slice(j * dk, (j + 1) * dk)
            cols_ref[j:j + 1, :] = conv[0:1, at]
            cols_ref[hb + j:hb + j + 1, :] = conv[1:2, at]
        q, k = cols_ref[:hb, :], cols_ref[hb:2 * hb, :]
        cols_ref[:hb, :] = q * jax.lax.rsqrt(
            jnp.sum(q * q, axis=1, keepdims=True) + 1e-6) * dk ** -0.5
        cols_ref[hb:2 * hb, :] = k * jax.lax.rsqrt(
            jnp.sum(k * k, axis=1, keepdims=True) + 1e-6)
        cols_ref[2 * hb:3 * hb, :] = a_ref[...]
        # one transpose of the tile: a head of q, k, alpha a column (the
        # rows past 3 hb are whatever the scratch held: never read)
        cols = cols_ref[...].T  # [dk, 128]
        betas = beta_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
        for j in range(hb):
            q = cols[:, j:j + 1]  # [dk, 1]: broadcast along the lanes
            k = cols[:, hb + j:hb + j + 1]
            a = cols[:, 2 * hb + j:2 * hb + j + 1]
            beta = jnp.sum(
                jnp.where(lane == first + j, betas, 0.0), axis=1, keepdims=True)
            sd = s_ref[j] * a  # decayed state [dk, dv]
            r = jnp.sum(sd * k, axis=0, keepdims=True)  # S'^T k: [1, dv]
            u = beta * conv[2:3, j * dv:(j + 1) * dv] - beta * r
            sn = sd + k * u
            o_ref[j:j + 1, :] = jnp.sum(sn * q, axis=0, keepdims=True)
            s_out_ref[j] = sn
