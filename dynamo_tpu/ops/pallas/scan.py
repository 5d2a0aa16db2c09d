"""Pallas TPU kernels of the Mamba-1 (selective scan, arXiv:2312.00752)
mixer, the decode step and the prefill's walk: a state ``S [N, C]`` a
sequence (``N`` states a channel, ``C`` channels), kept in float32 between tokens, under a decay
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
the tails' pool in the same call.

``scan_chunk`` is one call a layer of a prefill program, chosen beside its
XLA twin in ``ops/attention.scan_chunk_prefill`` (the twin, a ``lax.scan``
over chunks with an associative scan inside, runs on the CPU, under
``DYNAMO_PALLAS=0`` and as the tests' oracle; the kernel wherever Pallas is
active, interpreted off the chip). It IS the recurrence, token by token
with ``scan_step``'s arithmetic, the member's state held on the chip from
its row of the pool to its row of the pool: a grid step takes a block of a
member's tokens, and inside it 512 channels' state (8 vregs) rides in
registers through the block's walk, eight tokens a trip. Its roofline is
the vector unit, not HBM: 80 vregs of state a token a layer, ~8 vector
operations and one exponential each (``scan_chunk`` below has the count).
"""

from __future__ import annotations

import contextlib
import functools

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


# ------------------------------------------------------------ the prefill
CHUNK_TOKENS = 64  # tokens a grid step
CHUNK_LANES = 512  # channels whose state rides in registers through a
# block's walk: 16 x 512 float32 is 8 vregs, A's as many
_SUB = 8  # tokens a trip of the walk: a sublane tile of the rows
_LANES = 128
_LOG2E = 1.4426950408889634


def _rows_sums(p, sub):
    """``out[t] = sum over the sublanes of p[t]`` for eight ``[8, 128]``
    tiles, as ONE tile: three rounds of pairs, each half of a pair kept
    where the round's bit of the sublane index is clear and the other
    half rotated onto it, so a pair costs two selects, a rotation and an
    addition where eight separate reductions cost three of each a tile."""
    for bit in (1, 2, 4):
        keep = (sub & bit) == 0
        p = [jnp.where(keep, a, b) + pltpu.roll(jnp.where(keep, b, a), bit, 0)
             for a, b in zip(p[0::2], p[1::2])]
    return p[0]


def _chunk_kernel(rows_ref, fresh_ref, len_ref, layer_ref, x_ref, dt_ref,
                  b_ref, c_ref, a_ref, s0_ref, y_ref, s_ref, dx_ref, *,
                  lanes: int):
    del rows_ref, layer_ref  # the index maps' alone
    r, i = pl.program_id(0), pl.program_id(1)
    trips, _, ch = dt_ref.shape
    f32 = jnp.float32

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.where(fresh_ref[r] != 0, 0.0, s0_ref[...])

    @pl.when(i * trips * _SUB >= len_ref[r])
    def _():
        # wholly past the row's tokens: nothing walks, the output defined
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i * trips * _SUB < len_ref[r])
    def _():
        # dt x for the block, once. The scratch is 4-D like ``dt``'s block:
        # a row read of a 3-D ref is lowered through a transpose that keeps
        # the load from putting the row on every sublane itself (a
        # ``vperm`` a row instead). No ref below is indexed by a Python
        # int: each would be put on the device at trace time, ~50 small
        # transfers a trace, seconds of a program's set-up on the chip's
        # host
        dx_ref[...] = (dt_ref[...] * x_ref[...].astype(f32).reshape(
            dt_ref.shape))[None]
        first = r * 0  # a traced 0, the scratch's leading index
        sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANES), 0)
        tiles = lanes // _LANES

        def group(g, carry):
            cols = [pl.ds(pl.multiple_of(g * lanes + _LANES * j, _LANES),
                          _LANES) for j in range(tiles)]
            a = [a_ref[:, at] for at in cols]

            def trip(k, s):
                s = list(s)
                p = [[] for _ in cols]
                for t in range(_SUB):
                    at_t = k * _SUB + t
                    b, c = b_ref[at_t], c_ref[at_t]  # [N, 128], every lane
                    for j, at in enumerate(cols):
                        # a row of the block, read onto every sublane
                        dt = dt_ref[k, t:t + 1, at]
                        dx = dx_ref[first, k, t:t + 1, at]
                        s[j] = s[j] * jnp.exp2(dt * a[j]) + b * dx
                        sc = s[j] * c
                        p[j].append(functools.reduce(jnp.add, [
                            sc[n:n + _SUB]
                            for n in range(0, sc.shape[0], _SUB)]))
                for j, at in enumerate(cols):
                    y_ref[k, :, at] = _rows_sums(p[j], sub)
                return tuple(s)

            s = jax.lax.fori_loop(
                0, trips, trip, tuple(s_ref[:, at] for at in cols))
            for j, at in enumerate(cols):
                s_ref[:, at] = s[j]
            return carry

        jax.lax.fori_loop(0, ch // lanes, group, 0)


@functools.partial(jax.jit, static_argnames=("block", "lanes", "interpret"))
def scan_chunk(
    x: jax.Array,  # [R, T, C]: convolved, SiLU applied
    dt: jax.Array,  # [R, T, C] float32, softplus applied, 0 at a pad
    a: jax.Array,  # [N, C] float32: -exp(A_log)
    b: jax.Array,  # [R, T, N]
    c: jax.Array,  # [R, T, N]
    pool: jax.Array,  # [L, rows + 1, N, C] float32 (aliased in place)
    rows: jax.Array,  # [R] int32: each member's row (the last = trash)
    fresh: jax.Array,  # [R] bool: start from a zero state
    num_tokens: jax.Array,  # [R] int32: the members' real tokens
    layer: jax.Array,  # int32 scalar: the pool's layer
    *,
    block: int = CHUNK_TOKENS,
    lanes: int = CHUNK_LANES,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The selective scan over the members' new tokens, from and to their
    rows of layer ``layer`` of the state pool, in place. Returns ``(y [R,
    T, C] float32 = sum_n S_t C_t, pool)``; the caller adds ``D x``.

    Grid ``(member, token block)``, the blocks of a member in order. A
    block's ``x``, ``dt`` and ``y`` are ``[block, C]`` (as ``[block / 8,
    8, C]``, the same bytes: a token's row is then read onto every sublane
    by the load itself), B and C come with each value on every lane (``[T,
    N, 128]``, made in XLA: a lane broadcast in the kernel costs the XLU
    more than the bytes cost HBM), A whole, fetched once. The state block
    ``[N, C]`` of the OUTPUT is resident over a member's blocks and is the
    carry between them; within a block ``lanes`` channels at a time take
    their state into registers for the walk. A token a 128-lane tile costs
    the vector unit 8 multiplications, 2 + 1 additions, 2 exponentials
    (``exp2`` of ``dt (A log2 e)``, the scaling done once outside) and 3.5
    slots of the eight tokens' shared sublane reduction (``_rows_sums``):
    153 bundles a trip of 8 tokens x 512 channels for a v5e, 3.6 of its 4
    vector slots packed: ~0.12 us a token a layer at 5,120 channels and
    1.5 GHz (0.115 read on the chip, PERF.md section 6, PR 56). A padded token (``dt == 0``) leaves the state
    as it was; a block wholly past ``num_tokens`` is neither fetched (its
    index is the last real block's) nor walked, and its ``y`` is zero. A
    member on the trash row reads and writes that row (two such members
    in turn: the grid is sequential). T is padded to the block here.

    A jit of its own with ``layer`` a VALUE (scalar-prefetched): the
    kernel's body is ~600 operations to trace and to lower (0.3 s of the
    host a call), and a model's scan layers then share one trace a shape
    and one lowering a program, where a call a layer made a prefill
    program's set-up 0.9 s longer each time it was lowered. The trace
    names the Mosaic call after this jit, ``scan_chunk``: that is the
    leaf ``models/regions.py: SCOPE_SCAN_CHUNK``, so no scope is opened
    around the call."""
    _, _, N, C = pool.shape
    R, T, _ = x.shape
    f32 = jnp.float32
    lanes = next(w for w in (lanes, 256, _LANES) if C % w == 0)
    tb = min(block, -(-T // 16) * 16)
    pad = -T % tb
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (x, dt, b, c))
    nb = (T + pad) // tb

    def tokens(*shape):
        # a block past the row's tokens is the row's last real one again:
        # its index does not change, so nothing is fetched for it
        return pl.BlockSpec(
            (None, *shape),
            lambda r, i, rows_, fresh_, len_, layer_: (
                r, jnp.minimum(i, jnp.maximum((len_[r] - 1) // tb, 0)),
                *(0,) * (len(shape) - 1)))

    def tiles(v):  # [R, T, C] -> [R, T / 8, 8, C]: the same bytes
        return v.reshape(R, (T + pad) // _SUB, _SUB, C)

    def wide(v):  # [R, T, N] -> [R, T, N, 128]: a value on every lane
        return jnp.broadcast_to(v.astype(f32)[..., None], (*v.shape, _LANES))

    state_spec = pl.BlockSpec(
        (None, None, N, C),
        lambda r, i, rows_, fresh_, len_, layer_: (
            layer_[0], rows_[r], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R, nb),
        in_specs=[tokens(tb, C), tokens(tb // _SUB, _SUB, C),
                  tokens(tb, N, _LANES), tokens(tb, N, _LANES),
                  pl.BlockSpec((N, C), lambda r, i, *_: (0, 0)), state_spec],
        out_specs=[
            pl.BlockSpec((None, tb // _SUB, _SUB, C),
                         lambda r, i, *_: (r, i, 0, 0)),
            state_spec],
        scratch_shapes=[pltpu.VMEM((1, tb // _SUB, _SUB, C), f32)],
    )
    y, pool = pl.pallas_call(
        functools.partial(_chunk_kernel, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, (T + pad) // _SUB, _SUB, C), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the four scalar-prefetch arguments
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2**20,
        ),
        interpret=interpret,
    )(rows.astype(jnp.int32), fresh.astype(jnp.int32),
      jnp.minimum(num_tokens.astype(jnp.int32), T),
      jnp.asarray(layer, jnp.int32).reshape(1), x, tiles(dt.astype(f32)),
      wide(b), wide(c), a.astype(f32) * _LOG2E, pool)
    return y.reshape(R, T + pad, C)[:, :T], pool
