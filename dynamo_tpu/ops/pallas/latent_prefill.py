"""Pallas TPU kernel: prefill attention over a paged LATENT cache.

The prefill side of ``latent_decode.py``: a call's queries (a prompt, a
chunk of one at ``start_pos > 0``, the members of a pack, a verify's few
rows) attend over each sequence's paged latent rows ``[c | k_r | lane
padding]``, NOT absorbed: a block of rows is up-projected to per-head keys
and values (``c W_uk[h]``, ``c W_uv[h]``; the roped key is shared by the
heads) and scored at ``(dn + dr + dv) x 2`` FLOP a pair where the absorbed
form pays ``(2 dc + dr) x 2``. The mathematics and the types are the XLA
walk's (``ops/attention.latent_prefill_walk``, the twin): operands in the
model's dtype, float32 accumulation, scores and running softmax,
probabilities rounded to the values' dtype for ``p . v``.

What the walk cannot do and this does: a tile-by-block score lives and
dies in VMEM. The walk's ``[H, T, 256]`` float32 scores went through HBM
three times a block-step, 100 MB where the block's latents are 0.33 MB,
and its one tile of all the call's rows scored every block against rows
that could not see it.

- Grid ``(pack member, group of heads)``. Heads are independent, so a
  group repeats only the blocks' fetch, never an up-projection, and keeps
  its running ``(m, l, acc)`` for every query tile in VMEM scratch.
- Inside a program the BLOCKS are outermost: a block's live pages come by
  DMA from the pool (``memory_space=ANY``; the table, ``start_pos``,
  ``kv_len`` and ``layer`` are scalar-prefetch operands, ``layer`` run-time
  so the layers share one trace), the next block in flight while one is
  scored; its tail past ``kv_len`` is zeroed before use (0 x a non-finite
  value would be NaN). A head of the group up-projects the block ONCE and
  every query tile that can see the block scores it, the group's heads
  as independent chains in one tile-by-block body (a head's softmax runs
  behind another's matmuls: 16% over a head at a time on the chip).
- A query tile visits the blocks ``counts`` names: the caller makes them
  with ``ops/attention.prefill_blocks`` under ``latent_prefill_tiling``,
  the one function the engine's ``prefill_kv`` counters and the tests use.
  A fresh 1,024-row chunk scores 10 of 16 tile-block pairs; a padded
  member (``counts`` all 0) fetches nothing.
- Scores are held TRANSPOSED, ``[block tokens, tile rows]``: the softmax's
  maximum and sum run down the sublanes (plain vector operations, no
  cross-lane reduce), the running ``m`` and ``l`` are one row ``[1, tq]``
  a (head, tile) where the row-major form keeps ``[tq, 128]``, and the
  accumulator is ``acc^T [dv, tq]``, transposed once when a tile is
  written out.

The pool is READ ONLY (the call's own rows are written before it, as for
the walk): no aliasing. ``q_rope`` comes padded a head to the width of the
pool's lanes past ``dc`` (``[k_r | 0]``: the writers pad rows with zeros),
so the roped score is one aligned product against those lanes.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.fused_decode import NEG_INF

# Heads a program holds. The block's fetch is repeated once a group (0.33
# MB against ~9 us of matmuls a head a block at the published widths), and
# a group's queries, weights, output and accumulators sit in VMEM: 2.7 MiB
# a head at 1,024 rows. Chosen on the chip: PERF.md section 6, PR 36.
_GROUP_HEADS = 8
_VMEM_LIMIT = 100 * 1024 * 1024  # of a v5e core's 128 MiB


def head_group(num_heads: int) -> int:
    """Heads a program of the kernel holds: the largest divisor of
    ``num_heads`` no larger than ``_GROUP_HEADS``."""
    return max(g for g in range(1, _GROUP_HEADS + 1) if num_heads % g == 0)


def _latent_prefill_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,  # [N, P] int32
    start_ref,  # [N] int32: position of a member's first row
    len_ref,  # [N] int32: start + its real rows
    counts_ref,  # [N, tiles] int32: blocks a query tile visits
    layer_ref,  # [1] int32
    # inputs
    qn_ref,  # [Tp, hg * dn] VMEM: the group's q_nope, a head a lane block
    qr_ref,  # [Tp, hg * drp]: q_rope, a head padded to the pool's lanes
    wuk_ref,  # [hg, dc, dn]
    wuv_ref,  # [hg, dc, dv]
    pool_ref,  # [L, num_pages, page, D] ANY/HBM
    # outputs: o [Tp, hg * dv]; with ``visits`` also [tiles] int32 SMEM,
    # the blocks each tile of this program scored (tests)
    # scratch: buf [2, bp, page, D]; sems DMA [2, bp]; acc [hg, tiles, dv,
    # tq] float32, acc^T a (head, tile); m_s, l_s [hg, tiles, 1, tq]
    *refs,
    scale: float,
    tq: int,
    bp: int,
    dc: int,
):
    o_ref, *visits_ref, buf, sems, acc, m_s, l_s, kn_s, vt_s = refs
    n = pl.program_id(0)
    layer = layer_ref[0]
    P = block_tables_ref.shape[1]
    hg, n_tiles, dv, _ = acc.shape
    page, D = buf.shape[2], buf.shape[3]
    dn, drp = wuk_ref.shape[2], D - dc
    span = bp * page
    mm = qn_ref.dtype  # the MXU's operand type: the model's

    start, kv_len = start_ref[n], len_ref[n]
    n_blocks = counts_ref[n, 0]
    for i in range(1, n_tiles):
        n_blocks = jnp.maximum(n_blocks, counts_ref[n, i])

    def issue(slot, j):
        for p in range(bp):
            # past the table's end the ids repeat its last entry: those
            # positions lie past the sequence's length and are zeroed
            gp = jnp.minimum(j * bp + p, P - 1)
            pltpu.make_async_copy(
                pool_ref.at[layer, block_tables_ref[n, gp]],
                buf.at[slot, p], sems.at[slot, p],
            ).start()

    def wait(slot):
        for p in range(bp):
            pltpu.make_async_copy(
                pool_ref.at[layer, 0], buf.at[slot, p], sems.at[slot, p]
            ).wait()

    @pl.when(n_blocks > 0)
    def _():
        issue(0, 0)

    for i in range(n_tiles if visits_ref else 0):
        visits_ref[0][i] = 0
    acc[...] = jnp.zeros_like(acc)
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    row_pos = jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
    col_pos = jax.lax.broadcasted_iota(jnp.int32, (1, tq), 1)
    nt = (((1,), (1,)), ((), ()))  # a . b^T
    nn = (((1,), (0,)), ((), ()))

    def pair(h, i, r0, k_n, v_t, k_r, valid):
        q_n = qn_ref[pl.ds(r0, tq), h * dn:(h + 1) * dn]
        q_r = qr_ref[pl.ds(r0, tq), h * drp:(h + 1) * drp]
        s = (
            jax.lax.dot_general(
                k_n, q_n, nt, preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                k_r, q_r, nt, preferred_element_type=jnp.float32)
        ) * scale  # [span, tq]: s^T
        s = jnp.where(valid, s, NEG_INF)
        # every row of a visited tile has met a key by its first block
        # (key 0 is under ``kv_len`` and at or before every row), so
        # ``m_new`` is finite and a masked score's probability is exactly 0
        m = m_s[h, i]
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        m_s[h, i] = m_new
        l_s[h, i] = alpha * l_s[h, i] + jnp.sum(p, axis=0, keepdims=True)
        acc[h, i] = alpha * acc[h, i] + jax.lax.dot_general(
            v_t, p.astype(mm), nn, preferred_element_type=jnp.float32)

    def block(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_blocks)
        def _():
            issue(1 - slot, j + 1)

        wait(slot)
        rows = buf[slot].reshape(span, D)
        rows = jnp.where(
            row_pos < kv_len - j * span, rows, jnp.zeros_like(rows)
        ).astype(mm)
        c, k_r = rows[:, :dc], rows[:, dc:]
        kv_pos = j * span + row_pos  # [span, 1]
        # the block's keys and values a head, made once and scored by
        # every tile below
        for h in range(hg):
            kn_s[h] = jnp.dot(
                c, wuk_ref[h], preferred_element_type=jnp.float32
            ).astype(mm)  # [span, dn]
            vt_s[h] = jax.lax.dot_general(
                wuv_ref[h], c, (((0,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(mm)  # [dv, span]: (c W_uv[h])^T

        def tile(i, _):
            @pl.when(counts_ref[n, i] > j)
            def _():
                if visits_ref:
                    visits_ref[0][i] += 1
                r0 = pl.multiple_of(i * tq, tq)
                q_pos = start + r0 + col_pos
                valid = (kv_pos <= q_pos) & (kv_pos < kv_len)
                # the heads of the group are independent chains in one
                # basic block: a head's softmax overlaps another's matmuls
                for h in range(hg):
                    pair(h, i, r0, kn_s[h], vt_s[h], k_r, valid)

        jax.lax.fori_loop(0, n_tiles, tile, None)

    jax.lax.fori_loop(0, n_blocks, block, None)

    for h in range(hg):
        def write(i, _, h=h):
            r0 = pl.multiple_of(i * tq, tq)
            l = l_s[h, i]
            # a tile without a real row visits nothing: l is 0 there
            out = acc[h, i] / jnp.where(l == 0.0, 1.0, l)
            o_ref[pl.ds(r0, tq), h * dv:(h + 1) * dv] = out.T.astype(
                o_ref.dtype)

        jax.lax.fori_loop(0, n_tiles, write, None)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "tq", "bp", "heads", "visits", "interpret", "scope"),
)
def latent_prefill_kernel(
    q_nope: jax.Array,  # [N, T, H, dn]: member n's rows at start_pos[n] + t
    q_rope: jax.Array,  # [N, T, H, D - dc]: padded to the pool's lanes
    pool: jax.Array,  # [L, num_pages, page, D] (read only)
    w_uk: jax.Array,  # [H, dc, dn]
    w_uv: jax.Array,  # [H, dc, dv]
    block_tables: jax.Array,  # [N, P] int32
    start_pos: jax.Array,  # [N] int32
    kv_len: jax.Array,  # [N] int32: start_pos + the real rows
    counts: jax.Array,  # [N, ceil(T / tq)] int32: ``prefill_blocks``'s
    *,
    layer: int | jax.Array,  # traced: one trace serves every layer
    scale: float,
    tq: int,  # rows a query tile (``latent_prefill_tiling``)
    bp: int,  # pages a block
    heads: int | None = None,  # tests: force the group of heads
    visits: bool = False,  # tests: also return the blocks each tile scored
    interpret: bool = False,
    scope: str | None = None,  # a jax.named_scope around the kernel
) -> jax.Array:
    """Causal attention of ``N`` sequences' new rows over their paged
    latents, layer ``layer`` of the pool. Returns ``[N, T, H, dv]`` in the
    queries' dtype. ``T`` is padded here to whole tiles; a tile's trip
    count is ``counts``'s, so rows past a member's ``kv_len`` in a tile
    that has a real row read every key under ``kv_len`` (finite, unused)
    and a tile without one reads nothing (zeros). With ``visits`` returns
    ``(out, [N, H / hg, tiles] int32)``: the tile-by-block pairs each
    program scored, to hold against ``counts``."""
    N, T, H, _ = q_nope.shape
    dc, dv_model = w_uk.shape[1], w_uv.shape[2]
    if not interpret:
        # compiled, a head is a slice of the lanes: whole lane tiles.
        # Zero columns are exact (0 to every score, output sliced off)
        # and none at the published 128 / 128
        from dynamo_tpu.ops.attention import pad_heads

        q_nope, w_uk, w_uv = (
            pad_heads(x, -(-x.shape[-1] // 128) * 128)
            for x in (q_nope, w_uk, w_uv)
        )
    dn, dv = w_uk.shape[2], w_uv.shape[2]
    _, _, page, D = pool.shape
    drp = D - dc
    assert q_rope.shape == (N, T, H, drp), (q_rope.shape, pool.shape)
    n_tiles = counts.shape[1]
    Tp = n_tiles * tq
    assert Tp >= T and counts.shape == (N, n_tiles), (T, tq, counts.shape)
    hg = heads or head_group(H)
    dt = q_nope.dtype

    def flat(x):  # [N, T, H, d] -> [N, Tp, H * d]
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        return x.reshape(N, Tp, -1)

    def lanes(d):
        return pl.BlockSpec(
            (None, Tp, hg * d), lambda n, g, *_: (n, 0, g),
            memory_space=pltpu.VMEM)

    def weights(d):
        return pl.BlockSpec(
            (hg, dc, d), lambda n, g, *_: (g, 0, 0), memory_space=pltpu.VMEM)

    kernel = functools.partial(
        _latent_prefill_kernel, scale=scale, tq=tq, bp=bp, dc=dc)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N, H // hg),
        in_specs=[
            lanes(dn), lanes(drp), weights(dn), weights(dv),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[lanes(dv)] + [pl.BlockSpec(
            (None, None, n_tiles), lambda n, g, *_: (n, g, 0),
            memory_space=pltpu.SMEM)] * visits,
        scratch_shapes=[
            pltpu.VMEM((2, bp, page, D), pool.dtype),
            pltpu.SemaphoreType.DMA((2, bp)),
            pltpu.VMEM((hg, n_tiles, dv, tq), jnp.float32),
            pltpu.VMEM((hg, n_tiles, 1, tq), jnp.float32),
            pltpu.VMEM((hg, n_tiles, 1, tq), jnp.float32),
            pltpu.VMEM((hg, bp * page, dn), dt),
            pltpu.VMEM((hg, dv, bp * page), dt),
        ],
    )
    # the profiler names the custom call after the innermost scope around
    # it (``scope``), else this jit
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        out, *seen = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((N, Tp, H * dv), dt)] + [
                jax.ShapeDtypeStruct((N, H // hg, n_tiles), jnp.int32)
            ] * visits,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
        )(
            block_tables.astype(jnp.int32), start_pos.astype(jnp.int32),
            kv_len.astype(jnp.int32), counts.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1),
            flat(q_nope), flat(q_rope), w_uk.astype(dt), w_uv.astype(dt),
            pool,
        )
    out = out[:, :T].reshape(N, T, H, dv)[..., :dv_model]
    return (out, seen[0]) if visits else out
