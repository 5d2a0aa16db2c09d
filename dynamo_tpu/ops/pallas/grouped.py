"""Grouped product for calls whose rows fit VMEM: rows [m, k] sorted by
group x w [g, k, n] -> [m, n], row r of group i times ``w[i]``.

A decode step's expert layer is bound by its weights: a step's 512 to
1,536 rows fall on a few dozen experts, ~8 to ~100 rows each, against a
machine balance of ~240 rows a weight byte pair. What the call costs is
what it moves, so this kernel moves the least a call can:

- the rows and the output are RESIDENT: one VMEM block each, the rows
  some group reaches fetched once and written once a call (in blocks of
  128; the rows of experts held elsewhere, which sort last, never move);
- the stacked weights stay in HBM (``pl.ANY``: the compiler may not copy
  the stack, or a slice of it, into VMEM ahead of the call) and the
  kernel's loop runs over the NON-EMPTY groups alone: a touched expert's
  matrix crosses the bus once a call, in tiles that divide it (whole where
  two fit beside the rows), by the kernel's own double-buffered copies;
  the next tile's copy starts before the current one is waited for, so
  the bus is never idle between tiles;
- a group's rows are taken from the resident block at its own offset,
  aligned down to the sublane packing and masked by row index, in chunks
  of 128 rows with the weight tile already in VMEM; each chunk is ONE
  product over the whole k, accumulated in float32.

megablox (``jax.experimental.pallas.ops.tpu.megablox``) walks (group,
128-row tile) pairs instead: a group that crosses a row-tile boundary has
its weights fetched again, the rows' tile is fetched again every grid
step, and a contracted width its k-tile does not divide takes a float32
mask of both operands (PERF.md section 6, PR 54). It stays the path for the
calls whose rows stream (prefill): ``tile_n`` is the rule, read from the
call's static shape alone.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 100 * 1024 * 1024  # of a v5e core's 128 MiB
# rows + output + two weight tiles; what is left of the limit is the
# chunks' operands and float32 products
_RESIDENT_BYTES = 80 * 1024 * 1024
_CHUNK = 128  # rows a product: the MXU's height
_LANES = 128


def tile_n(m: int, k: int, n: int, itemsize: int) -> int | None:
    """The widest tile of an expert's n columns (n whole, else a multiple
    of 128 that divides n) with which the rows [m, k], the output [m, n]
    and two weight tiles [k, tile] fit the kernel's VMEM budget; None
    where none does: the call's rows stream, megablox's case. None too
    where k or n is no multiple of the 128 lanes (an expert width of 768
    split over tp = 4): Mosaic slices rows and tiles by whole lane tiles."""
    if k % _LANES or n % _LANES:
        return None
    mp = -(-m // _CHUNK) * _CHUNK
    left = (_RESIDENT_BYTES - mp * k * itemsize
            - mp * n * itemsize)
    tiles = [n] + [t for t in range(n - _LANES, 0, -_LANES) if n % t == 0]
    for t in tiles:
        if 2 * k * t * itemsize <= left:
            return t
    return None


def _kernel(offs_ref, ids_ref, count_ref, a_hbm, w_hbm, o_hbm,
            a_buf, o_buf, w_buf, sems, w_sems, *, tn: int, align: int):
    """``offs_ref`` [g + 1] the groups' first rows, ``ids_ref`` [g] the
    non-empty groups first, in order, ``count_ref`` [1] how many they are
    (SMEM). An item of the loop is (non-empty group, tile of n)."""
    mp, n = o_buf.shape
    nt = n // tn
    items = count_ref[0] * nt
    # the rows some group reaches, in blocks of a chunk: what lies past
    # them (assignments to experts held elsewhere sort last) is neither
    # fetched nor written
    blocks = pl.cdiv(offs_ref[w_hbm.shape[0]], _CHUNK)

    def block(b, src, dst, sem):
        at = pl.ds(pl.multiple_of(b * _CHUNK, _CHUNK), _CHUNK)
        return pltpu.make_async_copy(src.at[at], dst.at[at], sem)

    def rows_in(b):
        return block(b, a_hbm, a_buf, sems.at[0])

    def rows_out(b):
        return block(b, o_buf, o_hbm, sems.at[1])

    def each_block(do):
        def body(b, carry):
            do(b)
            return carry

        jax.lax.fori_loop(0, blocks, body, 0)

    def cols(i):
        """An item's columns of n, in the stack and in the output."""
        if nt == 1:
            return slice(None)
        return pl.ds(pl.multiple_of((i % nt) * tn, _LANES), tn)

    def fetch(i, slot):
        return pltpu.make_async_copy(
            w_hbm.at[ids_ref[i // nt], :, cols(i)], w_buf.at[slot],
            w_sems.at[slot])

    @pl.when(items > 0)
    def _():
        fetch(0, 0).start()
        each_block(lambda b: rows_in(b).start())

    def item(i, carry):
        slot = i % 2

        # the other buffer's product ended with the last item: its next
        # tile starts now, beside this item's, and lands under its product
        @pl.when(i + 1 < items)
        def _():
            fetch(i + 1, 1 - slot).start()

        fetch(i, slot).wait()

        @pl.when(i == 0)
        def _():
            each_block(lambda b: rows_in(b).wait())

        gid = ids_ref[i // nt]
        lo, hi = offs_ref[gid], offs_ref[gid + 1]
        first = (lo // align) * align

        def chunk(c, carry):
            # a chunk past the block's end slides back inside it: the mask
            # goes by the row's index, so it covers the same rows
            r0 = pl.multiple_of(
                jnp.minimum(first + c * _CHUNK, mp - _CHUNK), align)
            at = (pl.ds(r0, _CHUNK), cols(i))
            y = jnp.dot(a_buf[at[0], :], w_buf[slot],
                        preferred_element_type=jnp.float32)
            rid = r0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
            o_buf[at] = jnp.where(
                (rid >= lo) & (rid < hi), y.astype(o_buf.dtype), o_buf[at])
            return carry

        jax.lax.fori_loop(0, pl.cdiv(hi - first, _CHUNK), chunk, 0)
        return carry

    jax.lax.fori_loop(0, items, item, 0)
    each_block(lambda b: rows_out(b).start())
    each_block(lambda b: rows_out(b).wait())


def group_schedule(sizes: jax.Array):
    """(offsets [g + 1], ids [g], count [1]), int32: each group's first
    row, the non-empty groups' indices first and in order (what follows
    them is 0 and never read), and how many they are. No sort and no
    scatter: a compare against every place and a sum."""
    g = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)])
    live = sizes > 0
    place = jnp.cumsum(live) - 1  # a non-empty group's place in the list
    at = jnp.arange(g, dtype=jnp.int32)
    ids = jnp.sum(
        jnp.where(live[None, :] & (place[None, :] == at[:, None]),
                  at[None, :], 0), axis=1, dtype=jnp.int32)
    return offs, ids, jnp.sum(live, dtype=jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("tn", "scope", "interpret"))
def grouped_matmul(a: jax.Array, w: jax.Array, sizes: jax.Array, *,
                   tn: int | None = None, scope: str | None = None,
                   interpret: bool = False):
    """rows [m, k] sorted by group x w [g, k, n] -> [m, n] in the rows'
    dtype, float32 over the whole k; ``sizes`` [g] rows a group, in order.
    Rows past ``sum(sizes)`` come back undefined. The call must fit
    (``tile_n`` not None); ``tn`` overrides the tile it would take.
    ``scope``: a ``jax.named_scope`` around the Mosaic call, the name a
    trace and the compiled program give it. Its own jit, as megablox's
    is: a layer's three calls, and every layer's, trace and lower once."""
    m, k = a.shape
    g, _, n = w.shape
    itemsize = jnp.dtype(a.dtype).itemsize
    whole = tile_n(m, k, n, itemsize)
    if whole is None or n % (tn or whole) or (tn or whole) % _LANES:
        raise ValueError(f"grouped product {a.shape} x {w.shape}: no tile")
    tn = tn or whole
    mp = -(-m // _CHUNK) * _CHUNK
    kernel = functools.partial(
        _kernel, tn=tn, align=max(8, 32 // itemsize))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    schedule = group_schedule(sizes)
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(1,),
                in_specs=[any_space, any_space],
                out_specs=any_space,
                scratch_shapes=[
                    pltpu.VMEM((mp, k), a.dtype),
                    pltpu.VMEM((mp, n), a.dtype),
                    pltpu.VMEM((2, k, tn), w.dtype),
                    pltpu.SemaphoreType.DMA((2,)),  # rows in, rows out
                    pltpu.SemaphoreType.DMA((2,)),  # a weight buffer each
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((mp, n), a.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            cost_estimate=pl.CostEstimate(
                flops=2 * m * k * n, transcendentals=0,
                bytes_accessed=(mp * (k + n) + min(g, m) * k * n) * itemsize),
            interpret=interpret,
        )(*schedule, jnp.pad(a, ((0, mp - m), (0, 0))), w)
    return out[:m]
