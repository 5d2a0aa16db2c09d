"""Pallas TPU kernel: paged decode attention over a LATENT cache + append.

Latent attention (MLA) keeps one row a token a layer, shared by every
head: ``[c (kv_lora_rank) | k_r (qk_rope_head_dim)]``, lane-padded to the
pool's width. In the absorbed form a decode step scores all ``H`` query
heads of a sequence against those rows (``q_lat . c + q_rope . k_r``) and
takes its values from the SAME rows' leading ``kv_lora_rank`` lanes
(``p . c``): the row is key and value at once. ``fused_decode.py`` reads a K
pool and a V pool; a latent kernel that did so would move every byte
twice. This one has ONE pool ``[L, num_pages, page, D]``: a page is
fetched once into VMEM and used on both sides of the softmax.

Everything else is ``fused_decode.py``'s design, and its schedule is
imported from there, not copied: one program a sequence; a loop over the
sequence's OWN live chunks of ``chunk_pages`` pages (``live_chunks``: a
2,000-token context in a 10,240-token table moves and scores 2,000 tokens'
chunks; a slot with nothing in the pool issues no fetch); two buffers, the
next chunk (this sequence's, or the next live sequence's first) in flight
while one is scored; the step's new row merged analytically as one more
flash chunk and spliced into its page by a staged read-modify-write in the
same call (not for the trash page). Differences that the shapes force:

- 32 query rows a sequence against 1,152-byte rows is ~60 FLOP a byte:
  the matmuls are not hidden behind the DMA as the GQA kernel's are, so
  the operands go to the MXU in the POOL's dtype (bfloat16 on the chip)
  with float32 accumulation, and the probabilities are rounded to it for
  ``p . c``; the running softmax stays float32.
- ``W_uk`` and ``W_uv`` are absorbed OUTSIDE: the caller hands
  ``q = [q_nope W_uk | q_rope | 0]`` pre-scaled and gets ``o_lat [B, H,
  kv_lora_rank]`` back, to put through ``W_uv``.

A fetched chunk's tail (a partly filled last page, pages past a table
that does not divide into chunks) is zeroed before use: masked scores
give those rows probability 0, but 0 x a non-finite value would be NaN.
Pair with ``donate_argnums`` at every jit boundary above: the pool is
input/output-aliased.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.fused_decode import NEG_INF, _schedule


# Tokens a chunk of the latent kernel holds. The GQA kernel's chunk is
# sized by bytes and pages (``fused_decode.chunk_pages``: 16 pages, 1 MiB);
# a latent row is 1,280 B, so that rule gives 256-token chunks of 20 KB
# pages, and this kernel is bound by its matmuls at 32 rows a sequence
# (a quarter of the systolic array's rows), not by the DMA: what a longer
# chunk saves is the loop's fixed work. The kernel alone at ~260k live
# tokens over 128 slots, 16-token pages, us a call (my chip runs, PR 32:
# PERF.md section 6): chunks of 128 / 256 / 512 tokens 1,332 / 927 / 734;
# eight calls in one program, a copy of the pool (~0.5 ms a call) on top
# of each: 256 / 512 / 1,024 tokens 1,588 / 1,422 / 1,402, and at 64-token
# pages 512 / 1,024 tokens 1,308 / 1,264. 512 wastes a sequence's last 256
# tokens in the mean where 1,024 wastes 512.
_CHUNK_TOKENS = 512


def latent_chunk_pages(pool, pages_per_seq: int) -> int:
    """Pages a chunk of the kernel holds for a latent pool ``[L,
    num_pages, page, D]`` (an array or its shape-bearing stand-in) under
    tables ``pages_per_seq`` wide: about ``_CHUNK_TOKENS`` of tokens. A
    table no wider than that is one chunk; a wider one is cut into equal
    chunks of about a power of two of pages, as ``fused_decode.
    chunk_pages`` cuts it."""
    target = max(1, _CHUNK_TOKENS // pool.shape[2])
    if pages_per_seq <= target:
        return pages_per_seq
    target = 1 << (target.bit_length() - 1)
    n_chunks = -(-pages_per_seq // target)
    return -(-pages_per_seq // n_chunks)


def latent_schedule(pool, block_tables: jax.Array, seq_lens: jax.Array):
    """The kernel's schedule for a batch (``fused_decode._schedule`` at
    this pool's chunk): the same for every layer of a step, so a model
    makes it once a step and hands it to each layer's call."""
    Pw = latent_chunk_pages(pool, block_tables.shape[1])
    return _schedule(seq_lens.astype(jnp.int32), pool.shape[2], Pw, 0)


def _latent_decode_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,  # [B, P] int32
    seq_lens_ref,  # [B] int32 (length INCLUDING the new token)
    dst_page_ref,  # [B] int32 pool page for the new row (0 = trash)
    dst_off_ref,  # [B] int32 row offset within the page
    layer_ref,  # [1] int32: which layer of the pool this call serves
    # the schedule (fused_decode._schedule), each [B + 1] int32
    first_ref, count_ref, slot_ref, next_ref,
    # inputs
    q_ref,  # [1, H, D] VMEM: [q_lat | q_rope | 0], pre-scaled
    new_ref,  # [1, 1, D] VMEM: the step's new row
    pool_ref,  # [L, num_pages, page, D] ANY/HBM (aliased out)
    # outputs
    o_ref,  # [1, H, dc]
    pool_out_ref,
    # scratch
    buf,  # [2, Pw, page, D]
    sems,  # DMA [2, Pw]
    stage,  # [page, D]
    rmw_sems,  # DMA [2]: in, out
    *,
    page_size: int,
    pages_per_seq: int,
    chunk: int,
    dc: int,
):
    layer = layer_ref[0]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    P, Pw, page = pages_per_seq, chunk, page_size
    H, D = q_ref.shape[1], q_ref.shape[2]
    Nw = Pw * page
    mm = buf.dtype  # the MXU's operand type: the pool's

    dst_page = dst_page_ref[b]
    lands = dst_page != 0

    def rmw_in():
        return pltpu.make_async_copy(
            pool_ref.at[layer, dst_page], stage, rmw_sems.at[0]
        )

    def rmw_out():
        return pltpu.make_async_copy(
            stage, pool_out_ref.at[layer, dst_page], rmw_sems.at[1]
        )

    @pl.when(lands)
    def _():
        rmw_in().start()

    def issue(slot, seq, c):
        for p in range(Pw):
            gp = c * Pw + p
            if P % Pw:
                gp = jnp.minimum(gp, P - 1)
            pltpu.make_async_copy(
                pool_ref.at[layer, block_tables_ref[seq, gp]],
                buf.at[slot, p], sems.at[slot, p],
            ).start()

    def wait(slot):
        for p in range(Pw):
            pltpu.make_async_copy(
                pool_ref.at[layer, 0], buf.at[slot, p], sems.at[slot, p]
            ).wait()

    @pl.when(b == 0)
    def _():
        head = next_ref[0]

        @pl.when(head < nb)
        def _():
            issue(0, head, first_ref[head])

    seq_len = seq_lens_ref[b]
    q = q_ref[0].astype(mm)  # [H, D]
    # the pool holds positions < seq_len - 1; the new token merges below
    pos_end = jnp.minimum(seq_len - 1, P * page)
    row_pos = jax.lax.broadcasted_iota(jnp.int32, (Nw, 1), 0)
    col_pos = jax.lax.broadcasted_iota(jnp.int32, (1, Nw), 1)

    first = first_ref[b]
    count = count_ref[b]
    slot0 = slot_ref[b]
    after = next_ref[b + 1]

    def merge_chunk(j, carry):
        m, l, acc = carry
        c = first + j
        slot = jax.lax.rem(slot0 + j, 2)
        is_last = j + 1 == count
        nxt_seq = jnp.where(is_last, after, b)
        nxt_chunk = jnp.where(is_last, first_ref[nxt_seq], c + 1)

        @pl.when(nxt_seq < nb)
        def _():
            issue(1 - slot, nxt_seq, nxt_chunk)

        wait(slot)
        live = pos_end - c * Nw  # rows of this chunk that hold context
        rows = buf[slot].reshape(Nw, D)
        # every chunk, not the last alone: under ``lax.cond`` the kernel
        # ran 7-25% slower, and without the select no faster (my chip
        # runs, PR 32)
        rows = jnp.where(row_pos < live, rows, jnp.zeros_like(rows))
        scores = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [H, Nw]
        scores = jnp.where(col_pos < live, scores, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(scores - m_new)
        l = l * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            probs.astype(mm), rows[:, :dc], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, count, merge_chunk,
        (
            jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, dc), jnp.float32),
        ),
    )

    # the new token as one more flash chunk: always visible to its query
    new = new_ref[0]  # [1, D]
    new_f = new.astype(jnp.float32)
    s_new = jnp.sum(q.astype(jnp.float32) * new_f, axis=-1, keepdims=True)
    m_f = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_f)
    p_new = jnp.exp(s_new - m_f)
    l = l * alpha + p_new
    acc = acc * alpha + p_new * new_f[:, :dc]

    @pl.when(lands)
    def _():
        rmw_in().wait()
        hit = (
            jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
            == dst_off_ref[b]
        )
        stage[...] = jnp.where(hit, new.astype(stage.dtype), stage[...])
        rmw_out().start()

    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    # the stage is the next program's too: its out-DMA drains first
    @pl.when(lands)
    def _():
        rmw_out().wait()


@functools.partial(
    jax.jit,
    static_argnames=("dc", "interpret", "chunk_override", "scope"),
    donate_argnums=(1,),
)
def latent_decode_attention(
    q: jax.Array,  # [B, H, D]: [q_lat | q_rope | 0], pre-scaled
    pool: jax.Array,  # [L, num_pages, page, D] (donated)
    new_rows: jax.Array,  # [B, D]: the step's rows, padded to the pool's D
    block_tables: jax.Array,  # [B, P] int32
    seq_lens: jax.Array,  # [B] int32, INCLUDING the new token
    dst_page: jax.Array,  # [B] int32 (0 = trash: not written)
    dst_off: jax.Array,  # [B] int32
    *,
    layer: int | jax.Array,  # traced: one trace serves every layer
    dc: int,  # kv_lora_rank: the value lanes of a row
    interpret: bool = False,
    chunk_override: int | None = None,  # tests: force a chunk size
    scope: str | None = None,  # a jax.named_scope around the kernel
    schedule: tuple | None = None,  # ``latent_schedule``'s, made once a step
) -> tuple[jax.Array, jax.Array]:
    """One absorbed decode-attention + append step over layer ``layer`` of
    a latent pool. Returns ``(o_lat [B, H, dc], pool)`` with the new rows
    written in place."""
    B, H, D = q.shape
    _, _, page, Dp = pool.shape
    assert D == Dp and new_rows.shape == (B, D), (q.shape, pool.shape)
    P = block_tables.shape[1]
    Pw = chunk_override or latent_chunk_pages(pool, P)
    if schedule is None:
        schedule = _schedule(seq_lens.astype(jnp.int32), page, Pw, 0)
    kernel = functools.partial(
        _latent_decode_kernel, page_size=page, pages_per_seq=P, chunk=Pw,
        dc=dc,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, D), lambda b, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, H, dc), lambda b, *_: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, Pw, page, D), pool.dtype),
            pltpu.SemaphoreType.DMA((2, Pw)),
            pltpu.VMEM((page, D), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    # operands count the 9 scalar-prefetch arguments: 9 = q, 10 = new
    # rows, 11 = the pool -> output 1. The profiler names the custom call
    # after the innermost scope around it (``scope``), else this jit
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        out, pool = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, H, dc), q.dtype),
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            ],
            input_output_aliases={11: 1},
            interpret=interpret,
        )(
            block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
            dst_page.astype(jnp.int32), dst_off.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1), *schedule,
            q, new_rows.astype(pool.dtype)[:, None, :], pool,
        )
    return out, pool
