"""Attention ops over the paged KV cache (pure-JAX reference forms).

The paged layout is PAGE-MAJOR: per layer, K and V live in page arrays of
shape ``[num_pages, num_kv_heads, page_size, head_dim]`` (one page = one
contiguous all-heads block = one DMA descriptor); a sequence's pages are
listed in its row of ``block_tables [B, max_pages_per_seq]``. This is the
TPU-first replacement for the reference's engine-internal (vLLM) paged
attention + its block-copy CUDA kernel (lib/llm/src/kernels/block_copy.cu):
XLA-friendly gathers/scatters here, the one place a decode step's
attention is chosen (``decode_update_attention``): the fused Pallas kernel
(ops/pallas/fused_decode.py) where Pallas runs, the XLA reference
(``paged_decode_attention``) where it does not; and the attention of every
prefill-like program (``paged_prefill_attention``): a walk over a
sequence's pages in blocks, under a running softmax.

All functions are shape-static and jit-safe. The reference forms
(``causal_attention``, ``paged_decode_attention``) handle GQA by repeating KV
heads up to the query head count; the prefill walk scores a KV head against
its group of query heads.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

# the names of the kernels' regions in a trace are models/regions.py's:
# ``attn_latent`` names the latent decode kernel (its roofline share
# divides by that operation's time); ``prefill_latent`` is in no
# configuration's ``trace_names``; ``kda_step`` / ``kda_chunk`` name the
# recurrent layers' kernels, ``ssd_step`` / ``ssd_chunk`` the SSD mixer's
from dynamo_tpu.models.regions import (
    SCOPE_ATTN_LATENT,
    SCOPE_KDA_CHUNK,
    SCOPE_KDA_CHUNK_OPERANDS,
    SCOPE_KDA_STEP,
    SCOPE_LATENT_SCHEDULE,
    SCOPE_PREFILL_LATENT,
    SCOPE_SCAN,
    SCOPE_SSD_CHUNK,
    SCOPE_SSD_STEP,
)

NEG_INF = -1e30


def use_pallas() -> bool:
    """Pallas decode kernel on TPU unless DYNAMO_PALLAS overrides (0/1)."""
    env = (os.environ.get("DYNAMO_PALLAS") or "").strip().lower()
    if env in ("1", "true", "on"):
        return True
    if env in ("0", "false", "off", "no"):
        return False
    return jax.default_backend() == "tpu"


LANES = 128  # the lane tile: the last dim of what a Mosaic kernel DMAs


def pool_head_dim(
    head_dim: int,
    kv_heads: int = 1,
    *,
    pair_dim: int | None = None,  # the other side's width (V's for K)
    tp: int = 1,  # shards of the head axis
    quantized: bool = False,  # a QuantPool: one scale a page a KV head
    compiled: bool | None = None,  # None: asked of the backend
) -> tuple[int, int, int]:
    """``(heads, width, heads a row)`` of a side of the KV PAGE POOL for a
    model with ``kv_heads`` heads ``head_dim`` wide: the pool is ``[L,
    pages, heads, page, width]``. THE one place the layout is decided; a
    latent pool, which has no head axis, takes the width alone.

    The Mosaic kernels DMA whole pages, and a page slice must fill the
    128-lane tile (last dim % 128). So wherever the kernels will run
    compiled (``use_pallas()`` on a TPU; ``compiled`` asks for that layout
    elsewhere, as the CPU tests do) the pool's last dim is whole tiles,
    and THAT IS THE INVARIANT the dispatchers rest on: a pool that reaches
    ``decode_update_attention`` or ``write_new_kv`` on a TPU with Pallas on
    is lane-aligned, so neither has a misaligned branch. Two ways there:

    - PACKED, ``r = 128 // head_dim`` heads a row, where ``head_dim``
      divides 128, ``r > 1`` and ``r`` divides ``kv_heads`` (and the
      packed heads still divide over ``tp``): row ``t`` of packed head
      ``j`` holds KV heads ``r j .. r j + r - 1`` side by side (gpt-oss's
      and LFM2's 64: two a row). The pool holds no zeros. A GQA model
      whose KV heads pair up is, exactly, a model of ``kv_heads / r``
      heads of 128 whose queries are zero on the other heads' lanes:
      writers reshape (``pool_rows``, ``page_tiles``), the decode readers
      put a query head in its KV head's lanes and take those lanes of the
      output (``slot_queries``, ``slot_outputs``), the prefill walk
      un-packs what it gathers (``gather_ctx``). The kernels' column to
      head map is one for K and V, so a side packs only if its pair
      (``pair_dim``) packs as many a row.
    - PADDED with zeros up to the tile otherwise (MiMo's K 192 -> 256, an
      odd head count, an fp8 pool: packing would merge two heads'
      scales). Exact too: padded q.k dims add 0 to every score, padded V
      columns are sliced off. It costs pool memory (4/3 for MiMo's K).

    A 128-wide head is neither. Off the chip and under ``DYNAMO_PALLAS=0``
    nothing is padded or packed: the XLA paths take any width. Readers
    tell the three apart by the pool's shape against the model's heads,
    never by a model's name.
    """
    if compiled is None:
        compiled = use_pallas() and jax.default_backend() == "tpu"
    # dynalint: disable=DL014 -- layout probe, not a dispatch site: the
    # XLA path of an unpadded pool is counted where it is taken
    # (note_fallback in decode_update_attention / write_new_kv)
    if not compiled:
        return kv_heads, head_dim, 1

    def a_row(d: int) -> int:
        return LANES // d if LANES % d == 0 else 1

    r = a_row(head_dim)
    if (
        r > 1 and not quantized and kv_heads % r == 0
        and (kv_heads // r) % tp == 0
        and (pair_dim is None or a_row(pair_dim) == r)
    ):
        return kv_heads // r, LANES, r
    return kv_heads, -(-head_dim // LANES) * LANES, 1


def pad_heads(x: jax.Array, pool_dim: int) -> jax.Array:
    """Zero-pad the last dim of [..., D] rows up to a pool's width;
    identity where the pool is as wide (a plain pool, packed rows)."""
    d = x.shape[-1]
    if d == pool_dim:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, pool_dim - d)]
    return jnp.pad(x, pad)


def pool_rows(x: jax.Array, pool_heads: int, pool_dim: int) -> jax.Array:
    """A model's KV rows in a pool's layout (``pool_head_dim``): [..., KH,
    D] -> [..., pool_heads, pool_dim]. A packed pool's row is the model's
    own ``KH / pool_heads`` heads side by side, a contiguous reshape; a
    padded pool's is the head and zeros."""
    if x.shape[-2] != pool_heads:
        x = x.reshape(*x.shape[:-2], pool_heads, -1)
    return pad_heads(x, pool_dim)


def page_tiles(
    arr: jax.Array, page_size: int, pool_dim: int,
    pool_heads: int | None = None,  # None: the rows' own (no packing)
) -> jax.Array:
    """Prefill KV rows -> page-major write tiles in the pool's layout
    (``pool_rows``: packed, or zero-padded to the pool width): [..., T,
    KH, D] -> [n_tiles, pool_heads, page_size, pool_dim] (leading dims
    fold into the tile count). The SINGLE tile builder for every prefill
    pool writer (models/llama.py x3) so a pool's layout (pool_head_dim)
    can't be missed by one of them."""
    arr = pool_rows(arr, pool_heads or arr.shape[-2], pool_dim)
    kh, hd = arr.shape[-2], arr.shape[-1]
    return arr.reshape(-1, page_size, kh, hd).transpose(0, 2, 1, 3)


def _own_slot(heads: int, kv_heads: int, r: int) -> jax.Array:
    """[1, H, r, 1] bool: the slot of a packed row that query head ``h``'s
    KV head sits in (KV head ``k`` in slot ``k % r`` of packed head ``k
    // r``)."""
    slot = (jnp.arange(heads) // (heads // kv_heads)) % r
    return (slot[:, None] == jnp.arange(r)[None, :])[None, :, :, None]


def slot_queries(
    q: jax.Array, kv_heads: int, pool_heads: int, pool_dim: int
) -> jax.Array:
    """Decode queries [B, H, D] as a pool's rows are laid out: zero-padded
    to a padded pool's width, or, over a packed pool, each query head in
    the lanes of its own KV head with zeros in the others', which add 0
    to every score. The query heads of a packed head's ``r`` KV heads are
    contiguous, so the readers' ``G = H // pool_heads`` needs no more."""
    r = kv_heads // pool_heads
    if r == 1:
        return pad_heads(q, pool_dim)
    B, H, D = q.shape
    copies = jnp.broadcast_to(q[:, :, None, :], (B, H, r, D))
    placed = jnp.where(_own_slot(H, kv_heads, r), copies, 0)
    return placed.reshape(B, H, r * D)  # = pool_dim


def slot_outputs(
    out: jax.Array, kv_heads: int, pool_heads: int, v_dim: int
) -> jax.Array:
    """``slot_queries``' inverse on the V side: of a reader's [B, H, pool
    width] output, the model's ``v_dim`` columns: a padded pool's first, a
    packed pool's own slot (the other slots hold other heads' values
    under this head's probabilities, and are dropped). A select and a sum
    with zeros, which is exact: as strided slices stacked, the chip's
    compiler gave other heads' columns under ``jit`` (my chip runs, PR
    50; the CPU's did not)."""
    r = kv_heads // pool_heads
    if r == 1:
        return out[..., :v_dim]
    B, H, _ = out.shape
    by_slot = out.reshape(B, H, r, v_dim)
    return jnp.where(_own_slot(H, kv_heads, r), by_slot, 0).sum(axis=2)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[.., S, kv_heads, D] -> [.., S, kv_heads*n_rep, D] (GQA expansion)."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def gather_pages(
    pages: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    block_table: jax.Array,  # [max_pages_per_seq] int32
    layer=None,  # ``pages`` is a pool [L, num_pages, ...]: its layer
) -> jax.Array:
    """Materialize the pages ``block_table`` lists as [len(table) * page,
    kv_heads, head_dim]: a whole table (the decode reference) or one block
    of it (the prefill walk). With ``layer`` the pages are gathered out
    of the pool in one indexing step: ``pool[layer][table]`` had the
    compiler copy the layer's whole slice of the pool first (0.83 ms a
    call at 600 MB; my chip run, PR 28)."""
    toks = pages[block_table] if layer is None else pages[layer, block_table]
    P, H, page, D = toks.shape
    return toks.transpose(0, 2, 1, 3).reshape(P * page, H, D)


def gather_ctx(
    pool, li, block_table: jax.Array, head_dim: int,
    kv_heads: int | None = None,  # the MODEL's; None: the pool's own
):
    """One layer's rows on the pages ``block_table`` lists (a block of
    a sequence's table in the prefill walk, a whole table in the tests'
    oracle), as the MODEL has them, ``[tokens, kv_heads, head_dim]``,
    pool-form-agnostic: plain arrays gather in the pool dtype; QuantPool
    (ops/quant.py) gathers fp8 pages and dequantizes with the
    per-page/head scales. A packed pool's rows (``pool_head_dim``: fewer
    heads than the model's) split back into the model's heads, a plain
    reshape of the token-major rows; a lane-padded pool's are sliced back
    to the model's head dim. The single gather of the prefill walk
    (prefill, packed prefill, verify), so neither the fp8 gather/dequant
    path nor a pool's layout can be missed by one of them."""
    from dynamo_tpu.ops.quant import gather_dequant_pages, is_quant

    if is_quant(pool):
        return gather_dequant_pages(pool, block_table, li)[..., :head_dim]
    rows = gather_pages(pool, block_table, li)
    if kv_heads is not None and kv_heads != rows.shape[1]:
        rows = rows.reshape(rows.shape[0], kv_heads, -1)
    return rows[..., :head_dim]


def causal_attention(
    q: jax.Array,  # [T, heads, D]
    k: jax.Array,  # [S, kv_heads, D]
    v: jax.Array,  # [S, kv_heads, D]
    q_positions: jax.Array,  # [T] absolute positions of the queries
    kv_len: jax.Array,  # scalar: number of valid kv tokens
    *,
    window: int = 0,  # sliding window (0 = full); key j needs j > pos - window
    sinks: jax.Array | None = None,  # [H] learned sink logits (gpt-oss)
    kv_offset: jax.Array | int = 0,  # absolute position of k[0]
    scale: float | None = None,  # the softmax scale (None: 1 / sqrt(D))
) -> jax.Array:
    """Causal attention of new queries over (cached + new) keys. K and V
    may differ in width: the scale is q's, the output is V's.

    Key j is visible to query i iff j <= q_positions[i] and j < kv_len
    (and, with a sliding window, j > q_positions[i] - window); key s of
    ``k`` stands at position ``kv_offset + s``. ``sinks``
    adds a per-head learned logit to the softmax normalization — a
    virtual key with zero value the head can dump probability mass on
    (gpt-oss attention; HF eager_attention_forward concat semantics).
    Returns [T, heads, Dv]. Softmax in f32 regardless of input dtype.
    """
    T, H, D = q.shape
    S, KH, _ = k.shape
    n_rep = H // KH
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("thd,shd->hts", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    kv_pos = kv_offset + jnp.arange(S)[None, :]  # [1, S]
    mask = (kv_pos <= q_positions[:, None]) & (kv_pos < kv_len)  # [T, S]
    if window:
        mask &= kv_pos > q_positions[:, None] - window
    logits = jnp.where(mask[None, :, :], logits, NEG_INF)
    if sinks is not None:
        sink_col = jnp.broadcast_to(
            sinks.astype(jnp.float32)[:, None, None], (H, T, 1)
        )
        probs = jax.nn.softmax(
            jnp.concatenate([logits, sink_col], axis=-1), axis=-1
        )[..., :S]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# The prefill walk's shapes (``prefill_tiling``): rows a query tile, tokens
# a block of pages. Fixed by a probe on the chip at the cells' shapes
# (PERF.md section 6, PR 31): the scores of a tile against a block,
# ``[rows, H, 128, 256]`` float32, are 8 MiB a row at MiMo's 64 heads and stay
# in the chip's fast memory; 32 MiB and more (256 x 512 under a pack of two)
# cost a third more time a call, 128 MiB (512 x 1,024) three times.
_TILE_ROWS = 128
_BLOCK_TOKENS = 256
# A call of more than 1,024 rows (a 4,096-row bucket: PR 53) walks blocks
# four times as long. On the chip the block length does not move such a
# call's time (a 4,096-row prefill of a 9-layer window/full model at
# blocks of 256 / 512 / 1,024 tokens: 63.8 / 61.2 / 64.0 ms from position
# 0, 77.4 / 72.5 / 75.9 ms from position 4,096, 132.3 / 130.9 / 133.9 ms a
# pack of two: my chip run, PR 53): what it moves is the walk's count of
# loop steps, ~1,100 a layer a row at 256 tokens, a dozen small operations
# each, of which a traced run wrote ~600,000 events in 6 s that the
# profiler took 277 s to put away, 194-223 s at 1,024. At 1,024 tokens the
# scores of a tile against a block are 16 MiB a row at 32 heads, under the
# 32 MiB that the probe above found to cost time; a rule by those bytes
# alone would also lengthen the blocks of every older configuration's
# calls (<= 1,024 rows), which keep their blocks and their compiled
# programs until their cells are measured at another (ROADMAP.md S5 (a))
_LONG_CALL_ROWS = 1024
_LONG_BLOCK_TOKENS = 1024


def prefill_tiling(
    n_queries: int, pages_per_seq: int, page_size: int, window: int = 0
) -> tuple[int, int]:
    """``(tq, bp)`` of the prefill walk, from what a program sees at trace
    time: rows a query tile and pages a KV block. A table, or a window
    layer's reach from one tile (its rows and the ``window - 1`` tokens
    before them), no wider than one block IS one block: such a tile's
    walk is a single step. A call of more than ``_LONG_CALL_ROWS`` rows
    takes the longer block."""
    tq = min(n_queries, _TILE_ROWS)
    reach = pages_per_seq
    if window:
        reach = min(reach, (tq + window - 2) // page_size + 2)
    block = (_LONG_BLOCK_TOKENS if n_queries > _LONG_CALL_ROWS
             else _BLOCK_TOKENS)
    return tq, min(reach, max(1, block // page_size))


def prefill_blocks(
    start_pos, num_tokens, tile, tq: int, window: int, page_size: int,
    bp: int,
):
    """``(first page, blocks)`` a query tile of the prefill walk visits:
    blocks of ``bp`` pages from the page of the first key its mask can
    reach. The tile's rows ``[tile * tq, (tile + 1) * tq)`` of a call
    stand at ``start_pos + row``; those under ``num_tokens`` are real, and
    see the keys ``[max(0, p0 - window + 1), p1]`` between the first row's
    window and the last real row (``window`` 0: from 0). No block for a
    tile without a real row. Plain arithmetic on numpy or jax integers
    alike: the walk's trip count, the engine's ``prefill_kv`` counters and
    the tests are this one function (as ``live_chunks`` is the decode
    kernel's)."""
    p0 = start_pos + tile * tq
    p1 = (p0 + tq).clip(None, start_pos + num_tokens) - 1
    live = p1 >= p0
    first = ((p0 - window + 1).clip(0) if window else p0 * 0) // page_size
    return first * live, ((p1 // page_size - first) // bp + 1) * live


@functools.partial(
    jax.jit,
    static_argnames=("head_dim", "v_dim", "window", "kv_heads", "scale"))
def paged_prefill_attention(
    q: jax.Array,  # [T, H, D]: queries at positions start_pos + arange(T)
    k_pool,  # [L, num_pages, KH, page, >= D] (arrays or a QuantPool;
    # packed: [L, num_pages, KH / r, page, r * D], see pool_head_dim)
    v_pool,  # [L, num_pages, KH, page, >= Dv]
    layer,  # scalar: the pools' layer. Not static: one trace a layer kind
    block_table: jax.Array,  # [P] the sequence's pages
    start_pos: jax.Array,  # scalar: position of q[0]
    kv_len: jax.Array,  # scalar: start_pos + the real rows of q
    *,
    head_dim: int,  # the model's K width (the pool may be lane-padded)
    v_dim: int,
    kv_heads: int | None = None,  # the model's KV heads (None: the pool's;
    # a packed pool has fewer)
    window: int = 0,
    sinks: jax.Array | None = None,  # [H]
    new_kv: tuple | None = None,  # (k [T, KH, D], v [T, KH, Dv]) exact rows
    scale: float | None = None,  # the softmax scale (None: 1 / sqrt(D))
) -> jax.Array:
    """``causal_attention`` of a call's queries over the sequence's PAGED
    context, walked in blocks with a running softmax: what is gathered and
    scored follows the prompt, the tile's causal edge and the window, not
    the table's width (a 300-token prompt in a 4,608-token table costs 300
    tokens' worth).

    Queries go in tiles of ``tq`` rows, keys in blocks of ``bp`` pages
    (``prefill_tiling``); a tile visits the blocks ``prefill_blocks``
    names, a run-time count (``fori_loop``; under ``vmap`` a pack runs to
    its longest member). A block's pages are gathered in one indexing
    step (``gather_ctx``, which hands back the model's own ``[span, KH,
    D]`` rows whatever the pool's layout: the products stay ``D`` deep
    over a packed pool too), scored a KV head at a time against the
    ``H / KH`` query heads that share it (no repeated copy of K and V),
    masked by position and folded into running ``(m, l, acc)``. A head's
    sink logit is the walk's first ``(m, l) = (sink, 1)`` with a zero
    value row: ``causal_attention``'s concatenated column. Precision is
    ``causal_attention``'s: float32 operands, scores, softmax and
    accumulation. ``new_kv`` lays the call's exact rows over a quantised
    pool's read-back, block by block. Plain XLA: the CPU and a tp mesh
    (heads sharded by GSPMD) run it as it stands. A jit of its own inside
    the program's, so that the layers of a kind share one trace, under
    ``vmap`` too: traced a layer it cost a packed program of 7 layers
    1.8 s of set-up, warm (my chip run, PR 31). Returns [T, H, Dv]."""
    T, H, D = q.shape
    KH, page = kv_heads or k_pool.shape[2], k_pool.shape[3]
    G = H // KH
    P = block_table.shape[0]
    tq, bp = prefill_tiling(T, P, page, window)
    n_tiles = -(-T // tq)
    span = bp * page
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    # [tiles, KH, G * tq, D]: a KV head's G query heads, tile rows within
    qt = jnp.pad(q.astype(jnp.float32), ((0, n_tiles * tq - T), (0, 0), (0, 0)))
    qt = qt.reshape(n_tiles, tq, KH, G, D).transpose(0, 2, 3, 1, 4)
    qt = qt.reshape(n_tiles, KH, G * tq, D)
    if sinks is None:
        m0 = jnp.full((KH, G, tq), NEG_INF, jnp.float32)
    else:
        m0 = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(KH, G, 1), (KH, G, tq)
        )
    l0 = jnp.full((KH, G, tq), 0.0 if sinks is None else 1.0, jnp.float32)
    acc0 = jnp.zeros((KH, G, tq, v_dim), jnp.float32)

    def tile_attention(i):
        q_pos = start_pos + i * tq + jnp.arange(tq)
        first, count = prefill_blocks(
            start_pos, kv_len - start_pos, i, tq, window, page, bp
        )

        def block(j, carry):
            m, l, acc = carry
            page0 = first + j * bp
            # past the table's end the ids repeat its last entry: those
            # positions lie past the sequence's length and are masked
            ids = block_table[jnp.minimum(page0 + jnp.arange(bp), P - 1)]
            kb = gather_ctx(k_pool, layer, ids, head_dim, KH)  # [span, KH, D]
            vb = gather_ctx(v_pool, layer, ids, v_dim, KH)
            kv_pos = page0 * page + jnp.arange(span)
            if new_kv is not None:
                rows = start_pos + jnp.arange(T) - page0 * page
                rows = jnp.where(rows < 0, span, rows)  # before the block
                kb = kb.at[rows].set(new_kv[0].astype(kb.dtype), mode="drop")
                vb = vb.at[rows].set(new_kv[1].astype(vb.dtype), mode="drop")
            s = jnp.einsum(
                "kqd,skd->kqs", qt[i], kb.astype(jnp.float32)
            ).reshape(KH, G, tq, span) * scale
            mask = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos < kv_len)
            if window:
                mask &= kv_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            # a row that has met no key yet (m_new still NEG_INF) sums
            # ones here; its first real key's alpha of exactly 0 drops them
            p = jnp.exp(s - m_new[..., None])
            pv = jnp.einsum(
                "kqs,skd->kqd", p.reshape(KH, G * tq, span),
                vb.astype(jnp.float32),
            ).reshape(KH, G, tq, v_dim)
            return (m_new, alpha * l + p.sum(axis=-1),
                    alpha[..., None] * acc + pv)

        _, l, acc = jax.lax.fori_loop(0, count, block, (m0, l0, acc0))
        # a tile of padded rows alone visits nothing: l may be 0 there
        return acc / jnp.where(l == 0.0, 1.0, l)[..., None]

    out = jax.lax.map(tile_attention, jnp.arange(n_tiles))
    # [tiles, KH, G, tq, Dv] -> [T, H, Dv]
    out = out.transpose(0, 3, 1, 2, 4).reshape(n_tiles * tq, H, v_dim)
    return out[:T].astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,  # [B, heads, D] (one new token per sequence)
    k_pages: jax.Array,  # [num_pages, kv_heads, page_size, D]
    v_pages: jax.Array,  # [num_pages, kv_heads, page_size, D]
    block_tables: jax.Array,  # [B, max_pages_per_seq]
    seq_lens: jax.Array,  # [B] context length INCLUDING the new token
    *,
    window: int = 0,
    sinks: jax.Array | None = None,  # [H]
    scale: float | None = None,  # softmax scale (default 1/sqrt(D))
    new_kv: tuple | None = None,  # exact new-token rows (quant pools)
) -> jax.Array:
    """Decode-step attention: each query attends to its full paged context.

    Pure-JAX reference: gathers [B, max_ctx, kv_heads, D] then masked
    attention. The fused kernel (ops/pallas/fused_decode.py) computes
    the same thing without materializing the gather. ``scale``
    overrides the 1/sqrt(q.shape[-1]) default — needed when q is laid
    out as a wider pool row (pool_head_dim: zero-padded, or placed in
    its KV head's lanes of a packed row) and the true model D differs
    from the row's width. ``k_pages``/``v_pages`` may be
    QuantPool LAYER slices (ops/quant.py): the gather then dequantizes —
    this is the XLA gather/dequant path for CPU and DYNAMO_PALLAS=0.

    ``new_kv=(k_new, v_new)`` overlays the EXACT (unquantized) new-token
    rows at position ``seq_lens - 1`` after the gather — the XLA mirror
    of the fused kernel's analytic new-token merge: the decode query's
    strongest key/value never pays quantization error. Quantized pools
    only (the bf16 write is already exact).
    """
    from dynamo_tpu.ops.quant import gather_dequant_pages, is_quant

    B, H, D = q.shape
    page_size = k_pages.shape[2]
    P = block_tables.shape[1]
    max_ctx = P * page_size

    if is_quant(k_pages):
        k = jax.vmap(lambda bt: gather_dequant_pages(k_pages, bt))(
            block_tables
        )
        v = jax.vmap(lambda bt: gather_dequant_pages(v_pages, bt))(
            block_tables
        )
        if new_kv is not None:
            kn, vn = new_kv  # [B, KH, D] exact post-rope rows
            rows = jnp.arange(B)
            pos = jnp.clip(seq_lens - 1, 0, max_ctx - 1)
            k = k.at[rows, pos].set(kn.astype(k.dtype))
            v = v.at[rows, pos].set(vn.astype(v.dtype))
    else:
        k = jax.vmap(lambda bt: gather_pages(k_pages, bt))(block_tables)
        v = jax.vmap(lambda bt: gather_pages(v_pages, bt))(block_tables)
    KH = k.shape[2]
    n_rep = H // KH
    k = repeat_kv(k, n_rep)  # [B, max_ctx, H, D]
    v = repeat_kv(v, n_rep)

    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum(
        "bhd,bshd->bhs", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    kv_pos = jnp.arange(max_ctx)[None, :]
    mask = kv_pos < seq_lens[:, None]  # [B, max_ctx]
    if window:
        # decode query position = seq_len - 1: keys j >= seq_len - window
        mask &= kv_pos >= seq_lens[:, None] - window
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    if sinks is not None:
        sink_col = jnp.broadcast_to(
            sinks.astype(jnp.float32)[None, :, None], (B, H, 1)
        )
        probs = jax.nn.softmax(
            jnp.concatenate([logits, sink_col], axis=-1), axis=-1
        )[..., :max_ctx]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_update_attention(
    q: jax.Array,  # [B, H, D] (model head dim)
    k_pages: jax.Array,  # [L, num_pages, pool heads, page, pool_d]
    v_pages: jax.Array,
    k_new: jax.Array,  # [B, KH, D] new-token KV rows (post-rope)
    v_new: jax.Array,
    block_tables: jax.Array,  # [B, P]
    seq_lens: jax.Array,  # [B] length INCLUDING the new token
    dst_page: jax.Array,  # [B] pool page for the new row (0 = trash)
    dst_off: jax.Array,  # [B]
    *,
    layer: int,
    mesh=None,
    window: int = 0,
    sinks: jax.Array | None = None,
    scope: str | None = None,  # names the kernel in a trace (llama.attn_scope)
    scale: float | None = None,  # the softmax scale (None: 1 / sqrt(D)):
    # differential attention's rows are a PAIR of heads wide
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The per-layer decode step, KV append + paged attention, and THE
    place its implementation is chosen, from what the program can observe:

    - the fused kernel (ops/pallas/fused_decode.py), one ``pallas_call`` a
      layer, wherever Pallas is active (``use_pallas()``: a TPU, or
      ``DYNAMO_PALLAS=1`` interpreted on the CPU). With a tp mesh it runs
      under ``shard_map``: query heads and KV heads are both head-sharded,
      every GQA group is local to its shard, so the kernel needs no
      collective (``pallas_call`` has no SPMD partitioning rule: without
      the ``shard_map`` GSPMD would all-gather the whole KV cache every
      step). Sinks are per query head and shard with the heads.
    - the XLA reference (``write_new_kv``'s scatter, then
      ``paged_decode_attention``) where it is not, and for an fp8 pool
      under tp > 1 (counted as ``quant_tp_shardmap``).

    K and V may differ in width (q, k_new and the K pool one, v_new, the
    V pool and the output the other). The pools' layout
    (``pool_head_dim``) is read from their shapes against the new rows':
    a pool wider than the model's heads gets q and the new rows
    zero-padded to its width and the padded output columns sliced off; a
    pool of fewer, packed heads gets the new rows reshaped
    (``pool_rows``), each query head in its KV head's lanes
    (``slot_queries``) and those lanes of the output taken
    (``slot_outputs``), so that both implementations below see a model of
    ``pool heads`` heads a row wide and neither knows. The softmax scale
    is pinned to the model's ``D`` either way. A window layer is handed
    the pages its window reaches, not the table (``window_table``).

    Returns ``(attn [B, H, Dv], k_pages, v_pages)``: pools updated in
    place on the fused path (input/output aliasing + donation at the
    model jit boundary). QuantPool pools (ops/quant.py, kv_dtype=fp8)
    ride the same slots: the fused kernel dequantizes in-register and
    quantizes the append in its staged RMW; the XLA path is the quantized
    scatter + gather/dequant attention."""
    from dynamo_tpu.ops.quant import is_quant

    D, Dv = q.shape[-1], v_new.shape[-1]
    if window:
        # the decode query stands at seq_len - 1
        block_tables, offset = jax.vmap(
            lambda bt, n: window_table(bt, n - 1, 1, window, k_pages.shape[3])
        )(block_tables, seq_lens)
        seq_lens = seq_lens - offset
    tp = mesh is not None and mesh.shape.get("tp", 1) > 1
    quantized = is_quant(k_pages)
    # zero q lanes (a padded pool's, or the other heads' of a packed
    # row) add 0 to every score, the V columns that are not this head's
    # are dropped: the scale pins to the TRUE model dim
    KH, pool_kh = k_new.shape[1], k_pages.shape[2]
    q = slot_queries(q, KH, pool_kh, k_pages.shape[-1])
    k_new = pool_rows(k_new, pool_kh, k_pages.shape[-1])
    v_new = pool_rows(v_new, v_pages.shape[2], v_pages.shape[-1])
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    # quantized pools under the tp shard_map are not plumbed yet: the
    # scale leaves would need their own specs. They take the XLA path,
    # which GSPMD partitions like any other gather/scatter
    if use_pallas() and not (quantized and tp):
        from jax.sharding import PartitionSpec as P

        from dynamo_tpu.ops.pallas.fused_decode import fused_decode_attention

        base = functools.partial(
            fused_decode_attention,
            layer=layer, window=window, scale=scale,
            interpret=jax.default_backend() != "tpu", scope=scope,
        )
        if sinks is not None:
            kernel = lambda q_, kp_, vp_, kn_, vn_, bt_, sl_, dp_, do_, s_: (  # noqa: E731
                base(q_, kp_, vp_, kn_, vn_, bt_, sl_, dp_, do_, sinks=s_)
            )
        else:
            kernel = lambda q_, kp_, vp_, kn_, vn_, bt_, sl_, dp_, do_: (  # noqa: E731
                base(q_, kp_, vp_, kn_, vn_, bt_, sl_, dp_, do_)
            )
        if tp:
            in_specs = [
                P(None, "tp", None),  # q: heads sharded
                P(None, None, "tp", None, None),  # k_pages: kv heads
                P(None, None, "tp", None, None),
                P(None, "tp", None),  # k_new: kv heads sharded
                P(None, "tp", None),
                P(None, None),  # block tables replicated
                P(None),  # seq lens
                P(None),  # dst_page
                P(None),  # dst_off
            ]
            if sinks is not None:
                in_specs.append(P("tp"))
            # dynalint: disable=DL013 -- array pools only: quantized+tp
            # is excluded above, and that exclusion is counted
            # (note_fallback quant_tp_shardmap)
            kernel = jax.shard_map(
                kernel,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=(
                    P(None, "tp", None),
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                ),
                check_vma=False,
            )
        args = (
            q, k_pages, v_pages, k_new, v_new, block_tables, seq_lens,
            dst_page, dst_off,
        )
        if sinks is not None:
            args = args + (sinks,)
        attn, k_pages, v_pages = kernel(*args)
        return slot_outputs(attn, KH, pool_kh, Dv), k_pages, v_pages

    from dynamo_tpu.ops.fallback import note_fallback
    from dynamo_tpu.ops.pallas.kv_write import write_new_kv

    if quantized and tp:
        # THE ROADMAP #7 residue: fp8 + tp>1 cannot ride the fused
        # kernel's shard_map (scale leaves lack specs), and it counts
        # itself instead of silently costing 3x. Checked FIRST: it forces
        # XLA even where Pallas runs, so it wins attribution over the
        # environmental reason below
        note_fallback("quant_tp_shardmap",
                      detail="decode_update_attention: fp8 pool under "
                             "tp shard_map takes the XLA scatter+gather")
    else:
        note_fallback("no_pallas_backend", expected=True,
                      detail="decode_update_attention: scatter+gather")
    k_pages, v_pages = write_new_kv(
        k_pages, v_pages, k_new, v_new, dst_page, dst_off,
        layer=layer, mesh=mesh,
    )
    k_l = k_pages.layer(layer) if quantized else k_pages[layer]
    v_l = v_pages.layer(layer) if quantized else v_pages[layer]
    attn = paged_decode_attention(
        q, k_l, v_l, block_tables, seq_lens,
        window=window, sinks=sinks, scale=scale,
        # exact new-token overlay (quant only): the XLA mirror of the
        # fused kernel's analytic merge. On the gather/dequant path the
        # freshly-written row would otherwise read back quantized
        new_kv=(k_new, v_new) if quantized else None,
    )
    return slot_outputs(attn, KH, pool_kh, Dv), k_pages, v_pages


def window_table(
    block_table: jax.Array,  # [P] one sequence's pages
    first_query: jax.Array,  # position of the first of the queries
    n_queries: int,  # consecutive queries (1 = a decode step)
    window: int,
    page_size: int,
) -> tuple[jax.Array, jax.Array | int]:
    """What a window layer reads for ``n_queries`` consecutive queries:
    (the pages that hold the queries and the ``window - 1`` tokens before
    the first, the position of the first token of those pages). Positions
    shift by whole pages, so every mask a reader builds comes out as over
    the whole table, and the reader moves and scores the window's pages,
    not a table's width. The whole table where that is no shorter."""
    n = (n_queries + window - 2) // page_size + 2
    P_ = block_table.shape[0]
    if not window or n >= P_:
        return block_table, 0
    first = jnp.maximum(first_query - window + 1, 0) // page_size
    # past the table's end the ids repeat its last entry: those positions
    # lie past the sequence's length and are masked
    ids = jnp.minimum(first + jnp.arange(n), P_ - 1)
    return block_table[ids], first * page_size


# ------------------------------------------------------- latent attention
# Latent attention (MLA, models/mla.py) keeps ONE pool ``[L, num_pages,
# page, D]``: a row a token, ``[c | k_r | lane padding]``, shared by the
# heads. Decode is absorbed (scores and values against the rows
# themselves); prefill is not (a block of rows is up-projected to per-head
# keys and values once and scored by the whole call's queries).


def latent_rows(pool, layer, ids: jax.Array) -> jax.Array:
    """The rows on pages ``ids`` [..., n] of layer ``layer`` of a latent
    pool, ``[..., n * page, D]``: in the pool's dtype, or float32 for a
    ``QuantPool`` (one scale a row). One indexing step out of the pool, as
    ``gather_pages``."""
    from dynamo_tpu.ops.quant import is_quant

    if is_quant(pool):
        rows = pool.vals[layer, ids].astype(jnp.float32) * pool.scale[
            layer, ids
        ].astype(jnp.float32)[..., None]
    else:
        rows = pool[layer, ids]
    return rows.reshape(*ids.shape[:-1], -1, rows.shape[-1])


def _flash_merge(carry, s, v, valid):
    """One block folded into a running softmax. carry ``(m, l, acc)``
    float32 with ``acc [..., q, dv]``; s ``[..., q, n]`` float32 scores,
    masked here by ``valid`` (broadcastable to s); v ``[..., n, dv]``."""
    m, l, acc = carry
    s = jnp.where(valid, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    # a row that has met no key yet has m_new == NEG_INF and exp(0) here
    p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
    pv = jnp.einsum(
        "...qn,...nd->...qd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_new, alpha * l + p.sum(axis=-1), alpha[..., None] * acc + pv


def paged_latent_decode_attention(
    q: jax.Array,  # [B, H, D]: [q_lat | q_rope | 0], pre-scaled
    pool,  # [L, num_pages, page, D], holding positions < seq_len - 1
    layer,
    new_rows: jax.Array,  # [B, D] the step's own rows, exact
    block_tables: jax.Array,  # [B, P]
    seq_lens: jax.Array,  # [B] INCLUDING the new token
    *,
    dc: int,
) -> jax.Array:
    """The XLA reference of ``ops/pallas/latent_decode.py``: absorbed
    decode attention over a latent pool, float32, walked in blocks of
    pages up to the batch's LONGEST live context (a run-time count), the
    new token merged analytically as the kernel does. Returns ``o_lat
    [B, H, dc]`` float32."""
    B, H, D = q.shape
    page, P = pool.shape[2], block_tables.shape[1]
    bp = min(P, max(1, _BLOCK_TOKENS // page))
    span = bp * page
    end = seq_lens - 1
    qf = q.astype(jnp.float32)

    def block(j, carry):
        ids = block_tables[:, jnp.minimum(j * bp + jnp.arange(bp), P - 1)]
        rows = latent_rows(pool, layer, ids).astype(jnp.float32)
        valid = (j * span + jnp.arange(span))[None, :] < end[:, None]
        rows = jnp.where(valid[..., None], rows, 0.0)
        s = jnp.einsum("bhd,bsd->bhs", qf, rows)
        return _flash_merge(carry, s, rows[..., :dc], valid[:, None, :])

    carry = jax.lax.fori_loop(
        0, (jnp.max(end) + span - 1) // span, block,
        (
            jnp.full((B, H), NEG_INF, jnp.float32),
            jnp.zeros((B, H), jnp.float32),
            jnp.zeros((B, H, dc), jnp.float32),
        ),
    )
    new = new_rows.astype(jnp.float32)[:, None, :]  # [B, 1, D]
    s_new = jnp.einsum("bhd,bsd->bhs", qf, new)
    _, l, acc = _flash_merge(carry, s_new, new[..., :dc], True)
    return acc / l[..., None]


def latent_kernel_serves(pool, mesh=None) -> bool:
    """Whether the Mosaic latent kernels (decode and prefill alike) serve
    this pool: Pallas active, a plain array, one shard of heads. Public
    for the engine, whose ``prefill_kv`` counters ask the tiling of the
    implementation its dispatches get."""
    from dynamo_tpu.ops.quant import is_quant

    tp = mesh is not None and mesh.shape.get("tp", 1) > 1
    return use_pallas() and not is_quant(pool) and not tp


def latent_decode_schedule(pool, block_tables, seq_lens, mesh=None):
    """What a decode STEP can make once for all its layers' calls of
    ``latent_decode_update_attention``: the kernel's schedule (each
    sequence's live chunks, buffers, successors: a dozen small operations
    that depend on the lengths alone), or None where the XLA walk
    serves."""
    if not latent_kernel_serves(pool, mesh):
        return None
    from dynamo_tpu.ops.pallas.latent_decode import latent_schedule

    with jax.named_scope(SCOPE_LATENT_SCHEDULE):
        return latent_schedule(pool, block_tables, seq_lens)


def latent_decode_update_attention(
    q_lat: jax.Array,  # [B, H, dc]: q_nope through W_uk
    q_rope: jax.Array,  # [B, H, dr]
    pool,  # [L, num_pages, page, D >= dc + dr] (or a QuantPool)
    new_rows: jax.Array,  # [B, dc + dr] the step's rows
    block_tables: jax.Array,
    seq_lens: jax.Array,  # [B] INCLUDING the new token
    dst_page: jax.Array,  # [B] (0 = trash)
    dst_off: jax.Array,
    *,
    layer,
    scale: float,
    mesh=None,
    schedule: tuple | None = None,  # ``latent_decode_schedule``'s
):
    """A latent layer's decode step, append + absorbed attention, and the
    place its implementation is chosen (``decode_update_attention``'s
    twin): the Mosaic kernel (ops/pallas/latent_decode.py) wherever Pallas
    is active and the pool is a plain array on one shard of heads; else
    the XLA walk (``paged_latent_decode_attention``), counted: an fp8 pool
    as ``latent_fp8_xla`` and a tp mesh as ``latent_tp_xla`` (the kernel
    has neither a dequantising chunk nor a ``shard_map`` yet; no cell
    reads either), the CPU and ``DYNAMO_PALLAS=0`` as
    ``no_pallas_backend``. Returns ``(o_lat [B, H, dc], pool)``."""
    from dynamo_tpu.ops.fallback import note_fallback
    from dynamo_tpu.ops.quant import is_quant, quant_append_rows

    dc, D = q_lat.shape[-1], pool.shape[-1]
    quantized = is_quant(pool)
    q = jnp.concatenate(
        [q_lat.astype(jnp.float32), q_rope.astype(jnp.float32)], axis=-1
    ) * scale
    q, new_rows = pad_heads(q, D), pad_heads(new_rows, D)
    if latent_kernel_serves(pool, mesh):
        from dynamo_tpu.ops.pallas.latent_decode import latent_decode_attention

        out, pool = latent_decode_attention(
            q.astype(pool.dtype), pool, new_rows, block_tables, seq_lens,
            dst_page, dst_off, layer=layer, dc=dc,
            interpret=jax.default_backend() != "tpu",
            scope=SCOPE_ATTN_LATENT, schedule=schedule,
        )
        return out, pool
    if not use_pallas():
        note_fallback("no_pallas_backend", expected=True,
                      detail="latent_decode_update_attention: XLA walk")
    elif quantized:
        note_fallback("latent_fp8_xla",
                      detail="latent decode: the kernel reads bf16 pools")
    else:
        note_fallback("latent_tp_xla",
                      detail="latent decode: no shard_map over heads yet")
    out = paged_latent_decode_attention(
        q, pool, layer, new_rows, block_tables, seq_lens, dc=dc
    )
    if quantized:
        pool = quant_append_rows(pool, new_rows, dst_page, dst_off, layer)
    else:
        pool = pool.at[layer, dst_page, dst_off].set(
            new_rows.astype(pool.dtype)
        )
    return out.astype(q_lat.dtype), pool


# Rows a query tile of the latent prefill KERNEL holds (PERF.md section 6,
# PR 36: the chip's timings of 128 / 256 / 512); a shorter call is one tile
# of whole lane tiles, since the kernel keeps a tile's rows on the lanes.
_LATENT_TILE_ROWS = 256


def latent_prefill_tiling(
    n_queries: int, pages_per_seq: int, page_size: int, kernel: bool = False
) -> tuple[int, int]:
    """``(tq, bp)`` of prefill attention over latents, asked of whichever
    implementation a dispatch gets (``latent_kernel_serves``): the
    kernel's tiles of ``_LATENT_TILE_ROWS`` rows, each visiting the blocks
    up to its own last real row, or the XLA walk's ONE tile of all the
    call's rows (its block's up-projection is paid once a block, so every
    query scores it while it is there); blocks of ``prefill_tiling``'s
    size either way. With ``prefill_blocks`` it is the trip counts of
    both and the engine's ``prefill_kv.*.latent`` counters alike."""
    bp = prefill_tiling(n_queries, pages_per_seq, page_size)[1]
    if not kernel:
        return n_queries, bp
    return min(_LATENT_TILE_ROWS, -(-n_queries // 128) * 128), bp


def latent_prefill_walk(
    q_nope: jax.Array,  # [T, H, dn]: queries at start_pos + arange(T)
    q_rope: jax.Array,  # [T, H, dr]
    pool,  # [L, num_pages, page, D] (the call's rows already written)
    layer,
    w_uk: jax.Array,  # [H, dc, dn]
    w_uv: jax.Array,  # [H, dc, dv]
    block_table: jax.Array,  # [P]
    start_pos: jax.Array,
    kv_len: jax.Array,  # start_pos + the real rows
    *,
    scale: float,
    new_rows: jax.Array | None = None,  # [T, dc + dr] exact (quant pools)
) -> jax.Array:
    """The XLA twin of ``ops/pallas/latent_prefill.py``, one sequence:
    the walk of ``paged_prefill_attention`` with the loop over blocks
    outermost. A block of ``bp`` pages is gathered (``latent_rows``),
    up-projected ONCE to per-head keys and values and scored by all ``T``
    query rows; blocks run from the table's first page to the last real
    row's (``prefill_blocks`` with one tile: a run-time count, under
    ``vmap`` a pack's longest member). Its ``[H, T, block]`` float32
    scores go through HBM, which is what the kernel is for; it serves
    where the kernel does not (the CPU, fp8 pools with their exact
    ``new_rows`` overlay, a tp mesh by GSPMD). Returns ``[T, H, dv]``."""
    T, H, _ = q_nope.shape
    dc, dv = w_uk.shape[1], w_uv.shape[2]
    dr = q_rope.shape[-1]
    page, P = pool.shape[2], block_table.shape[0]
    tq, bp = latent_prefill_tiling(T, P, page)
    span = bp * page
    q_pos = start_pos + jnp.arange(T)
    _, count = prefill_blocks(start_pos, kv_len - start_pos, 0, tq, 0, page, bp)
    dt = q_nope.dtype

    def block(j, carry):
        ids = block_table[jnp.minimum(j * bp + jnp.arange(bp), P - 1)]
        rows = latent_rows(pool, layer, ids)
        kv_pos = j * span + jnp.arange(span)
        if new_rows is not None:
            at = q_pos - j * span
            at = jnp.where(at < 0, span, at)  # before the block
            rows = rows.at[at, : dc + dr].set(
                new_rows.astype(rows.dtype), mode="drop")
        rows = jnp.where((kv_pos < kv_len)[:, None], rows, 0).astype(dt)
        c, k_r = rows[:, :dc], rows[:, dc: dc + dr]
        k_n = jnp.einsum("sc,hcn->hsn", c, w_uk,
                         preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("sc,hcv->hsv", c, w_uv,
                       preferred_element_type=jnp.float32).astype(dt)
        s = (
            jnp.einsum("thn,hsn->hts", q_nope, k_n,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("thr,sr->hts", q_rope, k_r,
                         preferred_element_type=jnp.float32)
        ) * scale
        valid = (kv_pos[None, :] <= q_pos[:, None]) & (kv_pos < kv_len)
        return _flash_merge(carry, s, v, valid)

    _, l, acc = jax.lax.fori_loop(
        0, count, block,
        (
            jnp.full((H, T), NEG_INF, jnp.float32),
            jnp.zeros((H, T), jnp.float32),
            jnp.zeros((H, T, dv), jnp.float32),
        ),
    )
    # a padded member of a pack visits nothing: l is 0 there
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(1, 0, 2).astype(dt)


@functools.partial(jax.jit, static_argnames=("scale", "mesh"))
def latent_prefill_attention(
    q_nope: jax.Array,  # [N, T, H, dn]: member n's rows at start_pos[n] + t
    q_rope: jax.Array,  # [N, T, H, dr]
    pool,  # [L, num_pages, page, D] (the call's rows already written)
    layer,  # scalar, not static: the layers share one trace
    w_uk: jax.Array,  # [H, dc, dn]
    w_uv: jax.Array,  # [H, dc, dv]
    block_tables: jax.Array,  # [N, P]
    start_pos: jax.Array,  # [N]
    kv_len: jax.Array,  # [N]: start_pos + the real rows
    *,
    scale: float,
    new_rows: jax.Array | None = None,  # [N, T, dc + dr] exact (quant pools)
    mesh=None,
) -> jax.Array:
    """Causal attention of ``N`` sequences' new queries over their PAGED
    latents, NOT absorbed (``(dn + dr + dv) x 2`` FLOP a pair where the
    absorbed form pays ``(2 dc + dr) x 2``), and the place its
    implementation is chosen (``latent_decode_update_attention``'s twin on
    the prefill side): every prefill program and the verify come through
    here, a single prompt or chunk as a pack of one. The Mosaic kernel
    (ops/pallas/latent_prefill.py: the pack's members a grid axis, a
    tile-by-block score never out of VMEM, a block up-projected once for
    every query tile, a tile stopping at its causal edge) wherever
    ``latent_kernel_serves``; else the XLA walk a member under ``vmap``,
    counted: an fp8 pool as ``latent_prefill_fp8_xla`` (the kernel has no
    dequantising block and no exact ``new_rows`` overlay), a tp mesh as
    ``latent_prefill_tp_xla`` (no ``shard_map`` over heads yet), the CPU
    and ``DYNAMO_PALLAS=0`` as ``no_pallas_backend``. Either way what is
    fetched and scored follows the prompts, not the table's width.
    Operands in the model's dtype, float32 accumulation, scores and
    softmax. Returns ``[N, T, H, dv]``."""
    from dynamo_tpu.ops.fallback import note_fallback
    from dynamo_tpu.ops.quant import is_quant

    # dynalint: disable=DL011 -- a probe of the pool's pytree form
    # (is_quant) and the static mesh, not of traced data
    if latent_kernel_serves(pool, mesh):
        from dynamo_tpu.ops.pallas.latent_prefill import latent_prefill_kernel

        T = q_nope.shape[1]
        page, P = pool.shape[2], block_tables.shape[1]
        tq, bp = latent_prefill_tiling(T, P, page, kernel=True)
        tiles = jnp.arange(-(-T // tq))[None, :]
        _, counts = prefill_blocks(
            start_pos[:, None], (kv_len - start_pos)[:, None], tiles, tq, 0,
            page, bp,
        )
        return latent_prefill_kernel(
            q_nope, pad_heads(q_rope, pool.shape[-1] - w_uk.shape[1]), pool,
            w_uk, w_uv, block_tables, start_pos, kv_len, counts,
            layer=layer, scale=scale, tq=tq, bp=bp,
            interpret=jax.default_backend() != "tpu",
            scope=SCOPE_PREFILL_LATENT,
        )
    if not use_pallas():
        note_fallback("no_pallas_backend", expected=True,
                      detail="latent_prefill_attention: XLA walk")
    elif is_quant(pool):
        note_fallback("latent_prefill_fp8_xla",
                      detail="latent prefill: the kernel reads bf16 pools")
    else:
        note_fallback("latent_prefill_tp_xla",
                      detail="latent prefill: no shard_map over heads yet")
    return jax.vmap(
        lambda qn, qr, bt, sp, kvl, nr: latent_prefill_walk(
            qn, qr, pool, layer, w_uk, w_uv, bt, sp, kvl, scale=scale,
            new_rows=nr)
    )(q_nope, q_rope, block_tables, start_pos, kv_len, new_rows)


# ------------------------------------------------------------------- KDA
# Kimi Delta Attention (arXiv:2510.26692): the gated delta rule with a
# decay a channel, over a state S [dk, dv] a head a sequence, float32:
#
#     S' = diag(exp(g_t)) S_{t-1}
#     S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
#     o_t = S_t^T q_t
#
# Appended at the file's end: no softmax line moved.

KDA_BLOCK = 64  # tokens a block of the chunkwise form
KDA_SUB = 16  # and a sub-block inside it: the span an inverse decay covers
_HI = jax.lax.Precision.HIGHEST


def kda_recurrence(q, k, v, g, beta, s0):
    """The recurrence a token at a time: what the chunkwise form must
    equal (tests; the plain form of ``reference_forward``). q, k, g: [T,
    H, dk]; v: [T, H, dv]; beta: [T, H]; s0: [H, dk, dv]. Returns (o [T,
    H, dv], s)."""

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        sd = jnp.exp(g_t)[..., None] * s
        r = jnp.einsum("hkv,hk->hv", sd, k_t, precision=_HI)
        s = sd + k_t[..., None] * (b_t[..., None] * (v_t - r))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=_HI)

    f32 = jnp.float32
    s, o = jax.lax.scan(step, s0.astype(f32), (
        q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
        beta.astype(f32),
    ))
    return o, s


def kda_chunk_operands(q, k, v, g, beta):
    """What a block of ``C`` tokens contributes that does not depend on
    the state it starts from. q, k, g: [..., C, dk]; v: [..., C, dv];
    beta: [..., C]; all float32, g <= 0 the log decay a channel. With
    ``G_t`` the decay summed from the block's start to token t, the
    weight of token s in token t is ``exp(G_t - G_s)`` a channel (s <=
    t). Returns ``(ut, w, qd, b, kend, gamma)``:

        U = ut - w S0;  O = qd S0 + b U;  S1 = gamma * S0 + kend^T U

    ``ut``, ``w``: ``T^-1 (beta v)`` and ``T^-1 (beta k exp(G))`` with ``T
    = I + beta * tril(A, -1)``, ``A[t, s] = sum_c k_t k_s exp(G_t - G_s)``
    (a unit lower triangular solve: forward substitution, as the
    recurrence orders it); ``b`` is A's twin for the queries with its
    diagonal. Every exponent is taken of a non-positive number, except
    inside a sub-block of 16 tokens, where keys are brought back to the
    sub-block's first token (FLA's own bound: a decay past e^-80 over 16
    tokens, -5 a token, is beyond it)."""
    C, dk = q.shape[-2:]
    sub = min(KDA_SUB, C)
    ns = C // sub
    G = jnp.cumsum(g, axis=-2)
    # each sub-block's reference: the decay up to the token before it
    ends = G.reshape(*G.shape[:-2], ns, sub, dk)[..., :-1, -1, :]
    R = jnp.concatenate([jnp.zeros_like(G[..., :1, :]), ends], axis=-2)
    Rt = jnp.repeat(R, sub, axis=-2)  # [..., C, dk]
    e_in = jnp.exp(G - Rt)  # <= 1
    kt, qt = k * e_in, q * e_in
    kh = k * jnp.exp(jnp.minimum(Rt - G, 80.0))

    def mm(a, b_):
        return jnp.einsum("...td,...sd->...ts", a, b_, precision=_HI)

    a_diag, b_diag = mm(kt, kh), mm(qt, kh)
    # a row of sub-block i against the keys before it, decayed to i's
    # reference (both factors <= 1)
    zero = jnp.zeros((*q.shape[:-2], sub, C), q.dtype)
    a_off, b_off = [zero], [zero]
    for i in range(1, ns):
        ko = k * jnp.exp(jnp.minimum(R[..., i:i + 1, :] - G, 0.0))
        rows = slice(i * sub, (i + 1) * sub)
        a_off.append(mm(kt[..., rows, :], ko))
        b_off.append(mm(qt[..., rows, :], ko))
    t = jnp.arange(C)
    same = (t[:, None] // sub) == (t[None, :] // sub)
    before = (t[None, :] // sub) < (t[:, None] // sub)
    strict, upto = t[None, :] < t[:, None], t[None, :] <= t[:, None]
    a = jnp.where(same & strict, a_diag,
                  jnp.where(before, jnp.concatenate(a_off, axis=-2), 0.0))
    b = jnp.where(same & upto, b_diag,
                  jnp.where(before, jnp.concatenate(b_off, axis=-2), 0.0))
    tri = jnp.eye(C, dtype=q.dtype) + beta[..., None] * a
    eg = jnp.exp(G)
    rhs = beta[..., None] * jnp.concatenate([v, k * eg], axis=-1)
    sol = jax.lax.linalg.triangular_solve(
        tri, rhs, left_side=True, lower=True, unit_diagonal=True
    )
    dv = v.shape[-1]
    last = G[..., -1:, :]
    return (sol[..., :dv], sol[..., dv:], q * eg, b,
            k * jnp.exp(last - G), jnp.exp(last[..., 0, :]))


def kda_prefill_blocks(num_tokens) -> int:
    """Blocks of ``KDA_BLOCK`` tokens that hold a real token, a call's
    rows summed: what ``kda_chunk`` must carry a state through (the
    engine's ``kda.prefill_blocks`` counter; numpy or python integers)."""
    import numpy as np

    return int((-(-np.asarray(num_tokens) // KDA_BLOCK)).sum())


def kda_chunk_prefill(q, k, v, g, beta, pool, rows, fresh, *, layer: int):
    """The chunkwise form over whole rows, from and to the sequences' rows
    of the state pool ``[L, rows + 1, H, dk, dv]`` float32, and the place
    its implementation is chosen: the ``kda_chunk`` kernel
    (ops/pallas/kda.py) wherever Pallas is active, which takes the layer's
    operands as they are (``[N, T, H d]``, a free reshape), forms a block's
    half that does not depend on the state (``kda_chunk_operands``' terms)
    in VMEM at the step that carries the state through it, reads a row
    once and writes it once in place; elsewhere ``kda_chunk_operands`` over
    all blocks at once in XLA and a ``lax.scan`` a BLOCK between a gather
    and a scatter (counted ``no_pallas_backend``). q, k, g: [N, T, H, dk];
    v: [N, T, H, dv]; beta: [N, T, H]; rows: [N] int32 (the pool's last
    row = trash); fresh: [N] bool, start from a zero state. A padded token
    carries g = 0 and beta = 0: it leaves the state as it was. Returns (o
    [N, T, H, dv] float32, pool)."""
    from dynamo_tpu.ops.fallback import note_fallback

    f32 = jnp.float32
    N, T, H, _ = q.shape
    C = min(KDA_BLOCK, -(-T // KDA_SUB) * KDA_SUB)
    pad = -T % C
    nb = (T + pad) // C
    if use_pallas():
        from dynamo_tpu.ops.pallas.kda import kda_chunk

        def flat(x):  # [N, T, ...] -> [N, T + pad, H d]: a head a lane tile
            x = x.astype(f32).reshape(N, T, -1)
            return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

        # what XLA is left of the batched half: the pad of the token axis
        with jax.named_scope(SCOPE_KDA_CHUNK_OPERANDS):
            args = [flat(x) for x in (q, k, v, g, beta)]
        o, pool = kda_chunk(
            *args, pool, rows, fresh, layer=layer, block=C,
            sub=min(KDA_SUB, C), interpret=jax.default_backend() != "tpu",
            scope=SCOPE_KDA_CHUNK,
        )
        return o[:, :T].reshape(N, T, H, -1), pool
    note_fallback("no_pallas_backend", expected=True,
                  detail="kda_chunk_prefill: XLA operands, lax.scan over blocks")

    def blocks(x):  # [N, T, H, d] -> [N, H, nb, C, d]
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(N, nb, C, H, -1).transpose(0, 3, 1, 2, 4)

    # the batched half of the chunkwise form is a region of its own: XLA
    # fusions, triangular solves and re-layouts beside the scan
    with jax.named_scope(SCOPE_KDA_CHUNK_OPERANDS):
        ut, w, qd, b, kend, gamma = kda_chunk_operands(
            blocks(q), blocks(k), blocks(v), blocks(g),
            blocks(beta[..., None])[..., 0],
        )

    def step(s, x):
        ut_, w_, qd_, b_, kend_, gamma_ = x  # [N, H, ...] of one block
        u = ut_ - jnp.einsum("nhck,nhkv->nhcv", w_, s, precision=_HI)
        o_ = (jnp.einsum("nhck,nhkv->nhcv", qd_, s, precision=_HI)
              + jnp.einsum("nhcs,nhsv->nhcv", b_, u, precision=_HI))
        s = gamma_[..., None] * s + jnp.einsum(
            "nhck,nhcv->nhkv", kend_, u, precision=_HI)
        return s, o_

    with jax.named_scope(SCOPE_KDA_CHUNK):
        s0 = jnp.where(fresh[:, None, None, None], 0.0, pool[layer, rows])
        s, o = jax.lax.scan(step, s0, tuple(
            jnp.moveaxis(x, 2, 0) for x in (ut, w, qd, b, kend, gamma)
        ))
        o = jnp.moveaxis(o, 0, 2)
        pool = pool.at[layer, rows].set(s)
    with jax.named_scope(SCOPE_KDA_CHUNK_OPERANDS):
        o = o.transpose(0, 2, 3, 1, 4).reshape(N, nb * C, H, -1)
        return o[:, :T], pool


def kda_decode_step(pool, conv, rows, x, taps, g, beta, *, layer: int):
    """One decode step of every slot over layer ``layer`` of the state
    pool ``[L, rows + 1, H, dk, dv]`` float32 and of the tails' pool ``conv
    [L, rows + 1, taps - 1, 3, H dk]``, FROM THE LAYER'S PROJECTIONS as
    the matmuls leave them, and the place the implementation is chosen.
    x: [B, 3, H dk], the new token's q | k | v projections apart; taps:
    [taps, 3, H dk], the layer's short convolutions; g: [B, H, dk] float32,
    the log decay a channel; beta: [B, H] float32; ``rows`` [B]: each
    slot's row, the trash row (the pools' last) for a slot that owns none.
    What lies between the projections and the state happens here, a slot
    at a time: the causal convolution over [the slot's tail; its new row]
    in float32, SiLU, q's and k's norm a head (q also ``dk ** -0.5``), the
    gated delta step, the tail shifted by the new row. Wherever Pallas is
    active that is the ``kda_step`` kernel (each live slot's state row and
    tail read once and written once, in place, in one call); else
    ``kda_step_xla`` at this file's end: a gather of the slots' rows and
    tails, the same arithmetic and scatters back (counted
    ``no_pallas_backend``). Returns (o [B, H, dv] float32, pool, conv)."""
    from dynamo_tpu.ops.fallback import note_fallback

    alpha, x = jnp.exp(g.astype(jnp.float32)), x.astype(conv.dtype)
    beta = beta.astype(jnp.float32)
    if use_pallas():
        from dynamo_tpu.ops.pallas.kda import kda_step

        return kda_step(
            pool, conv, rows, x, taps, alpha, beta, layer=layer,
            interpret=jax.default_backend() != "tpu", scope=SCOPE_KDA_STEP,
        )
    note_fallback("no_pallas_backend", expected=True,
                  detail="kda_decode_step: gather, step, scatter")
    return kda_step_xla(pool, conv, rows, x, taps, alpha, beta, layer=layer)


# ------------------------------------------------------------------- SSD
# Mamba-2's state-space duality mixer (arXiv:2405.21060): a state H [P, N]
# a head a sequence, float32, under a SCALAR decay a head a token, with B
# and C shared by the heads of a group:
#
#     H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t
#     y_t = H_t C_t + D x_t
#
# Appended at the file's end: no softmax or KDA line moved.


def ssd_recurrence(x, dt, A, B, C, D, h0):
    """The recurrence a token at a time: what the chunk form must equal
    (tests). x: [T, H, P]; dt: [T, H]; A, D: [H]; B, C: [T, G, N]; h0: [H,
    P, N]. Returns (y [T, H, P] float32, h)."""
    f32 = jnp.float32
    H = x.shape[1]
    rep = H // B.shape[1]

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(y, rep, axis=0) for y in (b_t, c_t))
        h = jnp.exp(dt_t * A)[:, None, None] * h + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y = jnp.einsum("hpn,hn->hp", h, c_t, precision=_HI)
        return h, y + D[:, None] * x_t

    h, y = jax.lax.scan(step, h0.astype(f32), (
        x.astype(f32), dt.astype(f32), B.astype(f32), C.astype(f32)))
    return y, h


def ssd_prefill_chunks(num_tokens, chunk: int) -> int:
    """Chunks of ``chunk`` tokens that hold a real token, a call's rows
    summed: what the chunk form carries a state through (the engine's
    ``ssd.prefill_chunks`` counter; numpy or python integers)."""
    import numpy as np

    return int((-(-np.asarray(num_tokens) // chunk)).sum())


@jax.named_scope(SCOPE_SSD_CHUNK)
def ssd_chunk_prefill(x, dt, A, B, C, D, pool, rows, fresh, *, layer: int,
                      chunk: int):
    """The chunkwise (SSD) form over whole rows, from and to the
    sequences' rows of the state pool ``[L, rows + 1, H, P, N]`` float32:
    inside a chunk of ``chunk`` tokens the masked ``(C B^T) (.) L`` product
    with ``L[t, s] = exp(sum of the log decays after s up to t)``, between
    chunks the carried state, read from the row once before the first
    chunk (or zero at a sequence's start) and written once after the
    last. Plain batched XLA, all of it: its arithmetic is a hundredth of
    the projections' beside it. x: [N, T, H, P]; dt: [N, T, H] float32,
    softplus applied, 0 at a padded token (which then leaves the state as
    it was and adds nothing); A, D: [H] float32; B, C: [N, T, G, N];
    rows: [N] int32 (the pool's last row = trash); fresh: [N] bool. The
    products inside a chunk run in x's dtype (float32 accumulation), what
    touches the carried state in float32 at ``HIGHEST``. Every exponent
    is of a non-positive number. Returns (y [N, T, H, P] float32,
    pool)."""
    f32 = jnp.float32
    N, T, H, P = x.shape
    G, S = B.shape[-2:]
    hg = H // G
    Q = min(chunk, T)
    pad = -T % Q
    nc = (T + pad) // Q
    cd = x.dtype

    def chunks(a):  # [N, T, h, ...] -> [N, h, nc, Q, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(N, nc, Q, *a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    xc = chunks(x)  # [N, H, nc, Q, P]
    dtc = chunks(dt.astype(f32))  # [N, H, nc, Q]
    Bc, Cc = chunks(B), chunks(C)  # [N, G, nc, Q, S]
    cum = jnp.cumsum(dtc * A[None, :, None, None], axis=-1)  # <= 0
    t = jnp.arange(Q)
    seen = t[:, None] >= t[None, :]
    decay = jnp.exp(jnp.where(
        seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    cb = jnp.einsum("ngctk,ngcsk->ngcts", Cc, Bc, preferred_element_type=f32)
    m = (decay.reshape(N, G, hg, nc, Q, Q) * cb[:, :, None]).reshape(
        N, H, nc, Q, Q)
    dx = dtc[..., None] * xc.astype(f32)  # [N, H, nc, Q, P]
    y = jnp.einsum("nhcts,nhcsp->nhctp", m.astype(cd), dx.astype(cd),
                   preferred_element_type=f32)
    # what a chunk adds to the state, decayed to its end, and its decay
    to_end = jnp.exp(cum[..., -1:] - cum)  # [N, H, nc, Q]
    adds = jnp.einsum(
        "ngjcsp,ngcsk->ngjcpk",
        (to_end[..., None] * dx).reshape(N, G, hg, nc, Q, P),
        Bc.astype(f32), precision=_HI,
    ).reshape(N, H, nc, P, S)
    gamma = jnp.exp(cum[..., -1])  # [N, H, nc]

    def carry(h, at):
        add, g = at
        return g[..., None, None] * h + add, h  # emits a chunk's START

    # a row at a time, by dynamic slices: a gather from (and a scatter
    # into) the pool makes the chip's compiler copy the pool, 0.5 GB a
    # layer at the published widths
    row = (1, 1) + pool.shape[2:]
    h0 = jnp.concatenate([
        jax.lax.dynamic_slice(pool, (layer, rows[i], 0, 0, 0), row)[0]
        for i in range(N)
    ])
    h0 = jnp.where(fresh[:, None, None, None], 0.0, h0)
    h, starts = jax.lax.scan(
        carry, h0, (jnp.moveaxis(adds, 2, 0), jnp.moveaxis(gamma, 2, 0)))
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "cngjpk,ngctk->ngjctp", starts.reshape(nc, N, G, hg, P, S),
        Cc.astype(f32), precision=_HI,
    ).reshape(N, H, nc, Q, P)
    y = jnp.moveaxis(y, 1, 3).reshape(N, nc * Q, H, P)[:, :T]
    y = y + D[:, None] * x.astype(f32)
    for i in range(N):  # in order: two members on the trash row are fine
        pool = jax.lax.dynamic_update_slice(
            pool, h[i][None, None], (layer, rows[i], 0, 0, 0))
    return y, pool


def ssd_decode_step(pool, conv, rows, x, dt, A, B, C, D, tail, *, layer: int):
    """One decode step of every slot over layer ``layer`` of the state
    pool ``[L, rows + 1, H, P, N]`` float32, the slots' new convolution
    tails ``tail [B, taps - 1, channels]`` put into ``conv [L, rows + 1,
    taps - 1, channels]``, and the place the implementation is chosen: the
    ``ssd_step`` kernel (ops/pallas/ssd.py) wherever Pallas is active
    (each live slot's row read once and written once, in place, the tails
    in the same call), else a gather of the slots' rows, the step in XLA
    and scatters back (counted ``no_pallas_backend``). ``rows`` [B]: each
    slot's row, the trash row (the pools' last) for a slot that owns
    none. x: [B, H, P]; dt: [B, H] float32, softplus applied; A, D: [H];
    B, C: [B, G, N]. Returns (y [B, H, P] float32, pool, conv)."""
    from dynamo_tpu.ops.fallback import note_fallback

    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    decay = jnp.exp(dt * A)  # [B, H]
    dx = dt[..., None] * x
    if use_pallas():
        from dynamo_tpu.ops.pallas.ssd import ssd_step

        y, pool, conv = ssd_step(
            pool, conv, rows, dx, decay, B, C, tail, layer=layer,
            interpret=jax.default_backend() != "tpu", scope=SCOPE_SSD_STEP,
        )
    else:
        note_fallback("no_pallas_backend", expected=True,
                      detail="ssd_decode_step: gather, step, scatter")
        with jax.named_scope(SCOPE_SSD_STEP):
            rep = x.shape[1] // B.shape[1]
            Bh, Ch = (jnp.repeat(a, rep, axis=1) for a in (B, C))
            h = decay[..., None, None] * pool[layer, rows] + (
                dx[..., None] * Bh[:, :, None, :])
            y = jnp.einsum("bhpn,bhn->bhp", h, Ch, precision=_HI)
            pool = pool.at[layer, rows].set(h)
            conv = conv.at[layer, rows].set(tail.astype(conv.dtype))
    return y + D[:, None] * x, pool, conv


# --------------------------------------------------- KDA's decode step, XLA
# Appended at the file's end: no softmax, KDA or SSD line moved.


def kda_step_xla(pool, conv, rows, x, taps, alpha, beta, *, layer: int):
    """``ops/pallas/kda.kda_step``'s XLA twin, from the same arguments
    (``kda_decode_step`` documents them; alpha = exp(g)): the slots' tails
    and state rows gathered, the causal taps in float32 in their order,
    SiLU, the norms, the step, the rows and the shifted tails scattered
    back."""
    f32 = jnp.float32
    B, H, dk = alpha.shape
    ext = jnp.concatenate(
        [conv[layer, rows].astype(x.dtype), x[:, None]], axis=1)
    taps = taps.astype(f32)
    q, k, v = jnp.moveaxis(jax.nn.silu(sum(
        taps[i] * ext[:, i].astype(f32) for i in range(taps.shape[0])
    )).reshape(B, 3, H, dk), 1, 0)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    sd = alpha[..., None] * pool[layer, rows]  # [B, H, dk, dv]
    r = jnp.einsum("bhkv,bhk->bhv", sd, k, precision=_HI)
    s = sd + k[..., None] * (beta[..., None] * (v - r))[:, :, None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
    return (o, pool.at[layer, rows].set(s),
            conv.at[layer, rows].set(ext[:, 1:].astype(conv.dtype)))


# ------------------------------------------------------ the selective scan
# Mamba-1's mixer (arXiv:2312.00752): a state S [N, C] a sequence, float32,
# N states a channel over C channels, under a decay that differs in EVERY
# element, with B and C shared by the channels:
#
#     S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
#     y_t[c]    = sum_n S_t[n, c] C_t[n] + D[c] x_t[c]
#
# The pool keeps the states LEADING and the channels on the lanes ([L, rows
# + 1, N, C]: ops/pallas/scan.py). Appended at the file's end: no softmax,
# KDA or SSD line moved.

# tokens a chunk of the prefill's XLA form (the twin of the ``scan_chunk``
# kernel: the CPU, ``DYNAMO_PALLAS=0``): its two temporaries are ``[rows,
# chunk, N, C]`` float32 each, 21 MB a row at 64 x 16 x 5,120
SCAN_CHUNK = 64


@jax.named_scope(SCOPE_SCAN)
def scan_chunk_prefill(x, dt, A, B, C, D, pool, rows, fresh, *, layer: int,
                       num_tokens=None, chunk: int = SCAN_CHUNK):
    """The selective scan over whole rows, from and to the sequences' rows
    of the state pool ``[L, rows + 1, N, C]`` float32, and the place the
    implementation is chosen: the ``scan_chunk`` kernel
    (ops/pallas/scan.py) wherever Pallas is active (the state on the chip
    through the walk of a row's tokens, the recurrence token by token, a
    block of tokens past ``num_tokens`` skipped), else its XLA twin
    (counted ``no_pallas_backend``): a ``lax.scan`` over
    chunks of ``chunk`` tokens that carries the state, and inside a chunk
    the recurrence as an ASSOCIATIVE scan of the pairs ``(decay, input)``
    under ``(a1, b1) . (a2, b2) = (a1 a2, a2 b1 + b2)``: every factor is an
    exponential of a non-positive number, so nothing overflows however
    fast a channel forgets (a cumulative-sum form divides by decays that
    underflow). Its temporaries are a chunk's pairs, ``[rows, chunk, N,
    C]`` float32 twice (21 MB a row each at 64 x 16 x 5,120; a 1,024-row
    call walks 16 chunks, a pack of two holds 84 MB), and the scan's
    halved copies of them. All in float32 on both paths. x: [R, T, C]; dt:
    [R, T, C] float32, softplus applied, 0 at a padded token (which then
    leaves the state as it was and adds nothing); A: [N, C], D: [C]
    float32; B, C: [R, T, N]; rows: [R] int32 (the pool's last row =
    trash); fresh: [R] bool; num_tokens: [R] int32, the members' real
    tokens (None: every token), past which a row's ``y`` is unspecified.
    Returns (y [R, T, C] float32, pool)."""
    from dynamo_tpu.ops.fallback import note_fallback

    if use_pallas():
        from dynamo_tpu.ops.pallas.scan import scan_chunk

        if num_tokens is None:
            num_tokens = jnp.full(x.shape[:1], x.shape[1], jnp.int32)
        # the kernel's own jit carries its name, SCOPE_SCAN_CHUNK, a leaf
        # of the region opened here
        y, pool = scan_chunk(
            x, dt, A, B, C, pool, rows, fresh, num_tokens, layer,
            interpret=jax.default_backend() != "tpu",
        )
        return y + D * x.astype(jnp.float32), pool
    note_fallback("no_pallas_backend", expected=True,
                  detail="scan_chunk_prefill: lax.scan over chunks, an "
                         "associative scan inside")
    f32 = jnp.float32
    R, T, Cn = x.shape
    Q = min(chunk, T)
    pad = -T % Q
    nc = (T + pad) // Q

    def chunks(a):  # [R, T, ...] -> [nc, R, Q, ...]
        a = jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(R, nc, Q, *a.shape[2:]), 1, 0)

    def combine(left, right):
        (a1, b1), (a2, b2) = left, right
        return a1 * a2, a2 * b1 + b2

    def one(s, at):
        x_c, dt_c, b_c, c_c = at  # [R, Q, C] x2, [R, Q, N] x2
        decay = jnp.exp(dt_c[:, :, None, :] * A)  # [R, Q, N, C]
        add = (dt_c * x_c)[:, :, None, :] * b_c[..., None]
        cum, inner = jax.lax.associative_scan(combine, (decay, add), axis=1)
        states = cum * s[:, None] + inner
        y = jnp.sum(states * c_c[..., None], axis=2)  # [R, Q, C]
        return states[:, -1], y

    # a row at a time, by dynamic slices (``ssd_chunk_prefill``: a gather
    # from the pool makes the chip's compiler copy it)
    row = (1, 1) + pool.shape[2:]
    s0 = jnp.concatenate([
        jax.lax.dynamic_slice(pool, (layer, rows[i], 0, 0), row)[0]
        for i in range(R)
    ])
    s0 = jnp.where(fresh[:, None, None], 0.0, s0)
    s, y = jax.lax.scan(one, s0, (chunks(x), chunks(dt), chunks(B), chunks(C)))
    y = jnp.moveaxis(y, 0, 1).reshape(R, nc * Q, Cn)[:, :T]
    y = y + D * x.astype(f32)
    for i in range(R):  # in order: two members on the trash row are fine
        pool = jax.lax.dynamic_update_slice(
            pool, s[i][None, None], (layer, rows[i], 0, 0))
    return y, pool


def scan_decode_step(pool, conv, rows, x, dt, A, B, C, D, tail, *, layer: int):
    """One decode step of every slot over layer ``layer`` of the state
    pool ``[L, rows + 1, N, C]`` float32, the slots' new convolution tails
    ``tail [B, taps - 1, C]`` put into ``conv [L, rows + 1, taps - 1,
    C]``, and the place the implementation is chosen: the ``scan_step``
    kernel (ops/pallas/scan.py) wherever Pallas is active (each live
    slot's row read once and written once, in place, the tails in the
    same call), else a gather of the slots' rows, the step in XLA and
    scatters back (counted ``no_pallas_backend``). ``rows`` [B]: each
    slot's row, the trash row (the pools' last) for a slot that owns
    none. x, dt: [B, C] (dt float32, softplus applied); A: [N, C], D:
    [C]; B, C: [B, N]. Returns (y [B, C] float32, pool, conv)."""
    from dynamo_tpu.ops.fallback import note_fallback

    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    if use_pallas():
        from dynamo_tpu.ops.pallas.scan import scan_step

        y, pool, conv = scan_step(
            pool, conv, rows, dt, dt * x, A, B, C, tail, layer=layer,
            interpret=jax.default_backend() != "tpu", scope=SCOPE_SCAN,
        )
    else:
        note_fallback("no_pallas_backend", expected=True,
                      detail="scan_decode_step: gather, step, scatter")
        with jax.named_scope(SCOPE_SCAN):
            s = jnp.exp(dt[:, None, :] * A) * pool[layer, rows] + (
                (dt * x)[:, None, :] * B[..., None])
            y = jnp.sum(s * C[..., None], axis=1)
            pool = pool.at[layer, rows].set(s)
            conv = conv.at[layer, rows].set(tail.astype(conv.dtype))
    return y + D * x, pool, conv
