"""Pallas TPU kernel v3: paged decode attention, deep DMA pipelining.

Why earlier kernels (and the jax library kernel) plateau ~7x off the HBM
roofline at decode shapes: their inner loops wait on a DOUBLE-BUFFERED
page DMA — a pipeline only one request deep — so every page fetch pays
most of its ~1-2us issue+latency serially: B*P serial waits per layer
dwarf the ~80us/layer the data itself needs at full bandwidth.

v3 changes both the schedule and the pool layout. The pool is
PAGE-MAJOR (``[num_pages, KH, page, D]``): one page's KV for all heads
is a single contiguous block, so each page moves with ONE DMA
descriptor. (In the old head-major layout the same all-heads slice was
a strided copy that expands to KH descriptors — and measurement shows
decode attention is DMA-DESCRIPTOR-bound: a no-DMA variant of this
kernel runs 16 layers in 0.9ms where the full head-major version needs
~15ms.) On top of that:

- One program per SEQUENCE fetches a WINDOW of that sequence's pages
  into VMEM with up to 2*window async copies issued back-to-back: the
  DMA engine works on the whole window concurrently instead of 1 page.
- Chunk-level double buffering with cross-program carry: while window
  chunk g computes, chunk g+1 — the next window of this sequence, or
  the FIRST window of the next sequence — is already in flight into the
  other buffer, so neither the chunk boundary nor the program boundary
  leaves the DMA engine idle.
- Within a window the page loop of tiny matmuls collapses into ONE
  [KH*G, window*KH*page] block-diagonal-masked score matmul
  (off-diagonal FLOPs are free at decode shapes; the MXU is latency-
  bound, and one big matmul beats window*KH small ones). Windows merge
  with flash-style online softmax, which reduces to a single pass when
  the table fits one window (the common serving shape).

Window size is chosen so VMEM stays bounded for ANY table length —
there is no large-table fallback path. WHOLE window chunks outside a
sequence's live range (or outside its sliding window) are skipped on
the prefetched seq_len — decode DMA tracks the actual context, not the
table width, for any table longer than one window. The guard is chunk-
granular on purpose: per-page guards measured ~20% slower (branches
between copy starts break the back-to-back DMA issue). Skipped buffer
slots hold stale data; masking handles correctness (V sanitized).

Reference counterpart: the engine-internal paged attention the
reference delegates to vLLM, plus its block-copy kernel
(lib/llm/src/kernels/block_copy.cu:42) — here the TPU owns both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# per-buffer-slot window budget (bytes of K or V, one chunk). The window
# buffer is four such slots (2 buffers x K+V) = 8 MiB, half of the 16 MiB
# scoped-VMEM limit the TPU compiler gives a kernel by default; the other
# half is the f32 working set of ONE window (upcast K/V, the score
# matrix), the fused kernel's page stage and the pipelined q/out blocks.
# Compiling for v5e, a 12 MiB buffer is still accepted and 14 MiB is
# refused, so 8 MiB leaves real headroom without raising the limit.
_WINDOW_SLOT_BYTES = 2 * 1024 * 1024

# cap on pages per window chunk: every window page owns 4 DMA semaphores
# (2 buffers x K+V) and the chip's semaphore space holds 512 words, shared
# with the fused kernel's RMW semaphores and the compiler's own — at 128
# window pages the compiler refuses the kernel ("memory space sflag")
_MAX_WINDOW_PAGES = 64


def _window_pages(KH: int, page: int, D: int, itemsize: int, P: int) -> int:
    """Pages per window chunk: bounded by the VMEM slot budget AND by the
    DMA-semaphore space. ``itemsize`` is the POOL dtype's. An 8-bit pool
    (ops/quant.py) gets a quarter of the slot BYTES, i.e. half the
    ELEMENTS of a bf16 one: its in-register upcast needs more working
    set per element — compiling for v5e, 1M fp8 elements per slot ask for
    16.6 MiB of scoped VMEM where 1M bf16 elements fit, and 768K fp8
    elements fit."""
    slot = _WINDOW_SLOT_BYTES // 4 if itemsize == 1 else _WINDOW_SLOT_BYTES
    per_page = KH * page * D * itemsize
    return max(1, min(P, _MAX_WINDOW_PAGES, slot // per_page))


def _decode_kernel_v3(
    # scalar prefetch (SMEM)
    block_tables_ref,  # [B, P] int32
    seq_lens_ref,  # [B] int32
    # inputs
    q_ref,  # [1, KH, G, D] VMEM (this sequence's query heads, pre-scaled)
    k_pages_ref,  # [num_pages, KH, page, D] ANY/HBM
    v_pages_ref,
    *rest,  # [kc_ref, vc_ref [1, 1, n_chunks*Nw] f32 when quantized,]
    # [sinks_ref [KH*G, 1] f32 VMEM when has_sinks,] o_ref, kv_buf, sems
    page_size: int,
    pages_per_seq: int,
    window_pages: int,
    window: int = 0,  # sliding window in tokens (0 = full attention)
    has_sinks: bool = False,  # per-head sink logits in the softmax denom
    quantized: bool = False,  # fp8 pages + host-expanded column scales
):
    i = 0
    if quantized:
        kc_ref, vc_ref = rest[:2]
        i = 2
    if has_sinks:
        sinks_ref = rest[i]
        i += 1
    else:
        sinks_ref = None
    o_ref, kv_buf, sems = rest[i: i + 3]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    P, Pw = pages_per_seq, window_pages
    n_chunks = (P + Pw - 1) // Pw  # static

    def chunk_live(seq, chunk):
        """Whether this window chunk intersects the sequence's live (and,
        for sliding layers, windowed) range. CHUNK granularity on purpose:
        a per-page guard was measured ~20% slower at near-full tables —
        branches between copy starts break the back-to-back DMA issue the
        kernel exists for — while chunk guards keep each window's issue
        burst intact and still skip whole windows of a long table that a
        short context (or a sliding window) never reads."""
        live = chunk * Pw * page_size < seq_lens_ref[seq]
        if window:
            live &= (chunk * Pw + Pw) * page_size > seq_lens_ref[seq] - window
        return live

    def issue(buf, seq, chunk):
        """Start one window's page copies (K and V). ``chunk`` is static;
        pages past P are skipped at trace time; whole chunks past the live
        range are skipped at run time (chunk_live). Skipped slots hold
        stale data, masked out by the validity check (V sanitized)."""

        @pl.when(chunk_live(seq, chunk))
        def _():
            for p in range(Pw):
                gp = chunk * Pw + p
                if gp >= P:
                    break
                pid = block_tables_ref[seq, gp]
                pltpu.make_async_copy(
                    k_pages_ref.at[pid], kv_buf.at[buf, 0, p],
                    sems.at[buf, 0, p],
                ).start()
                pltpu.make_async_copy(
                    v_pages_ref.at[pid], kv_buf.at[buf, 1, p],
                    sems.at[buf, 1, p],
                ).start()

    def wait(buf, seq, chunk):
        # must mirror issue() exactly: wait only on copies that started
        @pl.when(chunk_live(seq, chunk))
        def _():
            for p in range(Pw):
                if chunk * Pw + p >= P:
                    break
                pltpu.make_async_copy(
                    k_pages_ref.at[0], kv_buf.at[buf, 0, p],
                    sems.at[buf, 0, p],
                ).wait()
                pltpu.make_async_copy(
                    v_pages_ref.at[0], kv_buf.at[buf, 1, p],
                    sems.at[buf, 1, p],
                ).wait()

    # global chunk counter g = b * n_chunks + c; buffer = g % 2. Chunk 0
    # of program 0 is issued here; every other chunk is prefetched by its
    # predecessor, including across the program boundary.
    @pl.when(b == 0)
    def _():
        issue(0, 0, 0)

    KH, G, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    page = page_size
    Nw = Pw * KH * page
    seq_len = seq_lens_ref[b]
    qf = q_ref[0].reshape(KH * G, D).astype(jnp.float32)

    # flattened col c = (p*KH + kh)*page + t within a window: block-
    # diagonal by kv head; token position needs the window's page base
    row_kh = jax.lax.broadcasted_iota(jnp.int32, (KH * G, Nw), 0) // G
    col = jax.lax.broadcasted_iota(jnp.int32, (KH * G, Nw), 1)
    col_kh = (col // page) % KH
    col_page = col // (KH * page)  # window-local page index
    col_tok = col % page

    m = jnp.full((KH * G, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((KH * G, 1), jnp.float32)
    acc = jnp.zeros((KH * G, D), jnp.float32)

    for c in range(n_chunks):  # static unroll
        g = b * n_chunks + c
        buf = jax.lax.rem(g, 2)
        nxt = jax.lax.rem(g + 1, 2)
        if c + 1 < n_chunks:
            issue(nxt, b, c + 1)
        else:

            @pl.when(b + 1 < nb)
            def _(nxt=nxt):
                issue(nxt, b + 1, 0)

        wait(buf, b, c)
        kf = kv_buf[buf, 0].reshape(Nw, D).astype(jnp.float32)
        vf = kv_buf[buf, 1].reshape(Nw, D).astype(jnp.float32)
        if quantized or window or n_chunks > 1:
            # Only these shapes can SKIP fetches (chunk_live) and hence
            # read UNINITIALIZED VMEM: garbage K only feeds masked score
            # columns (where -> NEG_INF), but a non-finite V would turn
            # 0-prob x V into NaN in the acc matmul — sanitize. With one
            # always-live full-attention chunk every slot is written, and
            # skipping the isfinite select also sidesteps a Mosaic
            # layout-cast failure at small head dims (D=32).
            vf = jnp.where(jnp.isfinite(vf), vf, 0.0)
        scores = jax.lax.dot_general(
            qf, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [KH*G, Nw]
        if quantized:
            # dequant on the SCORE side: a page/head scale is constant
            # along D, so q.(s*k) == s*(q.k) — one [1, Nw] column scale
            # (host-expanded, ops/quant.table_col_scales) instead of a
            # [Pw, KH] -> [Pw, KH, page, D] broadcast Mosaic cannot lay
            # out; statically sliced per unrolled chunk
            scores = scores * kc_ref[0, :, c * Nw:(c + 1) * Nw]
        gp = c * Pw + col_page  # global page index
        pos = gp * page + col_tok
        valid = (col_kh == row_kh) & (pos < seq_len) & (gp < P)
        if window:
            # decode query sits at seq_len - 1: with a sliding window
            # only keys j >= seq_len - window are visible (gpt-oss
            # per-layer sliding attention)
            valid &= pos >= seq_len - window
        scores = jnp.where(valid, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(scores - m_new)  # masked cols underflow to 0
        l = l * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        if quantized:
            # V dequant folds into the probabilities the same way
            probs = probs * vc_ref[0, :, c * Nw:(c + 1) * Nw]
        acc = acc * alpha + jax.lax.dot_general(
            probs, vf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m = m_new

    if has_sinks:
        # merge the per-head sink logit as one more flash chunk: a virtual
        # key with value 0 — contributes exp(sink) to the denominator only
        # (HF gpt-oss eager_attention_forward concat-then-drop semantics)
        sink = sinks_ref[...]  # [KH*G, 1] f32, pre-shaped by the host
        m_f = jnp.maximum(m, sink)
        l = l * jnp.exp(m - m_f) + jnp.exp(sink - m_f)
        acc = acc * jnp.exp(m - m_f)
    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0] = out.reshape(KH, G, D).astype(o_ref.dtype)


def v3_supported(k_pages: jax.Array, block_tables: jax.Array) -> bool:
    """Whether the compiled kernel supports these shapes. The windowed
    schedule bounds VMEM for any table size, but Mosaic DMA slices must
    be LANE-ALIGNED: head_dim % 128 == 0 ("Slice shape along dimension 3
    must be aligned to tiling (128)"). Smaller heads (gpt-oss D=64, toy
    specs) fall back to the pure-XLA gather path on real TPUs."""
    from dynamo_tpu.ops.attention import lane_aligned

    return lane_aligned(k_pages.shape[-1])


# dynalint: disable=DL012 -- read-only attention: the kernel gathers
# from the pools and returns attention output; the pools stay live in
# the caller's decode state
@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def paged_decode_attention_v3(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [num_pages, KH, page, D] (fp8 when k_scale set)
    v_pages: jax.Array,
    block_tables: jax.Array,  # [B, P] int32
    seq_lens: jax.Array,  # [B] int32 (length INCLUDING the new token)
    *,
    window: int = 0,  # sliding window tokens (0 = full attention)
    sinks: jax.Array | None = None,  # [H] learned sink logits
    interpret: bool = False,
    scale: float | None = None,  # softmax scale; default 1/sqrt(D). The
    # caller overrides when q/pool are zero-padded past the true model
    # dim (ops/attention.pool_head_dim) so scores keep the real 1/sqrt(D)
    k_scale: jax.Array | None = None,  # [num_pages, KH] bf16 fp8 scales
    v_scale: jax.Array | None = None,  # (ops/quant.py layer slice)
) -> jax.Array:
    """Decode attention over the page-major paged cache. With
    ``k_scale``/``v_scale`` the pages are fp8 (ops/quant.py QuantPool
    layer slices) and the kernel dequantizes window chunks in-register —
    this is the quantized fallback path for ``DYNAMO_FUSED_DECODE=0``."""
    B, H, D = q.shape
    _, KH, page_size, _ = k_pages.shape
    G = H // KH
    P = block_tables.shape[1]
    Pw = _window_pages(KH, page_size, D, k_pages.dtype.itemsize, P)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q4 = (q.reshape(B, KH, G, D).astype(jnp.float32) * scale).astype(q.dtype)
    has_sinks = sinks is not None
    quantized = k_scale is not None

    kernel = functools.partial(
        _decode_kernel_v3,
        page_size=page_size,
        pages_per_seq=P,
        window_pages=Pw,
        window=window,
        has_sinks=has_sinks,
        quantized=quantized,
    )
    in_specs = [
        pl.BlockSpec(
            (1, KH, G, D), lambda b, *_: (b, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
              q4, k_pages, v_pages]
    if quantized:
        # host-expanded per-column scales: the kernel's own scale
        # indexing stays a static lane slice (same contract as
        # fused_decode)
        from dynamo_tpu.ops.quant import table_col_scales

        for sc in (k_scale, v_scale):
            cols = table_col_scales(sc, block_tables, page_size, Pw)
            in_specs.append(
                pl.BlockSpec(
                    (1,) + cols.shape[1:], lambda b, *_: (b, 0, 0),
                    memory_space=pltpu.VMEM,
                )
            )
            inputs.append(cols)
    if has_sinks:
        # already the [KH*G, 1] f32 column the flash merge consumes: an
        # IN-kernel (KH, G) -> (KH*G, 1) reshape is a vector layout cast
        # Mosaic cannot lower ("unsupported shape cast" at e.g. 4x4 ->
        # 16x1), so the host does it
        in_specs.append(
            pl.BlockSpec(
                (KH * G, 1), lambda b, *_: (0, 0), memory_space=pltpu.VMEM
            )
        )
        inputs.append(sinks.astype(jnp.float32).reshape(KH * G, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, KH, G, D), lambda b, *_: (b, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((2, 2, Pw, KH, page_size, D), k_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2, Pw)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        interpret=interpret,
    )(*inputs)
    return out.reshape(B, H, D)
