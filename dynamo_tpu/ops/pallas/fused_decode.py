"""Pallas TPU kernel: fused paged decode attention + KV append.

The per-layer decode hot path used to be TWO kernel launches: the
kv_write RMW kernel (ops/pallas/kv_write.py) landing the new token's K/V
row, then the v3 attention kernel (ops/pallas/paged_attention_v3.py)
reading the whole context back — including the page the write kernel
just round-tripped. This kernel collapses them into ONE ``pallas_call``
per layer, halving the decode program's kernel-launch count and dropping
one full page read per sequence per layer:

- Attention runs the v3 schedule unchanged (page-major pool, windowed
  deep-pipelined DMA, chunk-granular live guards, block-diagonal score
  matmul, flash merge) over the context WITHOUT the new token
  (``pos < seq_len - 1``), then merges the new token's contribution
  analytically as one extra flash chunk: its score is ``q . k_new`` and
  its value row is ``v_new`` — exact, because a single key/value needs
  no materialized page to attend to. Ordering (new token before the
  gpt-oss sink merge) is irrelevant: flash merges are associative.
- The KV append reuses kv_write's staged RMW: the destination page DMAs
  into a one-page VMEM stage at program start (overlapping the window
  fetches), the new row splices in after the chunk loop, and the page
  DMAs back while the program finishes its softmax/output write. The
  out-DMA is waited before the program ends, so the single stage buffer
  is safe to reuse by the next program. Sequences never share their
  tail page (prefix sharing covers sealed full pages only); the trash
  page (dst_page == 0, inactive slots) holds garbage by contract.

All-masked chunks (possible here at seq_len == 1, where the buffer has
no valid token yet) stay finite because NEG_INF is a finite sentinel:
masked columns contribute ``exp(0)`` rows that the first real merge
scales by ``exp(NEG_INF - real)`` == 0.

Pair with ``donate_argnums`` at every jit boundary above: the pools are
input/output-aliased, so the update is in place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.paged_attention_v3 import NEG_INF, _window_pages
from dynamo_tpu.ops.quant import (
    FP8_MAX,
    QuantPool,
    append_scale,
    is_quant,
    quant_values,
    rescale_factor,
    table_col_scales,
)


def _fused_decode_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,  # [B, P] int32
    seq_lens_ref,  # [B] int32 (length INCLUDING the new token)
    dst_page_ref,  # [B] int32 pool page for the new row (0 = trash)
    dst_off_ref,  # [B] int32 row offset within the page
    # inputs
    q_ref,  # [1, KH, G, D] VMEM (this sequence's query heads, pre-scaled)
    k_new_ref,  # [1, KH, D] VMEM (the new token's KV row)
    v_new_ref,  # [1, KH, D] VMEM
    k_pages_ref,  # [L, num_pages, KH, page, D] ANY/HBM (aliased out)
    v_pages_ref,
    *rest,  # [kc, vc, old_ks, old_vs,] [sinks,] o_ref, k_out_ref,
    # v_out_ref, [nks_ref, nvs_ref,] kv_buf, sems, stage_k, stage_v,
    # rmw_sems
    layer: int,
    page_size: int,
    pages_per_seq: int,
    window_pages: int,
    window: int = 0,  # sliding window in tokens (0 = full attention)
    has_sinks: bool = False,
    quantized: bool = False,  # fp8 pages + per-page/head scales
):
    i = 0
    if quantized:
        # host-prepared f32 scales: per window COLUMN [1, 1, n_chunks*Nw]
        # (ops/quant.table_col_scales) and the destination page's
        # current scales, lane-broadcast to [1, KH, 1, D] — all indexing
        # the kernel does on them is static (window chunk / whole block)
        # and heads sit on the LEADING axis, where Mosaic can broadcast
        # them over a [KH, page, D] page
        kc_ref, vc_ref, old_ks_ref, old_vs_ref = rest[:4]
        i = 4
    if has_sinks:
        sinks_ref = rest[i]
        i += 1
    else:
        sinks_ref = None
    o_ref, k_out_ref, v_out_ref = rest[i: i + 3]
    if quantized:
        nks_ref, nvs_ref = rest[i + 3: i + 5]  # [1, KH, 1, D] grown scales
    kv_buf, sems, stage_k, stage_v, rmw_sems = rest[-5:]
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    P, Pw = pages_per_seq, window_pages
    n_chunks = (P + Pw - 1) // Pw  # static

    # ---- staged RMW for the new token's page: start the in-DMA first so
    # it overlaps the window fetches (same page-granular RMW as kv_write)
    dst_page = dst_page_ref[b]

    def rmw_in(ch, buf):
        pages = k_pages_ref if ch == 0 else v_pages_ref
        return pltpu.make_async_copy(
            pages.at[layer, dst_page], buf, rmw_sems.at[0, ch]
        )

    def rmw_out(ch, buf):
        out = k_out_ref if ch == 0 else v_out_ref
        return pltpu.make_async_copy(
            buf, out.at[layer, dst_page], rmw_sems.at[1, ch]
        )

    rmw_in(0, stage_k).start()
    rmw_in(1, stage_v).start()

    # ---- v3 window pipeline over the EXISTING context -------------------
    def chunk_live(seq, chunk):
        """Chunk-granular live guard (see paged_attention_v3: per-page
        guards break the back-to-back DMA issue). seq_len - 1 tokens are
        real here, but the v3 formula (vs seq_len) is kept: the extra
        boundary chunk it can fetch is masked, and identical DMA
        behavior keeps the two kernels' schedules comparable."""
        live = chunk * Pw * page_size < seq_lens_ref[seq]
        if window:
            live &= (chunk * Pw + Pw) * page_size > seq_lens_ref[seq] - window
        return live

    def issue(buf, seq, chunk):
        @pl.when(chunk_live(seq, chunk))
        def _():
            for p in range(Pw):
                gp = chunk * Pw + p
                if gp >= P:
                    break
                pid = block_tables_ref[seq, gp]
                pltpu.make_async_copy(
                    k_pages_ref.at[layer, pid], kv_buf.at[buf, 0, p],
                    sems.at[buf, 0, p],
                ).start()
                pltpu.make_async_copy(
                    v_pages_ref.at[layer, pid], kv_buf.at[buf, 1, p],
                    sems.at[buf, 1, p],
                ).start()

    def wait(buf, seq, chunk):
        @pl.when(chunk_live(seq, chunk))
        def _():
            for p in range(Pw):
                if chunk * Pw + p >= P:
                    break
                pltpu.make_async_copy(
                    k_pages_ref.at[layer, 0], kv_buf.at[buf, 0, p],
                    sems.at[buf, 0, p],
                ).wait()
                pltpu.make_async_copy(
                    v_pages_ref.at[layer, 0], kv_buf.at[buf, 1, p],
                    sems.at[buf, 1, p],
                ).wait()

    @pl.when(b == 0)
    def _():
        issue(0, 0, 0)

    KH, G, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    page = page_size
    Nw = Pw * KH * page
    seq_len = seq_lens_ref[b]
    qf = q_ref[0].reshape(KH * G, D).astype(jnp.float32)

    row_kh = jax.lax.broadcasted_iota(jnp.int32, (KH * G, Nw), 0) // G
    col = jax.lax.broadcasted_iota(jnp.int32, (KH * G, Nw), 1)
    col_kh = (col // page) % KH
    col_page = col // (KH * page)
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
        # fp8 pools upcast in-register: the window's pages crossed HBM at
        # 1 byte/elem; the f32 form only ever exists in VMEM
        kf = kv_buf[buf, 0].reshape(Nw, D).astype(jnp.float32)
        vf = kv_buf[buf, 1].reshape(Nw, D).astype(jnp.float32)
        # the pool does NOT yet hold the new token, so every fetched
        # chunk can be fully masked (seq_len == 1) — sanitize V
        # unconditionally: garbage only ever multiplies 0-probability
        # columns, but a non-finite V row would turn 0 x V into NaN
        vf = jnp.where(jnp.isfinite(vf), vf, 0.0)
        scores = jax.lax.dot_general(
            qf, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            # dequant on the score side (see paged_attention_v3): the
            # page/head scale is constant along D, so it factors out of
            # q.k as one [1, Nw] column scale, statically sliced
            scores = scores * kc_ref[0, :, c * Nw:(c + 1) * Nw]
        gp = c * Pw + col_page
        pos = gp * page + col_tok
        # pos < seq_len - 1: the new token is NOT in the pool; its
        # contribution merges analytically below
        valid = (col_kh == row_kh) & (pos < seq_len - 1) & (gp < P)
        if window:
            valid &= pos >= seq_len - window
        scores = jnp.where(valid, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(scores - m_new)
        l = l * alpha + jnp.sum(probs, axis=-1, keepdims=True)
        if quantized:
            probs = probs * vc_ref[0, :, c * Nw:(c + 1) * Nw]
        acc = acc * alpha + jax.lax.dot_general(
            probs, vf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m = m_new

    # ---- the new token as one more flash chunk: score q.k_new, value
    # v_new — exact single-key attention, no page round-trip needed. The
    # decode query sits AT the new token, so it is always visible (and
    # always inside any sliding window).
    k_new_f = k_new_ref[0].astype(jnp.float32)  # [KH, D]
    v_new_f = v_new_ref[0].astype(jnp.float32)
    kn_rows = jnp.broadcast_to(
        k_new_f[:, None, :], (KH, G, D)
    ).reshape(KH * G, D)
    vn_rows = jnp.broadcast_to(
        v_new_f[:, None, :], (KH, G, D)
    ).reshape(KH * G, D)
    s_new = jnp.sum(qf * kn_rows, axis=-1, keepdims=True)  # [KH*G, 1]
    m_f = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_f)
    p_new = jnp.exp(s_new - m_f)
    l = l * alpha + p_new
    acc = acc * alpha + p_new * vn_rows
    m = m_f

    if has_sinks:
        sink = sinks_ref[...]  # [KH*G, 1] f32, pre-shaped by the host
        m_s = jnp.maximum(m, sink)
        l = l * jnp.exp(m - m_s) + jnp.exp(sink - m_s)
        acc = acc * jnp.exp(m - m_s)

    # ---- land the KV append: splice the row, write the page back
    rmw_in(0, stage_k).wait()
    rmw_in(1, stage_v).wait()
    off = dst_off_ref[b]
    row = (
        jax.lax.broadcasted_iota(jnp.int32, (1, page, 1), 1) == off
    )  # [1, page, 1]
    if quantized:
        # quantized staged RMW: the whole destination page is already in
        # VMEM, so growing the scale costs one in-register requantize —
        # new_scale = max(old, amax(row)/FP8_MAX) per head (rounded to
        # the stored bf16), existing fp8 values re-encode by old/new,
        # the new row quantizes under the grown scale, and the page DMAs
        # back at fp8 width. Grown scales leave via a tiny [1, KH, 1, D]
        # output; the host scatters them into the scale pool (XLA) right
        # after the pallas_call, inside the same jit. Heads stay on the
        # LEADING axis throughout ([KH, 1, D] rows and scales, every
        # lane of a scale row equal): Mosaic broadcasts along lanes OR
        # sublanes in one step, not both, and cannot move a [KH] lane
        # vector onto the leading axis.
        kn = k_new_ref[0][:, None, :].astype(jnp.float32)  # [KH, 1, D]
        vn_r = v_new_ref[0][:, None, :].astype(jnp.float32)
        oks = old_ks_ref[0]  # [KH, 1, D] f32
        ovs = old_vs_ref[0]
        nks = append_scale(oks, kn, keepdims=True)
        nvs = append_scale(ovs, vn_r, keepdims=True)
        page_k = stage_k[...].astype(jnp.float32) * rescale_factor(oks, nks)
        page_v = stage_v[...].astype(jnp.float32) * rescale_factor(ovs, nvs)
        row_k = quant_values(kn, nks)  # [KH, 1, D]
        row_v = quant_values(vn_r, nvs)
        stage_k[...] = jnp.clip(
            jnp.where(row, row_k, page_k), -FP8_MAX, FP8_MAX
        ).astype(stage_k.dtype)
        stage_v[...] = jnp.clip(
            jnp.where(row, row_v, page_v), -FP8_MAX, FP8_MAX
        ).astype(stage_v.dtype)
        nks_ref[0] = nks
        nvs_ref[0] = nvs
    else:
        stage_k[...] = jnp.where(row, k_new_ref[0][:, None, :], stage_k[...])
        stage_v[...] = jnp.where(row, v_new_ref[0][:, None, :], stage_v[...])
    rmw_out(0, stage_k).start()
    rmw_out(1, stage_v).start()

    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0] = out.reshape(KH, G, D).astype(o_ref.dtype)

    # the stage buffer is reused by the NEXT program: its out-DMA must
    # drain before this program ends (overlaps the softmax/output above)
    rmw_out(0, stage_k).wait()
    rmw_out(1, stage_v).wait()


@functools.partial(
    jax.jit,
    static_argnames=("layer", "interpret", "window", "window_pages_override"),
    donate_argnums=(1, 2),
)
def fused_decode_attention(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [L, num_pages, KH, page, D] (donated)
    v_pages: jax.Array,
    k_new: jax.Array,  # [B, KH, D] new-token KV rows (post-rope)
    v_new: jax.Array,
    block_tables: jax.Array,  # [B, P] int32
    seq_lens: jax.Array,  # [B] int32 (length INCLUDING the new token)
    dst_page: jax.Array,  # [B] int32 (0 = trash page for inactive slots)
    dst_off: jax.Array,  # [B] int32
    *,
    layer: int,
    window: int = 0,
    sinks: jax.Array | None = None,  # [H] learned sink logits
    interpret: bool = False,
    scale: float | None = None,  # see paged_decode_attention_v3
    window_pages_override: int | None = None,  # tests: force multi-chunk
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused decode-attention + KV-append step over layer ``layer``.

    Returns ``(attn_out [B, H, D], k_pages, v_pages)`` with the new rows
    written in place (pools input/output-aliased; pair with donation at
    the jit boundary above). ``k_pages``/``v_pages`` may be
    ``QuantPool`` (fp8 values + bf16 per-page/head scales): the kernel
    then dequantizes window chunks in-register and quantizes the append
    inside the staged RMW — HBM reads per step drop to fp8 width.
    """
    quantized = is_quant(k_pages)
    B, H, D = q.shape
    _, _, KH, page_size, _ = k_pages.shape
    G = H // KH
    P = block_tables.shape[1]
    # dtype-aware window sizing (ROADMAP #1 tuning note): itemsize is the
    # POOL's — at fp8 each VMEM byte holds twice the resident window of
    # bf16, so the slot budget buys 2x window pages instead of half-empty
    # slots
    Pw = window_pages_override or _window_pages(
        KH, page_size, D, k_pages.dtype.itemsize, P
    )
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q4 = (q.reshape(B, KH, G, D).astype(jnp.float32) * scale).astype(q.dtype)
    has_sinks = sinks is not None

    kernel = functools.partial(
        _fused_decode_kernel,
        layer=layer,
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
        pl.BlockSpec(
            (1, KH, D), lambda b, *_: (b, 0, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(
            (1, KH, D), lambda b, *_: (b, 0, 0), memory_space=pltpu.VMEM
        ),
        pl.BlockSpec(memory_space=pl.ANY),  # k_pages
        pl.BlockSpec(memory_space=pl.ANY),  # v_pages
    ]
    if quantized:
        k_vals, k_scale = k_pages
        v_vals, v_scale = v_pages
        # new rows stay UNQUANTIZED: the analytic new-token merge is
        # exact, and the staged RMW quantizes them under the grown scale
        # an append at row 0 means the page was just ACQUIRED — feed the
        # RMW a zero old-scale so the previous occupant's leftover scale
        # never ratchets into this occupancy (ops/quant.quant_append_rows
        # applies the same reset; the two paths must share the bits)
        held = (dst_off != 0)[:, None]  # [B, 1]

        def dst_scale(scale):  # -> [B, KH, 1, D] f32 (zeroed when fresh)
            s = jnp.where(held, scale[layer, dst_page], 0)
            return jnp.broadcast_to(
                s.astype(jnp.float32)[:, :, None, None], (B, KH, 1, D)
            )

        # host-expanded scales: dynamic page indexing happens in XLA,
        # the kernel's own scale indexing is fully static
        k_cols = table_col_scales(k_scale[layer], block_tables, page_size, Pw)
        v_cols = table_col_scales(v_scale[layer], block_tables, page_size, Pw)
        inputs = [
            block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
            dst_page.astype(jnp.int32), dst_off.astype(jnp.int32),
            q4, k_new, v_new, k_vals, v_vals,
            k_cols, v_cols, dst_scale(k_scale), dst_scale(v_scale),
        ]
        # trailing block dims equal the array dims (Pallas TPU blocks
        # must, or be multiples of (8, 128)): the per-sequence axis is
        # the only one blocked
        col_spec = pl.BlockSpec(
            (1,) + k_cols.shape[1:], lambda b, *_: (b, 0, 0),
            memory_space=pltpu.VMEM,
        )
        dst_spec = pl.BlockSpec(
            (1, KH, 1, D), lambda b, *_: (b, 0, 0, 0),
            memory_space=pltpu.VMEM,
        )
        in_specs += [col_spec, col_spec, dst_spec, dst_spec]
        pool_dtype = k_vals.dtype
        k_pages_op, v_pages_op = k_vals, v_vals
    else:
        inputs = [
            block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
            dst_page.astype(jnp.int32), dst_off.astype(jnp.int32),
            q4, k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype),
            k_pages, v_pages,
        ]
        pool_dtype = k_pages.dtype
        k_pages_op, v_pages_op = k_pages, v_pages
    if has_sinks:
        in_specs.append(
            pl.BlockSpec(
                (KH * G, 1), lambda b, *_: (0, 0), memory_space=pltpu.VMEM
            )
        )
        inputs.append(sinks.astype(jnp.float32).reshape(KH * G, 1))
    out_specs = [
        pl.BlockSpec(
            (1, KH, G, D), lambda b, *_: (b, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(memory_space=pl.ANY),  # k_pages out
        pl.BlockSpec(memory_space=pl.ANY),  # v_pages out
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        jax.ShapeDtypeStruct(k_pages_op.shape, pool_dtype),
        jax.ShapeDtypeStruct(v_pages_op.shape, pool_dtype),
    ]
    if quantized:
        out_specs += [dst_spec, dst_spec]
        out_shape += [jax.ShapeDtypeStruct((B, KH, 1, D), jnp.float32)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, 2, Pw, KH, page_size, D), pool_dtype),
            pltpu.SemaphoreType.DMA((2, 2, Pw)),
            pltpu.VMEM((KH, page_size, D), pool_dtype),  # stage_k
            pltpu.VMEM((KH, page_size, D), pool_dtype),  # stage_v
            pltpu.SemaphoreType.DMA((2, 2)),  # rmw in/out x k/v
        ],
    )
    # operand numbering includes the 4 scalar-prefetch args:
    # 4=q 5=k_new 6=v_new 7=k_pages 8=v_pages [9-12=scales] [then sinks]
    # -> outputs 1, 2 (the value pools; grown scales leave as outputs
    # 3/4 and are scattered into the scale pool below, same jit)
    # No jax.named_scope here: the profiler names this custom call after
    # the innermost scope around it, which is this function's own jit
    # (``fused_decode_attention``, what the benchmark's trace reduction
    # matches); models/llama.py's attn_kv scope encloses the call.
    results = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
    )(*inputs)
    if quantized:
        out, k_out, v_out, nks, nvs = results
        # append_scale already rounded through the stored dtype: the
        # cast back is exact
        k_pool = QuantPool(
            k_out,
            k_scale.at[layer, dst_page].set(
                nks[:, :, 0, 0].astype(k_scale.dtype)
            ),
        )
        v_pool = QuantPool(
            v_out,
            v_scale.at[layer, dst_page].set(
                nvs[:, :, 0, 0].astype(v_scale.dtype)
            ),
        )
        return out.reshape(B, H, D), k_pool, v_pool
    out, k_out, v_out = results
    return out.reshape(B, H, D), k_out, v_out
