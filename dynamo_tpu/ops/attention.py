"""Attention ops over the paged KV cache (pure-JAX reference forms).

The paged layout is PAGE-MAJOR: per layer, K and V live in page arrays of
shape ``[num_pages, num_kv_heads, page_size, head_dim]`` (one page = one
contiguous all-heads block = one DMA descriptor); a sequence's pages are
listed in its row of ``block_tables [B, max_pages_per_seq]``. This is the
TPU-first replacement for the reference's engine-internal (vLLM) paged
attention + its block-copy CUDA kernel (lib/llm/src/kernels/block_copy.cu):
XLA-friendly gathers/scatters here, a Pallas kernel (ops/pallas/) on the hot
decode path.

All functions are shape-static and jit-safe. GQA is handled by repeating KV
heads up to the query head count.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp


NEG_INF = -1e30


def use_pallas() -> bool:
    """Pallas decode kernel on TPU unless DYNAMO_PALLAS overrides (0/1)."""
    env = (os.environ.get("DYNAMO_PALLAS") or "").strip().lower()
    if env in ("1", "true", "on"):
        return True
    if env in ("0", "false", "off", "no"):
        return False
    return jax.default_backend() == "tpu"


def use_fused_decode() -> bool:
    """Fused KV-append + attention kernel (ops/pallas/fused_decode.py) on
    the decode path unless DYNAMO_FUSED_DECODE overrides (0/1). Only
    consulted where the Pallas path is active (use_pallas)."""
    env = (os.environ.get("DYNAMO_FUSED_DECODE") or "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return False
    return True


def lane_aligned(head_dim: int) -> bool:
    """Whether Mosaic DMA page slices are lane-aligned at this head dim
    (tiling constraint: last dim % 128). The single source for BOTH
    compiled-kernel dispatch gates (paged_attention_v3.v3_supported and
    kv_write.write_new_kv); misaligned heads (gpt-oss D=64, toy specs)
    take the pure-XLA paths on real TPUs."""
    return head_dim % 128 == 0


def pool_head_dim(head_dim: int) -> int:
    """Head dim of the KV PAGE POOL for a model with ``head_dim`` heads.

    On real TPUs, lane-misaligned heads (gpt-oss D=64) would be locked
    out of the Mosaic DMA kernels (see lane_aligned). Zero-padding the
    pool's last dim up to the 128-lane tile is mathematically EXACT for
    attention — padded q.k dims contribute 0 to every score, padded V
    columns are sliced off after the kernel — so the pool rounds up and
    both kernels stay on the fast path, at the cost of pool memory
    (2x for D=64). Writers pad rows to the pool width; readers slice
    back to the model dim (models/llama.py, ops/pallas/kv_write.py,
    paged_decode_attention_auto below).

    ``DYNAMO_POOL_PAD`` overrides: 0 = never pad (fall back to XLA
    gather paths), 1 = pad even off-TPU (lets CPU tests exercise the
    padded layout end to end).
    """
    env = (os.environ.get("DYNAMO_POOL_PAD") or "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return head_dim
    force = env in ("1", "true", "on", "force")
    # dynalint: disable=DL014 -- layout probe, not a dispatch site: the
    # unpadded layout's XLA fallback is counted where it is taken
    # (note_fallback at the attention/kv_write dispatchers)
    if force or (use_pallas() and jax.default_backend() == "tpu"):
        return -(-head_dim // 128) * 128
    return head_dim


def pad_heads(x: jax.Array, pool_dim: int) -> jax.Array:
    """Zero-pad the last (head) dim of [..., D] rows up to the pool
    width; identity when the pool is unpadded."""
    d = x.shape[-1]
    if d == pool_dim:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, pool_dim - d)]
    return jnp.pad(x, pad)


def page_tiles(arr: jax.Array, page_size: int, pool_dim: int) -> jax.Array:
    """Prefill KV rows -> page-major write tiles, zero-padded to the
    pool width: [..., T, KH, D] -> [n_tiles, KH, page_size, pool_dim]
    (leading dims fold into the tile count). The SINGLE tile builder for
    every prefill pool writer (models/llama.py x3, parallel/pipeline.py)
    so a lane-padded pool (pool_head_dim) can't be missed by one of
    them."""
    arr = pad_heads(arr, pool_dim)
    kh, hd = arr.shape[-2], arr.shape[-1]
    return arr.reshape(-1, page_size, kh, hd).transpose(0, 2, 1, 3)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[.., S, kv_heads, D] -> [.., S, kv_heads*n_rep, D] (GQA expansion)."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def gather_pages(
    pages: jax.Array,  # [num_pages, kv_heads, page_size, head_dim]
    block_table: jax.Array,  # [max_pages_per_seq] int32
) -> jax.Array:
    """Materialize one sequence's KV as [max_ctx, kv_heads, head_dim]."""
    toks = pages[block_table]  # [P, H, page, D]
    P, H, page, D = toks.shape
    return toks.transpose(0, 2, 1, 3).reshape(P * page, H, D)


def gather_ctx(pool, li: int, block_table: jax.Array, head_dim: int):
    """One layer's context for a sequence, pool-form-agnostic: plain
    arrays gather in the pool dtype; QuantPool (ops/quant.py) gathers
    fp8 pages and dequantizes with the per-page/head scales. Sliced back
    to the MODEL head dim when the pool is lane-padded. The single
    gather used by every XLA attention site (prefill/verify/CPU decode),
    so the fp8 gather/dequant path can't be missed by one of them."""
    from dynamo_tpu.ops.quant import gather_dequant_pages, is_quant

    if is_quant(pool):
        return gather_dequant_pages(pool.layer(li), block_table)[
            ..., :head_dim
        ]
    return gather_pages(pool[li], block_table)[..., :head_dim]


def causal_attention(
    q: jax.Array,  # [T, heads, D]
    k: jax.Array,  # [S, kv_heads, D]
    v: jax.Array,  # [S, kv_heads, D]
    q_positions: jax.Array,  # [T] absolute positions of the queries
    kv_len: jax.Array,  # scalar: number of valid kv tokens
    *,
    window: int = 0,  # sliding window (0 = full); key j needs j > pos - window
    sinks: jax.Array | None = None,  # [H] learned sink logits (gpt-oss)
) -> jax.Array:
    """Causal attention of new queries over (cached + new) keys.

    Key j is visible to query i iff j <= q_positions[i] and j < kv_len
    (and, with a sliding window, j > q_positions[i] - window). ``sinks``
    adds a per-head learned logit to the softmax normalization — a
    virtual key with zero value the head can dump probability mass on
    (gpt-oss attention; HF eager_attention_forward concat semantics).
    Returns [T, heads, D]. Softmax in f32 regardless of input dtype.
    """
    T, H, D = q.shape
    S, KH, _ = k.shape
    n_rep = H // KH
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("thd,shd->hts", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    kv_pos = jnp.arange(S)[None, :]  # [1, S]
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
    attention. The Pallas kernel (ops/pallas/paged_attention_v3.py)
    computes the same thing without materializing the gather. ``scale``
    overrides the 1/sqrt(q.shape[-1]) default — needed when q is
    zero-padded to a wider pool head dim (pool_head_dim) and the true
    model D differs from the padded width. ``k_pages``/``v_pages`` may be
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


def _decode_attention_tpu(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    *,
    window: int = 0,
    sinks: jax.Array | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Real-TPU decode attention: our v3 kernel (deep-pipelined windowed
    DMA + cross-program prefetch over the page-major pool — see
    ops/pallas/paged_attention_v3.py); its windowing bounds VMEM for any
    table size, so it is the only production path. ``DYNAMO_ATTN=lib``
    selects JAX's library multi-page kernel for comparison runs — it
    wants the old head-major layout, so the transpose is paid per call
    (debug only). Layout contract everywhere else:
    k_pages/v_pages [num_pages, KH, page, D], block_tables [B, P]."""
    choice = (os.environ.get("DYNAMO_ATTN") or "").strip()
    if choice == "lib" and window == 0 and sinks is None:
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention,
        )

        P = block_tables.shape[1]
        ppcb = 8
        while ppcb > 1 and P % ppcb:
            ppcb //= 2
        if scale is None:
            scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        return paged_attention(
            q,
            k_pages.transpose(1, 0, 2, 3),
            v_pages.transpose(1, 0, 2, 3),
            seq_lens,
            block_tables,
            pages_per_compute_block=ppcb,
        )
    from dynamo_tpu.ops.pallas.paged_attention_v3 import (
        paged_decode_attention_v3,
        v3_supported,
    )

    if choice == "v3" or v3_supported(k_pages, block_tables):
        return paged_decode_attention_v3(
            q, k_pages, v_pages, block_tables, seq_lens,
            window=window, sinks=sinks, scale=scale,
        )
    return paged_decode_attention(
        q, k_pages, v_pages, block_tables, seq_lens,
        window=window, sinks=sinks, scale=scale,
    )


def decode_update_attention(
    q: jax.Array,  # [B, H, D] (model head dim)
    k_pages: jax.Array,  # [L, num_pages, KH, page, pool_d]
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
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """ONE fused kernel for the per-layer decode step: KV append + paged
    attention (ops/pallas/fused_decode.py) — the dispatch-count half of
    the compile-and-dispatch work. Falls back to the two-kernel path
    (write_new_kv scatter/DMA + paged_decode_attention_auto) off the
    Pallas path, when DYNAMO_FUSED_DECODE=0, or for lane-misaligned
    pools on real TPUs.

    Returns ``(attn [B, H, D], k_pages, v_pages)`` — pools updated in
    place on the fused path (input/output aliasing + donation at the
    model jit boundary). QuantPool pools (ops/quant.py, kv_dtype=fp8)
    ride the same slots: the fused kernel dequantizes in-register and
    quantizes the append in its staged RMW; the fallback composition is
    the quantized scatter (write_new_kv) + gather/dequant attention."""
    from dynamo_tpu.ops.quant import is_quant

    D = q.shape[-1]
    pool_d = k_pages.shape[-1]
    on_tpu = jax.default_backend() == "tpu"
    quantized = is_quant(k_pages)
    fused_ok = (
        use_pallas()
        and use_fused_decode()
        and (not on_tpu or lane_aligned(pool_d))
        # quantized pools under tp shard_map are not plumbed yet: the
        # scale leaves would need their own specs — take the XLA path,
        # which GSPMD partitions like any other gather/scatter
        and not (quantized and mesh is not None
                 and mesh.shape.get("tp", 1) > 1)
    )
    if fused_ok:
        from jax.sharding import PartitionSpec as P

        from dynamo_tpu.ops.pallas.fused_decode import fused_decode_attention

        if pool_d != D:
            # lane-padded pool (pool_head_dim): zero-padded q/k dims add 0
            # to every score, padded V columns slice off — scale pins to
            # the TRUE model dim
            q = pad_heads(q, pool_d)
            k_new = pad_heads(k_new, pool_d)
            v_new = pad_heads(v_new, pool_d)
        scale = 1.0 / float(D) ** 0.5
        base = functools.partial(
            fused_decode_attention,
            layer=layer, window=window, scale=scale,
            interpret=not on_tpu,
        )
        if sinks is not None:
            kernel = lambda q_, kp_, vp_, kn_, vn_, bt_, sl_, dp_, do_, s_: (  # noqa: E731
                base(q_, kp_, vp_, kn_, vn_, bt_, sl_, dp_, do_, sinks=s_)
            )
        else:
            kernel = lambda q_, kp_, vp_, kn_, vn_, bt_, sl_, dp_, do_: (  # noqa: E731
                base(q_, kp_, vp_, kn_, vn_, bt_, sl_, dp_, do_)
            )
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
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
            # dynalint: disable=DL013 -- array pools only: fused_ok
            # excludes quantized+tp (scale leaves unspecced), and that
            # exclusion is counted (note_fallback quant_tp_shardmap)
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
        return attn[..., :D], k_pages, v_pages

    from dynamo_tpu.ops.fallback import note_fallback

    if quantized and mesh is not None and mesh.shape.get("tp", 1) > 1:
        # THE ROADMAP #7 residue: fp8 + tp>1 cannot ride the fused
        # kernel's shard_map (scale leaves lack specs) — now it counts
        # itself instead of silently costing 3x. Checked FIRST: this is
        # the intrinsic blocker (it forces XLA even where Pallas and
        # fused decode are available), so it wins attribution over the
        # environmental reasons below.
        note_fallback("quant_tp_shardmap",
                      detail="decode_update_attention: fp8 pool under "
                             "tp shard_map takes the XLA scatter+gather")
    elif not use_pallas():
        note_fallback("no_pallas_backend", expected=True,
                      detail="decode_update_attention: scatter+gather")
    elif not use_fused_decode():
        note_fallback("fused_decode_disabled", expected=True,
                      detail="decode_update_attention: DYNAMO_FUSED_DECODE=0")
    else:
        note_fallback("lane_misaligned",
                      detail=f"decode_update_attention: pool head dim "
                             f"{pool_d} not lane-aligned on TPU")

    from dynamo_tpu.ops.pallas.kv_write import write_new_kv

    k_pages, v_pages = write_new_kv(
        k_pages, v_pages, k_new, v_new, dst_page, dst_off,
        layer=layer, mesh=mesh,
    )
    k_l = k_pages.layer(layer) if quantized else k_pages[layer]
    v_l = v_pages.layer(layer) if quantized else v_pages[layer]
    attn = paged_decode_attention_auto(
        q, k_l, v_l, block_tables, seq_lens,
        mesh=mesh, window=window, sinks=sinks,
        # exact new-token overlay (quant only): the XLA mirror of the
        # fused kernel's analytic merge — on the gather/dequant path the
        # freshly-written row would otherwise read back quantized
        new_kv=(k_new, v_new) if quantized else None,
    )
    return attn, k_pages, v_pages


def paged_decode_attention_auto(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    mesh=None,
    *,
    window: int = 0,
    sinks: jax.Array | None = None,
    _scale: float | None = None,  # internal: set by the pad recursion
    new_kv: tuple | None = None,  # exact new-token rows (quant pools)
) -> jax.Array:
    """Dispatch: Pallas kernel on TPU, pure-JAX gather elsewhere.

    With a mesh, the kernel runs under shard_map over the "tp" axis: query
    heads and KV heads are both head-sharded, every GQA group is fully
    local to its shard, so the kernel needs zero collectives (pallas_call
    itself has no SPMD partitioning rule — without shard_map GSPMD would
    all-gather the whole KV cache every step). Sinks are per-query-head
    and shard with the heads.

    DYNAMO_PALLAS=1 off-TPU runs the kernel in interpret mode (slow; lets
    the whole engine be driven through the kernel path on CPU).

    When the pool head dim is wider than the model's (pool_head_dim
    zero-padding for lane alignment), q is zero-padded to the pool
    width — the padded dims multiply the pool's zero columns, so every
    score is unchanged — the softmax scale is pinned to the TRUE model
    dim, and the padded output columns are sliced off.

    ``k_pages``/``v_pages`` may be QuantPool LAYER slices: the Pallas
    route runs v3 with in-kernel dequant; the pure-JAX route gathers and
    dequantizes (paged_decode_attention).
    """
    from dynamo_tpu.ops.quant import is_quant

    D = q.shape[-1]
    pool_d = k_pages.shape[-1]
    if pool_d != D:
        if new_kv is not None:
            new_kv = tuple(pad_heads(x, pool_d) for x in new_kv)
        out = paged_decode_attention_auto(
            pad_heads(q, pool_d), k_pages, v_pages, block_tables, seq_lens,
            mesh, window=window, sinks=sinks, _scale=1.0 / float(D) ** 0.5,
            new_kv=new_kv,
        )
        return out[..., :D]
    scale = _scale
    if is_quant(k_pages) and use_pallas():
        # quantized v3 (interpret off-TPU). Under a tp mesh, or on a real
        # TPU with a lane-misaligned pool, the pure gather/dequant path
        # below is the fallback — GSPMD partitions it without shard_map.
        # The kernel reads the freshly-written row back at fp8 (it has no
        # overlay input) — tolerance-level difference vs the fused path.
        on_tpu = jax.default_backend() == "tpu"
        tp = mesh is not None and mesh.shape.get("tp", 1) > 1
        if not tp and (not on_tpu or lane_aligned(pool_d)):
            from dynamo_tpu.ops.pallas.paged_attention_v3 import (
                paged_decode_attention_v3,
            )

            return paged_decode_attention_v3(
                q, k_pages.vals, v_pages.vals, block_tables, seq_lens,
                window=window, sinks=sinks, scale=scale,
                interpret=not on_tpu,
                k_scale=k_pages.scale, v_scale=v_pages.scale,
            )
        from dynamo_tpu.ops.fallback import note_fallback

        note_fallback(
            "quant_tp_shardmap" if tp else "lane_misaligned",
            detail="paged_decode_attention_auto: quantized "
                   "gather/dequant path",
        )
        return paged_decode_attention(
            q, k_pages, v_pages, block_tables, seq_lens,
            window=window, sinks=sinks, scale=scale, new_kv=new_kv,
        )
    if use_pallas():
        from jax.sharding import PartitionSpec as P

        from dynamo_tpu.ops.pallas.paged_attention_v3 import (
            paged_decode_attention_v3,
        )

        on_tpu = jax.default_backend() == "tpu"
        if on_tpu:
            base = functools.partial(
                _decode_attention_tpu, window=window, scale=scale
            )
        else:
            # off-TPU (tests): our kernel in interpret mode
            base = functools.partial(
                paged_decode_attention_v3, interpret=True, window=window,
                scale=scale,
            )
        if sinks is not None:
            kernel = lambda q_, k_, v_, bt_, sl_, s_: base(  # noqa: E731
                q_, k_, v_, bt_, sl_, sinks=s_
            )
        else:
            kernel = lambda q_, k_, v_, bt_, sl_: base(  # noqa: E731
                q_, k_, v_, bt_, sl_
            )
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            in_specs = [
                P(None, "tp", None),  # q: heads sharded
                P(None, "tp", None, None),  # k_pages: kv heads sharded
                P(None, "tp", None, None),
                P(None, None),  # block tables replicated
                P(None),  # seq lens replicated
            ]
            if sinks is not None:
                in_specs.append(P("tp"))  # per-query-head sinks
            # dynalint: disable=DL013 -- array layer slices only: the
            # quantized form is diverted above (v3 kernel, or the
            # counted gather/dequant fallback) before this shard_map
            kernel = jax.shard_map(
                kernel,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=P(None, "tp", None),
                check_vma=False,
            )
        args = (q, k_pages, v_pages, block_tables, seq_lens)
        if sinks is not None:
            args = args + (sinks,)
        return kernel(*args)
    from dynamo_tpu.ops.fallback import note_fallback

    note_fallback("no_pallas_backend", expected=True,
                  detail="paged_decode_attention_auto: pure-JAX gather")
    return paged_decode_attention(
        q, k_pages, v_pages, block_tables, seq_lens,
        window=window, sinks=sinks, scale=scale, new_kv=new_kv,
    )
