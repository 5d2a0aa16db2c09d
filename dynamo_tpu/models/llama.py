"""Llama-family transformer in pure JAX with paged KV cache + TP shardings.

Functional core: ``init_params`` builds the weight pytree (randomly - this
environment has no model downloads; loading real safetensors goes through
``load_params`` when files are present), ``prefill_forward`` and
``decode_forward`` are the two jitted entry points. Tensor parallelism is
megatron-style, expressed as NamedShardings on the weights (attention heads
and MLP hidden column-sharded, output projections row-sharded) so XLA's SPMD
partitioner inserts the collectives; activations get light
``with_sharding_constraint`` guidance.

Page 0 of the KV cache is the trash page: padded token positions scatter
there, so static-shape prefill never corrupts live pages.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.ops.attention import (
    causal_attention,
    decode_update_attention,
    gather_ctx,
    gather_pages,
    page_tiles,
)
from dynamo_tpu.ops.quant import (
    QuantPool,
    init_quant_pool,
    is_quant,
    pack_pages,
    quant_page_tiles,
    unpack_pages,
)

TRASH_PAGE = 0  # reserved page index for padded-position scatters

Params = dict[str, Any]


# ---------------------------------------------------------------- init


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random init (serving-scale weights come from load_params)."""
    dtype = jnp.dtype(spec.dtype)
    d, hd = spec.hidden_size, spec.head_dim
    nh, nkv = spec.num_heads, spec.num_kv_heads
    keys = iter(jax.random.split(key, 4 + spec.num_layers * 8))

    def dense(k, shape, scale=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[0])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    params: Params = {
        "embed": dense(next(keys), (spec.vocab_size, d), scale=0.02),
        "final_norm": jnp.ones((d,), dtype),
        "layers": [],
    }
    if not spec.tie_embeddings:
        params["lm_head"] = dense(next(keys), (d, spec.vocab_size))
    for _ in range(spec.num_layers):
        layer = {
            "attn_norm": jnp.ones((d,), dtype),
            "wq": dense(next(keys), (d, nh * hd)),
            "wk": dense(next(keys), (d, nkv * hd)),
            "wv": dense(next(keys), (d, nkv * hd)),
            "wo": dense(next(keys), (nh * hd, d)),
            "mlp_norm": jnp.ones((d,), dtype),
        }
        if spec.attn_bias:
            layer.update(
                bq=jnp.zeros((nh * hd,), dtype),
                bk=jnp.zeros((nkv * hd,), dtype),
                bv=jnp.zeros((nkv * hd,), dtype),
                bo=jnp.zeros((d,), dtype),
            )
        if spec.attn_sinks:
            layer["sinks"] = jnp.zeros((nh,), dtype)
        if spec.num_experts:
            from dynamo_tpu.models import moe

            layer["moe"] = moe.init_moe_layer(spec, next(keys))
        else:
            layer.update(
                w_gate=dense(next(keys), (d, spec.intermediate_size)),
                w_up=dense(next(keys), (d, spec.intermediate_size)),
                w_down=dense(next(keys), (spec.intermediate_size, d)),
            )
        params["layers"].append(layer)
    return params


def param_shardings(spec: ModelSpec, mesh: Mesh) -> Params:
    """Megatron TP shardings over mesh axis "tp"."""

    def ns(*axes):
        return NamedSharding(mesh, P(*axes))

    layer = {
        "attn_norm": ns(),
        "wq": ns(None, "tp"),  # column (heads)
        "wk": ns(None, "tp"),
        "wv": ns(None, "tp"),
        "wo": ns("tp", None),  # row
        "mlp_norm": ns(),
    }
    if spec.attn_bias:
        layer.update(bq=ns("tp"), bk=ns("tp"), bv=ns("tp"), bo=ns())
    if spec.attn_sinks:
        layer["sinks"] = ns("tp")  # per-query-head, rides the head shards
    if spec.num_experts:
        from dynamo_tpu.models import moe

        layer["moe"] = moe.moe_layer_shardings(mesh, spec)
    else:
        layer.update(
            w_gate=ns(None, "tp"),
            w_up=ns(None, "tp"),
            w_down=ns("tp", None),
        )
    out = {
        "embed": ns(None, "tp"),
        "final_norm": ns(),
        "layers": [dict(layer) for _ in range(spec.num_layers)],
    }
    if not spec.tie_embeddings:
        out["lm_head"] = ns(None, "tp")
    return out


def cache_shardings(
    mesh: Mesh, kv_dtype: str = "bf16"
) -> tuple[Any, Any]:
    """KV pages [L, pages, kv_heads, page_size, D]: shard kv_heads on tp.
    Quantized pools shard the scale leaf [L, pages, KH] on the same head
    axis, so device_put with the QuantPool of shardings keeps values and
    scales co-located per shard."""
    s = NamedSharding(mesh, P(None, None, "tp", None, None))
    if kv_dtype == "fp8":
        qs = QuantPool(s, NamedSharding(mesh, P(None, None, "tp")))
        return qs, qs
    return s, s


def init_cache(
    spec: ModelSpec, num_pages: int, page_size: int, dtype=None,
    kv_dtype: str = "bf16",
) -> tuple[jax.Array, jax.Array]:
    """K and V page arrays [L, num_pages, kv_heads, page_size, head_dim].

    PAGE-MAJOR layout: one page's KV for ALL heads is a single contiguous
    [kv_heads, page_size, head_dim] block, so the decode kernels move a
    page with ONE DMA descriptor. (The previous head-major layout made the
    same slice a strided copy that expands to kv_heads descriptors — and
    decode attention is DMA-descriptor-bound, not bandwidth-bound: see
    ops/pallas/paged_attention_v3.py.) ``num_pages`` must already include
    the trash page (index 0).

    ``kv_dtype="fp8"`` allocates QuantPools instead (ops/quant.py): fp8
    values + bf16 per-page/head scales — half the HBM footprint and half
    the decode read traffic; every writer quantizes, every reader
    dequantizes, and the tolerance goldens (tests/test_quant_goldens.py)
    bound the numeric drift.
    """
    from dynamo_tpu.ops.attention import pool_head_dim

    # The pool head dim may exceed spec.head_dim (pool_head_dim: zero-pad
    # to the 128-lane tile so lane-misaligned heads like gpt-oss D=64
    # keep the Mosaic DMA kernels). Writers pad rows, readers slice —
    # exact for attention; see ops/attention.pool_head_dim.
    dtype = dtype or jnp.dtype(spec.dtype)
    pool_d = pool_head_dim(spec.head_dim)
    shape = (spec.num_layers, num_pages, spec.num_kv_heads, page_size,
             pool_d)
    if pool_d != spec.head_dim:
        import logging
        import math

        mib = 2 * math.prod(shape) * jnp.dtype(dtype).itemsize / 2**20
        logging.getLogger(__name__).info(
            "KV pool lane-padded for Mosaic DMA: head_dim %d -> %d "
            "(%.0f MiB total, %.2fx the unpadded pool; DYNAMO_POOL_PAD=0 "
            "to disable)", spec.head_dim, pool_d, mib,
            pool_d / spec.head_dim,
        )
    if kv_dtype == "fp8":
        # scale per (layer, page, kv_head): the append-time amax rides
        # the same page granularity every kernel DMAs at
        return init_quant_pool(shape, 3), init_quant_pool(shape, 3)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _set_page_tiles(
    pool, li: int, safe_pg: jax.Array, arr: jax.Array, page_size: int,
    valid_tok: jax.Array,  # [n_tiles, page] bool (True = real token)
):
    """Prefill page write for either pool form: plain pools scatter the
    tiles as-is; QuantPools zero the padded rows, take one amax scale per
    (page, head), and scatter fp8 values + scales. ``valid_tok`` marks
    real tokens — garbage in a partial tail page must not inflate the
    page scale (it is masked from attention and requantized over as
    decode appends land)."""
    tiles = page_tiles(arr, page_size, pool.shape[-1])
    if is_quant(pool):
        vals, s = quant_page_tiles(
            tiles, valid_tok[:, None, :, None], (2, 3)
        )
        return QuantPool(
            pool.vals.at[li, safe_pg].set(vals),
            pool.scale.at[li, safe_pg].set(s),
        )
    return pool.at[li, safe_pg].set(tiles)


# ---------------------------------------------------------------- layers


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def yarn_get_mscale(scale: float, m: float = 1.0) -> float:
    """HF yarn_get_mscale: the single source for the YaRN attention
    temperature formula (shared by yarn_freqs and mla.softmax_scale)."""
    import math

    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_freqs(spec: ModelSpec, dim: int):
    """YaRN-corrected inverse frequencies + cos/sin attention factor.

    Returns ``(inv_freq [dim//2] | None, attention_factor)``; None = no
    scaling configured. Semantics match HF ``_compute_yarn_parameters``
    (transformers modeling_rope_utils) so checkpoints that ship YaRN
    configs — gpt-oss (factor 32, truncate off) and DeepSeek-R1 (factor
    40, mscale 1) — reproduce HF numerics exactly."""
    import math

    import numpy as np

    if not spec.rope_scaling_factor:
        return None, 1.0
    base, factor = spec.rope_theta, spec.rope_scaling_factor
    orig = spec.rope_orig_max_pos
    half = dim // 2
    pos_freqs = base ** (np.arange(0, half, dtype=np.float64) * 2 / dim)
    inv_extra = 1.0 / pos_freqs
    inv_inter = 1.0 / (factor * pos_freqs)

    def corr_dim(n_rot: float) -> float:
        return (dim * math.log(orig / (n_rot * 2 * math.pi))) / (
            2 * math.log(base)
        )

    low = corr_dim(spec.rope_beta_fast)
    high = corr_dim(spec.rope_beta_slow)
    if spec.rope_truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip(
        (np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1
    )
    ext_factor = 1.0 - ramp
    inv = inv_inter * (1 - ext_factor) + inv_extra * ext_factor
    if spec.rope_mscale and spec.rope_mscale_all_dim:
        att = yarn_get_mscale(factor, spec.rope_mscale) / yarn_get_mscale(
            factor, spec.rope_mscale_all_dim
        )
    else:
        att = yarn_get_mscale(factor)
    return inv.astype(np.float32), float(att)


def rope(
    x: jax.Array, positions: jax.Array, theta: float,
    *, inv_freq=None, scale: float = 1.0,
) -> jax.Array:
    """Rotary embedding. x: [T, heads, D], positions: [T]. ``inv_freq``
    overrides the plain theta schedule (YaRN); ``scale`` multiplies the
    rotated output (YaRN attention factor — HF folds it into cos/sin,
    which is the same linear map)."""
    D = x.shape[-1]
    half = D // 2
    if inv_freq is None:
        freqs = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
        )
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [T, half]
    cos = jnp.cos(angles)[:, None, :] * scale  # [T, 1, half]
    sin = jnp.sin(angles)[:, None, :] * scale
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def rope_spec(spec: ModelSpec, x: jax.Array, positions: jax.Array) -> jax.Array:
    """spec-driven rope: plain theta schedule, or YaRN when configured."""
    inv, att = yarn_freqs(spec, x.shape[-1])
    return rope(x, positions, spec.rope_theta, inv_freq=inv, scale=att)


# Stable names for the regions of a layer, in decode and prefill alike:
# jax.named_scope is metadata on the operations (their op_name in an HLO
# dump and in a profiler's operation details); it changes no program.
SCOPE_QKV = "attn_qkv"  # q/k/v projections + rope
SCOPE_KV = "attn_kv"  # KV write + attention over the paged context
SCOPE_OUT = "attn_out"  # output projection
SCOPE_MLP = "mlp"
SCOPE_HEAD = "head"  # final norm + vocabulary projection


@jax.named_scope(SCOPE_QKV)
def _attn_qkv(spec: ModelSpec, lp: Params, x: jax.Array, positions: jax.Array):
    """x: [T, d] -> q [T, nh, hd], k/v [T, nkv, hd] with rope applied."""
    T = x.shape[0]
    q = x @ lp["wq"]
    k = x @ lp["wk"]
    v = x @ lp["wv"]
    if spec.attn_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(T, spec.num_heads, spec.head_dim)
    k = k.reshape(T, spec.num_kv_heads, spec.head_dim)
    v = v.reshape(T, spec.num_kv_heads, spec.head_dim)
    q = rope_spec(spec, q, positions)
    k = rope_spec(spec, k, positions)
    return q, k, v


@jax.named_scope(SCOPE_OUT)
def _o_proj(spec: ModelSpec, lp: Params, attn: jax.Array) -> jax.Array:
    out = attn @ lp["wo"]
    return out + lp["bo"] if spec.attn_bias else out


def _mlp(lp: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


@jax.named_scope(SCOPE_MLP)
def _ffn(spec: ModelSpec, lp: Params, x: jax.Array) -> jax.Array:
    """Dense MLP or routed MoE depending on the spec."""
    if spec.num_experts:
        from dynamo_tpu.models import moe

        return moe.moe_mlp(spec, lp["moe"], x)
    return _mlp(lp, x)


@jax.named_scope(SCOPE_MLP)
def _ffn_counted(spec: ModelSpec, lp: Params, x: jax.Array):
    """_ffn + dropped-slot count (0 for dense layers)."""
    if spec.num_experts:
        from dynamo_tpu.models import moe

        return moe.moe_mlp(spec, lp["moe"], x, return_dropped=True)
    return _mlp(lp, x), jnp.zeros((), jnp.int32)


@jax.named_scope(SCOPE_HEAD)
def _logits(spec: ModelSpec, params: Params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], spec.rms_eps)
    head = params["embed"].T if spec.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32)


# ---------------------------------------------------------------- prefill


def prefill_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad] int32 (padded)
    block_table: jax.Array,  # [max_pages_per_seq] int32
    start_pos: jax.Array,  # scalar: cached-prefix length (tokens)
    k_pages: jax.Array,  # [L, num_pages, kvh, page, D] (donated)
    v_pages: jax.Array,
    num_tokens: jax.Array,  # scalar: real token count in ``tokens``
    mesh: Mesh | None = None,  # static: replicate logits across the mesh
    mm_embeds: jax.Array | None = None,  # [M, d] multimodal embedding rows
    mm_pos: jax.Array | None = None,  # [M] window-relative positions (pad >= T)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Process one prompt; writes KV pages; returns (last_logits, k, v).

    Attention runs over the gathered paged context (cached prefix + newly
    written tokens), so prefix-cache hits skip recompute of cached tokens.
    ``mm_embeds``/``mm_pos``: encoder rows overwrite the placeholder
    tokens' embeddings (multimodal EPD injection — one masked scatter;
    padded positions >= T drop).
    """
    T = tokens.shape[0]
    idx = jnp.arange(T)
    positions = start_pos + idx  # absolute positions of new tokens
    page_size = k_pages.shape[3]

    # Page-granular KV write: prefix-cache hits and chunk boundaries are
    # page-aligned (engine invariant), so the T new tokens start at a page
    # boundary and land as whole [page_size, D] tiles — one scatter over
    # T/page indices instead of T token rows (XLA lowers tile scatters an
    # order of magnitude faster on TPU; the trailing tile stays
    # contiguous). Garbage in a partial tail page sits beyond num_tokens:
    # masked in attention, overwritten as decode appends. Fully-padded
    # pages go to the trash page (duplicate trash indices are fine).
    n_pg = T // page_size
    page_starts = start_pos + jnp.arange(n_pg) * page_size
    pg_idx_raw = block_table[page_starts // page_size]
    safe_pg = jnp.where(
        page_starts < start_pos + num_tokens, pg_idx_raw, TRASH_PAGE
    )
    valid_tok = (idx < num_tokens).reshape(n_pg, page_size)

    x = params["embed"][tokens]  # [T, d]
    if mm_embeds is not None:
        x = x.at[mm_pos].set(mm_embeds.astype(x.dtype), mode="drop")
    kv_len = start_pos + num_tokens
    moe_dropped = jnp.zeros((), jnp.int32)

    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q, k, v = _attn_qkv(spec, lp, h, positions)
        with jax.named_scope(SCOPE_KV):
            k_pages = _set_page_tiles(k_pages, li, safe_pg, k, page_size,
                                      valid_tok)
            v_pages = _set_page_tiles(v_pages, li, safe_pg, v, page_size,
                                      valid_tok)
            # [max_ctx, kvh, D] — sliced back to the model dim when
            # padded, dequantized when the pool is fp8
            k_ctx = gather_ctx(k_pages, li, block_table, spec.head_dim)
            v_ctx = gather_ctx(v_pages, li, block_table, spec.head_dim)
            if is_quant(k_pages):
                # overlay the EXACT in-flight rows over the quantized
                # read-back (the XLA mirror of the fused kernel's analytic
                # new-token merge): this prefill's own tokens attend to
                # each other at full precision; only the cached prefix
                # pays fp8
                k_ctx = k_ctx.at[positions].set(
                    k.astype(k_ctx.dtype), mode="drop"
                )
                v_ctx = v_ctx.at[positions].set(
                    v.astype(v_ctx.dtype), mode="drop"
                )
            attn = causal_attention(
                q, k_ctx, v_ctx, positions, kv_len,
                window=spec.attn_window(li), sinks=lp.get("sinks"),
            )
        attn = attn.reshape(T, spec.num_heads * spec.head_dim)
        x = x + _o_proj(spec, lp, attn)
        h = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        f, d = _ffn_counted(spec, lp, h)
        x = x + f
        moe_dropped = moe_dropped + d

    last = jnp.clip(num_tokens - 1, 0, T - 1)
    logits = _logits(spec, params, x[last])  # [V]
    logits = _replicate(logits, mesh)
    return logits, k_pages, v_pages, _replicate(moe_dropped, mesh)


def _replicate(x: jax.Array, mesh: Mesh | None) -> jax.Array:
    """Pin an output to fully-replicated across the mesh. Sampling runs on
    the leader's host (multi-host) or outside the SPMD program, so every
    process must hold an addressable full copy — without the constraint
    GSPMD may leave e.g. tp-sharded logits that only exist shard-wise."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


prefill_forward = jax.jit(
    prefill_forward_impl, static_argnums=(0,), static_argnames=("mesh",),
    donate_argnums=(5, 6),
)


def prefill_forward_batch_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [N, T_pad] int32 (padded)
    block_tables: jax.Array,  # [N, max_pages_per_seq] int32
    start_pos: jax.Array,  # [N] cached-prefix lengths (page-aligned)
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    num_tokens: jax.Array,  # [N] real token counts
    mesh: Mesh | None = None,  # static
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """N prompts in ONE dispatch — the packed-prefill admission path.

    A queue of same-bucket prompts lands as one jit call instead of N:
    matmuls batch over [N, T, d] (the MXU sees N*T rows), the per-layer
    KV write is ONE page-tile scatter over all N*T/page pages, and
    attention runs per prompt over its own table. This is what takes
    admission TTFT from O(N * dispatch) to O(dispatch): dispatch and
    host<->device round-trips dominate short prefills, especially when
    the host is far from the chip.

    Returns (last_logits [N, V], k_pages, v_pages, moe_dropped).
    """
    N, T = tokens.shape
    page_size = k_pages.shape[3]
    idx = jnp.arange(T)
    positions = start_pos[:, None] + idx[None, :]  # [N, T]
    n_pg = T // page_size
    page_starts = start_pos[:, None] + (
        jnp.arange(n_pg) * page_size
    )[None, :]  # [N, n_pg]
    pg_idx_raw = jnp.take_along_axis(
        block_tables, page_starts // page_size, axis=1
    )
    valid_pg = page_starts < (start_pos + num_tokens)[:, None]
    safe_pg = jnp.where(valid_pg, pg_idx_raw, TRASH_PAGE).reshape(N * n_pg)
    valid_tok = (idx[None, :] < num_tokens[:, None]).reshape(
        N * n_pg, page_size
    )

    x = params["embed"][tokens]  # [N, T, d]
    kv_len = start_pos + num_tokens  # [N]
    moe_dropped = jnp.zeros((), jnp.int32)

    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        with jax.named_scope(SCOPE_QKV):
            q = h @ lp["wq"]
            k = h @ lp["wk"]
            v = h @ lp["wv"]
            if spec.attn_bias:
                q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
            q = q.reshape(N, T, spec.num_heads, spec.head_dim)
            k = k.reshape(N, T, spec.num_kv_heads, spec.head_dim)
            v = v.reshape(N, T, spec.num_kv_heads, spec.head_dim)
            q = jax.vmap(lambda a, p: rope_spec(spec, a, p))(q, positions)
            k = jax.vmap(lambda a, p: rope_spec(spec, a, p))(k, positions)
        with jax.named_scope(SCOPE_KV):
            k_pages = _set_page_tiles(k_pages, li, safe_pg, k, page_size,
                                      valid_tok)
            v_pages = _set_page_tiles(v_pages, li, safe_pg, v, page_size,
                                      valid_tok)

        def one_attn(q_i, bt_i, pos_i, kvl_i, k_i, v_i, kp=k_pages,
                     vp=v_pages, li=li, lp=lp):
            k_ctx = gather_ctx(kp, li, bt_i, spec.head_dim)
            v_ctx = gather_ctx(vp, li, bt_i, spec.head_dim)
            if is_quant(kp):
                # exact in-flight rows over the quantized read-back
                # (see prefill_forward_impl)
                k_ctx = k_ctx.at[pos_i].set(
                    k_i.astype(k_ctx.dtype), mode="drop"
                )
                v_ctx = v_ctx.at[pos_i].set(
                    v_i.astype(v_ctx.dtype), mode="drop"
                )
            return causal_attention(
                q_i, k_ctx, v_ctx, pos_i, kvl_i,
                window=spec.attn_window(li), sinks=lp.get("sinks"),
            )

        with jax.named_scope(SCOPE_KV):
            attn = jax.vmap(one_attn)(
                q, block_tables, positions, kv_len, k, v
            )
        x = x + _o_proj(spec, lp, attn.reshape(N, T, -1))
        h = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        f, d = _ffn_counted(spec, lp, h.reshape(N * T, -1))
        x = x + f.reshape(N, T, -1)
        moe_dropped = moe_dropped + d

    last = jnp.clip(num_tokens - 1, 0, T - 1)  # [N]
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = _logits(spec, params, x_last)  # [N, V]
    logits = _replicate(logits, mesh)
    return logits, k_pages, v_pages, _replicate(moe_dropped, mesh)


prefill_forward_batch = jax.jit(
    prefill_forward_batch_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5, 6),
)


def prefill_forward_ring_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad] int32, T_pad divisible by mesh sp
    block_table: jax.Array,  # [max_pages_per_seq] int32
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    num_tokens: jax.Array,  # scalar: real token count
    mesh: Mesh,  # static
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Long-context prefill with sequence-parallel ring attention.

    Token activations shard over the "sp" mesh axis (sharding constraints
    guide GSPMD; only the attention itself is an explicit shard_map ring —
    see parallel/ring.py). No cached-prefix support: ring prefill serves
    cold ultra-long prompts; warm prefixes take the paged path. Padding at
    the tail is masked by causality (padded positions exceed every real
    query) and scatters to the trash page.
    """
    from dynamo_tpu.parallel.ring import ring_attention

    T = tokens.shape[0]
    idx = jnp.arange(T)
    page_size = k_pages.shape[3]
    # page-granular tile writes (see prefill_forward_impl): ring prefill is
    # cold (start 0), so the prompt starts page-aligned by construction
    n_pg = T // page_size
    page_starts = jnp.arange(n_pg) * page_size
    pg_idx_raw = block_table[page_starts // page_size]
    safe_pg = jnp.where(page_starts < num_tokens, pg_idx_raw, TRASH_PAGE)
    valid_tok = (idx < num_tokens).reshape(n_pg, page_size)

    sp_spec = NamedSharding(mesh, P("sp", None))
    x = params["embed"][tokens]
    x = jax.lax.with_sharding_constraint(x, sp_spec)

    moe_dropped = jnp.zeros((), jnp.int32)
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q, k, v = _attn_qkv(spec, lp, h, idx)
        k_pages = _set_page_tiles(k_pages, li, safe_pg, k, page_size,
                                  valid_tok)
        v_pages = _set_page_tiles(v_pages, li, safe_pg, v, page_size,
                                  valid_tok)
        attn = ring_attention(q, k, v, mesh=mesh)
        x = x + _o_proj(
            spec, lp, attn.reshape(T, spec.num_heads * spec.head_dim)
        )
        h = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        f, d = _ffn_counted(spec, lp, h)
        x = x + f
        moe_dropped = moe_dropped + d
        x = jax.lax.with_sharding_constraint(x, sp_spec)

    last = jnp.clip(num_tokens - 1, 0, T - 1)
    logits = _logits(spec, params, x[last])
    logits = _replicate(logits, mesh)
    return logits, k_pages, v_pages, _replicate(moe_dropped, mesh)


prefill_forward_ring = jax.jit(
    prefill_forward_ring_impl,
    static_argnums=(0,),
    static_argnames=("mesh",),
    donate_argnums=(4, 5),
)


# ----------------------------------------------------------------- verify


def verify_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [N, W] int32: [fed_token, draft...] per row
    block_tables: jax.Array,  # [N, max_pages_per_seq]
    start_pos: jax.Array,  # [N]: cache length before the fed token
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    num_tokens: jax.Array,  # [N] valid tokens per row (0 = padded row)
    mesh: Mesh | None = None,  # static
    allowed: jax.Array | None = None,  # [N, W, V] bool: guided masks
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Speculative-verify forward: N slots' (fed token + k drafts) in
    ONE short-prefill dispatch, with the target's greedy choice at EVERY
    position (engine/core.py _spec_phase).

    Differs from prefill in exactly two ways. (1) KV writes are
    TOKEN-granular (write_new_kv — the decode-path scatter/DMA kernel):
    a verify starts wherever decode left off, mid-page, so the
    page-tile scatter's page-aligned-start invariant does not hold.
    (2) Logits are computed for all W positions and argmax'd ON DEVICE —
    the host needs only the [N, W] int32 target tokens to run
    accept-longest-prefix, not a [N, W, V] logits download.

    Rejected-draft KV rows are garbage beyond the accepted prefix: they
    sit past the slot's post-verify seq_len, masked from attention, and
    are overwritten by the next real write at that position (the
    engine's page rollback handles the allocator side).

    Returns (targets [N, W] int32, k_pages, v_pages, moe_dropped).
    """
    from dynamo_tpu.ops.pallas.kv_write import write_new_kv

    N, W = tokens.shape
    page_size = k_pages.shape[3]
    idx = jnp.arange(W)
    positions = start_pos[:, None] + idx[None, :]  # [N, W]
    valid = idx[None, :] < num_tokens[:, None]
    pg_idx_raw = jnp.take_along_axis(
        block_tables, positions // page_size, axis=1
    )
    safe_pg2 = jnp.where(valid, pg_idx_raw, TRASH_PAGE)  # [N, W]
    offs2 = positions % page_size
    safe_pg = safe_pg2.reshape(N * W)
    offs = offs2.reshape(N * W)

    x = params["embed"][tokens]  # [N, W, d]
    kv_len = start_pos + num_tokens  # [N]
    moe_dropped = jnp.zeros((), jnp.int32)

    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q = h @ lp["wq"]
        k = h @ lp["wk"]
        v = h @ lp["wv"]
        if spec.attn_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(N, W, spec.num_heads, spec.head_dim)
        k = k.reshape(N, W, spec.num_kv_heads, spec.head_dim)
        v = v.reshape(N, W, spec.num_kv_heads, spec.head_dim)
        q = jax.vmap(lambda a, p: rope_spec(spec, a, p))(q, positions)
        k = jax.vmap(lambda a, p: rope_spec(spec, a, p))(k, positions)
        if is_quant(k_pages):
            # quantized append is a page-granular RMW: a verify's W
            # tokens often share a page, so land them one POSITION at a
            # time (static W loop, distinct pages within each call) —
            # the one-scatter fast path would lose same-page siblings
            for w in range(W):
                k_pages, v_pages = write_new_kv(
                    k_pages, v_pages, k[:, w], v[:, w],
                    safe_pg2[:, w], offs2[:, w], layer=li, mesh=mesh,
                )
        else:
            k_pages, v_pages = write_new_kv(
                k_pages, v_pages,
                k.reshape(N * W, spec.num_kv_heads, spec.head_dim),
                v.reshape(N * W, spec.num_kv_heads, spec.head_dim),
                safe_pg, offs, layer=li, mesh=mesh,
            )

        def one_attn(q_i, bt_i, pos_i, kvl_i, k_i, v_i, kp=k_pages,
                     vp=v_pages, li=li, lp=lp):
            k_ctx = gather_ctx(kp, li, bt_i, spec.head_dim)
            v_ctx = gather_ctx(vp, li, bt_i, spec.head_dim)
            if is_quant(kp):
                # exact verify-window rows over the quantized read-back:
                # the fed token + drafts judge each other at full
                # precision, like the fused decode path's analytic merge
                k_ctx = k_ctx.at[pos_i].set(
                    k_i.astype(k_ctx.dtype), mode="drop"
                )
                v_ctx = v_ctx.at[pos_i].set(
                    v_i.astype(v_ctx.dtype), mode="drop"
                )
            return causal_attention(
                q_i, k_ctx, v_ctx, pos_i, kvl_i,
                window=spec.attn_window(li), sinks=lp.get("sinks"),
            )

        attn = jax.vmap(one_attn)(q, block_tables, positions, kv_len, k, v)
        x = x + _o_proj(spec, lp, attn.reshape(N, W, -1))
        h = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        f, d = _ffn_counted(spec, lp, h.reshape(N * W, -1))
        x = x + f.reshape(N, W, -1)
        moe_dropped = moe_dropped + d

    logits = _logits(spec, params, x)  # [N, W, V]
    if allowed is not None:
        # guided decoding composes with speculation here: masking the
        # VERIFY logits per position means a rejected draft's correction
        # token is itself grammar-legal — conformance survives rejection
        logits = jnp.where(allowed, logits, -1e30)
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (
        _replicate(targets, mesh), k_pages, v_pages,
        _replicate(moe_dropped, mesh),
    )


verify_forward = jax.jit(
    verify_forward_impl, static_argnums=(0,), static_argnames=("mesh",),
    donate_argnums=(5, 6),
)


# ---------------------------------------------------------------- decode


def decode_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [B] int32: last sampled token per slot
    block_tables: jax.Array,  # [B, max_pages_per_seq]
    seq_lens: jax.Array,  # [B] length INCLUDING the new token
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    active: jax.Array,  # [B] bool: slot has a live request
    mesh: Mesh | None = None,  # static: routes attention through shard_map
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for the whole slot batch; returns (logits[B,V], k, v)."""
    B = tokens.shape[0]
    page_size = k_pages.shape[3]
    positions = seq_lens - 1  # position of the new token

    page_idx_raw = jnp.take_along_axis(
        block_tables, (positions // page_size)[:, None], axis=1
    )[:, 0]
    safe_page = jnp.where(active, page_idx_raw, TRASH_PAGE)
    offset = positions % page_size

    x = params["embed"][tokens]  # [B, d]

    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        # per-slot single-token qkv: vmap the [T=1] path
        with jax.named_scope(SCOPE_QKV):
            q = h @ lp["wq"]
            k = h @ lp["wk"]
            v = h @ lp["wv"]
            if spec.attn_bias:
                q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
            q = q.reshape(B, spec.num_heads, spec.head_dim)
            k = k.reshape(B, spec.num_kv_heads, spec.head_dim)
            v = v.reshape(B, spec.num_kv_heads, spec.head_dim)
            q = rope_spec(spec, q, positions)
            k = rope_spec(spec, k, positions)
        # KV append + paged attention in ONE kernel per layer on the
        # Pallas path (ops/pallas/fused_decode.py — halves the decode
        # program's kernel-launch count); scatter + gather attention
        # elsewhere (ops/attention.decode_update_attention dispatch)
        with jax.named_scope(SCOPE_KV):
            attn, k_pages, v_pages = decode_update_attention(
                q, k_pages, v_pages, k, v, block_tables, seq_lens,
                safe_page, offset, layer=li, mesh=mesh,
                window=spec.attn_window(li), sinks=lp.get("sinks"),
            )
        attn = attn.reshape(B, spec.num_heads * spec.head_dim)
        x = x + _o_proj(spec, lp, attn)
        h = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, lp, h)

    logits = _logits(spec, params, x)  # [B, V]
    return logits, k_pages, v_pages


decode_forward = jax.jit(
    decode_forward_impl, static_argnums=(0,), static_argnames=("mesh",),
    donate_argnums=(5, 6),
)


def decode_steps_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [B] last sampled token per slot
    block_tables: jax.Array,  # [B, max_pages_per_seq]
    seq_lens: jax.Array,  # [B] length INCLUDING the first new token
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    active: jax.Array,  # [B] bool
    temperature: jax.Array,  # [B] f32
    top_k: jax.Array,  # [B] int32
    top_p: jax.Array,  # [B] f32
    seeds: jax.Array,  # [B] uint32
    steps: jax.Array,  # [B] int32: tokens generated so far per slot
    n_steps: int = 1,  # static: decode steps per dispatch
    n_logprobs: int = 0,  # static: 0=off, N=sampled+top-N logprobs
    mesh: Mesh | None = None,  # static
    allowed: jax.Array | None = None,  # [B, V] bool: guided token masks
):
    """``n_steps`` decode iterations + on-device sampling in ONE dispatch.

    Returns (sampled [B, n_steps], k_pages, v_pages) — plus, when
    ``n_logprobs`` > 0, (sampled_logprobs [B, n], top_ids [B, n, N],
    top_logprobs [B, n, N]) between sampled and the caches. Amortizes host
    dispatch and device-sync cost over n steps (the same reason vLLM grew
    multi-step scheduling): only small arrays cross to the host per
    dispatch. Callers must pre-extend block tables so every active slot
    has page room for n more tokens; EOS inside a burst is handled
    host-side by discarding the tail. Sampling keys fold in the per-slot
    generated-count so bursts reproduce the per-request RNG stream exactly
    (engine/sampling.py contract).

    ``allowed`` is the guided-decoding constraint mask: the host-side
    automaton only advances as sampled tokens LAND, so the engine
    dispatches masked bursts at n_steps=1 (the mask is per-position) —
    a batch with no constrained slot passes None and compiles/runs the
    unmasked program unchanged.
    """
    from dynamo_tpu.engine.sampling import sample_tokens, token_logprobs

    B = tokens.shape[0]
    out0 = jnp.zeros((B, n_steps), jnp.int32)
    lp0 = jnp.zeros((B, n_steps), jnp.float32)
    ti0 = jnp.zeros((B, n_steps, max(n_logprobs, 1)), jnp.int32)
    tv0 = jnp.zeros((B, n_steps, max(n_logprobs, 1)), jnp.float32)

    def body(i, carry):
        toks, lens, kp, vp, out, lp, ti, tv = carry
        logits, kp, vp = decode_forward_impl(
            spec, params, toks, block_tables, lens, kp, vp, active, mesh=mesh
        )
        if allowed is not None:
            logits = jnp.where(allowed, logits, -1e30)
        nxt = sample_tokens(
            logits, temperature, top_k, top_p, seeds, steps + i
        )
        nxt = jnp.where(active, nxt, toks)
        out = out.at[:, i].set(nxt)
        if n_logprobs > 0:
            picked, top_i, top_v = token_logprobs(logits, nxt, n_logprobs)
            lp = lp.at[:, i].set(picked)
            ti = ti.at[:, i].set(top_i)
            tv = tv.at[:, i].set(top_v)
        return nxt, lens + active.astype(jnp.int32), kp, vp, out, lp, ti, tv

    _toks, _lens, k_pages, v_pages, out, lp, ti, tv = jax.lax.fori_loop(
        0, n_steps, body,
        (tokens, seq_lens, k_pages, v_pages, out0, lp0, ti0, tv0),
        unroll=False,
    )
    out = _replicate(out, mesh)
    if n_logprobs > 0:
        return (out, _replicate(lp, mesh), _replicate(ti, mesh),
                _replicate(tv, mesh), k_pages, v_pages)
    return out, k_pages, v_pages


decode_steps = jax.jit(
    decode_steps_impl,
    static_argnums=(0,),
    static_argnames=("n_steps", "n_logprobs", "mesh"),
    donate_argnums=(5, 6),
)


# ------------------------------------------------------- kv page movement


def _extract_kv_pages_impl(k_pages, v_pages, page_ids):
    """Gather whole pages for transfer: -> [L, n, kvh, page, D] x2.

    QuantPool pools pack fp8 values + bf16 scales into ONE uint8 payload
    per (layer, page) (ops/quant.pack_pages): KVBM tiers and the disagg
    wire then carry exactly those bytes — half the footprint, no silent
    upcast possible, and onboard re-materializes fp8 by bitcast."""
    if is_quant(k_pages):
        return pack_pages(k_pages, page_ids), pack_pages(v_pages, page_ids)
    return k_pages[:, page_ids], v_pages[:, page_ids]


# dynalint: disable=DL012 -- read-only gather: the live pools must
# survive the call (the extracted pages ship over the disagg wire while
# the source engine keeps serving from the same pools)
extract_kv_pages = jax.jit(_extract_kv_pages_impl)


def _insert_kv_pages_impl(k_pages, v_pages, page_ids, k_blocks, v_blocks):
    """Scatter transferred pages into the local pools (donated).
    Blocks are page-major stacks [L, n, kvh, page, D] — or packed uint8
    [L, n, X] payloads when the pool is quantized (both engines of a
    disagg pair must run the same kv_dtype)."""
    if is_quant(k_pages):
        kv_, ks_ = unpack_pages(
            k_blocks, k_pages.vals.shape[2:], k_pages.scale.shape[2:]
        )
        vv_, vs_ = unpack_pages(
            v_blocks, v_pages.vals.shape[2:], v_pages.scale.shape[2:]
        )
        return (
            QuantPool(
                k_pages.vals.at[:, page_ids].set(kv_),
                k_pages.scale.at[:, page_ids].set(ks_),
            ),
            QuantPool(
                v_pages.vals.at[:, page_ids].set(vv_),
                v_pages.scale.at[:, page_ids].set(vs_),
            ),
        )
    return (
        k_pages.at[:, page_ids].set(k_blocks),
        v_pages.at[:, page_ids].set(v_blocks),
    )


insert_kv_pages = jax.jit(_insert_kv_pages_impl, donate_argnums=(0, 1))


# ------------------------------------------------------------- embeddings


def embed_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad] int32 (padded)
    num_tokens: jax.Array,  # scalar: real token count
) -> jax.Array:
    """Sequence embedding: mean-pool the final-norm hidden states over the
    real tokens, L2-normalized — the serving surface behind /v1/embeddings
    (ref: the embeddings path of the HTTP service, http/service/openai.rs
    /v1/embeddings; engine side delegated in the reference, native here).
    Returns [hidden_size] float32."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = params["embed"][tokens]
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q, k, v = _attn_qkv(spec, lp, h, positions)
        attn = causal_attention(
            q, k, v, positions, num_tokens,
            window=spec.attn_window(li), sinks=lp.get("sinks"),
        )
        x = x + _o_proj(spec, lp, attn.reshape(T, -1))
        h = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, lp, h)
    xn = rms_norm(x, params["final_norm"], spec.rms_eps).astype(jnp.float32)
    mask = (positions < num_tokens)[:, None].astype(jnp.float32)
    pooled = (xn * mask).sum(axis=0) / jnp.maximum(mask.sum(), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-9)


embed_forward = jax.jit(embed_forward_impl, static_argnums=(0,))


# -------------------------------------------------------------- reference


def reference_forward(
    spec: ModelSpec, params: Params, tokens: jax.Array
) -> jax.Array:
    """Plain full-attention forward (no paging) - numerical ground truth for
    tests. tokens: [T] -> logits [T, V]."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = params["embed"][tokens]
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q, k, v = _attn_qkv(spec, lp, h, positions)
        attn = causal_attention(
            q, k, v, positions, jnp.asarray(T),
            window=spec.attn_window(li), sinks=lp.get("sinks"),
        )
        x = x + _o_proj(spec, lp, attn.reshape(T, -1))
        h = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, lp, h)
    xn = rms_norm(x, params["final_norm"], spec.rms_eps)
    head = params["embed"].T if spec.tie_embeddings else params["lm_head"]
    return (xn @ head).astype(jnp.float32)
