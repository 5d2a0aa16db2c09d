"""Multi-head Latent Attention (MLA): the DeepSeek-V2/V3/R1 attention.

The reference serves DeepSeek-R1 through engine configs
(recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml) and leaves
MLA to the engine; here the engine is ours, so MLA is implemented
TPU-natively. What makes MLA special for serving:

- The KV cache stores ONE latent vector per token — ``kv_lora_rank``
  compressed dims plus a small decoupled-RoPE key (``qk_rope_head_dim``)
  SHARED across heads — instead of per-head K and V. For R1
  (128 heads, d_c=512, d_r=64) that is ~14x less KV memory than GQA at
  the same head count, which is why wide-EP decode fits at all.
- Decode runs in the ABSORBED form: q_nope folds through W_uk so scores
  are taken directly against cached latents, and the attention output is
  re-expanded through W_uv afterwards — per step the cache traffic is
  the latent stream, never materialized per-head K/V.

Paged cache layout: ``[L, num_pages, page_size, d_c + d_r]`` — no head
axis (the latent is shared), page-major like the GQA pool, and
compatible with the engine's page/block bookkeeping. Rows gather by
block table with plain XLA ops; MLA decode is far less gather-bound
than GQA (one row per token, not KH) so the Pallas treatment is not the
first bottleneck here.

The DeepSeek block composes MLA with the MoE FFN (models/moe.py) plus
``n_shared_experts`` always-on dense experts; the first
``first_k_dense`` layers use a plain dense MLP (DeepSeek's
first_k_dense_replace). RoPE is the standard half-split form, with YaRN
frequency correction when the spec configures it (DeepSeek-R1 ships
factor 40 / mscale 1 — llama.yarn_freqs, HF-parity semantics).

Parity contract: ``reference_forward`` computes the plain non-absorbed
attention; the paged prefill/decode must match it (tests/test_mla.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.models.llama import (
    TRASH_PAGE, _logits, _replicate, rms_norm, rope_spec,
)
from dynamo_tpu.ops.quant import (
    QuantPool,
    gather_dequant_rows,
    init_quant_pool,
    is_quant,
    quant_append_rows,
    quant_page_tiles,
)

Params = dict[str, Any]

NEG_INF = -1e30


def latent_dim(spec: ModelSpec) -> int:
    return spec.kv_lora_rank + spec.qk_rope_head_dim


def softmax_scale(spec: ModelSpec) -> float:
    """MLA attention scale: 1/sqrt(dn+dr), times the YaRN mscale^2
    correction when the checkpoint ships mscale_all_dim (HF
    DeepseekV3Attention multiplies its scaling by
    yarn_get_mscale(factor, mscale_all_dim)^2 — R1: (0.1*ln(40)+1)^2)."""
    import math

    from dynamo_tpu.models.llama import yarn_get_mscale

    base = 1.0 / math.sqrt(spec.qk_nope_head_dim + spec.qk_rope_head_dim)
    if spec.rope_scaling_factor and spec.rope_mscale_all_dim:
        m = yarn_get_mscale(spec.rope_scaling_factor, spec.rope_mscale_all_dim)
        base *= m * m
    return base


# ---------------------------------------------------------------- init


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random-init DeepSeek-family params (MLA + MoE/dense FFN)."""
    assert spec.kv_lora_rank > 0, "not an MLA spec"
    dtype = jnp.dtype(spec.dtype)
    d = spec.hidden_size
    H = spec.num_heads
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    dc = spec.kv_lora_rank
    keys = iter(jax.random.split(key, 8 + spec.num_layers * 12))

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
    for li in range(spec.num_layers):
        layer: Params = {
            "attn_norm": jnp.ones((d,), dtype),
            "mlp_norm": jnp.ones((d,), dtype),
            "w_kv_a": dense(next(keys), (d, dc + dr)),
            "kv_norm": jnp.ones((dc,), dtype),
            "w_uk": dense(next(keys), (H, dc, dn), scale=1.0 / jnp.sqrt(dc)),
            "w_uv": dense(next(keys), (H, dc, dv), scale=1.0 / jnp.sqrt(dc)),
            "wo": dense(next(keys), (H * dv, d)),
        }
        if spec.q_lora_rank:
            layer["wq_a"] = dense(next(keys), (d, spec.q_lora_rank))
            layer["q_norm"] = jnp.ones((spec.q_lora_rank,), dtype)
            layer["wq_b"] = dense(
                next(keys), (spec.q_lora_rank, H * (dn + dr))
            )
        else:
            layer["wq"] = dense(next(keys), (d, H * (dn + dr)))
        if spec.num_experts and li >= spec.first_k_dense:
            from dynamo_tpu.models import moe

            layer["moe"] = moe.init_moe_layer(spec, next(keys))
            if spec.n_shared_experts:
                f = spec.moe_intermediate_size * spec.n_shared_experts
                layer["shared"] = {
                    "w_gate": dense(next(keys), (d, f)),
                    "w_up": dense(next(keys), (d, f)),
                    "w_down": dense(next(keys), (f, d)),
                }
        else:
            layer["w_gate"] = dense(next(keys), (d, spec.intermediate_size))
            layer["w_up"] = dense(next(keys), (d, spec.intermediate_size))
            layer["w_down"] = dense(next(keys), (spec.intermediate_size, d))
        params["layers"].append(layer)
    return params


def init_cache(
    spec: ModelSpec, num_pages: int, page_size: int, dtype=None,
    kv_dtype: str = "bf16",
) -> jax.Array:
    """Latent cache [L, num_pages, page_size, d_c + d_r] (page 0 = trash).
    ONE array — MLA has no separate K and V pools. ``kv_dtype="fp8"``
    allocates a QuantPool (ops/quant.py) with one bf16 scale per
    (layer, page, ROW): with no head axis the row is the natural scale
    unit, appends never requantize their neighbors, and the finer
    granularity keeps the absorbed-attention drift inside the tolerance
    goldens (a single per-page scale measured ~2x the greedy-token
    disagreement on CPU)."""
    dtype = dtype or jnp.dtype(spec.dtype)
    shape = (spec.num_layers, num_pages, page_size, latent_dim(spec))
    if kv_dtype == "fp8":
        return init_quant_pool(shape, 3)
    return jnp.zeros(shape, dtype)


def _set_latent_tiles(
    cache, li: int, safe_pg: jax.Array, tiles: jax.Array,
    valid_tok: jax.Array,  # [n_tiles, page] bool
):
    """Prefill latent page write for either cache form (the MLA analogue
    of llama._set_page_tiles; one scale per row, amax over the latent
    dim)."""
    if is_quant(cache):
        vals, s = quant_page_tiles(tiles, valid_tok[:, :, None], (2,))
        return QuantPool(
            cache.vals.at[li, safe_pg].set(vals),
            cache.scale.at[li, safe_pg].set(s),
        )
    return cache.at[li, safe_pg].set(tiles.astype(cache.dtype))


def _gather_rows_any(cache, li: int, block_table: jax.Array) -> jax.Array:
    """[num_pages, page, D] + [P] -> [P*page, D], dequantized when fp8."""
    if is_quant(cache):
        return gather_dequant_rows(cache.layer(li), block_table)
    return _gather_rows(cache[li], block_table)


def param_shardings(spec: ModelSpec, mesh: Mesh) -> Params:
    """TP shardings for MLA: the head axis is the parallel axis.

    The latent path (w_kv_a, kv_norm) is REPLICATED — the whole point of
    MLA is that the per-token latent is tiny and shared across heads, so
    every tp rank computes the full latent row locally (no collective)
    and per-head work (q projection, absorbed w_uk/w_uv, wo) shards over
    "tp". Experts shard over "ep" via moe_layer_shardings, matching the
    wide-EP layout the reference deploys DeepSeek-R1 with
    (recipes/deepseek-r1/sglang-wideep/tep16p-dep16d-disagg.yaml:63)."""

    def ns(*axes):
        return NamedSharding(mesh, P(*axes))

    layers = []
    for li in range(spec.num_layers):
        layer: Params = {
            "attn_norm": ns(),
            "mlp_norm": ns(),
            "w_kv_a": ns(),
            "kv_norm": ns(),
            "w_uk": ns("tp", None, None),  # heads
            "w_uv": ns("tp", None, None),
            "wo": ns("tp", None),  # row-parallel over flattened heads
        }
        if spec.q_lora_rank:
            layer["wq_a"] = ns()
            layer["q_norm"] = ns()
            layer["wq_b"] = ns(None, "tp")  # column (heads major)
        else:
            layer["wq"] = ns(None, "tp")
        if spec.num_experts and li >= spec.first_k_dense:
            from dynamo_tpu.models import moe

            layer["moe"] = moe.moe_layer_shardings(mesh, spec)
            if spec.n_shared_experts:
                layer["shared"] = {
                    "w_gate": ns(None, "tp"),
                    "w_up": ns(None, "tp"),
                    "w_down": ns("tp", None),
                }
        else:
            layer["w_gate"] = ns(None, "tp")
            layer["w_up"] = ns(None, "tp")
            layer["w_down"] = ns("tp", None)
        layers.append(layer)
    out = {
        "embed": ns(None, "tp"),
        "final_norm": ns(),
        "layers": layers,
    }
    if not spec.tie_embeddings:
        out["lm_head"] = ns(None, "tp")
    return out


def cache_shardings(mesh: Mesh, kv_dtype: str = "bf16"):
    """Latent cache [L, pages, page, d_c + d_r]: REPLICATED across the
    mesh. There is no head axis to split — the latent row is shared by
    every head — and at ~14x compression vs GQA the duplication is the
    cheap side of the trade (each rank attends against its local copy
    with zero gather collectives in the decode hot loop). Quantized
    caches replicate both leaves."""
    s = NamedSharding(mesh, P())
    return QuantPool(s, s) if kv_dtype == "fp8" else s


# --------------------------------------------------------------- pieces


def _q_heads(spec: ModelSpec, lp: Params, h: jax.Array, positions) -> tuple:
    """-> (q_nope [T, H, dn], q_rope [T, H, dr]) with RoPE applied."""
    T = h.shape[0]
    H, dn, dr = spec.num_heads, spec.qk_nope_head_dim, spec.qk_rope_head_dim
    if spec.q_lora_rank:
        q = rms_norm(h @ lp["wq_a"], lp["q_norm"], spec.rms_eps) @ lp["wq_b"]
    else:
        q = h @ lp["wq"]
    q = q.reshape(T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    return q_nope, rope_spec(spec, q_rope, positions)


def _latent_row(spec: ModelSpec, lp: Params, h: jax.Array, positions):
    """-> cache rows [T, d_c + d_r]: normalized latent + roped shared key."""
    dc = spec.kv_lora_rank
    kv_a = h @ lp["w_kv_a"]
    c = rms_norm(kv_a[:, :dc], lp["kv_norm"], spec.rms_eps)
    k_r = rope_spec(spec, kv_a[:, None, dc:], positions)[:, 0]
    return jnp.concatenate([c, k_r], axis=-1)


def _absorbed_attention(
    spec: ModelSpec,
    lp: Params,
    q_nope: jax.Array,  # [T, H, dn]
    q_rope: jax.Array,  # [T, H, dr]
    rows: jax.Array,  # [S, d_c + d_r] cached latents (+ self rows)
    mask: jax.Array,  # [T, S] bool
) -> jax.Array:
    """Latent-space attention -> per-head outputs [T, H, dv]."""
    dc = spec.kv_lora_rank
    scale = jnp.asarray(softmax_scale(spec), jnp.float32)
    c, k_r = rows[:, :dc], rows[:, dc:]
    # absorb W_uk: q_lat[t,h,:] = q_nope[t,h,:] @ w_uk[h].T  -> [T, H, dc]
    q_lat = jnp.einsum("thn,hcn->thc", q_nope.astype(jnp.float32),
                       lp["w_uk"].astype(jnp.float32))
    scores = (
        jnp.einsum("thc,sc->ths", q_lat, c.astype(jnp.float32))
        + jnp.einsum("thr,sr->ths", q_rope.astype(jnp.float32),
                     k_r.astype(jnp.float32))
    ) * scale
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("ths,sc->thc", probs, c.astype(jnp.float32))
    return jnp.einsum("thc,hcv->thv", o_lat,
                      lp["w_uv"].astype(jnp.float32))


def _ffn(spec: ModelSpec, li: int, lp: Params, x: jax.Array) -> jax.Array:
    if "moe" in lp:
        from dynamo_tpu.models import moe

        out = moe.moe_mlp(spec, lp["moe"], x)
        if "shared" in lp:
            sh = lp["shared"]
            out = out + (
                jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
            ) @ sh["w_down"]
        return out
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


# ------------------------------------------------------------- reference


def reference_forward(
    spec: ModelSpec, params: Params, tokens: jax.Array
) -> jax.Array:
    """Plain NON-absorbed MLA forward (per-head K/V materialized) — the
    numerical ground truth the paged/absorbed paths must match."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = params["embed"][tokens]
    dn = spec.qk_nope_head_dim
    scale = jnp.asarray(softmax_scale(spec), jnp.float32)
    mask = positions[:, None] >= positions[None, :]
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q_nope, q_rope = _q_heads(spec, lp, h, positions)
        rows = _latent_row(spec, lp, h, positions)
        c, k_r = rows[:, : spec.kv_lora_rank], rows[:, spec.kv_lora_rank:]
        k_nope = jnp.einsum("sc,hcn->shn", c.astype(jnp.float32),
                            lp["w_uk"].astype(jnp.float32))
        v = jnp.einsum("sc,hcv->shv", c.astype(jnp.float32),
                       lp["w_uv"].astype(jnp.float32))
        scores = (
            jnp.einsum("thn,shn->ths", q_nope.astype(jnp.float32), k_nope)
            + jnp.einsum("thr,sr->ths", q_rope.astype(jnp.float32),
                         k_r.astype(jnp.float32))
        ) * scale
        scores = jnp.where(mask[:, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("ths,shv->thv", probs, v)
        x = x + attn.reshape(T, -1).astype(x.dtype) @ lp["wo"]
        hh = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, li, lp, hh)
    return _logits_all(spec, params, x)


def _logits_all(spec, params, x):
    xn = rms_norm(x, params["final_norm"], spec.rms_eps)
    head = params["embed"].T if spec.tie_embeddings else params["lm_head"]
    return (xn @ head).astype(jnp.float32)


# ----------------------------------------------------------------- paged


def _gather_rows(cache_l: jax.Array, block_table: jax.Array) -> jax.Array:
    """[num_pages, page, D] + [P] -> [P*page, D]."""
    rows = cache_l[block_table]  # [P, page, D]
    P, page, D = rows.shape
    return rows.reshape(P * page, D)


def prefill_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad]
    block_table: jax.Array,  # [max_pages_per_seq]
    start_pos: jax.Array,  # scalar (page-aligned)
    cache: jax.Array,  # [L, pages, page, D] (donated)
    num_tokens: jax.Array,  # scalar
    mesh: Mesh | None = None,  # static: replicate logits across the mesh
) -> tuple[jax.Array, jax.Array]:
    """One prompt; writes latent rows page-granularly; returns
    (last_logits, cache). Mirrors llama.prefill_forward_impl."""
    T = tokens.shape[0]
    idx = jnp.arange(T)
    positions = start_pos + idx
    page_size = cache.shape[2]
    n_pg = T // page_size
    page_starts = start_pos + jnp.arange(n_pg) * page_size
    pg_idx = block_table[page_starts // page_size]
    safe_pg = jnp.where(
        page_starts < start_pos + num_tokens, pg_idx, TRASH_PAGE
    )
    valid_tok = (idx < num_tokens).reshape(n_pg, page_size)
    x = params["embed"][tokens]
    kv_len = start_pos + num_tokens
    max_ctx = block_table.shape[0] * page_size
    ctx_pos = jnp.arange(max_ctx)
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q_nope, q_rope = _q_heads(spec, lp, h, positions)
        new_rows = _latent_row(spec, lp, h, positions)
        cache = _set_latent_tiles(
            cache, li, safe_pg,
            new_rows.reshape(n_pg, page_size, -1), valid_tok,
        )
        rows = _gather_rows_any(cache, li, block_table)  # [max_ctx, D]
        if is_quant(cache):
            # exact in-flight rows over the quantized read-back (the XLA
            # mirror of the fused GQA kernel's analytic new-token merge)
            rows = rows.at[positions].set(
                new_rows.astype(rows.dtype), mode="drop"
            )
        mask = (ctx_pos[None, :] <= positions[:, None]) & (
            ctx_pos[None, :] < kv_len
        )
        attn = _absorbed_attention(spec, lp, q_nope, q_rope, rows, mask)
        x = x + attn.reshape(T, -1).astype(x.dtype) @ lp["wo"]
        hh = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, li, lp, hh)
    last = jnp.clip(num_tokens - 1, 0, T - 1)
    return _replicate(_logits_all(spec, params, x)[last], mesh), cache


prefill_forward = jax.jit(
    prefill_forward_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5,)
)


def prefill_forward_batch_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [N, T_pad]
    block_tables: jax.Array,  # [N, max_pages_per_seq]
    start_pos: jax.Array,  # [N] (page-aligned)
    cache: jax.Array,  # donated
    num_tokens: jax.Array,  # [N]
    mesh: Mesh | None = None,  # static
) -> tuple[jax.Array, jax.Array]:
    """N prompts in ONE dispatch — MLA's packed-prefill admission path
    (mirrors llama.prefill_forward_batch_impl: matmuls batch over
    [N, T, d], the latent write is one page-tile scatter, absorbed
    attention runs per prompt over its own table). Returns
    (last_logits [N, V], cache)."""
    N, T = tokens.shape
    page_size = cache.shape[2]
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

    x = params["embed"][tokens]  # [N, T, d]
    kv_len = start_pos + num_tokens  # [N]
    max_ctx = block_tables.shape[1] * page_size
    ctx_pos = jnp.arange(max_ctx)
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q_nope, q_rope = jax.vmap(
            lambda hh, pos: _q_heads(spec, lp, hh, pos)
        )(h, positions)  # [N, T, H, dn] / [N, T, H, dr]
        new_rows = jax.vmap(
            lambda hh, pos: _latent_row(spec, lp, hh, pos)
        )(h, positions)  # [N, T, D]
        cache = _set_latent_tiles(
            cache, li, safe_pg,
            new_rows.reshape(N * n_pg, page_size, -1),
            (idx[None, :] < num_tokens[:, None]).reshape(
                N * n_pg, page_size
            ),
        )

        def one_attn(qn, qr, bt, pos, kvl, nr, cache=cache, li=li, lp=lp):
            rows = _gather_rows_any(cache, li, bt)  # [max_ctx, D]
            if is_quant(cache):
                rows = rows.at[pos].set(nr.astype(rows.dtype), mode="drop")
            mask = (ctx_pos[None, :] <= pos[:, None]) & (
                ctx_pos[None, :] < kvl
            )
            return _absorbed_attention(spec, lp, qn, qr, rows, mask)

        attn = jax.vmap(one_attn)(
            q_nope, q_rope, block_tables, positions, kv_len, new_rows
        )  # [N, T, H, dv]
        x = x + attn.reshape(N, T, -1).astype(x.dtype) @ lp["wo"]
        hh = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, li, lp, hh.reshape(N * T, -1)).reshape(N, T, -1)

    last = jnp.clip(num_tokens - 1, 0, T - 1)  # [N]
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    return _replicate(_logits_all(spec, params, x_last), mesh), cache


prefill_forward_batch = jax.jit(
    prefill_forward_batch_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5,)
)


def verify_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [N, W] int32: [fed_token, draft...] per row
    block_tables: jax.Array,  # [N, max_pages_per_seq]
    start_pos: jax.Array,  # [N]: cache length before the fed token
    cache: jax.Array,  # donated
    num_tokens: jax.Array,  # [N] valid tokens per row (0 = padded row)
    mesh: Mesh | None = None,  # static
    allowed: jax.Array | None = None,  # [N, W, V] bool: guided masks
) -> tuple[jax.Array, jax.Array]:
    """Speculative-verify forward for MLA (mirrors llama.verify_forward):
    token-granular latent writes — a verify starts mid-page, so the
    page-tile invariant of prefill does not hold — and the target's
    greedy argmax at all W positions, returned as [N, W] int32 so only
    token ids cross to the host. Returns (targets, cache)."""
    N, W = tokens.shape
    page_size = cache.shape[2]
    idx = jnp.arange(W)
    positions = start_pos[:, None] + idx[None, :]  # [N, W]
    valid = idx[None, :] < num_tokens[:, None]
    pg_idx_raw = jnp.take_along_axis(
        block_tables, positions // page_size, axis=1
    )
    safe_pg = jnp.where(valid, pg_idx_raw, TRASH_PAGE).reshape(N * W)
    offs = (positions % page_size).reshape(N * W)

    x = params["embed"][tokens]  # [N, W, d]
    kv_len = start_pos + num_tokens  # [N]
    max_ctx = block_tables.shape[1] * page_size
    ctx_pos = jnp.arange(max_ctx)
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q_nope, q_rope = jax.vmap(
            lambda hh, pos: _q_heads(spec, lp, hh, pos)
        )(h, positions)  # [N, W, H, dn] / [N, W, H, dr]
        new_rows = jax.vmap(
            lambda hh, pos: _latent_row(spec, lp, hh, pos)
        )(h, positions)  # [N, W, D]
        if is_quant(cache):
            # per-row scales make this a plain scatter: every (page,
            # offset) slot owns its scale, so same-page siblings never
            # clash (unlike the GQA page RMW)
            cache = quant_append_rows(
                cache, new_rows.reshape(N * W, -1), safe_pg, offs, li
            )
        else:
            cache = cache.at[li, safe_pg, offs].set(
                new_rows.reshape(N * W, -1).astype(cache.dtype)
            )

        def one_attn(qn, qr, bt, pos, kvl, nr, cache=cache, li=li, lp=lp):
            rows = _gather_rows_any(cache, li, bt)  # [max_ctx, D]
            if is_quant(cache):
                # exact verify-window rows (llama mirror)
                rows = rows.at[pos].set(nr.astype(rows.dtype), mode="drop")
            mask = (ctx_pos[None, :] <= pos[:, None]) & (
                ctx_pos[None, :] < kvl
            )
            return _absorbed_attention(spec, lp, qn, qr, rows, mask)

        attn = jax.vmap(one_attn)(
            q_nope, q_rope, block_tables, positions, kv_len, new_rows
        )  # [N, W, H, dv]
        x = x + attn.reshape(N, W, -1).astype(x.dtype) @ lp["wo"]
        hh = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, li, lp, hh.reshape(N * W, -1)).reshape(N, W, -1)

    logits = _logits_all(spec, params, x)  # [N, W, V]
    if allowed is not None:
        # guided x spec: masked verify logits keep the correction token
        # on-grammar even when every draft is rejected (llama mirror)
        logits = jnp.where(allowed, logits, NEG_INF)
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _replicate(targets, mesh), cache


verify_forward = jax.jit(
    verify_forward_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5,)
)


def decode_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [B]
    block_tables: jax.Array,  # [B, P]
    seq_lens: jax.Array,  # [B] incl. the new token
    cache: jax.Array,  # donated
    active: jax.Array,  # [B] bool
    mesh: Mesh | None = None,  # static
) -> tuple[jax.Array, jax.Array]:
    """One decode step (absorbed latent attention); returns (logits, cache)."""
    B = tokens.shape[0]
    page_size = cache.shape[2]
    positions = seq_lens - 1
    page_idx = jnp.take_along_axis(
        block_tables, (positions // page_size)[:, None], axis=1
    )[:, 0]
    safe_page = jnp.where(active, page_idx, TRASH_PAGE)
    offset = positions % page_size
    max_ctx = block_tables.shape[1] * page_size
    ctx_pos = jnp.arange(max_ctx)
    x = params["embed"][tokens]
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q_nope, q_rope = _q_heads(spec, lp, h, positions)
        new_rows = _latent_row(spec, lp, h, positions)  # [B, D]
        if is_quant(cache):
            cache = quant_append_rows(
                cache, new_rows, safe_page, offset, li
            )
        else:
            cache = cache.at[li, safe_page, offset].set(
                new_rows.astype(cache.dtype)
            )
        rows = jax.vmap(
            lambda bt, cache=cache, li=li: _gather_rows_any(cache, li, bt)
        )(block_tables)  # [B, max_ctx, D]
        if is_quant(cache):
            # exact new-token overlay: the decode query's own latent row
            # (its strongest attention target) never pays fp8 error
            max_ctx_i = rows.shape[1]
            rows = rows.at[
                jnp.arange(B), jnp.clip(positions, 0, max_ctx_i - 1)
            ].set(new_rows.astype(rows.dtype))
        mask = ctx_pos[None, :] < seq_lens[:, None]  # [B, max_ctx]
        attn = jax.vmap(
            lambda qn, qr, r, m: _absorbed_attention(
                spec, lp, qn[None], qr[None], r, m[None]
            )[0]
        )(q_nope, q_rope, rows, mask)
        x = x + attn.reshape(B, -1).astype(x.dtype) @ lp["wo"]
        hh = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, li, lp, hh)
    return _replicate(_logits_all(spec, params, x), mesh), cache


decode_forward = jax.jit(
    decode_forward_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5,)
)


def decode_steps_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    cache: jax.Array,
    active: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    seeds: jax.Array,
    steps: jax.Array,
    n_steps: int = 1,
    n_logprobs: int = 0,  # static: 0=off, N=sampled+top-N logprobs
    mesh: Mesh | None = None,  # static
    allowed: jax.Array | None = None,  # [B, V] bool: guided token masks
):
    """Fused multi-step MLA decode + on-device sampling (the serving hot
    loop; mirrors llama.decode_steps for the GQA family, including the
    logprob surface)."""
    from dynamo_tpu.engine.sampling import sample_tokens, token_logprobs

    B = tokens.shape[0]
    out0 = jnp.zeros((B, n_steps), jnp.int32)
    lp0 = jnp.zeros((B, n_steps), jnp.float32)
    ti0 = jnp.zeros((B, n_steps, max(n_logprobs, 1)), jnp.int32)
    tv0 = jnp.zeros((B, n_steps, max(n_logprobs, 1)), jnp.float32)

    def body(i, carry):
        toks, lens, cache, out, lp, ti, tv = carry
        logits, cache = decode_forward_impl(
            spec, params, toks, block_tables, lens, cache, active,
            mesh=mesh,
        )
        if allowed is not None:
            logits = jnp.where(allowed, logits, NEG_INF)
        nxt = sample_tokens(logits, temperature, top_k, top_p, seeds,
                            steps + i)
        nxt = jnp.where(active, nxt, toks)
        out = out.at[:, i].set(nxt)
        if n_logprobs > 0:
            picked, top_i, top_v = token_logprobs(logits, nxt, n_logprobs)
            lp = lp.at[:, i].set(picked)
            ti = ti.at[:, i].set(top_i)
            tv = tv.at[:, i].set(top_v)
        return (nxt, lens + active.astype(jnp.int32), cache, out, lp, ti, tv)

    _t, _l, cache, out, lp, ti, tv = jax.lax.fori_loop(
        0, n_steps, body,
        (tokens, seq_lens, cache, out0, lp0, ti0, tv0),
    )
    out = _replicate(out, mesh)
    if n_logprobs > 0:
        return (out, _replicate(lp, mesh), _replicate(ti, mesh),
                _replicate(tv, mesh), cache)
    return out, cache


decode_steps = jax.jit(
    decode_steps_impl, static_argnums=(0,),
    static_argnames=("n_steps", "n_logprobs", "mesh"),
    # donate the latent cache: without this every MLA decode burst
    # COPIED the whole cache for its in-place page writes (the donation
    # audit in tests/test_donation.py caught exactly this)
    donate_argnums=(5,),
)


# ------------------------------------------------------------- embeddings


def embed_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad] int32 (padded)
    num_tokens: jax.Array,  # scalar: real token count
) -> jax.Array:
    """Sequence embedding for the MLA family: mean-pooled final-norm
    hidden states over the real tokens, L2-normalized (mirrors
    llama.embed_forward_impl — the /v1/embeddings surface)."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = params["embed"][tokens]
    mask2d = (positions[:, None] >= positions[None, :]) & (
        positions[None, :] < num_tokens
    )
    for li, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["attn_norm"], spec.rms_eps)
        q_nope, q_rope = _q_heads(spec, lp, h, positions)
        rows = _latent_row(spec, lp, h, positions)
        attn = _absorbed_attention(spec, lp, q_nope, q_rope, rows, mask2d)
        x = x + attn.reshape(T, -1).astype(x.dtype) @ lp["wo"]
        hh = rms_norm(x, lp["mlp_norm"], spec.rms_eps)
        x = x + _ffn(spec, li, lp, hh)
    xn = rms_norm(x, params["final_norm"], spec.rms_eps).astype(jnp.float32)
    valid = (positions < num_tokens)[:, None].astype(jnp.float32)
    pooled = (xn * valid).sum(axis=0) / jnp.maximum(valid.sum(), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-9)


embed_forward = jax.jit(embed_forward_impl, static_argnums=(0,))
