"""Multi-head Latent Attention (MLA): the DeepSeek-V2/V3/R1 family's
attention (JoyAI-LLM-Flash is the configuration the benchmark serves).

What makes it a family of its own for serving:

- The cache stores ONE latent row a token a layer, ``[c | k_r]``:
  ``kv_lora_rank`` compressed dims plus a small roped key
  (``qk_rope_head_dim``) SHARED by the heads, instead of per-head K and V:
  576 values (1,152 B in bfloat16) where 32 heads of 192 + 128 would be
  20,480 B.
- DECODE runs ABSORBED: ``q_nope`` folds through ``W_uk`` so scores are
  taken against the cached rows themselves, values are the same rows'
  leading ``kv_lora_rank`` lanes, and the output is re-expanded through
  ``W_uv``. A step moves the latent stream, once:
  ``ops/attention.latent_decode_update_attention`` chooses the Mosaic
  kernel (ops/pallas/latent_decode.py: one pool, a page fetched once and
  used as key and as value, a sequence's own live chunks, the new row
  written in the call) where Pallas runs and the XLA walk elsewhere.
- PREFILL (single, packed, the speculative verify) runs NOT absorbed
  through ``ops/attention.latent_prefill_attention``: a block of cached
  rows is up-projected to per-head keys and values once and scored by the
  call's queries at ``(192 + 128) x 2`` FLOP a pair where the absorbed
  form pays ``(576 + 512) x 2``; a chunk at ``start_pos > 0`` reads the
  earlier chunks' rows from their pages. It chooses the Mosaic kernel
  (ops/pallas/latent_prefill.py: a pack's members a grid axis, a
  tile-by-block score never out of VMEM, a query tile stopping at its
  causal edge) where Pallas runs and the XLA walk elsewhere. No program
  gathers a whole table or holds a ``[.., max_ctx]`` score.

Paged cache: ``[L, num_pages, page_size, D]`` with ``D`` the row's 576
values rounded up to the lane tile where the kernel runs compiled
(``ops/attention.pool_head_dim``); no head axis, page-major like the GQA
pool and under the same page / block bookkeeping. ``kv_dtype="fp8"`` keeps
a ``QuantPool`` with a scale a ROW and the XLA paths (counted:
``latent_fp8_xla``, ``latent_prefill_fp8_xla``).

The block composes MLA with the expert layer (models/moe.py: sigmoid
``noaux_tc`` routing, ``held_experts`` of an ep deployment, the layer's
counters) plus ``n_shared_experts`` always-on experts that every chip
computes whole; the first ``first_k_dense`` layers use a dense MLP. RoPE is
computed half-split; a model whose published weights rotate INTERLEAVED
pairs (``rope_interleave``) has the rope columns of ``wq_b`` / ``w_kv_a``
permuted where the weights are made (``init_params``) or loaded
(models/loader.py): exact, since both sides of every rope dot product get
the same permutation. YaRN when the spec configures it.

A model of shortcut-connected double layers (``ModelSpec.shortcut_moe``:
LongCat-Flash) runs the same programs: ``_layer`` is the one place a
decoder layer's data flow is written, and there a layer is two sub-layers
(a latent attention and a dense FFN each) with ONE expert layer fed by the
first FFN's input and added behind the second FFN. Its cache is a tuple of
two pools, one a sub-layer, each ``[L, num_pages, page_size, D]`` under the
same block tables; its layer holds ``"sub": [first, second]`` beside
``"moe"``. The queries' and the latent's fixed scalars
(``mla_scale_q_lora`` / ``mla_scale_kv_lora``) are applied where the
projections are (``_q_heads``, ``_latent_row``).

Every program takes the latent cache and, as ``counts``, the expert
layers' device-side counters (``[L, 2, n_held + 3]`` int32, the layout of
``llama.KindPools.counts``); ``models/family.MlaFamily`` carries the two as
the engine's ``(k_pages, v_pages)`` pair.

Parity contract: ``reference_forward`` computes the plain non-absorbed
attention over whole sequences; the paged programs must match it
(tests/test_mla.py), and perfbench/references/latent_moe.py, which shares
nothing with this file, must match both (tests/test_joyai.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.models.llama import (
    COUNT_DECODE, COUNT_PREFILL, TRASH_PAGE, _add, _draw, _embed, _norm,
    _replicate, _times, rms_norm, rope_spec,
)
# the regions this family opens (jax.named_scope: metadata only)
from dynamo_tpu.models.regions import (
    SCOPE_BURST, SCOPE_HEAD, SCOPE_INDEX, SCOPE_KV, SCOPE_LATENT_ABSORB,
    SCOPE_LATENT_KV, SCOPE_LATENT_Q, SCOPE_MLP, SCOPE_MOE_COUNT,
    SCOPE_MOE_SHARED, SCOPE_OUT, SCOPE_SAMPLER,
)
from dynamo_tpu.ops.attention import (
    latent_decode_schedule, latent_decode_update_attention,
    latent_prefill_attention, pad_heads, pool_head_dim,
)
from dynamo_tpu.ops.quant import (
    QuantPool,
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


def _half_split(spec: ModelSpec, w: jax.Array, lead: int) -> jax.Array:
    """Columns ``[..., lead + dr]`` of a projection whose trailing ``dr``
    rope columns are published pair-interleaved, brought to the half-split
    order ``rope_spec`` rotates (models/loader._deinterleave_rope_cols is
    the same permutation at load)."""
    if not spec.rope_interleave:
        return w
    dr = spec.qk_rope_head_dim
    perm = jnp.concatenate([jnp.arange(0, dr, 2), jnp.arange(1, dr, 2)])
    return jnp.concatenate([w[..., :lead], w[..., lead:][..., perm]], axis=-1)


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random weights in a STATED order, so that a reference that shares
    no code can draw the same (perfbench/references/latent_moe.py): the key
    split in ``4 + 8 x layers`` (``4 + 17 x layers`` for shortcut-connected
    double layers: the first sub-layer's eight, the second's eight, then
    the expert layer's one); embedding, head, then a layer ``wq_a``
    (``wq`` without a query rank), ``wq_b``, ``w_kv_a``, ``w_kv_b``, ``wo``
    and its MLP (dense: gate, up, down; experts: one key for
    ``moe.init_moe_layer``, one split in three for the shared expert's
    gate, up, down). ``N(0, 1 / fan_in)``, embedding ``N(0, 0.02^2)``, norm
    gains 1, rounded to the model's dtype. Matrices are drawn in the
    PUBLISHED layout (``w_kv_b`` one ``[dc, H x (dn + dv)]`` matrix, rope
    columns as ``rope_interleave`` says) and then brought to the program's:
    ``w_uk`` / ``w_uv`` a head, rope columns half-split."""
    assert spec.kv_lora_rank > 0, "not an MLA spec"
    dtype = jnp.dtype(spec.dtype)
    d = spec.hidden_size
    per_layer = 17 if spec.shortcut_moe else 8
    keys = iter(jax.random.split(key, 4 + spec.num_layers * per_layer))

    def dense(k, shape, scale=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[0])
        return _draw(k, scale, shape=shape, dtype=dtype)

    def sub_layer(k_qa, k_qb, k_kva, k_kvb, k_o, k1, k2, k3) -> Params:
        """A latent attention and a dense MLP with their two norms."""
        return {
            "attn_norm": jnp.ones((d,), dtype),
            "mlp_norm": jnp.ones((d,), dtype),
            **init_latent_mixer(spec, dense, k_qa, k_qb, k_kva, k_kvb, k_o),
            "w_gate": dense(k1, (d, spec.intermediate_size)),
            "w_up": dense(k2, (d, spec.intermediate_size)),
            "w_down": dense(k3, (spec.intermediate_size, d)),
        }

    params: Params = {
        "embed": dense(next(keys), (spec.vocab_size, d), scale=0.02),
        "final_norm": jnp.ones((d,), dtype),
        "layers": [],
    }
    head_key = next(keys)
    if not spec.tie_embeddings:
        params["lm_head"] = dense(head_key, (d, spec.vocab_size))
    for li in range(spec.num_layers):
        if spec.shortcut_moe:
            from dynamo_tpu.models import moe

            subs = [
                sub_layer(*(next(keys) for _ in range(8))) for _ in range(2)
            ]
            params["layers"].append(
                {"sub": subs, "moe": moe.init_moe_layer(spec, next(keys))})
            continue
        k_qa, k_qb, k_kva, k_kvb, k_o, k1, k2, k3 = (
            next(keys) for _ in range(8)
        )
        if not (spec.num_experts and li >= spec.first_k_dense):
            params["layers"].append(
                sub_layer(k_qa, k_qb, k_kva, k_kvb, k_o, k1, k2, k3))
            continue
        from dynamo_tpu.models import moe

        layer: Params = {
            "attn_norm": jnp.ones((d,), dtype),
            "mlp_norm": jnp.ones((d,), dtype),
            **init_latent_mixer(spec, dense, k_qa, k_qb, k_kva, k_kvb, k_o),
            "moe": moe.init_moe_layer(spec, k1),
        }
        if spec.n_shared_experts:
            f = spec.moe_intermediate_size * spec.n_shared_experts
            kg, ku, kd = jax.random.split(k2, 3)
            layer["shared"] = {
                "w_gate": dense(kg, (d, f)),
                "w_up": dense(ku, (d, f)),
                "w_down": dense(kd, (f, d)),
            }
        params["layers"].append(layer)
    return params


def init_latent_mixer(
    spec: ModelSpec, dense, k_qa, k_qb, k_kva, k_kvb, k_o, k_gate=None,
) -> Params:
    """A latent layer's mixer weights on the given keys, drawn in the
    published layout and brought to the program's (``init_params``): what
    this family's every layer holds, and a latent KIND's layer of a model
    that lists its kinds (llama.init_params). ``k_gate``: the key of the
    gate by head ``[d, H]`` (``LayerKind.head_gate``), where the kind has
    one."""
    dtype = jnp.dtype(spec.dtype)
    d, H = spec.hidden_size, spec.num_heads
    dn, dr, dv = spec.qk_nope_head_dim, spec.qk_rope_head_dim, spec.v_head_dim
    dc = spec.kv_lora_rank
    # behind a rank whose output the model scales by sqrt(d / rank)
    # (``mla_scale_*``), an up-projection is drawn with the model's WIDTH
    # as its fan-in: the scalar then brings its output back to unit
    # variance, which is what it is for. Drawn at 1 / rank the queries and
    # keys are 2 and 3.5 times too large, the scores' spread 7 times, and
    # the softmax a hard maximum over positions that bfloat16 rounding
    # flips (PERF.md section 6, PR 49)
    up_q = 1.0 / jnp.sqrt(d) if spec.mla_scale_q_lora else None
    up_kv = 1.0 / jnp.sqrt(d) if spec.mla_scale_kv_lora else None
    kv_b = dense(k_kvb, (dc, H * (dn + dv)), up_kv).reshape(dc, H, dn + dv)
    layer: Params = {
        "w_kv_a": _half_split(spec, dense(k_kva, (d, dc + dr)), dc),
        "kv_norm": jnp.ones((dc,), dtype),
        "w_uk": kv_b[..., :dn].transpose(1, 0, 2),  # [H, dc, dn]
        "w_uv": kv_b[..., dn:].transpose(1, 0, 2),  # [H, dc, dv]
        "wo": dense(k_o, (H * dv, d)),
    }
    q_in = spec.q_lora_rank or d
    wq = _half_split(
        spec,
        dense(k_qb if spec.q_lora_rank else k_qa,
              (q_in, H * (dn + dr)), up_q).reshape(q_in, H, dn + dr),
        dn,
    ).reshape(q_in, H * (dn + dr))
    if spec.q_lora_rank:
        layer["wq_a"] = dense(k_qa, (d, spec.q_lora_rank))
        layer["q_norm"] = jnp.ones((spec.q_lora_rank,), dtype)
        layer["wq_b"] = wq
    else:
        layer["wq"] = wq
    if k_gate is not None:
        layer["w_gate_head"] = dense(k_gate, (d, H))
    return layer


def init_cache(
    spec: ModelSpec, num_pages: int, page_size: int, dtype=None,
    kv_dtype: str = "bf16",
) -> jax.Array:
    """Latent cache [L, num_pages, page_size, D] (page 0 = trash). ONE
    array: MLA has no separate K and V pools. ``D`` is the row's ``d_c +
    d_r`` values, rounded up to the lane tile where the decode kernel runs
    compiled (``pool_head_dim``: writers pad with zeros, which add 0 to
    every score; the value lanes are the leading ``d_c``).
    ``kv_dtype="fp8"`` allocates a QuantPool (ops/quant.py), unpadded (the
    kernel does not read it), with one bf16 scale per (layer, page, ROW):
    with no head axis the row is the natural scale unit, appends never
    requantize their neighbors, and the finer granularity keeps the
    attention drift inside the tolerance goldens (a single per-page scale
    measured ~2x the greedy-token disagreement on CPU). A model of double
    layers (``spec.sub_layers`` 2) gets a TUPLE of such pools, one a
    sub-layer, under the same page ids (``sub_pools``)."""
    dtype = dtype or jnp.dtype(spec.dtype)
    lead = (spec.num_layers, num_pages, page_size)

    def pool():
        if kv_dtype == "fp8":
            return init_quant_pool(lead + (latent_dim(spec),), 3)
        return jnp.zeros(lead + (pool_head_dim(latent_dim(spec))[1],), dtype)

    if spec.sub_layers == 1:
        return pool()
    return tuple(pool() for _ in range(spec.sub_layers))


def sub_pools(cache) -> tuple:
    """The cache as its pools, one a sub-layer of a decoder layer: the
    one pool of a model of plain layers, or the tuple ``init_cache`` made
    (a ``QuantPool`` is a named tuple and ONE pool)."""
    return cache if type(cache) is tuple else (cache,)


def init_counts(spec: ModelSpec) -> jax.Array:
    """The expert layers' counters, zeroed: ``[L, 2, n_held + 3]`` int32
    by layer and phase (``llama.COUNT_PREFILL`` / ``COUNT_DECODE``), as
    ``llama.KindPools.counts``; ``[L, 2, 0]`` for a model without
    experts. Identity experts add two behind the held experts' sizes
    (``moe.moe_mlp``)."""
    n = spec.experts_here[0] + 3 if spec.num_experts else 0
    if spec.zero_experts:
        n += 2
    return jnp.zeros((spec.num_layers, 2, n), jnp.int32)


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
    tiles = pad_heads(tiles, cache.shape[-1])
    return cache.at[li, safe_pg].set(tiles.astype(cache.dtype))


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

    def mixer() -> Params:
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
        return layer

    dense_mlp = {
        "w_gate": ns(None, "tp"), "w_up": ns(None, "tp"),
        "w_down": ns("tp", None),
    }
    layers = []
    for li in range(spec.num_layers):
        if spec.shortcut_moe:
            from dynamo_tpu.models import moe

            layers.append({
                "sub": [{**mixer(), **dense_mlp} for _ in range(2)],
                "moe": moe.moe_layer_shardings(mesh, spec),
            })
            continue
        layer = mixer()
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
            layer.update(dense_mlp)
        layers.append(layer)
    out = {
        "embed": ns(None, "tp"),
        "final_norm": ns(),
        "layers": layers,
    }
    if not spec.tie_embeddings:
        out["lm_head"] = ns(None, "tp")
    return out


def cache_shardings(mesh: Mesh, kv_dtype: str = "bf16", sub_layers: int = 1):
    """Latent cache [L, pages, page, d_c + d_r]: REPLICATED across the
    mesh. There is no head axis to split — the latent row is shared by
    every head — and at ~14x compression vs GQA the duplication is the
    cheap side of the trade (each rank attends against its local copy
    with zero gather collectives in the decode hot loop). Quantized
    caches replicate both leaves; a pool a sub-layer, each the same."""
    s = NamedSharding(mesh, P())
    pool = QuantPool(s, s) if kv_dtype == "fp8" else s
    return pool if sub_layers == 1 else (pool,) * sub_layers


# --------------------------------------------------------------- pieces


@jax.named_scope(SCOPE_LATENT_Q)
def _q_heads(spec: ModelSpec, lp: Params, h: jax.Array, positions) -> tuple:
    """h [..., d] at ``positions`` [...] -> (q_nope [..., H, dn], q_rope
    [..., H, dr]) with RoPE applied."""
    H, dn, dr = spec.num_heads, spec.qk_nope_head_dim, spec.qk_rope_head_dim
    if spec.q_lora_rank:
        q = rms_norm(h @ lp["wq_a"], lp["q_norm"], spec.rms_eps) @ lp["wq_b"]
    else:
        q = h @ lp["wq"]
    if spec.mla_scale_q_lora:
        q = _times(q, (spec.hidden_size / spec.q_lora_rank) ** 0.5)
    q = q.reshape(*h.shape[:-1], H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    return q_nope, rope_spec(spec, q_rope, positions)


@jax.named_scope(SCOPE_LATENT_KV)
def _latent_row(spec: ModelSpec, lp: Params, h: jax.Array, positions):
    """-> cache rows [..., d_c + d_r]: normalized latent + roped shared
    key."""
    dc = spec.kv_lora_rank
    kv_a = h @ lp["w_kv_a"]
    c = rms_norm(kv_a[..., :dc], lp["kv_norm"], spec.rms_eps)
    if spec.mla_scale_kv_lora:
        # in float32: sqrt(12) rounded to bfloat16 first is 0.13% off on
        # every key and value
        c = (c.astype(jnp.float32) * (spec.hidden_size / dc) ** 0.5).astype(
            c.dtype)
    k_r = rope_spec(spec, kv_a[..., None, dc:], positions)[..., 0, :]
    return jnp.concatenate([c, k_r], axis=-1)


@jax.named_scope(SCOPE_OUT)
def _o_proj(lp: Params, attn: jax.Array, h: jax.Array) -> jax.Array:
    """The heads' outputs ``attn`` [..., H, dv] (or already flat) through
    the output projection, in the type of the layer's normed input ``h``
    [..., d] (the residual stream's); where the layer gates its attention
    output by HEAD (``w_gate_head`` [d, H]), with a sigmoid of ``h``
    first."""
    attn = attn.reshape(*h.shape[:-1], -1).astype(h.dtype)
    if "w_gate_head" in lp:
        gate = jax.nn.sigmoid((h @ lp["w_gate_head"]).astype(jnp.float32))
        attn = (
            attn.reshape(*gate.shape, -1) * gate.astype(h.dtype)[..., None]
        ).reshape(attn.shape)
    return attn @ lp["wo"]


# A layer's two halves as jits of their own inside the programs: the
# layers of a kind then share ONE trace and one lowered function (the
# expert layer's three grouped-product kernels are traced once a program,
# not once a layer), where plain Python traced each layer anew: seconds
# of every process's set-up, warm or cold (PERF.md section 6, PR 32).
_ATTN_IN = ("wq", "wq_a", "q_norm", "wq_b", "w_kv_a", "kv_norm")
_MLP = ("moe", "shared", "w_gate", "w_up", "w_down")


@partial(jax.jit, static_argnums=(0,))
def _attn_inputs_jit(spec: ModelSpec, ap: Params, h, positions):
    q_nope, q_rope = _q_heads(spec, ap, h, positions)
    return q_nope, q_rope, _latent_row(spec, ap, h, positions)


def _attn_inputs(spec: ModelSpec, lp: Params, h: jax.Array, positions):
    """h [..., d] -> (q_nope, q_rope, the tokens' new latent rows)."""
    return _attn_inputs_jit(
        spec, {k: lp[k] for k in _ATTN_IN if k in lp}, h, positions
    )


def _dense_attention(
    spec: ModelSpec,
    lp: Params,
    q_nope: jax.Array,  # [T, H, dn]
    q_rope: jax.Array,  # [T, H, dr]
    rows: jax.Array,  # [S, d_c + d_r] the sequence's own latent rows
    mask: jax.Array,  # [T, S] bool
) -> jax.Array:
    """Plain NON-absorbed attention over a whole sequence's rows, per-head
    K and V materialized, float32 -> [T, H, dv]. The ground truth's
    attention (``reference_forward``) and the cacheless embedding pass's;
    no paged program comes through here."""
    dc = spec.kv_lora_rank
    f32 = jnp.float32
    c, k_r = rows[:, :dc].astype(f32), rows[:, dc:].astype(f32)
    k_nope = jnp.einsum("sc,hcn->shn", c, lp["w_uk"].astype(f32))
    v = jnp.einsum("sc,hcv->shv", c, lp["w_uv"].astype(f32))
    scores = (
        jnp.einsum("thn,shn->ths", q_nope.astype(f32), k_nope)
        + jnp.einsum("thr,sr->ths", q_rope.astype(f32), k_r)
    ) * jnp.asarray(softmax_scale(spec), f32)
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    return jnp.einsum("ths,shv->thv", jax.nn.softmax(scores, axis=-1), v)


@jax.named_scope(SCOPE_MLP)
def _ffn(
    spec: ModelSpec, li: int, lp: Params, x: jax.Array,
    mesh: Mesh | None = None, counted: jax.Array | None = None,
):
    """The layer's MLP over [T, d] rows: dense, or the routed experts held
    here plus the shared expert, which every chip of an ep deployment
    computes whole. With ``counted`` ([T] bool: the real tokens) an expert
    layer returns (y, its counters' row): see moe.moe_mlp."""
    if "moe" not in lp:
        return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
    from dynamo_tpu.models import moe

    out = moe.moe_mlp(spec, lp["moe"], x, mesh=mesh, counted=counted)
    out, row = out if counted is not None else (out, None)
    if "shared" in lp:
        sh = lp["shared"]
        with jax.named_scope(SCOPE_MOE_SHARED):
            out = out + (
                jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
            ) @ sh["w_down"]
    return out if counted is None else (out, row)


@partial(jax.jit, static_argnums=(0,),
         static_argnames=("phase", "mesh", "counting"))
def _ffn_counting_jit(spec, li, mp, x, counts, counted, *, phase, mesh,
                      counting):
    if not counting:
        return _ffn(spec, li, mp, x, mesh), counts
    y, row = _ffn(spec, li, mp, x, mesh, counted=counted)
    with jax.named_scope(SCOPE_MOE_COUNT):
        step = jnp.ones((1,), jnp.int32)
        return y, counts.at[li, phase].add(jnp.concatenate([row, step]))


def _ffn_counting(
    spec: ModelSpec, li: int, lp: Params, x: jax.Array, counts,
    phase: int, counted: jax.Array, mesh: Mesh | None,
):
    """``_ffn`` over [T, d] rows, adding an expert layer's counters to
    ``counts`` where the caller keeps them. Returns (y, counts)."""
    counting = (
        counts is not None and "moe" in lp and counts.shape[-1] > 0
    )
    return _ffn_counting_jit(
        spec, li, {k: lp[k] for k in _MLP if k in lp}, x, counts, counted,
        phase=phase, mesh=mesh, counting=counting,
    )


@jax.named_scope(SCOPE_KV)
def _seq_attention(
    spec: ModelSpec, li: int, lp: Params, q_nope, q_rope, new_rows, cache,
    block_tables, start_pos, kv_len, mesh: Mesh | None = None,
):
    """Attention of N sequences' new queries (member n's at ``start_pos[n]
    + arange(T)``; their own rows already written to ``cache``) over
    their paged latents, each over its own table -> [N, T, H * dv]: every
    prefill program and the verify come through here, a single prompt or
    chunk as a pack of one. ``ops/attention.latent_prefill_attention``
    chooses the kernel or the XLA walk; a quantized pool gets the call's
    exact rows laid over its read-back (the XLA mirror of the decode
    kernel's analytic merge)."""
    N, T = q_nope.shape[:2]
    return latent_prefill_attention(
        q_nope, q_rope, cache, li, lp["w_uk"], lp["w_uv"], block_tables,
        start_pos, kv_len, scale=softmax_scale(spec),
        new_rows=new_rows if is_quant(cache) else None, mesh=mesh,
    ).reshape(N, T, -1)


def prefill_layer(
    spec: ModelSpec, li, lp: Params, h: jax.Array, positions, cache,
    safe_pg, valid_tok, block_tables, start_pos, kv_len,
    mesh: Mesh | None = None,
):
    """A latent layer's mixer over N sequences' new tokens (h [N, T, d] at
    ``positions`` [N, T]; ``safe_pg`` [N * T / page] the pages their rows
    land on, ``valid_tok`` [N * T / page, page] the real ones): the rows
    written to layer ``li`` of ``cache``, attention over each sequence's
    paged latents, the output projection. ``cache`` is this family's, or
    the pool of a latent KIND (llama.KindPools) with ``li`` the layer's
    index inside it. Returns (out [N, T, d], cache)."""
    q_nope, q_rope, new_rows = _attn_inputs(spec, lp, h, positions)
    with jax.named_scope(SCOPE_KV):
        cache = _set_latent_tiles(
            cache, li, safe_pg, new_rows.reshape(*valid_tok.shape, -1),
            valid_tok,
        )
    attn = _seq_attention(
        spec, li, lp, q_nope, q_rope, new_rows, cache, block_tables,
        start_pos, kv_len, mesh,
    )
    return _o_proj(lp, attn, h), cache


def decode_layer(
    spec: ModelSpec, li, lp: Params, h: jax.Array, positions, cache,
    block_tables, seq_lens, safe_page, offset, schedule,
    mesh: Mesh | None = None,
):
    """A latent layer's decode step over the slots (h [B, d]): the new
    rows appended to layer ``li`` of ``cache`` (this family's, or a latent
    kind's pool) and absorbed attention over the live pages in one call.
    ``schedule``: ``latent_decode_schedule``'s, made once a step. Returns
    (out [B, d], cache)."""
    q_nope, q_rope, new_rows = _attn_inputs(spec, lp, h, positions)
    # absorb W_uk: q_lat[b, h] = q_nope[b, h] W_uk[h]^T
    with jax.named_scope(SCOPE_LATENT_ABSORB):
        q_lat = jnp.einsum(
            "bhn,hcn->bhc", q_nope, lp["w_uk"],
            preferred_element_type=jnp.float32,
        )
    with jax.named_scope(SCOPE_KV):
        o_lat, cache = latent_decode_update_attention(
            q_lat, q_rope, cache, new_rows, block_tables, seq_lens,
            safe_page, offset, layer=li, scale=softmax_scale(spec),
            mesh=mesh, schedule=schedule,
        )
    with jax.named_scope(SCOPE_LATENT_ABSORB):
        attn = jnp.einsum(
            "bhc,hcv->bhv", o_lat.astype(lp["w_uv"].dtype), lp["w_uv"],
            preferred_element_type=jnp.float32,
        )
    return _o_proj(lp, attn, h), cache


def whole_layer(spec: ModelSpec, lp: Params, h: jax.Array, positions, mask):
    """A latent layer's mixer over one whole sequence with no cache (h [T,
    d]; ``mask`` [T, T] bool): plain non-absorbed attention."""
    q_nope, q_rope = _q_heads(spec, lp, h, positions)
    rows = _latent_row(spec, lp, h, positions)
    attn = _dense_attention(spec, lp, q_nope, q_rope, rows, mask)
    return _o_proj(lp, attn, h)


def _layer(
    spec: ModelSpec, li: int, lp: Params, x: jax.Array, mix, cache, counts,
    phase: int, counted, mesh: Mesh | None,
):
    """Decoder layer ``li`` over the residual stream ``x`` [..., d]: THE
    place a layer's data flow is written, for every program. ``mix(li, ap,
    h, pool) -> (out, pool)`` is the calling program's latent mixer over
    the normed input ``h`` (``ap``: the weights of one attention; ``pool``:
    that attention's pool of the cache, None for a cacheless pass);
    ``counted`` [rows] bool the real rows of ``x`` flattened, for the
    expert layer's counters. Returns (x, cache, counts).

    A plain layer: attention, then its MLP (dense, or experts). A
    shortcut-connected double layer (``lp["sub"]``):

        x1 = x  + MLA_0(norm(x));   u = norm(x1);   m = MoE(u)
        x2 = x1 + FFN_0(u)
        x3 = x2 + MLA_1(norm(x2))
        x' = x3 + FFN_1(norm(x3)) + m

    ``m`` is made from the first attention's output and is not read until
    the end: nothing of the second attention or either dense FFN depends
    on it, which is what lets a deployment hide the experts' exchange
    behind them and lets the compiler order the grouped products beside
    the dense ones."""

    def ffn(mp, hh, counts):
        y, counts = _ffn_counting(
            spec, li, mp, hh.reshape(-1, hh.shape[-1]), counts, phase,
            counted, mesh,
        )
        return y.reshape(hh.shape), counts

    if "sub" not in lp:
        h = _norm(spec, x, lp, "attn_norm")
        out, cache = mix(li, lp, h, cache)
        x = _add(x, out)
        y, counts = ffn(lp, _norm(spec, x, lp, "mlp_norm"), counts)
        return _add(x, y), cache, counts
    first, second = lp["sub"]
    pools = (None, None) if cache is None else sub_pools(cache)
    out, pool0 = mix(li, first, _norm(spec, x, first, "attn_norm"),
                     pools[0])
    x = _add(x, out)
    u = _norm(spec, x, first, "mlp_norm")
    m, counts = ffn({"moe": lp["moe"]}, u, counts)  # the shortcut
    y, counts = ffn(first, u, counts)
    x = _add(x, y)
    out, pool1 = mix(li, second, _norm(spec, x, second, "attn_norm"),
                     pools[1])
    x = _add(x, out)
    y, counts = ffn(second, _norm(spec, x, second, "mlp_norm"), counts)
    x = _add(_add(x, y), m)
    return x, None if cache is None else (pool0, pool1), counts


def _with_counts(out: tuple, counts):
    """A program's results, the counters appended where the caller passed
    them."""
    return out if counts is None else out + (counts,)


# ------------------------------------------------------------- reference


def reference_forward(
    spec: ModelSpec, params: Params, tokens: jax.Array
) -> jax.Array:
    """Plain NON-absorbed MLA forward over a whole sequence, no cache: the
    numerical ground truth the paged programs must match. Shares
    ``_q_heads``, ``_latent_row`` and ``_ffn`` with them."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = _embed(params, tokens)
    mask = positions[:, None] >= positions[None, :]

    def mix(li, ap, h, pool):
        return whole_layer(spec, ap, h, positions, mask), pool

    for li, lp in enumerate(params["layers"]):
        x, _, _ = _layer(spec, li, lp, x, mix, None, None, 0, None, None)
    return _logits_all(spec, params, x)


@jax.named_scope(SCOPE_HEAD)
def _logits_all(spec, params, x):
    xn = rms_norm(x, params["final_norm"], spec.rms_eps)
    head = params["embed"].T if spec.tie_embeddings else params["lm_head"]
    return (xn @ head).astype(jnp.float32)


# ----------------------------------------------------------------- paged


def prefill_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad]
    block_table: jax.Array,  # [max_pages_per_seq]
    start_pos: jax.Array,  # scalar (page-aligned)
    cache: jax.Array,  # [L, pages, page, D] (donated)
    num_tokens: jax.Array,  # scalar
    mesh: Mesh | None = None,  # static: replicate logits across the mesh
    counts: jax.Array | None = None,  # the expert counters (donated)
):
    """One prompt, or one chunk of it at ``start_pos``; writes latent rows
    page-granularly; returns (last_logits, cache[, counts]). Mirrors
    llama.prefill_forward_impl."""
    T = tokens.shape[0]
    page_size = sub_pools(cache)[0].shape[2]
    n_pg = T // page_size
    with jax.named_scope(SCOPE_INDEX):
        idx = jnp.arange(T)
        positions = start_pos + idx
        page_starts = start_pos + jnp.arange(n_pg) * page_size
        pg_idx = block_table[page_starts // page_size]
        safe_pg = jnp.where(
            page_starts < start_pos + num_tokens, pg_idx, TRASH_PAGE
        )
        real = idx < num_tokens
    x = _embed(params, tokens)
    with jax.named_scope(SCOPE_INDEX):
        kv_len = start_pos + num_tokens

    def mix(li, ap, h, pool):
        q_nope, q_rope, new_rows = _attn_inputs(spec, ap, h, positions)
        with jax.named_scope(SCOPE_KV):
            pool = _set_latent_tiles(
                pool, li, safe_pg,
                new_rows.reshape(n_pg, page_size, -1),
                real.reshape(n_pg, page_size),
            )
        attn = _seq_attention(
            spec, li, ap, q_nope[None], q_rope[None], new_rows[None], pool,
            block_table[None], start_pos[None], kv_len[None], mesh,
        )[0]
        return _o_proj(ap, attn, h), pool

    for li, lp in enumerate(params["layers"]):
        x, cache, counts = _layer(
            spec, li, lp, x, mix, cache, counts, COUNT_PREFILL, real, mesh)
    with jax.named_scope(SCOPE_HEAD):
        last = jnp.clip(num_tokens - 1, 0, T - 1)
        logits = _logits_all(spec, params, x)[last]
    logits = _replicate(logits, mesh)
    return _with_counts((logits, cache), counts)


prefill_forward = jax.jit(
    prefill_forward_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5,),
    donate_argnames=("counts",),
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
    counts: jax.Array | None = None,
):
    """N prompts in ONE dispatch: MLA's packed-prefill admission path
    (mirrors llama.prefill_forward_batch_impl: matmuls batch over
    [N, T, d], the latent write is one page-tile scatter, the walk runs a
    prompt over its own table). Returns (last_logits [N, V], cache[,
    counts])."""
    N, T = tokens.shape
    page_size = sub_pools(cache)[0].shape[2]
    n_pg = T // page_size
    with jax.named_scope(SCOPE_INDEX):
        idx = jnp.arange(T)
        positions = start_pos[:, None] + idx[None, :]  # [N, T]
        page_starts = start_pos[:, None] + (
            jnp.arange(n_pg) * page_size
        )[None, :]  # [N, n_pg]
        pg_idx_raw = jnp.take_along_axis(
            block_tables, page_starts // page_size, axis=1
        )
        valid_pg = page_starts < (start_pos + num_tokens)[:, None]
        safe_pg = jnp.where(
            valid_pg, pg_idx_raw, TRASH_PAGE).reshape(N * n_pg)
        real = idx[None, :] < num_tokens[:, None]  # [N, T]

    x = _embed(params, tokens)  # [N, T, d]
    with jax.named_scope(SCOPE_INDEX):
        kv_len = start_pos + num_tokens  # [N]

    def mix(li, ap, h, pool):
        return prefill_layer(
            spec, li, ap, h, positions, pool, safe_pg,
            real.reshape(N * n_pg, page_size), block_tables, start_pos,
            kv_len, mesh,
        )

    for li, lp in enumerate(params["layers"]):
        x, cache, counts = _layer(
            spec, li, lp, x, mix, cache, counts, COUNT_PREFILL,
            real.reshape(N * T), mesh,
        )

    with jax.named_scope(SCOPE_HEAD):
        last = jnp.clip(num_tokens - 1, 0, T - 1)  # [N]
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = _logits_all(spec, params, x_last)
    logits = _replicate(logits, mesh)
    return _with_counts((logits, cache), counts)


prefill_forward_batch = jax.jit(
    prefill_forward_batch_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5,),
    donate_argnames=("counts",),
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
    counts: jax.Array | None = None,
):
    """Speculative-verify forward for MLA (mirrors llama.verify_forward):
    token-granular latent writes (a verify starts mid-page, so the
    page-tile invariant of prefill does not hold), the walk of the
    prefill programs over each row's table, and the target's greedy
    argmax at all W positions, returned as [N, W] int32 so only token ids
    cross to the host. Returns (targets, cache[, counts])."""
    N, W = tokens.shape
    page_size = sub_pools(cache)[0].shape[2]
    idx = jnp.arange(W)
    positions = start_pos[:, None] + idx[None, :]  # [N, W]
    valid = idx[None, :] < num_tokens[:, None]
    pg_idx_raw = jnp.take_along_axis(
        block_tables, positions // page_size, axis=1
    )
    safe_pg = jnp.where(valid, pg_idx_raw, TRASH_PAGE).reshape(N * W)
    offs = (positions % page_size).reshape(N * W)

    x = _embed(params, tokens)  # [N, W, d]
    kv_len = start_pos + num_tokens  # [N]

    def mix(li, ap, h, pool):
        q_nope, q_rope, new_rows = _attn_inputs(spec, ap, h, positions)
        flat = new_rows.reshape(N * W, -1)
        with jax.named_scope(SCOPE_KV):
            if is_quant(pool):
                # per-row scales make this a plain scatter: every (page,
                # offset) slot owns its scale, so same-page siblings never
                # clash (unlike the GQA page RMW)
                pool = quant_append_rows(pool, flat, safe_pg, offs, li)
            else:
                pool = pool.at[li, safe_pg, offs].set(
                    pad_heads(flat, pool.shape[-1]).astype(pool.dtype)
                )
        attn = _seq_attention(
            spec, li, ap, q_nope, q_rope, new_rows, pool, block_tables,
            start_pos, kv_len, mesh,
        )
        return _o_proj(ap, attn, h), pool

    for li, lp in enumerate(params["layers"]):
        x, cache, counts = _layer(
            spec, li, lp, x, mix, cache, counts, COUNT_PREFILL,
            valid.reshape(N * W), mesh,
        )

    logits = _logits_all(spec, params, x)  # [N, W, V]
    if allowed is not None:
        # guided x spec: masked verify logits keep the correction token
        # on-grammar even when every draft is rejected (llama mirror)
        logits = jnp.where(allowed, logits, NEG_INF)
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _with_counts((_replicate(targets, mesh), cache), counts)


verify_forward = jax.jit(
    verify_forward_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5,),
    donate_argnames=("counts",),
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
    counts: jax.Array | None = None,
):
    """One decode step (absorbed latent attention over the sequences' live
    pages); returns (logits, cache[, counts])."""
    page_size = sub_pools(cache)[0].shape[2]
    with jax.named_scope(SCOPE_INDEX):
        positions = seq_lens - 1
        page_idx = jnp.take_along_axis(
            block_tables, (positions // page_size)[:, None], axis=1
        )[:, 0]
        safe_page = jnp.where(active, page_idx, TRASH_PAGE)
        offset = positions % page_size
    # the kernel's schedule follows the lengths alone: once a step
    # (and the pools of a double layer's sub-layers share their shape)
    schedule = latent_decode_schedule(
        sub_pools(cache)[0], block_tables, seq_lens, mesh)
    x = _embed(params, tokens)

    def mix(li, ap, h, pool):
        return decode_layer(
            spec, li, ap, h, positions, pool, block_tables, seq_lens,
            safe_page, offset, schedule, mesh,
        )

    for li, lp in enumerate(params["layers"]):
        x, cache, counts = _layer(
            spec, li, lp, x, mix, cache, counts, COUNT_DECODE, active, mesh)
    logits = _replicate(_logits_all(spec, params, x), mesh)
    return _with_counts((logits, cache), counts)


decode_forward = jax.jit(
    decode_forward_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5,),
    donate_argnames=("counts",),
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
    counts: jax.Array | None = None,
):
    """Fused multi-step MLA decode + on-device sampling (the serving hot
    loop; mirrors llama.decode_steps for the GQA family, including the
    logprob surface). Returns (out[, logprobs, top ids, top values],
    cache[, counts])."""
    from dynamo_tpu.engine.sampling import sample_tokens, token_logprobs

    B = tokens.shape[0]
    out0 = jnp.zeros((B, n_steps), jnp.int32)
    lp0 = jnp.zeros((B, n_steps), jnp.float32)
    ti0 = jnp.zeros((B, n_steps, max(n_logprobs, 1)), jnp.int32)
    tv0 = jnp.zeros((B, n_steps, max(n_logprobs, 1)), jnp.float32)

    def body(i, carry):
        toks, lens, cache, counts, out, lp, ti, tv = carry
        logits, cache, *rest = decode_forward_impl(
            spec, params, toks, block_tables, lens, cache, active,
            mesh=mesh, counts=counts,
        )
        counts = rest[0] if rest else None
        # as llama.decode_steps_impl: the carry is the burst's region, the
        # sampler's functions open theirs beneath it
        with jax.named_scope(SCOPE_BURST):
            if allowed is not None:
                with jax.named_scope(SCOPE_SAMPLER):
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
            lens = lens + active.astype(jnp.int32)
        return nxt, lens, cache, counts, out, lp, ti, tv

    _t, _l, cache, counts, out, lp, ti, tv = jax.lax.fori_loop(
        0, n_steps, body,
        (tokens, seq_lens, cache, counts, out0, lp0, ti0, tv0),
    )
    out = _replicate(out, mesh)
    if n_logprobs > 0:
        return _with_counts(
            (out, _replicate(lp, mesh), _replicate(ti, mesh),
             _replicate(tv, mesh), cache), counts)
    return _with_counts((out, cache), counts)


decode_steps = jax.jit(
    decode_steps_impl, static_argnums=(0,),
    static_argnames=("n_steps", "n_logprobs", "mesh"),
    # donate the latent cache: without this every MLA decode burst
    # COPIED the whole cache for its in-place page writes (the donation
    # audit in tests/test_donation.py caught exactly this)
    donate_argnums=(5,), donate_argnames=("counts",),
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
    x = _embed(params, tokens)
    mask2d = (positions[:, None] >= positions[None, :]) & (
        positions[None, :] < num_tokens
    )

    def mix(li, ap, h, pool):
        return whole_layer(spec, ap, h, positions, mask2d), pool

    for li, lp in enumerate(params["layers"]):
        x, _, _ = _layer(spec, li, lp, x, mix, None, None, 0, None, None)
    xn = rms_norm(x, params["final_norm"], spec.rms_eps).astype(jnp.float32)
    valid = (positions < num_tokens)[:, None].astype(jnp.float32)
    pooled = (xn * valid).sum(axis=0) / jnp.maximum(valid.sum(), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-9)


embed_forward = jax.jit(embed_forward_impl, static_argnums=(0,))
