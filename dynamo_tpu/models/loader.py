"""Checkpoint loading: HF-format safetensors -> the functional param pytree.

TPU-native counterpart of the reference's LocalModel build path
(lib/llm/src/local_model.rs:323 ``build``, hub.rs model fetch): given a
local model directory containing ``config.json`` + ``*.safetensors``, derive
the ModelSpec and materialize ``models/llama.py``-shaped params, cast to the
serving dtype and (optionally) placed with tensor-parallel shardings in one
pass — each tensor is read from the memory-mapped safetensors file, mapped,
and ``jax.device_put`` straight to its sharding, so host RAM never holds a
second full copy of the checkpoint.

Also provides ``save_params`` (params -> HF-format safetensors) so tests can
round-trip a generated checkpoint hermetically (no downloads in this
environment), and so converted checkpoints can be re-exported.

Weight-name mapping (HF LlamaForCausalLM / MixtralForCausalLM):

    model.embed_tokens.weight            -> embed            [V, d]
    model.norm.weight                    -> final_norm       [d]
    lm_head.weight                       -> lm_head (T)      [d, V]
    ...layers.{i}.input_layernorm        -> attn_norm        [d]
    ...layers.{i}.self_attn.{q,k,v,o}_proj.weight -> wq/wk/wv/wo (T)
    ...layers.{i}.post_attention_layernorm -> mlp_norm       [d]
    ...layers.{i}.mlp.{gate,up,down}_proj.weight -> w_gate/w_up/w_down (T)
    ...layers.{i}.block_sparse_moe.gate.weight -> moe.router (T, f32)
    ...layers.{i}.block_sparse_moe.experts.{e}.w{1,3,2}.weight
                                         -> moe.w_gate/w_up/w_down[e] (T)

HF stores linear weights as [out_features, in_features]; our forward is
``x @ W`` so every projection transposes on load. The RoPE convention
(half-split rotate, not interleaved) matches HF's exported llama weights,
so no permutation is needed.
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import ModelSpec

Params = dict[str, Any]

__all__ = [
    "spec_from_hf_config",
    "load_params",
    "save_params",
    "load_model_dir",
]


# ------------------------------------------------------------- spec <-> config


# the falcon_h1 family's scalar multipliers: config.json and ModelSpec give
# them the same names
_FALCON_SCALARS = (
    "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
    "attention_in_multiplier", "attention_out_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier",
)


def spec_from_hf_config(cfg: dict, name: str | None = None) -> ModelSpec:
    """Map an HF ``config.json`` dict to a ModelSpec (llama/mixtral family)."""
    model_type = cfg.get("model_type", "llama")
    heads = int(cfg["num_attention_heads"])
    hidden = int(cfg["hidden_size"])
    moe = {}
    n_experts = int(
        cfg.get("num_local_experts") or cfg.get("num_experts")
        or cfg.get("n_routed_experts") or 0
    )
    if model_type in ("mixtral", "qwen2_moe", "qwen3_moe", "gpt_oss") or n_experts:
        moe = dict(
            num_experts=n_experts,
            num_experts_per_token=int(
                cfg.get("num_experts_per_tok")
                or cfg.get("experts_per_token") or cfg.get("moe_topk") or 2
            ),
            moe_intermediate_size=int(
                cfg.get("moe_intermediate_size")
                or cfg.get("expert_ffn_hidden_size")
                or cfg["intermediate_size"]
            ),
        )
    # gpt-oss attention extras: sinks + per-layer sliding windows +
    # projection/expert biases + clamped swiglu (HF GptOssConfig)
    extras: dict = {}
    if model_type == "gpt_oss":
        n_layers = int(cfg["num_hidden_layers"])
        extras = dict(
            sliding_window=int(cfg.get("sliding_window") or 0),
            # HF GptOssConfig defaults to alternating sliding/full when
            # layer_types is absent — mirror that, not all-sliding
            layer_types=tuple(
                cfg.get("layer_types")
                or ("sliding_attention" if i % 2 == 0 else "full_attention"
                    for i in range(n_layers))
            ),
            attn_sinks=True,
            attn_bias=bool(cfg.get("attention_bias", True)),
            moe_bias=True,
            swiglu_limit=float(cfg.get("swiglu_limit") or 7.0),
            swiglu_alpha=1.702,
        )
    if model_type in ("deepseek_v2", "deepseek_v3", "joyai_llm_flash"):
        # DeepSeek MLA checkpoints store rope dims pair-interleaved
        # (HF DeepseekV3Config.rope_interleave defaults True)
        extras["rope_interleave"] = bool(cfg.get("rope_interleave", True))
        if n_experts:
            # V3 noaux_tc routing (HF DeepseekV3TopkRouter defaults)
            # fallbacks = the HF DeepseekV3Config class defaults, so a
            # minimal config.json routes exactly as transformers would
            extras.update(
                moe_scoring=str(cfg.get("scoring_func") or "sigmoid"),
                n_group=int(cfg.get("n_group") or 8),
                topk_group=int(cfg.get("topk_group") or 4),
                routed_scaling_factor=float(
                    cfg.get("routed_scaling_factor") or 2.5
                ),
                norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            )
    if model_type == "longcat_flash" or "zero_expert_num" in cfg:
        # LongCat-Flash: shortcut-connected double layers over latent
        # attention, identity experts behind the FFN experts in one
        # softmax router with a correction bias. The config names its
        # depth ``num_layers`` and its widths ``ffn_hidden_size`` /
        # ``expert_ffn_hidden_size``; it has no ``rope_interleave`` key
        # (the DeepSeek family's interleaved pairs are assumed) and no
        # ``norm_topk_prob`` (absent = false)
        if cfg.get("zero_expert_type", "identity") != "identity":
            raise NotImplementedError(
                f"longcat_flash: zero_expert_type {cfg['zero_expert_type']!r}")
        if str(cfg.get("attention_method", "MLA")).upper() != "MLA":
            raise NotImplementedError(
                f"longcat_flash: attention_method {cfg['attention_method']!r}")
        if cfg.get("router_bias"):
            raise NotImplementedError("longcat_flash: router_bias true")
        extras.update(
            shortcut_moe=True,
            zero_experts=int(cfg.get("zero_expert_num") or 0),
            moe_scoring="softmax_bias",
            routed_scaling_factor=float(
                cfg.get("routed_scaling_factor") or 1.0),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", False)),
            mla_scale_q_lora=bool(cfg.get("mla_scale_q_lora", False)),
            mla_scale_kv_lora=bool(cfg.get("mla_scale_kv_lora", False)),
            rope_interleave=bool(cfg.get("rope_interleave", True)),
        )
    if model_type == "solar_open2":
        # KDA layers among gated NoPE GQA layers (``gqa_layers`` lists the
        # softmax ones), sigmoid routing with a correction bias in every
        # layer, a shared expert
        from dynamo_tpu.engine.config import LayerKind

        lin = cfg["linear_attn_config"]
        gqa = set(cfg["gqa_layers"])
        if cfg.get("kda_use_full_proj"):
            raise NotImplementedError("kda_use_full_proj: low rank only")
        extras.update(
            layer_kinds=(
                LayerKind(int(cfg["num_key_value_heads"]),
                          float(cfg.get("rope_theta", 10000.0))),
                LayerKind(0, 0.0, mixer="kda"),
            ),
            layer_pattern=tuple(
                0 if i in gqa else 1
                for i in range(int(cfg["num_hidden_layers"]))
            ),
            use_rope=bool(cfg.get("use_rope", False)),
            attn_gate=bool(cfg.get("use_gqa_gate", False)),
            kda_heads=int(lin["num_heads"]), kda_head_dim=int(lin["head_dim"]),
            kda_conv=int(lin["short_conv_kernel_size"]),
            kda_neg_eigval=bool(cfg.get("kda_allow_neg_eigval", False)),
            moe_scoring="sigmoid",
            routed_scaling_factor=float(cfg.get("routed_scaling_factor") or 1.0),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
        )
    if model_type == "falcon_h1":
        # every layer a Mamba-2 (SSD) mixer and GQA attention in parallel
        # off one norm, a dense MLP, the family's fixed multipliers
        from dynamo_tpu.engine.config import LayerKind

        for key in ("attention_bias", "mlp_bias", "projectors_bias",
                    "mamba_proj_bias"):
            if cfg.get(key):
                raise NotImplementedError(f"falcon_h1: {key} true")
        if not cfg.get("mamba_rms_norm", True) or cfg.get(
                "mamba_norm_before_gate"):
            raise NotImplementedError(
                "falcon_h1: the gated norm after the gate only")
        if not cfg.get("mamba_conv_bias", True):
            raise NotImplementedError("falcon_h1: the taps carry a bias")
        n_layers = int(cfg["num_hidden_layers"])
        extras.update(
            layer_kinds=(LayerKind(
                int(cfg["num_key_value_heads"]),
                float(cfg.get("rope_theta", 1e11)), mixer="ssd"),),
            layer_pattern=(0,) * n_layers,
            ssm_heads=int(cfg["mamba_n_heads"]),
            ssm_head_dim=int(cfg["mamba_d_head"]),
            ssm_state=int(cfg["mamba_d_state"]),
            ssm_groups=int(cfg["mamba_n_groups"]),
            ssm_conv=int(cfg["mamba_d_conv"]),
            ssm_chunk=int(cfg.get("mamba_chunk_size") or 128),
            ssm_multipliers=tuple(cfg.get("ssm_multipliers") or ()),
            mlp_multipliers=tuple(cfg.get("mlp_multipliers") or ()),
            **{key: float(cfg.get(key, 1.0)) for key in _FALCON_SCALARS},
        )
    if model_type == "lfm2_moe":
        # gated short-convolution layers among QK-normed GQA layers
        # (``layer_types``), leading dense layers, then a sigmoid router
        # with a selection-only bias over experts without a shared one
        from dynamo_tpu.engine.config import LayerKind

        if cfg.get("conv_bias"):
            raise NotImplementedError("lfm2_moe: conv_bias true")
        if not cfg.get("use_expert_bias", True):
            raise NotImplementedError("lfm2_moe: use_expert_bias false")
        theta = float((cfg.get("rope_parameters") or {}).get("rope_theta")
                      or cfg.get("rope_theta") or 1e6)
        extras.update(
            layer_kinds=(LayerKind(int(cfg["num_key_value_heads"]), theta),
                         LayerKind(0, 0.0, mixer="conv")),
            layer_pattern=tuple(
                0 if t == "full_attention" else 1 for t in cfg["layer_types"]),
            conv_taps=int(cfg["conv_L_cache"]), qk_norm=True,
            rope_theta=theta, rms_eps=float(cfg.get("norm_eps", 1e-5)),
            # the config carries no key for the tie; the family ties
            tie_embeddings=bool(cfg.get("tie_word_embeddings", True)),
            first_k_dense=int(cfg.get("num_dense_layers") or 0),
            moe_scoring="sigmoid",
            routed_scaling_factor=float(
                cfg.get("routed_scaling_factor") or 1.0),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            moe_norm_eps=1e-6,  # the family's published code
        )
    if model_type == "afmoe":
        extras.update(_afmoe_spec(cfg, hidden))
    if model_type == "phi4flash":
        extras.update(_phi4flash_spec(cfg, hidden))
    # YaRN rope scaling (gpt-oss, DeepSeek-R1)
    rs = cfg.get("rope_scaling") or {}
    if (rs.get("rope_type") or rs.get("type")) == "yarn":
        extras.update(
            rope_scaling_factor=float(rs["factor"]),
            rope_orig_max_pos=int(
                rs.get("original_max_position_embeddings")
                or cfg.get("max_position_embeddings") or 4096
            ),
            rope_beta_fast=float(rs.get("beta_fast") or 32),
            rope_beta_slow=float(rs.get("beta_slow") or 1),
            rope_mscale=float(rs.get("mscale") or 0),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim") or 0),
            rope_truncate=bool(rs.get("truncate", True)),
        )
    kw = dict(
        name=name or cfg.get("_name_or_path") or model_type,
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=hidden,
        intermediate_size=int(
            cfg.get("intermediate_size") or cfg["ffn_hidden_size"]),
        num_layers=int(cfg.get("num_hidden_layers") or cfg["num_layers"]),
        num_heads=heads,
        num_kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=int(cfg.get("head_dim") or hidden // heads),
        rope_theta=float(cfg.get("rope_theta", 500000.0)),
        rms_eps=float(cfg.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        # transformers >= 4.56 writes "dtype"; older wrote "torch_dtype"
        dtype=(
            ckpt_dtype
            if (ckpt_dtype := cfg.get("dtype") or cfg.get("torch_dtype"))
            in ("bfloat16", "float32", "float16")
            else "bfloat16"
        ),
        # DeepSeek-family extras (0/absent on other models)
        n_shared_experts=int(cfg.get("n_shared_experts") or 0),
        first_k_dense=int(cfg.get("first_k_dense_replace") or 0),
        kv_lora_rank=int(cfg.get("kv_lora_rank") or 0),
        qk_nope_head_dim=int(cfg.get("qk_nope_head_dim") or 0),
        qk_rope_head_dim=int(cfg.get("qk_rope_head_dim") or 0),
        v_head_dim=int(cfg.get("v_head_dim") or 0),
        q_lora_rank=int(cfg.get("q_lora_rank") or 0),
        nextn_predict_layers=int(cfg.get("num_nextn_predict_layers") or 0),
    )
    # a family's own keys win over the llama-family defaults above
    return ModelSpec(**{**kw, **moe, **extras})


def _phi4flash_spec(cfg: dict, hidden: int) -> dict:
    """The ``phi4flash`` family's (Phi-4-mini-flash, SambaY) own fields
    from its config.json. ``mb_per_layer`` 2: every even layer is a
    Mamba-1 scan below the model's middle and a GMU above it; the odd
    layers are differential attention, over a ``sliding_window`` in the
    self-decoder but for its last layer (``half + 1``), whose pages the
    cross layers above read. No key gives Mamba-1's sizes: the family's
    ``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank`` ceil(d / 16).
    A cut names the published layers it keeps (``layers_kept``) beside
    the published depth (``published_layers``)."""
    import math

    from dynamo_tpu.engine.config import LayerKind

    if int(cfg.get("mb_per_layer", 2)) != 2:
        raise NotImplementedError("phi4flash: mb_per_layer 2 only")
    if cfg.get("mlp_bias") or cfg.get("lm_head_bias"):
        raise NotImplementedError("phi4flash: no MLP or head bias")
    n_layers = int(cfg["num_hidden_layers"])
    published = int(cfg.get("published_layers") or n_layers)
    kept = tuple(int(l) for l in cfg.get("layers_kept") or range(n_layers))
    half = published // 2
    if len(kept) != n_layers or half not in kept or half + 1 not in kept:
        raise ValueError(
            "phi4flash: layers_kept lists num_hidden_layers layers, the "
            f"memory layer {half} and the shared layer {half + 1} among them")
    nkv = int(cfg["num_key_value_heads"])
    window = int(cfg.get("sliding_window") or 0)
    kinds = (
        LayerKind(nkv, 0.0, window=window, differential=True),
        LayerKind(nkv, 0.0, differential=True),
        LayerKind(0, 0.0, mixer="scan"),
        LayerKind(0, 0.0, mixer="gmu"),
        LayerKind(nkv, 0.0, differential=True, reads=(1, 0)),
    )

    def kind_of(l: int) -> int:
        if l % 2 == 0:
            return 2 if l <= half else 3
        return 4 if l > half + 1 else 1 if l == half + 1 else 0

    return dict(
        layer_kinds=kinds, layer_pattern=tuple(kind_of(l) for l in kept),
        layer_ids=kept if kept != tuple(range(n_layers)) or (
            published != n_layers) else (),
        norm="layer", use_rope=False,
        attn_bias=True, rms_eps=float(cfg.get("layer_norm_eps", 1e-5)),
        scan_inner=2 * hidden, scan_state=16, scan_conv=4,
        scan_dt_rank=math.ceil(hidden / 16),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", True)),
    )


def _afmoe_spec(cfg: dict, hidden: int) -> dict:
    """The ``afmoe`` family's (Arcee Trinity) own fields from its
    config.json: gated, QK-normed GQA whose ``sliding_attention`` layers
    rotate and whose ``full_attention`` layers carry no position (a kind
    each, in the order the layers first show them), four norms a layer,
    the embedding times ``sqrt(hidden)`` under ``mup_enabled``,
    ``num_dense_layers`` leading dense layers, then a sigmoid router whose
    ``expert_bias`` enters the choice alone, ``route_norm`` /
    ``route_scale`` on the chosen scores, beside ``num_shared_experts``."""
    from dynamo_tpu.engine.config import LayerKind

    if str(cfg.get("score_func") or "sigmoid") != "sigmoid":
        raise NotImplementedError(f"afmoe: score_func {cfg['score_func']!r}")
    if max(int(cfg.get(k) or 1) for k in (
            "n_group", "topk_group", "num_expert_groups",
            "num_limited_groups")) > 1:
        raise NotImplementedError("afmoe: group-limited routing")
    n_layers = int(cfg["num_hidden_layers"])
    every = int(cfg.get("global_attn_every_n_layers") or 4)
    types = list(cfg.get("layer_types") or (
        "full_attention" if (i + 1) % every == 0 else "sliding_attention"
        for i in range(n_layers)))
    nkv = int(cfg.get("num_key_value_heads") or cfg["num_attention_heads"])
    theta = float(cfg.get("rope_theta", 10000.0))
    kind_of = {
        "sliding_attention": LayerKind(
            nkv, theta, window=int(cfg["sliding_window"])),
        "full_attention": LayerKind(nkv, theta, rope=False),
    }
    seen = list(dict.fromkeys(types))
    return dict(
        layer_kinds=tuple(kind_of[t] for t in seen),
        layer_pattern=tuple(seen.index(t) for t in types),
        qk_norm=True, attn_gate=True, sandwich_norm=True,
        embedding_multiplier=(
            float(hidden) ** 0.5 if cfg.get("mup_enabled") else 1.0),
        first_k_dense=int(cfg.get("num_dense_layers") or 0),
        n_shared_experts=int(cfg.get("num_shared_experts") or 0),
        moe_scoring="sigmoid",
        routed_scaling_factor=float(cfg.get("route_scale") or 1.0),
        norm_topk_prob=bool(cfg.get("route_norm", True)),
    )


def _no_latent_kind(spec: ModelSpec) -> None:
    """A model that keeps latent layers as a KIND beside others (Ling-3.0)
    runs on drawn weights only: the catalog it was added from gives its
    config and no tensor names, and none is invented here."""
    if spec.has_latent:
        raise NotImplementedError(
            "a latent kind beside other kinds has no published tensor "
            "names in this loader: drawn weights only"
        )


def hf_config_from_spec(spec: ModelSpec) -> dict:
    """Inverse of spec_from_hf_config (save_params / re-export): every
    architecture field the loader reads must round-trip, or an exported
    checkpoint silently loses features on reload."""
    _no_latent_kind(spec)
    if spec.shortcut_moe:
        return _longcat_config_from_spec(spec)
    if spec.kv_lora_rank:
        model_type = "deepseek_v3"
    elif "ssd" in spec.mixers:
        model_type = "falcon_h1"
    elif "scan" in spec.mixers:
        model_type = "phi4flash"
    elif "kda" in spec.mixers:
        model_type = "solar_open2"
    elif "conv" in spec.mixers:
        model_type = "lfm2_moe"
    elif spec.sandwich_norm:
        model_type = "afmoe"
    elif spec.attn_sinks:
        model_type = "gpt_oss"
    elif spec.num_experts:
        model_type = "mixtral"
    else:
        model_type = "llama"
    cfg = {
        "model_type": model_type,
        "vocab_size": spec.vocab_size,
        "hidden_size": spec.hidden_size,
        "intermediate_size": (
            spec.moe_intermediate_size
            if spec.num_experts and not spec.kv_lora_rank
            else spec.intermediate_size
        ),
        "num_hidden_layers": spec.num_layers,
        "num_attention_heads": spec.num_heads,
        "num_key_value_heads": spec.num_kv_heads,
        "head_dim": spec.head_dim,
        "rope_theta": spec.rope_theta,
        "rms_norm_eps": spec.rms_eps,
        "tie_word_embeddings": spec.tie_embeddings,
        "dtype": spec.dtype,  # transformers >= 4.56 key (loader reads both)
        "torch_dtype": spec.dtype,
    }
    if spec.num_experts:
        cfg["num_local_experts"] = spec.num_experts
        cfg["num_experts_per_tok"] = spec.num_experts_per_token
        cfg["moe_intermediate_size"] = spec.moe_intermediate_size
    if model_type == "solar_open2":
        cfg.update(
            intermediate_size=spec.intermediate_size,
            n_routed_experts=spec.num_experts,
            n_shared_experts=spec.n_shared_experts,
            first_k_dense_replace=spec.first_k_dense,
            routed_scaling_factor=spec.routed_scaling_factor,
            norm_topk_prob=spec.norm_topk_prob,
            use_rope=spec.use_rope, use_gqa_gate=spec.attn_gate,
            gqa_layers=[
                i for i in range(spec.num_layers)
                if not spec.kind(i).recurrent
            ],
            linear_attn_config={
                "short_conv_kernel_size": spec.kda_conv,
                "head_dim": spec.kda_head_dim, "num_heads": spec.kda_heads,
                "num_kv_heads": None,
            },
            kda_use_full_proj=False, kda_allow_neg_eigval=spec.kda_neg_eigval,
        )
        del cfg["num_local_experts"]
    if model_type == "lfm2_moe":
        attn = next(kd for kd in spec.layer_kinds if kd.paged)
        cfg.update(
            intermediate_size=spec.intermediate_size,
            num_experts=spec.num_experts,
            num_key_value_heads=attn.num_kv_heads,
            num_dense_layers=spec.first_k_dense,
            layer_types=[
                "conv" if spec.kind(i).mixer == "conv" else "full_attention"
                for i in range(spec.num_layers)
            ],
            conv_L_cache=spec.conv_taps, conv_bias=False,
            norm_eps=spec.rms_eps, use_expert_bias=True,
            norm_topk_prob=spec.norm_topk_prob,
            routed_scaling_factor=spec.routed_scaling_factor,
            rope_parameters={
                "rope_theta": attn.rope_theta, "rope_type": "default"},
        )
        del cfg["num_local_experts"]
    if model_type == "afmoe":
        window = max(kd.window for kd in spec.kinds)
        attn = spec.kinds[0]
        cfg.update(
            intermediate_size=spec.intermediate_size,
            num_experts=spec.num_experts,
            num_key_value_heads=attn.num_kv_heads,
            rope_theta=attn.rope_theta,
            num_dense_layers=spec.first_k_dense,
            num_shared_experts=spec.n_shared_experts,
            layer_types=[
                "sliding_attention" if spec.kind(i).window
                else "full_attention" for i in range(spec.num_layers)
            ],
            sliding_window=window,
            score_func="sigmoid", route_norm=spec.norm_topk_prob,
            route_scale=spec.routed_scaling_factor,
            mup_enabled=spec.embedding_multiplier != 1.0,
            n_group=1, topk_group=1,
        )
        del cfg["num_local_experts"]
    if model_type == "phi4flash":
        window = next(k.window for k in spec.layer_kinds if k.window)
        # the published depth: the memory layer stands at its middle
        published = 2 * spec.layer_id(spec.memory_layer)
        cfg.update(
            mb_per_layer=2, sliding_window=window,
            layer_norm_eps=spec.rms_eps, mlp_bias=False, lm_head_bias=False,
        )
        for key in ("head_dim", "rope_theta", "rms_norm_eps"):
            del cfg[key]
        if spec.layer_ids:
            cfg.update(layers_kept=list(spec.layer_ids),
                       published_layers=published)
    if model_type == "falcon_h1":
        cfg.update(
            mamba_n_heads=spec.ssm_heads, mamba_d_head=spec.ssm_head_dim,
            mamba_d_ssm=spec.ssm_heads * spec.ssm_head_dim,
            mamba_d_state=spec.ssm_state, mamba_n_groups=spec.ssm_groups,
            mamba_d_conv=spec.ssm_conv, mamba_chunk_size=spec.ssm_chunk,
            mamba_conv_bias=True, mamba_proj_bias=False,
            mamba_rms_norm=True, mamba_norm_before_gate=False,
            attention_bias=False, mlp_bias=False, projectors_bias=False,
            ssm_multipliers=list(spec.ssm_multipliers),
            mlp_multipliers=list(spec.mlp_multipliers),
            **{key: getattr(spec, key) for key in _FALCON_SCALARS},
        )
    if model_type == "gpt_oss":
        cfg.update(
            sliding_window=spec.sliding_window,
            layer_types=list(spec.layer_types),
            attention_bias=spec.attn_bias,
            swiglu_limit=spec.swiglu_limit,
        )
    if spec.kv_lora_rank:
        cfg.update(
            n_routed_experts=spec.num_experts,
            n_shared_experts=spec.n_shared_experts,
            first_k_dense_replace=spec.first_k_dense,
            kv_lora_rank=spec.kv_lora_rank,
            q_lora_rank=spec.q_lora_rank or None,
            qk_nope_head_dim=spec.qk_nope_head_dim,
            qk_rope_head_dim=spec.qk_rope_head_dim,
            v_head_dim=spec.v_head_dim,
            scoring_func=spec.moe_scoring,
            n_group=spec.n_group,
            topk_group=spec.topk_group,
            routed_scaling_factor=spec.routed_scaling_factor,
            norm_topk_prob=spec.norm_topk_prob,
            # our in-memory params are HALF-SPLIT (load_params permutes
            # interleaved checkpoints on the way in) — an exported
            # checkpoint must say so, or reload would de-interleave twice
            rope_interleave=False,
        )
    if spec.rope_scaling_factor:
        cfg["rope_scaling"] = {
            "rope_type": "yarn",
            "factor": spec.rope_scaling_factor,
            "original_max_position_embeddings": spec.rope_orig_max_pos,
            "beta_fast": spec.rope_beta_fast,
            "beta_slow": spec.rope_beta_slow,
            "truncate": spec.rope_truncate,
            **(
                {"mscale": spec.rope_mscale,
                 "mscale_all_dim": spec.rope_mscale_all_dim}
                if spec.rope_mscale or spec.rope_mscale_all_dim
                else {}
            ),
        }
        # HF convention: the POST-scaling context window (the original
        # lives inside rope_scaling)
        cfg["max_position_embeddings"] = int(
            spec.rope_orig_max_pos * spec.rope_scaling_factor
        )
    return cfg


def _longcat_config_from_spec(spec: ModelSpec) -> dict:
    """LongCat-Flash's own keys (the config has no ``num_hidden_layers``,
    ``intermediate_size`` or ``num_experts_per_tok``)."""
    return {
        "model_type": "longcat_flash", "attention_method": "MLA",
        "attention_bias": False,
        "vocab_size": spec.vocab_size, "hidden_size": spec.hidden_size,
        "ffn_hidden_size": spec.intermediate_size,
        "expert_ffn_hidden_size": spec.moe_intermediate_size,
        "num_layers": spec.num_layers,
        "num_attention_heads": spec.num_heads,
        "kv_lora_rank": spec.kv_lora_rank, "q_lora_rank": spec.q_lora_rank,
        "qk_rope_head_dim": spec.qk_rope_head_dim,
        "qk_nope_head_dim": spec.qk_nope_head_dim,
        "v_head_dim": spec.v_head_dim,
        "mla_scale_q_lora": spec.mla_scale_q_lora,
        "mla_scale_kv_lora": spec.mla_scale_kv_lora,
        "routed_scaling_factor": spec.routed_scaling_factor,
        "norm_topk_prob": spec.norm_topk_prob,
        "n_routed_experts": spec.num_experts,
        "zero_expert_num": spec.zero_experts,
        "zero_expert_type": "identity", "moe_topk": spec.num_experts_per_token,
        "rms_norm_eps": spec.rms_eps, "rope_theta": spec.rope_theta,
        # params in memory are half-split already (see the deepseek
        # branch of hf_config_from_spec): a reload must not permute again
        "rope_interleave": False,
        "tie_word_embeddings": spec.tie_embeddings,
        "dtype": spec.dtype, "torch_dtype": spec.dtype,
    }


# ------------------------------------------------------------------- name map


def _moe_scheme(names: set[str] | None) -> str:
    """Which MoE tensor-naming convention a checkpoint uses.

    mixtral:  model.layers.N.block_sparse_moe.gate.weight + experts.E.w{1,2,3}
    qwen_moe: model.layers.N.mlp.gate.weight + experts.E.{gate,up,down}_proj
    gpt_oss:  model.layers.N.mlp.router.weight + FUSED 3D
              experts.gate_up_proj [E, d, 2f] (gate/up interleaved on the
              last axis) and experts.down_proj [E, f, d]
    """
    if not names:
        return "mixtral"
    for n in names:
        if ".block_sparse_moe." in n:
            return "mixtral"
        if ".mlp.experts.gate_up_proj" in n:
            return "gpt_oss"
        if ".mlp.experts.0." in n:
            return "qwen_moe"
    return "mixtral"


def _latent_attention_names(m: dict, spec: ModelSpec, a: str, at: tuple):
    """One latent attention's tensors under the name prefix ``a`` -> the
    tree path ``at`` (its fused ``kv_b_proj`` splits in load_params)."""
    m[a + "o_proj.weight"] = (at + ("wo",), True, None)
    m[a + "kv_a_proj_with_mqa.weight"] = (at + ("w_kv_a",), True, None)
    m[a + "kv_a_layernorm.weight"] = (at + ("kv_norm",), False, None)
    if spec.q_lora_rank:
        m[a + "q_a_proj.weight"] = (at + ("wq_a",), True, None)
        m[a + "q_a_layernorm.weight"] = (at + ("q_norm",), False, None)
        m[a + "q_b_proj.weight"] = (at + ("wq_b",), True, None)
    else:
        m[a + "q_proj.weight"] = (at + ("wq",), True, None)


def _expert_names(m: dict, spec: ModelSpec, experts: str, at: tuple):
    """Every FFN expert's three projections, named one by one under the
    prefix ``experts`` -> the stacked leaves under ``at``."""
    for e in range(spec.num_experts):
        for hf, ours in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                         ("down_proj", "w_down")):
            m[f"{experts}{e}.{hf}.weight"] = (at + (ours, e), True, None)


def _dest_map_mla(
    spec: ModelSpec,
) -> dict[str, tuple[tuple, bool, str | None]]:
    """DeepSeek-family (MLA) tensor names -> models/mla.py tree paths.
    ``kv_b_proj`` (the fused per-head W_uk/W_uv) splits in load_params."""
    m: dict[str, tuple[tuple, bool, str | None]] = {
        "model.embed_tokens.weight": (("embed",), False, None),
        "model.norm.weight": (("final_norm",), False, None),
    }
    if not spec.tie_embeddings:
        m["lm_head.weight"] = (("lm_head",), True, None)
    for i in range(spec.num_layers):
        p = f"model.layers.{i}."
        li = ("layers", i)
        m[p + "input_layernorm.weight"] = (li + ("attn_norm",), False, None)
        m[p + "post_attention_layernorm.weight"] = (li + ("mlp_norm",), False, None)
        _latent_attention_names(m, spec, p + "self_attn.", li)
        if spec.num_experts and i >= spec.first_k_dense:
            m[p + "mlp.gate.weight"] = (li + ("moe", "router"), True, "float32")
            if spec.moe_scoring == "sigmoid":
                m[p + "mlp.gate.e_score_correction_bias"] = (
                    li + ("moe", "score_bias"), False, "float32"
                )
            _expert_names(m, spec, p + "mlp.experts.", li + ("moe",))
            if spec.n_shared_experts:
                sp_ = p + "mlp.shared_experts."
                m[sp_ + "gate_proj.weight"] = (li + ("shared", "w_gate"), True, None)
                m[sp_ + "up_proj.weight"] = (li + ("shared", "w_up"), True, None)
                m[sp_ + "down_proj.weight"] = (li + ("shared", "w_down"), True, None)
        else:
            for hf, ours in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                             ("down_proj", "w_down")):
                m[p + f"mlp.{hf}.weight"] = (li + (ours,), True, None)
    return m


def _dest_map_longcat(
    spec: ModelSpec,
) -> dict[str, tuple[tuple, bool, str | None]]:
    """LongCat-Flash tensor names (HF ``LongcatFlashForCausalLM``: the
    two attentions, dense MLPs and norm pairs of a layer are module lists
    indexed 0, 1) -> models/mla.py tree paths of a double layer. The
    identity experts have no tensors. ``kv_b_proj`` splits in
    load_params."""
    m: dict[str, tuple[tuple, bool, str | None]] = {
        "model.embed_tokens.weight": (("embed",), False, None),
        "model.norm.weight": (("final_norm",), False, None),
    }
    if not spec.tie_embeddings:
        m["lm_head.weight"] = (("lm_head",), True, None)
    for i in range(spec.num_layers):
        p = f"model.layers.{i}."
        li = ("layers", i)
        for j in range(2):
            sub = li + ("sub", j)
            a = p + f"self_attn.{j}."
            m[p + f"input_layernorm.{j}.weight"] = (
                sub + ("attn_norm",), False, None)
            m[p + f"post_attention_layernorm.{j}.weight"] = (
                sub + ("mlp_norm",), False, None)
            _latent_attention_names(m, spec, a, sub)
            for hf, ours in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                             ("down_proj", "w_down")):
                m[p + f"mlps.{j}.{hf}.weight"] = (sub + (ours,), True, None)
        m[p + "mlp.router.classifier.weight"] = (
            li + ("moe", "router"), True, "float32")
        m[p + "mlp.router.e_score_correction_bias"] = (
            li + ("moe", "score_bias"), False, "float32")
        _expert_names(m, spec, p + "mlp.experts.", li + ("moe",))
    return m


def _mla_dest_map(spec: ModelSpec):
    return (_dest_map_longcat if spec.shortcut_moe else _dest_map_mla)(spec)


def _kv_b_names(spec: ModelSpec) -> dict[str, tuple]:
    """The fused ``kv_b_proj`` tensor of every latent attention -> the
    tree path of the layer (or sub-layer) that holds its two halves."""
    if spec.shortcut_moe:
        return {
            f"model.layers.{i}.self_attn.{j}.kv_b_proj.weight":
                ("layers", i, "sub", j)
            for i in range(spec.num_layers) for j in range(2)
        }
    return {f"model.layers.{i}.self_attn.kv_b_proj.weight": ("layers", i)
            for i in range(spec.num_layers)}


def _dest_map(
    spec: ModelSpec, names: set[str] | None = None
) -> dict[str, tuple[tuple, bool, str | None]]:
    """HF tensor name -> ((pytree path), transpose, dtype-override).

    ``names`` (the checkpoint's tensor set) selects the MoE naming scheme;
    gpt-oss fused expert tensors (weights AND biases) are handled
    separately in load_params (they split, which this map cannot
    express). gpt-oss attention sinks, projection biases, and router
    bias map here when the spec enables them.
    """
    falcon = "ssd" in spec.mixers  # the published falcon_h1 names
    m: dict[str, tuple[tuple, bool, str | None]] = {
        "model.embed_tokens.weight": (("embed",), False, None),
        ("model.final_layernorm.weight" if falcon else "model.norm.weight"):
            (("final_norm",), False, None),
    }
    if not spec.tie_embeddings:
        m["lm_head.weight"] = (("lm_head",), True, None)
    scheme = _moe_scheme(names) if spec.num_experts else None
    for i in range(spec.num_layers):
        p = f"model.layers.{i}."
        li = ("layers", i)
        m[p + "input_layernorm.weight"] = (li + ("attn_norm",), False, None)
        m[p + ("pre_ff_layernorm.weight" if falcon
               else "post_attention_layernorm.weight")] = (
            li + ("mlp_norm",), False, None)
        for hf, ours in (("q_proj", "wq"), ("k_proj", "wk"),
                         ("v_proj", "wv"), ("o_proj", "wo")):
            m[p + f"self_attn.{hf}.weight"] = (li + (ours,), True, None)
        if spec.kind(i).mixer == "ssd":
            # the Mamba-2 mixer's own tensors beside the attention's. The
            # taps are stored [channels, 1, taps]: load_params folds the
            # middle axis
            a = p + "mamba."
            for hf, ours, tr, dt in (
                ("in_proj.weight", "ssm_in", True, None),
                ("conv1d.weight", "ssm_conv", True, None),
                ("conv1d.bias", "ssm_conv_bias", False, None),
                ("A_log", "ssm_a_log", False, "float32"),
                ("dt_bias", "ssm_dt_bias", False, "float32"),
                ("D", "ssm_d", False, "float32"),
                ("norm.weight", "ssm_norm", False, None),
                ("out_proj.weight", "ssm_out", True, None),
            ):
                m[a + hf] = (li + (ours,), tr, dt)
        elif spec.kind(i).recurrent:
            # a KDA layer's own tensors (the names of the public
            # flash-linear-attention KDA module). The taps are stored
            # [channels, 1, taps]: load_params folds the middle axis
            a = p + "self_attn."
            for hf, ours, tr, dt in (
                ("q_conv1d.weight", "conv_q", True, None),
                ("k_conv1d.weight", "conv_k", True, None),
                ("v_conv1d.weight", "conv_v", True, None),
                ("f_a_proj.weight", "w_f_down", True, None),
                ("f_b_proj.weight", "w_f_up", True, None),
                ("g_a_proj.weight", "w_g_down", True, None),
                ("g_b_proj.weight", "w_g_up", True, None),
                ("b_proj.weight", "w_beta", True, None),
                ("A_log", "a_log", False, "float32"),
                ("dt_bias", "dt_bias", False, "float32"),
                ("o_norm.weight", "o_norm", False, None),
            ):
                m[a + hf] = (li + (ours,), tr, dt)
        elif spec.attn_gate:
            m[p + "self_attn.g_proj.weight"] = (
                li + ("w_gate_attn",), True, None)
        if spec.attn_bias:
            for hf, ours in (("q_proj", "bq"), ("k_proj", "bk"),
                             ("v_proj", "bv"), ("o_proj", "bo")):
                m[p + f"self_attn.{hf}.bias"] = (li + (ours,), False, None)
        if spec.attn_sinks:
            m[p + "self_attn.sinks"] = (li + ("sinks",), False, None)
        if spec.num_experts:
            if scheme == "mixtral":
                mp = p + "block_sparse_moe."
                m[mp + "gate.weight"] = (li + ("moe", "router"), True, "float32")
                for e in range(spec.num_experts):
                    ep = mp + f"experts.{e}."
                    m[ep + "w1.weight"] = (li + ("moe", "w_gate", e), True, None)
                    m[ep + "w3.weight"] = (li + ("moe", "w_up", e), True, None)
                    m[ep + "w2.weight"] = (li + ("moe", "w_down", e), True, None)
            elif scheme == "qwen_moe":
                mp = p + "mlp."
                m[mp + "gate.weight"] = (li + ("moe", "router"), True, "float32")
                for e in range(spec.num_experts):
                    ep = mp + f"experts.{e}."
                    m[ep + "gate_proj.weight"] = (li + ("moe", "w_gate", e), True, None)
                    m[ep + "up_proj.weight"] = (li + ("moe", "w_up", e), True, None)
                    m[ep + "down_proj.weight"] = (li + ("moe", "w_down", e), True, None)
                if spec.moe_scoring == "sigmoid":
                    m[mp + "gate.e_score_correction_bias"] = (
                        li + ("moe", "score_bias"), False, "float32")
                for hf, ours in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                                 ("down_proj", "w_down")):
                    if spec.n_shared_experts:
                        m[mp + f"shared_experts.{hf}.weight"] = (
                            li + ("shared", ours), True, None)
            else:  # gpt_oss: router here; fused experts in load_params
                m[p + "mlp.router.weight"] = (li + ("moe", "router"), True, "float32")
                if spec.moe_bias:
                    m[p + "mlp.router.bias"] = (
                        li + ("moe", "router_bias"), False, "float32"
                    )
        else:
            mlp = "feed_forward." if falcon else "mlp."
            for hf, ours in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                             ("down_proj", "w_down")):
                m[p + mlp + f"{hf}.weight"] = (li + (ours,), True, None)
    return m


def _dest_map_lfm2(spec: ModelSpec) -> dict[str, tuple[tuple, bool, str | None]]:
    """``_dest_map`` for an ``lfm2_moe`` checkpoint, under the names of
    the family's published code: ``operator_norm`` / ``ffn_norm`` around a
    layer's ``conv`` or ``self_attn`` and its ``feed_forward`` (``w1``
    gate, ``w3`` up, ``w2`` down; an expert layer's ``gate`` is the
    router and ``expert_bias`` its selection bias), ``embedding_norm``
    after the last layer, the head the embedding's transpose. The taps are
    stored ``[channels, 1, taps]``: load_params folds the middle axis."""
    m: dict[str, tuple[tuple, bool, str | None]] = {
        "model.embed_tokens.weight": (("embed",), False, None),
        "model.embedding_norm.weight": (("final_norm",), False, None),
    }
    if not spec.tie_embeddings:
        m["lm_head.weight"] = (("lm_head",), True, None)
    mlp = (("w1", "w_gate"), ("w3", "w_up"), ("w2", "w_down"))
    for i in range(spec.num_layers):
        p = f"model.layers.{i}."
        li = ("layers", i)
        m[p + "operator_norm.weight"] = (li + ("attn_norm",), False, None)
        m[p + "ffn_norm.weight"] = (li + ("mlp_norm",), False, None)
        if spec.kind(i).mixer == "conv":
            for hf, ours in (("in_proj", "sconv_in"), ("conv", "sconv_taps"),
                             ("out_proj", "sconv_out")):
                m[p + f"conv.{hf}.weight"] = (li + (ours,), True, None)
        else:
            for hf, ours in (("q_proj", "wq"), ("k_proj", "wk"),
                             ("v_proj", "wv"), ("out_proj", "wo")):
                m[p + f"self_attn.{hf}.weight"] = (li + (ours,), True, None)
            for hf, ours in (("q_layernorm", "q_norm"),
                             ("k_layernorm", "k_norm")):
                m[p + f"self_attn.{hf}.weight"] = (li + (ours,), False, None)
        f = p + "feed_forward."
        if not spec.is_moe_layer(i):
            for hf, ours in mlp:
                m[f + f"{hf}.weight"] = (li + (ours,), True, None)
            continue
        m[f + "gate.weight"] = (li + ("moe", "router"), True, "float32")
        m[f + "expert_bias"] = (li + ("moe", "score_bias"), False, "float32")
        for e in range(spec.num_experts):
            for hf, ours in mlp:
                m[f + f"experts.{e}.{hf}.weight"] = (
                    li + ("moe", ours, e), True, None)
    return m


def _dest_map_afmoe(spec: ModelSpec) -> dict[str, tuple[tuple, bool, str | None]]:
    """``_dest_map`` for an ``afmoe`` checkpoint, under the names of the
    family's published code: FOUR norms a layer (``input_layernorm`` and
    ``pre_mlp_layernorm`` on the way in, ``post_attention_layernorm`` and
    ``post_mlp_layernorm`` on the way OUT: the second is no input norm
    here, as it is in every other family), ``self_attn.gate_proj`` the
    output gate, ``q_norm`` / ``k_norm`` a head, an expert layer's
    ``router.gate``, ``expert_bias``, ``shared_experts`` and ``experts``
    named one by one."""
    m: dict[str, tuple[tuple, bool, str | None]] = {
        "model.embed_tokens.weight": (("embed",), False, None),
        "model.norm.weight": (("final_norm",), False, None),
    }
    if not spec.tie_embeddings:
        m["lm_head.weight"] = (("lm_head",), True, None)
    mlp = (("gate_proj", "w_gate"), ("up_proj", "w_up"),
           ("down_proj", "w_down"))
    for i in range(spec.num_layers):
        p = f"model.layers.{i}."
        li = ("layers", i)
        for hf, ours in (("input_layernorm", "attn_norm"),
                         ("post_attention_layernorm", "post_attn_norm"),
                         ("pre_mlp_layernorm", "mlp_norm"),
                         ("post_mlp_layernorm", "post_mlp_norm"),
                         ("self_attn.q_norm", "q_norm"),
                         ("self_attn.k_norm", "k_norm")):
            m[p + hf + ".weight"] = (li + (ours,), False, None)
        for hf, ours in (("q_proj", "wq"), ("k_proj", "wk"), ("v_proj", "wv"),
                         ("o_proj", "wo"), ("gate_proj", "w_gate_attn")):
            m[p + f"self_attn.{hf}.weight"] = (li + (ours,), True, None)
        f = p + "mlp."
        if not spec.is_moe_layer(i):
            for hf, ours in mlp:
                m[f + f"{hf}.weight"] = (li + (ours,), True, None)
            continue
        m[f + "router.gate.weight"] = (li + ("moe", "router"), True, "float32")
        m[f + "expert_bias"] = (li + ("moe", "score_bias"), False, "float32")
        _expert_names(m, spec, f + "experts.", li + ("moe",))
        if spec.n_shared_experts:
            for hf, ours in mlp:
                m[f + f"shared_experts.{hf}.weight"] = (
                    li + ("shared", ours), True, None)
    return m


def _dest_map_phi4flash(
    spec: ModelSpec,
) -> dict[str, tuple[tuple, bool, str | None]]:
    """The published ``phi4flash`` names (Phi-4-mini-flash, SambaY) of the
    tensors that map one to one; the fused ones (``attn.Wqkv`` of a self
    attention layer, ``mlp.gate_up_proj``) split in ``_phi4flash_fused``.
    A layer's mixer is ``attn.`` whatever its kind: Mamba-1's own names
    on a scan layer, ``in_proj`` / ``out_proj`` alone on a GMU, ``Wqkv``
    (the queries alone) / ``out_proj`` on a cross layer, the lambdas and
    the pair norm under ``inner_cross_attn``. ``A_log`` is published
    ``[channels, states]`` and kept ``[states, channels]``."""
    f32 = "float32"
    m: dict[str, tuple[tuple, bool, str | None]] = {
        "model.embed_tokens.weight": (("embed",), False, None),
        "model.final_layernorm.weight": (("final_norm",), False, None),
        "model.final_layernorm.bias": (("final_norm_bias",), False, None),
    }
    for i in range(spec.num_layers):
        p, li, kd = f"model.layers.{i}.", ("layers", i), spec.kind(i)
        a = p + "attn."
        for hf, ours in (("input_layernorm", "attn_norm"),
                         ("post_attention_layernorm", "mlp_norm")):
            m[p + hf + ".weight"] = (li + (ours,), False, None)
            m[p + hf + ".bias"] = (li + (ours + "_bias",), False, None)
        m[p + "mlp.down_proj.weight"] = (li + ("w_down",), True, None)
        if kd.mixer == "scan":
            for hf, ours, tr, dt in (
                ("in_proj.weight", "scan_in", True, None),
                ("conv1d.weight", "scan_conv", True, None),
                ("conv1d.bias", "scan_conv_bias", False, None),
                ("x_proj.weight", "scan_x", True, None),
                ("dt_proj.weight", "scan_dt", True, None),
                ("dt_proj.bias", "scan_dt_bias", False, f32),
                ("A_log", "scan_a_log", True, f32),
                ("D", "scan_d", False, f32),
                ("out_proj.weight", "scan_out", True, None),
            ):
                m[a + hf] = (li + (ours,), tr, dt)
        elif kd.mixer == "gmu":
            m[a + "in_proj.weight"] = (li + ("gmu_in",), True, None)
            m[a + "out_proj.weight"] = (li + ("gmu_out",), True, None)
        else:
            m[a + "out_proj.weight"] = (li + ("wo",), True, None)
            m[a + "out_proj.bias"] = (li + ("bo",), False, None)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                m[a + "inner_cross_attn." + name] = (li + (name,), False, f32)
            m[a + "inner_cross_attn.subln.weight"] = (
                li + ("subln",), False, None)
            if kd.reads:  # the queries alone
                m[a + "Wqkv.weight"] = (li + ("wq",), True, None)
                m[a + "Wqkv.bias"] = (li + ("bq",), False, None)
    return m


def _phi4flash_fused(spec: ModelSpec) -> dict[str, list[tuple]]:
    """The published tensors that hold several of ours along their first
    axis: name -> [(path, first row, rows, transpose)]. ``attn.Wqkv`` of a
    self attention layer is ``[q | k | v]``, ``mlp.gate_up_proj`` ``[gate
    | up]``."""
    q = spec.num_heads * spec.head_dim
    f = spec.intermediate_size
    out: dict[str, list[tuple]] = {}
    for i in range(spec.num_layers):
        p, li, kd = f"model.layers.{i}.", ("layers", i), spec.kind(i)
        out[p + "mlp.gate_up_proj.weight"] = [
            (li + ("w_gate",), 0, f, True), (li + ("w_up",), f, f, True)]
        if kd.mixer == "softmax" and not kd.reads:
            kv = kd.num_kv_heads * spec.head_dim
            out[p + "attn.Wqkv.weight"] = [
                (li + ("wq",), 0, q, True), (li + ("wk",), q, kv, True),
                (li + ("wv",), q + kv, kv, True)]
            out[p + "attn.Wqkv.bias"] = [
                (li + ("bq",), 0, q, False), (li + ("bk",), q, kv, False),
                (li + ("bv",), q + kv, kv, False)]
    return out


def _is_taps(path: tuple) -> bool:
    """A short convolution's taps, published ``[channels, 1, taps]``."""
    return str(path[-1]).startswith("conv_") or path[-1] in (
        "ssm_conv", "sconv_taps", "scan_conv")


def _tree_set(tree: Params, path: tuple, value) -> None:
    node = tree
    for key in path[:-1]:
        if isinstance(key, int):
            while len(node) <= key:
                node.append({})
            node = node[key]
        else:
            node = node.setdefault(
                key, [] if key in ("layers", "sub") else {})
    node[path[-1]] = value


def _tree_get(tree: Params, path: tuple):
    node = tree
    for key in path:
        node = node[key]
    return node


# ------------------------------------------------------------------ load/save


def load_params(
    spec: ModelSpec,
    model_dir: str,
    *,
    mesh=None,
    dtype: str | None = None,
) -> Params:
    """Read ``*.safetensors`` under ``model_dir`` into the llama param tree.

    Tensors stream one at a time: mmap-read -> transpose/cast -> device_put
    (with the TP sharding when ``mesh`` is given). MoE expert tensors
    (stored per-expert in HF checkpoints) are stacked onto the leading
    expert axis our layer expects.
    """
    from safetensors import safe_open

    _no_latent_kind(spec)
    dtype = dtype or spec.dtype
    files = sorted(
        os.path.join(model_dir, f)
        for f in os.listdir(model_dir)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {model_dir}")
    all_names: set[str] = set()
    for path_file in files:
        with safe_open(path_file, framework="numpy") as f:
            all_names.update(f.keys())
    if spec.kv_lora_rank:
        dest = _mla_dest_map(spec)
        kv_b = _kv_b_names(spec)
        fused_gpt_oss = False
    elif "conv" in spec.mixers:
        dest = _dest_map_lfm2(spec)
        fused_gpt_oss = False
    elif spec.sandwich_norm:
        dest = _dest_map_afmoe(spec)
        fused_gpt_oss = False
    elif "scan" in spec.mixers:
        dest = _dest_map_phi4flash(spec)
        fused_gpt_oss = False
    else:
        dest = _dest_map(spec, all_names)
        fused_gpt_oss = bool(
            spec.num_experts and _moe_scheme(all_names) == "gpt_oss"
        )
    fused = _phi4flash_fused(spec) if "scan" in spec.mixers else {}

    params: Params = {}
    seen: set[str] = set()
    # MoE expert leaves accumulate per-expert then stack
    pending_experts: dict[tuple, dict[int, np.ndarray]] = {}

    shardings = None
    if mesh is not None:
        if spec.kv_lora_rank:
            raise NotImplementedError(
                "TP shardings for MLA checkpoints are not wired yet; "
                "load without a mesh"
            )
        from dynamo_tpu.models.llama import param_shardings

        shardings = param_shardings(spec, mesh)

    def place(path: tuple, arr: np.ndarray, dt: str):
        x = jnp.asarray(arr, dtype=jnp.dtype(dt))
        if shardings is not None:
            x = jax.device_put(x, _tree_get(shardings, path))
        _tree_set(params, path, x)

    skipped_extras: list[str] = []
    for path_file in files:
        with safe_open(path_file, framework="numpy") as f:
            for name in f.keys():
                if name not in dest:
                    part = name.split(".")
                    if (len(part) > 2 and part[1] == "layers"
                            and part[2].isdigit()
                            and int(part[2]) >= spec.num_layers):
                        # behind the decoder: the multi-token-prediction
                        # layers are dropped, as the published inference
                        # code drops them; anything deeper is a stray
                        if int(part[2]) >= (spec.num_layers
                                            + spec.nextn_predict_layers):
                            skipped_extras.append(name)
                        continue
                    if name in fused:
                        # several of ours along the published first axis
                        arr = f.get_tensor(name)
                        for path, lo, n, tr in fused[name]:
                            part = arr[lo:lo + n]
                            place(path, np.ascontiguousarray(
                                part.T if tr else part), dtype)
                        seen.add(name)
                        continue
                    if spec.kv_lora_rank and name in kv_b:
                        # fused per-head up-projections [H*(dn+dv), dc]:
                        # split into w_uk [H, dc, dn] / w_uv [H, dc, dv]
                        li = kv_b[name]
                        arr = f.get_tensor(name)
                        H, dn, dv = (spec.num_heads, spec.qk_nope_head_dim,
                                     spec.v_head_dim)
                        arr = arr.reshape(H, dn + dv, spec.kv_lora_rank)
                        place(li + ("w_uk",),
                              np.ascontiguousarray(
                                  arr[:, :dn].transpose(0, 2, 1)), dtype)
                        place(li + ("w_uv",),
                              np.ascontiguousarray(
                                  arr[:, dn:].transpose(0, 2, 1)), dtype)
                        seen.add(name)
                    elif fused_gpt_oss and name.endswith(
                        (".mlp.experts.gate_up_proj", ".mlp.experts.down_proj")
                    ):
                        # fused 3D expert tensors, already [in, out] per
                        # expert; gate/up interleave on the last axis
                        li = ("layers", int(name.split(".")[2]), "moe")
                        arr = f.get_tensor(name)
                        if name.endswith("gate_up_proj"):
                            place(li + ("w_gate",), arr[..., 0::2], dtype)
                            place(li + ("w_up",), arr[..., 1::2], dtype)
                        else:
                            place(li + ("w_down",), arr, dtype)
                        seen.add(name)
                    elif fused_gpt_oss and spec.moe_bias and name.endswith(
                        (".mlp.experts.gate_up_proj_bias",
                         ".mlp.experts.down_proj_bias")
                    ):
                        li = ("layers", int(name.split(".")[2]), "moe")
                        arr = f.get_tensor(name)
                        if name.endswith("gate_up_proj_bias"):
                            place(li + ("b_gate",), arr[..., 0::2], dtype)
                            place(li + ("b_up",), arr[..., 1::2], dtype)
                        else:
                            place(li + ("b_down",), arr, dtype)
                        seen.add(name)
                    elif name.endswith(("_bias", ".bias", ".sinks")):
                        skipped_extras.append(name)
                    continue
                path, transpose, dt_override = dest[name]
                arr = f.get_tensor(name)
                if arr.ndim == 3 and _is_taps(path):
                    arr = arr.reshape(arr.shape[0], -1)  # [C, 1, taps]
                if transpose:
                    arr = np.ascontiguousarray(arr.T)
                if spec.kv_lora_rank and spec.rope_interleave:
                    arr = _deinterleave_rope_cols(spec, name, arr)
                seen.add(name)
                dt = dt_override or dtype
                if len(path) >= 2 and isinstance(path[-1], int) and path[-2] in (
                    "w_gate", "w_up", "w_down"
                ):
                    # per-expert tensor: buffer until all experts present
                    key = path[:-1]
                    pending_experts.setdefault(key, {})[path[-1]] = arr.astype(
                        _np_dtype(dt)
                    )
                    bucket = pending_experts[key]
                    if len(bucket) == spec.num_experts:
                        stacked = np.stack(
                            [bucket[e] for e in range(spec.num_experts)]
                        )
                        place(key, stacked, dt)
                        del pending_experts[key]
                else:
                    place(path, arr, dt)

    dest_expected = set(dest) | set(fused)
    if spec.kv_lora_rank:
        dest_expected |= set(kv_b)
    if fused_gpt_oss:
        tails = ["gate_up_proj", "down_proj"]
        if spec.moe_bias:
            tails += ["gate_up_proj_bias", "down_proj_bias"]
        dest_expected |= {
            f"model.layers.{i}.mlp.experts.{t}"
            for i in range(spec.num_layers)
            for t in tails
        }
    if skipped_extras:
        import logging

        logging.getLogger("dynamo.loader").warning(
            "skipped %d tensors with no destination in this spec "
            "(unexpected for supported architectures), e.g. %s",
            len(skipped_extras), sorted(skipped_extras)[:3],
        )
    missing = dest_expected - seen
    if missing:
        raise ValueError(
            f"checkpoint {model_dir} missing {len(missing)} tensors, e.g. "
            f"{sorted(missing)[:4]}"
        )
    return params


def _deinterleave_rope_cols(
    spec: ModelSpec, name: str, arr: np.ndarray
) -> np.ndarray:
    """DeepSeek ``rope_interleave`` handling: checkpoint rope dims are
    pair-interleaved ([x0, y0, x1, y1, ...]); our rope is half-split
    ([x0, x1, ..., y0, y1, ...]). Permuting the q_rope and k_rope
    PROJECTION COLUMNS at load is exact — rope dims only ever meet in
    q.k dot products, and both sides get the same permutation (HF
    instead keeps the weights and swaps in apply_rotary_pos_emb_interleave).
    ``arr`` is already transposed to [in, out]."""
    dr = spec.qk_rope_head_dim
    perm = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    # a double layer's attentions are ``self_attn.0.`` / ``self_attn.1.``
    name = re.sub(r"self_attn\.\d+\.", "self_attn.", name)
    if name.endswith(("self_attn.q_b_proj.weight", "self_attn.q_proj.weight")):
        H, dn = spec.num_heads, spec.qk_nope_head_dim
        out = arr.reshape(arr.shape[0], H, dn + dr)
        out = np.concatenate([out[..., :dn], out[..., dn:][..., perm]], axis=-1)
        return np.ascontiguousarray(out.reshape(arr.shape))
    if name.endswith("self_attn.kv_a_proj_with_mqa.weight"):
        dc = spec.kv_lora_rank
        return np.ascontiguousarray(
            np.concatenate([arr[:, :dc], arr[:, dc:][:, perm]], axis=1)
        )
    return arr


def _np_dtype(dt: str):
    if dt == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dt)


def save_params(
    spec: ModelSpec, params: Params, model_dir: str, *, shard_bytes: int = 2**31
) -> None:
    """Write params as HF-format safetensors + config.json (test round-trips
    and checkpoint re-export). Large trees split into multiple shard files."""
    from safetensors.numpy import save_file

    _no_latent_kind(spec)
    os.makedirs(model_dir, exist_ok=True)
    if spec.kv_lora_rank:
        dest = _mla_dest_map(spec)
    elif "conv" in spec.mixers:
        dest = _dest_map_lfm2(spec)
    elif spec.sandwich_norm:
        dest = _dest_map_afmoe(spec)
    elif "scan" in spec.mixers:
        dest = _dest_map_phi4flash(spec)
    elif spec.moe_bias:
        # gpt-oss exports use the FUSED expert naming (synthesized
        # below); the name hint selects the gpt_oss scheme so the dest
        # map carries router(+bias) but not mixtral per-expert entries
        dest = _dest_map(
            spec, names={"model.layers.0.mlp.experts.gate_up_proj"}
        )
    elif "kda" in spec.mixers:
        # solar_open2's experts are named one by one
        dest = _dest_map(spec, names={"model.layers.0.mlp.experts.0."})
    else:
        dest = _dest_map(spec)
    tensors: dict[str, np.ndarray] = {}
    for name, (path, transpose, _dt) in dest.items():
        if len(path) >= 2 and isinstance(path[-1], int):
            arr = np.asarray(_tree_get(params, path[:-1])[path[-1]])
        else:
            arr = np.asarray(_tree_get(params, path))
        if transpose:
            arr = np.ascontiguousarray(arr.T)
        if _is_taps(path):
            arr = arr[:, None, :]  # the published [channels, 1, taps]
        tensors[name] = arr
    if "scan" in spec.mixers:
        # phi4flash's fused tensors: ours side by side along the first axis
        for name, parts in _phi4flash_fused(spec).items():
            tensors[name] = np.ascontiguousarray(np.concatenate([
                np.asarray(_tree_get(params, path)).T if tr
                else np.asarray(_tree_get(params, path))
                for path, _lo, _n, tr in parts]))
    if spec.moe_bias and not spec.kv_lora_rank:
        # gpt-oss fused expert tensors: re-interleave gate/up (weights
        # AND biases) the way load_params de-interleaves them
        for i, lp in enumerate(params["layers"]):
            moe = lp["moe"]
            wg = np.asarray(moe["w_gate"])
            wu = np.asarray(moe["w_up"])
            fused_w = np.empty(
                (wg.shape[0], wg.shape[1], 2 * wg.shape[2]), wg.dtype
            )
            fused_w[..., 0::2] = wg
            fused_w[..., 1::2] = wu
            bg = np.asarray(moe["b_gate"])
            bu = np.asarray(moe["b_up"])
            fused_b = np.empty((bg.shape[0], 2 * bg.shape[1]), bg.dtype)
            fused_b[..., 0::2] = bg
            fused_b[..., 1::2] = bu
            p = f"model.layers.{i}.mlp.experts."
            tensors[p + "gate_up_proj"] = fused_w
            tensors[p + "gate_up_proj_bias"] = fused_b
            tensors[p + "down_proj"] = np.asarray(moe["w_down"])
            tensors[p + "down_proj_bias"] = np.asarray(moe["b_down"])
    if spec.kv_lora_rank:
        # re-fuse the per-head up-projections into HF's kv_b_proj layout
        # (load_params splits them; see the kv_b_proj branch there)
        H, dn, dv, dc = (spec.num_heads, spec.qk_nope_head_dim,
                         spec.v_head_dim, spec.kv_lora_rank)
        for name, path in _kv_b_names(spec).items():
            lp = _tree_get(params, path)
            fused = np.concatenate(
                [np.asarray(lp["w_uk"]).transpose(0, 2, 1),
                 np.asarray(lp["w_uv"]).transpose(0, 2, 1)], axis=1
            ).reshape(H * (dn + dv), dc)
            tensors[name] = np.ascontiguousarray(fused)

    shards: list[dict[str, np.ndarray]] = [{}]
    size = 0
    for name in sorted(tensors):
        nbytes = tensors[name].nbytes
        if size + nbytes > shard_bytes and shards[-1]:
            shards.append({})
            size = 0
        shards[-1][name] = tensors[name]
        size += nbytes
    n = len(shards)
    for i, shard in enumerate(shards):
        fname = (
            "model.safetensors" if n == 1
            else f"model-{i + 1:05d}-of-{n:05d}.safetensors"
        )
        save_file(shard, os.path.join(model_dir, fname))
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(hf_config_from_spec(spec), f, indent=2)


def load_model_dir(
    model_dir: str, *, mesh=None, dtype: str | None = None,
    name: str | None = None,
) -> tuple[ModelSpec, Params]:
    """One-call path: config.json -> spec, safetensors -> params."""
    with open(os.path.join(model_dir, "config.json")) as f:
        cfg = json.load(f)
    spec = spec_from_hf_config(cfg, name=name or os.path.basename(model_dir.rstrip("/")))
    return spec, load_params(spec, model_dir, mesh=mesh, dtype=dtype)
