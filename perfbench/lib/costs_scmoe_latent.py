"""Parameters, bytes and operations of a decoder of shortcut-connected MoE
double layers over latent (MLA) attention with identity experts
(LongCat-Flash-Chat; configurations whose reference is ``scmoe_latent``),
from the published ``config.json`` keys and ``experts`` alone: a decoder
layer is TWO latent attentions, TWO dense FFNs and one expert layer (a
router over the FFN experts and the identity experts, which have no
weights); the cache holds a latent row a token a SUB-layer. What a call of
the latent decode kernel at the published head count must move and make,
what the grouped products over the experts a step touched must read, and a
layer's parameters. Every count errs low: what cannot be known from the
sampler's totals is left out or taken at its least. Kept with the
benchmark so that no PR that claims a gain can change the yardstick. No
JAX here.
"""

from __future__ import annotations

LANES = 128  # the chip's lane tile: a pool's row is rounded up to it
SUB_LAYERS = 2  # attentions (and cache layers, and dense FFNs) a layer

# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (the
# table of ``costs_latent_moe.py``, not imported: each family's costs
# stand alone). A device that is not here is an error.
PEAK_FLOPS_S = {"TPU v5 lite": 197e12}


def peak_flops_s(device_kind: str) -> float:
    if device_kind not in PEAK_FLOPS_S:
        raise SystemExit(
            f"perfbench: no peak rate for device kind {device_kind!r}: add "
            "it, with its source, to the table in lib/costs_scmoe_latent.py"
        )
    return PEAK_FLOPS_S[device_kind]


def _dims(config: dict) -> dict:
    ex = config.get("experts") or {"held": config["n_routed_experts"],
                                   "published": config["n_routed_experts"]}
    return {
        "d": config["hidden_size"], "nh": config["num_attention_heads"],
        "layers": config["num_layers"],
        "q_rank": config["q_lora_rank"], "dc": config["kv_lora_rank"],
        "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"],
        "f_dense": config["ffn_hidden_size"],
        "f": config["expert_ffn_hidden_size"],
        "held": int(ex["held"]), "experts": int(ex["published"]),
        "zeros": int(config.get("zero_expert_num") or 0),
        "topk": config["moe_topk"],
        "v": config["vocab_size"],
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def _lanes(dim: int) -> int:
    return -(-dim // LANES) * LANES


def cache_layers(config: dict) -> int:
    return SUB_LAYERS * _dims(config)["layers"]


def latent_values_per_token(config: dict) -> int:
    """What the cache must hold of a token in a sub-layer: ``[c, k_r]``."""
    m = _dims(config)
    return m["dc"] + m["dr"]


def latent_bytes_per_token(config: dict, kv_bytes: int = 2,
                           laid_out: bool = True) -> int:
    """One token's row in one sub-layer's pool: 576 values = 1,152 B in
    bfloat16, AS LAID OUT 640 lanes = 1,280 B (what a page's DMA moves)."""
    n = latent_values_per_token(config)
    return (_lanes(n) if laid_out else n) * kv_bytes


def cache_bytes_per_token(config: dict, kv_bytes: int = 2) -> int:
    """A token in every pool, as laid out."""
    return cache_layers(config) * latent_bytes_per_token(config, kv_bytes)


def attention_params(config: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o of ONE attention."""
    m = _dims(config)
    return (m["d"] * m["q_rank"] + m["q_rank"] * m["nh"] * (m["dn"] + m["dr"])
            + m["d"] * (m["dc"] + m["dr"])
            + m["dc"] * m["nh"] * (m["dn"] + m["dv"])
            + m["nh"] * m["dv"] * m["d"])


def dense_ffn_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f_dense"]


def router_params(config: dict) -> int:
    """W_r over the FFN experts and the identity experts (the correction
    bias, one float32 an output, is left out)."""
    m = _dims(config)
    return m["d"] * (m["experts"] + m["zeros"])


def expert_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f"]


def expert_bytes(config: dict) -> int:
    """One FFN expert's gate, up and down weights: a step that routes at
    least one token to it cannot avoid reading them. An identity expert has
    none."""
    return expert_params(config) * _dims(config)["wbytes"]


def layer_params(config: dict) -> int:
    """A decoder layer as this chip holds it, norm gains left out: two
    attentions, two dense FFNs, the router and the held experts."""
    m = _dims(config)
    return (SUB_LAYERS * (attention_params(config) + dense_ffn_params(config))
            + router_params(config) + m["held"] * expert_params(config))


def weight_bytes(config: dict) -> int:
    """All the weights held: the layers, embedding and head slices. (The
    router is float32 and counted at the weights' width: 19 M of 5.2 B.)"""
    m = _dims(config)
    return (2 * m["v"] * m["d"] + m["layers"] * layer_params(config)) * (
        m["wbytes"])


def decode_attention_bytes_per_call(config: dict, live_tokens: float,
                                    batch: float) -> float:
    """Bytes one call of the latent decode kernel (one sub-layer, all
    slots) must move: the live context's rows as laid out, read ONCE (they
    are key and value), the new rows written, the absorbed queries read
    and the latent outputs written."""
    m = _dims(config)
    row = latent_bytes_per_token(config)
    q_and_out = batch * m["nh"] * (
        _lanes(m["dc"] + m["dr"]) + m["dc"]) * m["wbytes"]
    return row * (live_tokens + batch) + q_and_out


def decode_attention_flops_per_call(config: dict, live_tokens: float,
                                    batch: float) -> float:
    """Multiply-adds x 2 of one call: every head scores a row's ``dc + dr``
    values and accumulates its ``dc`` (the lane padding's are not
    counted)."""
    m = _dims(config)
    return 2.0 * m["nh"] * (2 * m["dc"] + m["dr"]) * (live_tokens + batch)


def grouped_products_bytes_per_step(config: dict,
                                    experts_touched: float) -> float:
    """What the three grouped products of every expert layer must read a
    decode step: the matrices of the experts the step touched
    (``experts_touched``: held experts with an assignment, summed over the
    layers). The rows they move are a thousandth of that and left out."""
    return experts_touched * expert_bytes(config)


def decode_step_bytes(config: dict, live_tokens: float, batch: float,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read and write: every held weight but
    the embedding (of which ``batch`` rows) and the FFN experts no token
    reached (None = all of them), and each sub-layer's latent traffic."""
    m = _dims(config)
    weights = weight_bytes(config) - m["v"] * m["d"] * m["wbytes"]
    if experts_touched is not None:
        idle = m["layers"] * m["held"] - experts_touched
        weights -= max(0.0, idle) * expert_bytes(config)
    rows = batch * m["d"] * m["wbytes"]
    attn = cache_layers(config) * decode_attention_bytes_per_call(
        config, live_tokens, batch)
    return weights + rows + attn
