"""The bytes and operations a decode step of a latent-attention (MLA)
decoder with held experts and a shared expert must move and make, from
shapes alone: beside ``costs.py`` and ``costs_hybrid_moe.py`` (not edited),
for the configurations whose reference is ``latent_moe``. Read from the
public config keys and from ``layers_kept`` / ``experts``. Every count errs
low: what cannot be known from the sampler's totals is left out or taken
at its least. No JAX here.
"""

from __future__ import annotations

LANES = 128  # the chip's lane tile: a pool's row is rounded up to it

# The peak the first metric to read it brings (``costs.PEAKS`` holds the
# bandwidth alone and is not edited). Source: Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16. A device that is not here is an error.
PEAK_FLOPS_S = {"TPU v5 lite": 197e12}


def peak_flops_s(device_kind: str) -> float:
    if device_kind not in PEAK_FLOPS_S:
        raise SystemExit(
            f"perfbench: no peak rate for device kind {device_kind!r}: add "
            "it, with its source, to the table in lib/costs_latent_moe.py"
        )
    return PEAK_FLOPS_S[device_kind]


def _dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    kept = config.get("layers_kept") or list(range(layers))
    ex = config.get("experts") or {"held": config["n_routed_experts"],
                                   "published": config["n_routed_experts"]}
    return {
        "d": config["hidden_size"], "nh": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "dc": config["kv_lora_rank"],
        "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"],
        "dense": [i < config["first_k_dense_replace"] for i in kept],
        "f_dense": config["intermediate_size"],
        "f": config["moe_intermediate_size"],
        "shared": int(config.get("n_shared_experts") or 0),
        "held": int(ex["held"]), "experts": int(ex["published"]),
        "v": config["vocab_size"],
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def _lanes(dim: int) -> int:
    return -(-dim // LANES) * LANES


def latent_values_per_token(config: dict) -> int:
    """What the cache must hold of a token in a layer: ``[c, k_r]``."""
    m = _dims(config)
    return m["dc"] + m["dr"]


def latent_bytes_per_token(config: dict, kv_bytes: int = 2,
                           laid_out: bool = True) -> int:
    """One token's row in one layer: 576 values = 1,152 B in bfloat16, AS
    LAID OUT 640 lanes = 1,280 B (what a page's DMA moves)."""
    n = latent_values_per_token(config)
    return (_lanes(n) if laid_out else n) * kv_bytes


def attention_params(config: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o of one layer."""
    m = _dims(config)
    return (m["d"] * m["q_rank"] + m["q_rank"] * m["nh"] * (m["dn"] + m["dr"])
            + m["d"] * (m["dc"] + m["dr"])
            + m["dc"] * m["nh"] * (m["dn"] + m["dv"])
            + m["nh"] * m["dv"] * m["d"])


def expert_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f"]


def expert_bytes(config: dict) -> int:
    """One routed expert's gate, up and down weights: a step that routes
    at least one token to it cannot avoid reading them."""
    return expert_params(config) * _dims(config)["wbytes"]


def layer_params(config: dict, dense: bool) -> int:
    """A layer as this chip holds it, norm gains left out: attention, then
    the dense MLP, or router + shared expert + the held experts."""
    m = _dims(config)
    if dense:
        return attention_params(config) + 3 * m["d"] * m["f_dense"]
    return (attention_params(config) + m["d"] * m["experts"]
            + (m["shared"] + m["held"]) * expert_params(config))


def weight_bytes(config: dict) -> int:
    """All the weights held: the layers, embedding and head slices."""
    m = _dims(config)
    n = 2 * m["v"] * m["d"] + sum(
        layer_params(config, dense) for dense in m["dense"])
    return n * m["wbytes"]


def decode_attention_bytes_per_call(config: dict, live_tokens: float,
                                    batch: float) -> float:
    """Bytes one call of the latent decode kernel (one layer, all slots)
    must move: the live context's rows as laid out, read ONCE (they are
    key and value), the new rows written, the absorbed queries read and
    the latent outputs written."""
    m = _dims(config)
    row = latent_bytes_per_token(config)
    q_and_out = batch * m["nh"] * (_lanes(m["dc"] + m["dr"]) + m["dc"]) * m["wbytes"]
    return row * (live_tokens + batch) + q_and_out


def decode_attention_flops_per_call(config: dict, live_tokens: float,
                                    batch: float) -> float:
    """Multiply-adds x 2 of one call: every head scores a row's ``dc + dr``
    values and accumulates its ``dc`` (the lane padding's are not
    counted)."""
    m = _dims(config)
    return 2.0 * m["nh"] * (2 * m["dc"] + m["dr"]) * (live_tokens + batch)


def decode_step_bytes(config: dict, live_tokens: float, batch: float,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read and write: every held weight but
    the embedding (of which ``batch`` rows) and the routed experts no token
    reached (``experts_touched``: held experts with an assignment, summed
    over the expert layers; None = all of them), and each layer's latent
    traffic."""
    m = _dims(config)
    weights = weight_bytes(config) - m["v"] * m["d"] * m["wbytes"]
    if experts_touched is not None:
        idle = m["dense"].count(False) * m["held"] - experts_touched
        weights -= max(0.0, idle) * expert_bytes(config)
    rows = batch * m["d"] * m["wbytes"]
    attn = len(m["dense"]) * decode_attention_bytes_per_call(
        config, live_tokens, batch)
    return weights + rows + attn
