"""The bytes and operations of a decoder that interleaves KDA layers (a
recurrent state a sequence) with gated softmax GQA layers (pages), held
experts and a shared expert in every layer: what a call of each KDA kernel
and a whole decode step must move and make, from shapes alone. Beside
``costs.py``, ``costs_hybrid_moe.py`` and ``costs_latent_moe.py`` (not
edited), for the configurations whose reference is ``linear_moe``. Read
from the public config keys and from ``layers_kept`` / ``experts``. Every
count errs low: the kernels' float32 products are counted once, not by the
passes the matrix unit makes of them, and what cannot be known from the
sampler's totals is left out or taken at its least. No JAX here.
"""

from __future__ import annotations

from lib.costs_latent_moe import peak_flops_s  # noqa: F401  (the one table)

KDA_BLOCK = 64  # tokens a block of the chunkwise form (ops/attention.py)
STATE_BYTES = 4  # the state is float32 between tokens


def _dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    kept = config.get("layers_kept") or list(range(layers))
    ex = config.get("experts") or {"held": config["n_routed_experts"],
                                   "published": config["n_routed_experts"]}
    lin = config["linear_attn_config"]
    gqa = [i in config["gqa_layers"] for i in kept]
    hd_all = lin["num_heads"] * lin["head_dim"]
    return {
        "d": config["hidden_size"], "nh": config["num_attention_heads"],
        "nkv": config["num_key_value_heads"], "hd": config["head_dim"],
        "gqa": gqa, "n_gqa": sum(gqa), "n_kda": len(gqa) - sum(gqa),
        "gate": bool(config.get("use_gqa_gate")),
        "kh": lin["num_heads"], "kd": lin["head_dim"],
        "taps": lin["short_conv_kernel_size"],
        "rank": hd_all if config.get("kda_use_full_proj") else lin["head_dim"],
        "f": config["moe_intermediate_size"],
        "shared": int(config.get("n_shared_experts") or 0),
        "held": int(ex["held"]), "experts": int(ex["published"]),
        "v": config["vocab_size"],
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def state_bytes_per_row_layer(config: dict) -> int:
    """One sequence's state in one KDA layer: heads x d_k x d_v float32."""
    m = _dims(config)
    return m["kh"] * m["kd"] * m["kd"] * STATE_BYTES


def conv_tail_bytes_per_row_layer(config: dict) -> int:
    """The last ``taps - 1`` tokens' q, k and v projections, served dtype."""
    m = _dims(config)
    return (m["taps"] - 1) * 3 * m["kh"] * m["kd"] * m["wbytes"]


def kv_bytes_per_token_layer(config: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one GQA layer."""
    m = _dims(config)
    return 2 * m["nkv"] * m["hd"] * kv_bytes


def gqa_mixer_params(config: dict) -> int:
    """W_q, W_k, W_v, W_o and the output gate of a GQA layer."""
    m = _dims(config)
    wide = m["d"] * m["nh"] * m["hd"]
    return wide * (2 + m["gate"]) + 2 * m["d"] * m["nkv"] * m["hd"]


def kda_mixer_params(config: dict) -> int:
    """W_q, W_k, W_v, W_o, the decay's and the gate's low-rank pairs, beta,
    the taps, A_h, b_dt and the head norm's gain of a KDA layer."""
    m = _dims(config)
    wide = m["kh"] * m["kd"]
    pair = m["d"] * m["rank"] + m["rank"] * wide
    return (4 * m["d"] * wide + 2 * pair + m["d"] * m["kh"]
            + 3 * wide * m["taps"] + m["kh"] + wide + m["kd"])


def expert_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f"]


def expert_bytes(config: dict) -> int:
    """One routed expert's gate, up and down weights."""
    return expert_params(config) * _dims(config)["wbytes"]


def layer_params(config: dict, gqa: bool, held: int | None = None) -> int:
    """A layer as this chip holds it (``held`` routed experts; None = the
    configuration's), norm gains of the residual stream left out: mixer,
    router, shared expert, held experts."""
    m = _dims(config)
    mixer = gqa_mixer_params(config) if gqa else kda_mixer_params(config)
    n = m["held"] if held is None else held
    return (mixer + m["d"] * m["experts"]
            + (m["shared"] + n) * expert_params(config))


def weight_bytes(config: dict) -> int:
    """All the weights held: the layers, embedding and head slices."""
    m = _dims(config)
    n = 2 * m["v"] * m["d"] + sum(
        layer_params(config, gqa) for gqa in m["gqa"])
    return n * m["wbytes"]


def kda_step_bytes_per_call(config: dict, rows: float) -> float:
    """Bytes one call of ``kda_step`` (one layer, ``rows`` live slots) must
    move: each row's state read once and written once, its q, k, decay and
    v read and its output written (float32, a head a channel)."""
    m = _dims(config)
    operands = (3 * m["kd"] + 2 * m["kd"]) * m["kh"] * STATE_BYTES
    return rows * (2 * state_bytes_per_row_layer(config) + operands)


def kda_step_flops_per_call(config: dict, rows: float) -> float:
    """A head's step: the decay (1 a state element), S'^T k, the rank-one
    update and S^T q (2 each)."""
    m = _dims(config)
    return 7.0 * rows * m["kh"] * m["kd"] * m["kd"]


def kda_chunk_bytes_per_call(config: dict, blocks: float, rows: float) -> float:
    """Bytes one call of ``kda_chunk`` (one layer) must move for ``blocks``
    blocks of 64 real tokens over ``rows`` sequences: a block's operands a
    head (U~, W, Q' and the output [64, d]; B [64, 64]; K'^T with the
    block's decay [d, 128]) and each sequence's state in and out."""
    m = _dims(config)
    c, d = KDA_BLOCK, m["kd"]
    block = (4 * c * d + c * c + 2 * c * d) * STATE_BYTES
    return m["kh"] * (blocks * block + rows * 2 * d * d * STATE_BYTES)


def kda_chunk_flops_per_call(config: dict, blocks: float) -> float:
    """A head's block: W S and Q' S (2 x 64 x d x d each), B U (2 x 64 x
    64 x d), K'^T U (2 x d x 64 x d); the decay of the state (d x d)."""
    m = _dims(config)
    c, d = KDA_BLOCK, m["kd"]
    return blocks * m["kh"] * (2.0 * c * d * (3 * d + c) + d * d)


def decode_step_bytes(config: dict, live_tokens: float, batch: float,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read and write: every held weight but
    the embedding (of which ``batch`` rows) and the routed experts no token
    reached (``experts_touched``: held experts with an assignment, summed
    over the layers; None = all of them); every live row's state and
    convolution tail in every KDA layer, read and written; the live
    context's K and V in every GQA layer and the new token's written."""
    m = _dims(config)
    weights = weight_bytes(config) - m["v"] * m["d"] * m["wbytes"]
    if experts_touched is not None:
        idle = len(m["gqa"]) * m["held"] - experts_touched
        weights -= max(0.0, idle) * expert_bytes(config)
    rows = batch * m["d"] * m["wbytes"]
    state = m["n_kda"] * batch * 2 * (
        state_bytes_per_row_layer(config)
        + conv_tail_bytes_per_row_layer(config))
    kv = m["n_gqa"] * kv_bytes_per_token_layer(config) * (live_tokens + batch)
    return weights + rows + state + kv
