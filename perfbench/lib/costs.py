"""The table of peaks and the functions that compute the bytes a step must
move, from shapes alone. Kept with the benchmark so that no PR that claims
a gain can change the yardstick. No JAX here.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e": HBM2e at 819 GB/s. Its other
# peaks (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB) join the table with the
# first metric that reads them. A device that is not here is an error.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"perfbench: no peaks for device kind {device_kind!r}: add its "
            "published peaks, with their source, to the table in lib/costs.py"
        )
    return PEAKS[device_kind]


def _dims(config: dict) -> dict:
    d = config["hidden_size"]
    nh = config["num_attention_heads"]
    return {
        "d": d, "nh": nh, "nkv": config["num_key_value_heads"],
        "hd": config.get("head_dim") or d // nh,
        "f": config["intermediate_size"], "v": config["vocab_size"],
        "layers": config["num_hidden_layers"],
        "tied": bool(config.get("tie_word_embeddings", False)),
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def layer_params(config: dict) -> int:
    """Parameters of one decoder layer: q, k, v, o, gate, up, down and the
    two norm gains."""
    m = _dims(config)
    attn = m["d"] * m["nh"] * m["hd"] * 2 + m["d"] * m["nkv"] * m["hd"] * 2
    return attn + 3 * m["d"] * m["f"] + 2 * m["d"]


def embedding_params(config: dict) -> int:
    m = _dims(config)
    return m["v"] * m["d"]


def head_params(config: dict) -> int:
    m = _dims(config)
    return 0 if m["tied"] else m["d"] * m["v"]


def weight_bytes(config: dict) -> int:
    """All the weights the configuration holds at its depth."""
    m = _dims(config)
    n = (m["layers"] * layer_params(config) + embedding_params(config)
         + head_params(config) + m["d"])
    return n * m["wbytes"]


def kv_bytes_per_token_layer(config: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    m = _dims(config)
    return 2 * m["nkv"] * m["hd"] * kv_bytes


def decode_step_bytes(config: dict, live_tokens: float, batch: float,
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step must read and write: every layer's weights
    and the output head once (the embedding only ``batch`` rows), and the
    live context of every slot, K and V, in every layer, plus the new
    token's K and V written. ``live_tokens`` counts whole live pages, not
    the table's width."""
    m = _dims(config)
    head = (head_params(config) or embedding_params(config)) * m["wbytes"]
    weights = (m["layers"] * layer_params(config) + m["d"]) * m["wbytes"] + head
    rows = batch * m["d"] * m["wbytes"]
    kv = m["layers"] * kv_bytes_per_token_layer(config, kv_bytes) * (
        live_tokens + batch
    )
    return weights + rows + kv


def decode_attention_bytes_per_call(config: dict, live_tokens: float,
                                    batch: float, kv_bytes: int = 2) -> float:
    """Bytes one call of the fused decode-attention kernel (one layer, all
    slots) must move: the live pages' K and V read, the new token's K and
    V written, the queries read and the outputs written."""
    m = _dims(config)
    kv = kv_bytes_per_token_layer(config, kv_bytes) * (live_tokens + batch)
    q_and_out = 2 * batch * m["nh"] * m["hd"] * m["wbytes"]
    return kv + q_and_out
