"""Parameters and bytes of a decoder of gated, QK-normed GQA layers (window
layers and every-fourth full layers over pages of one width) between four
norms a layer, over held sigmoid-routed experts beside a shared one
(Trinity-Mini; configurations whose reference is ``gated_swa_moe``), from
the published ``config.json`` keys and ``layers_kept`` / ``experts`` alone:
a layer's weights by part, the pages' bytes a token a layer as laid out,
and what a call of each decode-attention kind and a step's grouped
products must move. No new kernel came with the family, so nothing here
counts operations: at 64 rows a step every product and both attention
kernels are bound by the bytes they read. Kept with the benchmark so that
no PR that claims a gain can change the yardstick. No JAX here.
"""

from __future__ import annotations

LANES = 128  # the chip's lane tile: a pool's head row is rounded up to it


def _dims(config: dict) -> dict:
    kept = config.get("layers_kept") or list(range(config["num_hidden_layers"]))
    ex = config.get("experts") or {
        "held": config["num_experts"], "published": config["num_experts"]}
    nh = config["num_attention_heads"]
    return {
        "d": config["hidden_size"], "nh": nh,
        "nkv": config["num_key_value_heads"],
        "hd": config.get("head_dim") or config["hidden_size"] // nh,
        "window": int(config["sliding_window"]),
        "windowed": [
            config["layer_types"][p] == "sliding_attention" for p in kept],
        "dense": [p < config["num_dense_layers"] for p in kept],
        "f_dense": config["intermediate_size"],
        "f": config["moe_intermediate_size"],
        "n_shared": int(config.get("num_shared_experts") or 0),
        "held": int(ex["held"]), "experts": int(ex["published"]),
        "v": config["vocab_size"],
        "tied": bool(config.get("tie_word_embeddings", False)),
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def _lanes(dim: int) -> int:
    return -(-dim // LANES) * LANES


def window_layers(config: dict) -> int:
    return _dims(config)["windowed"].count(True)


def full_layers(config: dict) -> int:
    return _dims(config)["windowed"].count(False)


def attention_params(config: dict) -> int:
    """W_q, the gate W_g and W_o (d x H hd each), W_k and W_v, and the two
    gains a head; no bias, no sinks."""
    m = _dims(config)
    return (3 * m["d"] * m["nh"] * m["hd"] + 2 * m["d"] * m["nkv"] * m["hd"]
            + 2 * m["hd"])


def norm_params(config: dict) -> int:
    """The four norms' gains of a layer."""
    return 4 * _dims(config)["d"]


def dense_mlp_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f_dense"]


def expert_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f"]


def expert_bytes(config: dict) -> int:
    """One expert's gate, up and down weights: a step that routes at
    least one token to it cannot avoid reading them."""
    return expert_params(config) * _dims(config)["wbytes"]


def shared_params(config: dict) -> int:
    return _dims(config)["n_shared"] * expert_params(config)


def router_params(config: dict) -> int:
    """W_r over ALL routed experts and the selection bias."""
    m = _dims(config)
    return m["d"] * m["experts"] + m["experts"]


def vocabulary_params(config: dict) -> int:
    """The embedding and, untied, the head."""
    m = _dims(config)
    return (1 if m["tied"] else 2) * m["v"] * m["d"]


def layer_params(config: dict, dense: bool) -> int:
    """A layer as this chip holds it: attention, its four norms, then the
    dense MLP, or the router, the HELD experts and the shared one."""
    m = _dims(config)
    n = attention_params(config) + norm_params(config)
    if dense:
        return n + dense_mlp_params(config)
    return (n + router_params(config) + shared_params(config)
            + m["held"] * expert_params(config))


def weight_bytes(config: dict) -> int:
    """All the weights held: the kept layers, the final norm, the
    embedding's and the head's slices. (The router and its bias are
    float32 and counted at the weights' width: 0.26 M a layer of 134 M.)"""
    m = _dims(config)
    n = vocabulary_params(config) + m["d"] + sum(
        layer_params(config, dn) for dn in m["dense"])
    return n * m["wbytes"]


def kv_bytes_per_token_layer(config: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer's pages, either kind (both keep
    ``num_key_value_heads`` heads of ``head_dim``), as laid out."""
    m = _dims(config)
    return 2 * m["nkv"] * _lanes(m["hd"]) * kv_bytes


def decode_attention_bytes_per_call(config: dict, ctx_tokens: float,
                                    batch: float) -> float:
    """Bytes one call of a decode-attention kernel (one layer, all slots)
    must move: ``ctx_tokens`` of context's K and V read (a window layer:
    the live rows' tokens inside its window; a full layer: all of them),
    the new token's K and V written, the queries read and the outputs
    written. What the kernel moves beyond (whole pages, whole chunks) is
    not counted."""
    m = _dims(config)
    kv = kv_bytes_per_token_layer(config) * (ctx_tokens + batch)
    q_and_out = 2 * batch * m["nh"] * _lanes(m["hd"]) * m["wbytes"]
    return kv + q_and_out


def decode_step_bytes(config: dict, live_tokens: float,
                      in_window_tokens: float, batch: float,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read and write: every held weight but
    the embedding (of which ``batch`` rows) and the experts no token
    reached (``experts_touched``: held experts with an assignment, summed
    over the expert layers; None = all of them), the window layers'
    in-window context and the full layers' whole."""
    m = _dims(config)
    weights = weight_bytes(config) - m["v"] * m["d"] * m["wbytes"]
    if experts_touched is not None:
        idle = m["dense"].count(False) * m["held"] - experts_touched
        weights -= max(0.0, idle) * expert_bytes(config)
    rows = batch * m["d"] * m["wbytes"]
    attn = (
        window_layers(config) * decode_attention_bytes_per_call(
            config, in_window_tokens, batch)
        + full_layers(config) * decode_attention_bytes_per_call(
            config, live_tokens, batch)
    )
    return weights + rows + attn
