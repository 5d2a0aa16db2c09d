"""Parameters and bytes of a decoder that interleaves gated short
convolutions with QK-normed GQA attention over experts ALL held
(LFM2-24B-A2B; configurations whose reference is ``shortconv_moe``), from
the published ``config.json`` keys and ``layers_kept`` alone: a layer's
weights by kind, the tail a row a conv layer, the pages' bytes a token an
attention layer AS LAID OUT (a 64-wide head takes a 128-lane row), and
what a decode step must move. The short convolution has no kernel of its
own, so nothing here counts operations: at 128 rows a step every product
is bound by its weights' bytes. Kept with the benchmark so that no PR that
claims a gain can change the yardstick. No JAX here.
"""

from __future__ import annotations

LANES = 128  # the chip's lane tile: a pool's head row is rounded up to it


def _dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    kept = config.get("layers_kept") or list(range(layers))
    nh = config["num_attention_heads"]
    kinds = [config["layer_types"][p] for p in kept]
    return {
        "d": config["hidden_size"], "nh": nh,
        "nkv": config["num_key_value_heads"],
        "hd": config.get("head_dim") or config["hidden_size"] // nh,
        "taps": config["conv_L_cache"],
        "attn": [k == "full_attention" for k in kinds],
        "dense": [p < config["num_dense_layers"] for p in kept],
        "f_dense": config["intermediate_size"],
        "f": config["moe_intermediate_size"],
        "experts": config["num_experts"],
        "v": config["vocab_size"],
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def _lanes(dim: int) -> int:
    return -(-dim // LANES) * LANES


def conv_mixer_params(config: dict) -> int:
    """W_in [d, B | C | x], W_out and the taps; no bias."""
    m = _dims(config)
    return m["d"] * 3 * m["d"] + m["d"] * m["d"] + m["taps"] * m["d"]


def attention_params(config: dict) -> int:
    """W_q, W_o, W_k, W_v and the two gains a head; no bias."""
    m = _dims(config)
    return (2 * m["d"] * m["nh"] * m["hd"] + 2 * m["d"] * m["nkv"] * m["hd"]
            + 2 * m["hd"])


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


def router_params(config: dict) -> int:
    """W_g and the selection bias."""
    m = _dims(config)
    return m["d"] * m["experts"] + m["experts"]


def vocabulary_params(config: dict) -> int:
    """The embedding; the head is its transpose."""
    m = _dims(config)
    return m["v"] * m["d"]


def layer_params(config: dict, attn: bool, dense: bool) -> int:
    """A layer whole, its two norms' gains left out: its mixer, then the
    dense MLP or the router and every expert."""
    m = _dims(config)
    mixer = attention_params(config) if attn else conv_mixer_params(config)
    if dense:
        return mixer + dense_mlp_params(config)
    return mixer + router_params(config) + m["experts"] * expert_params(config)


def weight_bytes(config: dict) -> int:
    """All the weights held: the kept layers and the (tied) embedding.
    (The router and its bias are float32 and counted at the weights'
    width: 0.13 M of 5.27 B.)"""
    m = _dims(config)
    n = vocabulary_params(config) + sum(
        layer_params(config, a, dn) for a, dn in zip(m["attn"], m["dense"]))
    return n * m["wbytes"]


def tail_bytes_per_row_layer(config: dict) -> int:
    """A sequence's whole state in one conv layer: the ``B * x`` of the
    last taps - 1 tokens, in the served dtype."""
    m = _dims(config)
    return (m["taps"] - 1) * m["d"] * m["wbytes"]


def kv_bytes_per_token_layer(config: dict, kv_bytes: int = 2,
                             laid_out: bool = True) -> int:
    """K and V of one token in one attention layer's pages: as laid out,
    a head's 64 values in a row of 128 lanes (what a page's DMA moves)."""
    m = _dims(config)
    width = _lanes(m["hd"]) if laid_out else m["hd"]
    return 2 * m["nkv"] * width * kv_bytes


def conv_mix_decode_bytes_per_step(config: dict, batch: float) -> float:
    """Bytes the conv layers' mixers must move a decode step: their
    projections and taps, and every live row's tail read and written."""
    m = _dims(config)
    layers = m["attn"].count(False)
    return layers * (conv_mixer_params(config) * m["wbytes"]
                     + 2 * batch * tail_bytes_per_row_layer(config))


def decode_step_bytes(config: dict, live_tokens: float, batch: float,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read and write: every weight but the
    embedding's gather (the tied head reads the table once; ``batch`` rows
    more for the embedding) and the experts no token reached
    (``experts_touched``: experts with an assignment, summed over the
    expert layers; None = all of them); every live row's tail in and out
    in every conv layer; the live context's K and V as laid out in every
    attention layer and the new token's written."""
    m = _dims(config)
    weights = weight_bytes(config)
    if experts_touched is not None:
        idle = m["dense"].count(False) * m["experts"] - experts_touched
        weights -= max(0.0, idle) * expert_bytes(config)
    rows = batch * m["d"] * m["wbytes"]
    tails = m["attn"].count(False) * 2 * batch * tail_bytes_per_row_layer(
        config)
    kv = m["attn"].count(True) * kv_bytes_per_token_layer(config) * (
        live_tokens + batch)
    return weights + rows + tails + kv
