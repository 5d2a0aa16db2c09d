"""Parameters, bytes and operations of a decoder-hybrid-decoder
(Phi-4-mini-flash, SambaY; configurations whose reference is ``sambay``):
Mamba-1 scan layers beside window layers of differential attention, one
full-attention layer whose pages the cross layers above read, GMU layers
that gate the last scan's output; from the published ``config.json`` keys,
``layers_kept`` and ``published_layers`` alone: a layer's weights by kind,
the state and the convolution tail a row a scan layer, the pages' bytes a
token a paged layer, what one call of the scan's decode kernel and of each
decode-attention kind must move, and a whole decode step's bytes. Kept with
the benchmark so that no PR that claims a gain can change the yardstick.
No JAX here.
"""

from __future__ import annotations

import math

from lib.costs_latent_moe import peak_flops_s  # noqa: F401  (the one table)

STATE_BYTES = 4  # the scan's state, dt, dt x and the outputs: float32
SCAN_STATE, SCAN_CONV = 16, 4  # Mamba-1's own: no key of the config


def _dims(config: dict) -> dict:
    d, nh = config["hidden_size"], config["num_attention_heads"]
    kept = list(config.get("layers_kept")
                or range(config["num_hidden_layers"]))
    half = int(
        config.get("published_layers") or config["num_hidden_layers"]) // 2
    return {
        "d": d, "nh": nh, "nkv": config["num_key_value_heads"],
        "hd": d // nh, "f": config["intermediate_size"],
        "c": 2 * d, "n": SCAN_STATE, "r": math.ceil(d / 16),
        "taps": SCAN_CONV, "window": int(config["sliding_window"]),
        "v": config["vocab_size"], "kept": kept, "half": half,
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def layer_kinds(config: dict) -> list[str]:
    """What each KEPT layer is: scan, window, full, gmu or cross."""
    m = _dims(config)

    def kind(l: int) -> str:
        if l % 2 == 0:
            return "scan" if l <= m["half"] else "gmu"
        if l <= m["half"] + 1:
            return "full" if l == m["half"] + 1 else "window"
        return "cross"

    return [kind(l) for l in m["kept"]]


def layers_of(config: dict, kind: str) -> int:
    return layer_kinds(config).count(kind)


def mixer_params(config: dict, kind: str) -> int:
    """A layer's mixer by kind. Scan: in_proj, the taps and their bias,
    x_proj, dt_proj and its bias, A_log, D, out_proj. Self attention:
    Wqkv and out_proj with their biases, the four lambda vectors, the pair
    norm's gain. Cross: the queries' and the output's alone. GMU: two
    projections."""
    m = _dims(config)
    d, c, n, r = m["d"], m["c"], m["n"], m["r"]
    q = m["nh"] * m["hd"]
    if kind == "scan":
        return (d * 2 * c + (m["taps"] + 1) * c + c * (r + 2 * n)
                + r * c + c + c * n + c + c * d)
    if kind == "gmu":
        return 2 * d * c
    extras = 4 * m["hd"] + 2 * m["hd"]
    if kind == "cross":
        return d * q + q + q * d + d + extras
    kv = 2 * m["nkv"] * m["hd"]
    return d * (q + kv) + q + kv + q * d + d + extras


def mlp_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f"]


def layer_params(config: dict, kind: str) -> int:
    """The mixer, the MLP and the two LayerNorms' gains and biases."""
    return (mixer_params(config, kind) + mlp_params(config)
            + 4 * config["hidden_size"])


def vocabulary_params(config: dict) -> int:
    """The embedding, which is the head, and the final LayerNorm."""
    m = _dims(config)
    return m["v"] * m["d"] + 2 * m["d"]


def total_params(config: dict) -> int:
    return vocabulary_params(config) + sum(
        layer_params(config, k) for k in layer_kinds(config))


def kv_bytes_per_token_layer(config: dict) -> int:
    """K and V of one token in one paged layer, as laid out: a pair of
    64-wide KV heads fills a 128-lane row, so no lane is padding."""
    m = _dims(config)
    return 2 * m["nkv"] * m["hd"] * m["wbytes"]


def state_bytes_per_row_layer(config: dict) -> int:
    m = _dims(config)
    return m["n"] * m["c"] * STATE_BYTES


def tail_bytes_per_row_layer(config: dict) -> int:
    m = _dims(config)
    return (m["taps"] - 1) * m["c"] * m["wbytes"]


def scan_step_bytes_per_call(config: dict, batch: float) -> float:
    """One call of the scan's decode kernel (a layer, a step): every live
    row's state read once and written once, its new tail written, its
    operand rows (dt, dt x in, y out: float32 [C]; B, C: float32 [N]),
    and ``A`` [N, C] float32 once a call."""
    m = _dims(config)
    row = (2 * state_bytes_per_row_layer(config)
           + tail_bytes_per_row_layer(config)
           + STATE_BYTES * (3 * m["c"] + 2 * m["n"]))
    return batch * row + STATE_BYTES * m["n"] * m["c"]


def scan_step_flops_per_call(config: dict, batch: float) -> float:
    """An exponential, its product's multiply, the decay's multiply-add,
    the input's multiply-add and the output's multiply-add an element of
    the state."""
    m = _dims(config)
    return batch * 8 * m["n"] * m["c"]


def attention_bytes_per_call(config: dict, ctx_tokens: float) -> float:
    """One call of a decode-attention kernel over ``ctx_tokens`` tokens
    of ONE layer's pages (the live rows' summed): K and V as laid out,
    read once. The queries, the new rows and the outputs are a few KB a
    row beside it and are left out."""
    return ctx_tokens * kv_bytes_per_token_layer(config)


def decode_step_bytes(config: dict, ctx_tokens: float, batch: float,
                      window_tokens: float | None = None) -> float:
    """The whole step: every layer's weights and the tied head once, the
    live rows' state in and out and tail in every scan layer, the window
    layers' in-window pages (``window_tokens``; all the context where
    None), and the ONE full pool once a reader (its own layer and every
    cross layer)."""
    m = _dims(config)
    weights = m["wbytes"] * total_params(config)
    scan = layers_of(config, "scan") * scan_step_bytes_per_call(config, batch)
    win = layers_of(config, "window") * attention_bytes_per_call(
        config, ctx_tokens if window_tokens is None else window_tokens)
    shared = (layers_of(config, "full") + layers_of(config, "cross")) * (
        attention_bytes_per_call(config, ctx_tokens))
    return weights + scan + win + shared
