"""Parameters, bytes and operations of a parallel-hybrid decoder
(Falcon-H1: a Mamba-2 / SSD mixer and GQA attention off one norm in every
layer, a dense SwiGLU MLP), from the published ``config.json`` keys alone:
a layer's weights, the state and the convolution tail a row a layer, the
pages' bytes a token a layer, what one call of the decode kernel
(``ssd_step``) and of the chunk form (``ssd_chunk``) must move and
compute, and a whole decode step's bytes. Kept with the benchmark so that
no PR that claims a gain can change the yardstick. No JAX here.
"""

from __future__ import annotations

from lib.costs_latent_moe import peak_flops_s  # noqa: F401  (the one table)

STATE_BYTES = 4  # the SSM state, its decay, dt x and the outputs: float32


def _dims(config: dict) -> dict:
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    g, n = config["mamba_n_groups"], config["mamba_d_state"]
    return {
        "d": config["hidden_size"], "nh": config["num_attention_heads"],
        "nkv": config["num_key_value_heads"], "hd": config["head_dim"],
        "f": config["intermediate_size"], "v": config["vocab_size"],
        "layers": config["num_hidden_layers"],
        "sh": heads, "sp": p, "sg": g, "sn": n, "d_ssm": heads * p,
        "channels": heads * p + 2 * g * n, "taps": config["mamba_d_conv"],
        "chunk": config["mamba_chunk_size"],
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def attention_params(config: dict) -> int:
    """W_q, W_o, W_k, W_v; no bias."""
    m = _dims(config)
    return 2 * m["d"] * m["nh"] * m["hd"] + 2 * m["d"] * m["nkv"] * m["hd"]


def ssm_params(config: dict) -> int:
    """The input projection (z | x | B | C | dt), the output projection,
    the taps and their bias, the gated norm's gain, A_log, D, dt_bias."""
    m = _dims(config)
    return (m["d"] * (m["d_ssm"] + m["channels"] + m["sh"])
            + m["d_ssm"] * m["d"] + (m["taps"] + 1) * m["channels"]
            + m["d_ssm"] + 3 * m["sh"])


def mlp_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f"]


def layer_params(config: dict) -> int:
    """Both mixers, the MLP and the two norms' gains."""
    return (attention_params(config) + ssm_params(config)
            + mlp_params(config) + 2 * config["hidden_size"])


def vocabulary_params(config: dict) -> int:
    """The embedding, and as much again for the untied head."""
    m = _dims(config)
    return m["v"] * m["d"]


def weight_bytes(config: dict) -> int:
    """All the weights held: the layers, the final norm, embedding and
    head. (A_log, D and dt_bias are float32: 96 values a layer, counted at
    the weights' width.)"""
    m = _dims(config)
    tied = bool(config.get("tie_word_embeddings", False))
    n = (m["layers"] * layer_params(config) + m["d"]
         + vocabulary_params(config) * (1 if tied else 2))
    return n * m["wbytes"]


def state_bytes_per_row_layer(config: dict) -> int:
    """A sequence's SSM state in one layer: heads x d_head x d_state."""
    m = _dims(config)
    return m["sh"] * m["sp"] * m["sn"] * STATE_BYTES


def conv_tail_bytes_per_row_layer(config: dict) -> int:
    """A sequence's convolution tail in one layer: the x | B | C
    projections of the last taps - 1 tokens, in the served dtype."""
    m = _dims(config)
    return (m["taps"] - 1) * m["channels"] * m["wbytes"]


def kv_bytes_per_token_layer(config: dict, kv_bytes: int = 2) -> int:
    """K and V of one token in one layer's pages."""
    m = _dims(config)
    return 2 * m["nkv"] * m["hd"] * kv_bytes


def ssd_step_bytes_per_call(config: dict, rows: float) -> float:
    """Bytes one call of ``ssd_step`` (one layer, ``rows`` live slots)
    must move: each row's state read once and written once, its tail
    written, and its operands: dt x and the decay a channel of a head, B
    and C a group, the output (float32)."""
    m = _dims(config)
    operands = (3 * m["sh"] * m["sp"] + 2 * m["sg"] * m["sn"]) * STATE_BYTES
    return rows * (2 * state_bytes_per_row_layer(config)
                   + 2 * conv_tail_bytes_per_row_layer(config) + operands)


def ssd_step_flops_per_call(config: dict, rows: float) -> float:
    """A state element's step: the decay, dt x B and their sum (3), the
    product with C and its sum along the state (2)."""
    m = _dims(config)
    return 5.0 * rows * m["sh"] * m["sp"] * m["sn"]


def ssd_chunk_bytes_per_call(config: dict, tokens: float, rows: float,
                             resumed: float) -> float:
    """Bytes the chunk form must move in one layer of one prefill program
    over ``tokens`` real tokens of ``rows`` sequences, ``resumed`` of which
    continue a state (``start_pos`` > 0): x, B and C read in the served
    dtype, dt read and the output written in float32; a resumed row's
    state read, every row's state written. What the form holds between its
    own products (decay matrices, the chunks' states) is its business, not
    the least it must move."""
    m = _dims(config)
    token = m["channels"] * m["wbytes"] + (m["sh"] + m["d_ssm"]) * STATE_BYTES
    return tokens * token + (rows + resumed) * state_bytes_per_row_layer(config)


def ssd_chunk_flops_per_call(config: dict, chunks: float) -> float:
    """A chunk of Q tokens: C B^T a group (2 Q Q N), the masked product
    with dt x a head (2 Q Q P), what the chunk adds to the state and what
    the carried state adds to its outputs (2 Q P N each), the state's
    decay (P N)."""
    m = _dims(config)
    q, p, n = m["chunk"], m["sp"], m["sn"]
    return chunks * (
        m["sg"] * 2.0 * q * q * n
        + m["sh"] * (2.0 * q * q * p + 4.0 * q * p * n + p * n))


def decode_step_bytes(config: dict, live_tokens: float, batch: float) -> float:
    """Bytes one decode step must read and write: every layer's weights,
    the final norm and the head once (the embedding only ``batch`` rows);
    every live row's state and convolution tail in every layer, read and
    written; the live context's K and V in every layer and the new
    token's written."""
    m = _dims(config)
    weights = weight_bytes(config) - vocabulary_params(config) * m["wbytes"]
    rows = batch * m["d"] * m["wbytes"]
    state = m["layers"] * batch * 2 * (
        state_bytes_per_row_layer(config)
        + conv_tail_bytes_per_row_layer(config))
    kv = m["layers"] * kv_bytes_per_token_layer(config) * (live_tokens + batch)
    return weights + rows + state + kv
