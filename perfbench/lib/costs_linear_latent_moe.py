"""The bytes and operations of a decoder that interleaves KDA layers (full
rank; a recurrent state a sequence) with latent-attention (MLA) layers
(one latent row a token), a leading dense layer, then held experts under a
group-limited router and a shared expert: what a call of each kernel and a
whole decode step must move and make, from shapes alone. Beside
``costs.py``, ``costs_latent_moe.py`` and ``costs_linear_moe.py`` (not
edited), for the configurations whose reference is ``linear_latent_moe``.
Read from the public config keys and from ``layers_kept`` / ``experts``.
Every count errs low: the kernels' float32 products are counted once, not
by the passes the matrix unit makes of them, and what cannot be known from
the sampler's totals is left out or taken at its least. No JAX here.
"""

from __future__ import annotations

from lib.costs_latent_moe import peak_flops_s  # noqa: F401  (the one table)

LANES = 128  # the chip's lane tile: a pool's row is rounded up to it
KDA_BLOCK = 64  # tokens a block of the chunkwise form (ops/attention.py)
KDA_SUB = 16  # and a sub-block inside it
F32 = 4  # the state, and the KDA kernels' operands, are float32


def _dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    kept = config.get("layers_kept") or list(range(layers))
    ex = config.get("experts") or {"held": config["num_experts"],
                                   "published": config["num_experts"]}
    group = config["layer_group_size"]
    latent = [(p + 1) % group == 0 for p in kept]
    dense = [p < config["first_k_dense_replace"] for p in kept]
    return {
        "d": config["hidden_size"], "h": config["num_attention_heads"],
        "hd": config["head_dim"], "taps": config["short_conv_kernel_size"],
        "latent": latent, "dense": dense, "n_latent": sum(latent),
        "n_kda": len(latent) - sum(latent),
        "n_expert": dense.count(False),
        "dc": config["kv_lora_rank"], "dn": config["qk_nope_head_dim"],
        "dr": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
        "f_dense": config["intermediate_size"],
        "f": config["moe_intermediate_size"],
        "fs": config["moe_shared_expert_intermediate_size"],
        "held": int(ex["held"]), "experts": int(ex["published"]),
        "v": config["vocab_size"],
        "wbytes": 2 if config.get("torch_dtype", "bfloat16") in (
            "bfloat16", "float16") else 4,
    }


def _lanes(dim: int) -> int:
    return -(-dim // LANES) * LANES


# ------------------------------------------------------------- parameters


def kda_mixer_params(config: dict) -> int:
    """W_q, W_k, W_v, W_o, the decay's and the output gate's full-rank
    projections, beta, the taps, A_h, b_dt and the head norm's gain."""
    m = _dims(config)
    wide = m["h"] * m["hd"]
    return (6 * m["d"] * wide + m["d"] * m["h"] + 3 * wide * m["taps"]
            + m["h"] + wide + m["hd"])


def latent_mixer_params(config: dict) -> int:
    """W_q (no low rank), W_kva, W_kvb, W_o, the gate by head and the
    latent's norm gain."""
    m = _dims(config)
    return (m["d"] * m["h"] * (m["dn"] + m["dr"]) + m["d"] * (m["dc"] + m["dr"])
            + m["dc"] * m["h"] * (m["dn"] + m["dv"]) + m["h"] * m["dv"] * m["d"]
            + m["d"] * m["h"] + m["dc"])


def router_params(config: dict) -> int:
    m = _dims(config)
    return m["d"] * m["experts"]


def expert_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f"]


def shared_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["fs"]


def dense_mlp_params(config: dict) -> int:
    m = _dims(config)
    return 3 * m["d"] * m["f_dense"]


def expert_bytes(config: dict) -> int:
    """One routed expert's gate, up and down weights."""
    return expert_params(config) * _dims(config)["wbytes"]


def layer_params(config: dict, latent: bool, dense: bool,
                 held: int | None = None) -> int:
    """A layer as this chip holds it (``held`` routed experts; None = the
    configuration's), norm gains of the residual stream left out: the
    mixer, then the dense MLP, or router + shared expert + held experts."""
    m = _dims(config)
    mixer = latent_mixer_params(config) if latent else kda_mixer_params(config)
    if dense:
        return mixer + dense_mlp_params(config)
    n = m["held"] if held is None else held
    return (mixer + router_params(config) + shared_params(config)
            + n * expert_params(config))


def weight_bytes(config: dict) -> int:
    """All the weights held: the layers, embedding and head slices."""
    m = _dims(config)
    n = 2 * m["v"] * m["d"] + sum(
        layer_params(config, lat, dense)
        for lat, dense in zip(m["latent"], m["dense"]))
    return n * m["wbytes"]


# ------------------------------------------------------------------ caches


def state_bytes_per_row_layer(config: dict) -> int:
    """One sequence's state in one KDA layer: heads x d_k x d_v float32."""
    m = _dims(config)
    return m["h"] * m["hd"] * m["hd"] * F32


def conv_tail_bytes_per_row_layer(config: dict) -> int:
    """The last ``taps - 1`` tokens' q, k and v projections, served dtype."""
    m = _dims(config)
    return (m["taps"] - 1) * 3 * m["h"] * m["hd"] * m["wbytes"]


def latent_bytes_per_token(config: dict, kv_bytes: int = 2,
                           laid_out: bool = False) -> int:
    """One token's row in one latent layer, ``[c | k_r]``: 576 values =
    1,152 B in bfloat16; ``laid_out`` = as the pool holds it, rounded up
    to the lane tile (640 lanes = 1,280 B: what a page's DMA moves)."""
    m = _dims(config)
    n = m["dc"] + m["dr"]
    return (_lanes(n) if laid_out else n) * kv_bytes


# ----------------------------------------------------------------- kernels


def kda_step_bytes_per_call(config: dict, rows: float) -> float:
    """Bytes one call of ``kda_step`` (one layer, ``rows`` live slots) must
    move: each row's state read once and written once, its q, k, decay and
    v read and its output written (float32, a head a channel)."""
    m = _dims(config)
    operands = 5 * m["hd"] * m["h"] * F32
    return rows * (2 * state_bytes_per_row_layer(config) + operands)


def kda_step_flops_per_call(config: dict, rows: float) -> float:
    """A head's step: the decay (1 a state element), S'^T k, the rank-one
    update and S^T q (2 each)."""
    m = _dims(config)
    return 7.0 * rows * m["h"] * m["hd"] * m["hd"]


def kda_chunk_bytes_per_call(config: dict, tokens: float, rows: float,
                             resumed: float) -> float:
    """Bytes one call of ``kda_chunk`` (one layer) must move AS THE KERNEL
    DOES THE WORK (a block's operands formed in VMEM): q, k, v and the log
    decay of every real token read and its output written (float32, ``H
    d`` wide each), beta read, and the state of every row written once
    and, for a row that resumes one (``start_pos`` > 0), read once."""
    m = _dims(config)
    wide = m["h"] * m["hd"]
    return (tokens * (5 * wide + m["h"]) * F32
            + (rows + resumed) * state_bytes_per_row_layer(config))


def kda_chunk_flops_per_call(config: dict, blocks: float) -> float:
    """A head's block of 64 tokens in sub-blocks of 16: A and B from the
    products of a sub-block's rows against the keys up to its end (2 x 2 x
    16 x d x 16 (1 + 2 + 3 + 4)); ``T^-1 [beta v | beta k e^G]`` by
    forward substitution (64 x 64 / 2 multiply-adds over 2 d columns); W S
    and Q' S (2 x 64 x d x d each), B U (2 x 64 x 64 x d), K'^T U (2 x d x
    64 x d) and the state's decay (d x d). ``blocks``: blocks that hold a
    real token, the call's rows summed."""
    m = _dims(config)
    c, s, d = KDA_BLOCK, KDA_SUB, m["hd"]
    n_sub = c // s
    a_and_b = 2 * 2.0 * s * d * s * (n_sub * (n_sub + 1) // 2)
    solve = 1.0 * c * c * 2 * d
    state = 2.0 * c * d * (3 * d + c) + d * d
    return blocks * m["h"] * (a_and_b + solve + state)


def latent_decode_bytes_per_call(config: dict, live_tokens: float,
                                 batch: float) -> float:
    """Bytes one call of ``attn_latent`` (one layer, all slots) must move:
    the live context's rows as laid out, read ONCE (they are key and
    value), the new rows written, the absorbed queries read and the
    latent outputs written."""
    m = _dims(config)
    row = latent_bytes_per_token(config, laid_out=True)
    q_and_out = batch * m["h"] * (
        _lanes(m["dc"] + m["dr"]) + m["dc"]) * m["wbytes"]
    return row * (live_tokens + batch) + q_and_out


def latent_decode_flops_per_call(config: dict, live_tokens: float,
                                 batch: float) -> float:
    """Multiply-adds x 2 of one call: every head scores a row's ``dc +
    dr`` values and accumulates its ``dc`` (the lane padding's are not
    counted): 53 a byte at 32 heads, under the chip's 240, so the bytes
    bound the call; the reader takes whichever is longer."""
    m = _dims(config)
    return 2.0 * m["h"] * (2 * m["dc"] + m["dr"]) * (live_tokens + batch)


# -------------------------------------------------------------- the step


def decode_step_bytes(config: dict, live_tokens: float, batch: float,
                      experts_touched: float | None = None) -> float:
    """Bytes one decode step must read and write: every held weight but
    the embedding (of which ``batch`` rows) and the routed experts no token
    reached (``experts_touched``: held experts with an assignment, summed
    over the expert layers; None = all of them); every live row's state
    and convolution tail in every KDA layer, read and written; the live
    latents of every latent layer and the new rows written."""
    m = _dims(config)
    weights = weight_bytes(config) - m["v"] * m["d"] * m["wbytes"]
    if experts_touched is not None:
        idle = m["n_expert"] * m["held"] - experts_touched
        weights -= max(0.0, idle) * expert_bytes(config)
    rows = batch * m["d"] * m["wbytes"]
    state = m["n_kda"] * batch * 2 * (
        state_bytes_per_row_layer(config)
        + conv_tail_bytes_per_row_layer(config))
    latents = m["n_latent"] * latent_bytes_per_token(
        config, laid_out=True) * (live_tokens + batch)
    return weights + rows + state + latents
