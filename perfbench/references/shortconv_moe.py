"""Plain reference of a decoder that interleaves gated short-convolution
layers with grouped-query attention layers whose queries and keys are
RMS-normed a head, over a dense SwiGLU MLP in its leading layers and a
sparse expert MLP with a sigmoid router and a selection-only bias in the
rest (LFM2-24B-A2B, ``model_type: lfm2_moe``): the layer equations in
straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")``; the convolution as shifted sums
over the whole sequence, attention over materialised scores, no cache, no
tail pool, no kernel, no sorting or grouping of tokens, no call into
``dynamo_tpu``. Read from the public ``config.json`` keys alone
(``layer_types``, ``conv_L_cache``, ``num_dense_layers``,
``use_expert_bias``, ``rope_parameters``...), never from the
configuration's ``model_spec``.

    RMS(x; w) = x / sqrt(mean(x^2) + norm_eps) * w
    x = E[tokens]; a layer's PUBLISHED index p (layers_kept) decides its
    kind: u = RMS(x; w_op); x = x + Mix_p(u); x = x + FFN_p(RMS(x; w_ffn))
    conv layer (layer_types[p] == "conv"), no bias (conv_bias false):
        [B | C | z] = u W_in              three of hidden_size, in that order
        y_t = B_t * z_t                    by element
        c_t = sum_{j < L} k_j * y_{t - (L - 1) + j}   a channel, L =
            conv_L_cache taps, y before the sequence's start is 0
        Mix = (C_t * c_t) W_out
    attention layer (layer_types[p] == "full_attention"), head width
    hidden_size / num_attention_heads:
        q = u W_q (num_attention_heads), k = u W_k, v = u W_v
            (num_key_value_heads)
        q = RMS(q; w_qn), k = RMS(k; w_kn)   over a head's width, one gain
            vector for all heads
        q, k rotated whole by the position at base
            rope_parameters.rope_theta, half-split pairs (j, j + hd / 2)
        a = softmax_causal(q k^T / sqrt(hd)) v, a KV head serving
            num_attention_heads / num_key_value_heads query heads
        Mix = concat_h(a_h) W_o
    FFN: p < num_dense_layers: (silu(u W_1) * (u W_3)) W_2, width
        intermediate_size; else
        s = sigmoid(u W_g) over num_experts, float32; the
        num_experts_per_tok largest of s + b chosen (use_expert_bias);
        w_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-6)
        (norm_topk_prob); FFN = sum over chosen e of w_e
        (silu(u W_1e) * (u W_3e)) W_2e, width moe_intermediate_size
    logits = RMS(x; w_final) E^T            (the embedding is tied)

Every expert's FFN is computed for every token and weighted (zero where
not chosen), one expert after the other. Departures from the published
model, all stated in the configuration's file: random weights; the depth
(``layers_kept``); the tied head (``assumed``).

It takes nothing the program has made. The weights are drawn here from the
seed by this file's own copy of the recipe the engine is documented to use
(``assumed`` in the configuration's file), a layer at a time, an expert at
a time in the arithmetic, so the reference fits beside the bf16 model.

``quant`` computes the same pass with every weight matrix (the router's
and the embedding's too; not the taps, the gains or the router's bias)
rounded to a lower precision (``"fp8"``: e4m3 with one scale an output
channel; ``"int8"``: symmetric, one scale an output channel): the CONTROL
of the output check.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_WARMED: set = set()  # the (rows, tokens, control) a process has compiled for
ROWS_AT_ONCE = 2  # sequences a layer call: the reference runs beside the model
CONV = ("w_in", "w_out")
ATTN = ("w_q", "w_k", "w_v", "w_o")
DENSE = ("m_gate", "m_up", "m_down")
EXPERTS = ("router", "score_bias", "e_gate", "e_up", "e_down")
NORM_EPS_TOPK = 1e-6  # the family's published constant in the weights' sum


def _dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    kept = config.get("layers_kept") or list(range(layers))
    if config.get("conv_bias"):
        raise SystemExit("shortconv_moe: the convolution carries no bias")
    if not config.get("use_expert_bias"):
        raise SystemExit("shortconv_moe: the router selects with its bias")
    kinds = [config["layer_types"][p] for p in kept]
    if set(kinds) - {"conv", "full_attention"}:
        raise SystemExit(f"shortconv_moe: layer types {sorted(set(kinds))}")
    heads = config["num_attention_heads"]
    return {
        "d": config["hidden_size"], "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "hd": config["hidden_size"] // heads,
        "taps": config["conv_L_cache"],
        "attn": [k == "full_attention" for k in kinds],
        "dense": [p < config["num_dense_layers"] for p in kept],
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "ffn": config["intermediate_size"],
        "f": config["moe_intermediate_size"],
        "experts": config["num_experts"],
        "topk": config["num_experts_per_tok"],
        "scaling": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "eps": float(config["norm_eps"]),
        "vocab": config["vocab_size"],
    }


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, scale, *, shape, dtype):
    # a stack of matrices as one matrix of their rows: the same bits (the
    # generator counts elements, not axes), a third of the compile
    rows = (int(np.prod(shape[:-1])), shape[-1]) if len(shape) > 2 else shape
    draw = jax.random.normal(key, rows, jnp.float32).reshape(shape)
    return (draw * scale).astype(dtype)


class Weights:
    """The model's weights from the seed, a layer at a time: the root key
    split in ``4 + 8 x layers`` and consumed IN ORDER, a layer taking what
    it needs and the next going on from there: the embedding (the head is
    its transpose), then a layer's mixer (conv: ``W_in``, ``W_out``;
    attention: ``W_q, W_k, W_v, W_o``) and then its MLP (dense: gate, up,
    down; experts: one key split in four: router, gate, up, down, each
    projection's experts one ``[experts, in, out]`` array, the selection
    bias ``N(0, 0.1^2)`` float32 on that key folded with 1). What the
    newer layers add is drawn on the root folded with ``2000 + layer``,
    split in 9: a conv layer's taps ``[taps, hidden]`` on the first
    (``N(0, 1 / taps)``); an attention layer's two gains a head on the
    eighth and ninth, ``1 + N(0, 0.1^2)`` (drawn, rounded, one added,
    rounded). ``N(0, 1 / fan_in)``, embedding and router ``N(0, 0.02^2)``,
    the layers' norm gains 1, no bias; everything rounded to the served
    dtype but the router and its bias."""

    def __init__(self, config: dict, seed: int):
        self.m = _dims(config)
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = len(self.m["attn"])
        self._root = jax.random.PRNGKey(seed)
        keys = iter(jax.random.split(self._root, 4 + self.layers * 8))
        self._embed_key = next(keys)
        self._layer_keys = [
            [next(keys) for _ in range((4 if attn else 2) + (3 if dense else 1))]
            for attn, dense in zip(self.m["attn"], self.m["dense"])
        ]

    def _matrix(self, key, shape, scale=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[-2])
        return _draw(key, scale, shape=shape, dtype=self.dtype)

    def embed(self):
        return self._matrix(
            self._embed_key, (self.m["vocab"], self.m["d"]), 0.02)

    def layer(self, i: int, part: str | None = None) -> dict:
        """Layer ``i``'s weights; ``part`` = "mixer" or "mlp" draws that
        half alone (``_warm``)."""
        m = self.m
        keys = self._layer_keys[i]
        n_mix = 4 if m["attn"][i] else 2
        mix, mlp = keys[:n_mix], keys[n_mix:]
        extra = jax.random.split(jax.random.fold_in(self._root, 2000 + i), 9)
        d, hd = m["d"], m["hd"]
        if part == "mlp":
            w = {}
        elif m["attn"][i]:
            one = jnp.ones((hd,), self.dtype)
            w = {
                "w_q": self._matrix(mix[0], (d, m["heads"] * hd)),
                "w_k": self._matrix(mix[1], (d, m["kv_heads"] * hd)),
                "w_v": self._matrix(mix[2], (d, m["kv_heads"] * hd)),
                "w_o": self._matrix(mix[3], (m["heads"] * hd, d)),
                "q_gain": one + _draw(
                    extra[7], 0.1, shape=(hd,), dtype=self.dtype),
                "k_gain": one + _draw(
                    extra[8], 0.1, shape=(hd,), dtype=self.dtype),
            }
        else:
            w = {
                "w_in": self._matrix(mix[0], (d, 3 * d)),
                "w_out": self._matrix(mix[1], (d, d)),
                "taps": self._matrix(extra[0], (m["taps"], d)),
            }
        if part == "mixer":
            return w
        if m["dense"][i]:
            w["m_gate"] = self._matrix(mlp[0], (d, m["ffn"]))
            w["m_up"] = self._matrix(mlp[1], (d, m["ffn"]))
            w["m_down"] = self._matrix(mlp[2], (m["ffn"], d))
            return w
        r1, r2, r3, r4 = jax.random.split(mlp[0], 4)
        n, f = m["experts"], m["f"]
        w["router"] = self._matrix(r1, (d, n), 0.02).astype(jnp.float32)
        w["e_gate"] = self._matrix(r2, (n, d, f))
        w["e_up"] = self._matrix(r3, (n, d, f))
        w["e_down"] = self._matrix(r4, (n, f, d))
        w["score_bias"] = _draw(
            jax.random.fold_in(mlp[0], 1), 0.1, shape=(n,), dtype=jnp.float32)
        return w


def _lower(w, quant):
    """``w`` (float32, [in, out]) rounded to ``quant``, one scale an output
    channel, and back to float32."""
    if quant is None:
        return w
    top = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    if quant == "fp8":
        s = jnp.maximum(top, 1e-12) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if quant == "int8":
        s = jnp.maximum(top, 1e-12) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    raise ValueError(f"unknown control precision {quant!r}")


def _rms(x, eps, gain=None):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if gain is None else y * gain.astype(jnp.float32)


def _rotary(x, positions, theta):
    """x: [S, T, H, D] rotated by its position (axis 1) over all D dims,
    pairs (j, j + D / 2), frequency theta^(-2j / D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq  # [T, half]
    angle = angle[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _conv(x, lw, taps, *, eps, quant):
    """A gated short-convolution layer's mixer over whole sequences from
    an empty past; x: [S, T, d] float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(v.astype(jnp.float32), quant) for k, v in lw.items()}
        T = x.shape[1]
        u = _rms(x, eps)
        b, c, z = jnp.split(u @ w["w_in"], 3, axis=-1)
        y = b * z
        tap = taps.astype(jnp.float32)
        n = tap.shape[0]  # tap[-1] weighs the token itself
        y = jnp.pad(y, ((0, 0), (n - 1, 0), (0, 0)))
        conv = sum(tap[i] * y[:, i:i + T] for i in range(n))
        return x + (c * conv) @ w["w_out"]


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "theta", "eps",
                                   "quant"))
def _attention(x, lw, q_gain, k_gain, *, heads, kv_heads, hd, theta, eps,
               quant):
    """A grouped-query attention layer's mixer over whole sequences, q and
    k normed a head before the rotation; x: [S, T, d] float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(v.astype(jnp.float32), quant) for k, v in lw.items()}
        S, T, _ = x.shape
        pos = jnp.arange(T)
        u = _rms(x, eps)
        q = _rms((u @ w["w_q"]).reshape(S, T, heads, hd), eps, q_gain)
        k = _rms((u @ w["w_k"]).reshape(S, T, kv_heads, hd), eps, k_gain)
        v = (u @ w["w_v"]).reshape(S, T, kv_heads, hd)
        q, k = _rotary(q, pos, theta), _rotary(k, pos, theta)
        q = q.reshape(S, T, kv_heads, heads // kv_heads, hd)
        scores = jnp.einsum("stgqd,sugd->sgqtu", q, k) / jnp.sqrt(
            jnp.float32(hd))
        seen = pos[:, None] >= pos[None, :]
        scores = jnp.where(seen, scores, -jnp.inf)
        a = jnp.einsum(
            "sgqtu,sugd->stgqd", jax.nn.softmax(scores, axis=-1), v)
        return x + a.reshape(S, T, heads * hd) @ w["w_o"]


def _ffn(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


@partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_mlp(x, lw, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(v.astype(jnp.float32), quant) for k, v in lw.items()}
        return x + _ffn(_rms(x, eps), w["m_gate"], w["m_up"], w["m_down"])


@partial(jax.jit, static_argnames=("topk", "scaling", "norm_topk", "eps",
                                   "quant"))
def _experts(x, lw, *, topk, scaling, norm_topk, eps, quant):
    """x plus the expert layer's output over whole sequences: the sigmoid
    router over all the experts, the choice by score plus bias, the
    weights by score alone, every expert's FFN one at a time over every
    token and weighted (zero where it is not among the token's chosen).
    One program: a loop over the experts, not a program an expert."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, eps)
        s = jax.nn.sigmoid(h @ _lower(lw["router"].astype(jnp.float32), quant))
        _, chosen = jax.lax.top_k(s + lw["score_bias"], topk)  # [S, T, topk]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        if norm_topk:
            picked = picked / (
                jnp.sum(picked, axis=-1, keepdims=True) + NORM_EPS_TOPK)
        picked = picked * scaling
        n = lw["e_gate"].shape[0]
        hit = chosen[..., None] == jnp.arange(n)  # [S, T, topk, experts]
        weights = jnp.sum(jnp.where(hit, picked[..., None], 0.0), axis=-2)

        def expert(e, x):
            gate, up, down = (
                _lower(jax.lax.dynamic_index_in_dim(
                    lw[name], e, keepdims=False).astype(jnp.float32), quant)
                for name in ("e_gate", "e_up", "e_down")
            )
            weight = jax.lax.dynamic_slice_in_dim(weights, e, 1, axis=-1)
            return x + weight * _ffn(h, gate, up, down)

        return jax.lax.fori_loop(0, n, expert, x)


def _layer(m: dict, i: int, x, lw, quant, part=None):
    """x through layer ``i`` (``part``: its mixer or its MLP alone). The
    layer's two norms carry gains of 1 and are applied without them."""
    if part == "mlp":
        pass
    elif m["attn"][i]:
        x = _attention(
            x, {k: lw[k] for k in ATTN}, lw["q_gain"], lw["k_gain"],
            heads=m["heads"], kv_heads=m["kv_heads"], hd=m["hd"],
            theta=m["theta"], eps=m["eps"], quant=quant,
        )
    else:
        x = _conv(x, {k: lw[k] for k in CONV}, lw["taps"], eps=m["eps"],
                  quant=quant)
    if part == "mixer":
        return x
    if m["dense"][i]:
        return _dense_mlp(
            x, {k: lw[k] for k in DENSE}, eps=m["eps"], quant=quant)
    return _experts(
        x, {k: lw[k] for k in EXPERTS}, topk=m["topk"], scaling=m["scaling"],
        norm_topk=m["norm_topk"], eps=m["eps"], quant=quant,
    )


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits_at(x, positions, table, *, eps, quant):
    """The final norm and the tied head at chosen positions of x: [S, T,
    d] -> [S, P, vocab]. The control rounds the head one scale a column
    of it: a row of the table."""
    with jax.default_matmul_precision("highest"):
        x = _rms(jnp.take_along_axis(x, positions[:, :, None], axis=1), eps)
        return x @ _lower(table.astype(jnp.float32).T, quant)


@partial(jax.jit, static_argnames=("quant",))
def _embed_rows(table, tokens, *, quant):
    rows = table[tokens].astype(jnp.float32)  # [S, T, d]
    if quant is None:
        return rows
    flat = rows.reshape(-1, rows.shape[-1]).T  # one scale a row of the table
    return _lower(flat, quant).T.reshape(rows.shape)


def _warm(w: "Weights", rows: int, T: int, quant) -> None:
    """Every program a pass will run, compiled once AHEAD on threads of
    their own: a kind of mixer, a kind of MLP, the embedding and the head
    each draw their weights (a compile a shape) and run once on zeros, so
    that the pass itself finds them compiled (as the sibling reference
    ``linear_latent_moe`` does: PERF.md section 6, PR 41); what is
    computed is the pass's own, later, as if this had not run."""
    from concurrent.futures import ThreadPoolExecutor

    m = w.m
    x0 = jnp.zeros((rows, T, m["d"]), jnp.float32)

    def first(flags, want):
        return next((i for i, f in enumerate(flags) if f == want), None)

    def half(i, part):
        _layer(m, i, x0, w.layer(i, part), quant, part).block_until_ready()

    def ends():
        table = w.embed()
        _embed_rows(table, np.zeros((rows, T), np.int32), quant=quant)
        _logits_at(x0, np.zeros((rows, 16), np.int32), table,
                   eps=m["eps"], quant=quant).block_until_ready()

    jobs = [ends] + [
        (lambda i=i, part=part: half(i, part))
        for part, flags in (("mixer", m["attn"]), ("mlp", m["dense"]))
        for i in (first(flags, False), first(flags, True)) if i is not None
    ]
    with ThreadPoolExecutor(len(jobs)) as pool:
        for job in [pool.submit(j) for j in jobs]:
            job.result()


def forward(config: dict, seed: int, tokens, positions, *, quant=None,
            early=None):
    """Logits of whole sequences at chosen positions.

    tokens: int32 [S, T] (pad the tail with anything: attention and the
    convolution are causal, so what follows a position cannot reach it);
    positions: int32 [S, P]. Returns float32 [S, P, vocab]. With ``early =
    (k, positions_k)`` also returns the logits the model's first ``k``
    layers alone would give (the final norm and head on the hidden state
    after layer ``k``) at ``positions_k``: (logits, early_logits)."""
    w = Weights(config, seed)
    m = w.m
    # rows are cut on the host, a few sequences at a time, and never
    # joined; between layers their hidden states wait ON THE HOST: the
    # reference runs in what the served model and its pools leave free
    tokens = np.asarray(tokens, np.int32)
    at = range(0, tokens.shape[0], ROWS_AT_ONCE)
    key = (min(ROWS_AT_ONCE, tokens.shape[0]), tokens.shape[1], quant)
    if key not in _WARMED:
        _WARMED.add(key)
        _warm(w, key[0], key[1], quant)
    table = w.embed()
    xs = [np.asarray(
        _embed_rows(table, tokens[a: a + ROWS_AT_ONCE], quant=quant))
        for a in at]

    def logits_at(where):
        # positions padded to a multiple of 16 (with position 0, cut off
        # again): the check asks for 1, 7 and 10 a row, one program; the
        # head a block of rows at a time
        where = np.asarray(where, np.int32)
        n = where.shape[1]
        where = np.pad(where, ((0, 0), (0, -n % 16)))
        return np.concatenate([
            np.asarray(_logits_at(
                jnp.asarray(x), where[a: a + ROWS_AT_ONCE], table,
                eps=m["eps"], quant=quant))
            for x, a in zip(xs, at)
        ])[:, :n]

    early_logits = None
    for i in range(w.layers):
        lw = w.layer(i)  # drawn once, then a few sequences at a time
        xs = [np.asarray(_layer(m, i, jnp.asarray(x), lw, quant)) for x in xs]
        del lw
        if early is not None and i + 1 == early[0]:
            early_logits = logits_at(early[1])
    logits = logits_at(positions)
    if early is None:
        return logits
    return logits, early_logits
