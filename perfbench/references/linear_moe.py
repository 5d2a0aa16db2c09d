"""Plain reference of a decoder that interleaves linear-attention layers
(Kimi Delta Attention, arXiv:2510.26692) with gated softmax GQA layers that
carry no positional term, over a sparse expert MLP with a shared expert
(Solar-Open2-250B): the layer equations in straightforward ``jax.numpy``,
float32, ``default_matmul_precision("highest")``; the recurrence as a
``lax.scan`` A TOKEN with no chunkwise form, no cache, no state pool, no
kernel, no sorting or grouping of tokens, no call into ``dynamo_tpu``. Read
from the public ``config.json`` keys alone (``gqa_layers``, ``use_rope``,
``use_gqa_gate``, ``linear_attn_config``, ``kda_use_full_proj``,
``kda_allow_neg_eigval``, ``n_shared_experts``...), never from the
configuration's ``model_spec``.

    x = E[tokens]; u = rms(x) the normed input of a sub-layer, eps
    rms_norm_eps; pre-norm residuals around mixer and MLP.
    GQA layer (published index in gqa_layers):
        q = u W_q (H heads x D), k = u W_k, v = u W_v (KV heads x D);
        no rotary and no other positional term (use_rope false);
        a_i = softmax_causal(q_i k_j^T / sqrt(D)) v_j, j = i // (H / KV);
        out = (concat_i(a_i) * sigmoid(u W_g)) W_o          (use_gqa_gate)
    KDA layer (every other), a head h of linear_attn_config.num_heads,
    d_k = d_v = linear_attn_config.head_dim:
        q~, k~, v~ = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
            conv: causal, depthwise, short_conv_kernel_size taps a channel
        q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(d_k);  k = k~ / sqrt(|k~|^2 + 1e-6)
        g_t = -exp(A_h) softplus(u W_fd W_fu + b_dt)   a channel, rank d_k
        beta_t = 2 sigmoid(u w_b,h)                    (kda_allow_neg_eigval)
        S' = diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S^T q_t                       S [d_k, d_v] float32, from zero
        out = concat_h(rms_h(o_t) * sigmoid(u W_gd W_gu)) W_o
    MLP (every layer; first_k_dense_replace 0):
        s = sigmoid(u W_r) over all routed experts; the num_experts_per_tok
        largest of s + b chosen; w_e = routed_scaling_factor s_e / sum of
        the chosen s (norm_topk_prob);
        x = x + sum over chosen e of w_e FFN_e(u) + FFN_shared(u),
        FFN(u) = (silu(u W_g) * (u W_u)) W_d
    logits = rms(x) W_head

Every held expert's FFN is computed for every token and weighted (zero
where not chosen), one expert after the other. Departures from the published model, all stated in the
configuration's file: random weights; the depth (``layers_kept``); one
chip's share of an expert-parallel deployment (``experts``: the router
scores all ``published``, the ``held`` from ``first`` are here, the chosen
that are absent add nothing, the shared expert is whole); ``vocab_size``
rows of the embedding and columns of the head.

It takes nothing the program has made. The weights are drawn here from the
seed by this file's own copy of the recipe the engine is documented to use
(``assumed`` in the configuration's file), a layer at a time, an expert at
a time in the arithmetic, so the reference fits beside the bf16 model.

``quant`` computes the same pass with every weight matrix (the router's
and the low-rank pairs' too; not the taps, ``A_h`` and ``b_dt``) rounded to
a lower precision (``"fp8"``: e4m3 with one scale an output channel;
``"int8"``: symmetric, one scale an output channel): the CONTROL of the
output check.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ROWS_AT_ONCE = 4  # sequences a layer call: the reference runs beside the model
GQA = ("w_q", "w_k", "w_v", "w_o", "w_gate_attn")
KDA = ("w_q", "w_k", "w_v", "w_o", "w_f_down", "w_f_up", "w_g_down",
       "w_g_up", "w_beta")
SHARED = ("s_gate", "s_up", "s_down")


def _dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    kept = config.get("layers_kept") or list(range(layers))
    ex = config.get("experts") or {
        "published": config["n_routed_experts"],
        "held": config["n_routed_experts"], "first": 0,
    }
    lin = config["linear_attn_config"]
    if config.get("use_rope", True):
        raise SystemExit("linear_moe: the softmax layers carry no rotary")
    if config.get("first_k_dense_replace"):
        raise SystemExit("linear_moe: every layer has experts")
    return {
        "d": config["hidden_size"], "nh": config["num_attention_heads"],
        "nkv": config["num_key_value_heads"], "hd": config["head_dim"],
        "gqa": [i in config["gqa_layers"] for i in kept],
        "gate": bool(config.get("use_gqa_gate")),
        "kh": lin["num_heads"], "kd": lin["head_dim"],
        "taps": lin["short_conv_kernel_size"],
        "rank": (lin["num_heads"] * lin["head_dim"]
                 if config.get("kda_use_full_proj") else lin["head_dim"]),
        "beta_max": 2.0 if config.get("kda_allow_neg_eigval") else 1.0,
        "f": config["moe_intermediate_size"],
        "shared": int(config.get("n_shared_experts") or 0),
        "experts": int(ex["published"]), "held": int(ex["held"]),
        "first": int(ex["first"]), "topk": config["num_experts_per_tok"],
        "scaling": float(config.get("routed_scaling_factor") or 1.0),
        "norm_topk": bool(config["norm_topk_prob"]),
        "eps": float(config["rms_norm_eps"]),
        "vocab": config["vocab_size"],
    }


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, scale, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class Weights:
    """The model's weights from the seed, a layer at a time: the root key
    split in ``4 + 8 x layers``, consumed in order: embedding, head, then a
    layer ``W_q, W_k, W_v, W_o``, one key for its routed experts (split in
    four: router, gate, up, down, each projection's held experts one ``[held,
    in, out]`` array; the correction bias ``N(0, 0.1^2)`` float32 on that
    key folded with 1) and one for the shared expert (split in three: gate,
    up, down). What the newer layers add is drawn on the root folded with
    ``2000 + layer``, split in 9: a GQA layer's gate on the first; a KDA
    layer's taps for q, k, v on the first three (``N(0, 1 / taps)``),
    ``W_fd``, ``W_fu`` on the next two, the sixth split in two for ``A_h``
    (``exp(A_h)`` uniform in (0.02, 0.1)) and the decay a channel (``-ln
    alpha`` log-uniform between ``1e-4`` and ``-ln 0.9``, so alpha spans
    (0.9, 0.9999); ``b_dt = softplus^-1(-ln alpha / exp(A_h))``), then
    ``W_gd``, ``W_gu``, ``w_b``. ``N(0, 1 / fan_in)``, embedding and router
    ``N(0, 0.02^2)``, norm gains 1, no bias but ``b_dt``; everything rounded
    to the served dtype but the router's bias, ``A_h`` and ``b_dt``."""

    def __init__(self, config: dict, seed: int):
        self.m = _dims(config)
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = len(self.m["gqa"])
        self._root = jax.random.PRNGKey(seed)
        keys = iter(jax.random.split(self._root, 4 + self.layers * 8))
        self._embed_key, self._head_key = next(keys), next(keys)
        per_layer = 6 if self.m["shared"] else 5
        self._layer_keys = [
            [next(keys) for _ in range(per_layer)] + [None] * (6 - per_layer)
            for _ in range(self.layers)
        ]

    def _matrix(self, key, shape, scale=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[-2])
        return _draw(key, scale, shape=shape, dtype=self.dtype)

    def embed(self):
        return self._matrix(
            self._embed_key, (self.m["vocab"], self.m["d"]), 0.02)

    def head(self):
        return self._matrix(self._head_key, (self.m["d"], self.m["vocab"]))

    def layer(self, i: int) -> dict:
        m = self.m
        k_q, k_k, k_v, k_o, k_moe, k_shared = self._layer_keys[i]
        extra = jax.random.split(jax.random.fold_in(self._root, 2000 + i), 9)
        d = m["d"]
        if m["gqa"][i]:
            nh, nkv, hd = m["nh"], m["nkv"], m["hd"]
            w = {
                "w_q": self._matrix(k_q, (d, nh * hd)),
                "w_k": self._matrix(k_k, (d, nkv * hd)),
                "w_v": self._matrix(k_v, (d, nkv * hd)),
                "w_o": self._matrix(k_o, (nh * hd, d)),
            }
            if m["gate"]:
                w["w_gate_attn"] = self._matrix(extra[0], (d, nh * hd))
        else:
            hd_all, r = m["kh"] * m["kd"], m["rank"]
            ka, kt = jax.random.split(extra[5])
            a = jax.random.uniform(ka, (m["kh"],), jnp.float32, 0.02, 0.1)
            tau = jnp.exp(jax.random.uniform(
                kt, (m["kh"], m["kd"]), jnp.float32,
                jnp.log(1e-4), jnp.log(-jnp.log(0.9))))
            w = {
                "w_q": self._matrix(k_q, (d, hd_all)),
                "w_k": self._matrix(k_k, (d, hd_all)),
                "w_v": self._matrix(k_v, (d, hd_all)),
                "w_o": self._matrix(k_o, (hd_all, d)),
                "taps": [self._matrix(extra[j], (m["taps"], hd_all))
                         for j in range(3)],
                "w_f_down": self._matrix(extra[3], (d, r)),
                "w_f_up": self._matrix(extra[4], (r, hd_all)),
                "a_log": jnp.log(a),
                "dt_bias": jnp.log(jnp.expm1(tau / a[:, None])),
                "w_g_down": self._matrix(extra[6], (d, r)),
                "w_g_up": self._matrix(extra[7], (r, hd_all)),
                "w_beta": self._matrix(extra[8], (d, m["kh"])),
            }
        r1, r2, r3, r4 = jax.random.split(k_moe, 4)
        held, f = m["held"], m["f"]
        w["router"] = self._matrix(r1, (d, m["experts"]), 0.02)
        w["e_gate"] = self._matrix(r2, (held, d, f))
        w["e_up"] = self._matrix(r3, (held, d, f))
        w["e_down"] = self._matrix(r4, (held, f, d))
        w["score_bias"] = _draw(
            jax.random.fold_in(k_moe, 1), 0.1,
            shape=(m["experts"],), dtype=jnp.float32,
        )
        if m["shared"]:
            s1, s2, s3 = jax.random.split(k_shared, 3)
            fs = f * m["shared"]
            w["s_gate"] = self._matrix(s1, (d, fs))
            w["s_up"] = self._matrix(s2, (d, fs))
            w["s_down"] = self._matrix(s3, (fs, d))
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


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


@partial(jax.jit, static_argnames=("heads", "kv", "hd", "eps", "quant"))
def _gqa(x, lw, *, heads, kv, hd, eps, quant):
    """A gated softmax layer without positions over whole sequences; x:
    [S, T, d] float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(v.astype(jnp.float32), quant) for k, v in lw.items()}
        S, T, _ = x.shape
        u = _rms(x, eps)
        q = (u @ w["w_q"]).reshape(S, T, heads, hd)
        k = (u @ w["w_k"]).reshape(S, T, kv, hd)
        v = (u @ w["w_v"]).reshape(S, T, kv, hd)
        # query head i reads KV head i // (heads / kv)
        k, v = (jnp.repeat(y, heads // kv, axis=2) for y in (k, v))
        scores = jnp.einsum("sthd,suhd->shtu", q, k) / jnp.sqrt(jnp.float32(hd))
        pos = jnp.arange(T)
        seen = pos[:, None] >= pos[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        a = jnp.einsum(
            "shtu,suhd->sthd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(S, T, heads * hd)
        if "w_gate_attn" in w:
            a = a * jax.nn.sigmoid(u @ w["w_gate_attn"])
        return x + a @ w["w_o"]


@partial(jax.jit, static_argnames=("heads", "hd", "beta_max", "eps", "quant"))
def _kda(x, lw, taps, a_log, dt_bias, *, heads, hd, beta_max, eps, quant):
    """A KDA layer over whole sequences, the state from zero, a token at a
    time; x: [S, T, d] float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(v.astype(jnp.float32), quant) for k, v in lw.items()}
        S, T, _ = x.shape
        u = _rms(x, eps)

        def conv(y, tap):  # causal, depthwise: tap[-1] weighs the token itself
            tap = tap.astype(jnp.float32)
            n = tap.shape[0]
            y = jnp.pad(y, ((0, 0), (n - 1, 0), (0, 0)))
            return sum(tap[i] * y[:, i:i + T] for i in range(n))

        q, k, v = (
            jax.nn.silu(conv(u @ w[name], tap)).reshape(S, T, heads, hd)
            for name, tap in zip(("w_q", "w_k", "w_v"), taps)
        )
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / jnp.sqrt(
            jnp.float32(hd))
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        f = (u @ w["w_f_down"]) @ w["w_f_up"]
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            f.reshape(S, T, heads, hd) + dt_bias)
        beta = beta_max * jax.nn.sigmoid(u @ w["w_beta"])  # [S, T, heads]

        def token(state, at):  # state: [S, heads, d_k, d_v]
            q_t, k_t, v_t, g_t, b_t = at
            decayed = jnp.exp(g_t)[..., None] * state
            seen = jnp.einsum("shkv,shk->shv", decayed, k_t)
            state = decayed + jnp.einsum(
                "shk,shv->shkv", k_t, b_t[..., None] * (v_t - seen))
            return state, jnp.einsum("shkv,shk->shv", state, q_t)

        _, o = jax.lax.scan(
            token, jnp.zeros((S, heads, hd, hd), jnp.float32),
            tuple(jnp.moveaxis(y, 1, 0) for y in (q, k, v, g, beta)),
        )
        o = _rms(jnp.moveaxis(o, 0, 1), eps).reshape(S, T, heads * hd)
        gate = jax.nn.sigmoid((u @ w["w_g_down"]) @ w["w_g_up"])
        return x + (o * gate) @ w["w_o"]


@partial(jax.jit, static_argnames=("topk", "first", "held", "scaling",
                                   "norm_topk", "shared", "eps", "quant"))
def _mlp(x, lw, *, topk, first, held, scaling, norm_topk, shared, eps, quant):
    """x plus the expert layer's output over whole sequences: the router
    over ALL the routed experts, the HELD experts' FFNs one at a time,
    each over every token and weighted (zero where it is not among the
    token's chosen), and the shared expert. One program: a loop over the
    held experts, not a program an expert."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, eps)
        s = jax.nn.sigmoid(h @ _lower(lw["router"].astype(jnp.float32), quant))
        _, chosen = jax.lax.top_k(s + lw["score_bias"], topk)  # [S, T, topk]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        if norm_topk:
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        picked = picked * scaling
        here = jnp.arange(first, first + held)
        hit = chosen[..., None] == here  # [S, T, topk, held]
        weights = jnp.sum(jnp.where(hit, picked[..., None], 0.0), axis=-2)

        def expert(e, x):
            gate, up, down = (
                _lower(jax.lax.dynamic_index_in_dim(
                    lw[name], e, keepdims=False).astype(jnp.float32), quant)
                for name in ("e_gate", "e_up", "e_down")
            )
            weight = jax.lax.dynamic_slice_in_dim(weights, e, 1, axis=-1)
            return x + weight * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

        if held:
            x = jax.lax.fori_loop(0, held, expert, x)
        if shared:
            w = {k: _lower(lw[k].astype(jnp.float32), quant) for k in SHARED}
            x = x + (jax.nn.silu(h @ w["s_gate"]) * (h @ w["s_up"])) @ w["s_down"]
        return x


def _layer(m: dict, i: int, x, lw, quant):
    if m["gqa"][i]:
        x = _gqa(
            x, {k: lw[k] for k in GQA if k in lw}, heads=m["nh"],
            kv=m["nkv"], hd=m["hd"], eps=m["eps"], quant=quant,
        )
    else:
        x = _kda(
            x, {k: lw[k] for k in KDA}, lw["taps"], lw["a_log"],
            lw["dt_bias"], heads=m["kh"], hd=m["kd"],
            beta_max=m["beta_max"], eps=m["eps"], quant=quant,
        )
    names = ("router", "score_bias", "e_gate", "e_up", "e_down") + (
        SHARED if m["shared"] else ())
    return _mlp(
        x, {k: lw[k] for k in names}, topk=m["topk"], first=m["first"],
        held=m["held"], scaling=m["scaling"], norm_topk=m["norm_topk"],
        shared=bool(m["shared"]), eps=m["eps"], quant=quant,
    )


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits_at(x, positions, head, *, eps, quant):
    """The final norm and the head at chosen positions of x: [S, T, d] ->
    [S, P, vocab]."""
    with jax.default_matmul_precision("highest"):
        x = _rms(jnp.take_along_axis(x, positions[:, :, None], axis=1), eps)
        return x @ _lower(head.astype(jnp.float32), quant)


@partial(jax.jit, static_argnames=("quant",))
def _embed_rows(table, tokens, *, quant):
    rows = table[tokens].astype(jnp.float32)  # [S, T, d]
    if quant is None:
        return rows
    flat = rows.reshape(-1, rows.shape[-1]).T  # one scale a row of the table
    return _lower(flat, quant).T.reshape(rows.shape)


def forward(config: dict, seed: int, tokens, positions, *, quant=None,
            early=None):
    """Logits of whole sequences at chosen positions.

    tokens: int32 [S, T] (pad the tail with anything: attention and the
    recurrence are causal, so what follows a position cannot reach it);
    positions: int32 [S, P]. Returns float32 [S, P, vocab]. With ``early =
    (k, positions_k)`` also returns the logits the model's first ``k``
    layers alone would give (the final norm and head on the hidden state
    after layer ``k``) at ``positions_k``: (logits, early_logits)."""
    w = Weights(config, seed)
    m = w.m
    # rows are cut on the host, a few sequences at a time, and never
    # joined; between layers their hidden states wait ON THE HOST: a
    # decode slot's worth of rows of 4,096 values is gigabytes, and the
    # reference runs in what the served model and its pools leave free
    tokens = np.asarray(tokens, np.int32)
    at = range(0, tokens.shape[0], ROWS_AT_ONCE)
    table = w.embed()
    xs = [np.asarray(
        _embed_rows(table, tokens[a: a + ROWS_AT_ONCE], quant=quant))
        for a in at]
    del table

    def logits_at(where):
        # positions padded to a multiple of 16 (with position 0, cut off
        # again): the check asks for 1, 6 and 10 a row, one program
        where = np.asarray(where, np.int32)
        n = where.shape[1]
        where = np.pad(where, ((0, 0), (0, -n % 16)))
        head = w.head()
        return np.concatenate([
            np.asarray(_logits_at(
                jnp.asarray(x), where[a: a + ROWS_AT_ONCE], head,
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
