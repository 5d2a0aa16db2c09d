"""Plain reference of a latent-attention (MLA) decoder over a sparse expert
MLP with a shared expert (JoyAI-LLM-Flash; the DeepSeek-V3 family's layer):
the published forward pass in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")``; NOT absorbed, no cache, no kernel,
no sorting or grouping of tokens, no call into ``dynamo_tpu``. Read from the
public ``config.json`` keys alone (``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``rope_interleave``, ``first_k_dense_replace``, ``n_shared_experts``,
``routed_scaling_factor``...), never from the configuration's
``model_spec``.

    x = E[tokens]; a row of x at position p:
    per layer l:
        h = rms(x)
        c_q = rms(h W_qa)                              q_lora_rank
        [q_n, q_r]_i = c_q W_qb        H heads x (qk_nope + qk_rope)
        [c_kv, k_r] = h W_kva            kv_lora_rank + qk_rope
        c = rms(c_kv)
        q_r, k_r rotated by p at base rope_theta, on INTERLEAVED pairs
            (2j, 2j + 1) when rope_interleave (else half-split pairs);
            k_r is shared by the heads
        [k_n, v]_i = c W_kvb,i                  qk_nope + v_head_dim
        s_i = (q_n,i . k_n,i + q_r,i . k_r) / sqrt(qk_nope + qk_rope)
        o_i = sum softmax_causal(s_i) v_i
        x = x + concat_i(o_i) W_o
        h = rms(x)
        l < first_k_dense_replace:  x = x + (silu(h W_g) * (h W_u)) W_d
        else: s = sigmoid(h W_r) over all routed experts, float32;
              the num_experts_per_tok largest of s + b are chosen
              (noaux_tc; n_group = topk_group = 1: no group limit);
              w_e = routed_scaling_factor * s_e / sum of the chosen s
              (norm_topk_prob);
              x = x + sum over chosen e of w_e * FFN_e(h) + FFN_shared(h)
    logits = rms(x) W_head

Every held expert's FFN is computed for every token and weighted (zero
where not chosen): the plainest form of the sum above. The cache the
program keeps, ``[c, k_r]``, appears here only as two intermediate values.

Departures from the published model, all stated in the configuration's
file: random weights; the depth (``layers_kept``); the share of one chip of
an expert-parallel deployment: of the ``experts.published`` routed experts
only ``experts.held`` from ``experts.first`` are here, the router still
scores all of them and the chosen experts that are absent add nothing
(their chips add them), while the SHARED expert is computed whole (every
chip computes it for its own tokens); ``vocab_size`` rows of the embedding
and columns of the head (one group of a vocabulary-parallel split); the
multi-token-prediction layer is left out.

It takes nothing the program has made. The weights are drawn here from the
seed by this file's own copy of the recipe the engine is documented to use
(``assumed`` in the configuration's file): the root key split in ``4 + 8 x
layers``; embedding, head, then a layer ``W_qa, W_qb, W_kva, W_kvb, W_o``
and three keys for its MLP (dense: gate, up, down; experts: the first split
in four for router, gate, up, down, each projection's held experts drawn
as one ``[held, in, out]`` array, the correction bias ``N(0, 0.1^2)`` in
float32 on that key folded with 1; the second split in three for the shared
expert's gate, up, down); ``N(0, 1 / fan_in)``, embedding and router ``N(0,
0.02^2)``, norm gains 1, everything but the bias rounded to the served
dtype; a layer at a time, an expert at a time in the arithmetic, so the
reference fits beside the bf16 model.

``quant`` computes the same pass with every weight matrix (the router's
too) rounded to a lower precision (``"fp8"``: e4m3 with one scale an output
channel; ``"int8"``: symmetric, one scale an output channel): the CONTROL
of the output check.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ROWS_AT_ONCE = 4  # sequences a layer call: the reference runs beside the model
ATTN = ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o")
DENSE = ("w_gate", "w_up", "w_down")
SHARED = ("s_gate", "s_up", "s_down")


def _dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    kept = config.get("layers_kept") or list(range(layers))
    ex = config.get("experts") or {
        "published": config["n_routed_experts"],
        "held": config["n_routed_experts"], "first": 0,
    }
    return {
        "d": config["hidden_size"], "nh": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "dc": config["kv_lora_rank"],
        "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"],
        "theta": float(config["rope_theta"]),
        "interleave": bool(config.get("rope_interleave", False)),
        "dense": [i < config["first_k_dense_replace"] for i in kept],
        "f_dense": config["intermediate_size"],
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
    """The model's weights from the seed, a layer at a time."""

    def __init__(self, config: dict, seed: int):
        self.m = _dims(config)
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = len(self.m["dense"])
        keys = iter(jax.random.split(
            jax.random.PRNGKey(seed), 4 + self.layers * 8))
        self._embed_key, self._head_key = next(keys), next(keys)
        self._layer_keys = [
            [next(keys) for _ in range(8)] for _ in range(self.layers)
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
        k_qa, k_qb, k_kva, k_kvb, k_o, k1, k2, k3 = self._layer_keys[i]
        d, nh = m["d"], m["nh"]
        w = {
            "w_qa": self._matrix(k_qa, (d, m["q_rank"])),
            "w_qb": self._matrix(k_qb, (m["q_rank"], nh * (m["dn"] + m["dr"]))),
            "w_kva": self._matrix(k_kva, (d, m["dc"] + m["dr"])),
            "w_kvb": self._matrix(k_kvb, (m["dc"], nh * (m["dn"] + m["dv"]))),
            "w_o": self._matrix(k_o, (nh * m["dv"], d)),
        }
        if m["dense"][i]:
            f = m["f_dense"]
            w["w_gate"] = self._matrix(k1, (d, f))
            w["w_up"] = self._matrix(k2, (d, f))
            w["w_down"] = self._matrix(k3, (f, d))
            return w
        r1, r2, r3, r4 = jax.random.split(k1, 4)
        held, f = m["held"], m["f"]
        w["router"] = self._matrix(r1, (d, m["experts"]), 0.02)
        w["e_gate"] = self._matrix(r2, (held, d, f))
        w["e_up"] = self._matrix(r3, (held, d, f))
        w["e_down"] = self._matrix(r4, (held, f, d))
        w["score_bias"] = _draw(
            jax.random.fold_in(k1, 1), 0.1,
            shape=(m["experts"],), dtype=jnp.float32,
        )
        if m["shared"]:
            s1, s2, s3 = jax.random.split(k2, 3)
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


def _rotary(x, positions, theta, interleave):
    """x: [S, T, ..., D] rotated by its position (axis 1) over all D dims:
    on the interleaved pairs (2j, 2j + 1), or on the half-split pairs
    (j, j + D/2); pair j turns by ``p * theta^(-2j / D)`` either way."""
    D = x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [T, half]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [a * cos - b * sin, b * cos + a * sin], axis=-1
        ).reshape(x.shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("heads", "dc", "dn", "dr", "dv", "theta",
                                   "interleave", "eps", "quant"))
def _attention(x, lw, *, heads, dc, dn, dr, dv, theta, interleave, eps,
               quant):
    """The attention half of one layer over whole sequences; x: [S, T, d]
    float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(lw[k].astype(jnp.float32), quant) for k in ATTN}
        S, T, _ = x.shape
        pos = jnp.arange(T)
        h = _rms(x, eps)
        q = (_rms(h @ w["w_qa"], eps) @ w["w_qb"]).reshape(
            S, T, heads, dn + dr)
        q_n, q_r = q[..., :dn], _rotary(q[..., dn:], pos, theta, interleave)
        kv = h @ w["w_kva"]
        c = _rms(kv[..., :dc], eps)
        k_r = _rotary(kv[..., dc:], pos, theta, interleave)  # [S, T, dr]
        up = (c @ w["w_kvb"]).reshape(S, T, heads, dn + dv)
        k_n, v = up[..., :dn], up[..., dn:]
        scores = (
            jnp.einsum("sthd,suhd->shtu", q_n, k_n)
            + jnp.einsum("sthd,sud->shtu", q_r, k_r)
        ) / jnp.sqrt(jnp.float32(dn + dr))
        seen = pos[:, None] >= pos[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        a = jnp.einsum(
            "shtu,suhd->sthd", jax.nn.softmax(scores, axis=-1), v
        ).reshape(S, T, heads * dv)
        return x + a @ w["w_o"]


@partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_mlp(x, lw, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(lw[k].astype(jnp.float32), quant) for k in DENSE}
        h = _rms(x, eps)
        return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=("topk", "first", "held", "scaling",
                                   "norm_topk", "eps", "quant"))
def _route(x, router, bias, *, topk, first, held, scaling, norm_topk, eps,
           quant):
    """(rms(x), the weight of each HELD expert for each token [S, T,
    held]: zero where it is not among the token's chosen)."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, eps)
        s = jax.nn.sigmoid(h @ _lower(router.astype(jnp.float32), quant))
        _, chosen = jax.lax.top_k(s + bias, topk)  # [S, T, topk]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        if norm_topk:
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        picked = picked * scaling
        here = jnp.arange(first, first + held)
        hit = chosen[..., None] == here  # [S, T, topk, held]
        return h, jnp.sum(jnp.where(hit, picked[..., None], 0.0), axis=-2)


@partial(jax.jit, static_argnames=("quant",))
def _expert(x, h, weights, gates, ups, downs, e, *, quant):
    """x plus held expert ``e``'s weighted FFN over every token. gates,
    ups, downs: the held experts' matrices, stacked; weights: [S, T,
    held]. ``e`` is an argument, not a constant: one program serves every
    expert."""
    with jax.default_matmul_precision("highest"):
        gate, up, down = (
            _lower(jax.lax.dynamic_index_in_dim(
                w, e, keepdims=False).astype(jnp.float32), quant)
            for w in (gates, ups, downs)
        )
        weight = jax.lax.dynamic_slice_in_dim(weights, e, 1, axis=-1)
        return x + weight * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)


@partial(jax.jit, static_argnames=("quant",))
def _shared(x, h, lw, *, quant):
    """x plus the shared expert's FFN of the normed rows ``h``."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(lw[k].astype(jnp.float32), quant) for k in SHARED}
        return x + (jax.nn.silu(h @ w["s_gate"]) * (h @ w["s_up"])) @ w["s_down"]


def _layer(m: dict, i: int, x, lw, quant):
    x = _attention(
        x, {k: lw[k] for k in ATTN}, heads=m["nh"], dc=m["dc"], dn=m["dn"],
        dr=m["dr"], dv=m["dv"], theta=m["theta"],
        interleave=m["interleave"], eps=m["eps"], quant=quant,
    )
    if m["dense"][i]:
        return _dense_mlp(
            x, {k: lw[k] for k in DENSE}, eps=m["eps"], quant=quant
        )
    h, weights = _route(
        x, lw["router"], lw["score_bias"], topk=m["topk"], first=m["first"],
        held=m["held"], scaling=m["scaling"], norm_topk=m["norm_topk"],
        eps=m["eps"], quant=quant,
    )
    for e in range(m["held"]):
        x = _expert(
            x, h, weights, lw["e_gate"], lw["e_up"], lw["e_down"],
            np.int32(e), quant=quant,
        )
    if m["shared"]:
        x = _shared(x, h, {k: lw[k] for k in SHARED}, quant=quant)
    return x


@partial(jax.jit, static_argnames=("eps",))
def _rms_at(x, positions, *, eps):
    """The final norm at chosen positions of x: [S, T, d] -> [S, P, d]."""
    return _rms(jnp.take_along_axis(x, positions[:, :, None], axis=1), eps)


@partial(jax.jit, static_argnames=("quant",))
def _head(x, head, *, quant):
    with jax.default_matmul_precision("highest"):
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

    tokens: int32 [S, T] (pad the tail with anything: attention is causal,
    so what follows a position cannot reach it); positions: int32 [S, P].
    Returns float32 [S, P, vocab]. With ``early = (k, positions_k)`` also
    returns the logits the model's first ``k`` layers alone would give (the
    final norm and head on the hidden state after layer ``k``) at
    ``positions_k``: (logits, early_logits)."""
    w = Weights(config, seed)
    m = w.m
    # rows are cut on the host (a device slice is a program of its own
    # for every offset), a few sequences at a time, and never joined: the
    # hidden states of a decode slot's worth of rows are the largest thing
    # the reference holds beside the model
    tokens = np.asarray(tokens, np.int32)
    at = range(0, tokens.shape[0], ROWS_AT_ONCE)
    table = w.embed()
    xs = [_embed_rows(table, tokens[a: a + ROWS_AT_ONCE], quant=quant)
          for a in at]
    del table

    def logits_at(where):
        where = np.asarray(where, np.int32)
        head = w.head()
        return np.concatenate([
            np.asarray(_head(_rms_at(
                x, where[a: a + ROWS_AT_ONCE], eps=m["eps"]
            ), head, quant=quant))
            for x, a in zip(xs, at)
        ])

    early_logits = None
    for i in range(w.layers):
        lw = w.layer(i)  # drawn once, then a few sequences at a time
        xs = [_layer(m, i, x, lw, quant) for x in xs]
        del lw
        if early is not None and i + 1 == early[0]:
            early_logits = logits_at(early[1])
    logits = logits_at(positions)
    if early is None:
        return logits
    return logits, early_logits
