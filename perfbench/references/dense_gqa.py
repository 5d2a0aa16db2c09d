"""Plain reference of a dense decoder with grouped-query attention
(Mistral-7B, Mistral-Nemo): the published forward pass in straightforward
``jax.numpy``, float32, ``default_matmul_precision("highest")``; no cache,
no kernels, no batching tricks, no call into ``dynamo_tpu``.

    x = E[tokens]
    per layer:  h = rmsnorm(x) * g_attn
                q, k, v = h Wq, h Wk, h Wv ; rotary(q, k) (half-split, theta)
                a = softmax(q k^T / sqrt(D) + causal) v   (G query heads a KV head)
                x = x + a Wo
                h = rmsnorm(x) * g_mlp
                x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * g_final) Whead

It takes nothing the program has made. The weights are drawn here from the
seed by this file's own copy of the recipe the engine is documented to use
for random weights (``N(0, 1/fan_in)`` per matrix, ``N(0, 0.02^2)`` for the
embedding, norm gains 1, one key a matrix in the order below, rounded to
the served dtype), a matrix at a time, so the reference fits beside the
bf16 model. Departures from the published model: random weights, and the
depth the configuration states.

``quant`` computes the same pass with every weight matrix rounded to a
lower precision (``"fp8"``: e4m3 with one scale an output channel;
``"int8"``: symmetric, one scale an output channel): the CONTROL of the
output check, the step below bf16 that would tempt a later PR.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
ROWS_AT_ONCE = 4  # sequences a layer call: the reference runs beside the model


def _shapes(config: dict) -> dict:
    d = config["hidden_size"]
    hd = config.get("head_dim") or d // config["num_attention_heads"]
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    f = config["intermediate_size"]
    return {
        "wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
        "wo": (nh * hd, d), "w_gate": (d, f), "w_up": (d, f),
        "w_down": (f, d),
    }


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, scale, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class Weights:
    """The model's weights from the seed, a matrix at a time."""

    def __init__(self, config: dict, seed: int):
        self.config = config
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = config["num_hidden_layers"]
        self.tied = bool(config.get("tie_word_embeddings", False))
        self.shapes = _shapes(config)
        keys = jax.random.split(jax.random.PRNGKey(seed), 4 + self.layers * 8)
        self._embed_key = keys[0]
        at = 1
        self._head_key = None
        if not self.tied:
            self._head_key = keys[1]
            at = 2
        self._layer_keys = [
            dict(zip(MATRICES, keys[at + 7 * i: at + 7 * i + 7]))
            for i in range(self.layers)
        ]

    def _matrix(self, key, shape):
        scale = 1.0 / jnp.sqrt(shape[0])
        return _draw(key, scale, shape=shape, dtype=self.dtype)

    def embed(self):
        shape = (self.config["vocab_size"], self.config["hidden_size"])
        return _draw(self._embed_key, 0.02, shape=shape, dtype=self.dtype)

    def head(self):
        if self.tied:
            return self.embed().T
        shape = (self.config["hidden_size"], self.config["vocab_size"])
        return self._matrix(self._head_key, shape)

    def layer(self, i: int) -> dict:
        return {
            name: self._matrix(self._layer_keys[i][name], self.shapes[name])
            for name in MATRICES
        }


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


def _rotary(x, positions, theta):
    """x: [S, T, heads, D]; half-split pairs (i, i + D/2), as the public
    Mistral code rotates them."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta",
                                   "eps", "quant"))
def _layer(x, lw, *, heads, kv_heads, head_dim, theta, eps, quant):
    """One decoder layer over whole sequences; x: [S, T, d] float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(v.astype(jnp.float32), quant) for k, v in lw.items()}
        S, T, _ = x.shape
        pos = jnp.arange(T)
        h = _rms(x, eps)
        q = (h @ w["wq"]).reshape(S, T, heads, head_dim)
        k = (h @ w["wk"]).reshape(S, T, kv_heads, head_dim)
        v = (h @ w["wv"]).reshape(S, T, kv_heads, head_dim)
        q, k = _rotary(q, pos, theta), _rotary(k, pos, theta)
        group = heads // kv_heads
        q = q.reshape(S, T, kv_heads, group, head_dim)
        scores = jnp.einsum("stkgd,sukd->skgtu", q, k) / jnp.sqrt(
            jnp.float32(head_dim)
        )
        causal = pos[:, None] >= pos[None, :]
        scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        a = jnp.einsum("skgtu,sukd->stkgd", probs, v).reshape(
            S, T, heads * head_dim
        )
        x = x + a @ w["wo"]
        h = _rms(x, eps)
        return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=("width", "quant"))
def _head_columns(x, head, start, *, width, quant):
    with jax.default_matmul_precision("highest"):
        cols = jax.lax.dynamic_slice_in_dim(head, start, width, axis=1)
        return x @ _lower(cols.astype(jnp.float32), quant)


@partial(jax.jit, static_argnames=("quant",))
def _embed_rows(table, tokens, *, quant):
    rows = table[tokens].astype(jnp.float32)  # [S, T, d]
    if quant is None:
        return rows
    flat = rows.reshape(-1, rows.shape[-1]).T  # one scale a row of the table
    return _lower(flat, quant).T.reshape(rows.shape)


def _logits(config, w, x, positions, *, quant):
    """Final norm and output head at chosen positions of x: [S, T, d]."""
    eps = float(config.get("rms_norm_eps", 1e-5))
    x = jnp.take_along_axis(x, positions[:, :, None], axis=1)  # [S, P, d]
    x = _rms(x, eps)
    head = w.head()
    vocab = config["vocab_size"]
    width = vocab // 4 if vocab % 4 == 0 and vocab > 65536 else vocab
    parts = [
        _head_columns(x, head, start, width=width, quant=quant)
        for start in range(0, vocab, width)
    ]
    return jnp.concatenate(parts, axis=-1)


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
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    hd = config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"]
    )
    eps = float(config.get("rms_norm_eps", 1e-5))
    x = _embed_rows(w.embed(), tokens, quant=quant)
    early_logits = None
    for i in range(w.layers):
        lw = w.layer(i)  # drawn once, then a few sequences at a time
        x = jnp.concatenate([
            _layer(
                x[at: at + ROWS_AT_ONCE], lw,
                heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"], head_dim=hd,
                theta=float(config["rope_theta"]), eps=eps, quant=quant,
            )
            for at in range(0, x.shape[0], ROWS_AT_ONCE)
        ])
        if early is not None and i + 1 == early[0]:
            early_logits = _logits(
                config, w, x, jnp.asarray(early[1], jnp.int32), quant=quant
            )
    logits = _logits(config, w, x, positions, quant=quant)
    if early is None:
        return logits
    return logits, early_logits
