"""Plain reference of a PARALLEL-HYBRID decoder (Falcon-H1): in every layer
one RMSNorm feeds a Mamba-2 (SSD) mixer and a GQA attention at once, their
outputs are scaled and summed into the residual, then a dense SwiGLU MLP;
the family's fixed multipliers stand where the published model applies
them. The layer equations in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")``; the state-space recurrence as a
``lax.scan`` A TOKEN with no chunk form, no cache, no state pool, no
kernel, no call into ``dynamo_tpu``. Read from the public ``config.json``
keys alone, never from the configuration's ``model_spec``.

    x_0 = E[token] * embedding_multiplier
    u   = rms(x)                                   (input_layernorm)
    a   = Attn(u * attention_in_multiplier) * attention_out_multiplier
    m   = SSM(u * ssm_in_multiplier) * ssm_out_multiplier
    x   = x + a + m
    h   = rms(x)                                   (pre_ff_layernorm)
    x   = x + W_down(silu(W_gate h * mlp_multipliers[0]) * (W_up h))
              * mlp_multipliers[1]
    logits = W_head rms(x_L) * lm_head_multiplier  (final_layernorm)

    Attn(u): q = W_q u (num_attention_heads x head_dim), k = (W_k u) *
        key_multiplier, v = W_v u (num_key_value_heads x head_dim);
        rotate-half rotary on the whole head at rope_theta, no scaling;
        causal softmax at 1 / sqrt(head_dim), query head i reads KV head
        i // (heads / kv heads); W_o. No bias anywhere.
    SSM(u) (mamba_d_ssm = mamba_n_heads x mamba_d_head channels):
        [z | xBC | dt] = (W_in u) * mup, mup = ssm_multipliers[0..4] on
            the segments z, x, B, C, dt
        xBC = silu(conv(xBC) + b_conv): causal, depthwise, mamba_d_conv
            taps a channel, over x | B | C
        x -> [heads, d_head]; B, C -> [mamba_n_groups, mamba_d_state],
            head i reads group i // (heads / groups)
        dt = softplus(dt + dt_bias); A = -exp(A_log) a head
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t (outer) B_t   [d_head,
            d_state] float32 a head, from zero
        y_t = H_t C_t + D x_t
        out = W_out grouprms(y_t * silu(z_t))    (mamba_rms_norm true,
            mamba_norm_before_gate false: the gate first, then an RMS norm
            over each of mamba_n_groups groups of channels)

Departures from the published model, all stated in the configuration's
file: random weights; the depth (``num_hidden_layers`` layers, the first of
the published stack). It takes nothing the program has made. The weights
are drawn here from the seed by this file's own copy of the recipe the
engine is documented to use (``assumed`` in the configuration's file), a
layer at a time; the embedding and the head in ``VOCAB_BLOCKS`` blocks of
rows (columns), each on its own folded key, and a layer's MLP is computed
in blocks of columns, so that neither a float32 vocabulary table nor a
float32 MLP matrix is ever held: the reference fits beside the served
model.

``quant`` computes the same pass with every weight matrix rounded to a
lower precision (``"fp8"``: e4m3 with one scale an output channel;
``"int8"``: symmetric, one scale an output channel; not the taps and their
bias, ``A_log``, ``dt_bias`` and ``D``): the CONTROL of the output check.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ROWS_AT_ONCE = 4  # sequences a layer call: the reference runs beside the model
VOCAB_BLOCKS = 8  # blocks the embedding and the head are drawn in
MLP_BLOCKS = 4  # blocks of columns a layer's MLP is computed in


def _dims(config: dict) -> dict:
    for key in ("attention_bias", "mlp_bias", "projectors_bias",
                "mamba_proj_bias"):
        if config.get(key):
            raise SystemExit(f"parallel_ssm: {key} true has no form here")
    if not config.get("mamba_rms_norm", True) or config.get(
            "mamba_norm_before_gate"):
        raise SystemExit("parallel_ssm: the gated norm after the gate only")
    if config.get("rope_scaling"):
        raise SystemExit("parallel_ssm: no rope scaling")
    heads, d_head = config["mamba_n_heads"], config["mamba_d_head"]
    if heads * d_head != config["mamba_d_ssm"]:
        raise SystemExit("parallel_ssm: mamba_d_ssm is heads x d_head")
    return {
        "d": config["hidden_size"], "nh": config["num_attention_heads"],
        "nkv": config["num_key_value_heads"], "hd": config["head_dim"],
        "theta": float(config["rope_theta"]),
        "f": config["intermediate_size"],
        "sh": heads, "sp": d_head, "sn": config["mamba_d_state"],
        "sg": config["mamba_n_groups"], "taps": config["mamba_d_conv"],
        "conv_bias": bool(config.get("mamba_conv_bias", True)),
        "eps": float(config["rms_norm_eps"]),
        "vocab": config["vocab_size"],
        "layers": config["num_hidden_layers"],
        "m_embed": float(config["embedding_multiplier"]),
        "m_head": float(config["lm_head_multiplier"]),
        "m_key": float(config["key_multiplier"]),
        "m_attn_in": float(config["attention_in_multiplier"]),
        "m_attn_out": float(config["attention_out_multiplier"]),
        "m_ssm_in": float(config["ssm_in_multiplier"]),
        "m_ssm_out": float(config["ssm_out_multiplier"]),
        "m_ssm": tuple(float(v) for v in config["ssm_multipliers"]),
        "m_mlp": tuple(float(v) for v in config["mlp_multipliers"]),
    }


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, scale, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class Weights:
    """The model's weights from the seed, a layer at a time: the root key
    split in ``4 + 8 x layers``, consumed in order: embedding, head, then a
    layer ``W_q, W_k, W_v, W_o, W_gate, W_up, W_down`` (seven of its eight).
    The embedding ``[vocab, d]`` is drawn in ``VOCAB_BLOCKS`` blocks of
    rows and the head ``[d, vocab]`` in as many blocks of columns, block
    ``b`` on its key folded with ``b``. What the SSM adds is drawn on the
    root folded with ``2000 + layer``, split in 9: ``W_in`` ``[d, z | x |
    B | C | dt]``, the taps ``[taps, channels]`` (``N(0, 1 / taps)``), their
    bias (``N(0, 0.1^2)``), ``A = exp(A_log)`` uniform in (1, 16) a head,
    the time step log-uniform in (1e-3, 1e-1) (``dt_bias`` its inverse
    softplus; a token's decay ``exp(-dt A)`` then spans (0.2, 0.999)),
    ``D`` uniform in (0.5, 1.5), ``W_out``. ``N(0, 1 / fan_in)``, embedding
    ``N(0, 0.02^2)``, norm gains 1; everything rounded to the served dtype
    but ``A_log``, ``dt_bias`` and ``D``."""

    def __init__(self, config: dict, seed: int):
        self.m = _dims(config)
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = self.m["layers"]
        self._root = jax.random.PRNGKey(seed)
        keys = iter(jax.random.split(self._root, 4 + self.layers * 8))
        self._embed_key, self._head_key = next(keys), next(keys)
        self._layer_keys = [
            [next(keys) for _ in range(7)] for _ in range(self.layers)
        ]
        if self.m["vocab"] % VOCAB_BLOCKS:
            raise SystemExit("parallel_ssm: the vocabulary cuts in 8 blocks")
        self.vocab_block = self.m["vocab"] // VOCAB_BLOCKS

    def _matrix(self, key, shape, scale=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[-2])
        return _draw(key, scale, shape=shape, dtype=self.dtype)

    def embed_block(self, b: int):
        """Rows ``b * vocab_block ...`` of the embedding."""
        return self._matrix(
            jax.random.fold_in(self._embed_key, b),
            (self.vocab_block, self.m["d"]), 0.02)

    def head_block(self, b: int):
        """Columns ``b * vocab_block ...`` of the head."""
        d = self.m["d"]
        return _draw(
            jax.random.fold_in(self._head_key, b), 1.0 / jnp.sqrt(d),
            shape=(d, self.vocab_block), dtype=self.dtype)

    def layer(self, i: int) -> dict:
        m = self.m
        k_q, k_k, k_v, k_o, k_gate, k_up, k_down = self._layer_keys[i]
        extra = jax.random.split(jax.random.fold_in(self._root, 2000 + i), 9)
        d, f = m["d"], m["f"]
        d_ssm = m["sh"] * m["sp"]
        ch = d_ssm + 2 * m["sg"] * m["sn"]
        f32 = jnp.float32
        step = jnp.exp(jax.random.uniform(
            extra[4], (m["sh"],), f32, jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "w_q": self._matrix(k_q, (d, m["nh"] * m["hd"])),
            "w_k": self._matrix(k_k, (d, m["nkv"] * m["hd"])),
            "w_v": self._matrix(k_v, (d, m["nkv"] * m["hd"])),
            "w_o": self._matrix(k_o, (m["nh"] * m["hd"], d)),
            "w_gate": self._matrix(k_gate, (d, f)),
            "w_up": self._matrix(k_up, (d, f)),
            "w_down": self._matrix(k_down, (f, d)),
            "w_in": self._matrix(extra[0], (d, d_ssm + ch + m["sh"])),
            "taps": self._matrix(extra[1], (m["taps"], ch)),
            "conv_bias": self._matrix(extra[2], (ch,), 0.1),
            "a_log": jnp.log(
                jax.random.uniform(extra[3], (m["sh"],), f32, 1.0, 16.0)),
            "dt_bias": jnp.log(jnp.expm1(step)),
            "d_skip": jax.random.uniform(extra[5], (m["sh"],), f32, 0.5, 1.5),
            "w_out": self._matrix(extra[6], (d_ssm, d)),
        }


def _lower(w, quant, top=None):
    """``w`` (float32, [in, out]) rounded to ``quant``, one scale an output
    channel, and back to float32. ``top``: the channels' largest magnitude
    where ``w`` is a block of the matrix's rows."""
    if quant is None:
        return w
    if top is None:
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


def _rotate(x, theta):
    """Rotate-half rotary on the whole head. x: [S, T, heads, D]."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(u, w, m):
    """u: [S, T, d] the normed input -> [S, T, d]."""
    S, T, _ = u.shape
    nh, nkv, hd = m["nh"], m["nkv"], m["hd"]
    u = u * m["m_attn_in"]
    q = (u @ w["w_q"]).reshape(S, T, nh, hd)
    k = ((u @ w["w_k"]) * m["m_key"]).reshape(S, T, nkv, hd)
    v = (u @ w["w_v"]).reshape(S, T, nkv, hd)
    q, k = _rotate(q, m["theta"]), _rotate(k, m["theta"])
    k, v = (jnp.repeat(y, nh // nkv, axis=2) for y in (k, v))
    scores = jnp.einsum("sthd,suhd->shtu", q, k) / jnp.sqrt(jnp.float32(hd))
    pos = jnp.arange(T)
    scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
    a = jnp.einsum("shtu,suhd->sthd", jax.nn.softmax(scores, axis=-1), v)
    return (a.reshape(S, T, nh * hd) @ w["w_o"]) * m["m_attn_out"]


def _ssm(u, w, taps, conv_bias, a_log, dt_bias, d_skip, m):
    """u: [S, T, d] the normed input -> [S, T, d]; the state from zero, a
    token at a time."""
    S, T, _ = u.shape
    H, P, N, G = m["sh"], m["sp"], m["sn"], m["sg"]
    d_ssm = H * P
    mz, mx, mb, mc, mdt = m["m_ssm"]
    mup = jnp.concatenate([
        jnp.full((d_ssm,), mz), jnp.full((d_ssm,), mx),
        jnp.full((G * N,), mb), jnp.full((G * N,), mc), jnp.full((H,), mdt),
    ]).astype(jnp.float32)
    zxbcdt = ((u * m["m_ssm_in"]) @ w["w_in"]) * mup
    z = zxbcdt[..., :d_ssm]
    xbc = zxbcdt[..., d_ssm: -H]
    dt = zxbcdt[..., -H:]
    # causal, depthwise: the last tap weighs the token itself
    n = taps.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (n - 1, 0), (0, 0)))
    conv = sum(taps[i].astype(jnp.float32) * padded[:, i:i + T]
               for i in range(n))
    if m["conv_bias"]:
        conv = conv + conv_bias.astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d_ssm].reshape(S, T, H, P)
    B = xbc[..., d_ssm: d_ssm + G * N].reshape(S, T, G, N)
    C = xbc[..., d_ssm + G * N:].reshape(S, T, G, N)
    B, C = (jnp.repeat(y, H // G, axis=2) for y in (B, C))  # a head's group
    dt = jax.nn.softplus(dt + dt_bias)  # [S, T, H]
    A = -jnp.exp(a_log)

    def token(state, at):  # state: [S, H, P, N]
        x_t, b_t, c_t, dt_t = at
        state = jnp.exp(dt_t * A)[..., None, None] * state + jnp.einsum(
            "shp,shn->shpn", dt_t[..., None] * x_t, b_t)
        return state, jnp.einsum("shpn,shn->shp", state, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((S, H, P, N), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, B, C, dt)),
    )
    y = jnp.moveaxis(y, 0, 1) + d_skip[:, None] * x  # [S, T, H, P]
    g = y.reshape(S, T, d_ssm) * jax.nn.silu(z)
    g = _rms(g.reshape(S, T, G, d_ssm // G), m["eps"]).reshape(S, T, d_ssm)
    return (g @ w["w_out"]) * m["m_ssm_out"]


def _static(m: dict):
    return tuple(sorted(m.items()))


@partial(jax.jit, static_argnames=("dims", "quant"))
def _mixers(x, lw, *, dims, quant):
    """x plus both mixers' outputs over whole sequences; x: [S, T, d]
    float32."""
    m = dict(dims)
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(lw[k].astype(jnp.float32), quant)
             for k in ("w_q", "w_k", "w_v", "w_o", "w_in", "w_out")}
        u = _rms(x, m["eps"])
        return x + _attention(u, w, m) + _ssm(
            u, w, lw["taps"], lw["conv_bias"], lw["a_log"], lw["dt_bias"],
            lw["d_skip"], m)


@partial(jax.jit, static_argnames=("dims", "quant"))
def _mlp(x, w_gate, w_up, w_down, *, dims, quant):
    """x plus the SwiGLU MLP's output, in ``MLP_BLOCKS`` blocks of the
    hidden columns: no float32 copy of a whole matrix."""
    m = dict(dims)
    g_mul, d_mul = m["m_mlp"]
    f = w_gate.shape[1]
    blocks = MLP_BLOCKS if f % MLP_BLOCKS == 0 else 1
    n = f // blocks
    with jax.default_matmul_precision("highest"):
        h = _rms(x, m["eps"])
        # the down projection's scales are a whole column's
        top = jnp.max(jnp.abs(w_down), axis=0, keepdims=True).astype(
            jnp.float32)

        def block(i, acc):
            gate = _lower(jax.lax.dynamic_slice_in_dim(
                w_gate, i * n, n, axis=1).astype(jnp.float32), quant)
            up = _lower(jax.lax.dynamic_slice_in_dim(
                w_up, i * n, n, axis=1).astype(jnp.float32), quant)
            down = _lower(jax.lax.dynamic_slice_in_dim(
                w_down, i * n, n, axis=0).astype(jnp.float32), quant, top)
            return acc + (jax.nn.silu((h @ gate) * g_mul) * (h @ up)) @ down

        return x + jax.lax.fori_loop(
            0, blocks, block, jnp.zeros_like(x)) * d_mul


@partial(jax.jit, static_argnames=("eps", "mult", "quant"))
def _logits_block(x, head, *, eps, mult, quant):
    """The final norm and one block of the head's columns on chosen rows:
    [R, d] -> [R, block]."""
    with jax.default_matmul_precision("highest"):
        return (_rms(x, eps) @ _lower(head.astype(jnp.float32), quant)) * mult


@partial(jax.jit, static_argnames=("quant",))
def _embed_rows(block, ids, *, quant):
    """Rows ``ids`` of one block of the table, float32 [n, d]."""
    rows = block[ids].astype(jnp.float32)
    if quant is None:
        return rows
    return _lower(rows.T, quant).T  # one scale a row of the table


def _embed(w: Weights, tokens: np.ndarray, quant) -> np.ndarray:
    """[S, T, d] float32 on the host, a block of the table at a time."""
    m = w.m
    out = np.zeros(tokens.shape + (m["d"],), np.float32)
    block_of = tokens // w.vocab_block
    for b in range(VOCAB_BLOCKS):
        hit = block_of == b
        if not hit.any():
            continue
        ids = tokens[hit] - b * w.vocab_block
        # padded to a power of two: a few programs, not one a count
        n = 1 << max(4, int(len(ids) - 1).bit_length())
        rows = _embed_rows(
            w.embed_block(b), jnp.asarray(np.pad(ids, (0, n - len(ids)))),
            quant=quant)
        out[hit] = np.asarray(rows)[: len(ids)]
    return out * np.float32(m["m_embed"])


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
    dims = _static(m)
    # rows are cut on the host, a few sequences at a time, and never
    # joined; between layers their hidden states wait ON THE HOST: the
    # reference runs in what the served model and its pools leave free
    tokens = np.asarray(tokens, np.int32)
    at = range(0, tokens.shape[0], ROWS_AT_ONCE)
    x_all = _embed(w, tokens, quant)
    xs = [x_all[a: a + ROWS_AT_ONCE] for a in at]
    del x_all

    def logits_at(where):
        # the chosen rows of every sequence first (a few MB), then the
        # head a block of columns at a time over all of them
        where = np.asarray(where, np.int32)
        rows = np.concatenate([
            np.take_along_axis(x, where[a: a + ROWS_AT_ONCE, :, None], axis=1)
            for x, a in zip(xs, at)
        ])  # [S, P, d]
        S, P, d = rows.shape
        flat = jnp.asarray(rows.reshape(S * P, d))
        out = np.zeros((S * P, m["vocab"]), np.float32)
        for b in range(VOCAB_BLOCKS):
            lo = b * w.vocab_block
            out[:, lo: lo + w.vocab_block] = np.asarray(_logits_block(
                flat, w.head_block(b), eps=m["eps"], mult=m["m_head"],
                quant=quant))
        return out.reshape(S, P, m["vocab"])

    early_logits = None
    for i in range(w.layers):
        lw = w.layer(i)  # drawn once, then a few sequences at a time
        mix = {k: v for k, v in lw.items()
               if k not in ("w_gate", "w_up", "w_down")}
        xs = [
            np.asarray(_mlp(
                _mixers(jnp.asarray(x), mix, dims=dims, quant=quant),
                lw["w_gate"], lw["w_up"], lw["w_down"], dims=dims,
                quant=quant))
            for x in xs
        ]
        del lw, mix
        if early is not None and i + 1 == early[0]:
            early_logits = logits_at(early[1])
    logits = logits_at(positions)
    if early is None:
        return logits
    return logits, early_logits
