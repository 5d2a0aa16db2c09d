"""Plain reference of a DECODER-HYBRID-DECODER (SambaY, arXiv:2507.06607, as
Phi-4-mini-flash-reasoning builds it, ``model_type`` ``phi4flash``): a
self-decoder of Mamba-1 selective-scan layers (arXiv:2312.00752) beside
window layers of differential attention (arXiv:2410.05258) that ends in one
scan layer and ONE full-attention layer, and a cross-decoder whose layers
own no cache: Gated Memory Units that gate the last scan's output, and
cross-attention layers that read the full layer's keys and values. The
layer equations in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")``; the scan as a ``lax.scan`` A
TOKEN, whole-sequence masked attention, EVERY row through EVERY layer; no
cache, no pages, no state pool, no kernel, no call into ``dynamo_tpu``.
Read from the public ``config.json`` keys and ``layers_kept`` alone, never
from the configuration's ``model_spec``.

``LN`` is LayerNorm with gain and bias at ``layer_norm_eps``; d = hidden
size, C = 2 d, N = 16, R = ceil(d / 16); ``l`` is the PUBLISHED layer index
(``layers_kept[i]``), ``half = num_hidden_layers / 2`` of the PUBLISHED
depth (``published_layers`` where a cut keeps fewer).

    x0 = E[token]                          no multiplier, no position (NoPE)
    every layer:  x1 = x + mixer_l(LN_in(x))
                  x' = x1 + W_down(silu(g) * u), [g | u] = LN_post(x1) W_gate_up

    l even, l <= half (scan):
       [xs | z] = h W_in
       xc = silu(conv4(xs) + b_conv)       causal, depthwise, 4 taps
       [dr | B | C] = xc W_x               C -> R + N + N
       dt = softplus(dr W_dt + b_dt)
       S_t[c, n] = exp(dt[c] A[c, n]) S_{t-1}[c, n] + dt[c] B[n] xc[c]
       y[c] = sum_n S_t[c, n] C[n] + D[c] xc[c]        A = -exp(A_log)
       l = half only:  m = y               the memory, before the gate
       mixer = (y * silu(z)) W_out
    l odd, l <= half + 1 (self attention; window for l < half, all keys
    for l = half + 1):
       [q | k | v] = h W_qkv + b
       q1, q2 = even, odd query heads; k1, k2 = even, odd KV heads;
       V_j = [v_2j | v_2j+1]; query pair i reads KV pair i // (pairs a KV pair)
       a1 = softmax(q1 k1^T / sqrt D) V,  a2 = softmax(q2 k2^T / sqrt D) V
       lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l),
       lam0(l) = 0.8 - 0.6 exp(-0.3 l)
       o_i = (1 - lam0(l)) rms(a1_i - lam a2_i)   one gain [2 D] a layer
       mixer = [o_0 ..] W_o + b
    l even, l >= half + 2 (GMU):   mixer = (silu(h W_1) * m) W_2
    l odd,  l >= half + 3 (cross): q = h W_q + b; k, v = layer (half + 1)'s;
                                   then as self attention over all keys
    logits = LN_final(x) E^T               tied, no bias

Departures from the published model, all stated in the configuration's
file: random weights; the depth (``layers_kept``). It takes nothing the
program has made. The weights are drawn here from the seed by this file's
own copy of the recipe the engine is documented to use (``assumed`` in the
configuration's file), a layer at a time; the embedding in
``VOCAB_BLOCKS`` blocks of rows, each on its own folded key, and a layer's
MLP is computed in blocks of columns, so that neither a float32 vocabulary
table nor a float32 MLP matrix is ever held: the reference fits beside the
served model.

``quant`` computes the same pass with every weight matrix rounded to a
lower precision (``"fp8"``: e4m3 with one scale an output channel;
``"int8"``: symmetric, one scale an output channel; not the taps, the
norms, the biases, the lambdas, ``A_log``, ``b_dt`` and ``D``): the
CONTROL of the output check.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ROWS_AT_ONCE = 4  # sequences a layer call: the reference runs beside the model
VOCAB_BLOCKS = 8  # blocks the embedding is drawn in
MLP_BLOCKS = 4  # blocks of columns a layer's MLP is computed in
SCAN_STATE, SCAN_CONV = 16, 4  # Mamba-1's own (``assumed``: no key gives them)


def _dims(config: dict) -> dict:
    if config.get("model_type") != "phi4flash":
        raise SystemExit("sambay: a phi4flash configuration")
    if config.get("mlp_bias") or config.get("lm_head_bias"):
        raise SystemExit("sambay: no bias on the MLP or the head")
    if not config.get("tie_word_embeddings"):
        raise SystemExit("sambay: the head is the embedding")
    if config.get("mb_per_layer") != 2:
        raise SystemExit("sambay: every second layer is a scan or a GMU")
    d, nh = config["hidden_size"], config["num_attention_heads"]
    kept = [int(l) for l in config["layers_kept"]]
    if len(kept) != config["num_hidden_layers"]:
        raise SystemExit("sambay: layers_kept lists the layers that run")
    published = int(
        config.get("published_layers") or config["num_hidden_layers"])
    return {
        "d": d, "nh": nh, "nkv": config["num_key_value_heads"],
        "hd": d // nh, "f": config["intermediate_size"],
        "c": 2 * d, "n": SCAN_STATE, "r": math.ceil(d / 16),
        "taps": SCAN_CONV, "window": int(config["sliding_window"]),
        "eps": float(config["layer_norm_eps"]),
        "vocab": config["vocab_size"], "kept": tuple(kept),
        "half": published // 2,
    }


def layer_kind(m: dict, l: int) -> str:
    """What the PUBLISHED layer ``l`` is."""
    half = m["half"]
    if l % 2 == 0:
        return "scan" if l <= half else "gmu"
    if l <= half + 1:
        return "full" if l == half + 1 else "window"
    return "cross"


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, scale, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class Weights:
    """The model's weights from the seed, a layer at a time. The root key
    is split in ``4 + 8 x layers`` and consumed in order: the embedding
    (``VOCAB_BLOCKS`` blocks of rows, block ``b`` on the key folded with
    ``b``, N(0, 0.02^2)), then three a layer, ``W_gate, W_up, W_down`` (the
    fused ``gate_up_proj``'s two halves apart). Everything else of layer
    ``i`` (the i-th KEPT layer) is drawn on the root folded with ``4000 +
    i``, split in 20: 0-3 the two LayerNorms' gains ``1 + N(0, 0.1^2)`` and
    biases ``N(0, 0.1^2)``; a scan layer 4 ``W_in``, 5 the taps ``[taps,
    C]`` N(0, 1 / taps), 6 their bias N(0, 0.1^2), 7 ``W_x``, 8 ``W_dt``,
    9 the time step log-uniform in (1e-3, 1e-1) a channel (``b_dt`` its
    inverse softplus), 10 ``W_out``, with ``A_log = log(1..N)`` a channel
    and ``D`` 1; an attention layer 4-7 ``W_q, W_k, W_v, W_o``, 8-11 their
    biases N(0, 0.1^2), 12-15 ``lq1, lk1, lq2, lk2`` N(0, 0.1^2) float32,
    16 the pair norm's gain ``1 + N(0, 0.1^2)`` (a cross layer: 4, 7, 8,
    11, 12-16 alone); a GMU 4 ``W_1``, 5 ``W_2``. The final LayerNorm's
    gain and bias on the root folded with 5000, split in 2. Matrices N(0,
    1 / fan_in); everything rounded to the served dtype but ``A_log``,
    ``b_dt``, ``D`` and the lambdas."""

    def __init__(self, config: dict, seed: int):
        self.m = m = _dims(config)
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = len(m["kept"])
        self._root = jax.random.PRNGKey(seed)
        keys = iter(jax.random.split(self._root, 4 + self.layers * 8))
        self._embed_key = next(keys)
        self._mlp_keys = [
            [next(keys) for _ in range(3)] for _ in range(self.layers)
        ]
        if m["vocab"] % VOCAB_BLOCKS:
            raise SystemExit("sambay: the vocabulary cuts in 8 blocks")
        self.vocab_block = m["vocab"] // VOCAB_BLOCKS

    def _matrix(self, key, shape, scale=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[0])
        return _draw(key, scale, shape=shape, dtype=self.dtype)

    def embed_block(self, b: int):
        """Rows ``b * vocab_block ...`` of the embedding."""
        return self._matrix(
            jax.random.fold_in(self._embed_key, b),
            (self.vocab_block, self.m["d"]), 0.02)

    def final_norm(self):
        k_g, k_b = jax.random.split(jax.random.fold_in(self._root, 5000))
        d = self.m["d"]
        return (1 + self._matrix(k_g, (d,), 0.1),
                self._matrix(k_b, (d,), 0.1))

    def layer(self, i: int) -> dict:
        m = self.m
        sk = jax.random.split(jax.random.fold_in(self._root, 4000 + i), 20)
        k_gate, k_up, k_down = self._mlp_keys[i]
        d, f, c, n, r = m["d"], m["f"], m["c"], m["n"], m["r"]
        nh, nkv, hd = m["nh"], m["nkv"], m["hd"]
        f32 = jnp.float32
        kind = layer_kind(m, m["kept"][i])
        w = {
            "kind": kind,
            "ln_in": (1 + self._matrix(sk[0], (d,), 0.1),
                      self._matrix(sk[1], (d,), 0.1)),
            "ln_post": (1 + self._matrix(sk[2], (d,), 0.1),
                        self._matrix(sk[3], (d,), 0.1)),
            "w_gate": self._matrix(k_gate, (d, f)),
            "w_up": self._matrix(k_up, (d, f)),
            "w_down": self._matrix(k_down, (f, d)),
        }
        if kind == "scan":
            step = jnp.exp(jax.random.uniform(
                sk[9], (c,), f32, jnp.log(1e-3), jnp.log(1e-1)))
            w.update(
                w_in=self._matrix(sk[4], (d, 2 * c)),
                taps=self._matrix(sk[5], (m["taps"], c)),
                conv_bias=self._matrix(sk[6], (c,), 0.1),
                w_x=self._matrix(sk[7], (c, r + 2 * n)),
                w_dt=self._matrix(sk[8], (r, c)),
                b_dt=jnp.log(jnp.expm1(step)),
                a_log=jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=f32)), (c, n)),
                d_skip=jnp.ones((c,), f32),
                w_out=self._matrix(sk[10], (c, d)),
            )
        elif kind == "gmu":
            w.update(w_1=self._matrix(sk[4], (d, c)),
                     w_2=self._matrix(sk[5], (c, d)))
        else:
            w.update(
                w_q=self._matrix(sk[4], (d, nh * hd)),
                w_o=self._matrix(sk[7], (nh * hd, d)),
                b_q=self._matrix(sk[8], (nh * hd,), 0.1),
                b_o=self._matrix(sk[11], (d,), 0.1),
                lambdas=tuple(
                    _draw(sk[12 + j], 0.1, shape=(hd,), dtype=f32)
                    for j in range(4)),
                subln=1 + self._matrix(sk[16], (2 * hd,), 0.1),
            )
            if kind != "cross":
                w.update(
                    w_k=self._matrix(sk[5], (d, nkv * hd)),
                    w_v=self._matrix(sk[6], (d, nkv * hd)),
                    b_k=self._matrix(sk[9], (nkv * hd,), 0.1),
                    b_v=self._matrix(sk[10], (nkv * hd,), 0.1),
                )
        return w


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


def _ln(x, gain_bias, eps):
    g, b = (a.astype(jnp.float32) for a in gain_bias)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g + b


def _scan(h, w, m, quant):
    """h: [S, T, d] the normed input -> (mixer [S, T, d], memory [S, T,
    C]); the state from zero, a token at a time."""
    f32 = jnp.float32
    S, T, _ = h.shape
    c, n, r = m["c"], m["n"], m["r"]
    low = lambda k: _lower(w[k].astype(f32), quant)  # noqa: E731
    xz = h @ low("w_in")
    xs, z = xz[..., :c], xz[..., c:]
    taps = w["taps"].astype(f32)
    padded = jnp.pad(xs, ((0, 0), (m["taps"] - 1, 0), (0, 0)))
    # causal, depthwise: the last tap weighs the token itself
    conv = sum(taps[i] * padded[:, i:i + T] for i in range(m["taps"]))
    xc = jax.nn.silu(conv + w["conv_bias"].astype(f32))
    dbc = xc @ low("w_x")
    dr, B, C = dbc[..., :r], dbc[..., r:r + n], dbc[..., r + n:]
    dt = jax.nn.softplus(dr @ low("w_dt") + w["b_dt"])  # [S, T, C]
    A = -jnp.exp(w["a_log"])  # [C, N]

    def token(state, at):  # state: [S, C, N]
        x_t, b_t, c_t, dt_t = at
        state = jnp.exp(dt_t[..., None] * A) * state + (
            (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        token, jnp.zeros((S, c, n), f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (xc, B, C, dt)),
    )
    y = jnp.moveaxis(y, 0, 1) + w["d_skip"] * xc
    return (y * jax.nn.silu(z)) @ low("w_out"), y


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def _diff_attention(q, k, v, w, m, lam0, window):
    """q: [S, T, heads, D]; k, v: [S, T, kv heads, D] -> [S, T, heads D]
    before the output projection; a query sees the ``window`` keys that
    end at its own (all of them where ``window`` is the length)."""
    f32 = jnp.float32
    S, T, nh, hd = q.shape
    nkv = k.shape[2]
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]  # [S, T, nh / 2, D]
    k1, k2 = k[:, :, 0::2], k[:, :, 1::2]  # [S, T, nkv / 2, D]
    V = v.reshape(S, T, nkv // 2, 2 * hd)  # [v_2j | v_2j+1]
    rep = (nh // 2) // (nkv // 2)
    k1, k2, V = (jnp.repeat(a, rep, axis=2) for a in (k1, k2, V))
    pos = jnp.arange(T)
    mask = (pos[:, None] >= pos[None, :]) & (
        pos[None, :] > pos[:, None] - window)

    def softmax_v(qq, kk):
        s = jnp.einsum("sthd,suhd->shtu", qq, kk) / jnp.sqrt(f32(hd))
        s = jnp.where(mask, s, -jnp.inf)
        return jnp.einsum("shtu,suhd->sthd", jax.nn.softmax(s, axis=-1), V)

    lq1, lk1, lq2, lk2 = w["lambdas"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    a = softmax_v(q1, k1) - lam * softmax_v(q2, k2)  # [S, T, nh / 2, 2 D]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + m["eps"])
    a = a * w["subln"].astype(f32) * (1.0 - lam0)
    return a.reshape(S, T, nh * hd)


def _attention(h, w, m, lam0, window, kv, quant):
    """A self or cross attention layer. h: [S, T, d]; ``kv``: the shared
    layer's (k, v) for a cross layer. Returns (mixer, (k, v))."""
    f32 = jnp.float32
    S, T, _ = h.shape
    nh, nkv, hd = m["nh"], m["nkv"], m["hd"]
    low = lambda k: _lower(w[k].astype(f32), quant)  # noqa: E731
    q = (h @ low("w_q") + w["b_q"].astype(f32)).reshape(S, T, nh, hd)
    if w["kind"] != "cross":
        k = (h @ low("w_k") + w["b_k"].astype(f32)).reshape(S, T, nkv, hd)
        v = (h @ low("w_v") + w["b_v"].astype(f32)).reshape(S, T, nkv, hd)
        kv = (k, v)
    a = _diff_attention(q, *kv, w, m, lam0, window)
    return a @ low("w_o") + w["b_o"].astype(f32), kv


def _static(m: dict):
    return tuple(sorted(m.items()))


@partial(jax.jit, static_argnames=("dims", "kind", "quant"))
def _mixer(x, mix, carry, lam0, window, *, dims, kind, quant):
    """x plus a layer's mixer over whole sequences, and what the layer
    makes that layers above may read: ``carry`` is what THIS kind reads
    (a GMU the memory, a cross layer the shared layer's (k, v); nothing
    for a scan or a self attention layer), the second result what it
    leaves (a scan its output before the gate, a self attention layer
    its (k, v); the caller keeps the memory layer's and the shared
    layer's). ``lam0`` (``lambda_init`` at the layer's published index)
    and ``window`` (the sequence's length where the layer sees all keys)
    are traced, so that the layers of a kind share ONE program: four in
    all, and compiling them is most of a cold check's time. x: [S, T, d]
    float32."""
    m = dict(dims)
    w = dict(mix, kind=kind)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        h = _ln(x, w["ln_in"], m["eps"])
        if kind == "scan":
            out, left = _scan(h, w, m, quant)
        elif kind == "gmu":
            out = (jax.nn.silu(h @ _lower(w["w_1"].astype(f32), quant))
                   * carry) @ _lower(w["w_2"].astype(f32), quant)
            left = None
        else:
            out, left = _attention(h, w, m, lam0, window, carry, quant)
        return x + out, left


@partial(jax.jit, static_argnames=("dims", "quant"))
def _mlp(x, ln, w_gate, w_up, w_down, *, dims, quant):
    """x plus the SwiGLU MLP's output, in ``MLP_BLOCKS`` blocks of the
    hidden columns: no float32 copy of a whole matrix."""
    m = dict(dims)
    f = w_gate.shape[1]
    blocks = MLP_BLOCKS if f % MLP_BLOCKS == 0 else 1
    n = f // blocks
    with jax.default_matmul_precision("highest"):
        h = _ln(x, ln, m["eps"])
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
            return acc + (jax.nn.silu(h @ gate) * (h @ up)) @ down

        return x + jax.lax.fori_loop(0, blocks, block, jnp.zeros_like(x))


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits_block(x, ln, block, *, eps, quant):
    """The final norm and one block of the tied head's columns (a block of
    the embedding's rows, transposed) on chosen rows: [R, d] -> [R,
    block]."""
    with jax.default_matmul_precision("highest"):
        return _ln(x, ln, eps) @ _lower(block.astype(jnp.float32).T, quant)


@partial(jax.jit, static_argnames=("quant",))
def _embed_rows(block, ids, *, quant):
    """Rows ``ids`` of one block of the table, float32 [n, d]."""
    rows = block[ids].astype(jnp.float32)
    if quant is None:
        return rows
    return _lower(rows.T, quant).T  # one scale a row of the table


def _embed(w: Weights, tokens: np.ndarray, quant) -> np.ndarray:
    """[S, T, d] float32 on the host, a block of the table at a time."""
    out = np.zeros(tokens.shape + (w.m["d"],), np.float32)
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
    return out


_COMPILED: set = set()  # what _compile_beside has been through


def _compile_beside(w: Weights, final, shape, n_rows: int, quant) -> None:
    """Each program of a pass (the four mixers, the MLP, the head) once on
    a group of zeros, a thread each, so that XLA compiles them BESIDE one
    another: one after the other, as the pass reaches them, compiling was
    three quarters of a cold check's reference (64 of 85 s on one v5e).
    What they compute is thrown away; jit keeps the programs, and the pass
    below draws its weights as if this had not run. Once a (sizes, shape,
    precision). shape: a group's ``[rows, T, d]``; n_rows: the head's."""
    from concurrent.futures import ThreadPoolExecutor

    m, dims = w.m, _static(w.m)
    key = (dims, tuple(shape), n_rows, quant)
    if key in _COMPILED:
        return
    _COMPILED.add(key)
    x = jnp.zeros(shape, jnp.float32)
    lam0, window = np.float32(0.5), np.int32(shape[1])
    mixers, mlp = {}, None  # a program's weights: its first layer's
    for i, l in enumerate(m["kept"]):
        kind = layer_kind(m, l)
        program = "self" if kind in ("window", "full") else kind
        if program in mixers:
            continue
        lw = w.layer(i)
        del lw["kind"]
        mlp = [lw.pop(k) for k in ("ln_post", "w_gate", "w_up", "w_down")]
        mixers[program] = lw

    def mixer(program, carry=None):
        return partial(
            _mixer, x, mixers[program], carry, lam0, window, dims=dims,
            kind=program, quant=quant)

    def left(program):  # zeros in the shape of what a layer leaves
        return jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(mixer(program))[1])

    jobs = [mixer(p) for p in ("scan", "self") if p in mixers]
    if "gmu" in mixers:
        jobs.append(mixer("gmu", left("scan")))
    if "cross" in mixers:
        jobs.append(mixer("cross", left("self")))
    jobs.append(partial(_mlp, x, *mlp, dims=dims, quant=quant))
    jobs.append(partial(
        _logits_block, jnp.zeros((n_rows, m["d"]), jnp.float32), final,
        w.embed_block(0), eps=m["eps"], quant=quant))
    with ThreadPoolExecutor(len(jobs)) as pool:
        for done in pool.map(lambda job: job(), jobs):
            jax.block_until_ready(done)


def forward(config: dict, seed: int, tokens, positions, *, quant=None,
            early=None):
    """Logits of whole sequences at chosen positions.

    tokens: int32 [S, T] (pad the tail with anything: attention and the
    scan are causal, so what follows a position cannot reach it);
    positions: int32 [S, P]. Returns float32 [S, P, vocab]. With ``early =
    (k, positions_k)`` also returns the logits at ``positions_k`` after the
    first ``k`` KEPT layers (the final norm and head on the hidden state
    there): (logits, early_logits). Here ``k`` is the whole depth: a prefix
    that ends inside the self-decoder is another model."""
    w = Weights(config, seed)
    m = w.m
    dims = _static(m)
    # rows are cut a few sequences at a time and never joined. The layers
    # BELOW the memory layer go layer by layer over all the groups (a
    # layer's weights drawn once, the groups' hidden states waiting on the
    # device: 42 MB a group of four rows of 1,024). From the memory layer
    # up the order turns round: a GROUP goes through all those layers at
    # once, their weights held (1.6 GB at 8 layers), so that what the
    # memory layer and the shared layer leave (126 MB a group) never
    # leaves the device nor waits for another group: carried to the host
    # and back a layer, it was most of the reference's time
    tokens = np.asarray(tokens, np.int32)
    at = range(0, tokens.shape[0], ROWS_AT_ONCE)
    x_all = _embed(w, tokens, quant)
    xs = [jnp.asarray(x_all[a: a + ROWS_AT_ONCE]) for a in at]
    del x_all
    final = w.final_norm()
    _compile_beside(
        w, final, xs[0].shape, tokens.shape[0] * np.shape(positions)[1], quant)

    def rows_at(x, a, where):
        return jnp.take_along_axis(
            x, jnp.asarray(where[a: a + ROWS_AT_ONCE, :, None]), axis=1)

    def logits_of(rows):
        # the chosen rows of every sequence (a few MB), then the head a
        # block of columns at a time over all of them
        rows = np.concatenate([np.asarray(r) for r in rows])  # [S, P, d]
        S, P, d = rows.shape
        flat = jnp.asarray(rows.reshape(S * P, d))
        out = np.zeros((S * P, m["vocab"]), np.float32)
        for b in range(VOCAB_BLOCKS):
            lo = b * w.vocab_block
            out[:, lo: lo + w.vocab_block] = np.asarray(_logits_block(
                flat, final, w.embed_block(b), eps=m["eps"], quant=quant))
        return out.reshape(S, P, m["vocab"])

    def layer(i):
        lw = w.layer(i)
        kind, l = lw.pop("kind"), m["kept"][i]
        mlp = [lw.pop(k) for k in ("ln_post", "w_gate", "w_up", "w_down")]

        # the window and the full layer are one program: the full layer's
        # window is the sequence
        program = "self" if kind in ("window", "full") else kind
        window = m["window"] if kind == "window" else tokens.shape[1]

        def step(x, carry):
            mem, kv = carry
            x, left = _mixer(
                x, lw, {"gmu": mem, "cross": kv}.get(kind),
                np.float32(lambda_init(l)), np.int32(window), dims=dims,
                kind=program, quant=quant)
            if l == m["half"]:
                mem = left  # the memory: this scan's output before its gate
            if kind == "full":
                kv = left  # the shared keys and values
            return _mlp(x, *mlp, dims=dims, quant=quant), (mem, kv)

        return step

    positions = np.asarray(positions, np.int32)
    k_early, at_early = early or (0, None)
    early_rows = [None] * len(xs)
    first = m["kept"].index(m["half"])  # the first layer that leaves a carry
    for i in range(first):
        step = layer(i)
        for j, a in enumerate(at):
            xs[j], _ = step(xs[j], (None, None))
            if i + 1 == k_early:
                early_rows[j] = rows_at(xs[j], a, at_early)
        del step
    upper = [layer(i) for i in range(first, w.layers)]
    for j, a in enumerate(at):
        x, carry = xs[j], (None, None)
        for i, step in enumerate(upper, first):
            x, carry = step(x, carry)
            if i + 1 == k_early:
                early_rows[j] = rows_at(x, a, at_early)
        xs[j] = rows_at(x, a, positions)
    logits = logits_of(xs)
    if early is None:
        return logits
    return logits, logits_of(early_rows)
