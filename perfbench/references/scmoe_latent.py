"""Plain reference of a decoder of shortcut-connected MoE double layers over
latent (MLA) attention with identity ("zero-computation") experts
(LongCat-Flash-Chat): the published forward pass in straightforward
``jax.numpy``, float32, ``default_matmul_precision("highest")``; NOT
absorbed, no cache, no kernel, no sorting or grouping of tokens, no call
into ``dynamo_tpu``. Read from the public ``config.json`` keys alone
(``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``n_routed_experts``, ``zero_expert_num``, ``zero_expert_type``,
``moe_topk``, ``routed_scaling_factor``, ``mla_scale_q_lora``,
``mla_scale_kv_lora``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``...), never from
the configuration's ``model_spec``.

    rms: RMSNorm with a gain (1 here) and rms_norm_eps; H heads, width d.
    x = E[tokens]; layer l, sub-layers j = 0, 1:

    MLA_j(h):  c_q = rms(h W_qa)                                 q_lora_rank
               [q_n, q_r]_i = sqrt(d / q_lora_rank) * (c_q W_qb)_i
                                        H x (qk_nope + qk_rope); mla_scale_q_lora
               [c_kv, k_r] = h W_kva              kv_lora_rank + qk_rope
               c = sqrt(d / kv_lora_rank) * rms(c_kv)    mla_scale_kv_lora;
                                                         k_r is NOT scaled
               q_r, k_r rotated by the position at rope_theta on INTERLEAVED
                   pairs (2j, 2j + 1); k_r is shared by the heads
               [k_n, v]_i = c W_kvb,i               qk_nope + v_head_dim
               s_i = (q_n,i . k_n,i + q_r,i . k_r) / sqrt(qk_nope + qk_rope)
               o_i = sum softmax_causal(s_i) v_i
               MLA_j = concat_i(o_i) W_o
    FFN_j(u) = (silu(u W_g) * (u W_u)) W_d                 ffn_hidden_size

    x1 = x  + MLA_0(rms(x))
    u  = rms(x1)
    m  = MoE(u)                  the shortcut: made here, added at the end
    x2 = x1 + FFN_0(u)
    x3 = x2 + MLA_1(rms(x2))
    x' = x3 + FFN_1(rms(x3)) + m

    MoE(u):  p = softmax(u W_r), float32, over ALL n_routed_experts +
                 zero_expert_num outputs (the identity experts' ids follow
                 the FFN experts')
             chosen = the moe_topk largest of p + b     b: correction bias
             w_e = routed_scaling_factor * p_e          NOT renormalised
             MoE = sum over chosen FFN experts e of w_e * FFN_e(u)
                   + (sum over chosen identity experts of w_e) * u
             FFN_e: SwiGLU of expert_ffn_hidden_size
    logits = rms(x_last) W_head                                    untied

Every held expert's FFN is computed for every token and weighted (zero
where not chosen): the plainest form of the sum above. The latent rows the
program caches, ``[c, k_r]``, appear here only as two intermediate values.

Departures from the published model, all stated in the configuration's
file: random weights; the depth (``num_layers``); the share of one chip of
an expert-parallel deployment: of the ``experts.published`` FFN experts
only ``experts.held`` from ``experts.first`` are here, the router still
scores all of them and the identity experts, a chosen FFN expert that is
absent adds nothing (its chip adds it), while the identity experts' term is
computed here for every token (no chip holds them; it counts ONCE when the
shares are summed: ``identity=False`` leaves it out, for the test that sums
them); ``vocab_size`` rows of the embedding and columns of the head (one
group of a vocabulary-parallel split).

It takes nothing the program has made. The weights are drawn here from the
seed by this file's own copy of the recipe the engine is documented to use
(``assumed`` in the configuration's file): the root key split in ``4 + 17 x
layers``; embedding, head, then a layer its first sub-layer's eight keys
(``W_qa, W_qb, W_kva, W_kvb, W_o``, then the dense FFN's gate, up, down),
its second sub-layer's eight, and one for the expert layer, split in four
for router, gate, up, down (each projection's held experts drawn as one
``[held, in, out]`` array; the correction bias ``N(0, 1 / outputs^2)`` in
float32 on that key folded with 1); ``N(0, 1 / fan_in)`` (``W_qb`` and ``W_kvb``
``N(0, 1 / d)``: the family scales their ranks by ``sqrt(d / rank)``, which
then gives unit-variance queries, keys and values), embedding ``N(0, 0.02^2)``, router ``N(0, 1.5^2 / d)``, norm gains 1, everything but the
bias rounded to the served dtype. A SUB-LAYER's weights at a time, the
expert layer's apart, an expert at a time in the arithmetic, and the hidden
states kept on the host between passes: a dense FFN is 0.9 GB in float32
and a layer's 16 experts 2.4 GB, so the whole layer does not fit beside the
bf16 model.

Every program of a pass is compiled AHEAD, from shapes alone, on threads of
their own (``_warm``; nothing is allocated), and called as compiled: the
chip's compiler takes ~50 s over them one after the other in a process's
first pass, which a cold run's 360 s do not have (PERF.md section 6, PR 49).

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

ROWS_AT_ONCE = 4  # sequences a pass: the reference runs beside the model
ATTN = ("w_qa", "w_qb", "w_kva", "w_kvb", "w_o")
DENSE = ("w_gate", "w_up", "w_down")


def _dims(config: dict) -> dict:
    if config.get("zero_expert_type", "identity") != "identity":
        raise ValueError("zero experts of another type than identity")
    ex = config.get("experts") or {
        "published": config["n_routed_experts"],
        "held": config["n_routed_experts"], "first": 0,
    }
    d = config["hidden_size"]
    return {
        "d": d, "nh": config["num_attention_heads"],
        "layers": config["num_layers"],
        "q_rank": config["q_lora_rank"], "dc": config["kv_lora_rank"],
        "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
        "dv": config["v_head_dim"],
        "q_scale": float((d / config["q_lora_rank"]) ** 0.5)
        if config.get("mla_scale_q_lora") else 1.0,
        "kv_scale": float((d / config["kv_lora_rank"]) ** 0.5)
        if config.get("mla_scale_kv_lora") else 1.0,
        "theta": float(config["rope_theta"]),
        "f_dense": config["ffn_hidden_size"],
        "f": config["expert_ffn_hidden_size"],
        "experts": int(ex["published"]), "held": int(ex["held"]),
        "first": int(ex["first"]),
        "zeros": int(config.get("zero_expert_num") or 0),
        "topk": config["moe_topk"],
        "scaling": float(config.get("routed_scaling_factor") or 1.0),
        "norm_topk": bool(config.get("norm_topk_prob", False)),
        "eps": float(config["rms_norm_eps"]),
        "vocab": config["vocab_size"],
    }


_PROGRAMS: dict = {}  # (function, argument shapes, statics) -> compiled


def _program(fn, args, static):
    """``fn`` compiled for arguments of ``args``' shapes (arrays or shape
    structs) and the given static arguments, once a process."""
    leaves, tree = jax.tree.flatten(args)
    key = (fn.__name__, tree,
           tuple((tuple(a.shape), str(a.dtype)) for a in leaves),
           tuple(sorted(static.items())))
    if key not in _PROGRAMS:
        shapes = jax.tree.unflatten(tree, [
            jax.ShapeDtypeStruct(a.shape, a.dtype) for a in leaves])
        _PROGRAMS[key] = fn.lower(*shapes, **static).compile()
    return _PROGRAMS[key]


def _run(fn, *args, **static):
    """``fn(*args, **static)`` through its compiled program (traced as it
    is where shapes alone are asked for: ``_warm``'s ``eval_shape``)."""
    args = jax.tree.map(  # a Python scalar (a draw's scale) as float32
        lambda a: a if hasattr(a, "shape") else np.float32(a), args)
    if any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves(args)):
        return fn(*args, **static)
    return _program(fn, args, static)(*args)


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, scale, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class Weights:
    """The model's weights from the seed, a sub-layer (or an expert layer)
    at a time."""

    def __init__(self, config: dict, seed: int):
        self.m = _dims(config)
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = self.m["layers"]
        keys = iter(jax.random.split(
            jax.random.PRNGKey(seed), 4 + self.layers * 17))
        self._embed_key, self._head_key = next(keys), next(keys)
        self._layer_keys = [
            [next(keys) for _ in range(17)] for _ in range(self.layers)
        ]

    def _matrix(self, key, shape, scale=None, dtype=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[-2])  # on the device, as the engine
        return _run(_draw, key, scale, shape=tuple(shape),
                    dtype=jnp.dtype(dtype or self.dtype).name)

    def embed(self):
        return self._matrix(
            self._embed_key, (self.m["vocab"], self.m["d"]), 0.02)

    def head(self):
        return self._matrix(self._head_key, (self.m["d"], self.m["vocab"]))

    def attention(self, i: int, j: int) -> dict:
        m = self.m
        k_qa, k_qb, k_kva, k_kvb, k_o = self._layer_keys[i][8 * j: 8 * j + 5]
        d, nh = m["d"], m["nh"]
        # the two up-projections behind the ranks this family scales are
        # drawn at the model's WIDTH as their fan-in: the scalars sqrt(d /
        # rank) bring their outputs to unit variance (whatever the config's
        # flags say: a scalar left out must show)
        up_q = up_kv = 1.0 / jnp.sqrt(d)
        return {
            "w_qa": self._matrix(k_qa, (d, m["q_rank"])),
            "w_qb": self._matrix(
                k_qb, (m["q_rank"], nh * (m["dn"] + m["dr"])), up_q),
            "w_kva": self._matrix(k_kva, (d, m["dc"] + m["dr"])),
            "w_kvb": self._matrix(
                k_kvb, (m["dc"], nh * (m["dn"] + m["dv"])), up_kv),
            "w_o": self._matrix(k_o, (nh * m["dv"], d)),
        }

    def dense(self, i: int, j: int) -> dict:
        d, f = self.m["d"], self.m["f_dense"]
        k1, k2, k3 = self._layer_keys[i][8 * j + 5: 8 * j + 8]
        return {
            "w_gate": self._matrix(k1, (d, f)),
            "w_up": self._matrix(k2, (d, f)),
            "w_down": self._matrix(k3, (f, d)),
        }

    def experts(self, i: int) -> dict:
        m = self.m
        key = self._layer_keys[i][16]
        r1, r2, r3, r4 = jax.random.split(key, 4)
        d, held, f = m["d"], m["held"], m["f"]
        outputs = m["experts"] + m["zeros"]
        return {
            "router": self._matrix(r1, (d, outputs), 1.5 / d ** 0.5),
            "e_gate": self._matrix(r2, (held, d, f)),
            "e_up": self._matrix(r3, (held, d, f)),
            "e_down": self._matrix(r4, (held, f, d)),
            "score_bias": self._matrix(
                jax.random.fold_in(key, 1), (outputs,), 1.0 / outputs,
                dtype=jnp.float32),
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
    """x: [S, T, ..., D] rotated by its position (axis 1) over all D dims
    on the interleaved pairs (2j, 2j + 1); pair j turns by ``p *
    theta^(-2j / D)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [T, half]
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [a * cos - b * sin, b * cos + a * sin], axis=-1
    ).reshape(x.shape)


@partial(jax.jit, static_argnames=(
    "heads", "dc", "dn", "dr", "dv", "q_scale", "kv_scale", "theta", "eps",
    "quant"))
def _attention(x, lw, *, heads, dc, dn, dr, dv, q_scale, kv_scale, theta,
               eps, quant):
    """x + MLA(rms(x)) over whole sequences; x: [S, T, d] float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(lw[k].astype(jnp.float32), quant) for k in ATTN}
        S, T, _ = x.shape
        pos = jnp.arange(T)
        h = _rms(x, eps)
        q = q_scale * (_rms(h @ w["w_qa"], eps) @ w["w_qb"])
        q = q.reshape(S, T, heads, dn + dr)
        q_n, q_r = q[..., :dn], _rotary(q[..., dn:], pos, theta)
        kv = h @ w["w_kva"]
        c = kv_scale * _rms(kv[..., :dc], eps)
        k_r = _rotary(kv[..., dc:], pos, theta)  # [S, T, dr]: not scaled
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
def _dense_ffn(x, lw, *, eps, quant):
    """x + FFN(rms(x))."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(lw[k].astype(jnp.float32), quant) for k in DENSE}
        u = _rms(x, eps)
        return x + (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=(
    "topk", "experts", "first", "held", "scaling", "norm_topk", "eps",
    "quant"))
def _route(x, router, bias, *, topk, experts, first, held, scaling,
           norm_topk, eps, quant):
    """(u = rms(x), the weight of each HELD expert for each token [S, T,
    held], zero where it is not among the token's chosen, and the sum of
    each token's chosen IDENTITY experts' weights [S, T, 1])."""
    with jax.default_matmul_precision("highest"):
        u = _rms(x, eps)
        p = jax.nn.softmax(
            u @ _lower(router.astype(jnp.float32), quant), axis=-1)
        _, chosen = jax.lax.top_k(p + bias, topk)  # [S, T, topk]
        picked = jnp.take_along_axis(p, chosen, axis=-1)
        if norm_topk:
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        picked = picked * scaling
        here = jnp.arange(first, first + held)
        hit = chosen[..., None] == here  # [S, T, topk, held]
        zero = jnp.sum(
            jnp.where(chosen >= experts, picked, 0.0), axis=-1, keepdims=True)
        return (u, jnp.sum(jnp.where(hit, picked[..., None], 0.0), axis=-2),
                zero)


@partial(jax.jit, static_argnames=("quant",))
def _expert(m, u, weights, gates, ups, downs, e, *, quant):
    """m plus held expert ``e``'s weighted FFN over every token. gates,
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
        return m + weight * ((jax.nn.silu(u @ gate) * (u @ up)) @ down)


def _moe(m_: dict, x, ew: dict, quant, identity: bool):
    """MoE(rms(x)): this share's FFN experts and (``identity``) the
    identity experts' term."""
    u, weights, zero = _run(
        _route, x, ew["router"], ew["score_bias"], topk=m_["topk"],
        experts=m_["experts"], first=m_["first"], held=m_["held"],
        scaling=m_["scaling"], norm_topk=m_["norm_topk"], eps=m_["eps"],
        quant=quant,
    )
    out = zero * u if identity else jnp.zeros_like(u)
    for e in range(m_["held"]):
        out = _run(
            _expert, out, u, weights, ew["e_gate"], ew["e_up"], ew["e_down"],
            np.int32(e), quant=quant,
        )
    return out


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits_at(x, positions, head, *, eps, quant):
    """The final norm and the head at chosen positions of x: [S, T, d] ->
    [S, P, vocab]."""
    with jax.default_matmul_precision("highest"):
        at = _rms(jnp.take_along_axis(x, positions[:, :, None], axis=1), eps)
        return at @ _lower(head.astype(jnp.float32), quant)


@partial(jax.jit, static_argnames=("quant",))
def _embed_rows(table, tokens, *, quant):
    rows = table[tokens].astype(jnp.float32)  # [S, T, d]
    if quant is None:
        return rows
    flat = rows.reshape(-1, rows.shape[-1]).T  # one scale a row of the table
    return _lower(flat, quant).T.reshape(rows.shape)


def _each(fn, xs: list) -> list:
    """``fn`` over the hidden states, a few sequences at a time: they live
    on the host between passes (a decode slot's worth of rows at this width
    is gigabytes) and only one group of rows is on the device."""
    return [np.asarray(fn(x)) for x in xs]


def _attention_statics(m: dict) -> dict:
    return dict(heads=m["nh"], dc=m["dc"], dn=m["dn"], dr=m["dr"], dv=m["dv"],
                q_scale=m["q_scale"], kv_scale=m["kv_scale"],
                theta=m["theta"], eps=m["eps"])


def _sub_layer(w: Weights, i: int, j: int, xs: list, quant) -> tuple:
    """(x + MLA_j(rms(x)), that + FFN_j(rms(that))) of every group of rows:
    the attention's weights, then the dense FFN's."""
    m = w.m
    lw = w.attention(i, j)
    xs = _each(lambda x: _run(
        _attention, x, lw, **_attention_statics(m), quant=quant), xs)
    lw = w.dense(i, j)
    return xs, _each(
        lambda x: _run(_dense_ffn, x, lw, eps=m["eps"], quant=quant), xs)


def layer(w: Weights, i: int, xs: list, quant=None, identity=True) -> list:
    """Decoder layer ``i`` over groups of rows ``xs`` ([S, T, d] float32 on
    the host): the data flow at the head of this file."""
    x1, x2 = _sub_layer(w, i, 0, xs, quant)
    ew = w.experts(i)
    ms = _each(lambda x: _moe(w.m, x, ew, quant, identity), x1)
    del ew, x1
    _, out = _sub_layer(w, i, 1, x2, quant)
    return [x + m for x, m in zip(out, ms)]


def _warm(w: Weights, rows: int, T: int, quant) -> None:
    """Every program a pass over ``rows`` x ``T`` tokens will run, compiled
    AHEAD from shapes alone on threads of their own: the draws of every
    weight shape, the attention, the dense FFN, the router, an expert, the
    embedding and the head. Nothing is allocated and nothing is computed;
    the pass finds them in ``_PROGRAMS``."""
    from concurrent.futures import ThreadPoolExecutor

    m = w.m
    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((rows, T, m["d"]), f32)
    shapes = jax.eval_shape(lambda: (
        w.attention(0, 0), w.dense(0, 0), w.experts(0), w.embed(), w.head()))
    attn, dense, ew, table, head = shapes
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    scale = jax.ShapeDtypeStruct((), f32)
    jobs = [
        (_draw, (key, scale), dict(shape=tuple(a.shape), dtype=a.dtype.name))
        for a in {(a.shape, a.dtype): a
                  for a in jax.tree.leaves(shapes)}.values()
    ] + [
        (_attention, (x, attn), dict(_attention_statics(m), quant=quant)),
        (_dense_ffn, (x, dense), dict(eps=m["eps"], quant=quant)),
        (_route, (x, ew["router"], ew["score_bias"]), dict(
            topk=m["topk"], experts=m["experts"], first=m["first"],
            held=m["held"], scaling=m["scaling"], norm_topk=m["norm_topk"],
            eps=m["eps"], quant=quant)),
        (_expert, (x, x, jax.ShapeDtypeStruct((rows, T, m["held"]), f32),
                   ew["e_gate"], ew["e_up"], ew["e_down"],
                   jax.ShapeDtypeStruct((), jnp.int32)), dict(quant=quant)),
        (_embed_rows, (table, jax.ShapeDtypeStruct((rows, T), jnp.int32)),
         dict(quant=quant)),
        (_logits_at, (x, jax.ShapeDtypeStruct((rows, 16), jnp.int32), head),
         dict(eps=m["eps"], quant=quant)),
    ]
    with ThreadPoolExecutor(8) as pool:
        for job in [pool.submit(_program, *j) for j in jobs]:
            job.result()


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
    tokens = np.asarray(tokens, np.int32)
    at = range(0, tokens.shape[0], ROWS_AT_ONCE)
    _warm(w, min(ROWS_AT_ONCE, tokens.shape[0]), tokens.shape[1], quant)
    table = w.embed()
    xs = [np.asarray(
        _run(_embed_rows, table, tokens[a: a + ROWS_AT_ONCE], quant=quant))
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
            np.asarray(_run(
                _logits_at, x, where[a: a + ROWS_AT_ONCE], head,
                eps=m["eps"], quant=quant))
            for x, a in zip(xs, at)
        ])[:, :n]

    early_logits = None
    for i in range(w.layers):
        xs = layer(w, i, xs, quant)
        if early is not None and i + 1 == early[0]:
            early_logits = logits_at(early[1])
    logits = logits_at(positions)
    if early is None:
        return logits
    return logits, early_logits
