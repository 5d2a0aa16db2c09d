"""Plain reference of a decoder of gated, QK-normed GQA layers whose window
layers rotate and whose full layers carry no position, between four norms
a layer, over sigmoid-routed experts beside a shared one (Trinity-Mini,
family ``afmoe``): the published forward pass in straightforward
``jax.numpy``, float32, ``default_matmul_precision("highest")``;
whole-sequence masked attention (a window mask; no cache, no kernel), no
sorting or grouping of tokens, no call into ``dynamo_tpu``. Read from the
public ``config.json`` keys alone (``layer_types``, ``sliding_window``,
``num_dense_layers``, ``num_experts_per_tok``, ``num_shared_experts``,
``route_norm``, ``route_scale``, ``score_func``, ``mup_enabled``,
``rope_theta``, ``rms_norm_eps``...), never from the configuration's
``model_spec``.

    rms: RMSNorm with its own gain, rms_norm_eps; width d, H query heads
    over KH key-value heads of head_dim.

    x0 = sqrt(d) * E[token]                      mup_enabled: once, at the embedding
    attn(h):  q = rms_head(h Wq) [H x hd],  k = rms_head(h Wk) [KH x hd]
                  one gain [hd] for all heads of q, one for k;  v = h Wv
              layer_types[l] == "sliding_attention": q, k rotated at the
                  position (rope_theta, all hd dims, half-split pairs
                  (i, i + hd/2)); a query at t sees keys t - window + 1 .. t
              "full_attention": q, k NOT rotated; sees keys 0 .. t
              o = softmax(q k^T / sqrt(hd)) v          no sinks, no bias
              attn = (o * sigmoid(h Wg)) Wo            Wg: d -> H hd, by element
    x1 = x  + rms_post_attn(attn(rms_in(x)))
    x' = x1 + rms_post_mlp(ffn(rms_pre_mlp(x1)))
    ffn, l < num_dense_layers:  (silu(u Wg) * (u Wu)) Wd      intermediate_size
    ffn, else:  s = sigmoid(u Wr), float32, over ALL routed experts
                chosen = the num_experts_per_tok largest of s + b
                    b: expert_bias, float32, enters the choice alone
                w_e = route_scale * s_e / (sum over chosen of s + 1e-20)
                    (route_norm; without it route_scale * s_e)
                ffn = sum over chosen e of w_e * FFN_e(u) + FFN_shared(u)
                    both SwiGLU of moe_intermediate_size (the shared one
                    times num_shared_experts)
    logits = rms_final(x_last) W_head                        untied

Every held expert's FFN is computed for every token and weighted (zero
where not chosen): the plainest form of the sum above. Attention scores a
block of ``QUERY_BLOCK`` queries against the whole sequence's keys under
the mask, block after block (``lax.map``), so that 4,096 positions fit:
the same numbers as the whole ``[T, T]`` matrix, a block of its rows at a
time.

Departures from the published model, all stated in the configuration's
file: random weights; the depth (``layers_kept`` picks the published
layers whose ``layer_types`` entry and whose place against
``num_dense_layers`` each kept layer takes); the share of one chip of an
expert-parallel deployment: of the ``experts.published`` routed experts
only ``experts.held`` from ``experts.first`` are here, the router still
scores all of them and a chosen expert that is absent adds nothing (its
chip adds it), while the shared expert is computed here for every token
(it counts ONCE when the shares are summed: ``shared=False`` leaves it
out, for the test that sums them) and ``rms_post_mlp`` is applied to THIS
share's partial sum (a deployment norms the combined sum); ``vocab_size``
rows of the embedding and columns of the head (one group of a
vocabulary-parallel split).

It takes nothing the program has made. The weights are drawn here from the
seed by this file's own copy of the recipe the engine is documented to use
(``assumed`` in the configuration's file): the root key split in ``4 + 8 x
layers``; embedding, head, then a layer ``Wq, Wk, Wv, Wo`` and its FFN
(dense: gate, up, down; experts: one key split in four for router, gate,
up, down, each projection's held experts drawn as one ``[held, in, out]``
array, the bias ``N(0, 0.1^2)`` in float32 on that key folded with 1; then
one key split in three for the shared expert); ``N(0, 1 / fan_in)``,
embedding and router ``N(0, 0.02^2)``; on keys folded from the root: the
gate ``Wg`` on the first and the q and k gains ``1 + N(0, 0.1^2)`` on the
eighth and ninth of nine split from ``fold_in(root, 2000 + layer)``, the
two output norms' gains ``1 + N(0, 0.1^2)`` on the two split from
``fold_in(root, 3000 + layer)``; the input norms' gains 1; everything but
the bias rounded to the served dtype. A layer at a time, an expert at a
time in the arithmetic, a few sequences at a time, so that the reference
fits beside the bf16 model.

Every program of a pass is compiled AHEAD, from shapes alone, on threads of
their own (``_warm``; nothing is allocated), and called as compiled: the
chip's compiler takes them one after the other in a process's first pass,
which a cold run's 360 s do not have.

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
QUERY_BLOCK = 256  # queries scored at once against the sequence's keys
ATTN = ("wq", "wk", "wv", "wg", "wo")
DENSE = ("w_gate", "w_up", "w_down")
SHARED = ("s_gate", "s_up", "s_down")


def _dims(config: dict) -> dict:
    if config.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("a router of another score than sigmoid")
    if max(int(config.get("n_group") or 1), int(config.get("topk_group") or 1),
           int(config.get("num_expert_groups") or 1),
           int(config.get("num_limited_groups") or 1)) > 1:
        raise ValueError("group-limited routing: this family publishes none")
    kept = config.get("layers_kept") or list(range(config["num_hidden_layers"]))
    ex = config.get("experts") or {
        "published": config["num_experts"], "held": config["num_experts"],
        "first": 0,
    }
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    return {
        "d": d, "nh": heads, "nkv": config["num_key_value_heads"],
        "hd": int(config.get("head_dim") or d // heads),
        "window": [
            int(config["sliding_window"])
            if config["layer_types"][i] == "sliding_attention" else 0
            for i in kept
        ],
        "moe": [i >= int(config["num_dense_layers"]) for i in kept],
        "theta": float(config["rope_theta"]),
        "f_dense": config["intermediate_size"],
        "f": config["moe_intermediate_size"],
        "n_shared": int(config.get("num_shared_experts") or 0),
        "experts": int(ex["published"]), "held": int(ex["held"]),
        "first": int(ex["first"]), "topk": config["num_experts_per_tok"],
        "scale": float(config.get("route_scale") or 1.0),
        "route_norm": bool(config.get("route_norm", True)),
        "embed_mult": float(d ** 0.5) if config.get("mup_enabled") else 1.0,
        "eps": float(config["rms_norm_eps"]),
        "vocab": config["vocab_size"],
        "tied": bool(config.get("tie_word_embeddings", False)),
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
    # a stack of matrices as one matrix of their rows: the same bits (the
    # generator counts elements, not axes)
    flat = shape if len(shape) < 3 else (
        int(np.prod(shape[:-1])), shape[-1])
    draw = jax.random.normal(key, flat, jnp.float32).reshape(shape)
    return (draw * scale).astype(dtype)


class Weights:
    """The model's weights from the seed, a layer's part at a time."""

    def __init__(self, config: dict, seed: int):
        self.m = m = _dims(config)
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = len(m["moe"])
        self._root = jax.random.PRNGKey(seed)
        keys = iter(jax.random.split(self._root, 4 + self.layers * 8))
        self._embed_key = next(keys)
        self._head_key = None if m["tied"] else next(keys)
        self._layer_keys = []
        for moe in m["moe"]:
            names = ATTN[:3] + ATTN[4:] + (
                (("moe",) + (("shared",) if m["n_shared"] else ()))
                if moe else DENSE)
            self._layer_keys.append({n: next(keys) for n in names})

    def _matrix(self, key, shape, scale=None, dtype=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[-2])  # on the device, as the engine
        return _run(_draw, key, scale, shape=tuple(shape),
                    dtype=jnp.dtype(dtype or self.dtype).name)

    def _gain(self, key, n):
        """``1 + N(0, 0.1^2)``, the sum in the served dtype."""
        return 1 + self._matrix(key, (n,), 0.1)

    def embed(self):
        return self._matrix(
            self._embed_key, (self.m["vocab"], self.m["d"]), 0.02)

    def head(self):
        if self._head_key is None:
            return self.embed().T
        return self._matrix(self._head_key, (self.m["d"], self.m["vocab"]))

    def attention(self, i: int) -> dict:
        m, keys = self.m, self._layer_keys[i]
        d, nh, nkv, hd = m["d"], m["nh"], m["nkv"], m["hd"]
        extra = jax.random.split(jax.random.fold_in(self._root, 2000 + i), 9)
        post, _ = jax.random.split(jax.random.fold_in(self._root, 3000 + i))
        return {
            "wq": self._matrix(keys["wq"], (d, nh * hd)),
            "wk": self._matrix(keys["wk"], (d, nkv * hd)),
            "wv": self._matrix(keys["wv"], (d, nkv * hd)),
            "wg": self._matrix(extra[0], (d, nh * hd)),
            "wo": self._matrix(keys["wo"], (nh * hd, d)),
            "q_gain": self._gain(extra[7], hd),
            "k_gain": self._gain(extra[8], hd),
            "post_gain": self._gain(post, d),
        }

    def post_mlp_gain(self, i: int):
        _, post = jax.random.split(jax.random.fold_in(self._root, 3000 + i))
        return self._gain(post, self.m["d"])

    def dense(self, i: int) -> dict:
        d, f = self.m["d"], self.m["f_dense"]
        keys = self._layer_keys[i]
        return {
            "w_gate": self._matrix(keys["w_gate"], (d, f)),
            "w_up": self._matrix(keys["w_up"], (d, f)),
            "w_down": self._matrix(keys["w_down"], (f, d)),
        }

    def experts(self, i: int) -> dict:
        m = self.m
        key = self._layer_keys[i]["moe"]
        r1, r2, r3, r4 = jax.random.split(key, 4)
        d, held, f = m["d"], m["held"], m["f"]
        out = {
            "router": self._matrix(r1, (d, m["experts"]), 0.02),
            "e_gate": self._matrix(r2, (held, d, f)),
            "e_up": self._matrix(r3, (held, d, f)),
            "e_down": self._matrix(r4, (held, f, d)),
            "score_bias": self._matrix(
                jax.random.fold_in(key, 1), (m["experts"],), 0.1,
                dtype=jnp.float32),
        }
        if m["n_shared"]:
            fs = f * m["n_shared"]
            kg, ku, kd = jax.random.split(self._layer_keys[i]["shared"], 3)
            out.update(
                s_gate=self._matrix(kg, (d, fs)),
                s_up=self._matrix(ku, (d, fs)),
                s_down=self._matrix(kd, (fs, d)),
            )
        return out


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
    """x: [S, T, heads, D] rotated by its position (axis 1) over all D dims
    on the half-split pairs (i, i + D/2); pair i turns by ``p *
    theta^(-2i / D)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "hd", "window", "theta", "eps", "quant", "block"))
def _attention(x, lw, *, heads, kv_heads, hd, window, theta, eps, quant,
               block=QUERY_BLOCK):
    """x + rms_post_attn(attn(rms_in(x))) over whole sequences; x: [S, T,
    d] float32. ``window`` 0: a full layer, which does not rotate."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(lw[k].astype(jnp.float32), quant) for k in ATTN}
        S, T, _ = x.shape
        pos = jnp.arange(T)
        h = _rms(x, eps)
        q = _rms((h @ w["wq"]).reshape(S, T, heads, hd), eps, lw["q_gain"])
        k = _rms((h @ w["wk"]).reshape(S, T, kv_heads, hd), eps, lw["k_gain"])
        v = (h @ w["wv"]).reshape(S, T, kv_heads, hd)
        if window:
            q, k = _rotary(q, pos, theta), _rotary(k, pos, theta)
        group = heads // kv_heads
        # a block of queries against every key, under the mask
        blocks = -(-T // block)
        qb = jnp.pad(q, ((0, 0), (0, blocks * block - T), (0, 0), (0, 0)))
        qb = qb.reshape(S, blocks, block, kv_heads, group, hd)

        def rows(at):
            q_pos = at * block + jnp.arange(block)
            scores = jnp.einsum(
                "stkgd,sukd->skgtu", qb[:, at], k) / jnp.sqrt(jnp.float32(hd))
            seen = q_pos[:, None] >= pos[None, :]
            if window:
                seen &= q_pos[:, None] - pos[None, :] < window
            scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
            return jnp.einsum(
                "skgtu,sukd->stkgd", jax.nn.softmax(scores, axis=-1), v)

        o = jax.lax.map(rows, jnp.arange(blocks))  # [blocks, S, block, ...]
        o = jnp.moveaxis(o, 0, 1).reshape(S, blocks * block, heads * hd)[:, :T]
        a = (o * jax.nn.sigmoid(h @ w["wg"])) @ w["wo"]
        return x + _rms(a, eps, lw["post_gain"])


@partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_ffn(x, lw, *, eps, quant):
    """FFN(rms_pre_mlp(x)), not yet normed."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(lw[k].astype(jnp.float32), quant) for k in DENSE}
        u = _rms(x, eps)
        return (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=(
    "topk", "held", "scale", "route_norm", "eps", "quant"))
def _route(x, router, bias, first, *, topk, held, scale, route_norm, eps,
           quant):
    """(u = rms_pre_mlp(x), the weight of each HELD expert for each token
    [S, T, held]: zero where it is not among the token's chosen). ``first``
    (the first held expert's id) is an argument, not a constant: one
    program serves every share."""
    with jax.default_matmul_precision("highest"):
        u = _rms(x, eps)
        s = jax.nn.sigmoid(u @ _lower(router.astype(jnp.float32), quant))
        _, chosen = jax.lax.top_k(s + bias, topk)  # [S, T, topk]
        picked = jnp.take_along_axis(s, chosen, axis=-1)
        if route_norm:
            picked = picked / (
                jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        picked = picked * scale
        here = first + jnp.arange(held)
        hit = chosen[..., None] == here  # [S, T, topk, held]
        return u, jnp.sum(jnp.where(hit, picked[..., None], 0.0), axis=-2)


@partial(jax.jit, static_argnames=("quant",))
def _shared(u, sw, *, quant):
    """The shared expert's FFN over every token."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(sw[k].astype(jnp.float32), quant) for k in SHARED}
        return (jax.nn.silu(u @ w["s_gate"]) * (u @ w["s_up"])) @ w["s_down"]


@partial(jax.jit, static_argnames=("quant",))
def _expert(acc, u, weights, gates, ups, downs, e, *, quant):
    """acc plus held expert ``e``'s weighted FFN over every token. gates,
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
        return acc + weight * ((jax.nn.silu(u @ gate) * (u @ up)) @ down)


@partial(jax.jit, static_argnames=("eps",))
def _close(x, y, gain, *, eps):
    """x + rms_post_mlp(y)."""
    return x + _rms(y, eps, gain)


def moe_out(m: dict, x, ew: dict, quant=None, shared=True):
    """What this share's expert layer puts out for the stream ``x``,
    BEFORE the output norm: its held experts' weighted FFNs and
    (``shared``) the shared expert's."""
    u, weights = _run(
        _route, x, ew["router"], ew["score_bias"], np.int32(m["first"]),
        topk=m["topk"], held=m["held"], scale=m["scale"],
        route_norm=m["route_norm"], eps=m["eps"], quant=quant,
    )
    if shared and m["n_shared"]:
        out = _run(_shared, u, {k: ew[k] for k in SHARED}, quant=quant)
    else:
        out = jnp.zeros_like(u)
    for e in range(m["held"]):
        out = _run(
            _expert, out, u, weights, ew["e_gate"], ew["e_up"], ew["e_down"],
            np.int32(e), quant=quant,
        )
    return out


def _attention_statics(m: dict, i: int) -> dict:
    return dict(heads=m["nh"], kv_heads=m["nkv"], hd=m["hd"],
                window=m["window"][i], theta=m["theta"], eps=m["eps"])


def layer(w: Weights, i: int, xs: list, quant=None, shared=True) -> list:
    """Kept layer ``i`` over groups of rows ``xs`` ([S, T, d] float32):
    the data flow at the head of this file. A group at a time through the
    whole layer, its state replaced IN ``xs`` as it comes out: what is
    held is every group's state once (1.9 GB for a decode slot's worth of
    64 rows of 3,648 tokens) and one group's intermediates, not three
    copies of every group's, beside a live engine that leaves the check
    half a gigabyte of the chip (PERF.md section 6, PR 53)."""
    m = w.m
    lw = w.attention(i)
    gain = w.post_mlp_gain(i)
    fw = w.experts(i) if m["moe"][i] else w.dense(i)
    for j, x in enumerate(xs):
        xs[j] = None
        x = _run(_attention, x, lw, **_attention_statics(m, i), quant=quant)
        if m["moe"][i]:
            y = moe_out(m, x, fw, quant, shared)
        else:
            y = _run(_dense_ffn, x, fw, eps=m["eps"], quant=quant)
        xs[j] = _run(_close, x, y, gain, eps=m["eps"])
    return xs


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits_at(x, positions, head, *, eps, quant):
    """The final norm and the head at chosen positions of x: [S, T, d] ->
    [S, P, vocab]."""
    with jax.default_matmul_precision("highest"):
        at = _rms(jnp.take_along_axis(x, positions[:, :, None], axis=1), eps)
        return at @ _lower(head.astype(jnp.float32), quant)


@partial(jax.jit, static_argnames=("mult", "quant"))
def _embed_rows(table, tokens, *, mult, quant):
    rows = table[tokens].astype(jnp.float32)  # [S, T, d]
    if quant is not None:
        # one scale a row of the table
        flat = rows.reshape(-1, rows.shape[-1]).T
        rows = _lower(flat, quant).T.reshape(rows.shape)
    return rows * mult


def _warm(w: Weights, rows: int, T: int, quant) -> None:
    """Every program a pass over ``rows`` x ``T`` tokens will run, compiled
    AHEAD from shapes alone on threads of their own: the draws of every
    weight shape, both kinds of attention, the dense FFN, the router, the
    shared expert, an expert, the closing norm, the embedding and the
    head. Nothing is allocated and nothing is computed; the pass finds
    them in ``_PROGRAMS``."""
    from concurrent.futures import ThreadPoolExecutor

    m = w.m
    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((rows, T, m["d"]), f32)
    dense_at = next((i for i, moe in enumerate(m["moe"]) if not moe), None)
    moe_at = next((i for i, moe in enumerate(m["moe"]) if moe), None)
    shapes = jax.eval_shape(lambda: (
        w.attention(0), w.post_mlp_gain(0), w.embed(), w.head(),
        None if dense_at is None else w.dense(dense_at),
        None if moe_at is None else w.experts(moe_at)))
    attn, gain, table, head, dense, ew = shapes
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    scale = jax.ShapeDtypeStruct((), f32)
    jobs = [
        (_draw, (key, scale), dict(shape=tuple(a.shape), dtype=a.dtype.name))
        for a in {(a.shape, a.dtype): a
                  for a in jax.tree.leaves(shapes)}.values()
    ] + [
        (_attention, (x, attn), dict(
            _attention_statics(m, i), quant=quant))
        for i in {win: i for i, win in enumerate(m["window"])}.values()
    ] + [
        (_close, (x, x, gain), dict(eps=m["eps"])),
        (_embed_rows, (table, jax.ShapeDtypeStruct((rows, T), jnp.int32)),
         dict(mult=m["embed_mult"], quant=quant)),
        (_logits_at, (x, jax.ShapeDtypeStruct((rows, 16), jnp.int32), head),
         dict(eps=m["eps"], quant=quant)),
    ]
    if dense is not None:
        jobs.append((_dense_ffn, (x, dense), dict(eps=m["eps"], quant=quant)))
    if ew is not None:
        jobs += [
            (_route, (x, ew["router"], ew["score_bias"],
                      jax.ShapeDtypeStruct((), jnp.int32)), dict(
                topk=m["topk"], held=m["held"], scale=m["scale"],
                route_norm=m["route_norm"], eps=m["eps"], quant=quant)),
            (_expert, (x, x, jax.ShapeDtypeStruct((rows, T, m["held"]), f32),
                       ew["e_gate"], ew["e_up"], ew["e_down"],
                       jax.ShapeDtypeStruct((), jnp.int32)),
             dict(quant=quant)),
        ]
        if m["n_shared"]:
            jobs.append(
                (_shared, (x, {k: ew[k] for k in SHARED}), dict(quant=quant)))
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
    # rows are cut on the host (a device slice is a program of its own for
    # every offset), a few sequences at a time, and never joined: the
    # hidden states of a decode slot's worth of rows are the largest thing
    # the reference holds beside the model
    tokens = np.asarray(tokens, np.int32)
    at = range(0, tokens.shape[0], ROWS_AT_ONCE)
    _warm(w, min(ROWS_AT_ONCE, tokens.shape[0]), tokens.shape[1], quant)
    table = w.embed()
    xs = [_run(_embed_rows, table, tokens[a: a + ROWS_AT_ONCE],
               mult=m["embed_mult"], quant=quant) for a in at]
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
