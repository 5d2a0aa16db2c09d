"""Plain reference of a decoder that interleaves linear-attention layers
(Kimi Delta Attention, arXiv:2510.26692, full-rank projections and the
bounded decay) with latent-attention (MLA) layers gated by head, over a
sparse expert MLP with group-limited routing, a shared expert and a clamp a
layer (Ling-3.0-flash, the language model of Ling-3.0-flash-VL): the layer
equations in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")``; the recurrence as a ``lax.scan`` A
TOKEN with no chunkwise form, attention over materialised keys and values
with no absorbed form, no cache, no state pool, no kernel, no sorting or
grouping of tokens, no call into ``dynamo_tpu``. Read from the public
``config.json`` keys alone (``layer_group_size``, ``first_k_dense_replace``,
``kv_lora_rank``, ``kda_lower_bound``, ``n_group``,
``expert_swiglu_limit_list``...), never from the configuration's
``model_spec``.

    x = E[tokens]; u = rms(x) the normed input of a sub-layer, eps
    rms_norm_eps; pre-norm residuals around mixer and MLP; a layer's
    PUBLISHED index p (layers_kept) decides its kind.
    KDA layer ((p + 1) % layer_group_size != 0), a head h of
    num_attention_heads, d_k = d_v = head_dim:
        q~, k~, v~ = silu(conv(u W_q)), silu(conv(u W_k)), silu(conv(u W_v))
            conv: causal, depthwise, short_conv_kernel_size taps a channel
        q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(d_k);  k = k~ / sqrt(|k~|^2 + 1e-6)
        g_t = kda_lower_bound sigmoid(exp(A_h) (u W_f + b_dt))   a channel,
            in (kda_lower_bound, 0); W_f full rank (no_kda_lora)
        beta_t = sigmoid(u w_b,h)
        S' = diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S^T q_t                       S [d_k, d_v] float32, from zero
        out = concat_h(rms_h(o_t) * sigmoid(u W_g)) W_o     W_g full rank
    MLA layer ((p + 1) % layer_group_size == 0), q_lora_rank null:
        [q_n | q_r]_h = u W_q               H x (qk_nope + qk_rope)
        [c | k_r] = u W_kva; c = rms(c)     kv_lora_rank + qk_rope
        q_r, k_r rotated by the position at base rope_theta, half-split
            pairs (j, j + qk_rope / 2); k_r is shared by the heads
        [k_n | v]_h = c W_kvb,h             qk_nope + v_head_dim
        s_h = (q_n,h . k_n,h + q_r,h . k_r) / sqrt(qk_nope + qk_rope)
        a_h = sum softmax_causal(s_h) v_h
        out = concat_h(a_h * sigmoid(u w_g)_h) W_o          w_g: d -> H
    MLP: p < first_k_dense_replace: (silu(u W_g) * (u W_u)) W_d; else
        s = sigmoid(u W_r) over all num_experts; c = s + b; a group's score
        the sum of its two largest c; the topk_group best of n_group
        groups; c of the other groups put to 0; the num_experts_per_tok
        largest c chosen; w_e = routed_scaling_factor s_e / sum of the
        chosen s (norm_topk_prob);
        x = x + sum over chosen e of w_e FFN_e(u; L_p) + FFN_shared(u; L'_p),
        FFN(u; L) = (silu(min(u W_g, L)) * clip(u W_u, -L, L)) W_d,
        L_p = expert_swiglu_limit_list[p], L'_p =
        share_expert_swiglu_limit_list[p], 0 = no clamp
    logits = rms(x) W_head

Every held expert's FFN is computed for every token and weighted (zero
where not chosen), one expert after the other. Departures from the
published model, all stated in the configuration's file: random weights;
the depth (``layers_kept``); one chip's share of an expert-parallel
deployment (``experts``: the router scores all ``published``, the ``held``
from ``first`` are here, the chosen that are absent add nothing, the shared
expert is whole); ``vocab_size`` rows of the embedding and columns of the
head; the vision tower and the multi-token-prediction layers are left out.

It takes nothing the program has made. The weights are drawn here from the
seed by this file's own copy of the recipe the engine is documented to use
(``assumed`` in the configuration's file), a layer at a time, an expert at
a time in the arithmetic, so the reference fits beside the bf16 model.

``quant`` computes the same pass with every weight matrix (the router's
too; not the taps, ``A_h`` and ``b_dt``) rounded to a lower precision
(``"fp8"``: e4m3 with one scale an output channel; ``"int8"``: symmetric,
one scale an output channel): the CONTROL of the output check.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_WARMED: set = set()  # the (rows, tokens, control) a process has compiled for
ROWS_AT_ONCE = 2  # sequences a layer call: the reference runs beside the model,
# and the chip's compiler takes half as long over a float32 ``highest`` product of
# 1,280 rows as over one of 2,560 (PERF.md section 6, PR 41)
KDA = ("w_q", "w_k", "w_v", "w_o", "w_f", "w_g", "w_beta")
MLA = ("w_q", "w_kva", "w_kvb", "w_o", "w_gate_head")
DENSE = ("m_gate", "m_up", "m_down")
EXPERTS = ("router", "score_bias", "e_gate", "e_up", "e_down",
           "s_gate", "s_up", "s_down")


def _dims(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    kept = config.get("layers_kept") or list(range(layers))
    ex = config.get("experts") or {
        "published": config["num_experts"], "held": config["num_experts"],
        "first": 0,
    }
    if config.get("q_lora_rank"):
        raise SystemExit("linear_latent_moe: queries have no low rank")
    if config.get("use_kda_lora") or not config.get("no_kda_lora"):
        raise SystemExit("linear_latent_moe: KDA's projections are full rank")
    if not config.get("kda_safe_gate"):
        raise SystemExit("linear_latent_moe: the decay is the bounded one")
    if config.get("gated_attention_proj_granularity_type") != "head_wise":
        raise SystemExit("linear_latent_moe: the latent layer gates by head")
    if config.get("score_function") != "sigmoid":
        raise SystemExit("linear_latent_moe: the router is a sigmoid")
    group = config["layer_group_size"]
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "hd": config["head_dim"], "taps": config["short_conv_kernel_size"],
        "bound": float(config["kda_lower_bound"]),
        "latent": [(p + 1) % group == 0 for p in kept],
        "dense": [p < config["first_k_dense_replace"] for p in kept],
        "clamp": [float(config["expert_swiglu_limit_list"][p]) for p in kept],
        "shared_clamp": [
            float(config["share_expert_swiglu_limit_list"][p]) for p in kept],
        "dc": config["kv_lora_rank"], "dn": config["qk_nope_head_dim"],
        "dr": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
        "theta": float(config["rope_theta"]),
        "ffn": config["intermediate_size"],
        "f": config["moe_intermediate_size"],
        "fs": config["moe_shared_expert_intermediate_size"],
        "experts": int(ex["published"]), "held": int(ex["held"]),
        "first": int(ex["first"]), "topk": config["num_experts_per_tok"],
        "groups": config["n_group"], "topk_group": config["topk_group"],
        "scaling": float(config["routed_scaling_factor"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "eps": float(config["rms_norm_eps"]),
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
    it needs and the next going on from there: embedding, head, then a
    layer four keys for its mixer (KDA: ``W_q, W_k, W_v, W_o``; MLA:
    ``W_q, W_kva, W_kvb, W_o``, ``W_kvb`` one ``[kv_lora_rank, H x
    (qk_nope + v)]`` matrix whose columns a head are its keys' then its
    values') and then its MLP (dense: gate, up, down; experts: one key
    split in four: router, gate, up, down, each projection's held experts
    one ``[held, in, out]`` array, the correction bias ``N(0, 0.1^2)``
    float32 on that key folded with 1; and one key split in three for the
    shared expert's gate, up, down). What the newer layers add is drawn on
    the root folded with ``2000 + layer``, split in 9: an MLA layer's gate
    ``[d, H]`` on the first; a KDA layer's taps for q, k, v on the first
    three (``N(0, 1 / taps)``), ``W_f`` on the fourth, the sixth split in
    two for ``A_h`` (``exp(A_h)`` uniform in (0.5, 2)) and the decay a
    channel at rest (``-ln alpha`` log-uniform between ``1e-4`` and ``-ln
    0.9``, so alpha spans (0.9, 0.9999) before the input's own term moves
    it; ``b_dt = logit(-ln alpha / -kda_lower_bound) / exp(A_h)``), ``W_g``
    on the seventh, ``w_b`` on the ninth. ``N(0, 1 / fan_in)``, embedding
    and router ``N(0, 0.02^2)``, norm gains 1, no bias but ``b_dt``;
    everything rounded to the served dtype but the router's bias, ``A_h``
    and ``b_dt``."""

    def __init__(self, config: dict, seed: int):
        self.m = _dims(config)
        self.dtype = jnp.dtype(config.get("torch_dtype", "bfloat16"))
        self.layers = len(self.m["latent"])
        self._root = jax.random.PRNGKey(seed)
        keys = iter(jax.random.split(self._root, 4 + self.layers * 8))
        self._embed_key, self._head_key = next(keys), next(keys)
        self._layer_keys = [
            [next(keys) for _ in range(4 + (3 if dense else 2))]
            for dense in self.m["dense"]
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

    def layer(self, i: int, part: str | None = None) -> dict:
        """Layer ``i``'s weights; ``part`` = "mixer" or "mlp" draws that
        half alone (``_warm``)."""
        m = self.m
        k1, k2, k3, k4, *mlp = self._layer_keys[i]
        extra = jax.random.split(jax.random.fold_in(self._root, 2000 + i), 9)
        d, H = m["d"], m["heads"]
        if part == "mlp":
            w = {}
        elif m["latent"][i]:
            w = {
                "w_q": self._matrix(k1, (d, H * (m["dn"] + m["dr"]))),
                "w_kva": self._matrix(k2, (d, m["dc"] + m["dr"])),
                "w_kvb": self._matrix(k3, (m["dc"], H * (m["dn"] + m["dv"]))),
                "w_o": self._matrix(k4, (H * m["dv"], d)),
                "w_gate_head": self._matrix(extra[0], (d, H)),
            }
        else:
            wide = H * m["hd"]
            ka, kt = jax.random.split(extra[5])
            a = jax.random.uniform(ka, (H,), jnp.float32, 0.5, 2.0)
            rest = jnp.exp(jax.random.uniform(
                kt, (H, m["hd"]), jnp.float32,
                jnp.log(1e-4), jnp.log(-jnp.log(0.9)))) / -m["bound"]
            w = {
                "w_q": self._matrix(k1, (d, wide)),
                "w_k": self._matrix(k2, (d, wide)),
                "w_v": self._matrix(k3, (d, wide)),
                "w_o": self._matrix(k4, (wide, d)),
                "taps": [self._matrix(extra[j], (m["taps"], wide))
                         for j in range(3)],
                "w_f": self._matrix(extra[3], (d, wide)),
                "a_log": jnp.log(a),
                "dt_bias": (jnp.log(rest) - jnp.log1p(-rest)) / a[:, None],
                "w_g": self._matrix(extra[6], (d, wide)),
                "w_beta": self._matrix(extra[8], (d, H)),
            }
        if part == "mixer":
            return w
        if m["dense"][i]:
            w["m_gate"] = self._matrix(mlp[0], (d, m["ffn"]))
            w["m_up"] = self._matrix(mlp[1], (d, m["ffn"]))
            w["m_down"] = self._matrix(mlp[2], (m["ffn"], d))
            return w
        r1, r2, r3, r4 = jax.random.split(mlp[0], 4)
        held, f = m["held"], m["f"]
        w["router"] = self._matrix(r1, (d, m["experts"]), 0.02)
        w["e_gate"] = self._matrix(r2, (held, d, f))
        w["e_up"] = self._matrix(r3, (held, d, f))
        w["e_down"] = self._matrix(r4, (held, f, d))
        w["score_bias"] = _draw(
            jax.random.fold_in(mlp[0], 1), 0.1,
            shape=(m["experts"],), dtype=jnp.float32,
        )
        s1, s2, s3 = jax.random.split(mlp[1], 3)
        w["s_gate"] = self._matrix(s1, (d, m["fs"]))
        w["s_up"] = self._matrix(s2, (d, m["fs"]))
        w["s_down"] = self._matrix(s3, (m["fs"], d))
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


def _rotary(x, positions, theta):
    """x: [S, T, ..., D] rotated by its position (axis 1) over all D dims,
    pairs (j, j + D / 2), frequency theta^(-2j / D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq  # [T, half]
    angle = angle.reshape(
        (1, angle.shape[0]) + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


@partial(jax.jit, static_argnames=("heads", "hd", "bound", "eps", "quant"))
def _kda(x, lw, taps, a_log, dt_bias, *, heads, hd, bound, eps, quant):
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
        f = (u @ w["w_f"]).reshape(S, T, heads, hd) + dt_bias
        g = bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * f)
        beta = jax.nn.sigmoid(u @ w["w_beta"])  # [S, T, heads]

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
        return x + (o * jax.nn.sigmoid(u @ w["w_g"])) @ w["w_o"]


@partial(jax.jit, static_argnames=("heads", "dc", "dn", "dr", "dv", "theta",
                                   "eps", "quant"))
def _mla(x, lw, *, heads, dc, dn, dr, dv, theta, eps, quant):
    """A latent layer over whole sequences, keys and values materialised a
    head, the output gated by head; x: [S, T, d] float32."""
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(v.astype(jnp.float32), quant) for k, v in lw.items()}
        S, T, _ = x.shape
        pos = jnp.arange(T)
        u = _rms(x, eps)
        q = (u @ w["w_q"]).reshape(S, T, heads, dn + dr)
        q_n, q_r = q[..., :dn], _rotary(q[..., dn:], pos, theta)
        kva = u @ w["w_kva"]
        c, k_r = _rms(kva[..., :dc], eps), _rotary(kva[..., dc:], pos, theta)
        kv = (c @ w["w_kvb"]).reshape(S, T, heads, dn + dv)
        k_n, v = kv[..., :dn], kv[..., dn:]
        scores = (
            jnp.einsum("sthd,suhd->shtu", q_n, k_n)
            + jnp.einsum("sthd,sud->shtu", q_r, k_r)
        ) / jnp.sqrt(jnp.float32(dn + dr))
        seen = pos[:, None] >= pos[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        a = jnp.einsum("shtu,suhd->sthd", jax.nn.softmax(scores, axis=-1), v)
        a = a * jax.nn.sigmoid(u @ w["w_gate_head"])[..., None]
        return x + a.reshape(S, T, heads * dv) @ w["w_o"]


def _ffn(h, gate, up, down, clamp):
    """``clamp`` may be traced (one program for layers that differ in it
    alone): 0 = none."""
    g, u = h @ gate, h @ up
    on = clamp > 0
    g = jnp.where(on, jnp.minimum(g, clamp), g)
    u = jnp.where(on, jnp.clip(u, -clamp, clamp), u)
    return (jax.nn.silu(g) * u) @ down


@partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_mlp(x, lw, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        w = {k: _lower(v.astype(jnp.float32), quant) for k, v in lw.items()}
        return x + _ffn(_rms(x, eps), w["m_gate"], w["m_up"], w["m_down"], 0.0)


@partial(jax.jit, static_argnames=(
    "topk", "groups", "topk_group", "first", "held", "scaling", "norm_topk",
    "shared", "eps", "quant"))
def _experts(x, lw, *, topk, groups, topk_group, first, held, scaling,
             norm_topk, clamp, shared_clamp, eps, quant, shared=True):
    """x plus the expert layer's output over whole sequences: the router
    over ALL the routed experts under its group limit, the HELD experts'
    FFNs one at a time, each over every token and weighted (zero where it
    is not among the token's chosen), and the shared expert (left out
    with ``shared`` false: a share that counts it elsewhere). One program:
    a loop over the held experts, not a program an expert."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, eps)
        s = jax.nn.sigmoid(h @ _lower(lw["router"].astype(jnp.float32), quant))
        c = s + lw["score_bias"]
        if groups > 1:
            by_group = c.reshape(*c.shape[:-1], groups, -1)
            score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
            _, best = jax.lax.top_k(score, topk_group)  # [S, T, topk_group]
            allowed = jnp.any(
                best[..., None] == jnp.arange(groups), axis=-2)  # [S, T, G]
            c = jnp.where(allowed[..., None], by_group, 0.0).reshape(c.shape)
        _, chosen = jax.lax.top_k(c, topk)  # [S, T, topk]
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
            return x + weight * _ffn(h, gate, up, down, clamp)

        if held:
            x = jax.lax.fori_loop(0, held, expert, x)
        if shared:
            w = {k: _lower(lw[k].astype(jnp.float32), quant)
                 for k in ("s_gate", "s_up", "s_down")}
            x = x + _ffn(h, w["s_gate"], w["s_up"], w["s_down"], shared_clamp)
        return x


def _layer(m: dict, i: int, x, lw, quant, part=None):
    """x through layer ``i`` (``part``: its mixer or its MLP alone)."""
    if part == "mlp":
        pass
    elif m["latent"][i]:
        x = _mla(
            x, {k: lw[k] for k in MLA}, heads=m["heads"], dc=m["dc"],
            dn=m["dn"], dr=m["dr"], dv=m["dv"], theta=m["theta"],
            eps=m["eps"], quant=quant,
        )
    else:
        x = _kda(
            x, {k: lw[k] for k in KDA}, lw["taps"], lw["a_log"],
            lw["dt_bias"], heads=m["heads"], hd=m["hd"], bound=m["bound"],
            eps=m["eps"], quant=quant,
        )
    if part == "mixer":
        return x
    if m["dense"][i]:
        return _dense_mlp(
            x, {k: lw[k] for k in DENSE}, eps=m["eps"], quant=quant)
    return _experts(
        x, {k: lw[k] for k in EXPERTS}, topk=m["topk"], groups=m["groups"],
        topk_group=m["topk_group"], first=m["first"], held=m["held"],
        scaling=m["scaling"], norm_topk=m["norm_topk"], clamp=m["clamp"][i],
        shared_clamp=m["shared_clamp"][i], eps=m["eps"], quant=quant,
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


def _warm(w: "Weights", rows: int, T: int, quant) -> None:
    """Every program a pass will run, compiled once AHEAD on threads of
    their own: a kind of mixer, a kind of MLP, the embedding and the head
    each draw their weights (a compile a shape) and run once on zeros, so
    that the pass itself finds them compiled. The chip's compiler takes
    ~50 s over them one after the other, a process's first pass, and the
    threads ~20 s (PERF.md section 6, PR 41); what is computed is the
    pass's own, later, as if this had not run."""
    from concurrent.futures import ThreadPoolExecutor

    m = w.m
    x0 = jnp.zeros((rows, T, m["d"]), jnp.float32)

    def first(flags, want):
        return next((i for i, f in enumerate(flags) if f == want), None)

    def half(i, part):
        _layer(m, i, x0, w.layer(i, part), quant, part).block_until_ready()

    def ends():
        _embed_rows(w.embed(), np.zeros((rows, T), np.int32), quant=quant)
        _logits_at(x0, np.zeros((rows, 16), np.int32), w.head(),
                   eps=m["eps"], quant=quant).block_until_ready()

    jobs = [ends] + [
        (lambda i=i, part=part: half(i, part))
        for part, flags in (("mixer", m["latent"]), ("mlp", m["dense"]))
        for i in (first(flags, False), first(flags, True)) if i is not None
    ]
    with ThreadPoolExecutor(len(jobs)) as pool:
        for job in [pool.submit(j) for j in jobs]:
            job.result()


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
    del table

    def logits_at(where):
        # positions padded to a multiple of 16 (with position 0, cut off
        # again): the check asks for 1, 7 and 10 a row, one program; the
        # head a block of rows at a time
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
