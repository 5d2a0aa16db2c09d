"""Llama-family transformer in pure JAX with paged KV cache + TP shardings.

Functional core: ``init_params`` builds the weight pytree (randomly - this
environment has no model downloads; loading real safetensors goes through
``load_params`` when files are present), ``prefill_forward`` and
``decode_forward`` are the two jitted entry points. Tensor parallelism is
megatron-style, expressed as NamedShardings on the weights (attention heads
and MLP hidden column-sharded, output projections row-sharded) so XLA's SPMD
partitioner inserts the collectives; activations get light
``with_sharding_constraint`` guidance.

Page 0 of the KV cache is the trash page: padded token positions scatter
there, so static-shape prefill never corrupts live pages.
"""

from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.models.regions import (
    SCOPE_ATTN_CROSS,
    SCOPE_ATTN_DIFF,
    SCOPE_ATTN_FULL,
    SCOPE_ATTN_WINDOW,
    SCOPE_BURST,
    SCOPE_CONV_MIX,
    SCOPE_CONV_PROJ,
    SCOPE_EMBED,
    SCOPE_GMU,
    SCOPE_HEAD,
    SCOPE_INDEX,
    SCOPE_KDA_CONV,
    SCOPE_KDA_GATES,
    SCOPE_KDA_PROJ,
    SCOPE_KV,
    SCOPE_MLP,
    SCOPE_MOE_COUNT,
    SCOPE_MOE_SHARED,
    SCOPE_NORM,
    SCOPE_NORM_OUT,
    SCOPE_OUT,
    SCOPE_QKV,
    SCOPE_RESIDUAL,
    SCOPE_SAMPLER,
    SCOPE_SSM_CONV,
    SCOPE_SSM_GATES,
    SCOPE_SSM_PROJ,
    SCOPE_STATE_ROWS,
)
from dynamo_tpu.ops.attention import (
    causal_attention,
    decode_update_attention,
    kda_chunk_prefill,
    kda_decode_step,
    page_tiles,
    paged_prefill_attention,
    scan_chunk_prefill,
    scan_decode_step,
    ssd_chunk_prefill,
    ssd_decode_step,
)
from dynamo_tpu.ops.quant import (
    QuantPool,
    init_quant_pool,
    is_quant,
    pack_pages,
    quant_page_tiles,
    unpack_pages,
)

TRASH_PAGE = 0  # reserved page index for padded-position scatters

Params = dict[str, Any]


# ---------------------------------------------------------------- init


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, scale, *, shape, dtype):
    """N(0, scale^2) of ``shape`` rounded to ``dtype``: one program a
    shape (drawn eagerly, op by op, every shape compiled three). A stack
    of matrices is drawn as one matrix of their rows and reshaped: the
    same bits (the generator counts elements, not axes), and the chip's
    compiler takes 3 s over ``[64 x 2560, 768]`` where it takes 9 over
    ``[64, 2560, 768]`` (PERF.md section 6, PR 41)."""
    flat = shape if len(shape) < 3 else (math.prod(shape[:-1]), shape[-1])
    draw = jax.random.normal(key, flat, jnp.float32).reshape(shape)
    return (draw * scale).astype(dtype)


def init_params(spec: ModelSpec, key: jax.Array) -> Params:
    """Random init (serving-scale weights come from load_params)."""
    dtype = jnp.dtype(spec.dtype)
    d, hd, vd = spec.hidden_size, spec.head_dim, spec.v_dim
    nh = spec.num_heads
    keys = iter(jax.random.split(key, 4 + spec.num_layers * 8))

    def dense(k, shape, scale=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[0])
        return _draw(k, scale, shape=shape, dtype=dtype)

    def table(k, shape, axis, scale=None):
        if spec.vocab_draw_blocks > 1:
            return _draw_blocks(
                k, shape[0] ** -0.5 if scale is None else scale, shape, axis,
                dtype, spec.vocab_draw_blocks)
        return dense(k, shape, scale=scale)

    params: Params = {
        "embed": table(next(keys), (spec.vocab_size, d), 0, scale=0.02),
        "final_norm": jnp.ones((d,), dtype),
        "layers": [],
    }
    if not spec.tie_embeddings:
        params["lm_head"] = table(next(keys), (d, spec.vocab_size), 1)
    if spec.norm == "layer":
        k_g, k_b = jax.random.split(jax.random.fold_in(key, 5000))
        params["final_norm"] = 1 + dense(k_g, (d,), scale=0.1)
        params["final_norm_bias"] = dense(k_b, (d,), scale=0.1)
    for li in range(spec.num_layers):
        kd = spec.kind(li)
        nkv = kd.num_kv_heads
        # what a model's newer layers add is drawn on keys of their own
        # (the matrices' keys stay where they were for every older model)
        extra = jax.random.split(jax.random.fold_in(key, 2000 + li), 9)
        if kd.latent:
            from dynamo_tpu.models import mla

            # the layer's four keys as its siblings use theirs: queries,
            # the latent's down- and up-projection, the output
            k_q, k_kva, k_kvb, k_o = (next(keys) for _ in range(4))
            layer = {
                "attn_norm": jnp.ones((d,), dtype),
                "mlp_norm": jnp.ones((d,), dtype),
                **mla.init_latent_mixer(
                    spec, dense, k_q, k_q, k_kva, k_kvb, k_o,
                    k_gate=extra[0] if kd.head_gate else None,
                ),
            }
        elif kd.mixer == "conv":
            layer = _init_conv_layer(spec, dense, keys, extra)
        elif kd.mixer in ("scan", "gmu"):
            layer = _init_scan_layer(spec, kd, dense, _own_keys(key, li))
        elif kd.differential:
            layer = _init_diff_layer(spec, kd, dense, _own_keys(key, li))
        elif not kd.paged:
            layer = _init_kda_layer(spec, kd, dense, keys, extra)
        else:
            layer = {
                "attn_norm": jnp.ones((d,), dtype),
                "wq": dense(next(keys), (d, nh * hd)),
                "wk": dense(next(keys), (d, nkv * hd)),
                "wv": dense(next(keys), (d, nkv * vd)),
                "wo": dense(next(keys), (nh * vd, d)),
                "mlp_norm": jnp.ones((d,), dtype),
            }
            if spec.attn_gate:
                layer["w_gate_attn"] = dense(extra[0], (d, nh * vd))
            if spec.qk_norm:
                # gains drawn about 1, N(1, 0.1^2): at 1 the two vectors
                # would drop out of every comparison on random weights
                layer["q_norm"] = 1 + dense(extra[7], (hd,), scale=0.1)
                layer["k_norm"] = 1 + dense(extra[8], (hd,), scale=0.1)
            if kd.mixer == "ssd":
                layer.update(_init_ssd_mixer(spec, dense, extra))
        # zero biases on a layer's attention projections, unless its
        # kind has none (a scan, a GMU) or drew its own (a differential)
        if spec.attn_bias and "wq" in layer and "bo" not in layer:
            layer.update(
                bq=jnp.zeros((nh * hd,), dtype),
                bk=jnp.zeros((nkv * hd,), dtype),
                bv=jnp.zeros((nkv * vd,), dtype),
                bo=jnp.zeros((d,), dtype),
            )
        if spec.norm == "layer":
            # LayerNorm: gains 1 + N(0, 0.1^2) and biases N(0, 0.1^2),
            # drawn away from 1 and 0 (a term left out of a program then
            # shows on random weights), whatever the layer's kind
            sk = _own_keys(key, li)
            layer.update(
                attn_norm=1 + dense(sk[0], (d,), scale=0.1),
                attn_norm_bias=dense(sk[1], (d,), scale=0.1),
                mlp_norm=1 + dense(sk[2], (d,), scale=0.1),
                mlp_norm_bias=dense(sk[3], (d,), scale=0.1),
            )
        if spec.sandwich_norm:
            # the gains of the two norms on the way OUT, drawn about 1 on
            # keys of their own (as ``q_norm``'s: at 1 a gain left out of
            # a program would show in no comparison on random weights)
            k_pa, k_pm = jax.random.split(
                jax.random.fold_in(key, 3000 + li))
            layer["post_attn_norm"] = 1 + dense(k_pa, (d,), scale=0.1)
            layer["post_mlp_norm"] = 1 + dense(k_pm, (d,), scale=0.1)
        if kd.sinks:
            # drawn non-zero, on a key of their own (the matrices' keys
            # stay where they were): a zero sink is exp(0) in every
            # denominator, but one value for all heads
            layer["sinks"] = _draw(
                jax.random.fold_in(key, 1000 + li), 1.0, shape=(nh,),
                dtype=dtype,
            )
        if spec.is_moe_layer(li):
            from dynamo_tpu.models import moe

            layer["moe"] = moe.init_moe_layer(spec, next(keys))
            if spec.n_shared_experts:
                f = spec.moe_intermediate_size * spec.n_shared_experts
                kg, ku, kdn = jax.random.split(next(keys), 3)
                layer["shared"] = {
                    "w_gate": dense(kg, (d, f)),
                    "w_up": dense(ku, (d, f)),
                    "w_down": dense(kdn, (f, d)),
                }
        else:
            layer.update(
                w_gate=dense(next(keys), (d, spec.intermediate_size)),
                w_up=dense(next(keys), (d, spec.intermediate_size)),
                w_down=dense(next(keys), (spec.intermediate_size, d)),
            )
        params["layers"].append(layer)
    return params


def _draw_blocks(key, scale, shape, axis: int, dtype, blocks: int):
    """``_draw`` of a vocabulary-sized table in ``blocks`` blocks along
    ``axis`` (``ModelSpec.vocab_draw_blocks``), block ``b`` on
    ``fold_in(key, b)``, one after the other into place: the float32
    normals of 261,120 x 5,120 values are 5.35 GB, a third of a chip, and
    a block's are an eighth of that."""
    n = shape[axis] // blocks
    if n * blocks != shape[axis]:
        raise ValueError(f"a table of {shape} does not cut in {blocks}")
    block = tuple(n if i == axis else v for i, v in enumerate(shape))

    def put(b, table):
        part = _draw(
            jax.random.fold_in(key, b), scale, shape=block, dtype=dtype)
        return jax.lax.dynamic_update_slice_in_dim(table, part, b * n, axis)

    return jax.lax.fori_loop(0, blocks, put, jnp.zeros(shape, dtype))


def _init_ssd_mixer(spec: ModelSpec, dense, extra) -> Params:
    """An SSD (Mamba-2) mixer's weights beside the layer's attention, on
    ``extra``: the input projection ``[d, z | x | B | C | dt]``, the taps
    ``[taps, channels]`` N(0, 1 / taps) and their bias N(0, 0.1^2), ``A =
    exp(a_log)`` uniform in (1, 16) a head, ``dt_bias`` the inverse
    softplus of a step log-uniform in (1e-3, 1e-1) (so a token's decay
    ``exp(-dt A)`` spans (0.2, 0.999) before the input's own term moves
    it), ``D`` uniform in (0.5, 1.5), the gated norm's gain 1, the output
    projection. ``a_log``, ``dt_bias`` and ``D`` stay float32."""
    dtype = jnp.dtype(spec.dtype)
    f32 = jnp.float32
    d, H = spec.hidden_size, spec.ssm_heads
    d_ssm, ch = H * spec.ssm_head_dim, spec.ssm_conv_dim
    step = jnp.exp(jax.random.uniform(
        extra[4], (H,), f32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "ssm_in": dense(extra[0], (d, d_ssm + ch + H)),
        "ssm_conv": dense(extra[1], (spec.ssm_conv, ch)),
        "ssm_conv_bias": dense(extra[2], (ch,), scale=0.1),
        "ssm_a_log": jnp.log(jax.random.uniform(extra[3], (H,), f32, 1.0, 16.0)),
        "ssm_dt_bias": jnp.log(jnp.expm1(step)),
        "ssm_d": jax.random.uniform(extra[5], (H,), f32, 0.5, 1.5),
        "ssm_norm": jnp.ones((d_ssm,), dtype),
        "ssm_out": dense(extra[6], (d_ssm, d)),
    }


def _own_keys(key, li: int):
    """Layer ``li``'s 20 keys of its own, one fold of the root: 0-3 a
    LayerNorm model's gains and biases (``init_params``), 4-16 the mixer
    of a kind that draws none of the layers' running keys (a scan, a
    GMU, a differential kind)."""
    return jax.random.split(jax.random.fold_in(key, 4000 + li), 20)


def _init_scan_layer(spec: ModelSpec, kd, dense, sk) -> Params:
    """A selective-scan (Mamba-1) or a GMU layer's mixer weights on the
    layer's own keys ``sk`` (its MLP is drawn by the caller).

    - a scan layer, 4-10: ``scan_in [d, x | z]``, the taps ``[taps, C]``
      N(0, 1 / taps) and their bias N(0, 0.1^2), ``scan_x [C, dt | B |
      C]``, ``scan_dt [R, C]``, the time step log-uniform in (1e-3, 1e-1)
      a channel with ``scan_dt_bias`` its inverse softplus, ``scan_out``;
      ``scan_a_log [N, C]`` is ``log(1..N)`` a channel and ``scan_d`` 1
      (Mamba's own), both float32 beside ``scan_dt_bias``;
    - a GMU layer, 4-5: ``gmu_in [d, C]``, ``gmu_out [C, d]``."""
    dtype, f32 = jnp.dtype(spec.dtype), jnp.float32
    d = spec.hidden_size
    C, N, R = spec.scan_inner, spec.scan_state, spec.scan_dt_rank
    layer = {
        "attn_norm": jnp.ones((d,), dtype),
        "mlp_norm": jnp.ones((d,), dtype),
    }
    if kd.mixer == "gmu":
        layer.update(gmu_in=dense(sk[4], (d, C)), gmu_out=dense(sk[5], (C, d)))
        return layer
    step = jnp.exp(jax.random.uniform(
        sk[9], (C,), f32, jnp.log(1e-3), jnp.log(1e-1)))
    layer.update(
        scan_in=dense(sk[4], (d, 2 * C)),
        scan_conv=dense(sk[5], (spec.scan_conv, C)),
        scan_conv_bias=dense(sk[6], (C,), scale=0.1),
        scan_x=dense(sk[7], (C, R + 2 * N)),
        scan_dt=dense(sk[8], (R, C)),
        scan_dt_bias=jnp.log(jnp.expm1(step)),
        scan_a_log=jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=f32))[:, None], (N, C)),
        scan_d=jnp.ones((C,), f32),
        scan_out=dense(sk[10], (C, d)),
    )
    return layer


def _init_diff_layer(spec: ModelSpec, kd, dense, sk) -> Params:
    """A differential-attention layer's mixer weights on the layer's own
    keys ``sk`` (its MLP is drawn by the caller), 4-16: ``wq, wk, wv,
    wo``, the four lambda vectors ``[head_dim]`` N(0, 0.1^2) float32, the
    pair norm's gain ``[2 v_dim]`` ``1 + N(0, 0.1^2)``, and under
    ``attn_bias`` the projections' biases N(0, 0.1^2), not zero, so that
    they are in a comparison on random weights; a layer that reads
    another's pages (``kd.reads``) draws the queries' and the output's
    alone, on the same keys."""
    dtype, f32 = jnp.dtype(spec.dtype), jnp.float32
    d, hd, vd, nh = spec.hidden_size, spec.head_dim, spec.v_dim, spec.num_heads
    nkv = kd.num_kv_heads
    layer = {
        "attn_norm": jnp.ones((d,), dtype),
        "mlp_norm": jnp.ones((d,), dtype),
        "wq": dense(sk[4], (d, nh * hd)),
        "wo": dense(sk[7], (nh * vd, d)),
        "subln": 1 + dense(sk[16], (2 * vd,), scale=0.1),
        **{
            name: _draw(sk[12 + i], 0.1, shape=(hd,), dtype=f32)
            for i, name in enumerate(
                ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        },
    }
    if not kd.reads:
        layer.update(
            wk=dense(sk[5], (d, nkv * hd)), wv=dense(sk[6], (d, nkv * vd)))
    if spec.attn_bias:
        layer.update(
            bq=dense(sk[8], (nh * hd,), scale=0.1),
            bo=dense(sk[11], (d,), scale=0.1),
        )
        if not kd.reads:
            layer.update(
                bk=dense(sk[9], (nkv * hd,), scale=0.1),
                bv=dense(sk[10], (nkv * vd,), scale=0.1),
            )
    return layer


def _init_conv_layer(spec: ModelSpec, dense, keys, extra) -> Params:
    """A gated short-convolution layer's mixer weights (its MLP is drawn
    by the caller): the input projection ``[d, B | C | x]`` and the output
    projection on the layer's own keys, the taps ``[taps, d]`` N(0, 1 /
    taps) on ``extra``; no bias."""
    dtype = jnp.dtype(spec.dtype)
    d = spec.hidden_size
    return {
        "attn_norm": jnp.ones((d,), dtype),
        "sconv_in": dense(next(keys), (d, 3 * d)),
        "sconv_out": dense(next(keys), (d, d)),
        "mlp_norm": jnp.ones((d,), dtype),
        "sconv_taps": dense(extra[0], (spec.conv_taps, d)),
    }


def _init_kda_layer(spec: ModelSpec, kd, dense, keys, extra) -> Params:
    """A KDA layer's mixer weights (its MLP is drawn by the caller): the
    four big matrices on the layer's own keys like an attention layer's,
    the rest on ``extra``. ``a_log`` and ``dt_bias`` are drawn so that the
    decay a token ``alpha = exp(-exp(a_log) softplus(dt_bias))`` spans
    (0.9, 0.9999) log-uniformly over the channels before the input's own
    term ``(x w_f_down) w_f_up`` moves it. A kind with ``full_rank`` draws
    ``w_f`` and ``w_g`` ``[d, H D]`` on the pairs' first keys; one with a
    ``gate_bound`` draws ``exp(a_log)`` uniform in (0.5, 2) and ``dt_bias``
    so that the same band of decays holds under its bounded form, ``-ln
    alpha = -gate_bound sigmoid(exp(a_log) dt_bias)``."""
    dtype = jnp.dtype(spec.dtype)
    d, H, D = spec.hidden_size, spec.kda_heads, spec.kda_head_dim
    r = D  # the decay's and the gate's pairs go through rank head_dim
    f32 = jnp.float32
    ka, kt = jax.random.split(extra[5])
    tau = jnp.exp(jax.random.uniform(
        kt, (H, D), f32, jnp.log(1e-4), jnp.log(-jnp.log(0.9))))
    if kd.gate_bound:
        a = jax.random.uniform(ka, (H,), f32, 0.5, 2.0)
        share = tau / -kd.gate_bound  # the sigmoid's value at rest
        dt_bias = (jnp.log(share) - jnp.log1p(-share)) / a[:, None]
    else:
        a = jax.random.uniform(ka, (H,), f32, 0.02, 0.1)
        dt_bias = jnp.log(jnp.expm1(tau / a[:, None]))
    if kd.full_rank:
        pairs = {"w_f": dense(extra[3], (d, H * D)),
                 "w_g": dense(extra[6], (d, H * D))}
    else:
        pairs = {
            "w_f_down": dense(extra[3], (d, r)),
            "w_f_up": dense(extra[4], (r, H * D)),
            "w_g_down": dense(extra[6], (d, r)),
            "w_g_up": dense(extra[7], (r, H * D)),
        }
    return {
        "attn_norm": jnp.ones((d,), dtype),
        "wq": dense(next(keys), (d, H * D)),
        "wk": dense(next(keys), (d, H * D)),
        "wv": dense(next(keys), (d, H * D)),
        "wo": dense(next(keys), (H * D, d)),
        "mlp_norm": jnp.ones((d,), dtype),
        # the short convolutions' taps, [taps, channels] a projection:
        # N(0, 1 / taps)
        "conv_q": dense(extra[0], (spec.kda_conv, H * D)),
        "conv_k": dense(extra[1], (spec.kda_conv, H * D)),
        "conv_v": dense(extra[2], (spec.kda_conv, H * D)),
        **pairs,
        "a_log": jnp.log(a),
        "dt_bias": dt_bias.reshape(H * D),
        "w_beta": dense(extra[8], (d, H)),
        "o_norm": jnp.ones((D,), dtype),
    }


def param_shardings(spec: ModelSpec, mesh: Mesh) -> Params:
    """Megatron TP shardings over mesh axis "tp"."""

    def ns(*axes):
        return NamedSharding(mesh, P(*axes))

    layers = []
    for li in range(spec.num_layers):
        layer = {
            "attn_norm": ns(),
            "wq": ns(None, "tp"),  # column (heads)
            "wk": ns(None, "tp"),
            "wv": ns(None, "tp"),
            "wo": ns("tp", None),  # row
            "mlp_norm": ns(),
        }
        if spec.attn_bias:
            layer.update(bq=ns("tp"), bk=ns("tp"), bv=ns("tp"), bo=ns())
        if spec.qk_norm:
            layer.update(q_norm=ns(), k_norm=ns())
        if spec.attn_gate:
            layer["w_gate_attn"] = ns(None, "tp")  # column (heads), as wq
        if spec.sandwich_norm:
            layer.update(post_attn_norm=ns(), post_mlp_norm=ns())
        if spec.kind(li).sinks:
            layer["sinks"] = ns("tp")  # per-query-head, rides the head shards
        if spec.is_moe_layer(li):
            from dynamo_tpu.models import moe

            layer["moe"] = moe.moe_layer_shardings(mesh, spec)
        else:
            layer.update(
                w_gate=ns(None, "tp"),
                w_up=ns(None, "tp"),
                w_down=ns("tp", None),
            )
        layers.append(layer)
    out = {
        "embed": ns(None, "tp"),
        "final_norm": ns(),
        "layers": layers,
    }
    if not spec.tie_embeddings:
        out["lm_head"] = ns(None, "tp")
    return out


class KindPools(NamedTuple):
    """The K (or V) side of the cache of a model with layer kinds
    (``ModelSpec.layer_kinds``): one page pool a kind, each ``[layers of
    the kind, num_pages, kv_heads of the kind, page_size, D]``, all over
    ONE page-id space (a sequence's block table serves every kind). The K
    side also carries the expert layers' device-side counters, which the
    programs add to as they run: ``counts [L, 2, n_held + 3]`` int32, by
    layer and by phase (``COUNT_PREFILL``, ``COUNT_DECODE``): assignments
    of real tokens to each held expert, their assignments in all, the
    held experts a call touched (at least one assignment: it read their
    weights), and the phase's steps (prefill programs; decode model
    steps). Empty
    (``[L, 2, 0]``) on the V side and where the model has no experts.

    A RECURRENT kind's entry in ``pools`` is not pages: on the K side its
    layers' states ``[layers of the kind, rows + 1, H, dk, dv]`` float32
    (None for a kind that keeps no state matrix, ``LayerKind.state``
    false: a short convolution's whole state is its tail), on the V side
    the tails of their short convolutions ``[layers, rows + 1, taps - 1,
    ...channels]``, a row a live sequence and a trash row last; ``rows``
    (K side) is the directory that finds a sequence's row from its block
    table (``StateRows``).

    A LATENT kind's entry (``LayerKind.latent``) is ONE pool of latent
    rows on the K side, ``[layers of the kind, num_pages, page_size, D]``
    as models/mla.py keeps its own (no head axis; ``D`` the row's
    ``kv_lora_rank + qk_rope_head_dim`` values rounded up to the lane tile
    where the kernels run compiled), over the same page ids and block
    table; its V side entry is None."""

    pools: tuple
    counts: jax.Array
    rows: Any = None


class PagesAndState(NamedTuple):
    """The entry in ``KindPools.pools`` of a kind that keeps BOTH (an SSD
    mixer in parallel with softmax attention, ``LayerKind.mixer ==
    "ssd"``): its page pool as a paged kind's and, beside it, on the K
    side its layers' states ``[layers of the kind, rows + 1, H, P, N]``
    float32, on the V side their convolution tails ``[layers, rows + 1,
    taps - 1, channels]``. One block table, one page-id space and one
    ``StateRows`` directory serve both. A kind that keeps one of the two
    keeps the bare array: the page transfer programs (``_extract_kv_pages_impl``,
    ``_insert_kv_pages_impl``) index every entry of a paged model as an
    array, and the benchmark's accepted tests read ``pools[ki].shape`` of
    a pages-only and of a state-only kind (``tests/perfbench/
    test_perfbench_mimo.py``, ``test_perfbench_solar.py``); only
    ``_entry_parts`` / ``_entry_of`` know the shapes."""

    pages: Any
    state: jax.Array


def _entry_parts(kd, entry):
    """(pages, rows) of a kind's entry on one side of the cache (or of
    the one pool of a model without kinds): None for what it keeps none
    of there. A bare array is pages where the kind is paged, else its
    rows (states on the K side, tails on the V side)."""
    if isinstance(entry, PagesAndState):
        return entry
    return (entry, None) if kd.paged else (None, entry)


def _entry_of(pages, rows):
    """The entry of a kind that keeps ``pages`` and ``rows`` on a side:
    the bare array where it keeps one of them, None where neither."""
    if pages is not None and rows is not None:
        return PagesAndState(pages, rows)
    return rows if pages is None else pages


def kind_pages(spec: ModelSpec, side, ki: int):
    """The page pool of kind ``ki`` on a cache side (None where the kind
    keeps none)."""
    return _entry_parts(spec.layer_kinds[ki], side.pools[ki])[0]


def latent_pool(spec: ModelSpec, k_pages):
    """The pool of the model's latent kind (``LayerKind.latent``) on the
    cache's K side."""
    return next(
        kind_pages(spec, k_pages, ki)
        for ki, kd in enumerate(spec.layer_kinds) if kd.latent
    )


class StateRows(NamedTuple):
    """Who owns each row of the recurrent kinds' state pools. A sequence
    is known to a program by its block table and by nothing else, so a
    row's ``owner`` is the sequence's FIRST PAGE (column 0 of its table; 0
    = nobody) and the programs find, claim and touch rows on the device:

    - a prefill at ``start_pos`` 0 takes the row that its owner holds
      already, else a free row, else the least recently used (by
      ``stamp``, the ``clock`` of a row's last use), and starts from a
      zero state;
    - a chunk at ``start_pos`` > 0 and a decode step take the row that
      matches. One that should match and does not runs on the trash row
      and is counted (``stats`` missing): its output is wrong;
    - an empty row of a pack, a table whose first page is the trash page
      (warm-up) and an inactive slot own nothing and touch nothing.

    The engine frees the rows of the pages it releases
    (``release_state_rows``), so a claim there always finds a free row;
    the take-over of the least recently used serves a caller that builds
    its own tables and releases nothing, for whom every live row is the
    latest touched. Every leaf leads with an axis of 1, as every leaf of
    the cache leads with a layer axis."""

    owner: jax.Array  # [1, rows + 1] int32
    stamp: jax.Array  # [1, rows + 1] int32
    stats: jax.Array  # [1, 3] int32: clock, claims, rows missing


STAT_CLOCK, STAT_CLAIMS, STAT_MISSING = 0, 1, 2


@jax.named_scope(SCOPE_STATE_ROWS)
def _claim_state_rows(rows: StateRows, owners, starts, live):
    """Rows for a prefill program's sequences. owners, starts: [N] int32;
    live: [N] bool (the row has tokens). Returns ``(idx [N], fresh [N],
    rows)``: each sequence's row (the trash row where it owns none),
    whether it starts from a zero state, the directory updated. One
    sequence at a time: two of a pack must not claim one row."""
    owner, stamp, stats = rows.owner[0], rows.stamp[0], rows.stats[0]
    R = owner.shape[0] - 1
    clock = stats[STAT_CLOCK] + 1
    idx, fresh = [], []
    for i in range(owners.shape[0]):
        use = live[i] & (owners[i] > 0)
        match = owner[:R] == owners[i]
        found = jnp.any(match)
        first = starts[i] == 0
        # free rows first, then the oldest
        lru = jnp.argmin(jnp.where(owner[:R] == 0, -1, stamp[:R]))
        ok = use & (found | first)
        at = jnp.where(ok, jnp.where(found, jnp.argmax(match), lru), R)
        owner = owner.at[at].set(jnp.where(ok, owners[i], owner[at]))
        stamp = stamp.at[at].set(jnp.where(ok, clock, stamp[at]))
        stats = stats.at[STAT_CLAIMS].add((use & first & ~found).astype(jnp.int32))
        stats = stats.at[STAT_MISSING].add((use & ~ok).astype(jnp.int32))
        idx.append(at)
        fresh.append(use & first)
    stats = stats.at[STAT_CLOCK].set(clock)
    return (jnp.stack(idx).astype(jnp.int32), jnp.stack(fresh),
            StateRows(owner[None], stamp[None], stats[None]))


@jax.named_scope(SCOPE_STATE_ROWS)
def _find_state_rows(rows: StateRows, owners, active):
    """Rows of a decode program's slots (owners: [B] int32, active: [B]
    bool), touched; the trash row for a slot that owns none. Returns (idx
    [B], rows)."""
    owner, stamp, stats = rows.owner[0], rows.stamp[0], rows.stats[0]
    R = owner.shape[0] - 1
    clock = stats[STAT_CLOCK] + 1
    use = active & (owners > 0)
    match = owner[None, :R] == owners[:, None]  # [B, R]
    ok = use & jnp.any(match, axis=1)
    idx = jnp.where(ok, jnp.argmax(match, axis=1), R).astype(jnp.int32)
    stamp = stamp.at[idx].set(jnp.where(ok, clock, stamp[idx]))
    stats = stats.at[STAT_CLOCK].set(clock).at[STAT_MISSING].add(
        jnp.sum(use & ~ok).astype(jnp.int32))
    return idx, StateRows(owner[None], stamp[None], stats[None])


@partial(jax.jit, donate_argnums=(0,))
def release_state_rows(k_pages: KindPools, pages: jax.Array) -> KindPools:
    """Free the state rows whose owner is among ``pages`` ([n] int32, pad
    with -1): the engine calls it with the pages it released, before the
    next prefill claims a row. A page that no row's owner is changes
    nothing, so tail pages and whole sequences go through alike."""
    rows = k_pages.rows
    gone = jnp.any(rows.owner[0][:, None] == pages[None, :], axis=1)
    return k_pages._replace(rows=rows._replace(
        owner=jnp.where(gone, 0, rows.owner[0])[None]
    ))


COUNT_PREFILL, COUNT_DECODE = 0, 1


def cache_shardings(
    mesh: Mesh, kv_dtype: str = "bf16", spec: ModelSpec | None = None,
) -> tuple[Any, Any]:
    """KV pages [L, pages, kv_heads, page_size, D]: shard kv_heads on tp.
    Quantized pools shard the scale leaf [L, pages, KH] on the same head
    axis, so device_put with the QuantPool of shardings keeps values and
    scales co-located per shard."""
    s = NamedSharding(mesh, P(None, None, "tp", None, None))
    if spec is not None and spec.layer_kinds:
        if spec.has_recurrent:
            raise ValueError("a model with recurrent layers runs on one "
                             "device: its state has no sharding yet")
        side = KindPools(
            tuple(s for _ in spec.layer_kinds), NamedSharding(mesh, P())
        )
        return side, side
    if kv_dtype == "fp8":
        qs = QuantPool(s, NamedSharding(mesh, P(None, None, "tp")))
        return qs, qs
    return s, s


def init_cache(
    spec: ModelSpec, num_pages: int, page_size: int, dtype=None,
    kv_dtype: str = "bf16", state_rows: int = 0, tp: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """K and V page arrays [L, num_pages, kv_heads, page_size, head_dim].

    PAGE-MAJOR layout: one page's KV for ALL heads is a single contiguous
    [kv_heads, page_size, head_dim] block, so the decode kernels move a
    page with ONE DMA descriptor. (The previous head-major layout made the
    same slice a strided copy that expands to kv_heads descriptors — and
    decode attention is DMA-descriptor-bound, not bandwidth-bound: see
    ops/pallas/fused_decode.py.) ``num_pages`` must already include
    the trash page (index 0).

    K pools are ``head_dim`` wide and V pools ``v_dim``; on the chip a
    side's rows are whole 128-lane tiles (pool_head_dim, which is asked
    for ``tp`` shards of the head axis): heads that divide the tile are
    PACKED several a row, ``[L, num_pages, kv_heads / r, page_size,
    128]``, other widths zero-padded up to it. A model with layer
    kinds gets a ``KindPools`` a side: a pool a kind; a recurrent kind's
    "pool" is ``state_rows`` rows of state (and a trash row), see
    ``KindPools``; a kind that keeps both gets ``PagesAndState``.

    ``kv_dtype="fp8"`` allocates QuantPools instead (ops/quant.py): fp8
    values + bf16 per-page/head scales — half the HBM footprint and half
    the decode read traffic; every writer quantizes, every reader
    dequantizes, and the tolerance goldens (tests/test_quant_goldens.py)
    bound the numeric drift.
    """
    from dynamo_tpu.ops.attention import pool_head_dim

    # A pool's rows may differ from the model's (pool_head_dim: whole
    # 128-lane tiles so lane-misaligned heads keep the Mosaic DMA
    # kernels; gpt-oss's and LFM2's D=64 packed two a row, MiMo's K 192
    # padded to 256). Writers reshape or pad rows, readers place queries
    # or slice: exact for attention; see ops/attention.pool_head_dim.
    dtype = dtype or jnp.dtype(spec.dtype)
    itemsize = 1 if kv_dtype == "fp8" else jnp.dtype(dtype).itemsize

    def side(layers: int, kv_heads: int, model_d: int, pair_d: int):
        import logging

        heads, pool_d, a_row = pool_head_dim(
            model_d, kv_heads, pair_dim=pair_d, tp=tp,
            quantized=kv_dtype == "fp8",
        )
        shape = (layers, num_pages, heads, page_size, pool_d)
        logging.getLogger(__name__).info(
            "KV pool %s: %d heads of %d as %d rows of %d lanes, %.0f MiB",
            "packed" if a_row > 1 else
            "lane-padded" if pool_d != model_d else "plain",
            kv_heads, model_d, heads, pool_d,
            math.prod(shape) * itemsize / 2**20,
        )
        if kv_dtype == "fp8":
            # scale per (layer, page, kv_head): the append-time amax rides
            # the same page granularity every kernel DMAs at
            return init_quant_pool(shape, 3)
        return jnp.zeros(shape, dtype)

    if not spec.layer_kinds:
        return (
            side(spec.num_layers, spec.num_kv_heads, spec.head_dim, spec.v_dim),
            side(spec.num_layers, spec.num_kv_heads, spec.v_dim, spec.head_dim),
        )
    if kv_dtype == "fp8":
        raise ValueError("kv_dtype=fp8 has no pools by layer kind yet")
    n_layers = [spec.layer_pattern.count(i) for i in range(len(spec.layer_kinds))]
    n_counts = spec.experts_here[0] + 3 if spec.num_experts else 0
    R1 = state_rows + 1  # the last row is the trash row
    H, D = spec.kda_heads, spec.kda_head_dim
    # a row of each recurrent mixer: its state matrix (float32, where
    # the kind keeps one: ``LayerKind.state``) and its convolution tails
    state_row = {
        "kda": (H, D, D),
        "ssd": (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state),
        "scan": (spec.scan_state, spec.scan_inner),
    }
    tail_row = {
        "kda": (spec.kda_conv - 1, 3, H * D),
        "ssd": (spec.ssm_conv - 1, spec.ssm_conv_dim),
        "conv": (spec.conv_taps - 1, spec.hidden_size),
        "scan": (spec.scan_conv - 1, spec.scan_inner),
    }

    def pages(n, kd, model_d, pair_d):
        # a differential kind keeps a PAIR of heads a row
        p = 2 if kd.differential else 1
        return side(n, kd.num_kv_heads // p, p * model_d, p * pair_d)

    def rows(n, shape, dt):
        return jnp.zeros((n, R1, *shape), dt)

    def latent(n):
        from dynamo_tpu.models import mla

        return jnp.zeros(
            (n, num_pages, page_size, pool_head_dim(mla.latent_dim(spec))[1]),
            dtype)

    def k_side(n, kd):
        if kd.latent:
            return latent(n)
        return _entry_of(
            pages(n, kd, spec.head_dim, spec.v_dim) if kd.paged else None,
            rows(n, state_row[kd.mixer], jnp.float32) if kd.state else None,
        )

    def v_side(n, kd):
        if kd.latent:
            return None
        return _entry_of(
            pages(n, kd, spec.v_dim, spec.head_dim) if kd.paged else None,
            rows(n, tail_row[kd.mixer], dtype) if kd.recurrent else None,
        )

    directory = None
    if spec.has_recurrent:
        if state_rows < 1:
            raise ValueError("a model with recurrent layers needs state_rows")
        directory = StateRows(
            jnp.zeros((1, R1), jnp.int32), jnp.zeros((1, R1), jnp.int32),
            jnp.zeros((1, 3), jnp.int32),
        )
    return (
        KindPools(
            tuple(k_side(n, kd) for n, kd in zip(n_layers, spec.layer_kinds)),
            jnp.zeros((spec.num_layers, 2, n_counts), jnp.int32), directory,
        ),
        KindPools(
            tuple(v_side(n, kd) for n, kd in zip(n_layers, spec.layer_kinds)),
            jnp.zeros((spec.num_layers, 2, 0), jnp.int32),
        ),
    )


def page_size_of(pages) -> int:
    """Tokens a page, of a cache side in any form: a page pool's last
    axes are ``[page_size, D]``, under a head axis or (latent rows)
    without one."""
    return jax.tree.leaves(pages)[0].shape[-2]


def _layer_pools(spec: ModelSpec, k_pages, v_pages, li: int):
    """(K pool, V pool, index of layer ``li`` within them)."""
    if not spec.layer_kinds:
        return k_pages, v_pages, li
    ki, lj = spec.pool_slot(li)
    return k_pages.pools[ki], v_pages.pools[ki], lj


def _put_pools(spec: ModelSpec, k_pages, v_pages, li: int, kp, vp):
    """The cache with layer ``li``'s pools replaced by ``kp``, ``vp``."""
    if not spec.layer_kinds:
        return kp, vp
    return _put_kind(k_pages, v_pages, spec.layer_pattern[li], kp, vp)


def _put_kind(k_pages, v_pages, ki: int, kp, vp):
    """The cache with kind ``ki``'s entries replaced by ``kp``, ``vp``."""
    def put(side, pool):
        return side._replace(
            pools=side.pools[:ki] + (pool,) + side.pools[ki + 1:]
        )

    return put(k_pages, kp), put(v_pages, vp)


def _set_page_tiles(
    pool, li: int, safe_pg: jax.Array, arr: jax.Array, page_size: int,
    valid_tok: jax.Array,  # [n_tiles, page] bool (True = real token)
):
    """Prefill page write for either pool form, in the pool's own layout
    (``page_tiles``: packed or padded rows by the pool's shape): plain
    pools scatter the tiles as-is; QuantPools zero the padded rows, take
    one amax scale per (page, head), and scatter fp8 values + scales.
    ``valid_tok`` marks real tokens — garbage in a partial tail page must
    not inflate the page scale (it is masked from attention and
    requantized over as decode appends land)."""
    tiles = page_tiles(arr, page_size, pool.shape[-1], pool.shape[2])
    if is_quant(pool):
        vals, s = quant_page_tiles(
            tiles, valid_tok[:, None, :, None], (2, 3)
        )
        return QuantPool(
            pool.vals.at[li, safe_pg].set(vals),
            pool.scale.at[li, safe_pg].set(s),
        )
    return pool.at[li, safe_pg].set(tiles)


# ---------------------------------------------------------------- layers


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def yarn_get_mscale(scale: float, m: float = 1.0) -> float:
    """HF yarn_get_mscale: the single source for the YaRN attention
    temperature formula (shared by yarn_freqs and mla.softmax_scale)."""
    import math

    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_freqs(spec: ModelSpec, dim: int, theta: float | None = None):
    """YaRN-corrected inverse frequencies + cos/sin attention factor.

    Returns ``(inv_freq [dim//2] | None, attention_factor)``; None = no
    scaling configured. Semantics match HF ``_compute_yarn_parameters``
    (transformers modeling_rope_utils) so checkpoints that ship YaRN
    configs — gpt-oss (factor 32, truncate off) and DeepSeek-R1 (factor
    40, mscale 1) — reproduce HF numerics exactly."""
    import math

    import numpy as np

    if not spec.rope_scaling_factor:
        return None, 1.0
    base, factor = theta or spec.rope_theta, spec.rope_scaling_factor
    orig = spec.rope_orig_max_pos
    half = dim // 2
    pos_freqs = base ** (np.arange(0, half, dtype=np.float64) * 2 / dim)
    inv_extra = 1.0 / pos_freqs
    inv_inter = 1.0 / (factor * pos_freqs)

    def corr_dim(n_rot: float) -> float:
        return (dim * math.log(orig / (n_rot * 2 * math.pi))) / (
            2 * math.log(base)
        )

    low = corr_dim(spec.rope_beta_fast)
    high = corr_dim(spec.rope_beta_slow)
    if spec.rope_truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip(
        (np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1
    )
    ext_factor = 1.0 - ramp
    inv = inv_inter * (1 - ext_factor) + inv_extra * ext_factor
    if spec.rope_mscale and spec.rope_mscale_all_dim:
        att = yarn_get_mscale(factor, spec.rope_mscale) / yarn_get_mscale(
            factor, spec.rope_mscale_all_dim
        )
    else:
        att = yarn_get_mscale(factor)
    return inv.astype(np.float32), float(att)


def rope(
    x: jax.Array, positions: jax.Array, theta: float,
    *, inv_freq=None, scale: float = 1.0, rotary_dim: int = 0,
) -> jax.Array:
    """Rotary embedding. x: [..., heads, D], positions: [...]. ``inv_freq``
    overrides the plain theta schedule (YaRN); ``scale`` multiplies the
    rotated output (YaRN attention factor — HF folds it into cos/sin,
    which is the same linear map). ``rotary_dim`` < D rotates the leading
    dims only (half-split pairs within them); the rest pass through."""
    D = x.shape[-1]
    R = rotary_dim or D
    half = R // 2
    if inv_freq is None:
        freqs = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
        )
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., half]
    cos = jnp.cos(angles)[..., None, :] * scale  # [..., 1, half]
    sin = jnp.sin(angles)[..., None, :] * scale
    x1, x2 = x[..., :half], x[..., half:R]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [(xf1 * cos - xf2 * sin).astype(x.dtype),
         (xf2 * cos + xf1 * sin).astype(x.dtype), x[..., R:]], axis=-1
    )


def rope_spec(
    spec: ModelSpec, x: jax.Array, positions: jax.Array,
    theta: float | None = None,
) -> jax.Array:
    """spec-driven rope: plain theta schedule (the layer kind's, where
    given), or YaRN when configured; over ``spec.rotary_dim`` dims."""
    theta = theta or spec.rope_theta
    rot = spec.rotary_dim or x.shape[-1]
    inv, att = yarn_freqs(spec, rot, theta)
    return rope(x, positions, theta, inv_freq=inv, scale=att, rotary_dim=rot)


# Stable names for the regions of a layer, in decode and prefill alike:
# jax.named_scope is metadata on the operations (their op_name in an HLO
# dump and in a profiler's operation details); it changes no program. The
# names and their groups are models/regions.py's. Inside attn_kv, where a
# model mixes kinds of attention layer, attn_window / attn_full: a Mosaic
# call is named after its innermost scope, so the two kinds' decode
# kernels read apart in a trace. A model of one kind opens neither, and
# its kernel keeps its own name (``fused_decode_attention``).


def _scope(name: str | None):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float):
    """LayerNorm over the last axis, a gain and a bias, in float32."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def _any_norm(spec: ModelSpec, x: jax.Array, p: Params, name: str):
    """The model's norm of ``x`` under the gain ``p[name]``: RMSNorm, or
    LayerNorm with the bias ``p[name + "_bias"]`` where ``ModelSpec.norm``
    is "layer" (such a model draws and loads a bias beside every gain)."""
    if spec.norm == "layer":
        return layer_norm(x, p[name], p[name + "_bias"], spec.rms_eps)
    return rms_norm(x, p[name], spec.rms_eps)


@jax.named_scope(SCOPE_NORM)
def _norm(spec: ModelSpec, x: jax.Array, lp: Params, name: str) -> jax.Array:
    """A layer's input norm, under its region's name."""
    return _any_norm(spec, x, lp, name)


@jax.named_scope(SCOPE_RESIDUAL)
def _add(x: jax.Array, y: jax.Array) -> jax.Array:
    return x + y


def _residual(
    spec: ModelSpec, lp: Params, x: jax.Array, y: jax.Array, gain: str
) -> jax.Array:
    """The residual half of a layer, for every program: the stream ``x``
    plus what a mixer or an FFN put out, ``y``, normed first under the
    layer's gain ``gain`` (``post_attn_norm`` / ``post_mlp_norm``) where
    the model norms its outputs (``ModelSpec.sandwich_norm``), under a
    region of its own so that a trace tells it from the input norms."""
    if spec.sandwich_norm:
        with jax.named_scope(SCOPE_NORM_OUT):
            y = rms_norm(y, lp[gain], spec.rms_eps)
    return _add(x, y)


def _times(x: jax.Array, m: float) -> jax.Array:
    """x scaled by one of a family's fixed multipliers; nothing where the
    model has none (1)."""
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


@jax.named_scope(SCOPE_EMBED)
def _embed(
    params: Params, tokens: jax.Array, spec: ModelSpec | None = None
) -> jax.Array:
    x = params["embed"][tokens]
    return x if spec is None else _times(x, spec.embedding_multiplier)


def attn_scope(spec: ModelSpec, li: int) -> str | None:
    if not (spec.has_attn_extras or spec.has_recurrent):
        return None
    return SCOPE_ATTN_WINDOW if spec.kind(li).window else SCOPE_ATTN_FULL


@jax.named_scope(SCOPE_QKV)
def _attn_qkv(
    spec: ModelSpec, li: int, lp: Params, x: jax.Array, positions: jax.Array
):
    """x: [..., d], positions: [...] -> q [..., nh, hd], k [..., nkv, hd]
    with rope applied (after an RMSNorm a head where the model has
    ``qk_norm``; not at all where the layer's kind carries no position),
    v [..., nkv, vd] scaled by ``value_scale``; nkv, the rope base and
    whether it rotates are the layer kind's."""
    kd = spec.kind(li)
    lead = x.shape[:-1]
    x = _times(x, spec.attention_in_multiplier)
    q = x @ lp["wq"]
    k = _times(x @ lp["wk"], spec.key_multiplier)
    v = x @ lp["wv"]
    if spec.attn_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(*lead, spec.num_heads, spec.head_dim)
    k = k.reshape(*lead, kd.num_kv_heads, spec.head_dim)
    v = v.reshape(*lead, kd.num_kv_heads, spec.v_dim)
    if spec.value_scale != 1.0:
        v = v * jnp.asarray(spec.value_scale, v.dtype)
    if spec.qk_norm:
        q = rms_norm(q, lp["q_norm"], spec.rms_eps)
        k = rms_norm(k, lp["k_norm"], spec.rms_eps)
    if spec.use_rope and kd.rope:
        q = rope_spec(spec, q, positions, kd.rope_theta)
        k = rope_spec(spec, k, positions, kd.rope_theta)
    return q, k, v


@jax.named_scope(SCOPE_OUT)
def _o_proj(
    spec: ModelSpec, lp: Params, attn: jax.Array, h: jax.Array,
) -> jax.Array:
    """The output projection of the heads' outputs ``attn`` [..., H, dv]
    (or already flat), ``h`` [..., d] the layer's normed input; where the
    layer gates its attention output (``w_gate_attn``), by element with a
    sigmoid of ``h`` first."""
    attn = attn.reshape(*h.shape[:-1], -1)
    if "w_gate_attn" in lp:
        attn = attn * jax.nn.sigmoid(
            (h @ lp["w_gate_attn"]).astype(jnp.float32)
        ).astype(attn.dtype)
    out = _times(attn @ lp["wo"], spec.attention_out_multiplier)
    return out + lp["bo"] if spec.attn_bias else out


def _mlp(
    lp: Params, x: jax.Array, mults: tuple = (), clamp: float = 0.0,
) -> jax.Array:
    """SwiGLU; ``mults`` (gate, down) where the family scales the gate
    projection and the down projection's output; ``clamp`` where the
    layer bounds its two halves: ``silu(min(gate, L)) * clip(up, -L, L)``."""
    g_mul, d_mul = mults or (1.0, 1.0)
    gate = _times(x @ lp["w_gate"], g_mul)
    gate = jax.nn.silu(jnp.minimum(gate, clamp) if clamp else gate)
    up = x @ lp["w_up"]
    if clamp:
        up = jnp.clip(up, -clamp, clamp)
    return _times((gate * up) @ lp["w_down"], d_mul)


@jax.named_scope(SCOPE_MLP)
def _ffn(
    spec: ModelSpec, lp: Params, x: jax.Array, *, mesh: Mesh | None = None,
    counted: jax.Array | None = None, li: int = 0,
):
    """Dense MLP or the routed experts, as the layer's weights say. x:
    [T, d]. With ``counted`` ([T] bool: the real tokens) an expert layer
    returns (y, its counters' row) — see moe.moe_mlp. ``li``: the layer,
    for a model that clamps its experts a layer (``ModelSpec.clamps``: a
    static of the program)."""
    if "moe" in lp:
        from dynamo_tpu.models import moe

        clamp, shared_clamp = spec.clamps(li)
        out = moe.moe_mlp(
            spec, lp["moe"], x, mesh=mesh, counted=counted, clamp=clamp)
        if "shared" in lp:
            # the shared expert, whole: every chip of an expert-parallel
            # deployment computes it for its own tokens
            with jax.named_scope(SCOPE_MOE_SHARED):
                y = _mlp(lp["shared"], x, clamp=shared_clamp)
                out = out + y if counted is None else (out[0] + y, out[1])
        return out
    return _mlp(lp, x, spec.mlp_multipliers)


def _ffn_counting(
    spec: ModelSpec, li: int, lp: Params, x: jax.Array, k_pages,
    phase: int, counted: jax.Array, mesh: Mesh | None,
):
    """_ffn over [T, d] rows, adding an expert layer's counters to the
    cache's K side where it keeps them. Returns (y, k_pages)."""
    keeps = isinstance(k_pages, KindPools) and k_pages.counts.shape[-1] > 0
    if "moe" in lp and keeps:
        y, row = _ffn(spec, lp, x, mesh=mesh, counted=counted, li=li)
        with jax.named_scope(SCOPE_MOE_COUNT):
            step = jnp.ones((1,), jnp.int32)
            counts = k_pages.counts.at[li, phase].add(
                jnp.concatenate([row, step])
            )
        return y, k_pages._replace(counts=counts)
    return _ffn(spec, lp, x, mesh=mesh, li=li), k_pages


def _ctx_attention(
    spec: ModelSpec, li: int, lp: Params, q, k, v, k_pool, v_pool, lj: int,
    block_table, positions, kv_len,
):
    """Attention of one sequence's new queries (q [T, H, D] at the
    consecutive ``positions``; their own k, v already written to the
    pools) over its paged context, walked in blocks of pages
    (``paged_prefill_attention``): what is gathered and scored follows
    the prompt's length, a query tile's causal edge and the layer's
    window, not the table's width. Every prefill program and the
    speculative verify come through here."""
    kd = spec.kind(li)
    with _scope(attn_scope(spec, li)):
        return paged_prefill_attention(
            q, k_pool, v_pool, lj, block_table, positions[0], kv_len,
            **_row_dims(spec, li), window=kd.window, sinks=lp.get("sinks"),
            # the EXACT in-flight rows over a quantised pool's read-back
            # (the XLA mirror of the fused kernel's analytic new-token
            # merge): the new tokens attend to each other at full
            # precision; only the cached prefix pays fp8
            new_kv=(k, v) if is_quant(k_pool) else None,
        )


@jax.named_scope(SCOPE_HEAD)
def _logits(spec: ModelSpec, params: Params, x: jax.Array) -> jax.Array:
    x = _any_norm(spec, x, params, "final_norm")
    head = params["embed"].T if spec.tie_embeddings else params["lm_head"]
    return _times((x @ head).astype(jnp.float32), spec.lm_head_multiplier)


# ----------------------------------------------- short convolutions' tails


def _causal_taps(taps: jax.Array, ext: jax.Array, T: int) -> jax.Array:
    """A causal depthwise convolution a channel, in float32: ``sum_i
    taps[i] * ext[:, i:i + T]``. taps: [n, channels]; ext: [N, n - 1 + T,
    channels], the sequence with the ``n - 1`` rows before it in front
    (its tail; zeros at a sequence's start). -> [N, T, channels]."""
    taps = taps.astype(jnp.float32)
    return sum(
        taps[i] * ext[:, i:i + T].astype(jnp.float32)
        for i in range(taps.shape[0])
    )


def _new_tail(ext: jax.Array, num_tokens: jax.Array, n: int) -> jax.Array:
    """The tail a sequence leaves: the ``n`` rows of ``ext`` [N, n + T,
    channels] that end at its last REAL token (num_tokens: [N]); a row
    without tokens keeps the tail it came with."""
    return jax.vmap(
        lambda e, at: jax.lax.dynamic_slice_in_dim(e, at, n, axis=0)
    )(ext, num_tokens)


# ------------------------------------------------------------------- KDA


def _kda_inputs(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, tail: jax.Array,
):
    """A KDA layer's operands from its normed input, in the forms its kind
    ``kd`` selects (``LayerKind.full_rank``, ``gate_bound``), for the chunkwise
    form (prefill, chunks, whole sequences; a decode step hands its
    projections to ``kda_decode_step`` instead). h: [N, T, d]; tail:
    [N, taps - 1, 3 H D], the q | k | v projections of the ``taps - 1``
    tokens before (zeros at a sequence's start). Returns (q, k, v, g [N,
    T, H, D] float32, beta [N, T, H] float32, ext [N, taps - 1 + T, 3 H
    D]: the projections with the tail in front, of which the caller keeps
    the new tail)."""
    N, T, _ = h.shape
    H, D = spec.kda_heads, spec.kda_head_dim
    with jax.named_scope(SCOPE_KDA_PROJ):
        x = jnp.concatenate(
            [h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]], axis=-1
        )
    with jax.named_scope(SCOPE_KDA_CONV):
        ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        conv = _causal_taps(jnp.concatenate(
            [lp["conv_q"], lp["conv_k"], lp["conv_v"]], axis=1), ext, T)
        q, k, v = (
            y.reshape(N, T, H, D)
            for y in jnp.split(jax.nn.silu(conv), 3, axis=-1)
        )
        q = q * jax.lax.rsqrt(
            jnp.sum(q * q, -1, keepdims=True) + 1e-6) * D ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g, beta = _kda_gates(spec, kd, lp, h)
    return q, k, v, g, beta, ext


@jax.named_scope(SCOPE_KDA_GATES)
def _kda_gates(spec: ModelSpec, kd, lp: Params, h: jax.Array):
    """A KDA layer's gates from its normed input h [..., d], in the forms
    its kind selects: (g [..., H, D] float32, the log decay a channel;
    beta [..., H] float32)."""
    f32 = jnp.float32
    heads = (*h.shape[:-1], spec.kda_heads, spec.kda_head_dim)
    f = h @ lp["w_f"] if kd.full_rank else (
        (h @ lp["w_f_down"]) @ lp["w_f_up"])
    f = f.astype(f32) + lp["dt_bias"]
    if kd.gate_bound:
        # the bounded ("safe") gate: a token's log decay in
        # (gate_bound, 0), inside what kda_chunk's sub-block inverse
        # decay holds at -5
        g = kd.gate_bound * jax.nn.sigmoid(
            jnp.exp(lp["a_log"])[:, None] * f.reshape(heads))
    else:
        g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(f).reshape(heads)
    beta = jax.nn.sigmoid((h @ lp["w_beta"]).astype(f32))
    if spec.kda_neg_eigval:
        beta = 2.0 * beta
    return g, beta


@jax.named_scope(SCOPE_OUT)
def _kda_out(spec: ModelSpec, kd, lp: Params, o: jax.Array, h: jax.Array):
    """o: [..., H, D] float32 -> the layer's output [..., d]: RMSNorm a
    head, the sigmoid gate of the layer's input, the output projection."""
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = (o * jax.lax.rsqrt(var + spec.rms_eps)).astype(h.dtype) * lp["o_norm"]
    gate = h @ lp["w_g"] if kd.full_rank else (
        (h @ lp["w_g_down"]) @ lp["w_g_up"])
    gate = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(h.dtype)
    return (o.reshape(*h.shape[:-1], -1) * gate) @ lp["wo"]


def ext_width(c_pool) -> int:
    """Channels of a convolution tail, q | k | v side by side: the tails'
    pool keeps them ``[..., taps - 1, 3, H D]``, a head block's channels of
    each apart, so that a program of the decode kernel reads and writes
    one block (as it does of the step's projections, ``[B, 3, H D]``)."""
    return c_pool.shape[-2] * c_pool.shape[-1]


def _kda_prefill(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, s_pool, c_pool, lj: int,
    idx: jax.Array, fresh: jax.Array, num_tokens: jax.Array,
):
    """A KDA layer over N sequences' new tokens, from and to their state
    rows. h: [N, T, d]; idx, fresh, num_tokens: [N]. Returns (out [N, T,
    d], s_pool, c_pool)."""
    N, T, _ = h.shape
    with jax.named_scope(SCOPE_QKV):
        with jax.named_scope(SCOPE_KDA_CONV):
            tail = jnp.where(
                fresh[:, None, None], 0,
                c_pool[lj, idx].reshape(N, -1, ext_width(c_pool)),
            )
        q, k, v, g, beta, ext = _kda_inputs(spec, kd, lp, h, tail)
        with jax.named_scope(SCOPE_KDA_GATES):
            # a padded token leaves the state as it was
            real = jnp.arange(T)[None, :] < num_tokens[:, None]
            g = jnp.where(real[..., None, None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
    with jax.named_scope(SCOPE_KV):
        o, s_pool = kda_chunk_prefill(
            q, k, v, g, beta, s_pool, idx, fresh, layer=lj
        )
        # the new tail: the projections of the last taps - 1 REAL tokens
        new_tail = _new_tail(ext, num_tokens, spec.kda_conv - 1)
        c_pool = c_pool.at[lj, idx].set(
            new_tail.reshape(N, *c_pool.shape[2:]).astype(c_pool.dtype)
        )
    return _kda_out(spec, kd, lp, o, h), s_pool, c_pool


def _kda_decode(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, s_pool, c_pool, lj: int,
    idx: jax.Array,
):
    """A KDA layer's decode step over the slots' state rows: the three
    projections as the matmuls leave them (q | k | v apart, the layout of
    the tails' pool), the gates, and ONE call that convolves over each
    slot's tail, normalises, steps the state and shifts the tail
    (``kda_decode_step``). h: [B, d]; idx: [B] (the trash row for a slot
    that owns none). Returns (out [B, d], s_pool, c_pool)."""
    with jax.named_scope(SCOPE_QKV):
        with jax.named_scope(SCOPE_KDA_PROJ):
            x = jnp.stack([h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]], axis=1)
            taps = jnp.stack(
                [lp["conv_q"], lp["conv_k"], lp["conv_v"]], axis=1)
        g, beta = _kda_gates(spec, kd, lp, h)
    with jax.named_scope(SCOPE_KV):
        o, s_pool, c_pool = kda_decode_step(
            s_pool, c_pool, idx, x, taps, g, beta, layer=lj)
    return _kda_out(spec, kd, lp, o, h), s_pool, c_pool


def _kda_whole(spec: ModelSpec, kd, lp: Params, h: jax.Array) -> jax.Array:
    """A KDA layer over one whole sequence from an empty state, keeping
    none (embeddings, ``reference_forward``). h: [T, d] -> [T, d]."""
    H, D = spec.kda_heads, spec.kda_head_dim
    tail = jnp.zeros((1, spec.kda_conv - 1, 3 * H * D), h.dtype)
    q, k, v, g, beta, _ = _kda_inputs(spec, kd, lp, h[None], tail)
    o, _ = kda_chunk_prefill(
        q, k, v, g, beta, jnp.zeros((1, 2, H, D, D), jnp.float32),
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool), layer=0,
    )
    return _kda_out(spec, kd, lp, o[0], h)


# ------------------------------------------------------------------- SSD


def _ssd_inputs(spec: ModelSpec, lp: Params, h: jax.Array, tail: jax.Array):
    """An SSD mixer's operands from the layer's normed input. h: [N, T,
    d]; tail: [N, taps - 1, channels], the x | B | C projections of the
    ``taps - 1`` tokens before (zeros at a sequence's start). Returns (z
    [N, T, H P], x [N, T, H, P], B, C [N, T, G, S], dt [N, T, H] float32
    with the softplus applied, ext [N, taps - 1 + T, channels]: the
    projections with the tail in front, of which the caller keeps the new
    tail)."""
    import numpy as np

    f32 = jnp.float32
    N, T, _ = h.shape
    H, P, G, S = (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_groups,
                  spec.ssm_state)
    d_ssm, ch = H * P, spec.ssm_conv_dim
    with jax.named_scope(SCOPE_SSM_PROJ):
        zxbcdt = _times(h, spec.ssm_in_multiplier) @ lp["ssm_in"]
        if spec.ssm_multipliers:
            mup = np.repeat(
                np.asarray(spec.ssm_multipliers, np.float32),
                (d_ssm, d_ssm, G * S, G * S, H))
            zxbcdt = zxbcdt * jnp.asarray(mup, zxbcdt.dtype)
        z, xbc, dt = jnp.split(zxbcdt, (d_ssm, d_ssm + ch), axis=-1)
    with jax.named_scope(SCOPE_SSM_CONV):
        ext = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        conv = _causal_taps(lp["ssm_conv"], ext, T) + (
            lp["ssm_conv_bias"].astype(f32))
        x, B, C = jnp.split(
            jax.nn.silu(conv).astype(h.dtype), (d_ssm, d_ssm + G * S), axis=-1)
    with jax.named_scope(SCOPE_SSM_GATES):
        dt = jax.nn.softplus(dt.astype(f32) + lp["ssm_dt_bias"])
    return (z, x.reshape(N, T, H, P), B.reshape(N, T, G, S),
            C.reshape(N, T, G, S), dt, ext)


def _ssd_out(spec: ModelSpec, lp: Params, y: jax.Array, z: jax.Array):
    """y: [..., H, P] float32, z: [..., H P] -> the mixer's output [...,
    d]: the gate ``silu(z)``, then RMSNorm a group of channels
    (``norm_before_gate`` false), the output projection, its multiplier."""
    G = spec.ssm_groups
    with jax.named_scope(SCOPE_SSM_GATES):
        g = y.reshape(*z.shape) * jax.nn.silu(z.astype(jnp.float32))
        g = g.reshape(*z.shape[:-1], G, -1)
        var = jnp.mean(g * g, axis=-1, keepdims=True)
        g = (g * jax.lax.rsqrt(var + spec.rms_eps)).reshape(*z.shape)
        g = g.astype(z.dtype) * lp["ssm_norm"]
    with jax.named_scope(SCOPE_SSM_PROJ):
        return _times(g @ lp["ssm_out"], spec.ssm_out_multiplier)


def _ssd_prefill(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, s_pool, c_pool, lj: int,
    idx: jax.Array, fresh: jax.Array, num_tokens: jax.Array,
):
    """An SSD mixer over N sequences' new tokens, from and to their state
    rows. h: [N, T, d]; idx, fresh, num_tokens: [N]. Returns (out [N, T,
    d], s_pool, c_pool)."""
    N, T, _ = h.shape
    with jax.named_scope(SCOPE_QKV):
        with jax.named_scope(SCOPE_SSM_CONV):
            tail = jnp.where(fresh[:, None, None], 0, c_pool[lj, idx])
        z, x, B, C, dt, ext = _ssd_inputs(spec, lp, h, tail)
        with jax.named_scope(SCOPE_SSM_GATES):
            # a padded token leaves the state as it was
            real = jnp.arange(T)[None, :] < num_tokens[:, None]
            dt = jnp.where(real[..., None], dt, 0.0)
    with jax.named_scope(SCOPE_KV):
        y, s_pool = ssd_chunk_prefill(
            x, dt, -jnp.exp(lp["ssm_a_log"]), B, C, lp["ssm_d"], s_pool,
            idx, fresh, layer=lj, chunk=spec.ssm_chunk,
        )
        # the new tail: the projections of the last taps - 1 REAL tokens
        new_tail = _new_tail(ext, num_tokens, spec.ssm_conv - 1)
        c_pool = c_pool.at[lj, idx].set(new_tail.astype(c_pool.dtype))
    with jax.named_scope(SCOPE_OUT):
        return _ssd_out(spec, lp, y, z), s_pool, c_pool


def _ssd_decode(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, s_pool, c_pool, lj: int,
    idx: jax.Array,
):
    """An SSD mixer's decode step over the slots' state rows. h: [B, d];
    idx: [B] (the trash row for a slot that owns none). Returns (out [B,
    d], s_pool, c_pool)."""
    with jax.named_scope(SCOPE_QKV):
        z, x, B, C, dt, ext = _ssd_inputs(
            spec, lp, h[:, None], c_pool[lj, idx])
    with jax.named_scope(SCOPE_KV):
        y, s_pool, c_pool = ssd_decode_step(
            s_pool, c_pool, idx, x[:, 0], dt[:, 0],
            -jnp.exp(lp["ssm_a_log"]), B[:, 0], C[:, 0], lp["ssm_d"],
            ext[:, 1:], layer=lj,
        )
    with jax.named_scope(SCOPE_OUT):
        return _ssd_out(spec, lp, y, z[:, 0]), s_pool, c_pool


def _ssd_whole(spec: ModelSpec, kd, lp: Params, h: jax.Array) -> jax.Array:
    """An SSD mixer over one whole sequence from an empty state, keeping
    none (embeddings, ``reference_forward``). h: [T, d] -> [T, d]."""
    H, P, S = spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state
    tail = jnp.zeros((1, spec.ssm_conv - 1, spec.ssm_conv_dim), h.dtype)
    z, x, B, C, dt, _ = _ssd_inputs(spec, lp, h[None], tail)
    y, _ = ssd_chunk_prefill(
        x, dt, -jnp.exp(lp["ssm_a_log"]), B, C, lp["ssm_d"],
        jnp.zeros((1, 2, H, P, S), jnp.float32), jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), bool), layer=0, chunk=spec.ssm_chunk,
    )
    return _ssd_out(spec, lp, y[0], z[0])


# ------------------------------------------- the gated short convolution


def _conv_mix(lp: Params, h: jax.Array, tail: jax.Array):
    """A gated short convolution (LFM2) up to its output projection: ``[B
    | C | x] = h W_in``, ``z = B * x``, ``y = C * conv(z)`` with the taps
    applied in float32 over ``z`` in the activation dtype (what a tail
    holds). h: [N, T, d]; tail: [N, taps - 1, d], the ``z`` of the ``taps
    - 1`` tokens before (zeros at a sequence's start). Returns (y [N, T,
    d], ext [N, taps - 1 + T, d]: ``z`` with the tail in front, of which
    the caller keeps the new tail)."""
    with jax.named_scope(SCOPE_CONV_PROJ):
        B, C, x = jnp.split(h @ lp["sconv_in"], 3, axis=-1)
    with jax.named_scope(SCOPE_CONV_MIX):
        ext = jnp.concatenate([tail.astype(h.dtype), B * x], axis=1)
        y = C.astype(jnp.float32) * _causal_taps(
            lp["sconv_taps"], ext, h.shape[1])
    return y.astype(h.dtype), ext


@jax.named_scope(SCOPE_OUT)
def _conv_out(lp: Params, y: jax.Array) -> jax.Array:
    with jax.named_scope(SCOPE_CONV_PROJ):
        return y @ lp["sconv_out"]


def _conv_prefill(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, s_pool, c_pool, lj: int,
    idx: jax.Array, fresh: jax.Array, num_tokens: jax.Array,
):
    """A short-convolution mixer over N sequences' new tokens, from and to
    their rows' tails (the kind keeps no state matrix: ``s_pool`` is None
    and goes back as it came). h: [N, T, d]; idx, fresh, num_tokens: [N].
    Returns (out [N, T, d], s_pool, c_pool)."""
    with jax.named_scope(SCOPE_QKV):
        with jax.named_scope(SCOPE_CONV_MIX):
            tail = jnp.where(fresh[:, None, None], 0, c_pool[lj, idx])
        y, ext = _conv_mix(lp, h, tail)
    with jax.named_scope(SCOPE_KV), jax.named_scope(SCOPE_CONV_MIX):
        # the new tail: the z of the last taps - 1 REAL tokens
        new_tail = _new_tail(ext, num_tokens, spec.conv_taps - 1)
        c_pool = c_pool.at[lj, idx].set(new_tail.astype(c_pool.dtype))
    return _conv_out(lp, y), s_pool, c_pool


def _conv_decode(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, s_pool, c_pool, lj: int,
    idx: jax.Array,
):
    """A short-convolution mixer's decode step over the slots' tails. h:
    [B, d]; idx: [B] (the trash row for a slot that owns none). Returns
    (out [B, d], s_pool, c_pool)."""
    with jax.named_scope(SCOPE_QKV):
        with jax.named_scope(SCOPE_CONV_MIX):
            tail = c_pool[lj, idx]
        y, ext = _conv_mix(lp, h[:, None], tail)
    with jax.named_scope(SCOPE_KV), jax.named_scope(SCOPE_CONV_MIX):
        c_pool = c_pool.at[lj, idx].set(ext[:, 1:].astype(c_pool.dtype))
    return _conv_out(lp, y[:, 0]), s_pool, c_pool


def _conv_whole(spec: ModelSpec, kd, lp: Params, h: jax.Array) -> jax.Array:
    """A short-convolution mixer over one whole sequence from an empty
    tail, keeping none (embeddings, ``reference_forward``). h: [T, d] ->
    [T, d]."""
    tail = jnp.zeros((1, spec.conv_taps - 1, h.shape[-1]), h.dtype)
    y, _ = _conv_mix(lp, h[None], tail)
    return _conv_out(lp, y[0])


# ---------------------------------------------------- the selective scan


def _scan_inputs(spec: ModelSpec, lp: Params, h: jax.Array, tail: jax.Array):
    """A selective-scan (Mamba-1) mixer's operands from the layer's normed
    input, under the SSD mixer's region names. h: [N, T, d]; tail: [N,
    taps - 1, C], the ``x`` half of the input projection for the ``taps -
    1`` tokens before (zeros at a sequence's start). Returns (z [N, T, C],
    x [N, T, C] (convolved, SiLU), B, C [N, T, S], dt [N, T, C] float32
    with the softplus applied, ext [N, taps - 1 + T, C]: the projection
    with the tail in front, of which the caller keeps the new tail)."""
    f32 = jnp.float32
    T = h.shape[1]
    R, S = spec.scan_dt_rank, spec.scan_state
    with jax.named_scope(SCOPE_SSM_PROJ):
        xs, z = jnp.split(h @ lp["scan_in"], 2, axis=-1)
    with jax.named_scope(SCOPE_SSM_CONV):
        ext = jnp.concatenate([tail.astype(xs.dtype), xs], axis=1)
        conv = _causal_taps(lp["scan_conv"], ext, T) + (
            lp["scan_conv_bias"].astype(f32))
        x = jax.nn.silu(conv).astype(h.dtype)
    with jax.named_scope(SCOPE_SSM_PROJ):
        dr, B, C = jnp.split(x @ lp["scan_x"], (R, R + S), axis=-1)
        dt = dr @ lp["scan_dt"]
    with jax.named_scope(SCOPE_SSM_GATES):
        dt = jax.nn.softplus(dt.astype(f32) + lp["scan_dt_bias"])
    return z, x, B, C, dt, ext


def _scan_out(lp: Params, y: jax.Array, z: jax.Array):
    """y: [..., C] float32, z: [..., C] -> (the mixer's output [..., d]:
    the gate ``silu(z)``, the output projection; ``y`` itself in the
    activations' dtype: the MEMORY a GMU layer reads, taken with the ``D``
    term and before the gate)."""
    with jax.named_scope(SCOPE_SSM_GATES):
        m = y.astype(z.dtype)
        g = (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    with jax.named_scope(SCOPE_SSM_PROJ):
        return g @ lp["scan_out"], m


def _scan_prefill(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, s_pool, c_pool, lj: int,
    idx: jax.Array, fresh: jax.Array, num_tokens: jax.Array,
):
    """A selective-scan mixer over N sequences' new tokens, from and to
    their state rows. h: [N, T, d]; idx, fresh, num_tokens: [N]. Returns
    ((out [N, T, d], memory [N, T, C]), s_pool, c_pool)."""
    T = h.shape[1]
    with jax.named_scope(SCOPE_QKV):
        with jax.named_scope(SCOPE_SSM_CONV):
            tail = jnp.where(fresh[:, None, None], 0, c_pool[lj, idx])
        z, x, B, C, dt, ext = _scan_inputs(spec, lp, h, tail)
        with jax.named_scope(SCOPE_SSM_GATES):
            # a padded token leaves the state as it was
            real = jnp.arange(T)[None, :] < num_tokens[:, None]
            dt = jnp.where(real[..., None], dt, 0.0)
    with jax.named_scope(SCOPE_KV):
        y, s_pool = scan_chunk_prefill(
            x, dt, -jnp.exp(lp["scan_a_log"]), B, C, lp["scan_d"], s_pool,
            idx, fresh, layer=lj, num_tokens=num_tokens,
        )
        # the new tail: the projections of the last taps - 1 REAL tokens
        new_tail = _new_tail(ext, num_tokens, spec.scan_conv - 1)
        c_pool = c_pool.at[lj, idx].set(new_tail.astype(c_pool.dtype))
    with jax.named_scope(SCOPE_OUT):
        return _scan_out(lp, y, z), s_pool, c_pool


def _scan_decode(
    spec: ModelSpec, kd, lp: Params, h: jax.Array, s_pool, c_pool, lj: int,
    idx: jax.Array,
):
    """A selective-scan mixer's decode step over the slots' state rows. h:
    [B, d]; idx: [B] (the trash row for a slot that owns none). Returns
    ((out [B, d], memory [B, C]), s_pool, c_pool)."""
    with jax.named_scope(SCOPE_QKV):
        z, x, B, C, dt, ext = _scan_inputs(
            spec, lp, h[:, None], c_pool[lj, idx])
    with jax.named_scope(SCOPE_KV):
        y, s_pool, c_pool = scan_decode_step(
            s_pool, c_pool, idx, x[:, 0], dt[:, 0],
            -jnp.exp(lp["scan_a_log"]), B[:, 0], C[:, 0], lp["scan_d"],
            ext[:, 1:], layer=lj,
        )
    with jax.named_scope(SCOPE_OUT):
        return _scan_out(lp, y, z[:, 0]), s_pool, c_pool


def _scan_whole(spec: ModelSpec, kd, lp: Params, h: jax.Array):
    """A selective-scan mixer over one whole sequence from an empty state,
    keeping none (embeddings, ``reference_forward``). h: [T, d] -> (out
    [T, d], memory [T, C])."""
    S, C = spec.scan_state, spec.scan_inner
    tail = jnp.zeros((1, spec.scan_conv - 1, C), h.dtype)
    z, x, B, Cm, dt, _ = _scan_inputs(spec, lp, h[None], tail)
    y, _ = scan_chunk_prefill(
        x, dt, -jnp.exp(lp["scan_a_log"]), B, Cm, lp["scan_d"],
        jnp.zeros((1, 2, S, C), jnp.float32), jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), bool), layer=0,
    )
    return _scan_out(lp, y[0], z[0])


# --------------------------------------------------- differential attention
# arXiv:2410.05258, as Phi-4-mini-flash pairs it: the even query heads q1
# and the odd ones q2, the even KV heads k1 and the odd ones k2, a value a
# PAIR of KV heads ``V_j = [v_2j | v_2j+1]``; query pair i reads KV pair i //
# (pairs a KV pair):
#
#     o_i = (1 - lam0) rms(softmax(q1_i k1^T / sqrt D) V - lam softmax(q2_i k2^T / sqrt D) V)
#
# A pair of KV heads is ONE row of the kind's pool, ``[k1 | k2]`` and ``[v_2j
# | v_2j+1]`` (``init_cache``), and a query head stands in the lanes of its
# own K with zeros in the other's, which add 0 to every score: both maps are
# then ORDINARY attention of ``num_heads`` queries ``2 D`` wide over
# ``num_kv_heads / 2`` heads, at the scale ``1 / sqrt D``, and every reader
# (the decode kernels, the prefill walk, ``causal_attention``) serves them in
# one call that reads K and V once.


def _pair_rows(spec: ModelSpec, li: int, q, k=None, v=None):
    """A differential layer's q [..., H, D], k [..., KH, D], v [..., KH,
    Dv] as its pool's rows see them: q [..., H, 2 D] with the pair's two
    maps' heads side by side a KV pair (``[q1.., q2..]``: the readers'
    grouping by ``H // pool heads`` then needs no more), k [..., KH / 2, 2
    D], v [..., KH / 2, 2 Dv]. Any other layer's come back as they are."""
    kd = spec.kind(li)
    if not kd.differential:
        return q, k, v
    *lead, H, D = q.shape
    P = kd.num_kv_heads // 2  # KV pairs
    g = H // (2 * P)  # query pairs a KV pair
    # [..., KV pair, query pair, map, D] -> [..., KV pair, map, query pair]
    q = q.reshape(*lead, P, g, 2, D).swapaxes(-2, -3)
    keep = [(0, 0)] * (q.ndim - 1)
    q = jnp.concatenate([
        jnp.pad(q[..., :1, :, :], keep + [(0, D)]),  # [q1 | 0]
        jnp.pad(q[..., 1:, :, :], keep + [(D, 0)]),  # [0 | q2]
    ], axis=-3).reshape(*lead, H, 2 * D)
    if k is not None:
        k = k.reshape(*lead, P, -1)
        v = v.reshape(*lead, P, -1)
    return q, k, v


def _row_dims(spec: ModelSpec, li: int) -> dict:
    """What the prefill walk is told of layer ``li``'s rows: the model's
    heads, or a differential layer's pairs (``_pair_rows``: half the KV
    heads, twice as wide, at the heads' own scale)."""
    p = 2 if spec.kind(li).differential else 1
    return dict(
        head_dim=p * spec.head_dim, v_dim=p * spec.v_dim,
        kv_heads=spec.kind(li).num_kv_heads // p,
        scale=_pair_scale(spec, li))


def _pair_scale(spec: ModelSpec, li: int) -> float | None:
    """The softmax scale of layer ``li`` where its rows are a pair wide
    (``1 / sqrt(head_dim)``, not the rows' width); None: the readers'."""
    return spec.head_dim ** -0.5 if spec.kind(li).differential else None


def lambda_init(layer_id: int) -> float:
    """Differential attention's ``lambda_init`` at a PUBLISHED layer."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_id)


@jax.named_scope(SCOPE_ATTN_DIFF)
def _diff_out(spec: ModelSpec, li: int, lp: Params, attn: jax.Array):
    """The two maps' outputs ``attn`` [..., H, 2 Dv] in ``_pair_rows``'
    order -> the pairs' [..., H / 2, 2 Dv] in the model's: ``(1 - lam0)
    rms(a1 - lam a2)`` under the layer's lambdas and the one gain a layer,
    in float32. Any other layer's come back as they are."""
    kd = spec.kind(li)
    if not kd.differential:
        return attn
    f32 = jnp.float32
    *lead, H, W = attn.shape
    P = kd.num_kv_heads // 2
    a = attn.astype(f32).reshape(*lead, P, 2, H // (2 * P), W)
    lam0 = lambda_init(spec.layer_id(li))
    lam = (
        jnp.exp(jnp.sum(lp["lambda_q1"].astype(f32) * lp["lambda_k1"]))
        - jnp.exp(jnp.sum(lp["lambda_q2"].astype(f32) * lp["lambda_k2"]))
        + lam0
    )
    o = a[..., 0, :, :] - lam * a[..., 1, :, :]
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + spec.rms_eps) * lp["subln"].astype(f32)
    return (o * (1.0 - lam0)).astype(attn.dtype).reshape(*lead, H // 2, W)


# -------------------------------------------- the layers that write no cache
# SambaY's cross-decoder (arXiv:2507.06607): the layers from
# ``ModelSpec.carried_from`` up mix what the layers below left for the SAME
# token (a GMU gates the memory layer's output) or read another layer's
# pages with queries of their own (``LayerKind.reads``), so they write
# nothing a later token needs and a prefill runs them for a sequence's
# last row alone.


def _gmu(lp: Params, h: jax.Array, m: jax.Array) -> jax.Array:
    """A Gated Memory Unit: ``W_2 (silu(W_1 h) * m)``, ``m`` the memory
    layer's output for the same rows."""
    with jax.named_scope(SCOPE_GMU):
        gate = jax.nn.silu((h @ lp["gmu_in"]).astype(jnp.float32))
        return (gate.astype(h.dtype) * m) @ lp["gmu_out"]


def _carried_layers(
    spec: ModelSpec, params: Params, x: jax.Array, mem: dict, k_pages,
    v_pages, cross, phase: int, counted: jax.Array, mesh: Mesh | None,
):
    """The layers from ``spec.carried_from`` up over the rows ``x`` [R, d]
    (a decode step's slots, a prefill's last row a sequence, every row of
    a whole-sequence pass), ONE body for every program. ``mem["m"]`` [R,
    C]: the memory layer's output for those rows. ``cross(li, q, k_pool,
    v_pool, lj) -> (attn, k_pool, v_pool)``: the program's attention of the
    rows' queries q [R, H, 2 D] (``_pair_rows``) over layer ``lj`` of the
    pools of the kind the layer reads. Returns (x, k_pages, v_pages)."""
    for li in range(spec.carried_from, spec.num_layers):
        lp, kd = params["layers"][li], spec.kind(li)
        h = _norm(spec, x, lp, "attn_norm")
        if kd.mixer == "gmu":
            mix = _gmu(lp, h, mem["m"])
        else:
            with jax.named_scope(SCOPE_QKV):
                q = (h @ lp["wq"] + lp["bq"]).reshape(
                    *h.shape[:-1], spec.num_heads, spec.head_dim)
                q, _, _ = _pair_rows(spec, li, q)
            ki, lj = kd.reads
            with jax.named_scope(SCOPE_KV):
                attn, kp, vp = cross(
                    li, q, kind_pages(spec, k_pages, ki),
                    kind_pages(spec, v_pages, ki), lj)
            if kp is not None:
                k_pages, v_pages = _put_kind(k_pages, v_pages, ki, kp, vp)
            mix = _o_proj(spec, lp, _diff_out(spec, li, lp, attn), h)
        x = _residual(spec, lp, x, mix, "post_attn_norm")
        h = _norm(spec, x, lp, "mlp_norm")
        f, k_pages = _ffn_counting(
            spec, li, lp, h, k_pages, phase, counted, mesh)
        x = _residual(spec, lp, x, f, "post_mlp_norm")
    return x, k_pages, v_pages


def _last_row_cross(spec: ModelSpec, block_tables, kv_len):
    """``_carried_layers``' ``cross`` of a prefill program: each
    sequence's ONE row, the last real one at ``kv_len - 1``, over the
    pages the layers below wrote in this call and before it, through the
    prefill walk. block_tables: [N, P]; kv_len: [N]."""
    def cross(li, q, k_pool, v_pool, lj):
        with jax.named_scope(SCOPE_ATTN_CROSS):
            attn = jax.vmap(
                lambda q_i, bt_i, n_i: paged_prefill_attention(
                    q_i[None], k_pool, v_pool, lj, bt_i,
                    jnp.maximum(n_i - 1, 0), n_i, **_row_dims(spec, li),
                )[0]
            )(q, block_tables, kv_len)
        return attn, None, None

    return cross


# what a recurrent mixer is called with, by ``LayerKind.mixer``: (prefill
# over [N, T, d] rows, decode step over [B, d] slots, a whole sequence),
# each ``(spec, kind, layer weights, ...)``
_RECURRENT = {
    "kda": (_kda_prefill, _kda_decode, _kda_whole),
    "ssd": (_ssd_prefill, _ssd_decode, _ssd_whole),
    "conv": (_conv_prefill, _conv_decode, _conv_whole),
    "scan": (_scan_prefill, _scan_decode, _scan_whole),
}


def _keep_memory(spec: ModelSpec, li: int, rec, mem: dict | None):
    """A recurrent mixer's output; a selective scan hands back (output,
    its output before the gate), of which the model's memory layer leaves
    the second in ``mem["m"]`` for the layers above."""
    if spec.kind(li).mixer != "scan":
        return rec
    rec, m = rec
    if li == spec.memory_layer:
        mem["m"] = m
    return rec


def _mixers(spec: ModelSpec, li: int, kp, vp, attend, recur, latent=None,
            mem: dict | None = None):
    """Layer ``li``'s token mixers over its normed input, ONE body for
    every kind: softmax attention over the kind's pages where it has KV
    heads (``attend(k_pool, v_pool) -> (out, k_pool, v_pool)``), latent
    attention over its one pool of latent rows where that is what its
    pages hold (``latent(pool) -> (out, pool)``: models/mla.py's layer),
    the recurrent mixer over its state rows where it has one (``recur(fn,
    kind, states, tails) -> (out, states, tails)``, ``fn`` the mixer's
    prefill and decode forms), their outputs summed where it has both. kp,
    vp: the kind's entries of the cache's two sides. A selective-scan
    mixer also hands back its output before the gate, which the model's
    memory layer leaves in ``mem["m"]`` for the layers above
    (``_carried_layers``). Returns (mix, kp, vp)."""
    kd = spec.kind(li)
    (k_pg, s_pool), (v_pg, c_pool) = _entry_parts(kd, kp), _entry_parts(kd, vp)
    mix = None
    if kd.latent:
        mix, k_pg = latent(k_pg)
    elif kd.paged:
        mix, k_pg, v_pg = attend(k_pg, v_pg)
    if kd.recurrent:
        rec, s_pool, c_pool = recur(_RECURRENT[kd.mixer], kd, s_pool, c_pool)
        rec = _keep_memory(spec, li, rec, mem)
        mix = rec if mix is None else mix + rec
    return mix, _entry_of(k_pg, s_pool), _entry_of(v_pg, c_pool)


def _state_owner(block_tables: jax.Array) -> jax.Array:
    """The id a sequence's state row is kept under: its first page."""
    return block_tables[..., 0].astype(jnp.int32)


# ---------------------------------------------------------------- prefill


def prefill_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad] int32 (padded)
    block_table: jax.Array,  # [max_pages_per_seq] int32
    start_pos: jax.Array,  # scalar: cached-prefix length (tokens)
    k_pages: jax.Array,  # [L, num_pages, kvh, page, D] (donated)
    v_pages: jax.Array,
    num_tokens: jax.Array,  # scalar: real token count in ``tokens``
    mesh: Mesh | None = None,  # static: replicate logits across the mesh
    mm_embeds: jax.Array | None = None,  # [M, d] multimodal embedding rows
    mm_pos: jax.Array | None = None,  # [M] window-relative positions (pad >= T)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Process one prompt; writes KV pages; returns (last_logits, k, v).

    Attention walks the paged context (cached prefix + newly written
    tokens) in blocks of pages (_ctx_attention), so prefix-cache hits skip
    recompute of cached tokens and its cost follows the prompt, not the
    table.
    ``mm_embeds``/``mm_pos``: encoder rows overwrite the placeholder
    tokens' embeddings (multimodal EPD injection — one masked scatter;
    padded positions >= T drop).
    """
    T = tokens.shape[0]
    page_size = page_size_of(k_pages)

    # Page-granular KV write: prefix-cache hits and chunk boundaries are
    # page-aligned (engine invariant), so the T new tokens start at a page
    # boundary and land as whole [page_size, D] tiles — one scatter over
    # T/page indices instead of T token rows (XLA lowers tile scatters an
    # order of magnitude faster on TPU; the trailing tile stays
    # contiguous). Garbage in a partial tail page sits beyond num_tokens:
    # masked in attention, overwritten as decode appends. Fully-padded
    # pages go to the trash page (duplicate trash indices are fine).
    n_pg = T // page_size
    with jax.named_scope(SCOPE_INDEX):
        idx = jnp.arange(T)
        positions = start_pos + idx  # absolute positions of new tokens
        page_starts = start_pos + jnp.arange(n_pg) * page_size
        pg_idx_raw = block_table[page_starts // page_size]
        safe_pg = jnp.where(
            page_starts < start_pos + num_tokens, pg_idx_raw, TRASH_PAGE
        )
        real = idx < num_tokens
        valid_tok = real.reshape(n_pg, page_size)

    x = _embed(params, tokens, spec)  # [T, d]
    if mm_embeds is not None:
        x = x.at[mm_pos].set(mm_embeds.astype(x.dtype), mode="drop")
    with jax.named_scope(SCOPE_INDEX):
        kv_len = start_pos + num_tokens
    if spec.has_recurrent:
        idx, fresh, rows = _claim_state_rows(
            k_pages.rows, _state_owner(block_table)[None], start_pos[None],
            (num_tokens > 0)[None],
        )
        k_pages = k_pages._replace(rows=rows)

    mem: dict = {}  # what the layers leave for those above (_mixers)
    for li, lp in enumerate(params["layers"][:spec.carried_from]):
        h = _norm(spec, x, lp, "attn_norm")
        kp, vp, lj = _layer_pools(spec, k_pages, v_pages, li)

        def attend(kp, vp, li=li, lp=lp, lj=lj, h=h):
            q, k, v = _pair_rows(
                spec, li, *_attn_qkv(spec, li, lp, h, positions))
            with jax.named_scope(SCOPE_KV):
                kp = _set_page_tiles(kp, lj, safe_pg, k, page_size, valid_tok)
                vp = _set_page_tiles(vp, lj, safe_pg, v, page_size, valid_tok)
                attn = _ctx_attention(
                    spec, li, lp, q, k, v, kp, vp, lj, block_table,
                    positions, kv_len,
                )
            return _o_proj(
                spec, lp, _diff_out(spec, li, lp, attn), h), kp, vp

        def recur(fn, kd, sp, cp, lp=lp, lj=lj, h=h):
            mix, sp, cp = fn[0](
                spec, kd, lp, h[None], sp, cp, lj, idx, fresh,
                num_tokens[None],
            )
            return jax.tree.map(lambda y: y[0], mix), sp, cp

        def latent(pool, lp=lp, lj=lj, h=h):
            from dynamo_tpu.models import mla

            mix, pool = mla.prefill_layer(
                spec, lj, lp, h[None], positions[None], pool, safe_pg,
                valid_tok, block_table[None], start_pos[None], kv_len[None],
                mesh,
            )
            return mix[0], pool

        mix, kp, vp = _mixers(spec, li, kp, vp, attend, recur, latent, mem)
        k_pages, v_pages = _put_pools(spec, k_pages, v_pages, li, kp, vp)
        x = _residual(spec, lp, x, mix, "post_attn_norm")
        h = _norm(spec, x, lp, "mlp_norm")
        f, k_pages = _ffn_counting(
            spec, li, lp, h, k_pages, COUNT_PREFILL, real, mesh
        )
        x = _residual(spec, lp, x, f, "post_mlp_norm")

    if spec.carried_from < spec.num_layers:
        # the layers that write no cache, for the last real row alone
        last = jnp.clip(num_tokens - 1, 0, T - 1)
        x_up, k_pages, v_pages = _carried_layers(
            spec, params, x[last][None], {"m": mem["m"][last][None]},
            k_pages, v_pages,
            _last_row_cross(spec, block_table[None], kv_len[None]),
            COUNT_PREFILL, (num_tokens > 0)[None], mesh,
        )
        logits = _logits(spec, params, x_up[0])
        return _replicate(logits, mesh), k_pages, v_pages, _no_drops(mesh)
    with jax.named_scope(SCOPE_HEAD):
        last = jnp.clip(num_tokens - 1, 0, T - 1)
        logits = _logits(spec, params, x[last])  # [V]
    logits = _replicate(logits, mesh)
    return logits, k_pages, v_pages, _no_drops(mesh)


def _no_recurrent(spec: ModelSpec, what: str) -> None:
    """Programs that have no recurrent form: a state cannot be split
    across sequence shards, nor rolled back past rejected drafts. The
    engine never reaches them for such a model (family.GqaFamily's
    ``supports_*``); a direct caller is told."""
    if spec.has_recurrent or spec.has_latent:
        raise NotImplementedError(
            f"{what}: no form for a model with recurrent or latent kinds"
        )


def _no_drops(mesh: Mesh | None) -> jax.Array:
    """The fourth value of every prefill-like program: once the count of
    expert assignments dropped for want of capacity. Nothing is dropped
    any more; callers that unpack four values get a constant zero."""
    return _replicate(jnp.zeros((), jnp.int32), mesh)


def _replicate(x: jax.Array, mesh: Mesh | None) -> jax.Array:
    """Pin an output to fully-replicated across the mesh. Sampling runs on
    the leader's host (multi-host) or outside the SPMD program, so every
    process must hold an addressable full copy — without the constraint
    GSPMD may leave e.g. tp-sharded logits that only exist shard-wise."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


prefill_forward = jax.jit(
    prefill_forward_impl, static_argnums=(0,), static_argnames=("mesh",),
    donate_argnums=(5, 6),
)


def prefill_forward_batch_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [N, T_pad] int32 (padded)
    block_tables: jax.Array,  # [N, max_pages_per_seq] int32
    start_pos: jax.Array,  # [N] cached-prefix lengths (page-aligned)
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    num_tokens: jax.Array,  # [N] real token counts
    mesh: Mesh | None = None,  # static
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """N prompts in ONE dispatch — the packed-prefill admission path.

    A queue of same-bucket prompts lands as one jit call instead of N:
    matmuls batch over [N, T, d] (the MXU sees N*T rows), the per-layer
    KV write is ONE page-tile scatter over all N*T/page pages, and
    attention runs per prompt over its own pages. This is what takes
    admission TTFT from O(N * dispatch) to O(dispatch): dispatch and
    host<->device round-trips dominate short prefills, especially when
    the host is far from the chip.

    Returns (last_logits [N, V], k_pages, v_pages, 0).
    """
    N, T = tokens.shape
    page_size = page_size_of(k_pages)
    n_pg = T // page_size
    with jax.named_scope(SCOPE_INDEX):
        idx = jnp.arange(T)
        positions = start_pos[:, None] + idx[None, :]  # [N, T]
        page_starts = start_pos[:, None] + (
            jnp.arange(n_pg) * page_size
        )[None, :]  # [N, n_pg]
        pg_idx_raw = jnp.take_along_axis(
            block_tables, page_starts // page_size, axis=1
        )
        valid_pg = page_starts < (start_pos + num_tokens)[:, None]
        safe_pg = jnp.where(
            valid_pg, pg_idx_raw, TRASH_PAGE).reshape(N * n_pg)
        real = idx[None, :] < num_tokens[:, None]  # [N, T]
        valid_tok = real.reshape(N * n_pg, page_size)

    x = _embed(params, tokens, spec)  # [N, T, d]
    with jax.named_scope(SCOPE_INDEX):
        kv_len = start_pos + num_tokens  # [N]
    if spec.has_recurrent:
        idx, fresh, rows = _claim_state_rows(
            k_pages.rows, _state_owner(block_tables), start_pos,
            num_tokens > 0,
        )
        k_pages = k_pages._replace(rows=rows)

    mem: dict = {}  # what the layers leave for those above (_mixers)
    for li, lp in enumerate(params["layers"][:spec.carried_from]):
        h = _norm(spec, x, lp, "attn_norm")
        kp, vp, lj = _layer_pools(spec, k_pages, v_pages, li)

        def attend(kp, vp, li=li, lp=lp, lj=lj, h=h):
            q, k, v = _pair_rows(
                spec, li, *_attn_qkv(spec, li, lp, h, positions))
            with jax.named_scope(SCOPE_KV):
                kp = _set_page_tiles(kp, lj, safe_pg, k, page_size, valid_tok)
                vp = _set_page_tiles(vp, lj, safe_pg, v, page_size, valid_tok)
                attn = jax.vmap(
                    lambda q_i, k_i, v_i, bt_i, pos_i, kvl_i, kp=kp, vp=vp,
                    li=li, lp=lp, lj=lj: _ctx_attention(
                        spec, li, lp, q_i, k_i, v_i, kp, vp, lj, bt_i, pos_i,
                        kvl_i,
                    )
                )(q, k, v, block_tables, positions, kv_len)
            return _o_proj(
                spec, lp, _diff_out(spec, li, lp, attn), h), kp, vp

        def recur(fn, kd, sp, cp, lp=lp, lj=lj, h=h):
            return fn[0](spec, kd, lp, h, sp, cp, lj, idx, fresh, num_tokens)

        def latent(pool, lp=lp, lj=lj, h=h):
            from dynamo_tpu.models import mla

            return mla.prefill_layer(
                spec, lj, lp, h, positions, pool, safe_pg, valid_tok,
                block_tables, start_pos, kv_len, mesh,
            )

        mix, kp, vp = _mixers(spec, li, kp, vp, attend, recur, latent, mem)
        k_pages, v_pages = _put_pools(spec, k_pages, v_pages, li, kp, vp)
        x = _residual(spec, lp, x, mix, "post_attn_norm")
        h = _norm(spec, x, lp, "mlp_norm")
        f, k_pages = _ffn_counting(
            spec, li, lp, h.reshape(N * T, -1), k_pages, COUNT_PREFILL,
            real.reshape(N * T), mesh,
        )
        x = _residual(
            spec, lp, x, f.reshape(N, T, -1), "post_mlp_norm")

    if spec.carried_from < spec.num_layers:
        # the layers that write no cache, for each sequence's last real row
        last = jnp.clip(num_tokens - 1, 0, T - 1)[:, None, None]
        x_up, k_pages, v_pages = _carried_layers(
            spec, params, jnp.take_along_axis(x, last, axis=1)[:, 0],
            {"m": jnp.take_along_axis(mem["m"], last, axis=1)[:, 0]},
            k_pages, v_pages, _last_row_cross(spec, block_tables, kv_len),
            COUNT_PREFILL, num_tokens > 0, mesh,
        )
        logits = _logits(spec, params, x_up)
        return _replicate(logits, mesh), k_pages, v_pages, _no_drops(mesh)
    with jax.named_scope(SCOPE_HEAD):
        last = jnp.clip(num_tokens - 1, 0, T - 1)  # [N]
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = _logits(spec, params, x_last)  # [N, V]
    logits = _replicate(logits, mesh)
    return logits, k_pages, v_pages, _no_drops(mesh)


prefill_forward_batch = jax.jit(
    prefill_forward_batch_impl, static_argnums=(0,),
    static_argnames=("mesh",), donate_argnums=(5, 6),
)


def prefill_forward_ring_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad] int32, T_pad divisible by mesh sp
    block_table: jax.Array,  # [max_pages_per_seq] int32
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    num_tokens: jax.Array,  # scalar: real token count
    mesh: Mesh,  # static
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Long-context prefill with sequence-parallel ring attention.

    Token activations shard over the "sp" mesh axis (sharding constraints
    guide GSPMD; only the attention itself is an explicit shard_map ring —
    see parallel/ring.py). No cached-prefix support: ring prefill serves
    cold ultra-long prompts; warm prefixes take the paged path. Padding at
    the tail is masked by causality (padded positions exceed every real
    query) and scatters to the trash page.
    """
    from dynamo_tpu.parallel.ring import ring_attention

    _no_recurrent(spec, "ring prefill")
    T = tokens.shape[0]
    idx = jnp.arange(T)
    page_size = page_size_of(k_pages)
    # page-granular tile writes (see prefill_forward_impl): ring prefill is
    # cold (start 0), so the prompt starts page-aligned by construction
    n_pg = T // page_size
    page_starts = jnp.arange(n_pg) * page_size
    pg_idx_raw = block_table[page_starts // page_size]
    safe_pg = jnp.where(page_starts < num_tokens, pg_idx_raw, TRASH_PAGE)
    valid_tok = (idx < num_tokens).reshape(n_pg, page_size)

    sp_spec = NamedSharding(mesh, P("sp", None))
    x = _embed(params, tokens, spec)
    x = jax.lax.with_sharding_constraint(x, sp_spec)

    for li, lp in enumerate(params["layers"]):
        h = _norm(spec, x, lp, "attn_norm")
        q, k, v = _attn_qkv(spec, li, lp, h, idx)
        kp, vp, lj = _layer_pools(spec, k_pages, v_pages, li)
        kp = _set_page_tiles(kp, lj, safe_pg, k, page_size, valid_tok)
        vp = _set_page_tiles(vp, lj, safe_pg, v, page_size, valid_tok)
        k_pages, v_pages = _put_pools(spec, k_pages, v_pages, li, kp, vp)
        attn = ring_attention(q, k, v, mesh=mesh)
        x = _residual(
            spec, lp, x, _o_proj(spec, lp, attn, h), "post_attn_norm")
        h = _norm(spec, x, lp, "mlp_norm")
        f = _ffn(spec, lp, h, mesh=mesh, li=li)
        x = _residual(spec, lp, x, f, "post_mlp_norm")
        x = jax.lax.with_sharding_constraint(x, sp_spec)

    last = jnp.clip(num_tokens - 1, 0, T - 1)
    logits = _logits(spec, params, x[last])
    logits = _replicate(logits, mesh)
    return logits, k_pages, v_pages, _no_drops(mesh)


prefill_forward_ring = jax.jit(
    prefill_forward_ring_impl,
    static_argnums=(0,),
    static_argnames=("mesh",),
    donate_argnums=(4, 5),
)


# ----------------------------------------------------------------- verify


def verify_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [N, W] int32: [fed_token, draft...] per row
    block_tables: jax.Array,  # [N, max_pages_per_seq]
    start_pos: jax.Array,  # [N]: cache length before the fed token
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    num_tokens: jax.Array,  # [N] valid tokens per row (0 = padded row)
    mesh: Mesh | None = None,  # static
    allowed: jax.Array | None = None,  # [N, W, V] bool: guided masks
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Speculative-verify forward: N slots' (fed token + k drafts) in
    ONE short-prefill dispatch, with the target's greedy choice at EVERY
    position (engine/core.py _spec_phase).

    Differs from prefill in exactly two ways. (1) KV writes are
    TOKEN-granular (write_new_kv — the decode-path scatter/DMA kernel):
    a verify starts wherever decode left off, mid-page, so the
    page-tile scatter's page-aligned-start invariant does not hold.
    (2) Logits are computed for all W positions and argmax'd ON DEVICE —
    the host needs only the [N, W] int32 target tokens to run
    accept-longest-prefix, not a [N, W, V] logits download.

    Rejected-draft KV rows are garbage beyond the accepted prefix: they
    sit past the slot's post-verify seq_len, masked from attention, and
    are overwritten by the next real write at that position (the
    engine's page rollback handles the allocator side).

    Returns (targets [N, W] int32, k_pages, v_pages, 0).
    """
    from dynamo_tpu.ops.pallas.kv_write import write_new_kv

    _no_recurrent(spec, "speculative verify")
    N, W = tokens.shape
    page_size = page_size_of(k_pages)
    idx = jnp.arange(W)
    positions = start_pos[:, None] + idx[None, :]  # [N, W]
    valid = idx[None, :] < num_tokens[:, None]
    pg_idx_raw = jnp.take_along_axis(
        block_tables, positions // page_size, axis=1
    )
    safe_pg2 = jnp.where(valid, pg_idx_raw, TRASH_PAGE)  # [N, W]
    offs2 = positions % page_size
    safe_pg = safe_pg2.reshape(N * W)
    offs = offs2.reshape(N * W)

    x = _embed(params, tokens, spec)  # [N, W, d]
    kv_len = start_pos + num_tokens  # [N]

    for li, lp in enumerate(params["layers"]):
        h = _norm(spec, x, lp, "attn_norm")
        q, k, v = _attn_qkv(spec, li, lp, h, positions)
        kp, vp, lj = _layer_pools(spec, k_pages, v_pages, li)
        if is_quant(kp):
            # quantized append is a page-granular RMW: a verify's W
            # tokens often share a page, so land them one POSITION at a
            # time (static W loop, distinct pages within each call) —
            # the one-scatter fast path would lose same-page siblings
            for w in range(W):
                kp, vp = write_new_kv(
                    kp, vp, k[:, w], v[:, w],
                    safe_pg2[:, w], offs2[:, w], layer=lj, mesh=mesh,
                )
        else:
            kp, vp = write_new_kv(
                kp, vp,
                k.reshape(N * W, *k.shape[2:]), v.reshape(N * W, *v.shape[2:]),
                safe_pg, offs, layer=lj, mesh=mesh,
            )
        # exact verify-window rows over a quantized read-back: the fed
        # token + drafts judge each other at full precision, like the
        # fused decode path's analytic merge (_ctx_attention)
        attn = jax.vmap(
            lambda q_i, k_i, v_i, bt_i, pos_i, kvl_i, kp=kp, vp=vp, li=li,
            lp=lp, lj=lj: _ctx_attention(
                spec, li, lp, q_i, k_i, v_i, kp, vp, lj, bt_i, pos_i, kvl_i,
            )
        )(q, k, v, block_tables, positions, kv_len)
        k_pages, v_pages = _put_pools(spec, k_pages, v_pages, li, kp, vp)
        x = _residual(
            spec, lp, x, _o_proj(spec, lp, attn, h), "post_attn_norm")
        h = _norm(spec, x, lp, "mlp_norm")
        x = _residual(spec, lp, x, _ffn(
            spec, lp, h.reshape(N * W, -1), mesh=mesh, li=li
        ).reshape(N, W, -1), "post_mlp_norm")

    logits = _logits(spec, params, x)  # [N, W, V]
    if allowed is not None:
        # guided decoding composes with speculation here: masking the
        # VERIFY logits per position means a rejected draft's correction
        # token is itself grammar-legal — conformance survives rejection
        logits = jnp.where(allowed, logits, -1e30)
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _replicate(targets, mesh), k_pages, v_pages, _no_drops(mesh)


verify_forward = jax.jit(
    verify_forward_impl, static_argnums=(0,), static_argnames=("mesh",),
    donate_argnums=(5, 6),
)


# ---------------------------------------------------------------- decode


def decode_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [B] int32: last sampled token per slot
    block_tables: jax.Array,  # [B, max_pages_per_seq]
    seq_lens: jax.Array,  # [B] length INCLUDING the new token
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    active: jax.Array,  # [B] bool: slot has a live request
    mesh: Mesh | None = None,  # static: routes attention through shard_map
    state_idx: jax.Array | None = None,  # [B]: the slots' state rows, where
    # the caller found them already (a burst finds them once)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for the whole slot batch; returns (logits[B,V], k, v)."""
    B = tokens.shape[0]
    if spec.has_recurrent and state_idx is None:
        state_idx, rows = _find_state_rows(
            k_pages.rows, _state_owner(block_tables), active
        )
        k_pages = k_pages._replace(rows=rows)
    page_size = page_size_of(k_pages)
    with jax.named_scope(SCOPE_INDEX):
        positions = seq_lens - 1  # position of the new token
        page_idx_raw = jnp.take_along_axis(
            block_tables, (positions // page_size)[:, None], axis=1
        )[:, 0]
        safe_page = jnp.where(active, page_idx_raw, TRASH_PAGE)
        offset = positions % page_size

    schedule = None
    if spec.has_latent:
        # the latent kernel's schedule follows the lengths alone: once a
        # step for the kind's layers (the kinds share one block table)
        from dynamo_tpu.ops.attention import latent_decode_schedule

        schedule = latent_decode_schedule(
            latent_pool(spec, k_pages), block_tables, seq_lens, mesh)
    x = _embed(params, tokens, spec)  # [B, d]
    mem: dict = {}  # what the layers leave for those above (_mixers)
    reads = {spec.kind(li).reads for li in range(spec.num_layers)} - {()}

    for li, lp in enumerate(params["layers"][:spec.carried_from]):
        h = _norm(spec, x, lp, "attn_norm")
        kp, vp, lj = _layer_pools(spec, k_pages, v_pages, li)

        def attend(kp, vp, li=li, lp=lp, lj=lj, h=h):
            q, k, v = _pair_rows(
                spec, li, *_attn_qkv(spec, li, lp, h, positions))
            if reads and spec.pool_slot(li) in reads:
                # the step's new rows of the layer whose pages the cross
                # layers read: the decode kernel scores the new token from
                # its rows, not from the page they land in
                mem["kv"] = (k, v)
            # KV append + paged attention in ONE kernel per layer on the
            # Pallas path (ops/pallas/fused_decode.py — halves the decode
            # program's kernel-launch count); scatter + gather attention
            # elsewhere (ops/attention.decode_update_attention dispatch)
            with jax.named_scope(SCOPE_KV):
                attn, kp, vp = decode_update_attention(
                    q, kp, vp, k, v, block_tables, seq_lens,
                    safe_page, offset, layer=lj, mesh=mesh,
                    window=spec.kind(li).window, sinks=lp.get("sinks"),
                    scope=attn_scope(spec, li),
                    scale=_pair_scale(spec, li),
                )
            return _o_proj(
                spec, lp, _diff_out(spec, li, lp, attn), h), kp, vp

        def recur(fn, kd, sp, cp, lp=lp, lj=lj, h=h):
            return fn[1](spec, kd, lp, h, sp, cp, lj, state_idx)

        def latent(pool, lp=lp, lj=lj, h=h):
            from dynamo_tpu.models import mla

            return mla.decode_layer(
                spec, lj, lp, h, positions, pool, block_tables, seq_lens,
                safe_page, offset, schedule, mesh,
            )

        mix, kp, vp = _mixers(spec, li, kp, vp, attend, recur, latent, mem)
        k_pages, v_pages = _put_pools(spec, k_pages, v_pages, li, kp, vp)
        x = _residual(spec, lp, x, mix, "post_attn_norm")
        h = _norm(spec, x, lp, "mlp_norm")
        f, k_pages = _ffn_counting(
            spec, li, lp, h, k_pages, COUNT_DECODE, active, mesh
        )
        x = _residual(spec, lp, x, f, "post_mlp_norm")

    if spec.carried_from < spec.num_layers:

        def cross(li, q, k_pool, v_pool, lj):
            # the read layer's decode kernel with NO write: a row bound
            # for the trash page is not written, and the step's own token
            # is scored from the rows that layer made (``mem["kv"]``)
            return decode_update_attention(
                q, k_pool, v_pool, *mem["kv"], block_tables, seq_lens,
                jnp.zeros_like(safe_page), offset, layer=lj, mesh=mesh,
                scope=SCOPE_ATTN_CROSS, scale=_pair_scale(spec, li),
            )

        x, k_pages, v_pages = _carried_layers(
            spec, params, x, mem, k_pages, v_pages, cross, COUNT_DECODE,
            active, mesh,
        )
    logits = _logits(spec, params, x)  # [B, V]
    return logits, k_pages, v_pages


decode_forward = jax.jit(
    decode_forward_impl, static_argnums=(0,), static_argnames=("mesh",),
    donate_argnums=(5, 6),
)


def decode_steps_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [B] last sampled token per slot
    block_tables: jax.Array,  # [B, max_pages_per_seq]
    seq_lens: jax.Array,  # [B] length INCLUDING the first new token
    k_pages: jax.Array,  # donated
    v_pages: jax.Array,
    active: jax.Array,  # [B] bool
    temperature: jax.Array,  # [B] f32
    top_k: jax.Array,  # [B] int32
    top_p: jax.Array,  # [B] f32
    seeds: jax.Array,  # [B] uint32
    steps: jax.Array,  # [B] int32: tokens generated so far per slot
    n_steps: int = 1,  # static: decode steps per dispatch
    n_logprobs: int = 0,  # static: 0=off, N=sampled+top-N logprobs
    mesh: Mesh | None = None,  # static
    allowed: jax.Array | None = None,  # [B, V] bool: guided token masks
):
    """``n_steps`` decode iterations + on-device sampling in ONE dispatch.

    Returns (sampled [B, n_steps], k_pages, v_pages) — plus, when
    ``n_logprobs`` > 0, (sampled_logprobs [B, n], top_ids [B, n, N],
    top_logprobs [B, n, N]) between sampled and the caches. Amortizes host
    dispatch and device-sync cost over n steps (the same reason vLLM grew
    multi-step scheduling): only small arrays cross to the host per
    dispatch. Callers must pre-extend block tables so every active slot
    has page room for n more tokens; EOS inside a burst is handled
    host-side by discarding the tail. Sampling keys fold in the per-slot
    generated-count so bursts reproduce the per-request RNG stream exactly
    (engine/sampling.py contract).

    ``allowed`` is the guided-decoding constraint mask: the host-side
    automaton only advances as sampled tokens LAND, so the engine
    dispatches masked bursts at n_steps=1 (the mask is per-position) —
    a batch with no constrained slot passes None and compiles/runs the
    unmasked program unchanged.
    """
    from dynamo_tpu.engine.sampling import sample_tokens, token_logprobs

    B = tokens.shape[0]
    out0 = jnp.zeros((B, n_steps), jnp.int32)
    lp0 = jnp.zeros((B, n_steps), jnp.float32)
    ti0 = jnp.zeros((B, n_steps, max(n_logprobs, 1)), jnp.int32)
    tv0 = jnp.zeros((B, n_steps, max(n_logprobs, 1)), jnp.float32)
    state_idx = None
    if spec.has_recurrent:
        # a burst's tables do not change: its slots' state rows are found
        # (and touched) once
        state_idx, rows = _find_state_rows(
            k_pages.rows, _state_owner(block_tables), active
        )
        k_pages = k_pages._replace(rows=rows)

    def body(i, carry):
        toks, lens, kp, vp, out, lp, ti, tv = carry
        logits, kp, vp = decode_forward_impl(
            spec, params, toks, block_tables, lens, kp, vp, active, mesh=mesh,
            state_idx=state_idx,
        )
        # the carry between steps is the burst's own region; the sampler's
        # functions open theirs beneath it (the innermost name is the region)
        with jax.named_scope(SCOPE_BURST):
            if allowed is not None:
                with jax.named_scope(SCOPE_SAMPLER):
                    logits = jnp.where(allowed, logits, -1e30)
            nxt = sample_tokens(
                logits, temperature, top_k, top_p, seeds, steps + i
            )
            nxt = jnp.where(active, nxt, toks)
            out = out.at[:, i].set(nxt)
            if n_logprobs > 0:
                picked, top_i, top_v = token_logprobs(logits, nxt, n_logprobs)
                lp = lp.at[:, i].set(picked)
                ti = ti.at[:, i].set(top_i)
                tv = tv.at[:, i].set(top_v)
            lens = lens + active.astype(jnp.int32)
        return nxt, lens, kp, vp, out, lp, ti, tv

    _toks, _lens, k_pages, v_pages, out, lp, ti, tv = jax.lax.fori_loop(
        0, n_steps, body,
        (tokens, seq_lens, k_pages, v_pages, out0, lp0, ti0, tv0),
        unroll=False,
    )
    out = _replicate(out, mesh)
    if n_logprobs > 0:
        return (out, _replicate(lp, mesh), _replicate(ti, mesh),
                _replicate(tv, mesh), k_pages, v_pages)
    return out, k_pages, v_pages


decode_steps = jax.jit(
    decode_steps_impl,
    static_argnums=(0,),
    static_argnames=("n_steps", "n_logprobs", "mesh"),
    donate_argnums=(5, 6),
)


# ------------------------------------------------------- kv page movement


def _extract_kv_pages_impl(k_pages, v_pages, page_ids):
    """Gather whole pages for transfer: -> [L, n, kvh, page, D] x2 (a
    tuple of such blocks, a layer kind each, from ``KindPools``).

    QuantPool pools pack fp8 values + bf16 scales into ONE uint8 payload
    per (layer, page) (ops/quant.pack_pages): KVBM tiers and the disagg
    wire then carry exactly those bytes — half the footprint, no silent
    upcast possible, and onboard re-materializes fp8 by bitcast."""
    if is_quant(k_pages):
        return pack_pages(k_pages, page_ids), pack_pages(v_pages, page_ids)
    if isinstance(k_pages, KindPools):
        return (tuple(p[:, page_ids] for p in k_pages.pools),
                tuple(p[:, page_ids] for p in v_pages.pools))
    return k_pages[:, page_ids], v_pages[:, page_ids]


# dynalint: disable=DL012 -- read-only gather: the live pools must
# survive the call (the extracted pages ship over the disagg wire while
# the source engine keeps serving from the same pools)
extract_kv_pages = jax.jit(_extract_kv_pages_impl)


def _put_pages(pool: jax.Array, page_ids, blocks) -> jax.Array:
    """``blocks`` on pages ``page_ids`` of ``pool``. Pages travel as
    opaque blocks in the sender's layout (``init_cache``: plain, padded
    or packed rows), so a block that is not a page of THIS pool is
    refused by its shape: the scatter's broadcasting would lay one
    packed head over two padded ones without a word."""
    want = (pool.shape[0], page_ids.shape[0], *pool.shape[2:])
    if tuple(blocks.shape) != want:
        raise ValueError(
            f"KV page blocks {tuple(blocks.shape)} are not pages of this "
            f"pool ({want}): the two sides lay their pools out "
            "differently (backend, DYNAMO_PALLAS, tp or model differ)"
        )
    return pool.at[:, page_ids].set(blocks)


def _insert_kv_pages_impl(k_pages, v_pages, page_ids, k_blocks, v_blocks):
    """Scatter transferred pages into the local pools (donated).
    Blocks are page-major stacks [L, n, kvh, page, D] in the pool's own
    layout (``_put_pages`` refuses another) — or packed uint8
    [L, n, X] payloads when the pool is quantized (both engines of a
    disagg pair must run the same kv_dtype)."""
    if is_quant(k_pages):
        kv_, ks_ = unpack_pages(
            k_blocks, k_pages.vals.shape[2:], k_pages.scale.shape[2:]
        )
        vv_, vs_ = unpack_pages(
            v_blocks, v_pages.vals.shape[2:], v_pages.scale.shape[2:]
        )
        return (
            QuantPool(
                k_pages.vals.at[:, page_ids].set(kv_),
                k_pages.scale.at[:, page_ids].set(ks_),
            ),
            QuantPool(
                v_pages.vals.at[:, page_ids].set(vv_),
                v_pages.scale.at[:, page_ids].set(vs_),
            ),
        )
    if isinstance(k_pages, KindPools):
        return tuple(
            side._replace(pools=tuple(
                _put_pages(p, page_ids, b) for p, b in zip(side.pools, blocks)
            ))
            for side, blocks in ((k_pages, k_blocks), (v_pages, v_blocks))
        )
    return (
        _put_pages(k_pages, page_ids, k_blocks),
        _put_pages(v_pages, page_ids, v_blocks),
    )


insert_kv_pages = jax.jit(_insert_kv_pages_impl, donate_argnums=(0, 1))


# ------------------------------------------------------------- embeddings


def _whole_mixer(spec: ModelSpec, li: int, lp: Params, h, positions, n,
                 mem: dict | None = None):
    """Layer ``li``'s mixer over one whole sequence with no cache (h: [T,
    d]; ``n`` real tokens): plain causal attention, a recurrent mixer
    from an empty state, or both summed, as the kind has them (a padded
    tail cannot reach a real token either way). ``mem``: what the layers
    leave for those above, as ``_mixers`` keeps it, with the keys and
    values of a layer that others read."""
    kd = spec.kind(li)
    mix = None
    if kd.latent:
        from dynamo_tpu.models import mla

        mix = mla.whole_layer(
            spec, lp, h, positions,
            (positions[:, None] >= positions[None, :])
            & (positions[None, :] < n),
        )
    elif kd.paged:
        q, k, v = _pair_rows(
            spec, li, *_attn_qkv(spec, li, lp, h, positions))
        if mem is not None:
            mem[spec.pool_slot(li)] = (k, v)
        attn = causal_attention(
            q, k, v, positions, n, window=kd.window, sinks=lp.get("sinks"),
            scale=_pair_scale(spec, li),
        )
        mix = _o_proj(spec, lp, _diff_out(spec, li, lp, attn), h)
    if kd.recurrent:
        rec = _keep_memory(
            spec, li, _RECURRENT[kd.mixer][2](spec, kd, lp, h), mem)
        mix = rec if mix is None else mix + rec
    return mix


def _whole_layers(spec: ModelSpec, params: Params, x, positions, n):
    """Every layer over one whole sequence with no cache, EVERY row
    through every layer (embeddings, ``reference_forward``). x: [T, d]."""
    mem = {} if spec.carried_from < spec.num_layers else None
    for li, lp in enumerate(params["layers"][:spec.carried_from]):
        h = _norm(spec, x, lp, "attn_norm")
        mix = _whole_mixer(spec, li, lp, h, positions, n, mem)
        x = _residual(spec, lp, x, mix, "post_attn_norm")
        h = _norm(spec, x, lp, "mlp_norm")
        x = _residual(spec, lp, x, _ffn(spec, lp, h, li=li), "post_mlp_norm")
    if mem is not None:

        def cross(li, q, k_pool, v_pool, lj):
            k, v = mem[spec.kind(li).reads]
            return causal_attention(
                q, k, v, positions, n, scale=_pair_scale(spec, li),
            ), None, None

        sides = KindPools((None,) * len(spec.layer_kinds), jnp.zeros((0,)))
        x, _, _ = _carried_layers(
            spec, params, x, mem, sides, sides, cross, COUNT_PREFILL,
            positions < n, None,
        )
    return x


def embed_forward_impl(
    spec: ModelSpec,
    params: Params,
    tokens: jax.Array,  # [T_pad] int32 (padded)
    num_tokens: jax.Array,  # scalar: real token count
) -> jax.Array:
    """Sequence embedding: mean-pool the final-norm hidden states over the
    real tokens, L2-normalized — the serving surface behind /v1/embeddings
    (ref: the embeddings path of the HTTP service, http/service/openai.rs
    /v1/embeddings; engine side delegated in the reference, native here).
    Returns [hidden_size] float32."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = _embed(params, tokens, spec)
    x = _whole_layers(spec, params, x, positions, num_tokens)
    xn = _any_norm(spec, x, params, "final_norm").astype(jnp.float32)
    mask = (positions < num_tokens)[:, None].astype(jnp.float32)
    pooled = (xn * mask).sum(axis=0) / jnp.maximum(mask.sum(), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-9)


embed_forward = jax.jit(embed_forward_impl, static_argnums=(0,))


# -------------------------------------------------------------- reference


def reference_forward(
    spec: ModelSpec, params: Params, tokens: jax.Array
) -> jax.Array:
    """Plain full-attention forward (no paging) - numerical ground truth for
    tests. tokens: [T] -> logits [T, V]."""
    T = tokens.shape[0]
    positions = jnp.arange(T)
    x = _embed(params, tokens, spec)
    x = _whole_layers(spec, params, x, positions, jnp.asarray(T))
    xn = _any_norm(spec, x, params, "final_norm")
    head = params["embed"].T if spec.tie_embeddings else params["lm_head"]
    return _times((xn @ head).astype(jnp.float32), spec.lm_head_multiplier)
