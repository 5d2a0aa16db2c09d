"""Pipeline parallelism: layer stages over the "pp" mesh axis.

The reference expresses PP as engine configuration
(components/backends/trtllm/engine_configs/deepseek_r1/wide_ep/
wide_ep_decode.yaml:25 ``pipeline_parallel_size``) and delegates the
mechanics to TRT-LLM. Here the engine is ours, so PP is built
TPU-natively: parameters and the paged KV cache are layer-partitioned
across the "pp" axis, and a step is a GPipe-style software pipeline
inside ONE ``shard_map`` — activations hop stage-to-stage with
``lax.ppermute`` over ICI while every stage computes a different
microbatch, so the chips stay busy outside the fill/drain bubbles.

Layout:
- ``stack_params`` restacks the per-layer param dicts into leaves with a
  leading layer axis ``[L, ...]``, sharded ``P("pp", ...)`` — each stage
  holds ``L / pp`` layers. Embedding / final norm / lm_head replicate
  across pp; lm_head column-shards over tp.
- The KV cache keeps its usual ``[L, pages, KH, page, D]`` layout,
  sharded ``P("pp", None, "tp", ...)``: a stage owns its layers' pages.
- TP composes INSIDE the stage body (shard_map exposes per-device
  shards, so Megatron TP is explicit here: column-parallel projections,
  ``psum`` over "tp" after attention-out and MLP-down). dp composes by
  sharding the batch. MoE layers are not yet expressible under pp
  (dense path only) — wide-EP decode runs pp=1 with ep/tp instead.

Scheduling (decode): the slot batch splits into ``pp`` microbatches;
at tick t stage s processes microbatch t-s. Invalid (bubble) ticks
compute on garbage and write their KV rows to the trash page, exactly
like padded slots in the non-pp path — no control flow, fixed shapes.
A full step takes 2*pp-1 ticks; per-stage work is 1/pp of the model, so
decode latency ~doubles at the bubble-heavy extreme while throughput
scales with the extra chips — PP here is a memory-capacity axis (fit
bigger models), not a latency axis, same trade the reference's configs
make.

Prefill runs the same pipeline with ONE microbatch (the whole prompt):
pure fill/drain, acceptable because prefill is compute-dense per stage.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.models.llama import TRASH_PAGE, rms_norm, rope
from dynamo_tpu.ops.attention import (
    causal_attention,
    page_tiles,
    paged_decode_attention_auto,
)
from dynamo_tpu.ops.pallas.kv_write import write_new_kv

Params = dict


# ---------------------------------------------------------------- params


def stack_params(spec: ModelSpec, params: Params) -> Params:
    """Per-layer dicts -> stacked leaves [L, ...] (pp-shardable)."""
    if spec.num_experts:
        raise NotImplementedError(
            "pipeline parallelism currently covers dense layers only; "
            "run MoE models with ep/tp (wide-EP) instead"
        )
    layers = params["layers"]
    stacked = {
        key: jnp.stack([lp[key] for lp in layers]) for key in layers[0]
    }
    out = {k: v for k, v in params.items() if k != "layers"}
    out["layers"] = stacked
    return out


def pp_param_shardings(spec: ModelSpec, mesh: Mesh) -> Params:
    def ns(*axes):
        return NamedSharding(mesh, P(*axes))

    layers = {
        "attn_norm": ns("pp", None),
        "wq": ns("pp", None, "tp"),
        "wk": ns("pp", None, "tp"),
        "wv": ns("pp", None, "tp"),
        "wo": ns("pp", "tp", None),
        "mlp_norm": ns("pp", None),
        "w_gate": ns("pp", None, "tp"),
        "w_up": ns("pp", None, "tp"),
        "w_down": ns("pp", "tp", None),
    }
    out = {"embed": ns(), "final_norm": ns(), "layers": layers}
    if not spec.tie_embeddings:
        out["lm_head"] = ns(None, "tp")
    return out


def pp_cache_shardings(mesh: Mesh) -> tuple[NamedSharding, NamedSharding]:
    """[L, pages, KH, page, D]: layers over pp, kv heads over tp."""
    s = NamedSharding(mesh, P("pp", None, "tp", None, None))
    return s, s


# ------------------------------------------------------------- stage body


def _stage_decode(
    spec: ModelSpec,
    lp,  # stacked local leaves [L_local, ...]
    x: jax.Array,  # [Bm, d] (microbatch activations)
    positions: jax.Array,  # [Bm]
    k_pages,  # local [L_local, pages, KH_local, page, D]
    v_pages,
    block_tables: jax.Array,  # [Bm, P]
    seq_lens: jax.Array,  # [Bm]
    dst_page: jax.Array,  # [Bm] (already trash-masked for bubbles)
    dst_off: jax.Array,  # [Bm]
    n_local: int,
    tp_size: int,
    dp_size: int,
):
    """One pipeline stage's layers over one microbatch (manual Megatron
    TP: projections are column-local, outputs psum over "tp").

    The page pool replicates over dp while slots are dp-sharded, so every
    dp replica must apply EVERY replica's KV-row writes (the slot groups'
    pages are disjoint): new rows are tiny, so an all-gather over "dp"
    before the write keeps the replicated pool bit-identical — the manual
    form of what GSPMD inserts for scatters onto replicated operands."""
    Bm = x.shape[0]
    hd = spec.head_dim
    for i in range(n_local):
        h = rms_norm(x, lp["attn_norm"][i], spec.rms_eps)
        q = (h @ lp["wq"][i]).reshape(Bm, -1, hd)
        k = (h @ lp["wk"][i]).reshape(Bm, -1, hd)
        v = (h @ lp["wv"][i]).reshape(Bm, -1, hd)
        q = rope(q, positions, spec.rope_theta)
        k = rope(k, positions, spec.rope_theta)
        k_w, v_w, page_w, off_w = k, v, dst_page, dst_off
        if dp_size > 1:
            k_w = jax.lax.all_gather(k, "dp", axis=0, tiled=True)
            v_w = jax.lax.all_gather(v, "dp", axis=0, tiled=True)
            page_w = jax.lax.all_gather(dst_page, "dp", axis=0, tiled=True)
            off_w = jax.lax.all_gather(dst_off, "dp", axis=0, tiled=True)
        k_pages, v_pages = write_new_kv(
            k_pages, v_pages, k_w, v_w, page_w, off_w, layer=i, mesh=None
        )
        attn = paged_decode_attention_auto(
            q, k_pages[i], v_pages[i], block_tables, seq_lens, mesh=None
        )
        o = attn.reshape(Bm, -1) @ lp["wo"][i]
        if tp_size > 1:
            o = jax.lax.psum(o, "tp")
        x = x + o
        h = rms_norm(x, lp["mlp_norm"][i], spec.rms_eps)
        m = (jax.nn.silu(h @ lp["w_gate"][i]) * (h @ lp["w_up"][i])) @ lp[
            "w_down"
        ][i]
        if tp_size > 1:
            m = jax.lax.psum(m, "tp")
        x = x + m
    return x, k_pages, v_pages


def _stage_prefill(
    spec: ModelSpec,
    lp,
    x: jax.Array,  # [T, d]
    positions: jax.Array,  # [T]
    k_pages,
    v_pages,
    safe_pg: jax.Array,  # [n_pg] (trash-masked for bubbles)
    num_tokens: jax.Array,
    n_local: int,
    tp_size: int,
    page_size: int,
):
    """One stage's layers over the whole (cold) prompt: causal
    self-attention, page-tile KV writes — the pp form of
    models/llama.py prefill_forward_impl."""
    T = x.shape[0]
    hd = spec.head_dim

    def to_tiles(arr):  # pads to the pool width when lane-padded
        return page_tiles(arr, page_size, k_pages.shape[-1])

    for i in range(n_local):
        h = rms_norm(x, lp["attn_norm"][i], spec.rms_eps)
        q = (h @ lp["wq"][i]).reshape(T, -1, hd)
        k = (h @ lp["wk"][i]).reshape(T, -1, hd)
        v = (h @ lp["wv"][i]).reshape(T, -1, hd)
        q = rope(q, positions, spec.rope_theta)
        k = rope(k, positions, spec.rope_theta)
        k_pages = k_pages.at[i, safe_pg].set(to_tiles(k))
        v_pages = v_pages.at[i, safe_pg].set(to_tiles(v))
        attn = causal_attention(q, k, v, positions, num_tokens)
        o = attn.reshape(T, -1) @ lp["wo"][i]
        if tp_size > 1:
            o = jax.lax.psum(o, "tp")
        x = x + o
        h = rms_norm(x, lp["mlp_norm"][i], spec.rms_eps)
        m = (jax.nn.silu(h @ lp["w_gate"][i]) * (h @ lp["w_up"][i])) @ lp[
            "w_down"
        ][i]
        if tp_size > 1:
            m = jax.lax.psum(m, "tp")
        x = x + m
    return x, k_pages, v_pages


def _logits_local(spec: ModelSpec, pp_params, x, tp_size: int):
    """Final norm + lm head; head column-sharded over tp -> all-gather."""
    xn = rms_norm(x, pp_params["final_norm"], spec.rms_eps)
    head = (
        pp_params["embed"].T
        if spec.tie_embeddings
        else pp_params["lm_head"]
    )
    lg = (xn @ head).astype(jnp.float32)
    if tp_size > 1 and not spec.tie_embeddings:
        lg = jax.lax.all_gather(lg, "tp", axis=lg.ndim - 1, tiled=True)
    return lg


# ------------------------------------------------------------ pp decode


@partial(jax.jit, static_argnames=("spec", "mesh"), donate_argnums=(5, 6))
def pp_decode_step(
    spec: ModelSpec,
    pp_params: Params,
    tokens: jax.Array,  # [B] int32
    block_tables: jax.Array,  # [B, P]
    seq_lens: jax.Array,  # [B] incl. the new token
    k_pages,  # [L, pages, KH, page, D] pp/tp-sharded
    v_pages,
    active: jax.Array,  # [B] bool
    *,
    mesh: Mesh,
):
    """One decode step for the whole batch, pipelined over pp stages.

    Returns (logits [B, V], k_pages, v_pages). The batch divides into pp
    microbatches; bubbles write to the trash page.
    """
    S = mesh.shape["pp"]
    tp_size = mesh.shape["tp"]
    dp_size = mesh.shape["dp"]
    B = tokens.shape[0]
    if (B // dp_size) % S:
        raise ValueError(f"batch {B}/dp={dp_size} must divide pp={S}")
    if spec.num_layers % S:
        raise ValueError(f"layers {spec.num_layers} must divide pp={S}")
    n_local = spec.num_layers // S
    page_size = k_pages.shape[3]

    def body(emb, positions, block_tables, seq_lens, dst_page, dst_off,
             lp, fnorm, head, k_l, v_l):
        s = jax.lax.axis_index("pp")
        Bl = emb.shape[0]
        mb = Bl // S
        # [S, mb, ...] microbatch views
        embs = emb.reshape(S, mb, -1)
        pos_m = positions.reshape(S, mb)
        bt_m = block_tables.reshape(S, mb, -1)
        len_m = seq_lens.reshape(S, mb)
        pg_m = dst_page.reshape(S, mb)
        off_m = dst_off.reshape(S, mb)

        state = jnp.zeros_like(embs[0])
        outs = jnp.zeros((S, mb, embs.shape[-1]), embs.dtype)
        perm = [(i, (i + 1) % S) for i in range(S)]
        for t in range(2 * S - 1):  # static unroll; S is small
            j = t - s  # this stage's microbatch index at tick t
            jc = jnp.clip(j, 0, S - 1)
            valid = (j >= 0) & (j < S)
            x_in = jnp.where((s == 0) & (t < S), embs[jnp.clip(t, 0, S - 1)],
                             state)
            x_out, k_l, v_l = _stage_decode(
                spec, lp, x_in, pos_m[jc], k_l, v_l, bt_m[jc], len_m[jc],
                jnp.where(valid, pg_m[jc], TRASH_PAGE), off_m[jc],
                n_local, tp_size, dp_size,
            )
            done = (s == S - 1) & valid
            outs = outs.at[jc].set(
                jnp.where(done, x_out, outs[jc])
            )
            state = jax.lax.ppermute(x_out, "pp", perm)
        # final activations live on the last stage: broadcast over pp
        outs = jax.lax.psum(
            jnp.where(s == S - 1, outs, jnp.zeros_like(outs)), "pp"
        )
        x = outs.reshape(Bl, -1)
        lg = _logits_local(spec, {"final_norm": fnorm, "embed": head,
                                  "lm_head": head}, x, tp_size)
        return lg, k_l, v_l

    positions = seq_lens - 1
    page_idx = jnp.take_along_axis(
        block_tables, (positions // page_size)[:, None], axis=1
    )[:, 0]
    dst_page = jnp.where(active, page_idx, TRASH_PAGE)
    dst_off = positions % page_size
    emb = pp_params["embed"][tokens]
    head = (
        pp_params["embed"] if spec.tie_embeddings else pp_params["lm_head"]
    )

    shard = jax.shard_map(
        partial(body),
        mesh=mesh,
        in_specs=(
            P("dp", None),  # emb
            P("dp"),  # positions
            P("dp", None),  # block_tables
            P("dp"),  # seq_lens
            P("dp"),  # dst_page
            P("dp"),  # dst_off
            {  # stacked layers: pp x tp
                "attn_norm": P("pp", None),
                "wq": P("pp", None, "tp"),
                "wk": P("pp", None, "tp"),
                "wv": P("pp", None, "tp"),
                "wo": P("pp", "tp", None),
                "mlp_norm": P("pp", None),
                "w_gate": P("pp", None, "tp"),
                "w_up": P("pp", None, "tp"),
                "w_down": P("pp", "tp", None),
            },
            P(None),  # final_norm
            P(None, "tp") if not spec.tie_embeddings else P(None, None),
            P("pp", None, "tp", None, None),  # k_pages
            P("pp", None, "tp", None, None),
        ),
        out_specs=(
            P("dp", None),  # logits (replicated over pp/tp post-gather)
            P("pp", None, "tp", None, None),
            P("pp", None, "tp", None, None),
        ),
        check_vma=False,
    )
    logits, k_pages, v_pages = shard(
        emb, positions, block_tables, seq_lens, dst_page, dst_off,
        pp_params["layers"], pp_params["final_norm"], head,
        k_pages, v_pages,
    )
    return logits, k_pages, v_pages


# ------------------------------------------------------------ pp prefill


@partial(jax.jit, static_argnames=("spec", "mesh"), donate_argnums=(4, 5))
def pp_prefill(
    spec: ModelSpec,
    pp_params: Params,
    tokens: jax.Array,  # [T] int32 (page-aligned length)
    block_table: jax.Array,  # [max_pages_per_seq]
    k_pages,
    v_pages,
    num_tokens: jax.Array,  # scalar
    *,
    mesh: Mesh,
):
    """Cold-prompt prefill through the pp pipeline (one microbatch: pure
    fill/drain). Returns (last-token logits [V], k_pages, v_pages)."""
    S = mesh.shape["pp"]
    tp_size = mesh.shape["tp"]
    n_local = spec.num_layers // S
    T = tokens.shape[0]
    page_size = k_pages.shape[3]
    n_pg = T // page_size
    page_starts = jnp.arange(n_pg) * page_size
    pg_idx = block_table[page_starts // page_size]
    base_pg = jnp.where(page_starts < num_tokens, pg_idx, TRASH_PAGE)

    emb = pp_params["embed"][tokens]
    head = (
        pp_params["embed"] if spec.tie_embeddings else pp_params["lm_head"]
    )

    def body(emb, base_pg, num_tokens, lp, fnorm, head, k_l, v_l):
        s = jax.lax.axis_index("pp")
        positions = jnp.arange(T)
        state = jnp.zeros_like(emb)
        out = jnp.zeros_like(emb)
        perm = [(i, (i + 1) % S) for i in range(S)]
        for t in range(S):
            valid = t == s
            x_in = jnp.where((s == 0) & (t == 0), emb, state)
            x_out, k_l, v_l = _stage_prefill(
                spec, lp, x_in, positions, k_l, v_l,
                jnp.where(valid, base_pg, TRASH_PAGE), num_tokens,
                n_local, tp_size, page_size,
            )
            out = jnp.where((s == S - 1) & (t == S - 1), x_out, out)
            state = jax.lax.ppermute(x_out, "pp", perm)
        out = jax.lax.psum(
            jnp.where(s == S - 1, out, jnp.zeros_like(out)), "pp"
        )
        last = jnp.clip(num_tokens - 1, 0, T - 1)
        lg = _logits_local(spec, {"final_norm": fnorm, "embed": head,
                                  "lm_head": head}, out[last], tp_size)
        return lg, k_l, v_l

    layer_specs = {
        "attn_norm": P("pp", None),
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
        "mlp_norm": P("pp", None),
        "w_gate": P("pp", None, "tp"),
        "w_up": P("pp", None, "tp"),
        "w_down": P("pp", "tp", None),
    }
    shard = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(), P(), P(), layer_specs, P(),
            P(None, "tp") if not spec.tie_embeddings else P(None, None),
            P("pp", None, "tp", None, None),
            P("pp", None, "tp", None, None),
        ),
        out_specs=(
            P(),
            P("pp", None, "tp", None, None),
            P("pp", None, "tp", None, None),
        ),
        check_vma=False,
    )
    return shard(
        emb, base_pg, num_tokens, pp_params["layers"],
        pp_params["final_norm"], head, k_pages, v_pages,
    )
