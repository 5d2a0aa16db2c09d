"""Ling-3.0-flash at toy widths on the CPU, against the benchmark's own
plain reference (``perfbench/references/linear_latent_moe.py``, loaded by
path: the same module the chip is held to, not a copy): KDA layers (full
rank, the bounded decay) over state rows AND a latent (MLA) layer gated by
head over latent pages in ONE model, one block table and one state
directory, under group-limited sigmoid routing with a clamp a layer.
Programs, kernels (interpreted) on a kind's pool, the share of an ep
deployment, and the engine around a sequence that owns both.
"""

import dataclasses

import jax
import jax.numpy as jnp
import kda_step_cases
import numpy as np
import pytest
from family_contract import (
    Family, _cache, _prefill, _programs, _table, _whole, cases, run,
)


from dynamo_tpu.engine.config import EngineConfig, LayerKind, ModelSpec
from dynamo_tpu.models import llama
from dynamo_tpu.models.family import GqaFamily, get_family
from dynamo_tpu.ops import attention as attn_ops

# the reference reads the published keys; the program reads SPEC. Three
# layers: a dense KDA layer, an expert KDA layer, an expert MLA layer
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "num_hidden_layers": 3, "layers_kept": [0, 1, 2],
    "layer_group_size": 3, "first_k_dense_replace": 1,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 6000000,
    "rotary_dim": 8, "short_conv_kernel_size": 4,
    "no_kda_lora": True, "use_kda_lora": False, "kda_safe_gate": True,
    "kda_lower_bound": -5,
    "gated_attention_proj_granularity_type": "head_wise",
    "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
    "num_experts": 4, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "expert_swiglu_limit_list": [0, 0.5, 0.75],
    "share_expert_swiglu_limit_list": [0, 0.6, 0.4],
    "rms_norm_eps": 1e-6, "vocab_size": 96, "torch_dtype": "float32",
    "experts": {"published": 16, "held": 4, "first": 4},
}
SPEC = ModelSpec.tiny_ling3(held_experts=(4, 4))
LATENT, KDA_KIND = 0, 1  # the kinds' places in SPEC.layer_kinds
PAGE, PAGES_PER_SEQ, T, ROWS = 4, 16, 40, 3
SEED = 13


def _served(engine, snap, served, outs):
    """Two prompts of 16 + 5 tokens: a block of 64 holds each chunk, the
    second chunk of each resumes a row; the latent walk ran four times;
    the counters by kind of cache."""
    assert engine._prefill_walks == {"latent": 0}
    assert engine._kv_chunk_pages is not None
    assert engine.kda["prefill_blocks"] == 4
    assert engine.kda["rows_resumed"] == 2
    assert engine.kda["decode_rows"] % 4 == 0 and engine.kda["decode_rows"] >= 16
    assert engine.prefill_kv["dispatches.latent"] == 4
    assert engine.prefill_kv["kernel_calls.latent"] == 4
    assert engine.prefill_kv["blocks_visited.latent"] >= 4
    assert engine.decode_kv["pages_live"] > 0
    assert snap["kda.rows_resumed"]["calls"] == 2
    assert snap["prefill_kv.blocks_visited.latent"]["calls"] >= 4
    m = engine.moe_counters()
    # two expert layers, 2 x 21 prompt tokens, top-4 of 16 in 2 of 4 groups
    assert m["layers"] == 2 and m["prefill.assignments"] == 2 * 2 * 21 * 4
    assert 0 < m["prefill.assignments_held"] < m["prefill.assignments"]
    assert m["prefill.assignments_held"] == sum(
        m[f"prefill.expert.{i}"] for i in range(4))


# the family's row of the contract (tests/family_contract.py); its packs
# end in a pack of two RESUMED chunks of 16-token starts
F = FAMILY = Family(
    spec=SPEC, config=CONFIG,
    reference="linear_latent_moe", seed=SEED, state_rows=ROWS,
    chunked_paths=("0", "1"), pack_path="1",
    packs=([(0, 0, 13), (0, 0, 0)], [(1, 0, 16), (2, 0, 16)],
           [(1, 16, 9), (2, 16, 16)]),
    inactive_paths=("0", "1"), streams=(False, True),
    also={"serves": _served})


@pytest.mark.parametrize("case,kw", cases(F))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


def test_one_family_one_table_one_directory():
    """The model is served by the kinds' family: latent pages are a pool
    of their own beside the state rows, in one page-id space (no V side),
    under one directory; the latent family is for a model whose every
    layer is latent."""
    fam = get_family(SPEC)
    assert isinstance(fam, GqaFamily) and fam.recurrent
    assert not SPEC.is_mla and SPEC.has_latent and SPEC.has_recurrent
    assert ModelSpec.tiny_deepseek().is_mla
    k, v = _cache(F)
    D = SPEC.kv_lora_rank + SPEC.qk_rope_head_dim
    assert k.pools[LATENT].shape == (1, 1 + 3 * PAGES_PER_SEQ, PAGE, D)
    assert v.pools[LATENT] is None
    assert k.pools[KDA_KIND].shape == (2, ROWS + 1, 4, 16, 16)
    assert k.pools[KDA_KIND].dtype == jnp.float32
    assert v.pools[KDA_KIND].shape == (2, ROWS + 1, 3, 3, 64)
    assert llama.page_size_of(k) == PAGE
    assert k.rows.owner.shape == (1, ROWS + 1)
    assert SPEC.clamps(0) == (0.0, 0.0) and SPEC.clamps(2) == (0.75, 0.4)


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernel"])
@pytest.mark.parametrize("T_", [16, 150], ids=["one-block", "ragged"])
def test_the_chunk_form_is_the_recurrence_at_both_ends_of_the_bound(
        monkeypatch, pallas, T_):
    """The bounded gate puts a token's log decay in (-5, 0). Channels at
    both ends of it in one call (-4.99 beside -1e-5 a token, what
    ``kda_lower_bound * sigmoid`` gives saturated either way): the
    chunkwise form, whose sub-block inverse decay holds e^80 over 16
    tokens, is the recurrence a token at a time, outputs and state."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    N, H, D = 2, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(T_), 6)
    q = jax.random.normal(ks[0], (N, T_, H, D))
    k = jax.random.normal(ks[1], (N, T_, H, D))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (N, T_, H, D))
    z = 12.0 * jax.random.normal(ks[3], (N, T_, H, D))
    g = -5.0 * jax.nn.sigmoid(z)
    assert float(g.min()) < -4.99 and float(g.max()) > -1e-4
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[4], (N, T_, H)))
    s0 = jax.random.normal(ks[5], (N, H, D, D))
    pool = jnp.concatenate([s0, s0[:1]])[None]
    o, new = attn_ops.kda_chunk_prefill(
        q, k, v, g, beta, pool, jnp.arange(N, dtype=jnp.int32),
        jnp.zeros((N,), bool), layer=0)
    for n in range(N):
        want_o, want_s = attn_ops.kda_recurrence(
            q[n], k[n], v[n], g[n], beta[n], s0[n])
        F.close(o[n], np.asarray(want_o), tol=1e-4)
        F.close(new[0, n], np.asarray(want_s), tol=1e-4)


def test_kernels_equal_their_xla_twins_on_a_kinds_pool(monkeypatch):
    """Both latent kernels and both KDA kernels, interpreted, against the
    XLA forms that serve off the chip, through the programs of a model
    with TWO latent layers: each reads and writes its own layer of the
    kind's pool (``layer=lj``, not the model's layer index) under the
    kinds' block table. Logits, the latent pool and the states agree."""
    spec = dataclasses.replace(
        SPEC, num_layers=4, layer_pattern=(1, 0, 1, 0),
        expert_clamp=(0.0, 0.5, 0.0, 0.75), shared_clamp=(0.0, 0.6, 0.0, 0.4))
    params = llama.init_params(spec, jax.random.PRNGKey(SEED))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (3, T), 0, 96))
    outs = {}
    for pallas in ("0", "1"):
        monkeypatch.setenv("DYNAMO_PALLAS", pallas)
        pf, _, df, _ = _programs(F)
        k, v = _cache(F, spec=spec)
        assert k.pools[LATENT].shape[0] == 2
        got = []
        for start, n in ((0, 16), (16, 11)):
            lg, k, v = _prefill(
                F, pf, params, toks, 1, start, n, k, v, spec=spec)
            got.append(np.asarray(lg))
        bts = np.zeros((3, PAGES_PER_SEQ), np.int32)
        bts[0] = np.asarray(_table(F, 1))
        for j in range(3):
            lg, k, v = df(
                spec, params, jnp.asarray([toks[1, 27 + j], 0, 0]),
                jnp.asarray(bts), jnp.asarray([28 + j, 1, 1], jnp.int32),
                k, v, jnp.asarray([True, False, False]))
            got.append(np.asarray(lg[0]))
        outs[pallas] = (got, np.asarray(k.pools[LATENT][:, 1:]),
                        np.asarray(k.pools[KDA_KIND][:, :1]))
    for a, b in zip(outs["1"][0], outs["0"][0]):
        F.close(a, b, tol=2e-4)
    F.close(outs["1"][1], outs["0"][1], tol=1e-5)
    F.close(outs["1"][2], outs["0"][2], tol=1e-4)
    # both layers of the kind's pool hold rows, and they differ
    pool = outs["1"][1]
    assert np.abs(pool[0]).max() > 0 and np.abs(pool[1]).max() > 0
    assert not np.allclose(pool[0], pool[1])
    want = _whole(spec, params, jnp.asarray(toks[1, :30]))
    F.close(outs["1"][0][-1], np.asarray(want[29]))


@pytest.mark.parametrize("case", kda_step_cases.CASES)
def test_kda_step_from_the_projections_equals_its_xla_twin(monkeypatch, case):
    """``kda_step``'s new half (the tail's shift, the taps, SiLU, the
    norms, the columns: in the kernel) against its XLA twin through the
    layer's decode function, under THIS model's gates (full rank, the
    bounded decay): the output, the state AND the tails' pool; a slot on
    the trash row among live ones; a tail that a ragged pack's prefill
    just wrote; 8 steps in a row; bf16 tails
    (``tests/kda_step_cases.py``)."""
    kda_step_cases.check(monkeypatch, SPEC, KDA_KIND, case)


def _kind(spec, ki, **kw):
    kinds = list(spec.layer_kinds)
    kinds[ki] = dataclasses.replace(kinds[ki], **kw)
    return dataclasses.replace(spec, layer_kinds=tuple(kinds))


def _low_rank(w, r):
    u, s, vt = np.linalg.svd(np.asarray(w, np.float64), full_matrices=False)
    return jnp.asarray((u[:, :r] * s[:r]) @ vt[:r], w.dtype)


def _with_layer(params, li, **kw):
    layers = list(params["layers"])
    layers[li] = {**layers[li], **kw}
    return dict(params, layers=layers)


# what the published keys select, each changed alone: (spec, params) of a
# program that differs from the reference in that one thing
MECHANISMS = {
    "gate-bound": lambda s, p: (_kind(s, KDA_KIND, gate_bound=-4.0), p),
    "full-rank-decay": lambda s, p: (
        s, _with_layer(p, 1, w_f=_low_rank(p["layers"][1]["w_f"], 16))),
    "head-wise-gate": lambda s, p: (s, _with_layer(
        p, 2, w_gate_head=jnp.zeros_like(p["layers"][2]["w_gate_head"]))),
    "expert-clamp": lambda s, p: (
        dataclasses.replace(s, expert_clamp=(0.0, 0.5, 0.0)), p),
    "shared-clamp": lambda s, p: (
        dataclasses.replace(s, shared_clamp=(0.0, 0.0, 0.4)), p),
    "topk-group": lambda s, p: (dataclasses.replace(s, topk_group=3), p),
    "routed-scaling-factor": lambda s, p: (
        dataclasses.replace(s, routed_scaling_factor=1.0), p),
}


@pytest.mark.parametrize("name", list(MECHANISMS))
def test_every_published_mechanism_moves_the_logits(model, name):
    """The program as the published keys select it is the reference's to
    3e-4; with any one mechanism changed (the decay's bound, a rank-16
    decay projection, a constant gate a head, a layer's expert clamp or
    its shared expert's lifted, one more routing group, the routed
    scale) it is not, by a hundred times that: the comparison sees each."""
    params, toks, want = model
    got = _whole(SPEC, params, jnp.asarray(toks[0]))
    F.close(got, want[0])
    spec, changed = MECHANISMS[name](SPEC, params)
    off = _whole(spec, changed, jnp.asarray(toks[0]))
    assert float(np.abs(np.asarray(off) - want[0]).max()) > 3e-2, name


def test_the_shares_add_up(ref):
    """Four chips, a routing group of four experts each, the shared expert
    counted once, make the uncut expert layer: the program's shares
    (``held_experts`` = group g of the router's four, its own clamp) add
    up to what the reference gives with all 16 experts held."""
    cfg = dict(CONFIG, experts={"published": 16, "held": 16, "first": 0})
    w = ref.Weights(cfg, SEED)
    m = w.m
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 10), 0, 96))
    x = ref._embed_rows(w.embed(), toks, quant=None)
    for li in (1, 2):  # a KDA layer's experts, then the MLA layer's
        lw = w.layer(li)
        kw = dict(
            topk=m["topk"], groups=m["groups"], topk_group=m["topk_group"],
            scaling=m["scaling"], norm_topk=m["norm_topk"],
            clamp=m["clamp"][li], shared_clamp=m["shared_clamp"][li],
            eps=m["eps"], quant=None)
        ex = {k: lw[k] for k in ref.EXPERTS}
        whole = np.asarray(ref._experts(x, ex, first=0, held=16, **kw) - x)
        alike = np.asarray(ref._experts(
            x, dict(ex, e_gate=ex["e_gate"][:0]), first=0, held=0, **kw) - x)
        h = np.asarray(ref._rms(x, m["eps"])).reshape(20, -1)
        shares = []
        for g in range(4):
            spec = dataclasses.replace(SPEC, held_experts=(4, 4 * g))
            lp = {
                "moe": {
                    "router": lw["router"].astype(jnp.float32),
                    "score_bias": lw["score_bias"],
                    "w_gate": lw["e_gate"][4 * g: 4 * g + 4],
                    "w_up": lw["e_up"][4 * g: 4 * g + 4],
                    "w_down": lw["e_down"][4 * g: 4 * g + 4],
                },
                "shared": {"w_gate": lw["s_gate"], "w_up": lw["s_up"],
                           "w_down": lw["s_down"]},
            }
            shares.append(np.asarray(
                llama._ffn(spec, lp, jnp.asarray(h), li=li)
            ).reshape(2, 10, -1))
            # a chip's share is the reference's share
            part = np.asarray(ref._experts(
                x, dict(ex, **{k: ex[k][4 * g: 4 * g + 4]
                               for k in ("e_gate", "e_up", "e_down")}),
                first=4 * g, held=4, **kw) - x)
            F.close(shares[-1], part, tol=1e-4)
        # (a group that no token's top two groups hold adds nothing)
        assert sum(np.abs(s - alike).max() > 1e-3 for s in shares) >= 3
        F.close(alike + sum(s - alike for s in shares), whole, tol=1e-4)


def test_the_memory_guard_charges_both_kinds():
    """A model with KDA and latent kinds is charged the chunkwise form's
    operands AND the latent walk's scores: more than either alone, so a
    pack halves where a model of one of them keeps it; the latent kind
    may stand first among the kinds, a state-only kind may not."""
    wide = dict(hidden_size=2560, num_heads=32, head_dim=128, kda_heads=32,
                kda_head_dim=128, kv_lora_rank=512, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128, rotary_dim=64)
    spec = ModelSpec.tiny_ling3(**wide)
    cfg = EngineConfig(
        page_size=64, num_pages=4096, max_pages_per_seq=160,
        max_decode_slots=128, prefill_buckets=(1024,), prefill_pack_size=2,
        max_prefill_chunk_tokens=1024)
    only_kda = dataclasses.replace(
        spec, layer_kinds=(LayerKind(4, 1e4), spec.layer_kinds[1]),
        kv_lora_rank=0)
    assert cfg.prefill_shapes(spec, 4 * 2**30) == {1024: 2}
    free = 1000 * 2**20
    assert cfg.prefill_shapes(only_kda, free) == {1024: 2}
    assert cfg.prefill_shapes(spec, free) == {1024: 1}
    with pytest.raises(ValueError, match="paged kind first"):
        ModelSpec.tiny_ling3(layer_kinds=SPEC.layer_kinds[::-1],
                             layer_pattern=(0, 0, 1))


def test_no_tensor_names_are_invented(tmp_path):
    """The catalog gives this model's config and no checkpoint names: the
    loader refuses to save or load it rather than guess."""
    from dynamo_tpu.models import loader

    with pytest.raises(NotImplementedError, match="drawn weights only"):
        loader.hf_config_from_spec(SPEC)
    with pytest.raises(NotImplementedError, match="drawn weights only"):
        loader.save_params(SPEC, {}, str(tmp_path))
