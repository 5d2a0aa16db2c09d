"""LFM2-MoE at toy widths on the CPU, against the benchmark's own plain
reference (``perfbench/references/shortconv_moe.py``, loaded by path: the
same module the chip is held to, not a copy): gated short-convolution
layers whose whole state is a two-token tail (a recurrent kind with NO
state matrix) beside QK-normed GQA layers, a leading dense layer, sigmoid
routing with a selection-only bias over experts all held. Programs, the
state directory, the routing's constants, the loader's name map and the
engine around a sequence that owns pages and a tail.
"""

import asyncio
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_contract import (
    REPO, Family, _cache, _greedy, _greedy_reference, _prefill,
    _programs, _table, _whole, case, cases, chunked, preempt,
    prefill_then_decode, run,
)


from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.family import GqaFamily, get_family

# the reference reads the published keys; the program reads SPEC. Four
# layers: a dense conv layer, an expert attention layer, two expert conv
# layers
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "layers_kept": [0, 1, 2, 3],
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "intermediate_size": 96,
    "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 4,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 96,
    "torch_dtype": "float32",
}
SPEC = ModelSpec.tiny_lfm2()
ATTN, CONV = 0, 1  # the kinds' places in SPEC.layer_kinds
PAGE, PAGES_PER_SEQ, T, ROWS = 4, 16, 40, 3
SEED = 13


def _packed(k, v):
    """Behind the pack of 13 tokens and an empty member: the tail the row
    keeps is the ``z`` of its last two REAL tokens; the trash row took the
    empty member's "tail": zeros, as it was."""
    owner = np.asarray(k.rows.owner[0])
    tails = np.asarray(v.pools[CONV])
    assert np.abs(tails[:, int(np.argmax(owner == 1))]).max() > 0
    assert not tails[:, ROWS].any()


def _served(engine, snap, served, outs):
    """Two prompts of 16 + 5 tokens: four prefill members, the second
    chunk of each resumes a tail; three expert layers, 2 x 21 prompt
    tokens, top-4 of 8, all held."""
    assert engine._prefill_walks == {"full": 0}
    assert engine.recurrent_state == {
        "prefill_chunks": 4, "rows_resumed": 2}
    assert not engine.kda and not engine.ssd
    assert snap["recurrent_state.prefill_chunks"]["calls"] == 4
    assert snap["recurrent_state.rows_resumed"]["calls"] == 2
    assert snap["recurrent_state.row_missing"]["calls"] == 0
    m = engine.moe_counters()
    assert m["layers"] == 3 and m["prefill.assignments"] == 3 * 2 * 21 * 4
    assert m["prefill.assignments_held"] == m["prefill.assignments"]
    assert m["prefill.assignments_held"] == sum(
        m[f"prefill.expert.{i}"] for i in range(8))


# the family's row of the contract (tests/family_contract.py). Chunks also
# where the boundary falls one and two tokens after the start (a tail still
# part zeros), on pages of one token; no kernel of its own: the default path
F = FAMILY = Family(
    spec=SPEC, config=CONFIG, reference="shortconv_moe",
    seed=SEED, state_rows=ROWS, prompts=(), pack_tol=1e-6,
    bursts_paths=(None,), inactive_paths=(None,), engine_path=None,
    streams=(False, True), also={"pack": _packed, "serves": _served})
RESUMED = {"resumed-after-one-token": [(0, 1), (1, 36)],
           "resumed-after-two-tokens": [(0, 2), (2, 35)],
           "three-one-token-chunks": [(0, 1), (1, 1), (2, 1), (3, 34)]}


@pytest.mark.parametrize("case,kw", cases(
    F, *(case(f"chunked-{name}", chunked, chunks=chunks, page=1)
         for name, chunks in RESUMED.items()),
    case("engine-preempt", preempt)))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


def test_a_recurrent_kind_that_keeps_tails_and_no_state():
    """The conv kind is recurrent (a row a sequence, one directory) and
    keeps NOTHING on the K side: no state pool, no dummy array; its tails
    ``[layers of the kind, rows + 1, taps - 1, hidden]`` are the V side's
    entry. The attention kind keeps bare page pools, as before."""
    fam = get_family(SPEC)
    assert isinstance(fam, GqaFamily) and fam.recurrent
    kinds = SPEC.layer_kinds
    assert kinds[CONV].recurrent and not kinds[CONV].state
    assert not kinds[CONV].paged and kinds[ATTN].paged
    assert SPEC.has_recurrent and SPEC.mixers == {"softmax", "conv"}
    k, v = _cache(F)
    assert k.pools[CONV] is None
    assert v.pools[CONV].shape == (3, ROWS + 1, 2, 64)
    assert v.pools[CONV].dtype == jnp.float32  # the toy's activation dtype
    assert k.pools[ATTN].shape == v.pools[ATTN].shape == (
        1, 1 + 3 * PAGES_PER_SEQ, 2, PAGE, 16)
    assert k.rows.owner.shape == (1, ROWS + 1)
    assert llama.page_size_of(k) == PAGE
    # every leaf of the cache is an array: nothing of size zero stands in
    assert all(leaf.size > 0 for leaf in jax.tree.leaves((k.pools, v.pools)))
    bf16 = llama.init_cache(
        dataclasses.replace(SPEC, dtype="bfloat16"), 9, PAGE, state_rows=2)
    assert bf16[1].pools[CONV].dtype == jnp.bfloat16
    assert bf16[1].pools[CONV].shape == (3, 3, 2, 64)


@pytest.mark.parametrize("kinds", ["kda", "ssd", "latent"])
def test_the_other_kinds_entries_keep_their_shapes(kinds):
    """What a kind keeps decides its entry, for the kinds that were there:
    a state-only kind a bare float32 array and bare tails, a kind with
    both ``PagesAndState`` on both sides, a latent kind one pool and
    None."""
    spec = {"kda": ModelSpec.tiny_solar, "ssd": ModelSpec.tiny_falcon_h1,
            "latent": ModelSpec.tiny_ling3}[kinds]()
    k, v = llama.init_cache(spec, 9, 4, state_rows=2)
    for ki, kd in enumerate(spec.layer_kinds):
        ke, ve = k.pools[ki], v.pools[ki]
        if kd.latent:
            assert ve is None and ke.ndim == 4
        elif kd.paged and kd.recurrent:
            assert isinstance(ke, llama.PagesAndState)
            assert isinstance(ve, llama.PagesAndState)
            assert ke.state.dtype == jnp.float32 and ke.state.shape[1] == 3
        elif kd.recurrent:
            assert ke.dtype == jnp.float32 and ke.shape[1] == 3
            assert ve.shape[:2] == ke.shape[:2]
        else:
            assert ke.ndim == 5 and ve.ndim == 5
        assert llama._entry_parts(kd, ke) == (
            llama._entry_parts(kd, llama._entry_of(*llama._entry_parts(kd, ke))))


@pytest.mark.parametrize("dtype,tol", [("float32", 3e-4), ("bfloat16", 0.12)])
def test_a_whole_prompt_and_decode_steps_are_the_references(
        ref, model, monkeypatch, dtype, tol):
    """A prompt through the prefill program, then teacher-forced decode
    steps through the tails of the conv layers and the pages of the
    attention layer: every position's logits are the reference's whole
    forward pass; in bfloat16 (weights, activations, pages, tails) to its
    rounding. The other slots are empty or inactive."""
    if dtype == "float32":
        prefill_then_decode(F, monkeypatch)
        return
    spec = dataclasses.replace(SPEC, dtype=dtype)
    params = llama.init_params(spec, jax.random.PRNGKey(SEED))
    toks = model[1]
    want = np.asarray(ref.forward(
        dict(CONFIG, torch_dtype=dtype), SEED, toks,
        np.tile(np.arange(T), (3, 1))))
    scale = float(np.sqrt(np.mean(want ** 2)))

    def held(got, at, what):
        # float32: to 3e-4 by element; bfloat16: the root mean square of
        # the difference under ``tol`` of the logits' own
        err = np.sqrt(np.mean((np.asarray(got, np.float32) - want[1, at]) ** 2))
        assert err < tol * scale, (what, err, scale)

    prefill_then_decode(
        F, monkeypatch, spec=spec, model=(params, toks, want), held=held)


def test_a_released_row_taken_over_starts_from_zeros(model):
    """A row released and claimed by another sequence starts from a zero
    tail (``fresh``), not from what its last owner left: the newcomer's
    logits are the reference's. An inactive slot and the other rows keep
    their tails to the bit through a decode step."""
    params, toks, want = model
    pf, _, df, _ = _programs(F)
    k, v = _cache(F, rows=2)
    for row, n in ((0, 9), (1, 14)):
        _, k, v = _prefill(F, pf, params, toks, row, 0, n, k, v)
    before = np.asarray(v.pools[CONV])
    assert np.abs(before[:, 0]).max() > 0 and np.abs(before[:, 1]).max() > 0
    # a decode step with row 1's slot live alone leaves row 0's tail
    bts = np.stack([np.asarray(_table(F, r)) for r in range(2)])
    _, k, v = df(
        SPEC, params, jnp.asarray(toks[:2, 20]), jnp.asarray(bts),
        jnp.asarray([10, 15], jnp.int32), k, v, jnp.asarray([False, True]))
    now = np.asarray(v.pools[CONV])
    np.testing.assert_array_equal(now[:, 0], before[:, 0])
    assert not np.array_equal(now[:, 1], before[:, 1])
    # sequence 0 goes; sequence 2 takes its row over and is the reference's
    k = llama.release_state_rows(k, jnp.asarray(
        [int(_table(F, 0)[0]), -1], jnp.int32))
    assert list(np.asarray(k.rows.owner[0])) == [0, 1 + PAGES_PER_SEQ, 0]
    logits, k, v = _prefill(F, pf, params, toks, 2, 0, 11, k, v)
    F.close(logits, want[2, 10])
    assert list(np.asarray(k.rows.owner[0]))[0] == 1 + 2 * PAGES_PER_SEQ
    assert int(k.rows.stats[0, llama.STAT_MISSING]) == 0


def _with_layer(params, li, **kw):
    layers = list(params["layers"])
    layers[li] = {**layers[li], **kw}
    return dict(params, layers=layers)


def _with_moe(params, li, **kw):
    return _with_layer(params, li, moe={**params["layers"][li]["moe"], **kw})


# what the published keys select, each changed alone: (spec, params) of a
# program that differs from the reference in that one thing
MECHANISMS = {
    "qk-norm-gains": lambda s, p: (s, _with_layer(
        p, 1, q_norm=jnp.ones_like(p["layers"][1]["q_norm"]),
        k_norm=jnp.ones_like(p["layers"][1]["k_norm"]))),
    "qk-norm": lambda s, p: (dataclasses.replace(s, qk_norm=False), p),
    "selection-bias": lambda s, p: (s, _with_moe(
        p, 2, score_bias=jnp.zeros_like(p["layers"][2]["moe"]["score_bias"]))),
    "taps": lambda s, p: (s, _with_layer(
        p, 2, sconv_taps=p["layers"][2]["sconv_taps"][::-1])),
    "gate-order": lambda s, p: (s, _with_layer(
        p, 0, sconv_in=jnp.roll(p["layers"][0]["sconv_in"], 64, axis=1))),
}


@pytest.mark.parametrize("name", list(MECHANISMS))
def test_every_published_mechanism_moves_the_logits(model, name):
    """The program as the published keys select it is the reference's to
    3e-4; with any one mechanism changed (the two norms' gains put to 1,
    the QK norm off, the selection bias zeroed, the taps reversed, the
    order of B | C | x rolled) it is not, by many times that: the
    comparison sees each. The gains are drawn about 1, not AT 1."""
    params, toks, want = model
    got = _whole(SPEC, params, jnp.asarray(toks[0]))
    F.close(got, want[0])
    assert float(jnp.abs(params["layers"][1]["q_norm"] - 1).max()) > 0.05
    spec, changed = MECHANISMS[name](SPEC, params)
    off = _whole(spec, changed, jnp.asarray(toks[0]))
    assert float(np.abs(np.asarray(off) - want[0]).max()) > 3e-3, name


def test_the_bias_changes_the_picks_and_not_the_weights():
    """The router: the 4 experts are the top-4 of ``s + b``; their weights
    are ``s`` at those experts over ``(their sum + 1e-6)``. A bias that
    lifts the two weakest experts into the picks moves the picks and
    leaves each picked expert's weight a function of the scores alone."""
    lp = moe.init_moe_layer(SPEC, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (5, 64))
    s = np.asarray(jax.nn.sigmoid(x @ lp["router"]), np.float64)
    topi0, _ = moe.route(SPEC, dict(lp, score_bias=jnp.zeros((8,))), x)
    weakest = np.argsort(s, axis=1)[:, :2]
    bias = np.zeros((8,), np.float32)
    bias[weakest[0]] = 5.0
    topi, topv = moe.route(SPEC, dict(lp, score_bias=jnp.asarray(bias)), x)
    topi, topv = np.asarray(topi), np.asarray(topv, np.float64)
    assert set(weakest[0]) <= set(topi[0])
    assert not set(weakest[0]) <= set(np.asarray(topi0)[0])
    for t in range(5):
        picked = s[t, topi[t]]
        np.testing.assert_allclose(
            topv[t], picked / (picked.sum() + 1e-6), rtol=2e-6)
        # with the bias in the weights the lifted experts would weigh ~5
        assert topv[t].max() < 1.0


def test_the_published_epsilon_is_in_the_sum():
    """``1e-6`` in the chosen scores' sum, where this repo's other sigmoid
    routers carry ``1e-20``: at scores of ~1e-6 (a router whose logits
    are all -14) the two differ by half, and the program carries the
    published one."""
    assert SPEC.moe_norm_eps == 1e-6
    assert ModelSpec.tiny_deepseek().moe_norm_eps == 1e-20
    lp = {"router": jnp.zeros((64, 8)), "score_bias": jnp.zeros((8,)),
          "router_bias": jnp.full((8,), -14.0)}
    x = jnp.ones((2, 64))
    s = float(jax.nn.sigmoid(-14.0))
    _, w = moe.route(SPEC, lp, x)
    np.testing.assert_allclose(
        np.asarray(w), s / (4 * s + 1e-6), rtol=1e-5)
    _, w20 = moe.route(dataclasses.replace(SPEC, moe_norm_eps=1e-20), lp, x)
    np.testing.assert_allclose(np.asarray(w20), 0.25, rtol=1e-5)
    assert float(w[0, 0]) < 0.2


def _lowered(spec):
    """The StableHLO of the toy prefill and decode programs, and the
    parameters' shapes."""
    params = jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0)))
    k, v = jax.eval_shape(lambda: llama.init_cache(spec, 9, 4))
    S, i32 = jax.ShapeDtypeStruct, jnp.int32
    pf = jax.jit(llama.prefill_forward_impl, static_argnums=(0,)).lower(
        spec, params, S((16,), i32), S((8,), i32), S((), i32), k, v,
        S((), i32)).as_text()
    df = jax.jit(llama.decode_forward_impl, static_argnums=(0,)).lower(
        spec, params, S((2,), i32), S((2, 8), i32), S((2,), i32), k, v,
        S((2,), jnp.bool_)).as_text()
    return (pf, df), params


@pytest.mark.parametrize("name", ["tiny", "tiny_moe"])
def test_without_qk_norm_the_dense_programs_hold_no_trace_of_it(name):
    """``qk_norm`` false (the default) leaves nothing of the norm in the
    dense toy model's and the toy expert model's prefill and decode
    programs: no gain among the parameters, and as many ``rsqrt`` as the
    layers' own norms need (two a layer and the final one), which is the
    program without the field. With it on, each attention layer gains
    two. (Byte equality with the parent commit's programs at the cells'
    widths is ``tools/hlo_metadata_proof.py``'s, recorded in CHANGES.md:
    no hash of a program is frozen here.)"""
    spec = getattr(ModelSpec, name)()
    assert not spec.qk_norm and spec.moe_norm_eps == 1e-20
    off, params = _lowered(spec)
    assert not {"q_norm", "k_norm"} & set(params["layers"][0])
    own = 2 * spec.num_layers + 1
    assert [t.count("rsqrt") for t in off] == [own, own]
    on, params = _lowered(dataclasses.replace(spec, qk_norm=True))
    assert {"q_norm", "k_norm"} <= set(params["layers"][0])
    assert [t.count("rsqrt") for t in on] == [own + 2 * spec.num_layers] * 2
    # the field is read at trace time and nowhere else: the same spec
    # lowers to the same text again
    assert _lowered(spec)[0] == off


# ------------------------------------------------------------- the loader


def test_the_checkpoint_names_round_trip(tmp_path):
    """A synthetic ``lfm2_moe`` checkpoint at toy size: every tensor of
    the name map lands, none is left over, the depthwise kernel is stored
    ``[channels, 1, taps]`` and comes back ``[taps, channels]``, the
    config's keys come back as the spec."""
    from safetensors import safe_open

    from dynamo_tpu.models import loader

    params = llama.init_params(SPEC, jax.random.PRNGKey(3))
    loader.save_params(SPEC, params, str(tmp_path))
    with safe_open(str(tmp_path / "model.safetensors"), "numpy") as f:
        names = set(f.keys())
        taps = f.get_tensor("model.layers.0.conv.conv.weight")
        w_in = f.get_tensor("model.layers.0.conv.in_proj.weight")
    assert taps.shape == (64, 1, 3) and w_in.shape == (192, 64)
    np.testing.assert_array_equal(
        taps[:, 0, :].T, np.asarray(params["layers"][0]["sconv_taps"]))
    assert names == set(loader._dest_map_lfm2(SPEC))
    for want in (
        "model.embed_tokens.weight", "model.embedding_norm.weight",
        "model.layers.0.operator_norm.weight", "model.layers.0.ffn_norm.weight",
        "model.layers.0.conv.out_proj.weight",
        "model.layers.0.feed_forward.w1.weight",
        "model.layers.1.self_attn.q_proj.weight",
        "model.layers.1.self_attn.out_proj.weight",
        "model.layers.1.self_attn.q_layernorm.weight",
        "model.layers.1.self_attn.k_layernorm.weight",
        "model.layers.1.feed_forward.gate.weight",
        "model.layers.1.feed_forward.expert_bias",
        "model.layers.3.feed_forward.experts.7.w2.weight",
    ):
        assert want in names, want
    assert "lm_head.weight" not in names  # tied
    assert not [n for n in names if "layers.0.feed_forward.experts" in n]
    spec, loaded = loader.load_model_dir(str(tmp_path), name=SPEC.name)
    assert spec == SPEC
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert loaded["layers"][1]["moe"]["router"].dtype == jnp.float32
    # a tensor too few is an error, not a silent default
    os.remove(tmp_path / "model.safetensors")
    short = dict(params, layers=params["layers"][:3])
    with pytest.raises((ValueError, KeyError, IndexError)):
        loader.save_params(SPEC, short, str(tmp_path))
        loader.load_params(SPEC, str(tmp_path))


def test_the_published_config_maps_to_the_spec():
    """The catalog row's keys (``layer_types``, ``conv_L_cache``,
    ``num_dense_layers``, ``rope_parameters``, ``norm_eps``) give the
    kinds, the pattern and the routing the cell's ``model_spec`` states."""
    import json

    from dynamo_tpu.models import loader

    with open(os.path.join(REPO, "perfbench/configs/lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    # the model whole, as published: the cell keeps its first ten layers
    spec = loader.spec_from_hf_config(
        dict(cfg, num_hidden_layers=40), name="lfm2-24b-a2b")
    want = ModelSpec(num_layers=10, **cfg["model_spec"])
    assert spec.num_layers == 40 and spec.layer_pattern.count(0) == 10
    assert spec.layer_pattern[:10] == want.layer_pattern
    assert spec.layer_kinds == want.layer_kinds
    for key in cfg["model_spec"]:
        if key not in ("layer_kinds", "layer_pattern"):
            assert getattr(spec, key) == getattr(want, key), key
    assert (spec.hidden_size, spec.num_heads, spec.head_dim) == (2048, 32, 64)
    assert spec.intermediate_size == 11776 and spec.vocab_size == 65536


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernel"])
async def test_the_chips_pool_layout_serves_the_same_tokens(
    monkeypatch, pallas
):
    """The toy model at the published head width (64) through the engine
    with the chip's pool layout asked for (ops/attention.pool_head_dim:
    two KV heads a 128-lane row, no zeros) and with the CPU's plain pool:
    prefill writes and walks, a prompt of two chunks, decode bursts on
    either decode path. The greedy streams are equal, and the whole
    forward pass's own."""
    from dynamo_tpu.ops import attention as att

    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    spec = ModelSpec.tiny_lfm2(head_dim=64)
    prompts = [[int(t) for t in np.arange(7, 7 + 21) % 96], [3, 9, 27]]

    async def serve():
        engine = InferenceEngine(spec, EngineConfig(
            page_size=PAGE, num_pages=64, max_pages_per_seq=PAGES_PER_SEQ,
            max_decode_slots=2, prefill_buckets=(16,),
            max_prefill_chunk_tokens=16, decode_steps_per_dispatch=4,
            seed=SEED))
        outs = await asyncio.gather(*(_greedy(engine, p, 7) for p in prompts))
        snap = engine.profile_snapshot()
        await engine.close()
        assert int(engine.k_pages.rows.stats[0, llama.STAT_MISSING]) == 0
        pool = llama.kind_pages(spec, engine.k_pages, ATTN)
        return (outs, engine.params, pool.shape,
                snap["kv_pool.heads_per_lane_row"]["calls"],
                snap["kv_pool.bytes"]["calls"])

    plain, params, shape, a_row, nbytes = await serve()
    assert shape == (1, 65, 2, PAGE, 64) and a_row == 1
    monkeypatch.setattr(att, "pool_head_dim", functools.partial(
        att.pool_head_dim, compiled=True))
    packed, _, shape, a_row, packed_bytes = await serve()
    assert shape == (1, 65, 1, PAGE, 128) and a_row == 2
    assert packed_bytes == nbytes  # no zeros: the model's own bytes
    assert packed == plain
    for prompt, got in zip(prompts, plain):
        assert got == _greedy_reference(F, params, prompt, 7, spec=spec)
