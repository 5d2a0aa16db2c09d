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
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import llama, moe
from dynamo_tpu.models.family import GqaFamily, get_family
from dynamo_tpu.runtime.context import PRIORITY_HEADER, Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "shortconv_moe",
        os.path.join(REPO, "perfbench/references/shortconv_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(ref):
    params = llama.init_params(SPEC, jax.random.PRNGKey(SEED))
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (3, T), 0, 96))
    want = np.asarray(ref.forward(
        CONFIG, SEED, toks, np.tile(np.arange(T), (3, 1))))
    return params, toks, want


def _cache(rows=ROWS, spec=SPEC, page=PAGE):
    return llama.init_cache(
        spec, 1 + 3 * PAGES_PER_SEQ * (PAGE // page), page, state_rows=rows)


def _table(row, page=PAGE):
    n = PAGES_PER_SEQ * (PAGE // page)
    return jnp.arange(n, dtype=jnp.int32) + 1 + row * n


def _close(got, want, tol=3e-4):
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


def _programs():
    return (jax.jit(llama.prefill_forward_impl, static_argnums=(0,)),
            jax.jit(llama.prefill_forward_batch_impl, static_argnums=(0,)),
            jax.jit(llama.decode_forward_impl, static_argnums=(0,)),
            jax.jit(llama.decode_steps_impl, static_argnums=(0,),
                    static_argnames=("n_steps", "n_logprobs")))


def _prefill(pf, params, toks, row, start, n, k, v, bucket=16, spec=SPEC,
             page=PAGE):
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = toks[row, start: start + n]
    logits, k, v, _ = pf(
        spec, params, jnp.asarray(padded), _table(row, page),
        jnp.asarray(start, jnp.int32), k, v, jnp.asarray(n, jnp.int32),
    )
    return logits, k, v


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
    k, v = _cache()
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
        ref, model, dtype, tol):
    """A prompt through the prefill program, then teacher-forced decode
    steps through the tails of the conv layers and the pages of the
    attention layer: every position's logits are the reference's whole
    forward pass; in bfloat16 (weights, activations, pages, tails) to its
    rounding. The other slots are empty or inactive."""
    if dtype == "float32":
        params, toks, want = model
        spec = SPEC
    else:
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
        if dtype == "float32":
            return _close(got, want[1, at])
        err = np.sqrt(np.mean((np.asarray(got, np.float32) - want[1, at]) ** 2))
        assert err < tol * scale, (what, err, scale)

    pf, _, df, _ = _programs()
    k, v = _cache(spec=spec)
    n = 21
    logits, k, v = _prefill(pf, params, toks, 1, 0, n, k, v, bucket=32,
                            spec=spec)
    held(logits, n - 1, "prefill")
    bts = np.zeros((3, PAGES_PER_SEQ), np.int32)
    bts[2] = np.asarray(_table(1))
    active = np.array([False, False, True])
    for j in range(6):
        fed = np.zeros((3,), np.int32)
        seq = np.ones((3,), np.int32)
        fed[2], seq[2] = toks[1, n + j], n + j + 1
        lg, k, v = df(spec, params, jnp.asarray(fed), jnp.asarray(bts),
                      jnp.asarray(seq), k, v, jnp.asarray(active))
        held(lg[2], n + j, f"decode step {j}")
    stats = np.asarray(k.rows.stats[0])
    assert stats[llama.STAT_CLAIMS] == 1 and stats[llama.STAT_MISSING] == 0


@pytest.mark.parametrize("page,chunks", [
    (4, [(0, 37)]),
    (4, [(0, 16), (16, 16), (32, 5)]),
    (1, [(0, 1), (1, 36)]),
    (1, [(0, 2), (2, 35)]),
    (1, [(0, 1), (1, 1), (2, 1), (3, 34)]),
], ids=["one-shot", "three-chunks", "resumed-after-one-token",
        "resumed-after-two-tokens", "three-one-token-chunks"])
def test_a_chunked_prompt_resumes_its_tail(model, page, chunks):
    """Chunks at ``start_pos`` > 0 resume the convolution from the tail
    the chunk before left in the row, also where the boundary falls one
    and two tokens after the sequence's start (a tail that is still part
    zeros): the last chunk's logits are the one-shot prefill's and the
    reference's."""
    params, toks, want = model
    pf = _programs()[0]
    k, v = _cache(page=page)
    for start, n in chunks:
        logits, k, v = _prefill(
            pf, params, toks, 0, start, n, k, v,
            bucket=64 if n > 16 else 16, page=page)
    _close(logits, want[0, 36])
    assert int(k.rows.stats[0, llama.STAT_MISSING]) == 0
    assert int(k.rows.stats[0, llama.STAT_CLAIMS]) == 1


def test_a_ragged_pack_keeps_rows_apart(model):
    """Rows of different lengths and an empty row in packed calls, one of
    them a pack of two RESUMED chunks: each row's logits are the
    reference's (no row's ``B * x`` leaks into its neighbour's
    convolution), the empty row claims nothing, and the tail a row keeps
    is the ``z`` of its last two REAL tokens whatever the padding."""
    params, toks, want = model
    pb = _programs()[1]
    k, v = _cache()

    def pack(members, bucket=16):
        nonlocal k, v
        padded = np.zeros((2, bucket), np.int32)
        bts = np.zeros((2, PAGES_PER_SEQ), np.int32)
        starts, lens = np.zeros(2, np.int32), np.zeros(2, np.int32)
        for i, (row, start, n) in enumerate(members):
            padded[i, :n] = toks[row, start: start + n]
            if n:
                bts[i], starts[i], lens[i] = np.asarray(_table(row)), start, n
        logits, k, v, _ = pb(
            SPEC, params, jnp.asarray(padded), jnp.asarray(bts),
            jnp.asarray(starts), k, v, jnp.asarray(lens))
        return logits

    logits = pack([(0, 0, 13), (0, 0, 0)])
    _close(logits[0], want[0, 12])
    owner = np.asarray(k.rows.owner[0])
    assert sorted(owner[:ROWS]) == [0, 0, 1] and owner[ROWS] == 0
    tails_13 = np.asarray(v.pools[CONV])[:, int(np.argmax(owner == 1))]
    # the same 13 tokens in a wider bucket leave the same tail: padding
    # past the last real token does not reach it
    k2, v2 = _cache()
    _, k2, v2 = _prefill(_programs()[0], params, toks, 0, 0, 13, k2, v2,
                         bucket=32)
    np.testing.assert_allclose(
        np.asarray(v2.pools[CONV])[:, 0], tails_13, rtol=1e-6, atol=1e-6)
    assert np.abs(tails_13).max() > 0
    # the trash row took the empty member's "tail": zeros, as it was
    assert not np.asarray(v.pools[CONV])[:, ROWS].any()
    logits = pack([(1, 0, 16), (2, 0, 8)])
    _close(logits[0], want[1, 15])
    _close(logits[1], want[2, 7])
    logits = pack([(1, 16, 9), (2, 8, 16)])  # two resumed chunks
    _close(logits[0], want[1, 24])
    _close(logits[1], want[2, 23])
    stats = np.asarray(k.rows.stats[0])
    assert stats[llama.STAT_CLAIMS] == 3 and stats[llama.STAT_MISSING] == 0


def test_bursts_of_one_and_eight_agree_after_prefill(model):
    """Eight greedy steps as one burst and as eight bursts of one after
    two prefills: the same tokens, the reference's own choices, the same
    tails and the same pages afterwards."""
    params, toks, want = model
    pf, _, _, ds = _programs()
    B = 3
    bts = np.zeros((B, PAGES_PER_SEQ), np.int32)
    bts[0], bts[1] = np.asarray(_table(0)), np.asarray(_table(1))
    active = jnp.asarray([True, True, False])
    z = jnp.zeros((B,), jnp.int32)

    def run(bursts):
        k, v = _cache()
        for row, n in ((0, 9), (1, 14)):
            _, k, v = _prefill(pf, params, toks, row, 0, n, k, v)
        fed = np.array([toks[0, 9], toks[1, 14], 0], np.int32)
        seq = np.array([10, 15, 1], np.int32)
        out = []
        for n_steps in bursts:
            o, k, v = ds(
                SPEC, params, jnp.asarray(fed), jnp.asarray(bts),
                jnp.asarray(seq), k, v, active, jnp.zeros((B,)), z,
                jnp.ones((B,)), jnp.zeros((B,), jnp.uint32), z,
                n_steps=n_steps, n_logprobs=0)
            o = np.asarray(o)
            out.append(o[:2])
            fed[:2], seq[:2] = o[:2, -1], seq[:2] + n_steps
        return np.concatenate(out, axis=1), k, v

    one, k1, v1 = run([1] * 8)
    eight, k8, v8 = run([8])
    np.testing.assert_array_equal(one, eight)
    # the first token of each row is the reference's greedy choice
    assert one[0, 0] == int(np.argmax(want[0, 9]))
    assert one[1, 0] == int(np.argmax(want[1, 14]))
    _close(v8.pools[CONV][:, :2], np.asarray(v1.pools[CONV][:, :2]), tol=1e-5)
    _close(k8.pools[ATTN][:, 1:], np.asarray(k1.pools[ATTN][:, 1:]), tol=1e-5)
    assert int(k8.rows.stats[0, llama.STAT_MISSING]) == 0


def test_a_released_row_taken_over_starts_from_zeros(model):
    """A row released and claimed by another sequence starts from a zero
    tail (``fresh``), not from what its last owner left: the newcomer's
    logits are the reference's. An inactive slot and the other rows keep
    their tails to the bit through a decode step."""
    params, toks, want = model
    pf, _, df, _ = _programs()
    k, v = _cache(rows=2)
    for row, n in ((0, 9), (1, 14)):
        _, k, v = _prefill(pf, params, toks, row, 0, n, k, v)
    before = np.asarray(v.pools[CONV])
    assert np.abs(before[:, 0]).max() > 0 and np.abs(before[:, 1]).max() > 0
    # a decode step with row 1's slot live alone leaves row 0's tail
    bts = np.stack([np.asarray(_table(r)) for r in range(2)])
    _, k, v = df(
        SPEC, params, jnp.asarray(toks[:2, 20]), jnp.asarray(bts),
        jnp.asarray([10, 15], jnp.int32), k, v, jnp.asarray([False, True]))
    now = np.asarray(v.pools[CONV])
    np.testing.assert_array_equal(now[:, 0], before[:, 0])
    assert not np.array_equal(now[:, 1], before[:, 1])
    # sequence 0 goes; sequence 2 takes its row over and is the reference's
    k = llama.release_state_rows(k, jnp.asarray(
        [int(_table(0)[0]), -1], jnp.int32))
    assert list(np.asarray(k.rows.owner[0])) == [0, 1 + PAGES_PER_SEQ, 0]
    logits, k, v = _prefill(pf, params, toks, 2, 0, 11, k, v)
    _close(logits, want[2, 10])
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
    got = llama.reference_forward(SPEC, params, jnp.asarray(toks[0]))
    _close(got, want[0])
    assert float(jnp.abs(params["layers"][1]["q_norm"] - 1).max()) > 0.05
    spec, changed = MECHANISMS[name](SPEC, params)
    off = llama.reference_forward(spec, changed, jnp.asarray(toks[0]))
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


# ------------------------------------------------------------- the engine


def _engine(**kw):
    base = dict(
        page_size=PAGE, num_pages=64, max_pages_per_seq=PAGES_PER_SEQ,
        max_decode_slots=2, prefill_buckets=(16,), max_prefill_chunk_tokens=16,
        decode_steps_per_dispatch=4, seed=SEED,
    )
    base.update(kw)
    return InferenceEngine(SPEC, EngineConfig(**base))


async def _greedy(engine, prompt, n, out=None, ctx=None):
    out = [] if out is None else out
    async for item in engine.generate(
        {"token_ids": list(prompt), "sampling": {"temperature": 0.0},
         "stop_conditions": {"max_tokens": n, "ignore_eos": True}},
        ctx or Context(),
    ):
        assert item.get("finish_reason") != "error", item
        out.extend(item.get("token_ids") or [])
    return out


_jit_reference = jax.jit(llama.reference_forward, static_argnums=0)


def _greedy_reference(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((64,), np.int32)
        padded[: len(seq)] = seq
        lg = _jit_reference(SPEC, params, jnp.asarray(padded))
        seq.append(int(np.argmax(np.asarray(lg[len(seq) - 1]))))
    return seq[len(prompt):]


async def test_serves_through_the_engine_and_counts():
    """The toy model through the REAL engine (scheduler, a prompt of two
    chunks, bursts): the greedy stream is the whole forward pass's own;
    nothing is reused under a prefix; the rows go back; the gates a
    recurrent model sets are set; the counters read what hand arithmetic
    gives."""
    engine = _engine()
    fam = engine.fam
    assert isinstance(fam, GqaFamily) and fam.recurrent
    assert not fam.supports_prefix_reuse and not engine.allocator.prefix_cache
    for gate in ("ring_prefill", "spec_decode", "mesh", "page_transfer",
                 "multimodal"):
        assert not getattr(fam, f"supports_{gate}"), gate
    assert engine._prefill_walks == {"full": 0}
    prompt = [int(t) for t in np.arange(7, 7 + 21) % 96]  # two chunks
    want = _greedy_reference(engine.params, prompt, 6)
    assert await _greedy(engine, prompt, 6) == want
    assert await _greedy(engine, prompt, 6) == want
    assert engine.allocator._hash_page == {}
    assert engine.allocator.active_pages == 0
    # two prompts of 16 + 5 tokens: four prefill members, the second
    # chunk of each resumes a tail
    assert engine.recurrent_state == {
        "prefill_chunks": 4, "rows_resumed": 2}
    assert not engine.kda and not engine.ssd
    await engine.close()
    engine._metrics_publishes = 0
    for _ in range(34):  # two refreshes bring the device's counters over
        engine._publish_metrics()
    c = engine.state_counters()
    assert c == {"rows": 2, "rows_live": 0, "claims": 2, "row_missing": 0}
    snap = engine.profile_snapshot()
    assert snap["recurrent_state.prefill_chunks"]["calls"] == 4
    assert snap["recurrent_state.rows_resumed"]["calls"] == 2
    assert snap["recurrent_state.row_missing"]["calls"] == 0
    m = engine.moe_counters()
    # three expert layers, 2 x 21 prompt tokens, top-4 of 8, all held
    assert m["layers"] == 3 and m["prefill.assignments"] == 3 * 2 * 21 * 4
    assert m["prefill.assignments_held"] == m["prefill.assignments"]
    assert m["prefill.assignments_held"] == sum(
        m[f"prefill.expert.{i}"] for i in range(8))


@pytest.mark.parametrize("pipeline", [False, True], ids=["plain", "pipelined"])
async def test_streams_share_the_engine(pipeline):
    """Three prompts on two slots, one of them chunked behind running
    bursts: every stream is what it gets alone, pipelined or not, rows
    are claimed and freed as slots turn over, none goes missing."""
    prompts = [[3, 9, 27], [8, 64, 32, 5],
               [int(t) for t in np.arange(5, 5 + 37) * 7 % 96]]
    engine = _engine(pipeline_decode=pipeline, async_admissions=True)
    want = [_greedy_reference(engine.params, p, n)
            for p, n in zip(prompts, (12, 9, 6))]
    outs = await asyncio.gather(*(
        _greedy(engine, p, n) for p, n in zip(prompts, (12, 9, 6))))
    assert outs == want
    assert engine.allocator.active_pages == 0
    await engine.close()
    assert int(engine.k_pages.rows.stats[0, llama.STAT_MISSING]) == 0
    assert int(engine.k_pages.rows.stats[0, llama.STAT_CLAIMS]) == 3


async def test_preempt_and_resume_by_recomputation():
    """A batch stream preempted for an interactive one gives its row and
    pages back and resumes by prefilling its prompt and its output so far
    from an empty tail: the tokens of an undisturbed run."""
    prompt = [5, 11, 17, 23, 29]
    engine = _engine(max_decode_slots=1, prefill_buckets=(16, 32, 64),
                     max_prefill_chunk_tokens=64)
    want = _greedy_reference(engine.params, prompt, 24)
    got: list = []
    batch = asyncio.create_task(_greedy(
        engine, prompt, 24, out=got,
        ctx=Context(headers={PRIORITY_HEADER: "batch"})))
    while len(got) < 6:
        await asyncio.sleep(0.002)
    quick = await _greedy(engine, [2, 4, 6], 3)
    assert quick == _greedy_reference(engine.params, [2, 4, 6], 3)
    assert await batch == want
    assert sum(engine.preemptions.values()) >= 1
    assert engine.allocator.active_pages == 0
    await engine.close()
    assert int(engine.k_pages.rows.stats[0, llama.STAT_MISSING]) == 0
