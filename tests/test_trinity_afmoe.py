"""Trinity (``afmoe``) at toy widths on the CPU, against the benchmark's own
plain reference (``perfbench/references/gated_swa_moe.py``, loaded by
path: the same module the chip is held to, not a copy): gated, QK-normed
GQA whose window layers rotate and whose full layers carry no position, a
pool a kind, four norms a layer, the embedding's muP factor, a leading
dense layer, sigmoid routing with a selection-only bias beside a shared
expert. Programs on both sides of the toy window, the kernel and its XLA
twin, the shares of an expert-parallel split, the two window counters,
the loader's name map and the engine around it.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_contract import (
    CHUNKS, REPO, Family, _cache, _engine, _prefill, _programs, cases, run,
)

from dynamo_tpu.engine.config import LayerKind, ModelSpec
from dynamo_tpu.models import llama
from dynamo_tpu.models.family import GqaFamily, get_family

# the reference reads the published keys; the program reads SPEC. Four
# layers: a dense window layer, a window expert layer, a full (NoPE)
# expert layer, a window expert layer; a window of 8 tokens
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 4, "layers_kept": [0, 1, 2, 3],
    "layer_types": ["sliding_attention"] * 2 + [
        "full_attention", "sliding_attention"],
    "global_attn_every_n_layers": 4, "sliding_window": 8,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "mup_enabled": True, "n_group": 1,
    "topk_group": 1, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "vocab_size": 96, "tie_word_embeddings": False,
    "torch_dtype": "float32",
}
SPEC = ModelSpec.tiny_trinity()
WINDOW, FULL = 0, 1  # the kinds' places in SPEC.layer_kinds
PAGE, PAGES_PER_SEQ, T = 4, 16, 40
SEED = 13


def _whole():
    """The whole-sequence pass under a name of its own: a patched helper
    is read at TRACE time, and ``jax.jit`` finds a function it has traced
    before by its identity."""
    return jax.jit(lambda *a: llama.reference_forward(*a), static_argnums=0)


def _served(engine, snap, served, outs):
    """A prompt of two chunks longer than the window, the second time over
    a reused prefix: the window counters read what hand arithmetic gives
    and reach ``/metrics`` through the collector; three expert layers,
    top-2 of 8, all held."""
    from dynamo_tpu.engine.telemetry import REGISTRY, EngineCollector

    assert engine._prefill_walks == {"full": 0, "window": 8}
    held, dead = (engine.kv["window_layer_tokens"],
                  engine.kv["window_dead_tokens"])
    # a slot of 22 tokens and more is live at every dispatched step: at
    # least 14 of them past the window of 8, in three window layers
    assert held > 0 and held % 3 == 0 and dead % 3 == 0
    assert 14 / 22 <= dead / held < 1
    collector = EngineCollector(engine)
    collector.sample()
    text = REGISTRY.exposition().decode()
    line = next(
        ln for ln in text.splitlines()
        if ln.startswith("dynamo_engine_window_tokens_total{")
        and f'engine="{collector.label}"' in ln and 'what="dead"' in ln)
    assert float(line.rsplit(" ", 1)[1]) == dead
    m = engine.moe_counters()
    assert m["layers"] == 3
    assert m["prefill.assignments_held"] == m["prefill.assignments"] > 0
    assert m["decode.assignments_held"] == sum(
        m[f"decode.expert.{i}"] for i in range(8))


# the family's row of the contract (tests/family_contract.py): prompts on
# both sides of the toy window (a prompt of 5's decode steps cross its edge
# at the 8th token), kernel and XLA twin; chunks that start past the window
F = FAMILY = Family(
    spec=SPEC, config=CONFIG, reference="gated_swa_moe",
    seed=SEED, prompts=((5, "1"), (21, "1"), (21, "0")),
    chunked={"three-chunks": CHUNKS["three-chunks"]}, bursts_paths=(None,),
    engine_path=None, also={"serves": _served})


@pytest.mark.parametrize("case,kw", cases(F))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


def test_the_kinds_the_pools_and_the_family():
    """Two paged kinds of the same heads, a pool each over one page-id
    space; the window kind rotates, the full kind does not; nothing
    recurrent, so every gate of a plain GQA model stays open."""
    fam = get_family(SPEC)
    assert isinstance(fam, GqaFamily) and not fam.recurrent
    win, full = SPEC.layer_kinds
    assert win.window == 8 and win.rope and not full.window and not full.rope
    assert LayerKind(2, 1e4).rope  # every older kind rotates
    assert [SPEC.kind(i).window for i in range(4)] == [8, 8, 0, 8]
    assert SPEC.sandwich_norm and SPEC.qk_norm and SPEC.attn_gate
    assert not SPEC.has_recurrent and SPEC.has_attn_extras
    k, v = _cache(F)
    pages = 1 + 3 * PAGES_PER_SEQ
    assert k.pools[WINDOW].shape == v.pools[WINDOW].shape == (
        3, pages, 2, PAGE, 16)
    assert k.pools[FULL].shape == v.pools[FULL].shape == (1, pages, 2, PAGE, 16)
    assert k.counts.shape == (4, 2, 8 + 3)
    layer = llama.init_params(SPEC, jax.random.PRNGKey(0))["layers"]
    assert {"post_attn_norm", "post_mlp_norm", "q_norm", "k_norm",
            "w_gate_attn"} <= set(layer[0]) & set(layer[2])
    assert "w_gate" in layer[0] and "moe" in layer[1] and "shared" in layer[1]
    # the gains are drawn away from 1: a norm left out would show
    assert float(jnp.abs(layer[1]["post_mlp_norm"] - 1).max()) > 0.05


def test_a_lower_precision_than_stated_fails_the_tolerance(ref, model):
    """The same prefill in bfloat16 (weights, activations, pages) against
    the float32 reference misses the float32 tolerance by two orders and
    stays inside bfloat16's own."""
    _, toks, want = model
    spec = dataclasses.replace(SPEC, dtype="bfloat16")
    params = llama.init_params(spec, jax.random.PRNGKey(SEED))
    k, v = _cache(F, spec=spec)
    logits, k, v = _prefill(F, _programs(F)[0], params, toks, 1, 0, 21, k, v, 32, spec)
    low = np.asarray(ref.forward(
        dict(CONFIG, torch_dtype="bfloat16"), SEED, toks,
        np.tile(np.arange(T), (3, 1))))[1, 20]
    lg = np.asarray(logits, np.float32)
    assert np.abs(lg - want[1, 20]).max() > 30 * 3e-4
    assert np.sqrt(np.mean((lg - low) ** 2)) < 0.1 * np.sqrt(np.mean(low ** 2))


# each moves ONE thing of the program away from the published layer; the
# comparison with the reference must then fail
def _swapped(spec):
    win, full = spec.layer_kinds
    return dataclasses.replace(spec, layer_kinds=(
        dataclasses.replace(win, rope=False),
        dataclasses.replace(full, rope=True)))


MOVED = {
    "the_flags_swapped": _swapped,
    "no_window": lambda s: dataclasses.replace(s, layer_kinds=(
        dataclasses.replace(s.layer_kinds[0], window=0), s.layer_kinds[1])),
    "no_embedding_factor": lambda s: dataclasses.replace(
        s, embedding_multiplier=1.0),
    "weights_not_renormalised": lambda s: dataclasses.replace(
        s, norm_topk_prob=False),
}


@pytest.mark.parametrize("name", sorted(MOVED))
def test_every_published_mechanism_moves_the_logits(model, name):
    params, toks, want = model
    spec = MOVED[name](SPEC)
    got = np.asarray(_whole()(spec, params, jnp.asarray(toks[1])))
    assert np.abs(got - want[1]).max() > 30 * 3e-4, name


@pytest.mark.parametrize("gain", ["post_attn_norm", "post_mlp_norm"])
def test_either_output_norm_dropped_fails(model, monkeypatch, gain):
    """A program that adds the mixer's (or the FFN's) output to the stream
    unnormed, as every other family's does, is not this model; with both
    norms it is the reference's."""
    params, toks, want = model
    real = llama._residual

    def dropped(spec, lp, x, y, name):
        if name == gain:
            return llama._add(x, y)
        return real(spec, lp, x, y, name)

    F.close(_whole()(SPEC, params, jnp.asarray(toks[1])), want[1])
    monkeypatch.setattr(llama, "_residual", dropped)
    got = np.asarray(_whole()(SPEC, params, jnp.asarray(toks[1])))
    assert np.abs(got - want[1]).max() > 30 * 3e-4


def test_the_gate_and_the_parts_lie_where_the_reference_puts_them(model):
    """One piece at a time: the gate's weights, the q gain, each output
    norm's gain and the selection bias each move the program's logits
    (none is a dead parameter), and the bias moves the picks and not the
    weights."""
    from dynamo_tpu.models import moe

    params, toks, want = model
    fwd = _whole()
    for li, key in ((0, "w_gate_attn"), (2, "w_gate_attn"), (1, "q_norm"),
                    (2, "k_norm"), (0, "post_attn_norm"),
                    (3, "post_mlp_norm")):
        layers = list(params["layers"])
        layers[li] = dict(layers[li], **{key: layers[li][key] * 0.5})
        got = np.asarray(fwd(SPEC, dict(params, layers=layers),
                             jnp.asarray(toks[1])))
        assert np.abs(got - want[1]).max() > 1e-2, (li, key)
    lp = params["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 64))
    ids, w = moe.route(SPEC, lp, x)
    ids0, w0 = moe.route(SPEC, dict(lp, score_bias=lp["score_bias"] * 0), x)
    assert (np.asarray(ids) != np.asarray(ids0)).any()
    same = (np.asarray(ids) == np.asarray(ids0)).all(axis=1)
    np.testing.assert_allclose(np.asarray(w)[same], np.asarray(w0)[same])
    # the chosen scores renormalised, then times route_scale
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.826, rtol=1e-5)


def test_the_shares_add_up(ref, model):
    """Four chips of two routed experts each make the uncut layer: BEFORE
    the output norm, the routed parts summed and the shared expert counted
    ONCE are the uncut reference's expert layer. And the program's share
    (two experts from the third) is the reference's share."""
    _, toks, _ = model
    cfg = dict(CONFIG, num_hidden_layers=2, layers_kept=[0, 1])
    uncut = dict(cfg, experts={"published": 8, "held": 8, "first": 0})
    full = ref.Weights(uncut, SEED)
    x = ref._run(ref._embed_rows, full.embed(), toks, mult=8.0, quant=None)
    x = ref.layer(full, 0, [x])[0]
    x1 = ref._run(ref._attention, x, full.attention(1),
                  **ref._attention_statics(full.m, 1), quant=None)
    full_experts = full.experts(1)
    whole = np.asarray(ref.moe_out(full.m, x1, full_experts))

    def share(first, shared):
        w = ref.Weights(dict(cfg, experts={
            "published": 8, "held": 2, "first": first}), SEED)
        ew = w.experts(1)
        for key in ("e_gate", "e_up", "e_down"):  # the uncut layer's own
            ew[key] = full_experts[key][first: first + 2]
        return np.asarray(ref.moe_out(w.m, x1, ew, shared=shared))

    routed = [share(first, False) for first in (0, 2, 4, 6)]
    shared_once = share(0, True) - routed[0]
    assert np.abs(shared_once).max() > 1e-3
    # a toy router that the bias all but decides: not one share alone adds
    assert sum(np.abs(r).max() > 0 for r in routed) >= 2
    F.close(sum(routed) + shared_once, whole, tol=1e-4)
    # counted with every share instead, the shared expert is four times it
    assert np.abs(
        sum(share(first, True) for first in (0, 2, 4, 6)) - whole
    ).max() > 1e-2

    held = dict(CONFIG, experts={"published": 8, "held": 2, "first": 2})
    spec = dataclasses.replace(SPEC, held_experts=(2, 2))
    params = llama.init_params(spec, jax.random.PRNGKey(SEED))
    assert params["layers"][1]["moe"]["w_gate"].shape == (2, 64, 32)
    got = _whole()(spec, params, jnp.asarray(toks[0]))
    want = ref.forward(held, SEED, toks, np.tile(np.arange(T), (3, 1)))
    F.close(got, np.asarray(want)[0])


def test_the_window_counters_against_a_hand_count():
    """``kv.window_layer_tokens`` / ``.window_dead_tokens`` over a
    hand-built burst: three window layers of window 8; a live slot's length
    grows by one a step; an inactive slot counts nothing."""
    engine = _engine(F)
    assert engine._window_layers == {8: 3}

    def burst(seq_lens, n_burst=2):
        lens = np.asarray(seq_lens, np.int32)
        return {"seq_lens": lens, "active": lens > 1, "n_burst": n_burst}

    def counts():
        snap = engine.profile_snapshot()
        return (snap["kv.window_layer_tokens"]["calls"],
                snap["kv.window_dead_tokens"]["calls"])

    assert counts() == (0, 0)
    # slot 1: 6 then 7 tokens, all inside the window. Slot 3: 18 then 19,
    # of which 10 and 11 lie more than 8 behind
    engine._count_decode_kv(burst([1, 6, 1, 18]))
    assert counts() == (3 * (6 + 7 + 18 + 19), 3 * (10 + 11))
    engine._count_decode_kv(burst([1, 1, 1, 1]))
    assert counts() == (150, 63)
    engine._count_decode_kv(burst([9], n_burst=1))  # one token past
    assert counts() == (150 + 3 * 9, 63 + 3 * 1)
    engine.reset_profile_window()
    assert counts() == (0, 0)
    # a model without window layers has no such family
    # (tests/test_engine_spans.py holds a dense engine's keys to a list)


def test_the_output_norms_have_a_region_of_their_own(model):
    """A decode program of the model opens ``norm_out`` (two norms a
    layer) beside ``norm``; the gate's sigmoid stays under ``attn_out``;
    the dense toy model opens no ``norm_out``."""
    def text(spec):
        params = jax.eval_shape(
            lambda: llama.init_params(spec, jax.random.PRNGKey(0)))
        k, v = jax.eval_shape(lambda: llama.init_cache(spec, 9, 4))
        S, i32 = jax.ShapeDtypeStruct, jnp.int32
        return jax.jit(llama.decode_forward_impl, static_argnums=(0,)).lower(
            spec, params, S((2,), i32), S((2, 8), i32), S((2,), i32), k, v,
            S((2,), jnp.bool_)).as_text(debug_info=True)

    own = text(SPEC)
    assert "norm_out/rsqrt" in own and "/norm/rsqrt" in own
    # a layer's two input norms, two output norms and its q and k norms,
    # and the final norm
    assert own.count("stablehlo.rsqrt") == 6 * SPEC.num_layers + 1
    assert "attn_out/logistic" in own or "attn_out/jit(sigmoid)" in own
    assert "norm_out" not in text(ModelSpec.tiny())


# ------------------------------------------------------------- the loader


def test_the_checkpoint_names_round_trip(tmp_path):
    """A synthetic ``afmoe`` checkpoint at toy size: every tensor of the
    name map lands, none is left over, ``post_attention_layernorm`` is the
    attention's OUTPUT norm, the config's keys come back as the spec."""
    from safetensors import safe_open

    from dynamo_tpu.models import loader

    params = llama.init_params(SPEC, jax.random.PRNGKey(3))
    loader.save_params(SPEC, params, str(tmp_path))
    with safe_open(str(tmp_path / "model.safetensors"), "numpy") as f:
        names = set(f.keys())
        post = f.get_tensor("model.layers.1.post_attention_layernorm.weight")
        gate = f.get_tensor("model.layers.1.self_attn.gate_proj.weight")
    np.testing.assert_array_equal(
        post, np.asarray(params["layers"][1]["post_attn_norm"]))
    assert gate.shape == (64, 64)
    assert names == set(loader._dest_map_afmoe(SPEC))
    for want in (
        "model.embed_tokens.weight", "model.norm.weight", "lm_head.weight",
        "model.layers.0.input_layernorm.weight",
        "model.layers.0.pre_mlp_layernorm.weight",
        "model.layers.0.post_mlp_layernorm.weight",
        "model.layers.0.mlp.gate_proj.weight",
        "model.layers.2.self_attn.q_norm.weight",
        "model.layers.2.self_attn.k_norm.weight",
        "model.layers.1.mlp.router.gate.weight",
        "model.layers.1.mlp.expert_bias",
        "model.layers.1.mlp.shared_experts.down_proj.weight",
        "model.layers.3.mlp.experts.7.up_proj.weight",
    ):
        assert want in names, want
    assert not [n for n in names if "layers.0.mlp.experts" in n]
    with open(tmp_path / "config.json") as f:
        cfg = json.load(f)
    assert cfg["model_type"] == "afmoe" and cfg["mup_enabled"] is True
    assert cfg["layer_types"] == CONFIG["layer_types"]
    assert (cfg["route_scale"], cfg["num_dense_layers"],
            cfg["num_shared_experts"], cfg["sliding_window"]) == (
        2.826, 1, 1, 8)
    spec, loaded = loader.load_model_dir(str(tmp_path), name=SPEC.name)
    assert spec == SPEC
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert loaded["layers"][1]["moe"]["router"].dtype == jnp.float32


def test_the_published_config_maps_to_the_spec():
    """The catalog row's keys (``layer_types``, ``sliding_window``,
    ``num_dense_layers``, ``route_scale``, ``route_norm``, ``score_func``,
    ``mup_enabled``, ``num_shared_experts``) give the kinds, the pattern
    and the routing the cell's ``model_spec`` states at the kept layers."""
    from dynamo_tpu.models import loader

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    spec = loader.spec_from_hf_config(row["config"], name="trinity-mini")
    with open(os.path.join(REPO, "perfbench/configs/trinity-mini.json")) as f:
        cfg = json.load(f)
    kept = cfg["layers_kept"]
    want = ModelSpec(num_layers=len(kept), **cfg["model_spec"])
    assert spec.num_layers == 32 and spec.layer_pattern.count(1) == 8
    assert tuple(spec.layer_pattern[i] for i in kept) == want.layer_pattern
    assert spec.layer_kinds == want.layer_kinds
    assert spec.first_k_dense == 2  # of which the cut keeps layer 0
    for key in cfg["model_spec"]:
        if key not in ("layer_kinds", "layer_pattern", "held_experts",
                       "first_k_dense"):
            assert getattr(spec, key) == getattr(want, key), key
    assert (spec.hidden_size, spec.num_heads, spec.head_dim,
            spec.intermediate_size, spec.vocab_size) == (
        2048, 32, 128, 6144, 200192)
    assert spec.embedding_multiplier == 2048 ** 0.5
    with pytest.raises(NotImplementedError):
        loader.spec_from_hf_config(dict(row["config"], score_func="softmax"))
    with pytest.raises(NotImplementedError):
        loader.spec_from_hf_config(dict(row["config"], n_group=4))
