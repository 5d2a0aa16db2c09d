"""LongCat-Flash's layer at toy widths on the CPU: shortcut-connected
double layers (two latent attentions, two dense FFNs, one expert layer fed
by the first FFN's input and added behind the second), identity experts
behind the FFN experts, a softmax router with a correction bias and
un-normalised weights, both MLA scalars. The program (``models/mla.py:
_layer``, a pool of latent pages a sub-layer, ``models/moe.py``) against
the benchmark's plain reference (``perfbench/references/scmoe_latent.py``),
which shares no code with it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_contract import (
    PROMPT, Family, _cache, _prefill, _programs, _tables, _whole, case, cases,
    run, two_slots,
)


from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.models import mla, moe
from dynamo_tpu.models.family import MlaFamily

# the reference reads the published keys; the program reads SPEC
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "rope_theta": 10000000, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "ffn_hidden_size": 96,
    "expert_ffn_hidden_size": 32, "n_routed_experts": 8,
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "routed_scaling_factor": 6, "rms_norm_eps": 1e-5, "vocab_size": 96,
    "num_layers": 2, "attention_method": "MLA", "torch_dtype": "float32",
    "experts": {"published": 8, "held": 2, "first": 2},
}
K = CONFIG["moe_topk"]


def _spec(**kw) -> ModelSpec:
    return ModelSpec.tiny_longcat(held_experts=(2, 2), **kw)


SPEC = _spec()
PAGE, PAGES_PER_SEQ, T = 4, 16, 40
SEED = 11


def _prefilled(k, counts, chunks):
    """Every counted row's picks are identity or FFN experts, k a row."""
    total = sum(n for _, n in chunks)
    c = np.asarray(counts)[:, 0]
    assert (c[:, 2] + c[:, 3] == K * total).all()
    assert (c[:, 4] == K * total).all() and (c[:, -1] == len(chunks)).all()


def _packed(k, counts):
    c = np.asarray(counts)[:, 0]
    assert (c[:, 2] + c[:, 3] == K * 25).all()


def _burst(k, counts, steps):
    """Every layer counted each step of the burst once."""
    assert (np.asarray(counts)[:, 1, -1] == steps).all()


def _two_slots(grew, steps):
    """Both sub-layers' pools under one table; the empty slot is counted
    nowhere."""
    grew = grew[:, 1]
    assert (grew[:, -1] == steps).all()
    assert (grew[:, 4] == steps * 2 * K).all()  # two counted rows a step
    assert (grew[:, 2] + grew[:, 3] == steps * 2 * K).all()  # zero + ffn picks
    assert (grew[:, :2].sum(axis=1) <= grew[:, 3]).all()  # held among ffn


def _served(engine, snap, served, outs):
    """Chunked prefill over both pools behind ONE prompt of 21 tokens;
    ``moe_counters()`` splits the picks."""
    assert isinstance(engine.fam, MlaFamily)
    assert type(engine.k_pages) is tuple and len(engine.k_pages) == 2
    assert engine.chunked_prefill["chunks"] == 2
    assert engine.prefill_kv["dispatches.latent"] == 2
    c = engine.moe_counters()
    assert c["layers"] == 2
    assert c["prefill.steps"] == 2 and c["prefill.assignments"] == 2 * 21 * K
    for phase in ("prefill", "decode"):
        assert (c[f"{phase}.zero_picks"] + c[f"{phase}.ffn_picks"]
                == c[f"{phase}.assignments"])
        assert c[f"{phase}.assignments_held"] <= c[f"{phase}.ffn_picks"]
        assert sum(c[f"{phase}.expert.{i}"] for i in range(2)) == c[
            f"{phase}.assignments_held"]
    assert c["decode.zero_picks"] > 0 and c["decode.ffn_picks"] > 0
    assert snap["moe.decode.zero_picks"]["calls"] == c["decode.zero_picks"]


# the family's row of the contract (tests/family_contract.py): a latent
# family of double layers (a pool a sub-layer under one table; the pair is
# the pools and the experts' counters); its pack's third member is padding
F = FAMILY = Family(
    spec=SPEC, config=CONFIG, reference="scmoe_latent",
    seed=SEED, prompts=(),
    chunked={"single": [(0, 13)],
             "three-chunks": [(0, 16), (16, 16), (32, 7)]},
    packs=([(0, 0, 16), (1, 0, 9), (0, 0, 0)],), served=((PROMPT, 6),),
    also={"chunked": _prefilled, "pack": _packed, "two-slots": _two_slots,
          "bursts": _burst, "serves": _served})


@pytest.mark.parametrize("case,kw", cases(
    F, case("two-slots-xla", two_slots, path="0"),
    case("two-slots-kernel", two_slots, path="1")))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


def test_the_cache_is_a_pool_a_sub_layer():
    cache, counts = _cache(F)
    assert type(cache) is tuple and len(cache) == 2
    assert all(p.shape[:3] == (2, 1 + 3 * PAGES_PER_SEQ, PAGE) for p in cache)
    assert counts.shape == (2, 2, 2 + 5)  # sizes, zero, ffn, total, touched, steps
    assert mla.sub_pools(cache) is cache
    one = mla.init_cache(ModelSpec.tiny_deepseek(), 4, PAGE)
    assert mla.sub_pools(one) == (one,)


# ------------------------------------------------------------------ router


def _router_layer():
    """A layer whose router reads its input's first three features: a row
    of +a on feature 0 picks FFN experts 0-2, on feature 1 identity experts
    8-10, on feature 2 experts 1, 2 and identity expert 9."""
    spec = ModelSpec.tiny_longcat()
    lp = moe.init_moe_layer(spec, jax.random.PRNGKey(5))
    router = np.zeros((64, 12), np.float32)
    router[0, [0, 1, 2]] = [3.0, 2.0, 1.0]
    router[1, [8, 9, 10]] = [3.0, 2.0, 1.0]
    router[2, [1, 2, 9]] = [1.0, 2.0, 3.0]
    lp["router"] = jnp.asarray(router)
    lp["score_bias"] = jnp.zeros((12,), jnp.float32)
    x = np.zeros((3, 64), np.float32)
    x[0, 0] = x[1, 1] = x[2, 2] = 4.0
    return spec, lp, jnp.asarray(x)


def test_the_router_picks_by_bias_and_weighs_by_probability():
    spec, lp, x = _router_layer()
    topi, topv = moe.route(spec, lp, x)
    np.testing.assert_array_equal(
        np.asarray(topi), [[0, 1, 2], [8, 9, 10], [9, 2, 1]])
    p = np.asarray(jax.nn.softmax(x @ lp["router"], axis=-1))
    want = 6.0 * np.take_along_axis(p, np.asarray(topi), axis=-1)
    np.testing.assert_allclose(np.asarray(topv), want, rtol=1e-6)
    assert (np.asarray(topv).sum(axis=-1) < 6.0).all()  # NOT renormalised
    # the bias moves the picks and never the weights
    bias = np.zeros((12,), np.float32)
    bias[11] = 1.0
    topi2, topv2 = moe.route(spec, dict(lp, score_bias=jnp.asarray(bias)), x)
    assert (np.asarray(topi2)[:, 0] == 11).all()
    np.testing.assert_allclose(
        np.asarray(topv2)[:, 0], 6.0 * p[:, 11], rtol=1e-6)


def test_identity_experts_add_the_tokens_own_input():
    """Rows whose picks are identity experts 0, 1 and 3 of 3: the layer is
    the held FFN experts' weighted sum plus ``(sum of the identity picks'
    weights) x the row``; the counters split the counted rows' picks."""
    spec, lp, x = _router_layer()
    topi, topv = (np.asarray(a) for a in moe.route(spec, lp, x))
    y, row = moe.moe_mlp(
        spec, lp, x, counted=jnp.asarray([True, True, True]))
    want = np.zeros((3, 64), np.float32)
    xs = np.asarray(x)
    for t in range(3):
        for e, w in zip(topi[t], topv[t]):
            if e >= spec.num_experts:
                want[t] += w * xs[t]
                continue
            h = np.asarray(jax.nn.silu(xs[t] @ lp["w_gate"][e])) * np.asarray(
                xs[t] @ lp["w_up"][e])
            want[t] += w * np.asarray(h @ lp["w_down"][e])
    F.close(y, want, tol=1e-5)
    np.testing.assert_allclose(np.asarray(y)[1], topv[1].sum() * xs[1],
                               rtol=1e-6)  # all identity: a scaled copy
    row = np.asarray(row)
    assert list(row[8:]) == [4, 5, 9, 3]  # zero, ffn, total, touched
    assert list(row[:8]) == [1, 2, 2, 0, 0, 0, 0, 0]
    # a row that is not counted is in neither
    _, row = moe.moe_mlp(
        spec, lp, x, counted=jnp.asarray([True, False, True]))
    assert list(np.asarray(row)[8:10]) == [1, 5]


@pytest.mark.parametrize("preset", ["tiny_moe", "tiny_deepseek"])
def test_a_spec_without_zero_experts_routes_as_before(preset):
    """Softmax and sigmoid routing are untouched: the picks and weights of
    ``jax.lax.top_k`` over the published formulas, bit for bit, the
    counters' row ``n_held + 2`` wide and no ``moe_zero`` in the program."""
    spec = getattr(ModelSpec, preset)()
    lp = moe.init_moe_layer(spec, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (9, spec.hidden_size))
    topi, topv = moe.route(spec, lp, x)
    logits = x.astype(jnp.float32) @ lp["router"]
    k = spec.num_experts_per_token
    if spec.moe_scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        choice = s + lp["score_bias"]
        G, gsz = spec.n_group, spec.num_experts // spec.n_group
        best = jax.lax.top_k(choice.reshape(9, G, gsz), 2)[0].sum(-1)
        kept = jax.lax.top_k(best, spec.topk_group)[1]
        mask = jnp.any(kept[:, :, None] == jnp.arange(G), axis=1)
        choice = jnp.where(jnp.repeat(mask, gsz, axis=-1), choice, 0.0)
        idx = jax.lax.top_k(choice, k)[1]
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + spec.moe_norm_eps)
        w = w * spec.routed_scaling_factor
    else:
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(topv), np.asarray(w))
    _, row = moe.moe_mlp(spec, lp, x, counted=jnp.ones((9,), bool))
    assert row.shape == (spec.num_experts + 2,)
    text = jax.jit(
        lambda lp_, x_: moe.moe_mlp(spec, lp_, x_)).lower(lp, x).as_text(
            debug_info=True)
    assert "moe_zero" not in text and "moe_route" in text


def test_the_regions_tell_the_shortcut_from_the_dense_ffns(model):
    """A traced decode program names the expert layer's regions, the
    identity term and the counters beside the dense FFNs' ``mlp`` and the
    attentions' own."""
    params, _, _ = model
    cache, counts = _cache(F)
    text = mla.decode_forward.lower(
        SPEC, params, jnp.zeros((3,), jnp.int32), _tables(F, [0, 1, None]),
        jnp.ones((3,), jnp.int32), cache, jnp.zeros((3,), bool),
        counts=counts,
    ).as_text(debug_info=True)
    for name in ("moe_route", "moe_dispatch", "moe_grouped", "moe_combine",
                 "moe_zero", "moe_count", "mlp", "latent_q", "latent_kv",
                 "latent_absorb", "attn_out", "attn_kv"):
        assert f"/{name}" in text or f"{name}/" in text, name


# ------------------------------------------------------------------ shares


def test_the_shares_add_up(ref):
    """Four chips of two FFN experts each make the uncut layer: the FFN
    parts summed and the identity experts' term counted ONCE. And the
    program's share is the reference's share."""
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 10), 0, 96))
    cfg = dict(CONFIG, num_layers=1)
    uncut = dict(cfg, experts={"published": 8, "held": 8, "first": 0})
    full = ref.Weights(uncut, SEED)
    xs = [np.asarray(ref._embed_rows(full.embed(), toks, quant=None))]
    whole = ref.layer(full, 0, xs)[0]
    full_experts = full.experts(0)

    class Share(ref.Weights):
        """A share whose experts are the uncut layer's own."""

        def experts(self, i):
            ew = super().experts(i)
            a, n = self.m["first"], self.m["held"]
            for k in ("e_gate", "e_up", "e_down"):
                ew[k] = full_experts[k][a: a + n]
            return ew

    def share(first, identity):
        w = Share(dict(cfg, experts={
            "published": 8, "held": 2, "first": first}), SEED)
        return ref.layer(w, 0, xs, identity=identity)[0]

    # what every share computes alike: both sub-layers without the shortcut
    _, x2 = ref._sub_layer(full, 0, 0, xs, None)
    _, base = ref._sub_layer(full, 0, 1, x2, None)
    ffn_parts = [share(first, False) - base[0] for first in (0, 2, 4, 6)]
    identity_once = share(0, True) - share(0, False)
    assert np.abs(identity_once).max() > 1e-3
    F.close(base[0] + sum(ffn_parts) + identity_once, whole, tol=1e-4)
    # counted with every share instead, the identity term is four times it
    assert np.abs(
        base[0] + sum(share(f, True) - base[0] for f in (0, 2, 4, 6)) - whole
    ).max() > 1e-3
    spec = _spec(num_layers=1)
    params = mla.init_params(spec, jax.random.PRNGKey(SEED))
    got = _whole(spec, params, jnp.asarray(toks[0]))
    want = ref.forward(cfg, SEED, toks, np.arange(10)[None].repeat(2, 0))
    F.close(got, np.asarray(want)[0])


def test_a_lower_precision_fails_a_tolerance(model, ref):
    """The check's control: the same pass with fp8 weights is outside a
    tolerance that the program's own difference is well inside."""
    params, toks, want = model
    got = np.stack([np.asarray(_whole(
        SPEC, params, jnp.asarray(t))) for t in toks])
    control = np.asarray(ref.forward(
        CONFIG, SEED, toks, np.tile(np.arange(T), (3, 1)), quant="fp8"))

    def rel_rms(a):
        return float(np.sqrt(np.mean((a - want) ** 2) / np.mean(want ** 2)))

    assert rel_rms(got) < 1e-4 < 0.02 < rel_rms(control)


def test_the_scalars_are_in_the_comparison(model, ref):
    """Without either MLA scalar the reference gives other logits: the
    program's agreement above is not blind to them."""
    _, toks, want = model
    at = np.tile(np.arange(T), (3, 1))
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora"):
        other = np.asarray(ref.forward(dict(CONFIG, **{key: False}), SEED,
                                       toks, at))
        assert np.abs(other - want).max() > 1e-2, key


# ------------------------------------------------------------------ engine


async def test_pages_move_with_both_pools():
    """``extract_pages`` / ``insert_pages`` carry a page of BOTH sub-layers'
    pools: what a transfer or the KVBM tiers move is the whole page."""
    fam = MlaFamily()
    k, v = fam.init_cache(SPEC, 8, PAGE)
    k = tuple(
        p.at[:, 3].set(float(j + 1)) for j, p in enumerate(k))
    blocks, inert = fam.extract_pages(k, v, jnp.asarray([3, 5]))
    assert blocks.shape[:2] == (4, 2)  # two pools x two layers
    assert float(blocks[0, 0].min()) == 1.0 and float(blocks[2, 0].max()) == 2.0
    k2, _ = fam.init_cache(SPEC, 8, PAGE)
    k2, _ = fam.insert_pages(k2, v, jnp.asarray([6, 7]), blocks, inert)
    assert float(k2[0][:, 6].min()) == 1.0 and float(k2[1][:, 6].max()) == 2.0
    assert float(jnp.abs(k2[0][:, 7]).max()) == 0.0


def test_a_checkpoint_round_trips_through_the_published_names(tmp_path):
    """``save_params`` -> ``load_model_dir``: LongCat-Flash's own config
    keys (``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
    ``moe_topk``, ``zero_expert_num``, both scalars) and tensor names (the
    module lists ``self_attn.J``, ``mlps.J``, ``input_layernorm.J``,
    ``mlp.router.classifier``) give the same model."""
    import json

    from dynamo_tpu.models.loader import (
        load_model_dir, save_params, spec_from_hf_config,
    )

    spec = ModelSpec.tiny_longcat()
    params = mla.init_params(spec, jax.random.PRNGKey(13))
    save_params(spec, params, str(tmp_path))
    with open(tmp_path / "config.json") as f:
        cfg = json.load(f)
    assert cfg["model_type"] == "longcat_flash"
    assert "num_hidden_layers" not in cfg and "intermediate_size" not in cfg
    assert (cfg["num_layers"], cfg["ffn_hidden_size"],
            cfg["expert_ffn_hidden_size"], cfg["moe_topk"],
            cfg["zero_expert_num"], cfg["n_routed_experts"]) == (
        2, 96, 32, 3, 4, 8)
    spec2, params2 = load_model_dir(str(tmp_path), dtype="float32")
    assert spec2.shortcut_moe and spec2.is_mla and spec2.zero_experts == 4
    assert spec2.moe_scoring == "softmax_bias" and not spec2.norm_topk_prob
    assert spec2.mla_scale_q_lora and spec2.mla_scale_kv_lora
    assert (spec2.intermediate_size, spec2.moe_intermediate_size,
            spec2.num_experts_per_token, spec2.routed_scaling_factor) == (
        96, 32, 3, 6.0)
    assert not spec2.rope_interleave  # exported layout is half-split
    tokens = jnp.asarray(np.arange(9) % spec.vocab_size, jnp.int32)
    F.close(_whole(spec2, params2, tokens),
           np.asarray(_whole(spec, params, tokens)), tol=1e-4)
    # the published config as the catalog has it
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12,
    }
    big = spec_from_hf_config(published, name="longcat-flash-chat")
    assert big.shortcut_moe and big.router_outputs == 768
    assert (big.num_layers, big.sub_layers, big.intermediate_size,
            big.moe_intermediate_size, big.num_experts_per_token) == (
        28, 2, 12288, 2048, 12)
    assert big.rope_interleave and not big.norm_topk_prob
    assert not big.tie_embeddings and big.rope_theta == 1e7


def test_the_verify_pass_writes_both_pools(model, ref):
    """The speculative verify (token-granular writes into BOTH sub-layers'
    pools from mid-page): the targets at all W positions are the
    reference's argmax, and a decode step behind it reads what it wrote."""
    params, toks, want = model
    cache, counts = _cache(F)
    _, cache, counts = _prefill(
        F, _programs(F)[0], params, toks, 0, 0, 10, cache, counts)
    fed = np.zeros((2, 4), np.int32)
    fed[0] = toks[0, 10:14]
    targets, cache, counts = mla.verify_forward(
        SPEC, params, jnp.asarray(fed), _tables(F, [0, 1]),
        jnp.asarray([10, 0], jnp.int32), cache,
        jnp.asarray([4, 0], jnp.int32), counts=counts,
    )
    np.testing.assert_array_equal(
        np.asarray(targets)[0], want[0, 10:14].argmax(axis=-1))
    logits, cache, counts = mla.decode_forward(
        SPEC, params, jnp.asarray([toks[0, 14], 0, 0], jnp.int32),
        _tables(F, [0, 1, None]), jnp.asarray([15, 1, 1], jnp.int32), cache,
        jnp.asarray([True, False, False]), counts=counts)
    F.close(logits[0], want[0, 14])
