"""The short-convolution reference (``shortconv_moe``: gated short
convolutions beside QK-normed GQA layers over bias-routed experts all
held) against a tiny engine on the CPU through the benchmark's own output
check, the terms the comparison must catch, the configuration's file
against the catalog row and the program's parameter count, the byte counts
against ISSUE 45's arithmetic, the readers of the cell's four new
per-layer metrics, and the whole command rehearsed on a toy cell. Toy
sizes in float32: what holds on the chip at published widths is in
PERF.md."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

CELL = "lfm2-24b.reasoning"
NEW = ["kernels.shortconv_experts_hbm_share",
       "kernels.shortconv_mix_decode_hbm_share",
       "model.shortconv_expert_decode_share",
       "model.shortconv_mixer_decode_share",
       "model.shortconv_decode_step_ms"]
# NOT ``model.decode_step_ms`` (a kernel a layer a step: the conv layers
# run none, so the cell reports its own step, NEW[4]) and NOT ``out_tok_s``
# (its runs spread past half the bound on the ``reasoning`` mix's window),
# hence not the two per-layer metrics that move ``out_tok_s`` either
# (``cache.pages_peak_share``, ``moe.expert_load_max_over_mean``)
JOINED = {"tpot_p50_ms", "engine.compiles_in_window",
          "device.idle_share", "device.peak_mem_share",
          "moe.tokens_per_expert_step"}

# the published keys at toy widths: 8 layers published, the first 4 kept
# (a dense conv layer, an expert attention layer, two expert conv layers)
TOY = {
    "name": "toy-lfm2", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 4,
    "layers_kept": [0, 1, 2, 3],
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "intermediate_size": 96,
    "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 4,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 96,
    "torch_dtype": "float32", "reference": "shortconv_moe",
    "model_spec": {
        "rope_theta": 1e6, "rms_eps": 1e-5, "tie_embeddings": True,
        "qk_norm": True,
        "layer_kinds": [{"num_kv_heads": 2, "rope_theta": 1e6},
                        {"num_kv_heads": 0, "rope_theta": 0.0,
                         "mixer": "conv"}],
        "layer_pattern": [1, 0, 1, 1], "conv_taps": 3,
        "num_experts": 8, "num_experts_per_token": 4,
        "moe_intermediate_size": 32, "moe_scoring": "sigmoid",
        "routed_scaling_factor": 1.0, "moe_norm_eps": 1e-6,
        "first_k_dense": 1,
    },
    "engine": {
        "page_size": 8, "num_pages": 96, "max_pages_per_seq": 16,
        "max_decode_slots": 4, "prefill_buckets": [32, 64],
        "prefill_pack_size": 2, "max_prefill_chunk_tokens": 64,
        "decode_steps_per_dispatch": 4, "kv_dtype": "bf16",
        "guided_mode": "off",
    },
    "correct": {
        "samples": 3, "min_tokens": 30, "max_tokens": 60, "decode_steps": 3,
        "padded_tokens": 72, "decode_layers": 4,
        "limits": {"prefill_rel_rms": 2e-4, "decode_rel_rms": 2e-4,
                   "packed_prefill_rel_rms": 2e-4, "served_token_gap": 0.01},
    },
    "trace_names": {
        "programs": {"decode": ["decode_steps"],
                     "prefill": ["prefill_forward"]},
        "full_attention_ops": ["attn_full"], "expert_ops": ["gmm"],
    },
}

# each changes one published key of the REFERENCE's config: the program,
# which has the published form, must then come out as not correct
FAULTS = {
    "another_rope_base": {
        "rope_parameters": {"rope_theta": 10000, "rope_type": "default"}},
    "routed_scale_doubled": {"routed_scaling_factor": 2},
    "weights_not_normed": {"norm_topk_prob": False},
    "another_norm_eps": {"norm_eps": 1e-2},
    "the_attention_layer_elsewhere": {
        "layer_types": ["conv", "conv", "full_attention", "conv"] * 2},
}


@pytest.fixture(scope="module")
def readings():
    """One tiny engine and the reference, read once."""
    os.environ["DYNAMO_PALLAS"] = "1"  # the kernels, interpreted
    try:
        from dynamo_tpu.engine.core import InferenceEngine
        from lib import correct
        from lib import stack as stk

        seed = 2**31 + 45  # a seed past 32 signed bits
        cfg = stk.engine_config(TOY, seed, profile=False)
        engine = InferenceEngine(stk.model_spec(TOY), cfg)
        ref = correct.load_reference(TOY)
        smp = correct.sample(TOY, cfg, list(engine._prefill_shapes), seed)
        wseed = stk.engine_seed(seed)
        rows = correct.served_sample(TOY, engine, seed)
        got = correct.engine_logits(engine, smp)
        packed, chosen = correct.served_outputs(engine, rows)
        yield {
            "correct": correct, "ref": ref, "smp": smp, "wseed": wseed,
            "limits": TOY["correct"]["limits"], "got": got, "rows": rows,
            "packed": packed, "chosen": chosen, "engine": engine,
            "want": correct.reference_logits(ref, TOY, wseed, smp),
            "want_rows": correct.served_reference(ref, TOY, wseed, rows),
        }
    finally:
        os.environ.pop("DYNAMO_PALLAS", None)


def _verdict(r, want=None, want_rows=None):
    c = r["correct"]
    served = c.served_numbers(
        r["packed"], r["chosen"],
        r["want_rows"] if want_rows is None else want_rows,
        r["rows"]["bursts"],
    )
    return c.compare(
        r["got"], r["want"] if want is None else want, r["limits"], served
    )


def test_the_program_agrees_with_the_plain_reference(readings):
    """Through ``lib/correct.py`` and ``lib/stack.py`` as they stand:
    ``fam.prefill``, ``fam.prefill_batch``, ``fam.decode_steps``,
    ``fam.m.decode_forward``, tables the check builds itself, and every
    leaf of the pair cut by a leading layer axis (a kind's missing K side
    is no leaf)."""
    verdict = _verdict(readings)
    assert verdict["ok"], verdict
    assert set(verdict["rows"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap",
    }
    assert readings["smp"]["decode_layers"] == TOY["num_hidden_layers"]


def test_tails_ride_the_pair_and_no_row_went_missing(readings):
    """The pair's leaves: the attention kind's pages on both sides, the
    conv kind's tails on the V side and NOTHING on the K side, the
    directory; the check's own tables found every row."""
    k, v = readings["engine"].k_pages, readings["engine"].v_pages
    pages = TOY["engine"]["num_pages"] + 1
    assert k.pools[0].shape == v.pools[0].shape == (1, pages, 2, 8, 16)
    assert k.pools[1] is None
    assert v.pools[1].shape == (3, 5, 2, 64)
    assert k.rows.owner.shape == (1, 5) and v.rows is None
    stats = np.asarray(k.rows.stats[0])
    assert stats[2] == 0 and stats[1] >= 4


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_published_key_changed_comes_out_as_not_correct(readings, fault):
    r = readings
    smp, rows = r["smp"], r["rows"]
    config = dict(TOY, **FAULTS[fault])
    last = np.asarray([[n - 1] for n in smp["lens"]], np.int32)
    after = np.asarray(
        [[n + j for j in range(smp["steps"])] for n in smp["lens"]], np.int32
    )
    full, early = r["ref"].forward(
        config, r["wseed"], smp["tokens"], last,
        early=(smp["decode_layers"], after),
    )
    at = np.asarray(
        [[n - 1] + [n + j for j in range(rows["generated"])]
         for n in rows["lens"]], np.int32,
    )
    want_rows = np.asarray(r["ref"].forward(
        config, r["wseed"], rows["tokens"], at), np.float32)
    verdict = _verdict(
        r, want=(np.asarray(full, np.float32)[:, 0],
                 np.asarray(early, np.float32)),
        want_rows=want_rows,
    )
    assert not verdict["ok"], verdict
    clean = _verdict(r)["rows"]
    worst = max(
        row["value"] / max(clean[k]["value"], 1e-7)
        for k, row in verdict["rows"].items() if k != "served_token_gap"
    )
    assert worst > 20, (fault, verdict["rows"])


def test_the_fp8_control_comes_out_as_not_correct(readings):
    r = readings
    low = r["correct"].reference_logits(
        r["ref"], TOY, r["wseed"], r["smp"], quant="fp8")
    verdict = r["correct"].compare(low, r["want"], {
        k: v for k, v in r["limits"].items() if "rel_rms" in k
        and "packed" not in k})
    assert not verdict["ok"]


def test_the_reference_at_two_chunkings_gives_the_same_logits(readings):
    """Rows a call are how the reference fits beside the model, not what
    it computes: one row a call gives the logits of two."""
    ref, r = readings["ref"], readings
    tokens = r["smp"]["tokens"]
    at = np.tile(np.arange(5, 60, 11), (tokens.shape[0], 1)).astype(np.int32)
    a = np.asarray(ref.forward(TOY, r["wseed"], tokens, at))
    was = ref.ROWS_AT_ONCE
    ref.ROWS_AT_ONCE = 1
    try:
        b = np.asarray(ref.forward(TOY, r["wseed"], tokens, at))
    finally:
        ref.ROWS_AT_ONCE = was
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(
            REPO, "perfbench/references/shortconv_moe.py")) as f:
        text = f.read()
    assert "import dynamo_tpu" not in text and "from dynamo_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


# ------------------------------------------------ the configuration's file


@pytest.fixture(scope="module")
def lfm2():
    with open(os.path.join(REPO, "perfbench/configs/lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_model_spec_says_what_the_published_keys_say(lfm2):
    """``model_spec`` repeats in the program's terms what the reference
    reads from the published keys: they must not drift apart."""
    from lib import stack as stk
    from references import shortconv_moe as ref

    spec = stk.model_spec(lfm2)
    hash(spec)  # a static argument of every program
    m = ref._dims(lfm2)
    assert spec.has_recurrent and not spec.has_latent and not spec.is_mla
    assert spec.mixers == {"softmax", "conv"}
    assert spec.num_layers == len(lfm2["layers_kept"]) == lfm2[
        "num_hidden_layers"] == 10
    assert lfm2["layers_kept"] == list(range(10))
    assert len(lfm2["layer_types"]) == 40
    assert (spec.hidden_size, spec.num_heads, spec.head_dim,
            spec.intermediate_size, spec.vocab_size) == (
        2048, 32, 64, 11776, 65536)
    # the pattern: two dense conv layers, then two periods of attention
    # and three convolutions, as published
    assert [spec.kind(li).mixer for li in range(10)] == [
        "softmax" if a else "conv" for a in m["attn"]] == [
        "conv", "conv", "softmax", "conv", "conv", "conv",
        "softmax", "conv", "conv", "conv"]
    assert [spec.is_moe_layer(li) for li in range(10)] == [
        not d for d in m["dense"]] == [False] * 2 + [True] * 8
    attn, conv = spec.kind(2), spec.kind(0)
    assert attn.paged and not attn.recurrent and attn.num_kv_heads == 8
    assert conv.recurrent and not conv.paged and not conv.state
    assert attn.rope_theta == spec.rope_theta == lfm2["rope_parameters"][
        "rope_theta"] == 1e6
    assert spec.qk_norm and spec.use_rope and spec.tie_embeddings
    assert spec.conv_taps == lfm2["conv_L_cache"] == m["taps"] == 3
    assert not lfm2["conv_bias"]
    assert (spec.num_experts, spec.experts_here, spec.num_experts_per_token,
            spec.moe_intermediate_size, spec.n_group, spec.n_shared_experts,
            spec.routed_scaling_factor, spec.first_k_dense) == (
        64, (64, 0), 4, 1536, 0, 0, 1.0, 2)
    assert spec.held_experts == ()  # every expert held
    assert spec.moe_scoring == "sigmoid" and lfm2["use_expert_bias"]
    assert spec.norm_topk_prob and lfm2["norm_topk_prob"]
    assert spec.moe_norm_eps == ref.NORM_EPS_TOPK == 1e-6
    assert spec.rms_eps == lfm2["norm_eps"] == m["eps"] == 1e-5
    assert spec.dtype == "bfloat16"
    # the check cuts every leaf by layer: only the full depth is sound
    assert lfm2["correct"]["decode_layers"] == spec.num_layers
    assert all(any(w in a for a in lfm2["assumed"]) for w in (
        "tied", "1e-6", "11,776", "64 = hidden_size", "B | C | x",
        "BEFORE the rotation", "embedding_norm", "expert_bias",
        "operator_norm"))
    assert "four pipeline stages" in lfm2["deployment"]
    assert "float32" in lfm2["precision"]


def test_only_the_depth_differs_from_the_catalog_row(lfm2):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert lfm2["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in lfm2 or lfm2[k] != v}
    assert differ == set(lfm2["reduced"]) == {"num_hidden_layers"}
    assert lfm2["reduced"]["num_hidden_layers"]["source"] == 40
    assert lfm2["reduced"]["num_hidden_layers"]["here"] == 10
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == lfm2["source"]
    assert entry["file"] == "perfbench/configs/lfm2-24b-a2b.json"


def test_the_engine_offers_what_the_check_asks_for(lfm2):
    """A pack of 2 at the one bucket beside the weights and the pages; a
    state row a slot, by the engine."""
    from lib import stack as stk

    cfg = stk.engine_config(lfm2, 1, profile=False)
    spec = stk.model_spec(lfm2)
    assert cfg.prefill_shapes(spec, 2 * 2**30) == {1024: 2}
    assert cfg.max_context == 10240 and cfg.max_decode_slots == 128
    assert "state_rows" not in lfm2["engine"]
    c = lfm2["correct"]
    assert c["max_tokens"] + 1 + 9 <= c["padded_tokens"]
    assert c["max_tokens"] <= max(lfm2["engine"]["prefill_buckets"])
    assert c["samples"] * cfg.max_pages_per_seq <= cfg.num_pages
    assert cfg.max_decode_slots * 10 <= cfg.num_pages
    assert set(c["limits"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap"}
    assert c["control"] == "fp8" and "readings" in c["limits_why"]


def test_the_arithmetic_of_the_cut_against_the_programs_weights(lfm2):
    """ISSUE 45's arithmetic and ``lib/costs_shortconv_moe.py`` against
    hand counts, and against the shapes ``init_params`` and ``init_cache``
    would make (``jax.eval_shape``: nothing is allocated)."""
    import jax

    from dynamo_tpu.models import llama
    from lib import costs_shortconv_moe as c
    from lib import stack as stk

    assert c.expert_params(lfm2) == 3 * 2048 * 1536 == 9437184  # 9.44 M
    assert 64 * c.expert_params(lfm2) == pytest.approx(604e6, rel=1e-3)
    assert 64 * c.expert_bytes(lfm2) == pytest.approx(1.208e9, rel=1e-3)
    assert c.conv_mixer_params(lfm2) == (
        2048 * 6144 + 2048 * 2048 + 3 * 2048)
    assert c.conv_mixer_params(lfm2) == pytest.approx(16.8e6, rel=2e-3)
    assert c.attention_params(lfm2) == (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64)
    assert c.attention_params(lfm2) == pytest.approx(10.5e6, rel=2e-3)
    assert c.dense_mlp_params(lfm2) == 3 * 2048 * 11776
    assert c.dense_mlp_params(lfm2) == pytest.approx(72.4e6, rel=2e-3)
    assert c.router_params(lfm2) == 2048 * 64 + 64  # 0.13 M
    assert c.vocabulary_params(lfm2) == 65536 * 2048  # 134 M
    assert c.weight_bytes(lfm2) == 2 * (
        2 * (c.dense_mlp_params(lfm2) + c.conv_mixer_params(lfm2))
        + 2 * c.layer_params(lfm2, True, False)
        + 6 * c.layer_params(lfm2, False, False)
        + c.vocabulary_params(lfm2))
    assert c.weight_bytes(lfm2) == pytest.approx(10.5e9, rel=5e-3)
    # the model whole: ~23.8 B parameters, 47.7 GB, three chips' memory
    whole = (2 * c.layer_params(lfm2, False, True)
             + 10 * c.layer_params(lfm2, True, False)
             + 28 * c.layer_params(lfm2, False, False)
             + c.vocabulary_params(lfm2))
    assert whole == pytest.approx(23.8e9, rel=5e-3)
    assert 2 * whole > 2.9 * 16e9
    assert c.tail_bytes_per_row_layer(lfm2) == 8192
    assert c.kv_bytes_per_token_layer(lfm2) == 4096  # 8 KB over 2 layers
    assert c.kv_bytes_per_token_layer(lfm2, laid_out=False) == 2048

    spec = stk.model_spec(lfm2)
    shapes = jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    # the residual stream's norm gains (two a layer and the last) are what
    # the costs leave out
    assert count == c.weight_bytes(lfm2) // 2 + 21 * 2048
    layers = shapes["layers"]
    assert layers[0]["sconv_in"].shape == (2048, 6144)
    assert layers[0]["sconv_out"].shape == (2048, 2048)
    assert layers[0]["sconv_taps"].shape == (3, 2048)
    assert layers[0]["w_gate"].shape == (2048, 11776) and "moe" not in layers[0]
    assert layers[2]["wq"].shape == (2048, 2048)
    assert layers[2]["wk"].shape == layers[2]["wv"].shape == (2048, 512)
    assert layers[2]["q_norm"].shape == layers[2]["k_norm"].shape == (64,)
    assert layers[2]["moe"]["router"].shape == (2048, 64)
    assert layers[2]["moe"]["router"].dtype == np.float32
    assert layers[2]["moe"]["score_bias"].shape == (64,)
    assert layers[9]["moe"]["w_gate"].shape == (64, 2048, 1536)
    assert "lm_head" not in shapes
    eng = lfm2["engine"]
    rows = eng["max_decode_slots"]
    k, v = jax.eval_shape(lambda: llama.init_cache(
        spec, eng["num_pages"] + 1, eng["page_size"], state_rows=rows))
    assert k.pools[0].shape[:4] == (2, eng["num_pages"] + 1, 8, 64)
    assert k.pools[0].shape[4] in (64, 128)  # 128 where the kernels compile
    assert k.pools[1] is None
    assert v.pools[1].shape == (8, rows + 1, 2, 2048)
    tails = (rows + 1) * 8 * c.tail_bytes_per_row_layer(lfm2)
    assert tails == pytest.approx(8e6, rel=0.06)
    pages = eng["num_pages"] * 64 * 2 * c.kv_bytes_per_token_layer(lfm2)
    assert pages == pytest.approx(2.1e9, rel=0.03)
    # ~12.7 GB of a v5e's 16 before activations: far over the floor
    assert 0.70 * 16.9e9 < c.weight_bytes(lfm2) + pages + tails < 0.80 * 16.9e9


def test_the_bytes_of_a_step_against_the_issues_reckoning(lfm2):
    """ISSUE 45's Motivation: at 128 slots and ~180k live tokens a step
    moves experts 8 x 1.208 = 9.66 GB, dense MLPs 0.29, mixers 0.31, head
    0.27, pages ~1.4 as padded: ~11.9 GB = 14.6 ms at 819 GB/s, four
    fifths of it expert weights."""
    from lib import costs_shortconv_moe as c

    experts = 8 * 64 * c.expert_bytes(lfm2)
    assert experts == pytest.approx(9.66e9, rel=2e-3)
    assert 2 * 2 * c.dense_mlp_params(lfm2) == pytest.approx(0.29e9, rel=0.01)
    mixers = 2 * (8 * c.conv_mixer_params(lfm2) + 2 * c.attention_params(lfm2))
    assert mixers == pytest.approx(0.31e9, rel=0.02)
    assert 2 * c.vocabulary_params(lfm2) == pytest.approx(0.27e9, rel=0.01)
    pages = 2 * c.kv_bytes_per_token_layer(lfm2) * 180000
    assert pages == pytest.approx(1.47e9, rel=0.01)
    step = c.decode_step_bytes(lfm2, 180000.0, 128.0, 8 * 64.0)
    assert step == pytest.approx(
        c.weight_bytes(lfm2) + 128 * 2048 * 2 + 8 * 2 * 128 * 8192
        + 2 * 4096 * 180128)
    assert step == pytest.approx(11.9e9, rel=0.02)
    assert step / 819e9 == pytest.approx(14.6e-3, rel=0.02)
    assert 0.78 < experts / step < 0.84
    # an expert no token reached is not read: 500 touched of 512
    assert c.decode_step_bytes(lfm2, 180000.0, 128.0, 500.0) == (
        pytest.approx(step - 12 * c.expert_bytes(lfm2)))
    # the conv layers' mixers a step: 33.6 MB of weights and 1 MB of tails
    # in and 1 MB out a layer
    mix = c.conv_mix_decode_bytes_per_step(lfm2, 128.0)
    assert mix == 8 * (2 * c.conv_mixer_params(lfm2) + 2 * 128 * 8192)
    assert 2 * c.conv_mixer_params(lfm2) == pytest.approx(33.6e6, rel=2e-3)
    assert 128 * 8192 == 2**20


# ------------------------------------------------------------ the readers


def _slice():
    """The decode programs of the cell's traced run on the chip, cut to
    their largest operations and their regions
    (``tests/perfbench/data/lfm2_decode_slice.json``)."""
    with open(os.path.join(
            REPO, "tests/perfbench/data/lfm2_decode_slice.json")) as f:
        return json.load(f)


def _run(joined=True):
    def snap(d, at):
        return {"window.at": {"secs": at, "calls": 0},
                **{k: {"secs": 0.0, "calls": v} for k, v in d.items()}}

    class Engine:
        class config:
            page_size = 64

    s = _slice()
    rows = [(t / 10, 0, 0, 120, 2800) for t in range(0, 101)]
    run = {
        # 300 experts touched a step (of 8 layers x 64) over 100 steps, as
        # the counters read in the run the slice is cut from (~65 live
        # slots' assignments under a skewed router)
        "profile": (
            snap({"recurrent_state.rows": 128,
                  "moe.decode.experts_touched": 0, "moe.decode.steps": 0},
                 0.0),
            snap({"recurrent_state.rows": 128,
                  "moe.decode.experts_touched": 30000,
                  "moe.decode.steps": 100}, 12.0)),
        "samples": rows, "t0": 0.0, "seconds": 10.0,
        "traced": (2.0, 3.0, 4.0), "engine": Engine,
        "device": {"kind": "TPU v5 lite"},
        "trace": {"busy_s": 1.0, "window_s": 1.0, "by_kind": {
            "decode": {"secs": s["secs"], "runs": s["runs"],
                       "ops": s["ops"]}}},
    }
    run["_regions"] = {"by_kind": {"decode": {
        "secs": s["secs"], "regions": s["regions"]}}} if joined else None
    return run, s


@pytest.fixture(scope="module")
def cell():
    from lib import spec as spec_mod

    return spec_mod.load_cell(REPO, CELL)


def test_the_readers_on_a_slice_of_the_cells_own_trace(lfm2, cell):
    from lib import costs_shortconv_moe as c

    run, s = _run()
    read = {n: cell.readers[f"shortconv:{n.split('.', 1)[1]}"] for n in NEW}
    # a step is counted by the attention kernel: two attention layers
    attn = sum(n for op, (_, n) in s["ops"].items() if "attn_full" in op)
    steps = attn / 2
    assert steps == pytest.approx(s["steps"])
    gmm_s = sum(t for op, (t, _) in s["ops"].items() if "gmm" in op)
    want = 100 * (300 * c.expert_bytes(lfm2) / 819e9) / (gmm_s / steps)
    assert read[NEW[0]](run, cell) == pytest.approx(want)
    conv_s = s["regions"]["conv_proj"] + s["regions"]["conv_mix"]
    want = 100 * (c.conv_mix_decode_bytes_per_step(lfm2, 120.0) / 819e9) / (
        conv_s / steps)
    assert read[NEW[1]](run, cell) == pytest.approx(want)
    ffn = ("mlp", "moe_route", "moe_experts", "moe_dispatch", "moe_grouped",
           "gmm", "moe_combine", "moe_count")
    assert read[NEW[2]](run, cell) == pytest.approx(
        100 * sum(s["regions"].get(r, 0.0) for r in ffn) / s["secs"])
    mix = ("conv_proj", "conv_mix", "attn_qkv", "attn_kv", "attn_out",
           "attn_full", "state_rows")
    assert read[NEW[3]](run, cell) == pytest.approx(
        100 * sum(s["regions"].get(r, 0.0) for r in mix) / s["secs"])
    for name in NEW[:4]:
        assert 0 < read[name](run, cell) <= 100, name
    # the experts set the pace, as the issue reckoned; the mixers are small
    assert read[NEW[2]](run, cell) > 60 > 25 > read[NEW[3]](run, cell)
    # the cell's own step: the programs' device time over the steps the
    # attention kernel counts. No unnamed XLA fusion is a name to count
    # by, so the configuration gives the accepted reader none
    assert read[NEW[4]](run, cell) == pytest.approx(1e3 * s["secs"] / steps)
    names = lfm2["trace_names"]
    assert set(names) == {"programs", "full_attention_ops", "expert_ops"}
    assert not [n for ops in names.values() for n in ops if "fusion" in n]


def test_without_scopes_or_counters_the_readers_find_nothing(cell):
    """A program that lacks the scopes and the counters (the parent
    commit's, or another configuration's), a trace that cannot be joined:
    nothing is read, nothing raises, the metrics are left out."""
    read = [cell.readers[f"shortconv:{n.split('.', 1)[1]}"] for n in NEW]
    bare, _ = _run(joined=False)
    bare["trace"]["by_kind"]["decode"]["ops"] = {
        "fused_decode_attention": [0.5, 70]}
    bare["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    for fn in read:
        assert fn(bare, cell) is None, fn
    # joined, but to another configuration's program (Solar's regions)
    other, _ = _run()
    other["_regions"]["by_kind"]["decode"]["regions"] = {
        "attn_qkv": 0.2, "kda_step": 0.2, "mlp": 0.2}
    other["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    for fn in read[:4]:  # the step reads no region: ``attn_full`` is there
        assert fn(other, cell) is None, fn
    empty = {"profile": ({}, {}), "t0": 0.0, "seconds": 1.0}
    for fn in read:
        assert fn(empty, cell) is None, fn


def test_the_new_entries_and_their_files_agree(lfm2):
    """Everything found BY NAME: a later PR appends behind this one."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(
        entry, config="lfm2-24b-a2b", traffic="reasoning", chips=1)
    config = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers"]
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    by_name = {e["name"]: e for e in bench["per_layer"]}
    for name in NEW:
        e = by_name[name]
        with open(os.path.join(
                REPO, "perfbench", "metrics", name + ".json")) as f:
            m = json.load(f)
        assert e["workloads"] == m["workloads"] == [CELL]
        assert {k: m[k] for k in e if k != "workloads"} == {
            k: e[k] for k in e if k != "workloads"}
        assert e["unit"] == ("ms" if name.endswith("_ms") else "%")
        assert e["source"] == "device_trace"
        assert e["moves"] == "tpot_p50_ms"
        assert e["better"] == ("higher" if name.startswith("kernels.")
                               else "lower")
        assert m["reader"].startswith("shortconv:")
    has = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
           if CELL in m.get("workloads", ())}
    assert has == JOINED | set(NEW)
    # a per-layer metric is reported only where the end-to-end metric it
    # should move is reported too
    reported = {m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", (CELL,))}
    assert reported == {"tpot_p50_ms", "setup_s"}
    assert {m["moves"] for m in bench["per_layer"]
            if CELL in m.get("workloads", (CELL,))} <= reported
    with open(os.path.join(REPO, "perfbench/traffic/reasoning.json")) as f:
        t = json.load(f)
    eng = lfm2["engine"]
    assert t["max_total_tokens"] <= eng["page_size"] * eng[
        "max_pages_per_seq"] - 8
    assert t["clients_per_slot"] * eng["max_decode_slots"] == 256
    # the cell's loader finds every file by name
    from lib import spec as spec_mod

    loaded = spec_mod.load_cell(REPO, CELL)
    assert loaded.config["reference"] == "shortconv_moe"
    assert len(loaded.per_layer) == 9 and len(loaded.end_to_end) == 2
    for name in ("references/shortconv_moe.py", "readers/shortconv.py",
                 "lib/costs_shortconv_moe.py"):
        assert os.path.exists(os.path.join(REPO, "perfbench", name))


# ------------------------------- the whole command, rehearsed on the CPU


def test_the_cell_rehearsed_at_toy_size(tmp_path):
    """``run.py`` on a toy cell of this configuration, by the files the
    real cell uses: the counters reach the result line through the
    program-counter readers; no device metric is printed."""
    import shutil
    import subprocess

    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    config = dict(TOY, engine=dict(TOY["engine"], pipeline_decode=True))
    (bench / "configs" / "toy-lfm2.json").write_text(json.dumps(config))
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps({
        "name": "toy-closed", "loop": "closed", "clients": 4,
        "pool_requests": 200,
        "prompt_tokens": {"dist": "uniform", "min": 30, "max": 90},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "max_total_tokens": 120, "lead_in_s": 2.0, "temperature": 0.0,
    }))
    names = ["tpot_p50_ms", "setup_s",
             "engine.compiles_in_window", "cache.pages_peak_share",
             "moe.tokens_per_expert_step",
             "moe.expert_load_max_over_mean"] + NEW
    entries = []
    for name in names:
        src = os.path.join(REPO, "perfbench", "metrics", name + ".json")
        shutil.copy(src, bench / "metrics")
        with open(src) as f:
            m = json.load(f)
        e = {k: m[k] for k in ("name", "unit", "better", "source")}
        if m["kind"] == "end_to_end":
            e["bound"] = 0.1
        else:
            e.update(layer=m["layer"], moves=m["moves"])
        entries.append((m["kind"], dict(e, workloads=["toy.closed"])))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "perfbench/run.py"], "paths": ["bench"],
        "run_seconds": 6,
        "configs": [{"name": "toy-lfm2", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-lfm2.json", "why": "toy"}],
        "workloads": [{"name": "toy.closed", "config": "toy-lfm2",
                       "traffic": "toy-closed", "chips": 1, "why": "toy"}],
        "end_to_end": [e for kind, e in entries if kind == "end_to_end"],
        "per_layer": [e for kind, e in entries if kind == "per_layer"],
    }))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--root", str(tmp_path), "--workload", "toy.closed", "--seed", "9",
         "--seconds", "6", "--trace", "1", "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=400,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    metrics = line["metrics"]
    assert metrics["engine.compiles_in_window"]["value"] == 0
    assert 0 < metrics["cache.pages_peak_share"]["value"] <= 100
    # every expert is held: with 4 slots at most 4 x 4 / 8 = 2 rows an
    # expert a step
    assert 0 < metrics["moe.tokens_per_expert_step"]["value"] <= 2
    assert metrics["moe.expert_load_max_over_mean"]["value"] >= 1
    assert not [k for k in metrics
                if k.startswith("kernels.") or k.startswith("model.")]
