"""The gated window/full GQA reference over held experts (``gated_swa_moe``)
against a tiny engine on the CPU through the benchmark's own output check,
the published keys the comparison must catch, the configuration's file
against the catalog row and the program's parameter count, the byte counts
against ISSUE 53's arithmetic, the readers of the cell's six new
per-layer metrics on a slice of a decode program's trace, and the whole
command rehearsed on a toy cell. Toy sizes in float32: what holds on the
chip at published widths is in PERF.md."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

CELL = "trinity-mini.longctx"
CONFIG = "trinity-mini"
NEW = ["model.gswa_attn_decode_share",
       "kernels.gswa_window_decode_hbm_share",
       "kernels.gswa_full_decode_hbm_share",
       "kernels.gswa_experts_hbm_share",
       "kernels.gswa_experts_prefetch_share",
       "cache.window_dead_share"]
# ``out_tok_s`` since the cell's two sets of six runs spread under half its
# bound (1.77% / 2.18%: PERF.md section 6), with the pool's peak fill that
# moves it; none of the lists that tests/perfbench holds to exact values,
# nor MiMo's attention shares, whose costs read MiMo's keys (section 7)
JOINED = {"tpot_p50_ms", "out_tok_s", "cache.pages_peak_share",
          "engine.compiles_in_window", "device.idle_share",
          "device.peak_mem_share", "moe.tokens_per_expert_step",
          # the step's device time is the accepted generic reader's (no
          # metric of this family's own repeats it)
          "model.decode_step_ms"}

# the published keys at toy widths: a dense window layer, a window expert
# layer, a full expert layer (layer_types is the published list's length,
# layers_kept reads it at three indices); a window of 8 tokens; 8 experts
# of which 4 are held from the third, top-2, a shared one
TOY = {
    "name": "toy-trinity", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 3,
    "layers_kept": [0, 1, 2],
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "global_attn_every_n_layers": 4, "sliding_window": 8,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_dense_layers": 1, "num_experts": 4, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "mup_enabled": True, "n_group": 1,
    "topk_group": 1, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "vocab_size": 96, "tie_word_embeddings": False, "torch_dtype": "float32",
    "experts": {"published": 8, "held": 4, "first": 2},
    "reference": "gated_swa_moe",
    "model_spec": {
        "qk_norm": True, "attn_gate": True, "sandwich_norm": True,
        "embedding_multiplier": 8.0,
        "layer_kinds": [
            {"num_kv_heads": 2, "rope_theta": 10000.0, "window": 8},
            {"num_kv_heads": 2, "rope_theta": 10000.0, "rope": False}],
        "layer_pattern": [0, 0, 1], "num_experts": 8,
        "held_experts": [4, 2], "num_experts_per_token": 2,
        "moe_intermediate_size": 32, "moe_scoring": "sigmoid",
        "norm_topk_prob": True, "routed_scaling_factor": 2.826,
        "n_shared_experts": 1, "first_k_dense": 1,
    },
    "engine": {
        "page_size": 8, "num_pages": 96, "max_pages_per_seq": 16,
        "max_decode_slots": 4, "prefill_buckets": [64],
        "prefill_pack_size": 2, "max_prefill_chunk_tokens": 64,
        "decode_steps_per_dispatch": 4, "kv_dtype": "bf16",
        "guided_mode": "off",
    },
    # every prompt of the toy check is longer than the toy window too
    "correct": {
        "samples": 3, "min_tokens": 20, "max_tokens": 56, "decode_steps": 3,
        "padded_tokens": 64,
        "limits": {"prefill_rel_rms": 2e-4, "decode_rel_rms": 2e-4,
                   "packed_prefill_rel_rms": 2e-4, "served_token_gap": 0.01},
    },
    "trace_names": {
        "programs": {"decode": ["decode_steps"],
                     "prefill": ["prefill_forward"]},
        "decode_attention_ops": ["attn_window", "attn_full"],
        "window_attention_ops": ["attn_window"],
        "full_attention_ops": ["attn_full"], "expert_ops": ["gmm"],
    },
}

# each changes one published key of the REFERENCE's config: the program,
# which has the published form, must then come out as not correct
FAULTS = {
    "every_layer_full": {"layer_types": ["full_attention"] * 4},
    "every_layer_windowed": {"layer_types": ["sliding_attention"] * 4},
    "a_shorter_window": {"sliding_window": 6},
    "no_embedding_factor": {"mup_enabled": False},
    "route_scale_1": {"route_scale": 1.0},
    "weights_not_renormalised": {"route_norm": False},
    "no_shared_expert": {"num_shared_experts": 0},
    "another_rope_base": {"rope_theta": 1000000},
    "top_1": {"num_experts_per_tok": 1},
}


@pytest.fixture(scope="module")
def readings():
    """One tiny engine and the reference, read once."""
    # the XLA twin of the decode kernel: the kernel itself is interpreted
    # against this reference in tests/test_trinity_afmoe.py and in the
    # rehearsal below, and tracing it takes this file's minute
    os.environ["DYNAMO_PALLAS"] = "0"
    try:
        from dynamo_tpu.engine.core import InferenceEngine
        from lib import correct
        from lib import stack as stk

        seed = 2**31 + 53  # a seed past 32 signed bits
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
    ``fam.m.decode_forward`` over ONE block table that the check builds
    itself, every prompt longer than the window, a share of the experts
    held from the third."""
    verdict = _verdict(readings)
    assert verdict["ok"], verdict
    assert set(verdict["rows"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap",
    }
    assert min(readings["smp"]["lens"] + readings["rows"]["lens"]) > 8
    assert readings["smp"]["decode_layers"] == 3  # no cut: a pool a kind
    k = readings["engine"].k_pages
    assert [p.shape[0] for p in k.pools] == [2, 1]
    counts = np.asarray(k.counts)
    assert counts.shape == (3, 2, 4 + 3) and counts[1:, :, -1].min() > 0
    assert not counts[0].any()  # the dense layer counts nothing


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


def test_the_reference_at_other_blockings_gives_the_same_logits(readings):
    """Rows a pass and queries a block are how the reference fits beside
    the model, not what it computes: one row a pass in blocks of 16
    queries gives the logits of four rows in blocks of 256."""
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
    w = ref.Weights(TOY, r["wseed"])
    x = ref._run(ref._embed_rows, w.embed(), tokens, mult=8.0, quant=None)
    for i in (1, 2):  # a window layer and the full one
        kw = dict(ref._attention_statics(w.m, i), quant=None)
        np.testing.assert_allclose(
            np.asarray(ref._attention(x, w.attention(i), block=16, **kw)),
            np.asarray(ref._attention(x, w.attention(i), **kw)),
            rtol=2e-5, atol=2e-6)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(
            REPO, "perfbench/references/gated_swa_moe.py")) as f:
        text = f.read()
    assert "import dynamo_tpu" not in text and "from dynamo_tpu" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "model_spec\"]" not in text and "model_spec']" not in text


# ------------------------------------------------ the configuration's file


@pytest.fixture(scope="module")
def trinity():
    with open(os.path.join(
            REPO, "perfbench/configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_model_spec_says_what_the_published_keys_say(trinity):
    """``model_spec`` repeats in the program's terms what the reference
    reads from the published keys: they must not drift apart."""
    from lib import stack as stk
    from references import gated_swa_moe as ref

    spec = stk.model_spec(trinity)
    hash(spec)  # a static argument of every program
    m = ref._dims(trinity)
    kept = trinity["layers_kept"]
    # the dense layer and two whole periods (W W W F)
    assert kept == [0, 4, 5, 6, 7, 8, 9, 10, 11]
    assert spec.num_layers == trinity["num_hidden_layers"] == len(kept) == 9
    assert (spec.hidden_size, spec.num_heads, spec.head_dim,
            spec.intermediate_size, spec.vocab_size) == (
        m["d"], m["nh"], m["hd"], m["f_dense"], m["vocab"]) == (
        2048, 32, 128, 6144, 25024)
    win, full = spec.layer_kinds
    assert (win.num_kv_heads, win.window, win.rope, win.rope_theta) == (
        m["nkv"], 2048, True, m["theta"]) == (4, 2048, True, 10000.0)
    assert (full.num_kv_heads, full.window, full.rope) == (4, 0, False)
    # the kept layers' kinds and FFNs are the published lists' at their
    # indices: seven window layers, two full; the one dense layer first
    assert [spec.kind(i).window for i in range(9)] == m["window"] == [
        2048 if trinity["layer_types"][p] == "sliding_attention" else 0
        for p in kept]
    assert m["window"] == [2048, 2048, 2048, 2048, 0, 2048, 2048, 2048, 0]
    assert [spec.is_moe_layer(i) for i in range(9)] == m["moe"] == [
        p >= trinity["num_dense_layers"] for p in kept]
    assert spec.qk_norm and spec.attn_gate and spec.sandwich_norm
    assert spec.use_rope and not spec.attn_sinks and not spec.attn_bias
    assert spec.embedding_multiplier == m["embed_mult"] == 2048 ** 0.5
    assert spec.rms_eps == m["eps"] == 1e-5 and not spec.tie_embeddings
    assert (spec.num_experts, spec.experts_here, spec.num_experts_per_token,
            spec.moe_intermediate_size, spec.routed_scaling_factor,
            spec.n_shared_experts) == (
        m["experts"], (m["held"], m["first"]), m["topk"], m["f"], m["scale"],
        m["n_shared"]) == (128, (16, 0), 8, 1024, 2.826, 1)
    assert spec.moe_scoring == "sigmoid" and spec.moe_norm_eps == 1e-20
    assert spec.norm_topk_prob and m["route_norm"] and spec.n_group <= 1
    assert spec.dtype == "bfloat16"
    assert all(any(w in a for a in trinity["assumed"]) for w in (
        "gate_proj", "BEFORE the rotation", "NoPE", "post_attention_layernorm",
        "sqrt(2,048)", "half-split", "CHOICE alone", "1e-20",
        "load_balance_coeff", "mlp.router.gate", "4 + 8 x layers",
        "fold_in(root, 3000 + layer)"))
    assert "ep=8" in trinity["deployment"]
    assert "all 32 layers" in trinity["deployment"]
    assert "float32" in trinity["precision"]


def test_only_the_stated_keys_differ_from_the_catalog_row(trinity):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    assert trinity["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in trinity or trinity[k] != v}
    assert differ == set(trinity["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, (source, here) in {"num_hidden_layers": (32, 9),
                                "num_experts": (128, 16),
                                "vocab_size": (200192, 25024)}.items():
        assert trinity["reduced"][key]["source"] == source == row[
            "config"][key]
        assert trinity["reduced"][key]["here"] == here == trinity[key]
    assert trinity["experts"] == {"published": 128, "held": 16, "first": 0}
    assert 25024 * 8 == 200192 and 16 * 8 == 128  # the floors: an eighth
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == trinity["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"


def test_the_engine_offers_what_the_check_asks_for(trinity):
    """The one 4,096-row bucket in packs of 2 under a 10,240-token table
    (the guard charges a bucket that wide the walk's true tiles), and a
    check whose every prompt is longer than the window."""
    from lib import stack as stk

    cfg = stk.engine_config(trinity, 1, profile=False)
    spec = stk.model_spec(trinity)
    assert cfg.prefill_shapes(spec, 4 * 2**30) == {4096: 2}
    assert cfg.max_context == 10240 and cfg.max_decode_slots == 64
    assert cfg.decode_steps_per_dispatch == 8 and cfg.pipeline_decode
    assert cfg.decode_steps_admit_pending == 0
    c = trinity["correct"]
    window = trinity["sliding_window"]
    assert c["min_tokens"] >= window + cfg.page_size  # past the window
    assert c["max_tokens"] + 1 + 9 <= c["padded_tokens"]
    assert c["max_tokens"] <= max(trinity["engine"]["prefill_buckets"])
    assert c["samples"] * cfg.max_pages_per_seq <= cfg.num_pages
    served = -(-(c["max_tokens"] + 10) // cfg.page_size)
    assert cfg.max_decode_slots * served <= cfg.num_pages
    assert "decode_layers" not in c  # a pool a kind cannot be cut alike
    assert set(c["limits"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap"}
    assert c["control"] == "fp8" and "readings" in c["limits_why"]
    with open(os.path.join(REPO, "perfbench/traffic/longctx.json")) as f:
        t = json.load(f)
    assert t["max_total_tokens"] <= cfg.max_context - 8
    assert t["clients_per_slot"] * cfg.max_decode_slots == 128
    assert t["pool_requests"] % cfg.max_decode_slots == 0
    assert (t["prompt_tokens"]["median"], t["prompt_tokens"]["sigma"],
            t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]) == (
        3072, 0.45, 1024, 8192)
    assert (t["output_tokens"]["median"], t["output_tokens"]["sigma"],
            t["output_tokens"]["min"], t["output_tokens"]["max"]) == (
        1024, 0.5, 256, 2048)
    from lib import traffic

    lens = traffic.quantile_lengths(t["prompt_tokens"], 256)
    # 4 in 5 over the window, 1 in 4 over a 4,096-token chunk
    assert 0.78 < np.mean(np.asarray(lens) > window) < 0.84
    assert 0.22 < np.mean(np.asarray(lens) > 4096) < 0.30


def test_the_arithmetic_of_the_cut_against_the_programs_weights(trinity):
    """ISSUE 53's arithmetic and ``lib/costs_gated_swa_moe.py`` against
    hand counts, and against the shapes ``init_params`` and ``init_cache``
    would make (``jax.eval_shape``: nothing is allocated)."""
    import jax

    from dynamo_tpu.models import llama
    from lib import costs_gated_swa_moe as c
    from lib import stack as stk

    assert c.attention_params(trinity) == (
        3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128) == 27263232  # 27.26 M
    assert c.dense_mlp_params(trinity) == 3 * 2048 * 6144 == 37748736
    assert c.expert_params(trinity) == c.shared_params(trinity) == 6291456
    assert c.expert_bytes(trinity) == 12582912
    assert c.router_params(trinity) == 2048 * 128 + 128
    assert c.layer_params(trinity, dense=True) == pytest.approx(
        65.0e6, rel=1e-3)
    # an expert layer here: 33.8 M outside its 16 held experts' 100.7 M
    assert c.layer_params(trinity, dense=False) - 16 * 6291456 == (
        pytest.approx(33.8e6, rel=2e-3))
    assert c.layer_params(trinity, dense=False) * 2 == pytest.approx(
        0.269e9, rel=2e-3)
    assert c.weight_bytes(trinity) == pytest.approx(2.487e9, rel=1e-3)
    assert (c.window_layers(trinity), c.full_layers(trinity)) == (7, 2)
    # ISSUE 53's fall-back, one period behind the dense layer: 1.411 GB
    assert c.weight_bytes(dict(trinity, layers_kept=[0, 4, 5, 6, 7])) == (
        pytest.approx(1.411e9, rel=1e-3))
    assert c.kv_bytes_per_token_layer(trinity) == 2048

    spec = stk.model_spec(trinity)
    shapes = jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == c.weight_bytes(trinity) // 2  # every leaf is counted
    dense, layer = shapes["layers"][0], shapes["layers"][4]  # the full one
    assert dense["wq"].shape == dense["w_gate_attn"].shape == (2048, 4096)
    assert dense["wk"].shape == dense["wv"].shape == (2048, 512)
    assert dense["wo"].shape == (4096, 2048)
    assert dense["q_norm"].shape == dense["k_norm"].shape == (128,)
    assert dense["post_attn_norm"].shape == dense["post_mlp_norm"].shape == (
        2048,)
    assert dense["w_gate"].shape == (2048, 6144)
    assert layer["moe"]["router"].shape == (2048, 128)
    assert layer["moe"]["router"].dtype == np.float32
    assert layer["moe"]["score_bias"].shape == (128,)
    assert layer["moe"]["w_gate"].shape == (16, 2048, 1024)
    assert layer["moe"]["w_down"].shape == (16, 1024, 2048)
    assert layer["shared"]["w_up"].shape == (2048, 1024)
    assert shapes["embed"].shape == (25024, 2048)
    assert shapes["lm_head"].shape == (2048, 25024)
    eng = trinity["engine"]
    k, v = jax.eval_shape(lambda: llama.init_cache(
        spec, eng["num_pages"] + 1, eng["page_size"]))
    assert [p.shape for p in k.pools] == [p.shape for p in v.pools] == [
        (7, 5633, 4, 64, 128), (2, 5633, 4, 64, 128)]
    assert k.counts.shape == (9, 2, 16 + 3)
    cache = eng["num_pages"] * 64 * 9 * c.kv_bytes_per_token_layer(trinity)
    assert cache == pytest.approx(6.64e9, rel=2e-3)
    # 9.13 GB of a v5e's 16.9 before the programs' buffers: over 40%,
    # with a pool that the cell's 64 rows fill to ~73% (84% at the peak)
    assert 0.53 * 16.9e9 < c.weight_bytes(trinity) + cache < 0.55 * 16.9e9


def test_the_bytes_of_a_step_against_the_issues_reckoning(trinity):
    """ISSUE 53, at the nine layers it asked for: a decode step at 64 live
    rows of ~4.0k tokens reads ~2.9 GB of keys and values (7 window layers
    capped at 2,048 a row, 2 full layers whole) beside ~2.4 GB of weights:
    ~5.4 GB, ~6.6 ms at 819 GB/s, the attention kinds' reads over half of
    it. At the issue's fall-back of five layers: ~3.0 GB, ~3.6 ms."""
    from lib import costs_gated_swa_moe as c

    batch, live = 64.0, 64 * 4000.0
    in_window = 64 * 2048.0
    a_window = c.decode_attention_bytes_per_call(trinity, in_window, batch)
    a_full = c.decode_attention_bytes_per_call(trinity, live, batch)
    assert 7 * a_window == pytest.approx(1.88e9, rel=0.01)
    assert 2 * a_full == pytest.approx(1.05e9, rel=0.01)
    assert trinity["layers_kept"] == [0, 4, 5, 6, 7, 8, 9, 10, 11]
    five = dict(trinity, layers_kept=[0, 4, 5, 6, 7])
    # 64 rows x 8 picks over 128 experts: 4 rows an expert, so ~98% of the
    # held experts (16 a layer) are touched a step
    step9 = c.decode_step_bytes(
        trinity, live, in_window, batch, 128 * (1 - np.exp(-4.0)))
    assert step9 == pytest.approx(5.3e9, rel=0.03)
    assert step9 / 819e9 == pytest.approx(6.5e-3, rel=0.04)
    assert (7 * a_window + 2 * a_full) / step9 > 0.5
    step5 = c.decode_step_bytes(
        five, live, in_window, batch, 64 * (1 - np.exp(-4.0)))
    assert step5 == pytest.approx(2.95e9, rel=0.03)
    assert (4 * a_window + a_full) / step5 > 0.5
    # a row wholly inside the window reads what it holds and no more
    assert c.decode_attention_bytes_per_call(trinity, 100.0, 1.0) == (
        2048 * 101 + 2 * 32 * 128 * 2)


# ------------------------------------------------------------ the readers


def _slice():
    """A decode program's largest operations and its regions
    (``tests/perfbench/data/trinity_decode_slice.json``)."""
    with open(os.path.join(
            REPO, "tests/perfbench/data/trinity_decode_slice.json")) as f:
        return json.load(f)


def _run(joined=True):
    def snap(d, at):
        return {"window.at": {"secs": at, "calls": 0},
                **{k: {"secs": 0.0, "calls": v} for k, v in d.items()}}

    class Engine:
        class config:
            page_size = 64

    s = _slice()
    rows = [(t / 10, 0, 0, 60, 3840) for t in range(0, 101)]
    names = ("moe.decode.experts_touched", "moe.decode.steps",
             "kv.window_layer_tokens", "kv.window_dead_tokens")
    run = {
        # 56 experts touched a step (of 8 layers x 16) over 100 steps, 60
        # live slots over 3,840 pages (4,096 tokens a row), 45% of the
        # window layers' tokens past their window
        "profile": (
            snap(dict.fromkeys(names, 0), 0.0),
            snap({"moe.decode.experts_touched": 5600,
                  "moe.decode.steps": 100,
                  "kv.window_layer_tokens": 98304000,
                  "kv.window_dead_tokens": 44236800}, 12.0)),
        "samples": rows, "t0": 0.0, "seconds": 10.0,
        "traced": (2.0, 3.0, 4.0), "engine": Engine,
        "device": {"kind": "TPU v5 lite"},
        "trace": {"busy_s": 1.0, "window_s": 1.0, "by_kind": {
            "decode": {"secs": s["secs"], "runs": s["runs"],
                       "ops": s["ops"]}}},
    }
    run["_regions"] = {"window_s": 1.0, "by_kind": {
        "decode": {"secs": s["secs"], "regions": s["regions"]},
        "prefill": {"secs": 0.4, "regions": {"norm_out": 0.01, "mlp": 0.2}},
    }, "rows": {tuple(k.split("|")): v for k, v in s["rows"].items()},
    } if joined else None
    return run, s


@pytest.fixture(scope="module")
def cell():
    from lib import spec as spec_mod

    return spec_mod.load_cell(REPO, CELL)


def _readers(cell):
    return {n: cell.readers["gswa:" + n.split(".", 1)[1]] for n in NEW}


def test_the_readers_on_a_slice_of_a_decode_programs_trace(trinity, cell):
    from lib import costs_gated_swa_moe as c

    run, s = _run()
    read = _readers(cell)
    ops = s["ops"]
    calls = sum(n for op, (_, n) in ops.items()
                if "attn_window" in op or "attn_full" in op)
    steps = calls / 9  # a kernel call a layer a step, nine layers
    assert steps == pytest.approx(s["steps"], rel=1e-3)
    # the step's device time is the accepted generic reader's
    assert cell.readers["device:decode_step_ms"](run, cell) == pytest.approx(
        1e3 * s["secs"] / steps)
    attn = ("attn_qkv", "attn_kv", "attn_window", "attn_full", "attn_out",
            "norm", "norm_out")
    assert read[NEW[0]](run, cell) == pytest.approx(
        100 * sum(s["regions"].get(r, 0.0) for r in attn) / s["secs"])
    assert s["regions"]["norm_out"] > 0 and s["regions"]["mlp"] > 0
    live = 64 * 3840.0
    win_s, win_n = (sum(x[i] for op, x in ops.items() if "attn_window" in op)
                    for i in (0, 1))
    # seven window layers to two full (the trace cuts a burst mid-way)
    assert win_n == pytest.approx(7 * steps, rel=0.01)
    assert read[NEW[1]](run, cell) == pytest.approx(100 * (
        c.decode_attention_bytes_per_call(trinity, live * 0.55, 60.0) / 819e9
    ) / (win_s / win_n))
    full_s, full_n = (sum(x[i] for op, x in ops.items() if "attn_full" in op)
                      for i in (0, 1))
    assert full_n == pytest.approx(2 * steps, rel=0.01)
    assert read[NEW[2]](run, cell) == pytest.approx(100 * (
        c.decode_attention_bytes_per_call(trinity, live, 60.0) / 819e9
    ) / (full_s / full_n))
    # the grouped products by REGION: the kernel's calls and the waits for
    # the compiler's prefetches of the stacked weights booked to them
    grouped_s = s["regions"]["gmm"] + s["regions"].get("moe_grouped", 0.0)
    kernel_s = sum(t for op, (t, _) in ops.items() if "gmm" in op)
    assert grouped_s > kernel_s
    least_s = 56 * c.expert_bytes(trinity) / 819e9
    assert read[NEW[3]](run, cell) == pytest.approx(
        100 * least_s / (grouped_s / steps))
    # by the kernel's name alone the same bytes read over 95% of the
    # peak: the calls whose operand the compiler copied read VMEM
    assert 100 * least_s / (kernel_s / steps) > 95
    sliced = sum(t for k, (t, _) in s["rows"].items()
                 if k.split("|")[2].startswith("slice-"))
    assert read[NEW[4]](run, cell) == pytest.approx(
        100 * sliced / grouped_s, rel=1e-4)
    assert 20 < read[NEW[4]](run, cell) < 40
    assert read[NEW[5]](run, cell) == pytest.approx(45.0)
    for name in NEW:
        assert 0 < read[name](run, cell) <= 100, name
    names = trinity["trace_names"]
    assert set(names) == {"programs", "decode_attention_ops",
                          "window_attention_ops", "full_attention_ops",
                          "expert_ops"}


def test_without_scopes_or_counters_the_readers_find_nothing(cell):
    """A program that lacks the scopes and the counters (the parent
    commit's, or another configuration's), a trace that cannot be joined:
    nothing is read, nothing raises, the metrics are left out."""
    read = list(_readers(cell).values())
    bare, _ = _run(joined=False)
    bare["trace"]["by_kind"]["decode"]["ops"] = {
        "fused_decode_attention": [0.5, 70]}
    bare["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    for fn in read:
        assert fn(bare, cell) is None, fn
    # joined, but to another configuration's program (MiMo's regions: no
    # ``norm_out``) and without the ``kv.window_*`` counters
    other, _ = _run()
    for kind in ("decode", "prefill"):
        other["_regions"]["by_kind"][kind]["regions"] = {
            "attn_qkv": 0.2, "attn_window": 0.2, "mlp": 0.2, "norm": 0.1}
    other["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    other["_regions"]["rows"] = {}
    for fn in (read[0], read[1], read[3], read[4], read[5]):
        assert fn(other, cell) is None, fn
    empty = {"profile": ({}, {}), "t0": 0.0, "seconds": 1.0}
    for fn in read:
        assert fn(empty, cell) is None, fn


def test_the_new_entries_and_their_files_agree(trinity):
    """Everything found BY NAME: a later PR appends behind this one."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config=CONFIG, traffic="longctx", chips=1)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert "64 slots" in entry["why"] and "4 rows" in entry["why"]
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
        assert e["unit"] == "%"
        assert e["source"] == ("program_counter" if name.startswith("cache.")
                               else "device_trace")
        assert e["moves"] == "tpot_p50_ms"
        assert m["reader"].startswith("gswa:")
        assert m["kind"] == "per_layer"
    assert by_name["cache.window_dead_share"]["layer"] == by_name[
        "cache.pages_peak_share"]["layer"]
    assert {by_name[n]["layer"] for n in NEW} <= {
        e["layer"] for e in bench["per_layer"] if e["name"] not in NEW}
    has = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
           if CELL in m.get("workloads", ())}
    assert has == JOINED | set(NEW)
    # a per-layer metric is reported only where the end-to-end metric it
    # should move is reported too
    reported = {m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", (CELL,))}
    assert reported == {"tpot_p50_ms", "out_tok_s", "setup_s"}
    assert {m["moves"] for m in bench["per_layer"]
            if CELL in m.get("workloads", (CELL,))} <= reported
    # the cell's loader finds every file by name
    from lib import spec as spec_mod

    loaded = spec_mod.load_cell(REPO, CELL)
    assert loaded.config["reference"] == "gated_swa_moe"
    assert loaded.traffic["name"] == "longctx"
    assert len(loaded.per_layer) == 12 and len(loaded.end_to_end) == 3
    for name in ("references/gated_swa_moe.py", "readers/gswa.py",
                 "lib/costs_gated_swa_moe.py", "traffic/longctx.json"):
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
    # the two expert layers of the toy (a window layer and the full one):
    # half the programs to compile
    config = dict(
        TOY, engine=dict(TOY["engine"], pipeline_decode=True,
                         decode_steps_per_dispatch=1),
        num_hidden_layers=2, layers_kept=[1, 2],
        model_spec=dict(TOY["model_spec"], layer_pattern=[0, 1],
                        first_k_dense=0))
    (bench / "configs" / "toy-trinity.json").write_text(json.dumps(config))
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps({
        "name": "toy-closed", "loop": "closed", "clients": 4,
        "pool_requests": 200,
        "prompt_tokens": {"dist": "uniform", "min": 30, "max": 90},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "max_total_tokens": 120, "lead_in_s": 1.0, "temperature": 0.0,
    }))
    names = ["tpot_p50_ms", "out_tok_s", "setup_s",
             "engine.compiles_in_window", "cache.pages_peak_share",
             "moe.tokens_per_expert_step"] + NEW
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
        "run_seconds": 2,
        "configs": [{"name": "toy-trinity", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-trinity.json", "why": "toy"}],
        "workloads": [{"name": "toy.closed", "config": "toy-trinity",
                       "traffic": "toy-closed", "chips": 1, "why": "toy"}],
        "end_to_end": [e for kind, e in entries if kind == "end_to_end"],
        "per_layer": [e for kind, e in entries if kind == "per_layer"],
    }))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("DYNAMO_PALLAS", None)  # the rehearsal interprets the kernels
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--root", str(tmp_path), "--workload", "toy.closed", "--seed", "9",
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=400,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    metrics = line["metrics"]
    assert metrics["engine.compiles_in_window"]["value"] == 0
    # 4 slots x 2 picks over 8 experts of which 4 are held
    assert 0 < metrics["moe.tokens_per_expert_step"]["value"] <= 2.0
    # rows of 30-106 tokens in a window of 8: most of what the window
    # layer holds lies past it
    assert 50 < metrics["cache.window_dead_share"]["value"] < 95
    assert 0 < metrics["cache.pages_peak_share"]["value"] <= 100
    assert not [k for k in metrics
                if k.startswith("kernels.") or k.startswith("model.")]
