"""The shortcut-connected MoE reference over latent attention with identity
experts (``scmoe_latent``) against a tiny engine on the CPU through the
benchmark's own output check, the published keys the comparison must
catch, the configuration's file against the catalog row and the program's
parameter count, the byte and operation counts against ISSUE 49's
arithmetic, the readers of the cell's six new per-layer metrics on a slice
of the cell's own trace, and the whole command rehearsed on a toy cell.
Toy sizes in float32: what holds on the chip at published widths is in
PERF.md."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

CELL = "longcat-flash.reasoning"
CONFIG = "longcat-flash-chat"
NEW = ["model.scmoe_decode_step_ms",
       "model.scmoe_shortcut_decode_share",
       "model.scmoe_latent_decode_share",
       "kernels.scmoe_latent_decode_roofline_share",
       "kernels.scmoe_experts_hbm_share",
       "moe.zero_pick_share"]
# NOT ``out_tok_s`` nor the per-layer metrics that move it, NOT
# ``model.decode_step_ms`` (its reader counts ONE kernel call a layer a
# step; this model runs two) and none of the lists that tests/perfbench
# holds to exact values (PERF.md section 7)
JOINED = {"tpot_p50_ms", "engine.compiles_in_window", "device.idle_share",
          "device.peak_mem_share", "moe.tokens_per_expert_step"}

# the published keys at toy widths: 2 double layers, 8 FFN experts of which
# 2 are held from 2, 4 identity experts, top-3
TOY = {
    "name": "toy-longcat", "hidden_size": 64, "num_attention_heads": 4,
    "q_lora_rank": 16, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000000,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
    "n_routed_experts": 2, "zero_expert_num": 4,
    "zero_expert_type": "identity", "moe_topk": 3,
    "routed_scaling_factor": 6, "rms_norm_eps": 1e-5, "vocab_size": 96,
    "num_layers": 2, "num_hidden_layers": 2, "attention_method": "MLA",
    "torch_dtype": "float32",
    "experts": {"published": 8, "held": 2, "first": 2},
    "reference": "scmoe_latent",
    "model_spec": {
        "num_layers": 2, "intermediate_size": 96, "num_kv_heads": 4,
        "tie_embeddings": False, "kv_lora_rank": 32, "q_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_interleave": True, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "shortcut_moe": True, "num_experts": 8,
        "held_experts": [2, 2], "zero_experts": 4,
        "num_experts_per_token": 3, "moe_intermediate_size": 32,
        "moe_scoring": "softmax_bias", "norm_topk_prob": False,
        "routed_scaling_factor": 6.0,
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
        "padded_tokens": 72, "decode_layers": 1,
        "limits": {"prefill_rel_rms": 2e-4, "decode_rel_rms": 2e-4,
                   "packed_prefill_rel_rms": 2e-4, "served_token_gap": 0.01},
    },
    "trace_names": {
        "programs": {"decode": ["decode_steps"],
                     "prefill": ["prefill_forward"]},
        "decode_attention_ops": ["attn_latent"], "expert_ops": ["gmm"],
    },
}

# each changes one published key of the REFERENCE's config: the program,
# which has the published form, must then come out as not correct
FAULTS = {
    "queries_not_scaled": {"mla_scale_q_lora": False},
    "latent_not_scaled": {"mla_scale_kv_lora": False},
    "routed_scale_halved": {"routed_scaling_factor": 3},
    "weights_renormalised": {"norm_topk_prob": True},
    "no_identity_experts": {"zero_expert_num": 0},
    "another_rope_base": {"rope_theta": 10000},
    "top_2": {"moe_topk": 2},
}


@pytest.fixture(scope="module")
def readings():
    """One tiny engine and the reference, read once."""
    os.environ["DYNAMO_PALLAS"] = "1"  # the kernels, interpreted
    try:
        from dynamo_tpu.engine.core import InferenceEngine
        from lib import correct
        from lib import stack as stk

        seed = 2**31 + 49  # a seed past 32 signed bits
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
    ``fam.m.decode_forward``, and every leaf of the pair cut by a leading
    DECODER-layer axis: the check's ``decode_layers`` cut (1 of 2 here)
    keeps both sub-layers' pools of the layers it keeps."""
    verdict = _verdict(readings)
    assert verdict["ok"], verdict
    assert set(verdict["rows"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap",
    }
    assert readings["smp"]["decode_layers"] == 1


def test_the_cache_is_a_pool_a_sub_layer_and_the_counters(readings):
    k, v = readings["engine"].k_pages, readings["engine"].v_pages
    pages = TOY["engine"]["num_pages"] + 1
    assert type(k) is tuple and len(k) == 2
    assert all(p.shape[:3] == (2, pages, 8) for p in k)
    counts = np.asarray(v)
    assert counts.shape == (2, 2, 2 + 5)
    # zero picks + ffn picks = assignments, in every layer and phase
    assert (counts[:, :, 2] + counts[:, :, 3] == counts[:, :, 4]).all()
    assert counts[:, :, 4].min() > 0 and counts[:, :, 2].min() > 0


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
    """Rows a pass are how the reference fits beside the model, not what it
    computes: one row a pass gives the logits of four."""
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
            REPO, "perfbench/references/scmoe_latent.py")) as f:
        text = f.read()
    assert "import dynamo_tpu" not in text and "from dynamo_tpu" not in text
    assert 'default_matmul_precision("highest")' in text
    assert "model_spec\"]" not in text and "model_spec']" not in text


# ------------------------------------------------ the configuration's file


@pytest.fixture(scope="module")
def longcat():
    with open(os.path.join(
            REPO, "perfbench/configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_model_spec_says_what_the_published_keys_say(longcat):
    """``model_spec`` repeats in the program's terms what the reference
    reads from the published keys: they must not drift apart."""
    from lib import stack as stk
    from references import scmoe_latent as ref

    spec = stk.model_spec(longcat)
    hash(spec)  # a static argument of every program
    m = ref._dims(longcat)
    assert spec.is_mla and spec.shortcut_moe and spec.sub_layers == 2
    assert spec.num_layers == longcat["num_layers"] == longcat[
        "num_hidden_layers"] == m["layers"] == 4
    assert (spec.hidden_size, spec.num_heads, spec.intermediate_size,
            spec.vocab_size) == (
        m["d"], m["nh"], m["f_dense"], m["vocab"]) == (
        6144, 64, 12288, 16384)
    assert (spec.q_lora_rank, spec.kv_lora_rank, spec.qk_nope_head_dim,
            spec.qk_rope_head_dim, spec.v_head_dim) == (
        m["q_rank"], m["dc"], m["dn"], m["dr"], m["dv"]) == (
        1536, 512, 128, 64, 128)
    assert spec.mla_scale_q_lora and spec.mla_scale_kv_lora
    assert m["q_scale"] == 2.0 and m["kv_scale"] == pytest.approx(12 ** 0.5)
    assert spec.rope_theta == m["theta"] == 1e7 and spec.rope_interleave
    assert spec.rms_eps == m["eps"] == 1e-5 and not spec.tie_embeddings
    assert (spec.num_experts, spec.zero_experts, spec.router_outputs,
            spec.experts_here, spec.num_experts_per_token,
            spec.moe_intermediate_size, spec.routed_scaling_factor) == (
        m["experts"], m["zeros"], 768, (m["held"], m["first"]), m["topk"],
        m["f"], m["scaling"]) == (512, 256, 768, (16, 0), 12, 2048, 6.0)
    assert spec.moe_scoring == "softmax_bias"
    assert not spec.norm_topk_prob and not m["norm_topk"]
    assert not spec.n_shared_experts and not spec.first_k_dense
    assert spec.dtype == "bfloat16"
    c = longcat["correct"]
    assert 0 < c["decode_layers"] <= spec.num_layers
    assert all(any(w in a for a in longcat["assumed"]) for w in (
        "INTERLEAVED", "norm_topk_prob absent", "rms_norm_eps 1e-5",
        "sqrt(6144/1536)", "sqrt(6144/512)", "longcat_flash", "PID",
        "4 + 17 x layers", "1.5^2/6144", "1/768^2"))
    assert "ep=32" in longcat["deployment"]
    assert "7 pipeline stages" in longcat["deployment"]
    assert "float32" in longcat["precision"]


def test_only_the_stated_keys_differ_from_the_catalog_row(longcat):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    assert longcat["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in longcat or longcat[k] != v}
    assert differ == set(longcat["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    for key, (source, here) in {"num_layers": (28, 4),
                                "n_routed_experts": (512, 16),
                                "vocab_size": (131072, 16384)}.items():
        assert longcat["reduced"][key]["source"] == source == row[
            "config"][key]
        assert longcat["reduced"][key]["here"] == here == longcat[key]
    assert longcat["experts"] == {"published": 512, "held": 16, "first": 0}
    assert longcat["zero_expert_num"] == 256  # the router stays 768 wide
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == longcat["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"


def test_the_engine_offers_what_the_check_asks_for(longcat):
    from lib import stack as stk

    cfg = stk.engine_config(longcat, 1, profile=False)
    spec = stk.model_spec(longcat)
    assert cfg.prefill_shapes(spec, 2 * 2**30) == {1024: 2}
    assert cfg.max_context == 10240 and cfg.max_decode_slots == 128
    assert cfg.decode_steps_per_dispatch == 8 and cfg.pipeline_decode
    c = longcat["correct"]
    assert c["max_tokens"] + 1 + 9 <= c["padded_tokens"]
    assert c["max_tokens"] <= max(longcat["engine"]["prefill_buckets"])
    assert c["samples"] * cfg.max_pages_per_seq <= cfg.num_pages
    served = -(-(c["max_tokens"] + 10) // cfg.page_size)
    assert cfg.max_decode_slots * served <= cfg.num_pages
    assert set(c["limits"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap"}
    assert c["control"] == "fp8" and "readings" in c["limits_why"]


def test_the_arithmetic_of_the_cut_against_the_programs_weights(longcat):
    """ISSUE 49's arithmetic and ``lib/costs_scmoe_latent.py`` against hand
    counts, and against the shapes ``init_params`` and ``init_cache`` would
    make (``jax.eval_shape``: nothing is allocated)."""
    import jax

    from dynamo_tpu.models import mla
    from lib import costs_scmoe_latent as c
    from lib import stack as stk

    assert c.attention_params(longcat) == (
        6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
        + 64 * 128 * 6144) == 90570752  # 90.57 M
    assert c.dense_ffn_params(longcat) == 3 * 6144 * 12288 == 226492416
    assert c.router_params(longcat) == 6144 * 768 == 4718592
    assert c.expert_params(longcat) == 3 * 6144 * 2048 == 37748736
    assert c.expert_bytes(longcat) == 75497472
    assert c.layer_params(longcat) == (
        2 * (90570752 + 226492416) + 4718592 + 16 * 37748736)
    # outside the routed experts: 638.8 M = 1.278 GB; 16 experts 1.208 GB
    assert c.layer_params(longcat) - 16 * c.expert_params(longcat) == (
        pytest.approx(638.8e6, rel=1e-3))
    assert 16 * c.expert_bytes(longcat) == pytest.approx(1.208e9, rel=1e-3)
    assert 2 * c.layer_params(longcat) == pytest.approx(2.486e9, rel=1e-3)
    assert c.weight_bytes(longcat) == pytest.approx(10.35e9, rel=2e-3)
    # a layer's 512 experts: 19.3 B = 38.7 GB, more than two chips
    assert 512 * c.expert_bytes(longcat) == pytest.approx(38.7e9, rel=2e-3)
    assert c.cache_layers(longcat) == 8
    assert c.latent_bytes_per_token(longcat) == 1280
    assert c.latent_bytes_per_token(longcat, laid_out=False) == 1152
    assert c.cache_bytes_per_token(longcat) == 10240

    spec = stk.model_spec(longcat)
    shapes = jax.eval_shape(
        lambda: mla.init_params(spec, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    layer = shapes["layers"][0]
    gains = 4 * (2 * (2 * 6144 + 1536 + 512)) + 6144  # norms, left out
    bias = 4 * 768
    assert count == c.weight_bytes(longcat) // 2 + gains + bias
    assert set(layer) == {"sub", "moe"} and len(layer["sub"]) == 2
    sub = layer["sub"][1]
    assert sub["wq_a"].shape == (6144, 1536)
    assert sub["wq_b"].shape == (1536, 64 * 192)
    assert sub["w_kv_a"].shape == (6144, 576)
    assert sub["w_uk"].shape == sub["w_uv"].shape == (64, 512, 128)
    assert sub["wo"].shape == (64 * 128, 6144)
    assert sub["w_gate"].shape == sub["w_up"].shape == (6144, 12288)
    assert sub["w_down"].shape == (12288, 6144)
    assert layer["moe"]["router"].shape == (6144, 768)
    assert layer["moe"]["router"].dtype == np.float32
    assert layer["moe"]["score_bias"].shape == (768,)
    assert layer["moe"]["w_gate"].shape == (16, 6144, 2048)
    assert layer["moe"]["w_down"].shape == (16, 2048, 6144)
    assert shapes["lm_head"].shape == (6144, 16384)
    eng = longcat["engine"]
    pools = jax.eval_shape(lambda: mla.init_cache(
        spec, eng["num_pages"] + 1, eng["page_size"]))
    assert len(pools) == 2
    for p in pools:
        assert p.shape[:3] == (4, eng["num_pages"] + 1, 64)
        assert p.shape[3] in (576, 640)  # 640 where the kernels compile
    cache = eng["num_pages"] * 64 * c.cache_bytes_per_token(longcat)
    assert cache == pytest.approx(2.68e9, rel=0.01)
    # ~13.1 GB of a v5e's 16.9 before activations: far over the floor
    assert 0.74 * 16.9e9 < c.weight_bytes(longcat) + cache < 0.80 * 16.9e9
    counts = jax.eval_shape(lambda: mla.init_counts(spec))
    assert counts.shape == (4, 2, 16 + 5)


def test_the_bytes_of_a_step_against_the_issues_reckoning(longcat):
    """ISSUE 49: a decode step with 128 rows live reads ~5.1 GB of the
    double layers' attention and dense weights, ~4.2 GB of the experts it
    touches, ~1.8 GB of latents through 8 kernel calls: ~11.4 GB, ~14 ms
    at 819 GB/s; ~9.8 GB at the ~77 rows the other ``reasoning`` cells
    hold."""
    from lib import costs_scmoe_latent as c

    dense = 4 * 2 * 2 * (c.attention_params(longcat)
                         + c.dense_ffn_params(longcat))
    assert dense == pytest.approx(5.07e9, rel=0.01)
    # 128 rows x ~8 FFN picks over 512 experts: ~2 rows an expert, so ~86%
    # of the 64 held experts are touched: ~55 x 75.5 MB
    touched = 64 * (1 - np.exp(-2.0))
    assert touched * c.expert_bytes(longcat) == pytest.approx(4.2e9, rel=0.02)
    live = 128 * 1250.0  # ~160k live latents, as joyai-flash.reasoning's
    latents = 8 * c.decode_attention_bytes_per_call(longcat, live, 128.0)
    assert latents == pytest.approx(1.8e9, rel=0.02)
    step = c.decode_step_bytes(longcat, live, 128.0, touched)
    assert step == pytest.approx(11.4e9, rel=0.02)
    assert step / 819e9 == pytest.approx(14e-3, rel=0.03)
    assert (touched * c.expert_bytes(longcat) + latents) / step > 0.5
    fewer = 64 * (1 - np.exp(-77 * 8 / 512))
    assert c.decode_step_bytes(longcat, 77 * 1250.0, 77.0, fewer) == (
        pytest.approx(9.8e9, rel=0.02))
    # the kernel's operations at 64 heads: 64 x (2 x 512 + 64) x 2 a row
    assert c.decode_attention_flops_per_call(longcat, 999.0, 1.0) == (
        2.0 * 64 * 1088 * 1000)
    # by bytes a call takes live x 1,280 / 819e9; by operations live x
    # 139,264 / 197e12: the call is bound by its bytes 2.2 to 1
    by_bytes = c.decode_attention_bytes_per_call(longcat, live, 128.0) / 819e9
    by_flops = c.decode_attention_flops_per_call(longcat, live, 128.0) / 197e12
    assert 2.0 < by_bytes / by_flops < 2.5
    assert c.grouped_products_bytes_per_step(longcat, 50.0) == (
        50 * c.expert_bytes(longcat))


# ------------------------------------------------------------ the readers


def _slice():
    """The decode programs of the cell's traced run on the chip, cut to
    their largest operations and their regions
    (``tests/perfbench/data/longcat_decode_slice.json``)."""
    with open(os.path.join(
            REPO, "tests/perfbench/data/longcat_decode_slice.json")) as f:
        return json.load(f)


def _run(joined=True):
    def snap(d, at):
        return {"window.at": {"secs": at, "calls": 0},
                **{k: {"secs": 0.0, "calls": v} for k, v in d.items()}}

    class Engine:
        class config:
            page_size = 64

    s = _slice()
    rows = [(t / 10, 0, 0, 82, 2440) for t in range(0, 101)]
    run = {
        # 20 experts touched a step (of 4 layers x 16) over 100 steps and 82
        # live slots over 2,440 pages, as the run the slice is cut from
        # read; a third of the picks identity experts
        "profile": (
            snap({"moe.decode.experts_touched": 0, "moe.decode.steps": 0,
                  "moe.decode.zero_picks": 0, "moe.decode.ffn_picks": 0},
                 0.0),
            snap({"moe.decode.experts_touched": 2000,
                  "moe.decode.steps": 100,
                  "moe.decode.zero_picks": 160000,
                  "moe.decode.ffn_picks": 320000}, 12.0)),
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


def _readers(cell):
    return {n: cell.readers["scmoe:" + n.split(".", 1)[1]] for n in NEW}


def test_the_readers_on_a_slice_of_the_cells_own_trace(longcat, cell):
    from lib import costs_scmoe_latent as c

    run, s = _run()
    read = _readers(cell)
    # a step is counted by the latent kernel: eight cache layers
    calls = sum(n for op, (_, n) in s["ops"].items() if "attn_latent" in op)
    steps = calls / 8
    assert steps == pytest.approx(s["steps"])
    assert read[NEW[0]](run, cell) == pytest.approx(1e3 * s["secs"] / steps)
    shortcut = ("moe_route", "moe_experts", "moe_dispatch", "moe_grouped",
                "gmm", "moe_combine", "moe_zero", "moe_count")
    assert read[NEW[1]](run, cell) == pytest.approx(
        100 * sum(s["regions"].get(r, 0.0) for r in shortcut) / s["secs"])
    latent = ("latent_q", "latent_kv", "latent_absorb", "attn_kv",
              "attn_latent", "latent_schedule", "attn_out")
    assert read[NEW[2]](run, cell) == pytest.approx(
        100 * sum(s["regions"].get(r, 0.0) for r in latent) / s["secs"])
    # the dense FFNs are in neither share
    assert s["regions"]["mlp"] > 0
    assert read[NEW[1]](run, cell) + read[NEW[2]](run, cell) < 100 * (
        1 - s["regions"]["mlp"] / s["secs"]) + 1e-6
    attn_s = sum(t for op, (t, _) in s["ops"].items() if "attn_latent" in op)
    live = 64 * 2440.0
    least = max(
        c.decode_attention_bytes_per_call(longcat, live, 82.0) / 819e9,
        c.decode_attention_flops_per_call(longcat, live, 82.0) / 197e12)
    assert read[NEW[3]](run, cell) == pytest.approx(
        100 * least / (attn_s / calls))
    gmm_s = sum(t for op, (t, _) in s["ops"].items() if "gmm" in op)
    assert read[NEW[4]](run, cell) == pytest.approx(
        100 * (20 * c.expert_bytes(longcat) / 819e9) / (gmm_s / steps))
    assert read[NEW[5]](run, cell) == pytest.approx(100 / 3)
    for name in NEW[1:]:
        assert 0 < read[name](run, cell) <= 100, name
    names = longcat["trace_names"]
    assert set(names) == {"programs", "decode_attention_ops", "expert_ops"}
    assert not [n for ops in (names["decode_attention_ops"],
                              names["expert_ops"]) for n in ops
                if "fusion" in n]


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
    # joined, but to another configuration's program (Solar's regions)
    other, _ = _run()
    other["_regions"]["by_kind"]["decode"]["regions"] = {
        "attn_qkv": 0.2, "kda_step": 0.2, "mlp": 0.2, "moe_route": 0.1}
    other["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    for fn in (read[1], read[2], read[4], read[5]):
        assert fn(other, cell) is None, fn
    empty = {"profile": ({}, {}), "t0": 0.0, "seconds": 1.0}
    for fn in read:
        assert fn(empty, cell) is None, fn


def test_the_new_entries_and_their_files_agree(longcat):
    """Everything found BY NAME: a later PR appends behind this one."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config=CONFIG, traffic="reasoning", chips=1)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert "data-parallel" in entry["why"] and "2 rows" in entry["why"]
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
        assert e["source"] == ("program_counter" if name.startswith("moe.")
                               else "device_trace")
        assert e["moves"] == "tpot_p50_ms"
        assert m["reader"].startswith("scmoe:")
        assert m["kind"] == "per_layer"
    assert by_name["moe.zero_pick_share"]["layer"] == by_name[
        "moe.tokens_per_expert_step"]["layer"]
    assert {by_name[n]["layer"] for n in NEW} <= {
        e["layer"] for e in bench["per_layer"] if e["name"] not in NEW}
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
    eng = longcat["engine"]
    assert t["max_total_tokens"] <= eng["page_size"] * eng[
        "max_pages_per_seq"] - 8
    assert t["clients_per_slot"] * eng["max_decode_slots"] == 256
    # the cell's loader finds every file by name
    from lib import spec as spec_mod

    loaded = spec_mod.load_cell(REPO, CELL)
    assert loaded.config["reference"] == "scmoe_latent"
    assert len(loaded.per_layer) == 10 and len(loaded.end_to_end) == 2
    for name in ("references/scmoe_latent.py", "readers/scmoe.py",
                 "lib/costs_scmoe_latent.py"):
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
    # every FFN expert held: a greedy stream on drawn weights can circle
    # among tokens that never pick one share's two experts
    config = dict(
        TOY, engine=dict(TOY["engine"], pipeline_decode=True),
        n_routed_experts=8, experts={"published": 8, "held": 8, "first": 0},
        model_spec=dict(TOY["model_spec"], held_experts=[8, 0]))
    (bench / "configs" / "toy-longcat.json").write_text(json.dumps(config))
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps({
        "name": "toy-closed", "loop": "closed", "clients": 4,
        "pool_requests": 200,
        "prompt_tokens": {"dist": "uniform", "min": 30, "max": 90},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "max_total_tokens": 120, "lead_in_s": 2.0, "temperature": 0.0,
    }))
    names = ["tpot_p50_ms", "setup_s", "engine.compiles_in_window",
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
        "run_seconds": 6,
        "configs": [{"name": "toy-longcat", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-longcat.json", "why": "toy"}],
        "workloads": [{"name": "toy.closed", "config": "toy-longcat",
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
    # 4 slots x 3 picks of which ~2 FFN over 8 held experts: ~1 row
    assert 0 < metrics["moe.tokens_per_expert_step"]["value"] <= 1.5
    # 4 identity experts of 12 outputs: about a third of the picks
    assert 10 < metrics["moe.zero_pick_share"]["value"] < 60
    assert not [k for k in metrics
                if k.startswith("kernels.") or k.startswith("model.")]
