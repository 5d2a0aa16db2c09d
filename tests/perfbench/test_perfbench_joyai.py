"""The latent-attention reference with held experts and a shared expert
(``latent_moe``) against a tiny engine on the CPU, the faults the
comparison must catch, the configuration's file against its own published
keys and the catalog row, the byte and operation counts and the readers
the cell's per-layer metrics use, and the whole command rehearsed on a toy
cell. Toy sizes in float32: what holds on the chip at published widths is
in PERF.md."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

# the published keys at toy widths: 5 layers published, 3 kept (layer 0
# dense, layers 1-2 with experts); 16 routed experts, 4 held from 4
TOY = {
    "name": "toy-joyai", "hidden_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 8, "q_lora_rank": 40,
    "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "rope_theta": 32000000, "rope_interleave": True,
    "first_k_dense_replace": 1, "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 4, "n_shared_experts": 1,
    "experts": {"published": 16, "held": 4, "first": 4},
    "layers_kept": [0, 1, 2], "num_experts_per_tok": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "vocab_size": 96, "num_hidden_layers": 3,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "reference": "latent_moe",
    "model_spec": {
        "kv_lora_rank": 24, "q_lora_rank": 40, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 12, "rope_interleave": True,
        "num_experts": 16, "held_experts": [4, 4],
        "num_experts_per_token": 4, "moe_intermediate_size": 16,
        "moe_scoring": "sigmoid", "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": 2.5, "n_shared_experts": 1,
        "first_k_dense": 1, "nextn_predict_layers": 1,
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
        "padded_tokens": 72,
        "limits": {"prefill_rel_rms": 2e-4, "decode_rel_rms": 2e-4,
                   "packed_prefill_rel_rms": 2e-4, "served_token_gap": 0.01},
    },
    "trace_names": {
        "programs": {"decode": ["decode_steps"],
                     "prefill": ["prefill_forward"]},
        "decode_attention_ops": ["attn_latent"], "expert_ops": ["gmm"],
    },
}

# each takes one term of the layer's equations out of the REFERENCE: the
# program, which has it, must then come out as not correct
FAULTS = {
    "rope_on_half_split_pairs": {"rope_interleave": False},
    "routed_scaling_left_out": {"routed_scaling_factor": 1.0},
    "shared_expert_left_out": {"n_shared_experts": 0},
    "first_layer_read_as_experts": None,  # see _forward
    "correction_bias_left_out": None,
}


def _forward(ref, config, fault, seed, tokens, positions, **kw):
    if fault == "correction_bias_left_out":
        real = ref._route

        def route(x, router, bias, **k):
            return real(x, router, bias * 0, **k)

        ref._route = route
        try:
            return ref.forward(config, seed, tokens, positions, **kw)
        finally:
            ref._route = real
    if fault == "first_layer_read_as_experts":
        real = ref._dense_mlp
        ref._dense_mlp = lambda x, lw, **k: x  # the dense MLP adds nothing
        try:
            return ref.forward(config, seed, tokens, positions, **kw)
        finally:
            ref._dense_mlp = real
    return ref.forward(dict(config, **FAULTS[fault]), seed, tokens,
                       positions, **kw)


@pytest.fixture(scope="module")
def readings():
    """One tiny engine and the reference, read once."""
    os.environ["DYNAMO_PALLAS"] = "1"  # the latent kernel, interpreted
    try:
        from dynamo_tpu.engine.core import InferenceEngine
        from lib import correct
        from lib import stack as stk

        seed = 2**31 + 7  # a seed past 32 signed bits
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
    ``fam.m.decode_forward`` and pools sliced by a leading layer axis."""
    verdict = _verdict(readings)
    assert verdict["ok"], verdict
    assert set(verdict["rows"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap",
    }
    # decode steps that cross a page
    assert readings["smp"]["decode_layers"] == TOY["num_hidden_layers"]


def test_the_cache_is_one_latent_pool_and_the_counters(readings):
    k, v = readings["engine"].k_pages, readings["engine"].v_pages
    pages = TOY["engine"]["num_pages"] + 1
    assert k.shape == (3, pages, 8, 24 + 8)  # [c, k_r]: no head axis
    assert v.shape == (3, 2, 4 + 3) and v.dtype == np.int32
    assert int(np.asarray(v)[0].sum()) == 0  # the dense layer keeps none
    assert int(np.asarray(v)[1:, :, -1].min()) > 0  # both phases stepped


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_term_left_out_comes_out_as_not_correct(readings, fault):
    r = readings
    smp, rows = r["smp"], r["rows"]
    last = np.asarray([[n - 1] for n in smp["lens"]], np.int32)
    after = np.asarray(
        [[n + j for j in range(smp["steps"])] for n in smp["lens"]], np.int32
    )
    full, early = _forward(
        r["ref"], TOY, fault, r["wseed"], smp["tokens"], last,
        early=(smp["decode_layers"], after),
    )
    at = np.asarray(
        [[n - 1] + [n + j for j in range(rows["generated"])]
         for n in rows["lens"]], np.int32,
    )
    want_rows = np.asarray(_forward(
        r["ref"], TOY, fault, r["wseed"], rows["tokens"], at), np.float32)
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


# ------------------------------------------------ the configuration's file


@pytest.fixture(scope="module")
def joyai():
    with open(os.path.join(REPO, "perfbench/configs/joyai-llm-flash.json")) as f:
        return json.load(f)


def test_model_spec_says_what_the_published_keys_say(joyai):
    """``model_spec`` repeats in the program's terms what the reference
    reads from the published keys: they must not drift apart."""
    from lib import stack as stk

    spec = stk.model_spec(joyai)
    hash(spec)  # a static argument of every program
    assert spec.is_mla
    assert spec.num_layers == len(joyai["layers_kept"]) == joyai[
        "num_hidden_layers"] == 7
    assert (spec.hidden_size, spec.num_heads, spec.intermediate_size) == (
        joyai["hidden_size"], joyai["num_attention_heads"],
        joyai["intermediate_size"]) == (2048, 32, 7168)
    assert (spec.q_lora_rank, spec.kv_lora_rank, spec.qk_nope_head_dim,
            spec.qk_rope_head_dim, spec.v_head_dim) == tuple(
        joyai[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                           "qk_rope_head_dim", "v_head_dim")
    ) == (1536, 512, 128, 64, 128)
    assert joyai["qk_head_dim"] == 128 + 64
    assert spec.rope_interleave is joyai["rope_interleave"] is True
    assert spec.rope_theta == joyai["rope_theta"] == 32e6
    assert joyai["rope_scaling"] is None and not spec.rope_scaling_factor
    assert spec.rms_eps == joyai["rms_norm_eps"]
    assert spec.first_k_dense == joyai["first_k_dense_replace"] == 1
    ex = joyai["experts"]
    assert spec.num_experts == ex["published"] == 256
    assert spec.experts_here == (ex["held"], ex["first"]) == (16, 0)
    assert joyai["n_routed_experts"] == ex["held"]
    assert spec.num_experts_per_token == joyai["num_experts_per_tok"] == 8
    assert spec.moe_intermediate_size == joyai["moe_intermediate_size"] == 768
    assert (spec.moe_scoring, spec.n_group, spec.topk_group) == (
        joyai["scoring_func"], joyai["n_group"], joyai["topk_group"])
    assert spec.norm_topk_prob is joyai["norm_topk_prob"]
    assert spec.routed_scaling_factor == joyai["routed_scaling_factor"] == 2.5
    assert spec.n_shared_experts == joyai["n_shared_experts"] == 1
    assert spec.nextn_predict_layers == joyai["num_nextn_predict_layers"] == 1
    assert not spec.tie_embeddings and spec.vocab_size == 129280 // 8


def test_only_the_stated_keys_differ_from_the_catalog_row(joyai):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
    assert joyai["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if joyai.get(k) != v}
    assert differ == set(joyai["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "joyai-llm-flash")
    assert set(entry["reduced"]) == differ and entry["source"] == joyai["source"]


def test_the_engine_offers_what_the_check_asks_for(joyai):
    """The guard charges the latent programs what they hold: the bucket
    keeps its pack of 2 beside 1.6 GB of weights and 5.9 GB of pages (the
    output check needs a packed prefill at every bucket its rows use), and
    whatever the table's width."""
    import dataclasses

    from lib import stack as stk

    cfg = stk.engine_config(joyai, 1, profile=False)
    spec = stk.model_spec(joyai)
    assert cfg.prefill_shapes(spec, 5 * 2**30) == {1024: 2}
    wide = dataclasses.replace(cfg, max_pages_per_seq=16 * cfg.max_pages_per_seq)
    assert wide.prefill_shapes(spec, 5 * 2**30) == {1024: 2}
    # and halves a pack that would not fit
    assert cfg.prefill_shapes(spec, 400 * 2**20) == {1024: 1}
    assert cfg.max_context == 10240
    c = joyai["correct"]
    assert c["max_tokens"] + 1 + 9 <= c["padded_tokens"]
    assert c["max_tokens"] <= max(joyai["engine"]["prefill_buckets"])


def test_the_bytes_and_operations_of_the_cut(joyai):
    from lib import costs_latent_moe as c

    assert c.latent_values_per_token(joyai) == 576
    assert c.latent_bytes_per_token(joyai, laid_out=False) == 1152
    assert c.latent_bytes_per_token(joyai) == 1280  # 640 lanes
    assert c.attention_params(joyai) == (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 4096 * 2048)
    assert c.attention_params(joyai) == pytest.approx(26.35e6, rel=1e-3)
    assert c.expert_params(joyai) == 3 * 2048 * 768
    assert c.layer_params(joyai, False) == pytest.approx(107.1e6, rel=2e-3)
    assert c.layer_params(joyai, True) == pytest.approx(70.4e6, rel=2e-3)
    # 6 expert layers, layer 0, embedding and head slices
    assert c.weight_bytes(joyai) == 2 * (
        6 * c.layer_params(joyai, False) + c.layer_params(joyai, True)
        + 2 * 16160 * 2048)
    assert c.weight_bytes(joyai) == pytest.approx(1.56e9, rel=0.01)
    # a call moves what is live, whatever the table
    call = c.decode_attention_bytes_per_call(joyai, 128 * 2000.0, 128.0)
    assert call == 1280 * (128 * 2000 + 128) + 128 * 32 * (640 + 512) * 2
    flops = c.decode_attention_flops_per_call(joyai, 128 * 2000.0, 128.0)
    assert flops == 2 * 32 * (1024 + 64) * (128 * 2000 + 128)
    assert 50 < flops / call < 60  # FLOP a byte: under the v5e's ridge, 240
    step = c.decode_step_bytes(joyai, 128 * 2000.0, 128.0)
    assert step == pytest.approx(
        c.weight_bytes(joyai) - 16160 * 2048 * 2 + 128 * 2048 * 2 + 7 * call)
    half = c.decode_step_bytes(joyai, 128 * 2000.0, 128.0, 6 * 8.0)
    assert step - half == 6 * 8 * c.expert_bytes(joyai)
    assert c.peak_flops_s("TPU v5 lite") == 197e12
    with pytest.raises(SystemExit):
        c.peak_flops_s("a device that is not in the table")


# ------------------------------------------------------------ the readers


def _run(before, after, ops, traced_slots=100, live_pages=12500):
    def snap(d):
        return {k: {"secs": 0.0, "calls": v} for k, v in d.items()}

    class Engine:
        class config:
            page_size = 16

    rows = [(t / 10, 0, 0, traced_slots if 20 <= t <= 30 else 100,
             live_pages) for t in range(0, 101)]
    return {"profile": (snap(before), snap(after)), "samples": rows,
            "t0": 0.0, "seconds": 10.0, "traced": (2.0, 3.0, 4.0),
            "engine": Engine, "device": {"kind": "TPU v5 lite"},
            "trace": {"by_kind": {"decode": {
                "secs": 1.8, "ops": ops, "runs": 25}}}}


class _Cell:
    def __init__(self, config):
        self.config = config


def test_the_latent_readers_on_a_small_trace(joyai):
    from lib import costs_latent_moe as c
    from readers import latent as reader

    # 200 model steps of 7 layers in 1.8 s of decode programs; the kernel
    # 0.5 ms a call; 100 live slots holding 12,500 pages = 200k tokens
    ops = {"attn_latent.1": [0.7, 1400], "gmm.7": [0.2, 3600],
           "fusion.3": [0.7, 9000]}
    before = {"decode_kv.pages_fetched": 1000, "decode_kv.pages_live": 900,
              "moe.decode.steps": 50, "moe.decode.experts_touched": 50 * 100}
    after = {"decode_kv.pages_fetched": 1000 + 26000,
             "decode_kv.pages_live": 900 + 20000,
             "moe.decode.steps": 250,
             "moe.decode.experts_touched": 50 * 100 + 200 * 96}
    run, cell = _run(before, after, ops), _Cell(joyai)
    call_s = c.decode_attention_bytes_per_call(joyai, 200000.0, 100.0) / 819e9
    assert c.decode_attention_flops_per_call(
        joyai, 200000.0, 100.0) / 197e12 < call_s  # bandwidth is the roof
    assert reader.latent_decode_attn_roofline_share(run, cell) == (
        pytest.approx(100 * call_s / 0.0005))
    step_s = c.decode_step_bytes(joyai, 200000.0, 100.0, 96.0) / 819e9
    assert reader.latent_decode_hbm_share(run, cell) == pytest.approx(
        100 * step_s / (1.8 / 200))
    assert reader.latent_pages_fetched_over_live(run, cell) == 1.3
    # fewer slots in the traced part: the touched experts scale down (as
    # ``readers/moe.py`` scales them), and the step's bytes with them
    fewer = _run(before, after, ops, traced_slots=50)
    window = (90 * 100 + 11 * 50) / 101
    step_s = c.decode_step_bytes(
        joyai, 200000.0, 50.0, 96.0 * 50 / window) / 819e9
    assert reader.latent_decode_hbm_share(fewer, cell) == pytest.approx(
        100 * step_s / (1.8 / 200))


def test_without_counters_or_names_the_readers_find_nothing(joyai):
    """A program that lacks the scope and the counters: nothing is read,
    nothing raises, the metrics are left out of the line."""
    from readers import latent as reader

    run = {"profile": ({"idle": {"secs": 1.0, "calls": 1}},) * 2,
           "trace": {"by_kind": {"decode": {"secs": 1.0, "ops": {
               "fused_decode_attention": [0.5, 70]}, "runs": 1}}},
           "traced": (1.0, 2.0, 3.0), "t0": 0.0, "samples": [],
           "device": {"kind": "TPU v5 lite"}}
    bare = _Cell({"trace_names": {"programs": {}}, "num_hidden_layers": 7})
    for cell in (bare, _Cell(joyai)):
        for fn in (reader.latent_decode_attn_roofline_share,
                   reader.latent_decode_hbm_share,
                   reader.latent_pages_fetched_over_live):
            assert fn(run, cell) is None
    assert reader.latent_pages_fetched_over_live(
        {"profile": ({}, {})}, bare) is None
    assert reader.latent_decode_hbm_share({"profile": ({}, {})}, bare) is None


def test_the_new_entries_and_their_files_agree(joyai):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells["joyai-flash.reasoning"]["traffic"] == "reasoning"
    assert cells["nemo12b.chat"] == dict(
        cells["nemo12b.chat"], config="mistral-nemo-12b",
        traffic="chat-nemo", chips=1)
    new = ["kernels.latent_decode_attn_roofline_share",
           "kernels.latent_decode_hbm_share",
           "cache.latent_pages_fetched_over_live",
           "model.prefill_paired_tok_s.reasoning"]
    assert [e["name"] for e in bench["per_layer"]][-4:] == new
    for e in bench["per_layer"][-4:]:
        with open(os.path.join(
                REPO, "perfbench", "metrics", e["name"] + ".json")) as f:
            m = json.load(f)
        assert e["workloads"] == m["workloads"] == ["joyai-flash.reasoning"]
        assert {k: m[k] for k in e if k != "workloads"} == {
            k: e[k] for k in e if k != "workloads"}
    with open(os.path.join(REPO, "perfbench/traffic/reasoning.json")) as f:
        t = json.load(f)
    eng = joyai["engine"]
    assert t["max_total_tokens"] <= eng["page_size"] * eng[
        "max_pages_per_seq"] - 8
    assert (t["loop"], t["clients_per_slot"], t["pool_requests"]) == (
        "closed", 2, 640)
    with open(os.path.join(REPO, "perfbench/traffic/chat.json")) as f:
        chat = json.load(f)
    with open(os.path.join(REPO, "perfbench/traffic/chat-nemo.json")) as f:
        nemo = json.load(f)
    same = ("loop", "prompt_tokens", "output_tokens", "max_total_tokens",
            "shared_prefix", "burst", "temperature")
    assert {k: nemo[k] for k in same} == {k: chat[k] for k in same}


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
    (bench / "configs" / "toy-joyai.json").write_text(json.dumps(config))
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps({
        "name": "toy-closed", "loop": "closed", "clients": 4,
        "pool_requests": 200,
        "prompt_tokens": {"dist": "uniform", "min": 30, "max": 90},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "max_total_tokens": 120, "lead_in_s": 2.0, "temperature": 0.0,
    }))
    names = ["tpot_p50_ms", "out_tok_s", "setup_s",
             "moe.tokens_per_expert_step", "moe.expert_load_max_over_mean",
             "cache.latent_pages_fetched_over_live",
             "cache.pages_peak_share",
             "kernels.latent_decode_attn_roofline_share",
             "kernels.latent_decode_hbm_share"]
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
        "configs": [{"name": "toy-joyai", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-joyai.json", "why": "toy"}],
        "workloads": [{"name": "toy.closed", "config": "toy-joyai",
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
    assert 0 < metrics["moe.tokens_per_expert_step"]["value"] <= 4.0
    assert 1.0 <= metrics["moe.expert_load_max_over_mean"]["value"] <= 4.0
    # a 16-page table is one chunk of the kernel: every live slot fetches
    # 16 pages for the 4 to 15 that hold its context
    assert 1.0 <= metrics["cache.latent_pages_fetched_over_live"]["value"] <= 4.0
    assert 0 < metrics["cache.pages_peak_share"]["value"] <= 100
    assert not [k for k in metrics if k.startswith("kernels.")]
