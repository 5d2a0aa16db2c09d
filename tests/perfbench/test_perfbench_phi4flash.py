"""The decoder-hybrid-decoder reference (``sambay``: Mamba-1 scans beside
window layers of differential attention, one full layer whose pages the
cross layers read, GMUs; Phi-4-mini-flash) against a tiny engine on the CPU
through the benchmark's own output check, the configuration's file against
its own published keys, the catalog row and the program's parameter count,
the byte and operation counts and the readers of the cell's six new
per-layer metrics on a committed trace slice, and the whole command
rehearsed on a toy cell. Toy sizes in float32: what holds on the chip at
published widths is in PERF.md."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

CELL = "phi4-flash.reasoning"
CONFIG = "phi-4-mini-flash"
NEW = ["model.sambay_cross_decode_share", "model.sambay_scan_decode_share",
       "kernels.sambay_scan_decode_roofline_share",
       "kernels.sambay_shared_kv_decode_hbm_share",
       "kernels.sambay_window_decode_hbm_share",
       "engine.sambay_prefill_cross_rows_share"]
KEPT = [0, 1, 2, 3, 16, 17, 18, 19, 20, 21]

# the published keys at toy widths: S W | S F | G C G C of a 32-layer stack
TOY = {
    "name": "toy-phi4flash", "model_type": "phi4flash", "hidden_size": 64,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "vocab_size": 96, "num_hidden_layers": 8,
    "layers_kept": [0, 1, 16, 17, 18, 19, 20, 21],
    "published_layers": 32, "sliding_window": 16, "layer_norm_eps": 1e-5,
    "mb_per_layer": 2, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "torch_dtype": "float32",
    "reference": "sambay",
    "engine": {
        "page_size": 8, "num_pages": 96, "max_pages_per_seq": 16,
        "max_decode_slots": 4, "prefill_buckets": [32, 64],
        "prefill_pack_size": 2, "max_prefill_chunk_tokens": 64,
        "decode_steps_per_dispatch": 4, "kv_dtype": "bf16",
        "guided_mode": "off",
    },
    "correct": {
        "samples": 3, "min_tokens": 30, "max_tokens": 60, "decode_steps": 3,
        "padded_tokens": 72, "decode_layers": 8,
        "limits": {"prefill_rel_rms": 2e-4, "decode_rel_rms": 2e-4,
                   "packed_prefill_rel_rms": 2e-4, "served_token_gap": 0.01},
    },
    "trace_names": {
        "programs": {"decode": ["decode_steps"],
                     "prefill": ["prefill_forward"]},
        "decode_attention_ops": ["attn_window", "attn_full", "attn_cross",
                                 "scan", "attn_cross"],
        "window_attention_ops": ["attn_window"],
        "shared_attention_ops": ["attn_full", "attn_cross"],
        "scan_decode_ops": ["scan"],
    },
}


def _toy():
    """The toy configuration with the ``model_spec`` the loader makes of
    its published keys, as the real file carries it."""
    from dynamo_tpu.engine.config import ModelSpec
    from dynamo_tpu.models.loader import spec_from_hf_config

    spec = spec_from_hf_config(TOY, name=TOY["name"])
    base, own = ModelSpec(), {}
    for f in dataclasses.fields(spec):
        v = getattr(spec, f.name)
        if v != getattr(base, f.name) and f.name not in (
                "name", "vocab_size", "hidden_size", "intermediate_size",
                "num_layers", "num_heads", "num_kv_heads", "head_dim",
                "tie_embeddings", "dtype"):
            own[f.name] = (
                [dataclasses.asdict(k) for k in v] if f.name == "layer_kinds"
                else list(v) if isinstance(v, tuple) else v)
    return dict(TOY, model_spec=dict(own, vocab_draw_blocks=8))


@pytest.fixture(scope="module")
def phi():
    with open(os.path.join(REPO, f"perfbench/configs/{CONFIG}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def readings():
    """A tiny engine through the benchmark's own output check, once."""
    from dynamo_tpu.engine.core import InferenceEngine
    from lib import correct
    from lib import stack as stk

    config = _toy()
    cfg = stk.engine_config(config, 5, profile=False)
    engine = InferenceEngine(stk.model_spec(config), cfg)
    out = correct.check_engine(engine, config, 5, stk.engine_seed(5))
    return config, out


def test_the_program_agrees_with_the_plain_reference(readings):
    _, out = readings
    assert out["ok"], out["rows"]
    for name, row in out["rows"].items():
        assert row["value"] <= row["limit"], (name, row)
    assert out["rows"]["prefill_rel_rms"]["value"] < 2e-5
    # every prompt of the toy check is longer than the toy window
    assert min(out["sample_lens"]) > TOY["sliding_window"]
    assert out["served_rows"] == {"rows": 4, "bursts": [1, 4]}


def test_the_fp8_control_comes_out_as_not_correct(readings):
    from lib import correct
    from lib import stack as stk

    config, _ = readings
    ref = correct.load_reference(config)
    cfg = stk.engine_config(config, 5, profile=False)
    smp = correct.sample(config, cfg, [32, 64], 5)
    want = correct.reference_logits(ref, config, 5, smp)
    low = correct.reference_logits(ref, config, 5, smp, quant="fp8")
    verdict = correct.compare(low, want, {
        k: v for k, v in config["correct"]["limits"].items()
        if k in ("prefill_rel_rms", "decode_rel_rms")})
    assert not verdict["ok"]
    assert verdict["rows"]["prefill_rel_rms"]["value"] > 100 * 2e-4


def test_model_spec_says_what_the_published_keys_say(phi):
    """``model_spec`` repeats in the program's terms what the reference
    reads from the published keys: they must not drift apart."""
    from dynamo_tpu.models.loader import spec_from_hf_config
    from lib import stack as stk
    from references import sambay

    spec = stk.model_spec(phi)
    hash(spec)  # a static argument of every program
    assert spec == dataclasses.replace(
        spec_from_hf_config(phi, name=CONFIG), vocab_draw_blocks=8)
    assert spec.num_layers == len(phi["layers_kept"]) == phi[
        "num_hidden_layers"] == 10
    assert phi["layers_kept"] == KEPT and phi["published_layers"] == 32
    assert (spec.hidden_size, spec.num_heads, spec.num_kv_heads,
            spec.head_dim, spec.intermediate_size, spec.vocab_size) == (
        2560, 40, 20, 64, 10240, 200064)
    assert (spec.scan_inner, spec.scan_state, spec.scan_dt_rank,
            spec.scan_conv) == (5120, 16, 160, 4)
    assert spec.tie_embeddings and not spec.use_rope and spec.attn_bias
    assert spec.norm == "layer" and spec.rms_eps == phi["layer_norm_eps"]
    m = sambay._dims(phi)
    kinds = {"scan": "scan", "gmu": "gmu"}
    for li, l in enumerate(KEPT):
        kd, theirs = spec.kind(li), sambay.layer_kind(m, l)
        assert spec.layer_id(li) == l
        if theirs in kinds:
            assert kd.mixer == kinds[theirs]
            continue
        assert kd.mixer == "softmax" and kd.differential
        assert kd.window == (512 if theirs == "window" else 0)
        assert bool(kd.reads) == (theirs == "cross")
        assert kd.paged == (theirs != "cross")
    assert spec.carried_from == 6 and spec.memory_layer == 4
    assert spec.layer_id(spec.memory_layer) == m["half"] == 16
    # the cross layers read the full layer's pool, the one layer of its kind
    full = spec.layer_pattern[5]
    assert {spec.kind(li).reads for li in (7, 9)} == {(full, 0)}
    assert spec.vocab_draw_blocks == sambay.VOCAB_BLOCKS == 8
    assert (m["c"], m["n"], m["r"], m["taps"], m["hd"]) == (
        5120, 16, 160, 4, 64)
    # a prefix of these layers ends inside the self-decoder
    assert phi["correct"]["decode_layers"] == spec.num_layers
    assert phi["correct"]["min_tokens"] > phi["sliding_window"]
    assert all(any(w in a for a in phi["assumed"]) for w in (
        "d_state 16", "dt_rank", "mb_per_layer", "lambda_init", "NoPE",
        "LayerNorm", "inner_cross_attn", "8 blocks", "ONE departure"))
    assert "float32" in phi["precision"]


def test_the_decode_steps_kernels_stand_for_every_layer(phi):
    """``model.decode_step_ms`` (``readers/device.py``) counts a step as
    the calls of ``trace_names.decode_attention_ops`` over
    ``num_hidden_layers``, so the list must stand for one call a layer a
    step. A GMU layer runs no kernel: ``attn_cross`` is listed a second
    time for them, which holds only while GMU and cross layers come in
    equal numbers. Another cut has to re-tune the list, and this says so
    (PERF.md section 7: a configuration should state its calls a step)."""
    from lib import stack as stk

    spec = stk.model_spec(phi)
    kinds = [spec.kind(li) for li in range(spec.num_layers)]
    calls = {
        "attn_window": sum(k.paged and k.window > 0 for k in kinds),
        "attn_full": sum(k.paged and not k.window for k in kinds),
        "attn_cross": sum(bool(k.reads) for k in kinds),
        "scan": sum(k.mixer == "scan" for k in kinds),
    }
    ops = phi["trace_names"]["decode_attention_ops"]
    assert set(ops) == set(calls)
    assert sum(calls[name] for name in ops) == phi["num_hidden_layers"]
    assert ops.count("attn_cross") == 2
    assert sum(k.mixer == "gmu" for k in kinds) == calls["attn_cross"]


def test_only_the_depth_differs_from_the_catalog_row(phi):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert phi["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in phi or phi[k] != v}
    assert differ == set(phi["reduced"]) == {"num_hidden_layers"}
    assert phi["reduced"]["num_hidden_layers"]["source"] == 32
    assert phi["reduced"]["num_hidden_layers"]["here"] == 10
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == CONFIG)
    assert set(entry["reduced"]) == differ and entry["source"] == phi["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"


def test_the_engine_offers_what_the_check_asks_for(phi):
    from lib import stack as stk

    cfg = stk.engine_config(phi, 1, profile=False)
    spec = stk.model_spec(phi)
    assert cfg.prefill_shapes(spec, 2 * 2**30) == {1024: 2}
    assert cfg.max_context == 10240
    c = phi["correct"]
    assert c["max_tokens"] + 1 + 9 <= c["padded_tokens"]
    assert c["max_tokens"] <= max(phi["engine"]["prefill_buckets"])
    assert c["samples"] * cfg.max_pages_per_seq <= cfg.num_pages
    # the served rows of the check: a slot each, at most 16 pages a row
    assert cfg.max_decode_slots * 16 <= cfg.num_pages
    assert set(c["limits"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap"}


def test_the_arithmetic_of_the_cut_against_the_programs_weights(phi):
    """ISSUE 55's table and ``lib/costs_sambay.py`` against hand counts,
    and against the shapes ``init_params`` and ``init_cache`` would make
    (``jax.eval_shape``: nothing is allocated)."""
    import jax

    from dynamo_tpu.models import llama
    from lib import costs_sambay as c
    from lib import stack as stk

    assert c.layer_kinds(phi) == (
        ["scan", "window"] * 2 + ["scan", "full"] + ["gmu", "cross"] * 2)
    assert c.mixer_params(phi, "scan") == (
        2560 * 10240 + 5 * 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 5120 * 16 + 5120 + 5120 * 2560)
    assert c.mixer_params(phi, "scan") == pytest.approx(41.24e6, rel=1e-3)
    assert c.mixer_params(phi, "window") == c.mixer_params(phi, "full") == (
        2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128)
    assert c.mixer_params(phi, "full") == pytest.approx(19.67e6, rel=1e-3)
    assert c.mixer_params(phi, "gmu") == 2 * 2560 * 5120 == 26214400
    assert c.mixer_params(phi, "cross") == pytest.approx(13.11e6, rel=1e-3)
    assert c.mlp_params(phi) == 3 * 2560 * 10240 == 78643200
    for kind, want in (("scan", 119.9e6), ("window", 98.3e6),
                       ("gmu", 104.9e6), ("cross", 91.8e6)):
        assert c.layer_params(phi, kind) == pytest.approx(want, rel=1e-3)
    assert c.vocabulary_params(phi) == 200064 * 2560 + 2 * 2560
    assert c.total_params(phi) == pytest.approx(1560e6, rel=1e-3)
    # the published model by the same functions: 3.85 B
    whole = c.vocabulary_params(phi) + sum(
        n * c.layer_params(phi, k) for n, k in (
            (9, "scan"), (9, "window"), (7, "gmu"), (7, "cross")))
    assert whole == pytest.approx(3.853e9, rel=1e-3)
    assert c.kv_bytes_per_token_layer(phi) == 5120
    assert c.state_bytes_per_row_layer(phi) == 327680
    assert c.tail_bytes_per_row_layer(phi) == 30720

    spec = stk.model_spec(phi)
    shapes = jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == c.total_params(phi)
    for li, kind in enumerate(c.layer_kinds(phi)):
        layer = sum(int(np.prod(a.shape))
                    for a in jax.tree.leaves(shapes["layers"][li]))
        assert layer == c.layer_params(phi, kind), (li, kind)
    scan = shapes["layers"][0]
    assert scan["scan_in"].shape == (2560, 10240)
    assert scan["scan_x"].shape == (5120, 192)
    assert scan["scan_a_log"].shape == (16, 5120)
    assert scan["scan_a_log"].dtype == np.float32
    assert "wk" not in shapes["layers"][7] and "wq" in shapes["layers"][7]
    eng = phi["engine"]
    rows = eng["max_decode_slots"]
    k, v = jax.eval_shape(lambda: llama.init_cache(
        spec, eng["num_pages"] + 1, eng["page_size"], state_rows=rows))
    pages = eng["num_pages"] + 1
    assert k.pools[0].shape == v.pools[0].shape == (2, pages, 10, 64, 128)
    assert k.pools[1].shape == v.pools[1].shape == (1, pages, 10, 64, 128)
    assert k.pools[2].shape == (3, rows + 1, 16, 5120)
    assert v.pools[2].shape == (3, rows + 1, 3, 5120)
    assert k.pools[3] is None and k.pools[4] is None  # GMU, cross: nothing
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves((k.pools, v.pools)))
    state = (rows + 1) * 3 * (327680 + 30720)
    assert held == pages * 64 * 3 * 5120 + state
    assert state == pytest.approx(0.139e9, rel=0.01)
    # over two fifths of a v5e's 16.9 GB, over the floor for a cell
    assert 0.40 * 16.9e9 < 2 * c.total_params(phi) + held < 0.65 * 16.9e9


def test_the_bytes_and_operations_of_the_kernels(phi):
    from lib import costs_sambay as c

    call = c.scan_step_bytes_per_call(phi, 100.0)
    assert call == 100 * (2 * 327680 + 30720 + 4 * (3 * 5120 + 32)) + (
        4 * 16 * 5120)
    flops = c.scan_step_flops_per_call(phi, 100.0)
    assert flops == 100 * 8 * 16 * 5120
    assert flops / call < 1  # FLOP a byte: bandwidth is the roof by far
    assert c.attention_bytes_per_call(phi, 180000.0) == 180000 * 5120
    step = c.decode_step_bytes(phi, 180000.0, 100.0, 45000.0)
    assert step == pytest.approx(
        2 * c.total_params(phi) + 3 * call + 2 * 45000 * 5120
        + 3 * 180000 * 5120)
    # ISSUE 55's reckoning at ~100 live rows and ~180k live tokens, at the
    # 10 layers kept: the ONE full pool's reads (3 readers) are as large a
    # part of a step as all the weights
    shared = 3 * 180000 * 5120
    assert shared == pytest.approx(2.76e9, rel=0.01)
    assert 0.35 < shared / step < 0.5
    assert 2 * c.vocabulary_params(phi) == pytest.approx(1.02e9, rel=0.01)
    assert c.peak_flops_s("TPU v5 lite") == 197e12


# ------------------------------------------------------------ the readers


def _run(slice_):
    """A run as the readers see it, made of the committed slice of the
    cell's traced run on the chip."""
    def snap(d, at):
        return {"window.at": {"secs": at, "calls": 0},
                **{k: {"secs": 0.0, "calls": v} for k, v in d.items()}}

    class Engine:
        class config:
            page_size = 64

    live = slice_["live"]
    rows = [(t / 10, 0, 0, live["slots"], live["pages"]) for t in range(101)]
    c0, c1 = slice_["counters"]
    return {
        "profile": (snap(c0, 0.0), snap(c1, 12.0)),
        "samples": rows, "t0": 0.0, "seconds": 10.0,
        "traced": (2.0, 3.0, 4.0), "engine": Engine,
        "device": {"kind": "TPU v5 lite"},
        "trace": {"busy_s": 1.0, "window_s": 1.0, "by_kind": {
            "decode": {"secs": slice_["secs"], "runs": slice_["runs"],
                       "ops": slice_["ops"]}}},
        "_regions": {"by_kind": {"decode": {
            "secs": slice_["secs"], "regions": slice_["regions"]}}},
    }


@pytest.fixture(scope="module")
def slice_():
    with open(os.path.join(
            REPO, "tests/perfbench/data/phi4flash_decode_slice.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    from lib import spec as spec_mod

    return spec_mod.load_cell(REPO, CELL)


def test_the_readers_on_a_slice_of_the_cells_trace(phi, cell, slice_):
    from lib import costs_sambay as c

    run = _run(slice_)
    read = {n: cell.readers[f"sambay:{n.split('.', 1)[1]}"] for n in NEW}
    ops, regions, secs = slice_["ops"], slice_["regions"], slice_["secs"]
    live = slice_["live"]
    tokens, batch = 64 * live["pages"], live["slots"]
    # a step: the kernels' calls over the 10 layers, attn_cross twice
    steps = (ops["attn_window"][1] + ops["attn_full"][1] + ops["scan"][1]
             + 2 * ops["attn_cross"][1]) / 10
    assert steps == pytest.approx(slice_["steps"], rel=1e-6)
    # the full layer's kernel runs once a step: the window's edges cut a
    # program's calls apart, so the counts agree to a call in four hundred
    assert ops["attn_full"][1] == pytest.approx(steps, rel=0.005)
    assert cell.readers["device:decode_step_ms"](run, cell) == pytest.approx(
        1e3 * secs / steps)
    assert read[NEW[0]](run, cell) == pytest.approx(100 * (
        regions["gmu"] + regions["attn_cross"]
        + regions["attn_diff"] * 2 / 5) / secs)
    assert read[NEW[1]](run, cell) == pytest.approx(100 * sum(
        regions.get(r, 0.0) for r in (
            "ssm_proj", "ssm_conv", "ssm_gates", "scan", "state_rows")) / secs)
    scan_s = c.scan_step_bytes_per_call(phi, batch) / 819e9
    assert read[NEW[2]](run, cell) == pytest.approx(
        100 * scan_s / (ops["scan"][0] / ops["scan"][1]))
    shared_calls = ops["attn_full"][1] + ops["attn_cross"][1]
    shared_s = ops["attn_full"][0] + ops["attn_cross"][0]
    assert read[NEW[3]](run, cell) == pytest.approx(
        100 * (tokens * 5120 / 819e9) / (shared_s / shared_calls))
    c0, c1 = slice_["counters"]
    dead = (c1["kv.window_dead_tokens"] - c0["kv.window_dead_tokens"]) / (
        c1["kv.window_layer_tokens"] - c0["kv.window_layer_tokens"])
    assert read[NEW[4]](run, cell) == pytest.approx(
        100 * (tokens * (1 - dead) * 5120 / 819e9)
        / (ops["attn_window"][0] / ops["attn_window"][1]))
    assert read[NEW[5]](run, cell) == pytest.approx(100 * (
        c1["prefill.cross_rows"] - c0["prefill.cross_rows"]) / (
        c1["prefill.rows"] - c0["prefill.rows"]))
    for name in NEW:
        assert 0 < read[name](run, cell) <= 100, name
    assert read[NEW[5]](run, cell) < 1


def test_without_scopes_or_counters_the_readers_find_nothing(cell, slice_):
    """A program that lacks the scopes and the counters (the parent
    commit's, or another configuration's), a trace that cannot be joined:
    nothing is read, nothing raises, the metrics are left out."""
    read = [cell.readers[f"sambay:{n.split('.', 1)[1]}"] for n in NEW]
    bare = _run(slice_)
    bare["trace"]["by_kind"]["decode"]["ops"] = {
        "fused_decode_attention": [0.5, 70]}
    bare["_regions"] = None
    bare["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    for fn in read:
        assert fn(bare, cell) is None, fn
    other = _run(slice_)
    other["_regions"] = {"by_kind": {"decode": {
        "secs": 0.9, "regions": {"attn_qkv": 0.2, "mlp": 0.7}}}}
    other["trace"]["by_kind"]["decode"]["ops"] = {"attn_full": [0.1, 10]}
    for fn in read[:3]:
        assert fn(other, cell) is None, fn
    empty = {"profile": ({}, {}), "t0": 0.0, "seconds": 1.0}
    for fn in read:
        assert fn(empty, cell) is None, fn


def test_the_new_entries_and_their_files_agree(phi):
    """Everything found BY NAME: a later PR appends behind this one."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config=CONFIG, traffic="reasoning", chips=1)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers"]
    ours = [e for e in bench["per_layer"] if e["name"] in NEW]
    assert [e["name"] for e in ours] == NEW
    for e in ours:
        with open(os.path.join(
                REPO, "perfbench", "metrics", e["name"] + ".json")) as f:
            m = json.load(f)
        assert e["workloads"] == m["workloads"] == [CELL]
        assert {k: m[k] for k in e if k != "workloads"} == {
            k: e[k] for k in e if k != "workloads"}
        assert e["unit"] == "%" and e["moves"] == "tpot_p50_ms"
        assert m["reader"].startswith("sambay:")
    joined = {"tpot_p50_ms", "engine.compiles_in_window",
              "model.decode_step_ms", "device.idle_share",
              "device.peak_mem_share"}
    has = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
           if CELL in m.get("workloads", ())}
    assert joined | set(NEW) <= has <= joined | set(NEW) | {"out_tok_s"}
    with open(os.path.join(REPO, "perfbench/traffic/reasoning.json")) as f:
        t = json.load(f)
    eng = phi["engine"]
    assert t["max_total_tokens"] <= eng["page_size"] * eng[
        "max_pages_per_seq"] - 8
    assert t["clients_per_slot"] * eng["max_decode_slots"] == 256
    # the cell's loader finds every file by name
    from lib import spec as spec_mod

    loaded = spec_mod.load_cell(REPO, CELL)
    assert loaded.config["reference"] == "sambay"
    assert len(loaded.per_layer) == len(NEW) + 4
    assert os.path.exists(os.path.join(REPO, "perfbench/references/sambay.py"))


# ------------------------------- the whole command, rehearsed on the CPU


def test_the_cell_rehearsed_at_toy_size(tmp_path):
    """``run.py`` on a toy cell of this configuration, by the files the
    real cell uses: the counters reach the result line through the
    program-counter reader; no device metric is printed."""
    import shutil
    import subprocess

    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    config = dict(_toy(), engine=dict(TOY["engine"], pipeline_decode=True))
    (bench / "configs" / "toy-phi4flash.json").write_text(json.dumps(config))
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps({
        "name": "toy-closed", "loop": "closed", "clients": 4,
        "pool_requests": 200,
        "prompt_tokens": {"dist": "uniform", "min": 30, "max": 90},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "max_total_tokens": 120, "lead_in_s": 2.0, "temperature": 0.0,
    }))
    names = ["tpot_p50_ms", "setup_s", "engine.compiles_in_window"] + NEW
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
        "configs": [{"name": "toy-phi4flash", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-phi4flash.json",
                     "why": "toy"}],
        "workloads": [{"name": "toy.closed", "config": "toy-phi4flash",
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
    # prompts of 30-90 tokens: one row in 30-90 goes through the upper half
    assert 1.0 < metrics["engine.sambay_prefill_cross_rows_share"][
        "value"] < 3.5
    assert not [k for k in metrics
                if k.startswith("kernels.") or k.startswith("model.sambay")]
