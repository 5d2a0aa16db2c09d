"""The parallel-hybrid reference (``parallel_ssm``: a Mamba-2 mixer and GQA
attention off one norm in every layer, the Falcon-H1 family's multipliers)
against a tiny engine on the CPU through the benchmark's own output check,
the faults the comparison must catch, the configuration's file against its
own published keys, the catalog row and the program's parameter count, the
byte and operation counts and the readers of the cell's five new per-layer
metrics, and the whole command rehearsed on a toy cell. Toy sizes in
float32: what holds on the chip at published widths is in PERF.md."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

CELL = "falcon-h1.reasoning"
NEW = ["kernels.ssd_decode_roofline_share",
       "kernels.ssd_prefill_roofline_share", "kernels.ssm_decode_hbm_share",
       "model.ssm_decode_share", "cache.ssm_state_rows_peak_share"]

# the published keys at toy widths: 12 layers published, the first 3 kept
TOY = {
    "name": "toy-falcon-h1", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 3,
    "intermediate_size": 96, "vocab_size": 96, "rms_norm_eps": 1e-5,
    "rope_theta": 1e11, "rope_scaling": None, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_ssm": 32,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "embedding_multiplier": 5.0, "lm_head_multiplier": 0.125,
    "key_multiplier": 0.3, "attention_in_multiplier": 0.9,
    "attention_out_multiplier": 0.6, "ssm_in_multiplier": 0.5,
    "ssm_out_multiplier": 0.7, "ssm_multipliers": [0.35, 0.5, 0.7, 0.8, 0.6],
    "mlp_multipliers": [0.7, 0.4],
    "reference": "parallel_ssm",
    "model_spec": {
        "layer_kinds": [{"num_kv_heads": 2, "rope_theta": 1e11,
                         "mixer": "ssd"}],
        "layer_pattern": [0, 0, 0],
        "ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 16, "ssm_groups": 2,
        "ssm_conv": 4, "ssm_chunk": 16,
        "embedding_multiplier": 5.0, "lm_head_multiplier": 0.125,
        "key_multiplier": 0.3, "attention_in_multiplier": 0.9,
        "attention_out_multiplier": 0.6, "ssm_in_multiplier": 0.5,
        "ssm_out_multiplier": 0.7,
        "ssm_multipliers": [0.35, 0.5, 0.7, 0.8, 0.6],
        "mlp_multipliers": [0.7, 0.4], "vocab_draw_blocks": 8,
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
        "padded_tokens": 72, "decode_layers": 3,
        "limits": {"prefill_rel_rms": 2e-4, "decode_rel_rms": 2e-4,
                   "packed_prefill_rel_rms": 2e-4, "served_token_gap": 0.01},
    },
    "trace_names": {
        "programs": {"decode": ["decode_steps"],
                     "prefill": ["prefill_forward"]},
        "decode_attention_ops": ["attn_full"],
        "full_attention_ops": ["attn_full"], "ssd_decode_ops": ["ssd_step"],
    },
}

# each takes one term of the layer's equations out of the REFERENCE (a
# multiplier back to 1, the taps' bias, the skip D x): the program, which
# has it, must then come out as not correct
FAULTS = {
    "key_multiplier_left_out": {"key_multiplier": 1.0},
    "attention_out_multiplier_left_out": {"attention_out_multiplier": 1.0},
    "ssm_out_multiplier_left_out": {"ssm_out_multiplier": 1.0},
    "dt_multiplier_left_out": {"ssm_multipliers": [0.35, 0.5, 0.7, 0.8, 1.0]},
    "mlp_gate_multiplier_left_out": {"mlp_multipliers": [1.0, 0.4]},
    "conv_bias_left_out": {"mamba_conv_bias": False},
    "skip_left_out": None,  # see _forward
    "attention_left_out": None,
}


def _forward(ref, config, fault, seed, tokens, positions, **kw):
    if FAULTS[fault] is not None:
        return ref.forward(dict(config, **FAULTS[fault]), seed, tokens,
                           positions, **kw)
    if fault == "skip_left_out":
        name, real = "_ssm", ref._ssm

        def patched(u, w, taps, conv_bias, a_log, dt_bias, d_skip, m):
            return real(u, w, taps, conv_bias, a_log, dt_bias, d_skip * 0, m)
    else:  # the SSM alone in the sum
        name, real = "_attention", ref._attention

        def patched(u, w, m):
            return real(u, w, m) * 0

    setattr(ref, name, patched)
    ref._mixers.clear_cache()
    try:
        return ref.forward(config, seed, tokens, positions, **kw)
    finally:
        setattr(ref, name, real)
        ref._mixers.clear_cache()


@pytest.fixture(scope="module")
def readings():
    """One tiny engine and the reference, read once."""
    os.environ["DYNAMO_PALLAS"] = "1"  # the kernel, interpreted
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
    ``fam.m.decode_forward``, tables the check builds itself, no slot
    argument, no release, and every leaf of the pair cut by a leading
    layer axis."""
    verdict = _verdict(readings)
    assert verdict["ok"], verdict
    assert set(verdict["rows"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap",
    }
    assert readings["smp"]["decode_layers"] == TOY["num_hidden_layers"]


def test_pages_and_state_ride_the_pair_and_no_row_went_missing(readings):
    """The pair's leaves: for the one kind its page pool and, beside it,
    the states (K side) and the convolution tails (V side), the directory
    (a leading axis of 1); the check's own tables found every row."""
    k, v = readings["engine"].k_pages, readings["engine"].v_pages
    pages = TOY["engine"]["num_pages"] + 1
    assert k.pools[0].pages.shape == v.pools[0].pages.shape == (
        3, pages, 2, 8, 16)
    assert k.pools[0].state.shape == (3, 5, 4, 8, 16)
    assert k.pools[0].state.dtype == np.float32
    assert v.pools[0].state.shape == (3, 5, 3, 32 + 2 * 2 * 16)
    assert k.rows.owner.shape == (1, 5) and v.rows is None
    stats = np.asarray(k.rows.stats[0])
    assert stats[2] == 0 and stats[1] >= 4


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


def test_the_reference_at_two_chunkings_gives_the_same_logits(readings):
    """Rows a call, the MLP's column blocks and the vocabulary's blocks
    are how the reference fits beside the model, not what it computes: two
    rows a call with the MLP whole give the logits of four with it in
    four blocks."""
    ref, r = readings["ref"], readings
    tokens = r["smp"]["tokens"]
    at = np.tile(np.arange(5, 60, 11), (tokens.shape[0], 1)).astype(np.int32)
    a = np.asarray(ref.forward(TOY, r["wseed"], tokens, at))
    was = ref.ROWS_AT_ONCE, ref.MLP_BLOCKS
    ref.ROWS_AT_ONCE, ref.MLP_BLOCKS = 2, 1
    ref._mlp.clear_cache()
    try:
        b = np.asarray(ref.forward(TOY, r["wseed"], tokens, at))
    finally:
        ref.ROWS_AT_ONCE, ref.MLP_BLOCKS = was
        ref._mlp.clear_cache()
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


# ------------------------------------------------ the configuration's file


@pytest.fixture(scope="module")
def falcon():
    with open(os.path.join(REPO, "perfbench/configs/falcon-h1-34b.json")) as f:
        return json.load(f)


def test_model_spec_says_what_the_published_keys_say(falcon):
    """``model_spec`` repeats in the program's terms what the reference
    reads from the published keys: they must not drift apart."""
    from lib import stack as stk

    spec = stk.model_spec(falcon)
    hash(spec)  # a static argument of every program
    assert not spec.is_mla and spec.has_recurrent and spec.mixers == {"ssd"}
    assert spec.num_layers == len(falcon["layers_kept"]) == falcon[
        "num_hidden_layers"] == 4
    assert (spec.hidden_size, spec.num_heads, spec.num_kv_heads,
            spec.head_dim, spec.intermediate_size, spec.vocab_size) == tuple(
        falcon[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size")) == (
        5120, 20, 4, 128, 21504, 261120)
    for li in range(4):  # every layer keeps both
        kind = spec.kind(li)
        assert kind.paged and kind.recurrent and kind.mixer == "ssd"
        assert (kind.num_kv_heads, kind.window, kind.sinks) == (4, 0, False)
        assert kind.rope_theta == falcon["rope_theta"] == 1e11
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state,
            spec.ssm_groups, spec.ssm_conv, spec.ssm_chunk) == tuple(
        falcon[k] for k in (
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size")) == (
        32, 128, 256, 2, 4, 128)
    assert spec.ssm_heads * spec.ssm_head_dim == falcon["mamba_d_ssm"] == 4096
    assert spec.ssm_conv_dim == 5120
    for ours, theirs in (
            ("embedding_multiplier", "embedding_multiplier"),
            ("lm_head_multiplier", "lm_head_multiplier"),
            ("key_multiplier", "key_multiplier"),
            ("attention_in_multiplier", "attention_in_multiplier"),
            ("attention_out_multiplier", "attention_out_multiplier"),
            ("ssm_in_multiplier", "ssm_in_multiplier"),
            ("ssm_out_multiplier", "ssm_out_multiplier")):
        assert getattr(spec, ours) == falcon[theirs]
    assert spec.ssm_multipliers == tuple(falcon["ssm_multipliers"])
    assert spec.mlp_multipliers == tuple(falcon["mlp_multipliers"])
    assert len(spec.ssm_multipliers) == 5 and len(spec.mlp_multipliers) == 2
    assert spec.rms_eps == falcon["rms_norm_eps"] == 1e-5
    # the tables are drawn as the reference draws them: a float32 table
    # whole would be 5.35 GB
    from references import parallel_ssm

    assert spec.vocab_draw_blocks == parallel_ssm.VOCAB_BLOCKS == 8
    assert not spec.tie_embeddings and not falcon["tie_word_embeddings"]
    assert spec.use_rope and not spec.rope_scaling_factor
    for key in ("attention_bias", "mlp_bias", "projectors_bias",
                "mamba_proj_bias", "mamba_norm_before_gate"):
        assert falcon[key] is False
    assert falcon["mamba_conv_bias"] is falcon["mamba_rms_norm"] is True
    # the check cuts every leaf by layer: only the full depth is sound
    assert falcon["correct"]["decode_layers"] == spec.num_layers
    assert all(any(w in a for a in falcon["assumed"]) for w in (
        "mamba_use_mlp", "num_logits_to_keep", "clamp", "A_log", "8 blocks"))
    assert "float32" in falcon["precision"]


def test_only_the_depth_differs_from_the_catalog_row(falcon):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert falcon["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in falcon or falcon[k] != v}
    assert differ == set(falcon["reduced"]) == {"num_hidden_layers"}
    assert falcon["reduced"]["num_hidden_layers"]["source"] == 72
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "falcon-h1-34b")
    assert set(entry["reduced"]) == differ and entry["source"] == falcon["source"]
    assert entry["file"] == "perfbench/configs/falcon-h1-34b.json"


def test_the_engine_offers_what_the_check_asks_for(falcon):
    """A pack of 2 at the one bucket beside 8.8 GB of weights, 2.2 GB of
    state and 1.9 GB of pages, whatever the table's width; a state row a
    decode slot."""
    import dataclasses

    from lib import stack as stk

    cfg = stk.engine_config(falcon, 1, profile=False)
    spec = stk.model_spec(falcon)
    assert cfg.prefill_shapes(spec, 2 * 2**30) == {1024: 2}
    wide = dataclasses.replace(cfg, max_pages_per_seq=16 * cfg.max_pages_per_seq)
    assert wide.prefill_shapes(spec, 2 * 2**30) == {1024: 2}
    assert cfg.max_context == 10240
    assert "state_rows" not in falcon["engine"]  # one a slot, by the engine
    assert cfg.max_decode_slots in (128, 96)
    c = falcon["correct"]
    assert c["max_tokens"] + 1 + 9 <= c["padded_tokens"]
    assert c["max_tokens"] <= max(falcon["engine"]["prefill_buckets"])
    assert c["samples"] * cfg.max_pages_per_seq <= cfg.num_pages
    # the served rows of the check: a slot each, at most 10 pages a row
    assert cfg.max_decode_slots * 10 <= cfg.num_pages
    assert set(c["limits"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap"}


def test_the_arithmetic_of_the_cut_against_the_programs_weights(falcon):
    """ISSUE.md's table and ``lib/costs_parallel_ssm.py`` against hand
    counts, and against the shapes ``init_params`` and ``init_cache`` would
    make (``jax.eval_shape``: nothing is allocated)."""
    import jax

    from dynamo_tpu.models import llama
    from lib import costs_parallel_ssm as c
    from lib import stack as stk

    assert c.attention_params(falcon) == (
        2 * 5120 * 2560 + 2 * 5120 * 512) == 31457280
    assert c.ssm_params(falcon) == (
        5120 * 9248 + 4096 * 5120 + 5 * 5120 + 4096 + 3 * 32)
    assert c.ssm_params(falcon) == pytest.approx(68.35e6, rel=1e-3)
    assert c.mlp_params(falcon) == 3 * 5120 * 21504 == 330301440
    assert c.layer_params(falcon) == pytest.approx(430.1e6, rel=1e-4)
    assert c.vocabulary_params(falcon) == 261120 * 5120
    assert 2 * c.vocabulary_params(falcon) * 2 == pytest.approx(5.35e9, rel=1e-3)
    assert c.weight_bytes(falcon) == 2 * (
        4 * c.layer_params(falcon) + 5120 + 2 * 261120 * 5120)
    assert c.weight_bytes(falcon) == pytest.approx(8.79e9, rel=1e-3)
    # the whole model by the same functions: 34 B
    whole = 72 * c.layer_params(falcon) + 2 * c.vocabulary_params(falcon)
    assert whole == pytest.approx(33.6e9, rel=0.01)
    assert c.state_bytes_per_row_layer(falcon) == 4194304
    assert c.conv_tail_bytes_per_row_layer(falcon) == 30720
    assert c.kv_bytes_per_token_layer(falcon) == 2048

    spec = stk.model_spec(falcon)
    shapes = jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == c.weight_bytes(falcon) // 2
    assert shapes["layers"][0]["ssm_in"].shape == (5120, 9248)
    assert shapes["layers"][0]["ssm_a_log"].dtype == np.float32
    eng = falcon["engine"]
    rows = eng["max_decode_slots"]
    k, v = jax.eval_shape(lambda: llama.init_cache(
        spec, eng["num_pages"] + 1, eng["page_size"], state_rows=rows))
    assert k.pools[0].state.shape == (4, rows + 1, 32, 128, 256)
    assert v.pools[0].state.shape == (4, rows + 1, 3, 5120)
    assert k.pools[0].pages.shape == (4, eng["num_pages"] + 1, 4, 64, 128)
    state = (rows + 1) * 4 * (c.state_bytes_per_row_layer(falcon)
                              + c.conv_tail_bytes_per_row_layer(falcon))
    pages = 2 * int(np.prod(k.pools[0].pages.shape)) * 2
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves((k, v)))
    assert held == pytest.approx(state + pages, rel=1e-4)
    if rows == 128:
        assert state == pytest.approx(2.18e9, rel=0.01)
    assert pages == pytest.approx(
        (eng["num_pages"] + 1) * 64 * 8192, rel=1e-6)
    # three quarters of a v5e's 16 GB, far over the floor for a cell
    assert 0.7 * 16e9 < c.weight_bytes(falcon) + state + pages < 0.85 * 16e9


def test_the_bytes_and_operations_of_the_kernels(falcon):
    from lib import costs_parallel_ssm as c

    row, tail = 4194304, 30720
    call = c.ssd_step_bytes_per_call(falcon, 90.0)
    assert call == 90 * (2 * row + 2 * tail + (3 * 4096 + 2 * 512) * 4)
    flops = c.ssd_step_flops_per_call(falcon, 90.0)
    assert flops == 5 * 90 * 32 * 128 * 256
    assert flops / call < 1  # FLOP a byte: bandwidth is the roof by far
    # a pack of two prompts of 1,024 and 300 tokens, the first resumed: 8
    # + 3 chunks
    moved = c.ssd_chunk_bytes_per_call(falcon, 1324.0, 2, 1)
    assert moved == 1324 * (5120 * 2 + (32 + 4096) * 4) + 3 * row
    ops = c.ssd_chunk_flops_per_call(falcon, 11)
    assert ops == 11 * (2 * 2 * 128 * 128 * 256 + 32 * (
        2 * 128 * 128 * 128 + 4 * 128 * 128 * 256 + 128 * 256))
    # ISSUE.md's ~5 MFLOP a token a layer beside 860 of projections
    assert c.ssd_chunk_flops_per_call(falcon, 1) / 128 == pytest.approx(
        5.4e6, rel=0.02)
    step = c.decode_step_bytes(falcon, 180000.0, 90.0)
    assert step == pytest.approx(
        c.weight_bytes(falcon) - 261120 * 5120 * 2 + 90 * 5120 * 2
        + 4 * 90 * 2 * (row + tail) + 4 * 2048 * (180000 + 90))
    # the state is about a third of a step's bytes at 90 live rows, the
    # pages a seventh, the head a quarter
    assert 0.25 < 4 * 90 * 2 * row / step < 0.33
    assert 0.1 < 4 * 2048 * 180000 / step < 0.2
    assert 0.2 < 261120 * 5120 * 2 / step < 0.3
    assert c.peak_flops_s("TPU v5 lite") == 197e12


# ------------------------------------------------------------ the readers


def _run(decode_regions=None, prefill_regions=None):
    def snap(d, at):
        return {"window.at": {"secs": at, "calls": 0},
                **{k: {"secs": 0.0, "calls": v} for k, v in d.items()}}

    class Engine:
        class config:
            page_size = 64

    rows = [(t / 10, 0, 0, 90 if 20 <= t <= 30 else 120, 2800)
            for t in range(0, 101)]
    run = {
        # between the snapshots (the second waits for the profiler, past
        # the window's 10 s) five dispatches: 5,688 tokens in 8 rows, 2
        # of them resumed
        "profile": (
            snap({"recurrent_state.rows": 128, "ssd.prefill_chunks": 40,
                  "ssd.rows_resumed": 3}, 0.0),
            snap({"recurrent_state.rows": 128, "ssd.prefill_chunks": 92,
                  "ssd.rows_resumed": 5}, 12.0)),
        "samples": rows, "t0": 0.0, "seconds": 10.0,
        "traced": (2.0, 3.0, 4.0), "engine": Engine,
        "device": {"kind": "TPU v5 lite"},
        "prefills": [(1.0, [900, 900]), (2.2, [1024, 300]),
                     (2.6, [64, 0]), (3.5, [1000, 1000]), (11.0, [500, 0]),
                     (12.5, [700, 0])],
        # 60 model steps in 0.9 s of decode programs: each kernel four
        # times a step
        "trace": {"busy_s": 1.0, "window_s": 1.0, "by_kind": {
            "decode": {"secs": 0.9, "runs": 8, "ops": {
                "attn_full.1": [0.06, 240], "ssd_step.4": [0.3, 240],
                "fusion.3": [0.54, 9000]}},
            "prefill": {"secs": 0.1, "runs": 2, "ops": {
                "fusion.9": [0.1, 400]}}}},
    }
    if decode_regions is not None:
        run["_regions"] = {"by_kind": {
            "decode": {"secs": 0.9, "regions": decode_regions},
            "prefill": {"secs": 0.1, "regions": prefill_regions}}}
    else:
        run["_regions"] = None  # no registry, or a trace without programs
    return run


@pytest.fixture(scope="module")
def cell():
    from lib import spec as spec_mod

    return spec_mod.load_cell(REPO, CELL)


def test_the_ssm_readers_on_a_small_trace(falcon, cell):
    from lib import costs_parallel_ssm as c

    run = _run(
        {"ssd_step": 0.3, "ssm_proj": 0.08, "ssm_conv": 0.01,
         "ssm_gates": 0.02, "state_rows": 0.004, "attn_full": 0.06,
         "mlp": 0.3, "head": 0.1, "norm": 0.026},
        {"ssd_chunk": 0.008, "mlp": 0.07, "attn_kv": 0.022})
    read = {n: cell.readers[f"ssm:{n.split('.', 1)[1]}"] for n in NEW}
    # 90 live slots holding 2,800 pages = 179,200 tokens while traced
    call_s = c.ssd_step_bytes_per_call(falcon, 90.0) / 819e9
    assert c.ssd_step_flops_per_call(falcon, 90.0) / 197e12 < call_s
    assert read[NEW[0]](run, cell) == pytest.approx(100 * call_s / (0.3 / 240))
    # the traced part saw the dispatches at 2.2 and 2.6 s: 1,388 tokens
    # over 3 rows; of the 52 chunks and 2 resumed rows the engine counted
    # between its snapshots, the traced part's share by tokens and by rows;
    # 4 layers
    chunk_s = 4 * max(
        c.ssd_chunk_bytes_per_call(falcon, 1388.0, 3, 2 * 3 / 8) / 819e9,
        c.ssd_chunk_flops_per_call(falcon, 52 * 1388 / 5688) / 197e12)
    assert read[NEW[1]](run, cell) == pytest.approx(100 * chunk_s / 0.008)
    step_s = c.decode_step_bytes(falcon, 179200.0, 90.0) / 819e9
    assert read[NEW[2]](run, cell) == pytest.approx(100 * step_s / (0.9 / 60))
    assert read[NEW[3]](run, cell) == pytest.approx(
        100 * (0.3 + 0.08 + 0.01 + 0.02 + 0.004) / 0.9)
    assert read[NEW[4]](run, cell) == 100 * 120 / 128
    for name in NEW:
        assert 0 < read[name](run, cell) <= 100, name
    # the readers the benchmark had count a layer's attention kernel a step
    assert cell.readers["device:decode_step_ms"](run, cell) == pytest.approx(
        1e3 * 0.9 / 60)


def test_without_scopes_or_counters_the_readers_find_nothing(falcon, cell):
    """A program that lacks the scopes and the counters (the parent
    commit's, or another configuration's), a trace that cannot be joined:
    nothing is read, nothing raises, the metrics are left out."""
    read = [cell.readers[f"ssm:{n.split('.', 1)[1]}"] for n in NEW]
    bare = _run()
    bare["trace"]["by_kind"]["decode"]["ops"] = {
        "fused_decode_attention": [0.5, 70]}
    bare["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    for fn in read:
        assert fn(bare, cell) is None, fn
    # joined, but to a program without the SSM's regions
    other = _run({"attn_qkv": 0.2, "mlp": 0.7}, {"mlp": 0.1})
    other["trace"]["by_kind"]["decode"]["ops"].pop("ssd_step.4")
    for fn in read[:4]:
        assert fn(other, cell) is None, fn
    empty = {"profile": ({}, {}), "t0": 0.0, "seconds": 1.0}
    for fn in read:
        assert fn(empty, cell) is None, fn


def test_the_new_entries_and_their_files_agree(falcon):
    """Everything found BY NAME: a later PR appends behind this one."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(
        entry, config="falcon-h1-34b", traffic="reasoning", chips=1)
    config = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b")
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
        assert m["reader"].startswith("ssm:")
    joined = {"tpot_p50_ms", "out_tok_s", "engine.compiles_in_window",
              "cache.pages_peak_share", "model.decode_step_ms",
              "device.idle_share", "device.peak_mem_share"}
    has = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
           if CELL in m.get("workloads", ())}
    assert has == joined | set(NEW)
    # the accepted lists keep their order: the cell is appended
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in joined:
            assert m["workloads"][-1] == CELL or CELL in m["workloads"]
    with open(os.path.join(REPO, "perfbench/traffic/reasoning.json")) as f:
        t = json.load(f)
    eng = falcon["engine"]
    assert t["max_total_tokens"] <= eng["page_size"] * eng[
        "max_pages_per_seq"] - 8
    assert t["clients_per_slot"] * eng["max_decode_slots"] in (256, 192)
    # the cell's loader finds every file by name
    from lib import spec as spec_mod

    loaded = spec_mod.load_cell(REPO, CELL)
    assert loaded.config["reference"] == "parallel_ssm"
    assert len(loaded.per_layer) == 10 and len(loaded.end_to_end) == 3
    assert os.path.exists(os.path.join(
        REPO, "perfbench/references/parallel_ssm.py"))


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
    (bench / "configs" / "toy-falcon-h1.json").write_text(json.dumps(config))
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps({
        "name": "toy-closed", "loop": "closed", "clients": 4,
        "pool_requests": 200,
        "prompt_tokens": {"dist": "uniform", "min": 30, "max": 90},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "max_total_tokens": 120, "lead_in_s": 2.0, "temperature": 0.0,
    }))
    names = ["tpot_p50_ms", "out_tok_s", "setup_s",
             "engine.compiles_in_window", "cache.pages_peak_share"] + NEW
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
        "configs": [{"name": "toy-falcon-h1", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-falcon-h1.json", "why": "toy"}],
        "workloads": [{"name": "toy.closed", "config": "toy-falcon-h1",
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
    # four clients on four slots and four rows: every row is live
    assert 50 <= metrics["cache.ssm_state_rows_peak_share"]["value"] <= 100
    assert not [k for k in metrics
                if k.startswith("kernels.") or k == "model.ssm_decode_share"]
