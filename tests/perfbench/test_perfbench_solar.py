"""The linear-attention reference with held experts and a shared expert
(``linear_moe``) against a tiny engine on the CPU through the benchmark's
own output check, the faults the comparison must catch, the configuration's
file against its own published keys, the catalog row and ``ModelSpec``'s
parameter count, the byte and operation counts and the readers the cell's
four new per-layer metrics use, and the whole command rehearsed on a toy
cell. Toy sizes in float32: what holds on the chip at published widths is
in PERF.md."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

# the published keys at toy widths: 12 layers published, the first 4 kept
# (one GQA layer, three KDA layers); 8 routed experts, 4 held from 2
TOY = {
    "name": "toy-solar", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
    "layers_kept": [0, 1, 2, 3], "gqa_layers": [0, 4, 8],
    "use_rope": False, "use_gqa_gate": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "first_k_dense_replace": 0, "intermediate_size": 64,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "n_shared_experts": 1,
    "experts": {"published": 8, "held": 4, "first": 2},
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "vocab_size": 96, "tie_word_embeddings": False, "torch_dtype": "float32",
    "reference": "linear_moe",
    "model_spec": {
        "layer_kinds": [{"num_kv_heads": 2, "rope_theta": 10000.0},
                        {"num_kv_heads": 0, "rope_theta": 0.0, "mixer": "kda"}],
        "layer_pattern": [0, 1, 1, 1], "use_rope": False, "attn_gate": True,
        "kda_heads": 4, "kda_head_dim": 16, "kda_conv": 4,
        "kda_neg_eigval": True, "num_experts": 8, "held_experts": [4, 2],
        "num_experts_per_token": 2, "moe_intermediate_size": 32,
        "moe_scoring": "sigmoid", "norm_topk_prob": True,
        "routed_scaling_factor": 1.0, "n_shared_experts": 1,
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
        "decode_attention_ops": ["attn_full", "kda_step"],
        "full_attention_ops": ["attn_full"], "kda_decode_ops": ["kda_step"],
        "kda_prefill_ops": ["kda_chunk"], "expert_ops": ["gmm"],
    },
}

# each takes one term of the layers' equations out of the REFERENCE: the
# program, which has it, must then come out as not correct
FAULTS = {
    "gqa_gate_left_out": {"use_gqa_gate": False},
    "shared_expert_left_out": {"n_shared_experts": 0},
    "beta_held_under_one": {"kda_allow_neg_eigval": False},
    "correction_bias_left_out": None,  # see _forward
    "decay_left_out": None,
    "convolution_left_out": None,
}


def _forward(ref, config, fault, seed, tokens, positions, **kw):
    if fault in ("gqa_gate_left_out", "beta_held_under_one"):
        return ref.forward(dict(config, **FAULTS[fault]), seed, tokens,
                           positions, **kw)
    if fault == "shared_expert_left_out":
        # the weights' keys stay where they are: only the term goes
        name, real = "_mlp", ref._mlp

        def patched(x, lw, **k):
            return real(x, lw, **dict(k, shared=False))
    elif fault == "correction_bias_left_out":
        name, real = "_mlp", ref._mlp

        def patched(x, lw, **k):
            return real(x, dict(lw, score_bias=lw["score_bias"] * 0), **k)
    elif fault == "decay_left_out":
        name, real = "_kda", ref._kda

        def patched(x, lw, taps, a_log, dt_bias, **k):  # alpha = 1
            return real(x, lw, taps, a_log - 40.0, dt_bias, **k)
    else:
        name, real = "_kda", ref._kda

        def patched(x, lw, taps, a_log, dt_bias, **k):  # the token alone
            alone = [t.at[:-1].set(0) for t in taps]
            return real(x, lw, alone, a_log, dt_bias, **k)

    setattr(ref, name, patched)
    try:
        return ref.forward(config, seed, tokens, positions, **kw)
    finally:
        setattr(ref, name, real)


@pytest.fixture(scope="module")
def readings():
    """One tiny engine and the reference, read once."""
    os.environ["DYNAMO_PALLAS"] = "1"  # the kernels, interpreted
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


def test_the_state_rides_the_pair_and_no_row_went_missing(readings):
    """The pair's leaves: a page pool for the GQA kind, the states and
    the convolution tails for the KDA kind, the directory (a leading axis
    of 1); the check's own tables found every row (3 sampled prompts, then
    4 served rows over 4 state rows: take-overs, nothing missing)."""
    k, v = readings["engine"].k_pages, readings["engine"].v_pages
    pages = TOY["engine"]["num_pages"] + 1
    assert k.pools[0].shape == v.pools[0].shape == (1, pages, 2, 8, 16)
    assert k.pools[1].shape == (3, 5, 4, 16, 16)
    assert k.pools[1].dtype == np.float32
    assert v.pools[1].shape == (3, 5, 3, 3, 64)
    assert k.rows.owner.shape == (1, 5) and v.rows is None
    stats = np.asarray(k.rows.stats[0])
    assert stats[2] == 0 and stats[1] >= 4
    assert k.counts.shape == (4, 2, 4 + 3)
    assert int(np.asarray(k.counts)[:, :, -1].min()) > 0  # both phases ran


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
def solar():
    with open(os.path.join(REPO, "perfbench/configs/solar-open2-250b.json")) as f:
        return json.load(f)


def test_model_spec_says_what_the_published_keys_say(solar):
    """``model_spec`` repeats in the program's terms what the reference
    reads from the published keys: they must not drift apart."""
    from lib import stack as stk

    spec = stk.model_spec(solar)
    hash(spec)  # a static argument of every program
    assert not spec.is_mla and spec.has_recurrent
    assert spec.num_layers == len(solar["layers_kept"]) == solar[
        "num_hidden_layers"] == 4
    assert (spec.hidden_size, spec.num_heads, spec.num_kv_heads,
            spec.head_dim) == tuple(solar[k] for k in (
                "hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim")) == (4096, 64, 8, 128)
    # the kinds by layer are the published list's
    for li, published in enumerate(solar["layers_kept"]):
        kind = spec.kind(li)
        assert kind.recurrent == (published not in solar["gqa_layers"])
        if not kind.recurrent:
            assert (kind.num_kv_heads, kind.window, kind.sinks) == (8, 0, False)
    assert [spec.kind(li).recurrent for li in range(4)] == [
        False, True, True, True]
    assert solar["gqa_layers"] == list(range(0, 48, solar["gqa_interval"] + 1))
    assert spec.use_rope is solar["use_rope"] is False
    assert spec.attn_gate is solar["use_gqa_gate"] is True
    lin = solar["linear_attn_config"]
    assert (spec.kda_heads, spec.kda_head_dim, spec.kda_conv) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    ) == (64, 128, 4)
    assert lin["num_kv_heads"] is None
    assert solar["kda_use_full_proj"] is False  # rank head_dim pairs
    assert spec.kda_neg_eigval is solar["kda_allow_neg_eigval"] is True
    assert spec.rms_eps == solar["rms_norm_eps"] == 1e-5
    assert spec.first_k_dense == solar["first_k_dense_replace"] == 0
    ex = solar["experts"]
    assert spec.num_experts == ex["published"] == 320
    assert spec.experts_here == (ex["held"], ex["first"]) == (20, 0)
    assert solar["n_routed_experts"] == ex["held"]
    assert spec.num_experts_per_token == solar["num_experts_per_tok"] == 8
    assert spec.moe_intermediate_size == solar["moe_intermediate_size"] == 1280
    assert spec.moe_scoring == "sigmoid" and not spec.n_group
    assert spec.norm_topk_prob is solar["norm_topk_prob"] is True
    assert spec.routed_scaling_factor == solar["routed_scaling_factor"] == 1
    assert spec.n_shared_experts == solar["n_shared_experts"] == 1
    assert not spec.tie_embeddings and spec.vocab_size == 196608 // 8
    # the check cuts every leaf by layer: only the full depth is sound
    assert solar["correct"]["decode_layers"] == spec.num_layers
    assert {"gate", "scoring function", "intermediate_size", "float32"} <= {
        w for w in ("gate", "scoring function", "intermediate_size", "float32")
        if any(w in a for a in solar["assumed"])}


def test_only_the_stated_keys_differ_from_the_catalog_row(solar):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    assert solar["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if solar.get(k) != v}
    assert differ == set(solar["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "solar-open2-250b")
    assert set(entry["reduced"]) == differ and entry["source"] == solar["source"]


def test_the_engine_offers_what_the_check_asks_for(solar):
    """A pack of 2 at the one bucket beside 4.1 GB of weights, 1.68 GB of
    state and 2.7 GB of pages (the output check needs a packed prefill at
    every bucket its rows use), whatever the table's width; a state row a
    decode slot."""
    import dataclasses

    from lib import stack as stk

    cfg = stk.engine_config(solar, 1, profile=False)
    spec = stk.model_spec(solar)
    assert cfg.prefill_shapes(spec, 4 * 2**30) == {1024: 2}
    wide = dataclasses.replace(cfg, max_pages_per_seq=16 * cfg.max_pages_per_seq)
    assert wide.prefill_shapes(spec, 4 * 2**30) == {1024: 2}
    assert cfg.prefill_shapes(spec, 2**30) == {1024: 1}
    assert cfg.max_context == 10240
    assert "state_rows" not in solar["engine"]  # one a slot, by the engine
    assert cfg.max_decode_slots == 128
    c = solar["correct"]
    assert c["max_tokens"] + 1 + 9 <= c["padded_tokens"]
    assert c["max_tokens"] <= max(solar["engine"]["prefill_buckets"])
    assert c["samples"] * cfg.max_pages_per_seq <= cfg.num_pages


def test_the_arithmetic_of_the_cut_against_the_programs_weights(solar):
    """``why`` and ``lib/costs_linear_moe.py`` against hand counts, and
    against the shapes ``init_params`` and ``init_cache`` would make
    (``jax.eval_shape``: nothing is allocated)."""
    import jax

    from dynamo_tpu.models import llama
    from lib import costs_linear_moe as c
    from lib import stack as stk

    assert c.gqa_mixer_params(solar) == 3 * 4096 * 8192 + 2 * 4096 * 1024
    assert c.gqa_mixer_params(solar) == pytest.approx(109.1e6, rel=1e-3)
    assert c.kda_mixer_params(solar) == (
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
        + 3 * 8192 * 4 + 64 + 8192 + 128)
    assert c.kda_mixer_params(solar) == pytest.approx(137.7e6, rel=1e-3)
    assert c.expert_params(solar) == 3 * 4096 * 1280
    assert c.layer_params(solar, True) == pytest.approx(440.7e6, rel=1e-3)
    assert c.layer_params(solar, False) == pytest.approx(469.4e6, rel=1e-3)
    assert c.weight_bytes(solar) == 2 * (
        c.layer_params(solar, True) + 3 * c.layer_params(solar, False)
        + 2 * 24576 * 4096)
    assert c.weight_bytes(solar) == pytest.approx(4.10e9, rel=0.01)
    # the whole model, by the same functions: 250.3 B, and 13.9 B active
    # a token (top-8 of the experts; the embedding is looked up, not read)
    whole = (12 * c.layer_params(solar, True, held=320)
             + 36 * c.layer_params(solar, False, held=320)
             + 2 * 196608 * 4096)
    assert whole == pytest.approx(250.3e9, rel=2e-3)
    active = (12 * c.layer_params(solar, True, held=8)
              + 36 * c.layer_params(solar, False, held=8)
              + 196608 * 4096)
    assert active == pytest.approx(13.9e9, rel=5e-3)
    assert c.state_bytes_per_row_layer(solar) == 64 * 128 * 128 * 4
    assert c.conv_tail_bytes_per_row_layer(solar) == 3 * 24576 * 2
    assert c.kv_bytes_per_token_layer(solar) == 4096

    spec = stk.model_spec(solar)
    shapes = jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0)))
    gains = 4096 * (2 * 4 + 1)  # attn_norm, mlp_norm a layer; final_norm
    bias = 4 * 320  # the routers' correction biases
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count - gains - bias == c.weight_bytes(solar) // 2
    eng = solar["engine"]
    k, v = jax.eval_shape(lambda: llama.init_cache(
        spec, eng["num_pages"] + 1, eng["page_size"],
        state_rows=eng["max_decode_slots"]))
    assert k.pools[1].shape == (3, 129, 64, 128, 128)
    assert v.pools[1].shape == (3, 129, 3, 3, 8192)
    state = 129 * 3 * (c.state_bytes_per_row_layer(solar)
                      + c.conv_tail_bytes_per_row_layer(solar))
    assert state == pytest.approx(1.68e9, rel=0.01)
    assert k.pools[0].shape == (1, 10241, 8, 64, 128)
    pages = 2 * int(np.prod(k.pools[0].shape)) * 2
    assert pages == pytest.approx(2.68e9, rel=0.01)
    # a quarter of a v5e's 16 GB is the floor for a cell
    assert c.weight_bytes(solar) + state + pages > 0.5 * 16e9


def test_the_bytes_and_operations_of_the_kernels(solar):
    from lib import costs_linear_moe as c

    row = 64 * 128 * 128 * 4
    call = c.kda_step_bytes_per_call(solar, 256.0)
    assert call == 256 * (2 * row + 64 * 128 * 5 * 4)
    flops = c.kda_step_flops_per_call(solar, 256.0)
    assert flops == 7 * 256 * 64 * 128 * 128
    assert flops / call < 1  # FLOP a byte: bandwidth is the roof by far
    # a pack of two prompts of 1,024 and 300 tokens: 16 + 5 blocks
    blocks = 16 + 5
    chunk = c.kda_chunk_bytes_per_call(solar, blocks, 2)
    assert chunk == 64 * (
        blocks * (4 * 64 * 128 + 64 * 64 + 2 * 64 * 128) * 4
        + 2 * 2 * 128 * 128 * 4)
    ops = c.kda_chunk_flops_per_call(solar, blocks)
    assert ops == blocks * 64 * (
        2 * 64 * 128 * 128 * 3 + 2 * 64 * 64 * 128 + 128 * 128)
    assert 30 < ops / chunk < 60  # under the v5e's ridge (240)
    step = c.decode_step_bytes(solar, 256 * 1600.0, 256.0)
    assert step == pytest.approx(
        c.weight_bytes(solar) - 24576 * 4096 * 2 + 256 * 4096 * 2
        + 3 * 256 * 2 * (row + 3 * 24576 * 2)
        + 4096 * (256 * 1600 + 256))
    # the state is about half of a step's bytes at 256 live rows
    assert 0.4 < 3 * 256 * 2 * row / step < 0.6
    # and about a quarter at the cell's 64 slots with ~45 live
    few = c.decode_step_bytes(solar, 45 * 1600.0, 45.0, 4 * 14.0)
    assert 0.15 < 3 * 45 * 2 * row / few < 0.3
    half = c.decode_step_bytes(solar, 256 * 1600.0, 256.0, 4 * 10.0)
    assert step - half == 4 * 10 * c.expert_bytes(solar)
    assert c.peak_flops_s("TPU v5 lite") == 197e12


# ------------------------------------------------------------ the readers


def _run(before, after, decode_ops, prefill_ops, traced_slots=200):
    def snap(d):
        return {k: {"secs": 0.0, "calls": v} for k, v in d.items()}

    class Engine:
        class config:
            page_size = 64

    rows = [(t / 10, 0, 0, traced_slots if 20 <= t <= 30 else 240,
             5000) for t in range(0, 101)]
    return {"profile": (snap(before), snap(after)), "samples": rows,
            "t0": 0.0, "seconds": 10.0, "traced": (2.0, 3.0, 4.0),
            "engine": Engine, "device": {"kind": "TPU v5 lite"},
            "prefills": [(1.0, [900, 900]), (2.2, [1024, 300]),
                         (2.6, [64, 0]), (3.5, [1000, 1000])],
            "trace": {"by_kind": {
                "decode": {"secs": 2.4, "ops": decode_ops, "runs": 15},
                "prefill": {"secs": 0.5, "ops": prefill_ops, "runs": 2}}}}


class _Cell:
    def __init__(self, config):
        self.config = config


def test_the_recurrent_readers_on_a_small_trace(solar):
    from lib import costs_linear_moe as c
    from readers import recurrent as reader

    # 120 model steps in 2.4 s of decode programs: the GQA kernel once a
    # step, kda_step three times, 5 ms a call; 200 live slots holding 5,000
    # pages = 320k tokens; two prefill programs, kda_chunk 3 calls each
    decode_ops = {"attn_full.1": [0.12, 120], "kda_step.4": [1.8, 360],
                  "gmm.7": [0.3, 1440], "fusion.3": [0.18, 9000]}
    prefill_ops = {"kda_chunk.2": [0.03, 6], "fusion.9": [0.47, 400]}
    before = {"recurrent_state.rows": 256, "moe.decode.steps": 50,
              "moe.decode.experts_touched": 50 * 80}
    after = {"recurrent_state.rows": 256, "moe.decode.steps": 250,
             "moe.decode.experts_touched": 50 * 80 + 200 * 78}
    run, cell = _run(before, after, decode_ops, prefill_ops), _Cell(solar)
    call_s = c.kda_step_bytes_per_call(solar, 200.0) / 819e9
    assert c.kda_step_flops_per_call(solar, 200.0) / 197e12 < call_s
    assert reader.kda_decode_roofline_share(run, cell) == pytest.approx(
        100 * call_s / 0.005)
    # the traced part saw the dispatches at 2.2 and 2.6 s: (16 + 5 + 1)
    # blocks and 3 rows over 2 dispatches
    chunk_s = max(
        c.kda_chunk_bytes_per_call(solar, 11.0, 1.5) / 819e9,
        c.kda_chunk_flops_per_call(solar, 11.0) / 197e12)
    assert reader.kda_prefill_roofline_share(run, cell) == pytest.approx(
        100 * chunk_s / 0.005)
    window = (90 * 240 + 11 * 200) / 101
    step_s = c.decode_step_bytes(
        solar, 320000.0, 200.0, 78.0 * 200 / window) / 819e9
    assert reader.linear_decode_hbm_share(run, cell) == pytest.approx(
        100 * step_s / (2.4 / 120))
    assert reader.state_rows_peak_share(run, cell) == 100 * 240 / 256
    # the readers the benchmark had count a mixer's kernel a layer a step
    from readers import device

    assert device.decode_step_ms(run, cell) == pytest.approx(1e3 * 2.4 / 120)
    # and the two kernel shares of the window/global family that this
    # cell joins read the GQA layer's kernel and the expert ops by this
    # configuration's widths (``costs_hybrid_moe_keys`` in its file)
    from readers import moe

    attn_s = (4096 * (320000.0 + 200) + 200 * 64 * (128 + 128) * 2) / 819e9
    assert moe.full_decode_attn_hbm_share(run, cell) == pytest.approx(
        100 * attn_s / 0.001)
    experts_s = 78.0 * 200 / window * 3 * 4096 * 1280 * 2 / 819e9
    assert moe.moe_experts_hbm_share(run, cell) == pytest.approx(
        100 * experts_s / (0.3 / 120))
    assert moe.window_decode_attn_hbm_share(run, cell) is None


def test_without_counters_or_names_the_readers_find_nothing(solar):
    """A program that lacks the scopes and the counters (the parent
    commit's, or another configuration's): nothing is read, nothing
    raises, the metrics are left out of the line."""
    from readers import recurrent as reader

    run = {"profile": ({"idle": {"secs": 1.0, "calls": 1}},) * 2,
           "trace": {"by_kind": {"decode": {"secs": 1.0, "ops": {
               "fused_decode_attention": [0.5, 70]}, "runs": 1}}},
           "traced": (1.0, 2.0, 3.0), "t0": 0.0, "seconds": 5.0,
           "samples": [(1.5, 0, 10, 3, 40)], "prefills": [(1.5, [40])],
           "device": {"kind": "TPU v5 lite"}}
    bare = _Cell({"trace_names": {"programs": {}}, "num_hidden_layers": 7})
    for cell in (bare, _Cell(solar)):
        for fn in (reader.kda_decode_roofline_share,
                   reader.kda_prefill_roofline_share,
                   reader.linear_decode_hbm_share,
                   reader.state_rows_peak_share):
            assert fn(run, cell) is None
    empty = {"profile": ({}, {}), "t0": 0.0, "seconds": 1.0}
    assert reader.state_rows_peak_share(empty, bare) is None
    assert reader.linear_decode_hbm_share(empty, bare) is None


def test_the_new_entries_and_their_files_agree(solar):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]
    assert cell == dict(
        cell, name="solar-open2.reasoning", config="solar-open2-250b",
        traffic="reasoning", chips=1)
    assert len(cell["why"]) <= 200 and len(bench["configs"][-1]["why"]) <= 200
    new = ["kernels.kda_decode_roofline_share",
           "kernels.kda_prefill_roofline_share",
           "kernels.linear_decode_hbm_share", "cache.state_rows_peak_share"]
    # found by name, not by place: a later PR appends behind them
    ours = [e for e in bench["per_layer"] if e["name"] in new]
    assert [e["name"] for e in ours] == new
    for e in ours:
        with open(os.path.join(
                REPO, "perfbench", "metrics", e["name"] + ".json")) as f:
            m = json.load(f)
        assert e["workloads"] == m["workloads"] == ["solar-open2.reasoning"]
        assert {k: m[k] for k in e if k != "workloads"} == {
            k: e[k] for k in e if k != "workloads"}
    joined = {"tpot_p50_ms", "out_tok_s", "engine.host_share",
              "engine.compiles_in_window", "cache.pages_peak_share",
              "model.decode_step_ms", "device.idle_share",
              "device.peak_mem_share", "moe.tokens_per_expert_step",
              "moe.expert_load_max_over_mean",
              "kernels.moe_experts_hbm_share",
              "kernels.full_decode_attn_hbm_share"}
    # not joined: a ``spans:`` metric's list must equal its metric file's
    # (tests/perfbench/test_perfbench_spans.py), which is not this PR's
    assert "solar-open2.reasoning" not in next(
        m for m in bench["per_layer"]
        if m["name"] == "model.prefill_paired_tok_s.reasoning")["workloads"]
    has = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
           if "solar-open2.reasoning" in m.get("workloads", ())}
    assert has == joined | set(new)
    with open(os.path.join(REPO, "perfbench/traffic/reasoning.json")) as f:
        t = json.load(f)
    eng = solar["engine"]
    assert t["max_total_tokens"] <= eng["page_size"] * eng[
        "max_pages_per_seq"] - 8
    assert t["clients_per_slot"] * eng["max_decode_slots"] == 256
    # the cell's loader finds every file by name
    from lib import spec as spec_mod

    loaded = spec_mod.load_cell(REPO, "solar-open2.reasoning")
    assert loaded.config["reference"] == "linear_moe"
    assert len(loaded.per_layer) == 14 and len(loaded.end_to_end) == 3


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
    (bench / "configs" / "toy-solar.json").write_text(json.dumps(config))
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps({
        "name": "toy-closed", "loop": "closed", "clients": 4,
        "pool_requests": 200,
        "prompt_tokens": {"dist": "uniform", "min": 30, "max": 90},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "max_total_tokens": 120, "lead_in_s": 2.0, "temperature": 0.0,
    }))
    names = ["tpot_p50_ms", "out_tok_s", "setup_s",
             "moe.tokens_per_expert_step", "moe.expert_load_max_over_mean",
             "cache.pages_peak_share", "cache.state_rows_peak_share",
             "kernels.kda_decode_roofline_share",
             "kernels.kda_prefill_roofline_share",
             "kernels.linear_decode_hbm_share"]
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
        "configs": [{"name": "toy-solar", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-solar.json", "why": "toy"}],
        "workloads": [{"name": "toy.closed", "config": "toy-solar",
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
    assert 0 < metrics["cache.pages_peak_share"]["value"] <= 100
    # four clients on four slots and four rows: every row is live
    assert 50 <= metrics["cache.state_rows_peak_share"]["value"] <= 100
    assert not [k for k in metrics if k.startswith("kernels.")]
