"""The linear + latent reference (``linear_latent_moe``: KDA layers with
the bounded full-rank decay beside latent (MLA) layers gated by head,
group-limited experts with a clamp a layer) against a tiny engine on the
CPU through the benchmark's own output check, the terms the comparison
must catch, the configuration's file against its own published keys, the
catalog row and the program's parameter count, the byte and operation
counts and the readers of the cell's five new per-layer metrics, and the
whole command rehearsed on a toy cell. Toy sizes in float32: what holds on
the chip at published widths is in PERF.md."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

CELL = "ling3-flash.reasoning"
NEW = ["kernels.linlat_kda_decode_roofline_share",
       "kernels.linlat_kda_prefill_roofline_share",
       "kernels.linlat_latent_decode_roofline_share",
       "kernels.linlat_decode_hbm_share", "model.linlat_mixer_decode_share"]

# the published keys at toy widths: 6 layers published in groups of 3, a
# leading dense layer; layers 0, 4 and 5 kept (dense KDA, KDA, MLA)
TOY = {
    "name": "toy-ling3", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 3,
    "layers_kept": [0, 4, 5], "layer_group_size": 3,
    "first_k_dense_replace": 1, "intermediate_size": 96,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 6000000,
    "rotary_dim": 8, "short_conv_kernel_size": 4, "no_kda_lora": True,
    "use_kda_lora": False, "kda_safe_gate": True, "kda_lower_bound": -5,
    "gated_attention_proj_granularity_type": "head_wise",
    "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
    "num_experts": 4, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "expert_swiglu_limit_list": [0, 0, 0, 0, 0.5, 0.75],
    "share_expert_swiglu_limit_list": [0, 0, 0, 0.6, 0.6, 0.4],
    "rms_norm_eps": 1e-6, "vocab_size": 96, "torch_dtype": "float32",
    "experts": {"published": 16, "held": 4, "first": 4},
    "reference": "linear_latent_moe",
    "model_spec": {
        "tie_embeddings": False,
        "layer_kinds": [
            {"num_kv_heads": 0, "rope_theta": 6e6, "mixer": "latent",
             "head_gate": True},
            {"num_kv_heads": 0, "rope_theta": 0.0, "mixer": "kda",
             "gate_bound": -5.0, "full_rank": True}],
        "layer_pattern": [1, 1, 0],
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rotary_dim": 8, "kda_heads": 4, "kda_head_dim": 16,
        "num_experts": 16, "held_experts": [4, 4],
        "num_experts_per_token": 4, "moe_intermediate_size": 32,
        "moe_scoring": "sigmoid", "n_group": 4, "topk_group": 2,
        "routed_scaling_factor": 2.5, "n_shared_experts": 1,
        "first_k_dense": 1, "expert_clamp": [0, 0.5, 0.75],
        "shared_clamp": [0, 0.6, 0.4],
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
        "decode_attention_ops": ["kda_step", "attn_latent"],
        "kda_decode_ops": ["kda_step"], "kda_prefill_ops": ["kda_chunk"],
        "latent_decode_ops": ["attn_latent"], "expert_ops": ["gmm"],
    },
}

# each changes one published key of the REFERENCE's config: the program,
# which has the published form, must then come out as not correct
FAULTS = {
    "another_decay_bound": {"kda_lower_bound": -2},
    "expert_clamp_left_out": {"expert_swiglu_limit_list": [0] * 6},
    "shared_clamp_left_out": {"share_expert_swiglu_limit_list": [0] * 6},
    "one_more_routing_group": {"topk_group": 3},
    "routed_scale_left_out": {"routed_scaling_factor": 1.0},
    "the_latent_layer_elsewhere": {"layers_kept": [0, 3, 4]},
}


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
    ``fam.m.decode_forward``, tables the check builds itself, and every
    leaf of the pair cut by a leading layer axis."""
    verdict = _verdict(readings)
    assert verdict["ok"], verdict
    assert set(verdict["rows"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap",
    }
    assert readings["smp"]["decode_layers"] == TOY["num_hidden_layers"]


def test_latent_pages_and_state_ride_one_pair_and_no_row_went_missing(
        readings):
    """The pair's leaves: the latent kind's ONE pool (K side alone), the
    KDA kind's states and tails, the directory; the check's own tables
    found every row."""
    k, v = readings["engine"].k_pages, readings["engine"].v_pages
    pages = TOY["engine"]["num_pages"] + 1
    assert k.pools[0].shape == (1, pages, 8, 40) and v.pools[0] is None
    assert k.pools[1].shape == (2, 5, 4, 16, 16)
    assert k.pools[1].dtype == np.float32
    assert v.pools[1].shape == (2, 5, 3, 3, 64)
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


# ------------------------------------------------ the configuration's file


@pytest.fixture(scope="module")
def ling():
    with open(os.path.join(REPO, "perfbench/configs/ling-3.0-flash.json")) as f:
        return json.load(f)


def test_model_spec_says_what_the_published_keys_say(ling):
    """``model_spec`` repeats in the program's terms what the reference
    reads from the published keys: they must not drift apart."""
    from lib import stack as stk
    from references import linear_latent_moe as ref

    spec = stk.model_spec(ling)
    hash(spec)  # a static argument of every program
    m = ref._dims(ling)
    assert not spec.is_mla and spec.has_latent and spec.has_recurrent
    assert spec.mixers == {"kda", "latent"}
    assert spec.num_layers == len(ling["layers_kept"]) == ling[
        "num_hidden_layers"] == 7
    assert ling["layers_kept"] == [0, 36, 37, 38, 39, 40, 41]
    assert (spec.hidden_size, spec.num_heads, spec.head_dim,
            spec.intermediate_size, spec.vocab_size) == (
        2560, 32, 128, 6144, 19648)
    # the pattern: five KDA layers to one MLA layer, the last of its group
    assert [spec.kind(li).mixer for li in range(7)] == [
        "latent" if lat else "kda" for lat in m["latent"]] == (
        ["kda"] * 6 + ["latent"])
    assert [spec.is_moe_layer(li) for li in range(7)] == [
        not d for d in m["dense"]] == [False] + [True] * 6
    kda, lat = spec.kind(0), spec.kind(6)
    assert kda.recurrent and not kda.paged and kda.full_rank
    assert kda.gate_bound == ling["kda_lower_bound"] == -5
    assert lat.paged and lat.latent and not lat.recurrent and lat.head_gate
    assert lat.rope_theta == spec.rope_theta == ling["rope_theta"] == 6e6
    assert (spec.kv_lora_rank, spec.qk_nope_head_dim, spec.qk_rope_head_dim,
            spec.v_head_dim, spec.q_lora_rank, spec.rotary_dim) == (
        512, 128, 64, 128, 0, 64)
    assert ling["q_lora_rank"] is None
    assert (spec.kda_heads, spec.kda_head_dim, spec.kda_conv) == (
        ling["num_attention_heads"], ling["head_dim"],
        ling["short_conv_kernel_size"]) == (32, 128, 4)
    assert not spec.kda_neg_eigval
    assert (spec.num_experts, spec.experts_here, spec.num_experts_per_token,
            spec.moe_intermediate_size, spec.n_group, spec.topk_group,
            spec.routed_scaling_factor, spec.n_shared_experts) == (
        512, (64, 0), 8, 768, 8, 4, 2.5, 1)
    assert ling["experts"] == {"published": 512, "held": 64, "first": 0}
    assert spec.experts_here[0] == 512 // spec.n_group  # a group a chip
    assert spec.moe_scoring == ling["score_function"] == "sigmoid"
    assert spec.norm_topk_prob and ling["norm_topk_prob"]
    # a layer's clamps are the published lists' entries of the layers kept
    for li, p in enumerate(ling["layers_kept"]):
        want = (ling["expert_swiglu_limit_list"][p],
                ling["share_expert_swiglu_limit_list"][p])
        if li >= spec.first_k_dense:
            assert spec.clamps(li) == want == (m["clamp"][li],
                                               m["shared_clamp"][li])
    assert [spec.clamps(li) for li in (1, 5, 6)] == [(4, 5), (4, 7), (4, 7)]
    assert len(ling["expert_swiglu_limit_list"]) == len(
        ling["share_expert_swiglu_limit_list"]) == 42
    assert spec.rms_eps == ling["rms_norm_eps"] == 1e-6
    assert not spec.tie_embeddings and spec.dtype == "bfloat16"
    # the check cuts every leaf by layer: only the full depth is sound
    assert ling["correct"]["decode_layers"] == spec.num_layers
    assert all(any(w in a for a in ling["assumed"]) for w in (
        "layer_group_size", "no_kda_lora", "kda_safe_gate", "head_wise",
        "use_qk_norm", "no alpha", "two largest", "A_log", "vision tower",
        "MTP"))
    assert "float32" in ling["precision"] and "8 v5e" in ling["deployment"]


def test_only_the_cut_differs_from_the_catalog_row(ling):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    assert ling["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if k not in ling or ling[k] != v}
    assert differ == set(ling["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert [ling["reduced"][k]["source"] for k in sorted(differ)] == [
        512, 42, 157184]
    assert ling["vocab_size"] * 8 == 157184
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "ling-3.0-flash")
    assert set(entry["reduced"]) == differ and entry["source"] == ling["source"]
    assert entry["file"] == "perfbench/configs/ling-3.0-flash.json"


def test_the_engine_offers_what_the_check_asks_for(ling):
    """A pack of 2 at the one bucket beside the weights, the state rows
    and the latent pages, charged for both kinds; a state row a slot."""
    from lib import stack as stk

    cfg = stk.engine_config(ling, 1, profile=False)
    spec = stk.model_spec(ling)
    assert cfg.prefill_shapes(spec, 3 * 2**30) == {1024: 2}
    assert cfg.max_context == 10240 and cfg.max_decode_slots == 128
    assert "state_rows" not in ling["engine"]  # one a slot, by the engine
    c = ling["correct"]
    assert c["max_tokens"] + 1 + 9 <= c["padded_tokens"]
    assert c["max_tokens"] <= max(ling["engine"]["prefill_buckets"])
    assert c["samples"] * cfg.max_pages_per_seq <= cfg.num_pages
    assert cfg.max_decode_slots * 10 <= cfg.num_pages
    assert set(c["limits"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap"}


def test_the_arithmetic_of_the_cut_against_the_programs_weights(ling):
    """ISSUE.md's table and ``lib/costs_linear_latent_moe.py`` against
    hand counts, and against the shapes ``init_params`` and ``init_cache``
    would make (``jax.eval_shape``: nothing is allocated)."""
    import jax

    from dynamo_tpu.models import llama
    from lib import costs_linear_latent_moe as c
    from lib import stack as stk

    assert c.kda_mixer_params(ling) == pytest.approx(63.05e6, rel=2e-4)
    assert c.kda_mixer_params(ling) == (
        6 * 2560 * 4096 + 2560 * 32 + 4 * 12288 + 32 + 4096 + 128)
    assert c.latent_mixer_params(ling) == pytest.approx(31.97e6, rel=2e-4)
    assert c.latent_mixer_params(ling) == (
        2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560 + 2560 * 32 + 512)
    assert c.router_params(ling) == 2560 * 512
    assert c.expert_params(ling) == c.shared_params(ling) == 3 * 2560 * 768
    assert c.dense_mlp_params(ling) == 3 * 2560 * 6144 == 47185920
    kda_layer = c.layer_params(ling, latent=False, dense=False)
    assert kda_layer == pytest.approx(447.8e6, abs=0.1e6)
    assert c.layer_params(ling, True, False) == pytest.approx(416.7e6, abs=0.1e6)
    assert c.layer_params(ling, False, True) == pytest.approx(110.2e6, abs=0.1e6)
    # a layer whole: its 512 experts are 6.04 GB, the model ~124 B
    whole = c.layer_params(ling, False, False, held=512)
    assert 512 * c.expert_bytes(ling) == pytest.approx(6.04e9, rel=1e-3)
    model = (2 * c.layer_params(ling, False, True)
             + 33 * whole + 7 * c.layer_params(ling, True, False, held=512)
             + 2 * 157184 * 2560)
    assert model == pytest.approx(124e9, rel=0.02)
    assert c.weight_bytes(ling) == 2 * (
        c.layer_params(ling, False, True) + 5 * kda_layer
        + c.layer_params(ling, True, False) + 2 * 19648 * 2560)
    assert c.weight_bytes(ling) == pytest.approx(5.73e9, rel=2e-3)
    assert c.state_bytes_per_row_layer(ling) == 2097152
    assert c.conv_tail_bytes_per_row_layer(ling) == 3 * 12288 * 2
    assert c.latent_bytes_per_token(ling) == 1152
    assert c.latent_bytes_per_token(ling, laid_out=True) == 1280

    spec = stk.model_spec(ling)
    shapes = jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    # the residual stream's norm gains (two a layer and the last) and the
    # router's correction bias are what the costs leave out
    assert count == c.weight_bytes(ling) // 2 + 15 * 2560 + 6 * 512
    layers = shapes["layers"]
    assert layers[0]["w_f"].shape == layers[0]["w_g"].shape == (2560, 4096)
    assert layers[0]["w_gate"].shape == (2560, 6144) and "moe" not in layers[0]
    assert layers[1]["a_log"].dtype == layers[1]["dt_bias"].dtype == np.float32
    assert layers[6]["wq"].shape == (2560, 6144)
    assert layers[6]["w_kv_a"].shape == (2560, 576)
    assert layers[6]["w_uk"].shape == layers[6]["w_uv"].shape == (32, 512, 128)
    assert layers[6]["w_gate_head"].shape == (2560, 32)
    assert layers[6]["moe"]["router"].shape == (2560, 512)
    assert layers[6]["moe"]["w_gate"].shape == (64, 2560, 768)
    eng = ling["engine"]
    rows = eng["max_decode_slots"]
    k, v = jax.eval_shape(lambda: llama.init_cache(
        spec, eng["num_pages"] + 1, eng["page_size"], state_rows=rows))
    assert k.pools[0].shape[0] == 1 and k.pools[0].shape[1:3] == (
        eng["num_pages"] + 1, 64)
    assert k.pools[0].shape[3] in (576, 640) and v.pools[0] is None
    assert k.pools[1].shape == (6, rows + 1, 32, 128, 128)
    assert v.pools[1].shape == (6, rows + 1, 3, 3, 4096)
    state = (rows + 1) * 6 * (c.state_bytes_per_row_layer(ling)
                              + c.conv_tail_bytes_per_row_layer(ling))
    assert state == pytest.approx(1.68e9, rel=0.01)
    latents = (eng["num_pages"] + 1) * 64 * 1280
    assert latents == pytest.approx(0.34e9, rel=0.02)
    # 46% of a v5e's 15.75 GiB before the programs' own buffers: over the
    # floor of a quarter
    assert 0.40 * 16.9e9 < c.weight_bytes(ling) + state + latents < 0.55 * 16.9e9


def test_the_bytes_and_operations_of_the_kernels(ling):
    from lib import costs_linear_latent_moe as c

    row, tail = 2097152, 73728
    call = c.kda_step_bytes_per_call(ling, 90.0)
    assert call == 90 * (2 * row + 5 * 4096 * 4)
    flops = c.kda_step_flops_per_call(ling, 90.0)
    assert flops == 7 * 90 * 32 * 128 * 128
    assert flops / call < 1  # FLOP a byte: bandwidth is the roof by far
    # a pack of two prompts of 1,024 and 300 tokens, the first resumed: as
    # PR 40's kernel does the work, tokens in and out and the states
    moved = c.kda_chunk_bytes_per_call(ling, 1324.0, 2, 1)
    assert moved == 1324 * (5 * 4096 + 32) * 4 + 3 * row
    ops = c.kda_chunk_flops_per_call(ling, 21)
    block = (2 * 2 * 16 * 128 * 16 * 10 + 64 * 64 * 256
             + 2 * 64 * 128 * (3 * 128 + 64) + 128 * 128)
    assert ops == 21 * 32 * block
    assert block == pytest.approx(9.7e6, rel=0.01)
    # the latent kernel at 180k live rows and 90 slots: at 32 heads its
    # products take a fifth of what its bytes do at the peaks (53 FLOP a
    # byte against the chip's 240): the roof is the bandwidth
    moved = c.latent_decode_bytes_per_call(ling, 180000.0, 90.0)
    assert moved == 1280 * 180090 + 90 * 32 * (640 + 512) * 2
    ops = c.latent_decode_flops_per_call(ling, 180000.0, 90.0)
    assert ops == 2 * 32 * (2 * 512 + 64) * 180090
    assert 0.15 < (ops / 197e12) / (moved / 819e9) < 0.3
    step = c.decode_step_bytes(ling, 180000.0, 90.0, 6 * 48.0)
    assert step == pytest.approx(
        c.weight_bytes(ling) - 19648 * 2560 * 2 - 6 * 16 * c.expert_bytes(ling)
        + 90 * 2560 * 2 + 6 * 90 * 2 * (row + tail) + 1280 * 180090)
    # ISSUE.md's reckoning: the state a third of a step's bytes, the
    # touched experts about half, the latents a few per cent
    assert 0.25 < 6 * 90 * 2 * row / step < 0.40
    assert 0.40 < 6 * 48 * c.expert_bytes(ling) / step < 0.55
    assert 1280 * 180000 / step < 0.05
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
            snap({"recurrent_state.rows": 128, "kda.prefill_blocks": 40,
                  "kda.rows_resumed": 3, "moe.decode.experts_touched": 0,
                  "moe.decode.steps": 0}, 0.0),
            snap({"recurrent_state.rows": 128, "kda.prefill_blocks": 133,
                  "kda.rows_resumed": 5, "moe.decode.experts_touched": 28800,
                  "moe.decode.steps": 100}, 12.0)),
        "samples": rows, "t0": 0.0, "seconds": 10.0,
        "traced": (2.0, 3.0, 4.0), "engine": Engine,
        "device": {"kind": "TPU v5 lite"},
        "prefills": [(1.0, [900, 900]), (2.2, [1024, 300]),
                     (2.6, [64, 0]), (3.5, [1000, 1000]), (11.0, [500, 0]),
                     (12.5, [700, 0])],
        # 60 model steps in 0.6 s of decode programs: kda_step six times
        # a step, attn_latent once
        "trace": {"busy_s": 1.0, "window_s": 1.0, "by_kind": {
            "decode": {"secs": 0.6, "runs": 8, "ops": {
                "attn_latent.1": [0.03, 60], "kda_step.4": [0.3, 360],
                "fusion.3": [0.27, 9000]}},
            "prefill": {"secs": 0.1, "runs": 2, "ops": {
                "fusion.9": [0.1, 400]}}}},
    }
    if decode_regions is not None:
        run["_regions"] = {"by_kind": {
            "decode": {"secs": 0.6, "regions": decode_regions},
            "prefill": {"secs": 0.1, "regions": prefill_regions}}}
    else:
        run["_regions"] = None  # no registry, or a trace without programs
    return run


@pytest.fixture(scope="module")
def cell():
    from lib import spec as spec_mod

    return spec_mod.load_cell(REPO, CELL)


def test_the_readers_on_a_small_trace(ling, cell):
    from lib import costs_linear_latent_moe as c

    run = _run(
        {"kda_step": 0.3, "kda_proj": 0.04, "kda_conv": 0.03,
         "kda_gates": 0.01, "state_rows": 0.002, "attn_latent": 0.03,
         "latent_q": 0.004, "latent_kv": 0.001, "latent_absorb": 0.003,
         "latent_schedule": 0.001, "attn_out": 0.02, "gmm": 0.1,
         "moe_route": 0.01, "head": 0.02, "norm": 0.028},
        {"kda_chunk": 0.02, "kda_chunk_operands": 0.001, "mlp": 0.06,
         "prefill_latent": 0.019})
    read = {n: cell.readers[f"linear_latent:{n.split('.', 1)[1]}"]
            for n in NEW}
    # 90 live slots holding 2,800 pages = 179,200 tokens while traced
    call_s = c.kda_step_bytes_per_call(ling, 90.0) / 819e9
    assert c.kda_step_flops_per_call(ling, 90.0) / 197e12 < call_s
    assert read[NEW[0]](run, cell) == pytest.approx(100 * call_s / (0.3 / 360))
    # the traced part saw the dispatches at 2.2 and 2.6 s: 1,388 tokens
    # over 3 rows; of the 93 blocks and 2 resumed rows the engine counted
    # between its snapshots, the traced part's share by tokens and by rows;
    # 6 KDA layers; the operand scope's time counts too
    chunk_s = 6 * max(
        c.kda_chunk_bytes_per_call(ling, 1388.0, 3, 2 * 3 / 8) / 819e9,
        c.kda_chunk_flops_per_call(ling, 93 * 1388 / 5688) / 197e12)
    assert read[NEW[1]](run, cell) == pytest.approx(100 * chunk_s / 0.021)
    lat_s = max(
        c.latent_decode_bytes_per_call(ling, 179200.0, 90.0) / 819e9,
        c.latent_decode_flops_per_call(ling, 179200.0, 90.0) / 197e12)
    assert read[NEW[2]](run, cell) == pytest.approx(100 * lat_s / (0.03 / 60))
    # 288 experts touched a step over the window (its mean of live slots:
    # 90 in 11 samples of 101, else 120), scaled to the traced part's 90
    window = (11 * 90 + 90 * 120) / 101
    step_s = c.decode_step_bytes(
        ling, 179200.0, 90.0, 288 * 90 / window) / 819e9
    assert read[NEW[3]](run, cell) == pytest.approx(100 * step_s / (0.6 / 60))
    assert read[NEW[4]](run, cell) == pytest.approx(100 * (
        0.3 + 0.04 + 0.03 + 0.01 + 0.002 + 0.03 + 0.004 + 0.001 + 0.003
        + 0.001 + 0.02) / 0.6)
    for name in NEW:
        assert 0 < read[name](run, cell) <= 100, name
    # the readers the benchmark had count seven kernel calls a step
    assert cell.readers["device:decode_step_ms"](run, cell) == pytest.approx(
        1e3 * 0.6 / 60)


def test_without_scopes_or_counters_the_readers_find_nothing(ling, cell):
    """A program that lacks the scopes and the counters (the parent
    commit's, or another configuration's), a trace that cannot be joined:
    nothing is read, nothing raises, the metrics are left out."""
    read = [cell.readers[f"linear_latent:{n.split('.', 1)[1]}"] for n in NEW]
    bare = _run()
    bare["trace"]["by_kind"]["decode"]["ops"] = {
        "fused_decode_attention": [0.5, 70]}
    bare["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    for fn in read:
        assert fn(bare, cell) is None, fn
    # joined, but to a program of one of the two mechanisms alone (Solar's)
    other = _run({"attn_qkv": 0.2, "kda_step": 0.2, "mlp": 0.2},
                 {"mlp": 0.1})
    other["trace"]["by_kind"]["decode"]["ops"].pop("attn_latent.1")
    other["profile"] = ({"idle": {"secs": 1.0, "calls": 1}},) * 2
    for fn in read[1:]:
        assert fn(other, cell) is None, fn
    empty = {"profile": ({}, {}), "t0": 0.0, "seconds": 1.0}
    for fn in read:
        assert fn(empty, cell) is None, fn


def test_the_new_entries_and_their_files_agree(ling):
    """Everything found BY NAME: a later PR appends behind this one."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(
        entry, config="ling-3.0-flash", traffic="reasoning", chips=1)
    config = next(c for c in bench["configs"] if c["name"] == "ling-3.0-flash")
    assert len(entry["why"]) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert not any(w["chips"] == 4 for w in bench["workloads"])
    ours = [e for e in bench["per_layer"] if e["name"] in NEW]
    assert [e["name"] for e in ours] == NEW
    for e in ours:
        with open(os.path.join(
                REPO, "perfbench", "metrics", e["name"] + ".json")) as f:
            m = json.load(f)
        assert e["workloads"] == m["workloads"] == [CELL]
        assert {k: m[k] for k in e if k != "workloads"} == {
            k: e[k] for k in e if k != "workloads"}
        assert e["unit"] == "%" and e["source"] == "device_trace"
        assert e["moves"] == ("out_tok_s" if "prefill" in e["name"]
                              else "tpot_p50_ms")
        assert m["reader"].startswith("linear_latent:")
    joined = {"tpot_p50_ms", "out_tok_s", "engine.compiles_in_window",
              "cache.pages_peak_share", "model.decode_step_ms",
              "device.idle_share", "device.peak_mem_share",
              "moe.tokens_per_expert_step", "moe.expert_load_max_over_mean"}
    has = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
           if CELL in m.get("workloads", ())}
    assert has == joined | set(NEW)
    with open(os.path.join(REPO, "perfbench/traffic/reasoning.json")) as f:
        t = json.load(f)
    eng = ling["engine"]
    assert t["max_total_tokens"] <= eng["page_size"] * eng[
        "max_pages_per_seq"] - 8
    assert t["clients_per_slot"] * eng["max_decode_slots"] == 256
    # the cell's loader finds every file by name
    from lib import spec as spec_mod

    loaded = spec_mod.load_cell(REPO, CELL)
    assert loaded.config["reference"] == "linear_latent_moe"
    assert len(loaded.per_layer) == 12 and len(loaded.end_to_end) == 3
    for name in ("references/linear_latent_moe.py", "readers/linear_latent.py",
                 "lib/costs_linear_latent_moe.py"):
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
    (bench / "configs" / "toy-ling3.json").write_text(json.dumps(config))
    (bench / "traffic" / "toy-closed.json").write_text(json.dumps({
        "name": "toy-closed", "loop": "closed", "clients": 4,
        "pool_requests": 200,
        "prompt_tokens": {"dist": "uniform", "min": 30, "max": 90},
        "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
        "max_total_tokens": 120, "lead_in_s": 2.0, "temperature": 0.0,
    }))
    names = ["tpot_p50_ms", "out_tok_s", "setup_s",
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
        "configs": [{"name": "toy-ling3", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-ling3.json", "why": "toy"}],
        "workloads": [{"name": "toy.closed", "config": "toy-ling3",
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
    # the expert counters of this family reach the accepted readers as
    # they are: top-4 of 16 in 2 of 4 groups, a group of 4 held
    assert 0 < metrics["moe.tokens_per_expert_step"]["value"] <= 4
    assert metrics["moe.expert_load_max_over_mean"]["value"] >= 1
    assert not [k for k in metrics
                if k.startswith("kernels.") or k.startswith("model.")]
