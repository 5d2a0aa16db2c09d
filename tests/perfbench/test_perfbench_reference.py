"""The plain reference against a tiny engine on the CPU, and its controls:
the comparison that decides ``correct`` passes the program, and fails the
reference computed in a lower precision and an engine with other weights.
Toy sizes: what holds on the chip at published widths is in PERF.md."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

TOY = {
    "name": "toy", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 272, "num_hidden_layers": 2, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "reference": "dense_gqa",
    "engine": {
        "page_size": 16, "num_pages": 64, "max_pages_per_seq": 16,
        "max_decode_slots": 4, "prefill_buckets": [64, 128],
        "prefill_pack_size": 2, "max_prefill_chunk_tokens": 128,
        "decode_steps_per_dispatch": 4, "kv_dtype": "bf16",
        "guided_mode": "off",
    },
    "correct": {
        "samples": 3, "min_tokens": 36, "max_tokens": 100, "decode_steps": 3,
        "padded_tokens": 128, "decode_layers": 1,
        "limits": {"prefill_rel_rms": 0.03, "decode_rel_rms": 0.03,
                   "packed_prefill_rel_rms": 0.03, "served_token_gap": 0.1},
    },
}


@pytest.fixture(scope="module")
def readings():
    """One tiny engine, the reference and its controls, read once."""
    os.environ["DYNAMO_PALLAS"] = "1"  # the fused kernel, interpreted
    try:
        from dynamo_tpu.engine.core import InferenceEngine
        from lib import correct
        from lib import stack as stk

        seed = 2**31 + 5  # a seed past 32 signed bits
        cfg = stk.engine_config(TOY, seed, profile=False)
        engine = InferenceEngine(stk.model_spec(TOY), cfg)
        ref = correct.load_reference(TOY)
        smp = correct.sample(TOY, cfg, list(engine._prefill_shapes), seed)
        wseed = stk.engine_seed(seed)
        out = {
            "correct": correct, "smp": smp, "limits": TOY["correct"]["limits"],
            "got": correct.engine_logits(engine, smp),
            "want": correct.reference_logits(ref, TOY, wseed, smp),
            "other": correct.reference_logits(ref, TOY, wseed + 1, smp),
        }
        for quant in ("fp8", "int8"):
            out[quant] = correct.reference_logits(
                ref, TOY, wseed, smp, quant=quant
            )
        # the second sample: the programs as served
        rows = correct.served_sample(TOY, engine, seed)
        packed, chosen = correct.served_outputs(engine, rows)
        out.update(
            rows=rows, packed=packed, chosen=chosen, engine=engine,
            want_rows=correct.served_reference(ref, TOY, wseed, rows),
            other_rows=correct.served_reference(ref, TOY, wseed + 1, rows),
            fp8_rows=correct.served_reference(
                ref, TOY, wseed, rows, quant="fp8"),
        )
        yield out
    finally:
        os.environ.pop("DYNAMO_PALLAS", None)


def test_the_program_agrees_with_the_plain_reference(readings):
    r = readings
    verdict = r["correct"].compare(r["got"], r["want"], r["limits"])
    assert verdict["ok"], verdict
    assert set(verdict["rows"]) == {"prefill_rel_rms", "decode_rel_rms"}
    assert all(row["value"] > 0 for row in verdict["rows"].values())


def _served(r, chosen=None, packed=None, want="want_rows"):
    c = r["correct"]
    return c.served_numbers(
        r["packed"] if packed is None else packed,
        r["chosen"] if chosen is None else chosen,
        r[want], r["rows"]["bursts"],
    )


def test_the_programs_as_served_agree_with_the_plain_reference(readings):
    r = readings
    served = _served(r)
    verdict = r["correct"].compare(r["got"], r["want"], r["limits"], served)
    assert verdict["ok"], verdict
    assert set(verdict["rows"]) == {
        "prefill_rel_rms", "decode_rel_rms", "packed_prefill_rel_rms",
        "served_token_gap",
    }
    assert {"token_gap_burst_of_1", "token_gap_burst_of_4"} <= set(
        verdict["also"])
    assert 0 < served["packed_prefill_rel_rms"] < 0.03
    # most tokens are the reference's own, the rest near-ties
    assert served["also"]["tokens_as_the_reference"] > 0.7
    assert served["also"]["token_gap_max"] < 0.5


def test_every_slot_decodes_a_burst_of_every_length_served(readings):
    r = readings
    rows, engine = r["rows"], r["engine"]
    assert len(rows["lens"]) == engine.config.max_decode_slots == 4
    assert rows["bursts"] == sorted(engine._burst_lengths) == [1, 4]
    assert r["chosen"].shape == (4, 5)
    # the packed prefill ran in the engine's own shapes, a bucket each
    assert {engine.config.bucket_for(n) for n in rows["lens"]} <= set(
        engine._prefill_shapes)
    # the reference read the sequences the engine decoded
    for i, n in enumerate(rows["lens"]):
        assert list(rows["tokens"][i, n + 1: n + 6]) == list(r["chosen"][i])


@pytest.mark.parametrize("fault", ["a_slot_reads_its_neighbour", "a_lost_carry",
                                   "another_sampler", "other_weights"])
def test_a_fault_in_a_served_program_comes_out_as_not_correct(readings, fault):
    r = readings
    chosen, want = r["chosen"].copy(), "want_rows"
    if fault == "a_slot_reads_its_neighbour":
        chosen = np.roll(chosen, 1, axis=0)  # row i answers for row i - 1
    elif fault == "a_lost_carry":
        chosen[:, 2:] = chosen[:, 1:2]  # the burst repeats its first token
    elif fault == "another_sampler":
        chosen = (chosen + 1) % 272
    else:
        want = "other_rows"
    served = _served(r, chosen=chosen, want=want)
    verdict = r["correct"].compare(r["got"], r["want"], r["limits"], served)
    assert not verdict["ok"]
    gap = verdict["rows"]["served_token_gap"]["value"]
    assert gap > 10 * _served(r)["served_token_gap"] + 0.5


def test_the_fp8_control_chooses_worse_tokens_than_the_program(readings):
    r = readings
    low = r["fp8_rows"]
    ctl = _served(r, chosen=low[:, 1:].argmax(-1), packed=low[:, 0])
    program = _served(r)
    assert ctl["packed_prefill_rel_rms"] > 3 * program["packed_prefill_rel_rms"]
    assert ctl["also"]["tokens_as_the_reference"] <= (
        program["also"]["tokens_as_the_reference"])


def test_a_row_without_a_limit_is_an_error(readings):
    r = readings
    limits = {k: v for k, v in r["limits"].items() if k != "served_token_gap"}
    with pytest.raises(SystemExit):
        r["correct"].compare(r["got"], r["want"], limits, _served(r))


def test_token_gap_by_hand():
    from lib import correct

    want = np.array([[3.0, 1.0, -1.0, -3.0], [0.0, 4.0, 0.0, -4.0]])
    rms = np.sqrt([5.0, 8.0])
    gaps = correct.token_gap(np.array([0, 3]), want)
    assert gaps[0] == 0.0 and gaps[1] == pytest.approx(8.0 / rms[1])
    assert correct.token_gap(np.array([2, 1]), want)[0] == pytest.approx(4 / rms[0])


def test_the_sample_crosses_a_page_boundary_in_decode(readings):
    lens, steps = readings["smp"]["lens"], readings["smp"]["steps"]
    assert any(n // 16 != (n + steps) // 16 for n in lens)
    assert readings["got"][1].shape == (len(lens), steps, 272)


def test_the_fp8_control_comes_out_as_not_correct(readings):
    r = readings
    verdict = r["correct"].compare(r["fp8"], r["want"], r["limits"])
    assert not verdict["ok"]
    program = r["correct"].compare(r["got"], r["want"], r["limits"])
    for key, row in verdict["rows"].items():
        assert row["value"] > 3 * program["rows"][key]["value"]


def test_the_int8_control_differs_more_than_the_program(readings):
    r = readings
    ctl = r["correct"].compare(r["int8"], r["want"], r["limits"])
    program = r["correct"].compare(r["got"], r["want"], r["limits"])
    for key, row in ctl["rows"].items():
        assert row["value"] > program["rows"][key]["value"]


def test_weights_of_another_seed_come_out_as_not_correct(readings):
    r = readings
    verdict = r["correct"].compare(r["got"], r["other"], r["limits"])
    assert not verdict["ok"]
    assert verdict["rows"]["prefill_rel_rms"]["value"] > 0.5


def test_non_finite_logits_are_not_correct(readings):
    r = readings
    bad = (np.full_like(r["got"][0], np.nan), r["got"][1])
    assert not r["correct"].compare(bad, r["want"], r["limits"])["ok"]
