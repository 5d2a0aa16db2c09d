"""The benchmark's own arithmetic: percentiles and latency definitions on
hand-made streams, the traffic generator's promises, the byte functions
against hand sums, the trace reduction's pieces. No engine, no device."""

import json
import os
import sys
from collections import Counter

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from lib import costs, stats, trace, traffic  # noqa: E402


def _cfg(name):
    with open(os.path.join(REPO, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    with open(os.path.join(REPO, "perfbench", "traffic", name + ".json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- stats


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 0.5, 3.0),
    ([1, 2, 3, 4], 0.5, 2.5),
    ([10], 0.95, 10.0),
    ([0, 10], 0.95, 9.5),
    (list(range(101)), 0.95, 95.0),
    ([], 0.5, None),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == want


def _stream(due, sent, chunks, tokens, ok=True, windowed=True):
    return {"due": due, "sent": sent, "chunks": chunks, "ok": ok,
            "completion_tokens": tokens, "windowed": windowed}


def test_ttft_is_timed_from_the_due_instant_not_the_send():
    rec = _stream(due=1.0, sent=1.4, chunks=[1.9, 2.0], tokens=9)
    assert stats.ttft_s(rec) == pytest.approx(0.9)
    assert stats.late_s(rec) == pytest.approx(0.4)


def test_tpot_counts_tokens_not_chunks():
    # 17 tokens in three chunks (1 + 8 + 8): a burst delivers several
    rec = _stream(0.0, 0.0, [1.0, 1.2, 1.4], tokens=17)
    assert stats.tpot_s(rec) == pytest.approx(0.4 / 16)
    assert stats.gaps_s(rec) == pytest.approx([0.2, 0.2])


@pytest.mark.parametrize("rec", [
    _stream(0.0, 0.0, [1.0], tokens=1),  # one token: no gap to divide
    _stream(0.0, 0.0, [1.0, 2.0], tokens=9, ok=False),  # failed
])
def test_tpot_of_a_stream_without_two_tokens_or_failed_is_none(rec):
    assert stats.tpot_s(rec) is None
    assert stats.ttft_s(_stream(0, 0, [], 0, ok=False)) is None


def test_failed_request_misses_every_latency():
    good = _stream(0.0, 0.0, [0.1, 0.2], tokens=9)
    bad = _stream(0.0, 0.0, [0.5], tokens=None, ok=False)
    pooled = stats.pooled([good, bad], stats.ttft_s)
    assert pooled == [pytest.approx(0.1)]
    assert stats.pooled([good, bad], stats.gaps_s) == [pytest.approx(0.1)]


def test_tokens_in_window_credits_chunks_evenly_and_only_inside():
    inside = _stream(0, 0, [1.0, 2.0, 3.0], tokens=30)
    straddles = _stream(0, 0, [9.0, 11.0], tokens=10)  # half inside [0, 10)
    before = _stream(0, 0, [-2.0, -1.0], tokens=8)
    failed = _stream(0, 0, [1.0], tokens=5, ok=False)
    got = stats.tokens_in_window([inside, straddles, before, failed], 10.0)
    assert got == pytest.approx(30 + 5)


def test_windowed_filter():
    recs = [_stream(0, 0, [1], 2), _stream(0, 0, [1], 2, windowed=False)]
    assert len(stats.windowed(recs)) == 1


# -------------------------------------------------------------- traffic


@pytest.mark.parametrize("mix,slots", [("chat", 0), ("batch", 32)])
def test_every_seed_offers_the_same_work_in_another_order(mix, slots):
    t = _mix(mix)
    plans = [
        traffic.make_plan(t, seed, 30.0, decode_slots=slots)
        for seed in (0, 1, 7, 2**31 + 11)
    ]
    offered = [traffic.offered(p) for p in plans]
    assert all(o == offered[0] for o in offered)

    def multiset(p, key):
        return Counter(
            r[key] for r in p["requests"]
            if p["loop"] == "closed" or r["windowed"]
        )

    for key in ("prompt_tokens", "max_tokens"):
        assert all(multiset(p, key) == multiset(plans[0], key) for p in plans)
    orders = {
        tuple(r["prompt_tokens"] for r in p["requests"]) for p in plans
    }
    assert len(orders) == len(plans)


def test_open_loop_holds_exactly_rate_times_seconds_arrivals_in_the_window():
    t = dict(_mix("chat"), rate_rps=7.5)
    p = traffic.make_plan(t, 3, 20.0)
    win = [r for r in p["requests"] if r["windowed"]]
    assert len(win) == 150
    assert all(0.0 <= r["due"] < 20.0 for r in win)
    lead = [r for r in p["requests"] if r["due"] < 0]
    tail = [r for r in p["requests"] if r["due"] >= 20.0]
    assert len(lead) == round(7.5 * t["lead_in_s"])
    assert len(tail) == round(7.5 * t["tail_s"])
    assert not any(r["windowed"] for r in lead + tail)
    dues = [r["due"] for r in win]
    assert dues == sorted(dues)


def test_lengths_keep_to_the_mix_and_to_the_table():
    for name in ("chat", "batch"):
        t = _mix(name)
        p = traffic.make_plan(t, 5, 30.0, decode_slots=32)
        for r in p["requests"]:
            assert t["prompt_tokens"]["min"] <= r["prompt_tokens"] <= t["prompt_tokens"]["max"]
            assert r["prompt_tokens"] + r["max_tokens"] <= t["max_total_tokens"]
            assert r["max_tokens"] >= 1


def test_closed_loop_clients_follow_the_slots():
    p = traffic.make_plan(_mix("batch"), 1, 10.0, decode_slots=32)
    assert p["clients"] == 64 and p["loop"] == "closed"
    with pytest.raises(ValueError):
        traffic.make_plan(_mix("batch"), 1, 10.0, decode_slots=0)


def test_quantile_lengths_have_the_distribution_s_median():
    xs = traffic.quantile_lengths(_mix("chat")["prompt_tokens"], 401)
    assert sorted(xs)[200] == 200
    assert traffic.quantile_lengths({"dist": "fixed", "value": 9}, 3) == [9, 9, 9]
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "zipf", "min": 1, "max": 2}, 3)


def test_bursts_put_arrivals_at_one_instant():
    t = dict(_mix("chat"), rate_rps=8.0, burst={"size": 4})
    p = traffic.make_plan(t, 2, 10.0)
    dues = [r["due"] for r in p["requests"] if r["windowed"]]
    assert len(dues) == 80 and len(set(dues)) == 20


def test_content_has_one_byte_a_token_and_shares_only_what_is_asked():
    req = {"prompt_tokens": 100, "content_seed": 5, "prefix_tokens": 0,
           "prefix_group": 0}
    a = traffic.content_for(req, 29)
    b = traffic.content_for(dict(req, content_seed=6), 29)
    assert len(a) == len(b) == 71 and a[0] == "#" and a != b
    s1 = traffic.content_for(dict(req, prefix_tokens=40), 29)
    s2 = traffic.content_for(dict(req, prefix_tokens=40, content_seed=9), 29)
    assert s1[:40] == s2[:40] and s1[40] == "#" and s1[41:] != s2[41:]
    assert len(s1) == 71


# ---------------------------------------------------------------- costs


def test_byte_functions_against_hand_sums_mistral_7b():
    c = _cfg("mistral-7b-v0.3")
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336 + 2 * 4096
    assert costs.layer_params(c) == layer == 218_112_000
    total = (18 * layer + 2 * 32768 * 4096 + 4096) * 2
    assert costs.weight_bytes(c) == total
    assert costs.kv_bytes_per_token_layer(c) == 4096  # 4 KiB a token a layer
    # one step, 10 slots holding 3,000 live tokens
    want = (18 * layer + 4096 + 32768 * 4096) * 2 + 10 * 4096 * 2 \
        + 18 * 4096 * (3000 + 10)
    assert costs.decode_step_bytes(c, 3000, 10) == want
    assert costs.decode_attention_bytes_per_call(c, 3000, 10) == (
        4096 * 3010 + 2 * 10 * 32 * 128 * 2
    )


def test_byte_functions_against_hand_sums_mistral_nemo():
    c = _cfg("mistral-nemo-12b")
    # 32 heads x 128 = 4096 is not the hidden size 5120
    layer = 5120 * 4096 * 2 + 5120 * 1024 * 2 + 3 * 5120 * 14336 + 2 * 5120
    assert costs.layer_params(c) == layer == 272_640_000
    assert costs.embedding_params(c) + costs.head_params(c) == 2 * 131072 * 5120
    assert costs.weight_bytes(c) == (12 * layer + 2 * 131072 * 5120 + 5120) * 2
    assert costs.kv_bytes_per_token_layer(c) == 4096
    # the head over 131k entries is a sixth of what a step reads
    step = costs.decode_step_bytes(c, 0, 0)
    assert step == (12 * layer + 5120 + 131072 * 5120) * 2
    assert 0.16 < 131072 * 5120 * 2 / step < 0.18


def test_an_unknown_device_is_an_error_not_a_default():
    assert costs.peaks_for("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(SystemExit):
        costs.peaks_for("cpu")


# ---------------------------------------------------------------- trace


class _E:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = []


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


PROGRAMS = {"decode": ["decode_steps"], "prefill": ["prefill_forward"]}


@pytest.mark.parametrize("raw,want", [
    ("%fusion.w_down.12 = bf16[8]", "fusion.w_down"),
    ("fused_decode_attention.3", "fused_decode_attention"),
    ("jit_decode_steps_impl(123456)", "jit_decode_steps_impl"),
    ("slice-done.7.1", "slice-done"),
    ("copy", "copy"),
])
def test_normalise_drops_what_the_compiler_appends(raw, want):
    assert trace.normalise(raw) == want


def test_classify_by_program_name():
    assert trace.classify("jit_decode_steps_impl(1)", PROGRAMS) == "decode"
    assert trace.classify("jit_prefill_forward_batch_impl", PROGRAMS) == "prefill"
    assert trace.classify("jit__chain_feed", PROGRAMS) == "other"


def test_reduce_plane_busy_is_a_union_and_gaps_are_named_by_their_programs():
    ops = _L(trace.OPS_LINE, [
        _E("fused_decode_attention.1", 0, 100),
        _E("fusion.1", 50, 100),  # overlaps: union 0..150
        _E("fused_decode_attention.2", 200, 100),  # gap 150..200 inside decode
        _E("fusion.9", 500, 100),  # gap 300..500 between decode and prefill
    ])
    mods = _L(trace.MODULES_LINE, [
        _E("jit_decode_steps_impl(1)", 0, 300),
        _E("jit_prefill_forward_impl(2)", 500, 100),
    ])
    r = trace.reduce_plane(_P("/device:TPU:0", [ops, mods]), PROGRAMS, None)
    assert r["busy_s"] == pytest.approx(350e-9)
    assert r["window_s"] == pytest.approx(600e-9)
    assert r["gaps"]["inside_a_decode_program"] == pytest.approx(50e-9)
    assert r["gaps"]["between_decode_and_prefill_programs"] == pytest.approx(200e-9)
    assert r["by_kind"]["decode"]["runs"] == 1
    assert r["by_kind"]["decode"]["ops"]["fused_decode_attention"][1] == 2
    assert r["by_kind"]["prefill"]["secs"] == pytest.approx(100e-9)
    top = trace.breakdown({"by_op": r["by_op"], "gaps": r["gaps"]})
    assert top["device_ops"][0][0] in ("fusion", "fused_decode_attention")
    assert len(top["idle_gaps"]) == 2


def test_a_plane_without_device_operations_reduces_to_nothing():
    assert trace.reduce_plane(_P("/device:TPU:0", []), PROGRAMS, None) is None
    host = _P("/host:CPU", [_L("python3", [_E("x", 0, 5)])])

    class _Prof:
        planes = [host]

    assert trace.device_planes(_Prof()) == []


# a slice of a trace recorded on the v5e chip (PR 24, mistral7b.chat: one
# 8-step decode burst of 32 slots over 18 layers, then one single prefill)
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_chat_slice.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce_file(RECORDED, PROGRAMS)


def test_recorded_trace_programs_and_kernel_calls(recorded):
    kinds = recorded["by_kind"]
    assert kinds["decode"]["runs"] == 1 and kinds["prefill"]["runs"] == 1
    # the fused kernel runs once a layer a step: 8 steps x 18 layers
    assert kinds["decode"]["ops"]["fused_decode_attention"][1] == 8 * 18
    assert kinds["decode"]["secs"] == pytest.approx(0.119215, rel=1e-3)
    assert kinds["prefill"]["secs"] == pytest.approx(0.019492, rel=1e-3)
    assert "fused_decode_attention" not in kinds["prefill"]["ops"]


def test_recorded_trace_busy_never_exceeds_its_window(recorded):
    assert 0 < recorded["busy_s"] <= recorded["window_s"]
    assert recorded["window_s"] == pytest.approx(0.1387214, rel=1e-4)
    assert recorded["busy_s"] == pytest.approx(0.1387074, rel=1e-4)
    # the while loop that holds a burst's steps counts only its own time
    assert recorded["by_op"]["while"][0] < 1e-3
    total = sum(rec[0] for rec in recorded["by_op"].values())
    assert total == pytest.approx(recorded["busy_s"], rel=0.02)


def test_recorded_trace_breakdown_names_what_took_the_time(recorded):
    top = trace.breakdown(recorded)
    names = [n for n, _ in top["device_ops"]]
    assert names[:3] == ["fusion_14336x4096", "fused_decode_attention",
                         "fusion_4096x14336"]
    assert len(top["device_ops"]) == 10 and len(top["idle_gaps"]) <= 10
    assert all(s >= 0 for _, s in top["idle_gaps"])


def test_self_times_take_children_out_of_their_parent():
    ops = [("while", 0.0, 100.0), ("a", 10.0, 40.0), ("b", 40.0, 90.0),
           ("c", 120.0, 130.0)]
    got = {n: own for n, _, _, own in trace.self_times(ops)}
    assert got == {"while": 20.0, "a": 30.0, "b": 50.0, "c": 10.0}


def test_an_unnamed_fusion_is_told_apart_by_its_largest_operand():
    line = ("%fusion.1727 = (f32[32]{0}, bf16[32,4096]{1,0}) fusion("
            "bf16[32,4096]{1,0} %g.1, bf16[14336,4096]{1,0:T(8,128)} %g.2), "
            "kind=kOutput, calls=%fused_computation.464")
    assert trace.normalise(line) == "fusion_14336x4096"


def test_a_closed_loop_pool_taken_again_has_new_content():
    from lib import loadgen

    pool = [{"id": "c0", "content_seed": 5, "prompt_tokens": 40,
             "prefix_tokens": 0, "prefix_group": 0},
            {"id": "c1", "content_seed": 6, "prompt_tokens": 40,
             "prefix_tokens": 0, "prefix_group": 0}]
    src = loadgen.cycling(pool)
    got = [next(src) for _ in range(5)]
    assert [r["id"] for r in got] == ["c0", "c1", "c0.1", "c1.1", "c0.2"]
    assert got[0] is pool[0]
    texts = {traffic.content_for(r, 10) for r in got}
    assert len(texts) == 5 and all(len(t) == 30 for t in texts)


# ------------------------------------------------------ device readers


def _device_readers():
    from lib import spec

    return spec.load_readers([os.path.join(REPO, "perfbench", "readers")])


def test_prefill_tokens_are_those_dispatched_during_the_traced_part():
    read = _device_readers()["device:prefill_tok_s"]
    run = {
        "t0": 100.0, "traced": (1.0, 4.0, 9.0),
        "trace": {"by_kind": {"prefill": {"secs": 0.5, "ops": {}, "runs": 3}}},
        # (instant, real tokens a row): before, a single, a pack with two
        # empty rows, the last instant inside, after
        "prefills": [(100.5, [70]), (101.5, [10]), (102.0, [20, 30, 0, 0]),
                     (103.9, [5]), (104.0, [99])],
    }
    assert read(run, None) == (10 + 50 + 5) / 0.5
    assert read(dict(run, prefills=[]), None) is None
    assert read(dict(run, trace=None), None) is None


def test_the_memory_share_is_of_the_peak_when_the_window_closed():
    read = _device_readers()["device:peak_mem_share"]
    assert read({"memory_peak_bytes": 0}, None) is None  # as on the CPU
