"""Serving-ladder variance protocol (bench.py) + engine re-admission
latency machinery (engine/core.py eager re-admission, profile phase
attribution — benchmarks/profile_engine.py).

The round-6 serving work stands on two legs: measurements that carry
their own repeat/median/spread evidence (so a frac_of_raw_decode swing
can be told apart from run-to-run noise), and a scheduler that re-fills a
freed slot in the same step cycle instead of a full admission pass
later. These tests pin both on CPU."""

import asyncio

import pytest

import bench
from dynamo_tpu.engine.config import EngineConfig, ModelSpec

pytestmark = pytest.mark.integration

TINY = ModelSpec(
    name="tiny-test",
    vocab_size=272,
    hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)


def test_aggregate_rung_median_spread_and_tails():
    """Per-rung aggregation: MEDIAN headline, (max-min)/median spread,
    latency-percentile medians, tail ratios vs the recorded bars."""
    reps = [
        {"concurrency": 32, "output_tok_per_s": 90.0,
         "ttft_ms_p50": 100.0, "ttft_ms_p99": 150.0,
         "itl_ms_p50": 10.0, "itl_ms_p99": 20.0},
        {"concurrency": 32, "output_tok_per_s": 110.0,
         "ttft_ms_p50": 120.0, "ttft_ms_p99": 260.0,
         "itl_ms_p50": 12.0, "itl_ms_p99": 14.0},
        {"concurrency": 32, "output_tok_per_s": 100.0,
         "ttft_ms_p50": 110.0, "ttft_ms_p99": 200.0,
         "itl_ms_p50": 11.0, "itl_ms_p99": 15.0},
    ]
    agg = bench.aggregate_rung(reps)
    assert agg["repeats"] == 3
    assert agg["output_tok_per_s"] == 100.0  # median, not best/last
    assert agg["spread_frac"] == round((110.0 - 90.0) / 100.0, 4)
    assert agg["rep_values"] == [90.0, 100.0, 110.0]
    assert agg["ttft_ms_p50"] == 110.0 and agg["ttft_ms_p99"] == 200.0
    # tail ratios computed from the medians, checked against the bars
    assert agg["ttft_p99_over_p50"] == round(200.0 / 110.0, 2)
    assert agg["ttft_tail_ok"] is True  # 1.82 <= 2.0
    assert agg["itl_p99_over_p50"] == round(15.0 / 11.0, 2)
    assert agg["itl_tail_ok"] is True  # 1.36 <= 1.5
    # a violated bar is flagged, not hidden
    bad = bench.aggregate_rung([
        {**reps[0], "itl_ms_p99": 40.0}, {**reps[1], "itl_ms_p99": 40.0},
        {**reps[2], "itl_ms_p99": 40.0},
    ])
    assert bad["itl_tail_ok"] is False


def test_frac_of_raw_prefers_matched_rung_and_uses_medians():
    serving = {"rungs": [
        {"concurrency": 8, "output_tok_per_s": 50.0},
        {"concurrency": 64, "output_tok_per_s": 80.0},
    ]}
    frac, c = bench.frac_of_raw(serving, raw_value=200.0, batch=64)
    assert (frac, c) == (0.4, 64)  # matched rung's MEDIAN / raw median
    frac, c = bench.frac_of_raw(serving, raw_value=200.0, batch=16)
    assert (frac, c) == (0.4, 64)  # no match: top rung fallback


def test_cpu_smoke_ladder_carries_variance_protocol():
    """The real ladder path (engine + closed-loop streams) on a tiny CPU
    model: every rung entry must carry the repeat protocol fields and
    the ladder must carry the tuning + bars it was judged against."""
    # the cold>warm TTFT assertion below measures compile cost: the
    # test process runs with the persistent compile cache off
    # (tests/conftest.py), so 'cold' never replays compiles from disk
    ladder = bench.serving_measurement(
        TINY, page_size=16, on_tpu=False, family="gqa",
        rungs_override=[2], window_override=1.0, repeats=2,
    )
    assert ladder["repeats"] == 2
    assert ladder["family"] == "gqa"
    for key in ("burst", "prefill_budget", "bars"):
        assert key in ladder
    assert ladder["bars"]["frac_of_raw_decode"] == 0.60
    assert ladder["bars"]["ttft_p99_over_p50_max"] == 2.0
    assert ladder["bars"]["itl_p99_over_p50_max"] == 1.5
    (rung,) = ladder["rungs"]
    assert rung["repeats"] == 2
    assert isinstance(rung["spread_frac"], float)
    assert len(rung["rep_values"]) == 2
    # roofline attribution schema (ROADMAP #2): per-rung analytic
    # bytes_per_step + the achieved-bandwidth estimate, and the pool
    # pricing inputs at the ladder level
    assert ladder["kv_dtype"] == "bf16"
    assert ladder["kv_bytes_per_token"] > 0
    assert rung["bytes_per_step"] > 0
    assert isinstance(rung["est_hbm_gbps"], float)
    # the estimate is self-consistent with the rung median
    assert rung["est_hbm_gbps"] == round(
        rung["bytes_per_step"] * rung["output_tok_per_s"]
        / rung["concurrency"] / 1e9, 3,
    )
    # the headline IS the median of the repeated windows
    vals = sorted(rung["rep_values"])
    assert rung["output_tok_per_s"] == vals[len(vals) // 2]
    # frac derivation consumes the rung median
    frac, c = bench.frac_of_raw(ladder, raw_value=1000.0, batch=2)
    assert c == 2
    assert frac == round(rung["output_tok_per_s"] / 1000.0, 3)
    # compile-and-dispatch artifact schema (BENCH_r06 evidence): the
    # cold/warm first-request TTFT delta and the dispatch overhead
    # fraction must ride in every serving section
    assert isinstance(ladder["cold_ttft_ms"], float)
    assert isinstance(ladder["warm_ttft_ms"], float)
    # cold pays the compiles the warm request doesn't (on CPU the gap
    # is compile-dominated and decisive)
    assert ladder["cold_ttft_ms"] > ladder["warm_ttft_ms"]
    assert isinstance(ladder["dispatch_overhead_frac"], float)
    # no upper bound on CPU: a smoke window short enough to still be
    # compiling legitimately exceeds 1.0 (the number is an on-chip
    # metric; the exact-math contract is test_dispatch_overhead_fraction_math)
    assert ladder["dispatch_overhead_frac"] >= 0.0
    disp = ladder["dispatch"]
    assert disp["dispatches"] > 0
    assert disp["compile_events"] >= 0
    for key in ("dispatches_per_step", "d2h_wait_s", "issue_s"):
        assert key in disp


def test_fp8_ladder_bytes_per_step_reduction(monkeypatch):
    """The ROADMAP #2 byte claim, measured analytically from the pool
    dtypes the REAL engine allocated (CPU): at the same rung, the fp8
    ladder's bytes_per_step must show >= 1.8x reduction vs bf16 — the
    attributable half of the >=1.6x on-chip tok/s bar (deferred to
    BENCH_r06). Rung 8 is the serving-representative point where KV
    traffic dominates the param read (at tiny batches the fixed param
    bytes mask the pool halving for this toy model)."""
    ladders = {}
    for kv_dtype in ("bf16", "fp8"):
        monkeypatch.setenv("DYN_KV_DTYPE", kv_dtype)
        ladders[kv_dtype] = bench.serving_measurement(
            TINY, page_size=16, on_tpu=False, family="gqa",
            rungs_override=[8], window_override=1.0, repeats=1,
        )
    monkeypatch.delenv("DYN_KV_DTYPE", raising=False)
    assert ladders["fp8"]["kv_dtype"] == "fp8"
    # pool pricing: fp8 values + bf16 scales vs the full-width pool
    assert (
        ladders["bf16"]["kv_bytes_per_token"]
        >= 1.8 * ladders["fp8"]["kv_bytes_per_token"]
    )
    (r_bf16,) = ladders["bf16"]["rungs"]
    (r_fp8,) = ladders["fp8"]["rungs"]
    assert r_bf16["concurrency"] == r_fp8["concurrency"] == 8
    ratio = r_bf16["bytes_per_step"] / r_fp8["bytes_per_step"]
    assert ratio >= 1.8, (
        f"fp8 bytes_per_step reduction {ratio:.2f}x < 1.8x "
        f"({r_bf16['bytes_per_step']} vs {r_fp8['bytes_per_step']})"
    )
    # both ladders actually served tokens through the real engine
    for lad in ladders.values():
        assert lad["rungs"][0]["output_tok_per_s"] > 0


def test_spec_decode_artifact_schema():
    """The speculative-decoding bench section (bench.spec_decode_
    measurement): per-rung acceptance_rate + per_stream_toks_s for BOTH
    modes at low concurrency, the accepted-tokens-per-dispatch proxy,
    and the recorded bar — the artifact fields the >=1.5x low-
    concurrency claim is judged on."""
    out = bench.spec_decode_measurement(
        TINY, page_size=16, on_tpu=False, family="gqa",
        concurrencies=(1, 2), osl=32, reqs_per_stream=1,
    )
    assert out["family"] == "gqa"
    assert out["mode"] == "prompt-lookup spec decode"
    assert out["k_max"] >= 1
    assert out["bars"]["accepted_tokens_per_dispatch_min"] == 1.5
    assert out["bars"]["incompressible_dispatch_overhead_max"] == 0.05
    ctl = out["incompressible_control"]
    for key in ("dispatches", "dispatches_nospec",
                "dispatch_overhead_frac", "per_stream_toks_s",
                "per_stream_toks_s_nospec"):
        assert key in ctl, key
    # the decay claim itself: spec on an incompressible prompt costs
    # (almost) no extra dispatches — dispatch counts are CPU-exact
    assert ctl["dispatch_overhead_frac"] <= 0.05, ctl
    assert [r["concurrency"] for r in out["rungs"]] == [1, 2]
    for rung in out["rungs"]:
        for key in (
            "per_stream_toks_s", "per_stream_toks_s_nospec", "speedup",
            "acceptance_rate", "accepted_tokens_per_dispatch",
            "verifies", "dispatches", "dispatches_nospec",
        ):
            assert key in rung, key
        assert rung["per_stream_toks_s"] > 0
        assert rung["per_stream_toks_s_nospec"] > 0
    # headline convenience fields mirror rung 1 (concurrency 1)
    assert out["per_stream_toks_s"] == out["rungs"][0]["per_stream_toks_s"]
    assert out["acceptance_rate"] == out["rungs"][0]["acceptance_rate"]


def test_guided_rung_artifact_schema_and_overhead_bar():
    """The guided-decoding bench rung (bench.guided_measurement):
    constrained vs free ITL from ONE mixed run (paired medians over
    shared engine cycles), the grammar-compiler micro-bench, and the
    recorded <5% masking-overhead bar — met on the CPU rung."""
    out = bench.guided_measurement(
        TINY, page_size=16, on_tpu=False, family="gqa",
        concurrency=2, osl=24,
    )
    assert out["mode"] == "guided mixed-concurrency ITL"
    for key in ("guided_itl_ms", "free_itl_ms", "free_itl_ms_baseline",
                "guided_tokens", "free_tokens", "grammar_kind",
                "masking_overhead_frac", "grammar_compiler", "bars"):
        assert key in out, key
    assert out["bars"]["masking_itl_overhead_max"] == 0.05
    comp = out["grammar_compiler"]
    for key in ("compiles", "hits", "hit_rate", "compile_ms_total"):
        assert key in comp, key
    # mask-compile cost is attributable: the rung compiled (or shared)
    # at least one grammar and the request path hit the cache
    assert comp["compiles"] + comp["hits"] > 0
    # the acceptance bar, judged on the CPU rung: paired medians over
    # the SAME dispatches keep this stable
    assert out["masking_overhead_frac"] is not None
    assert out["masking_overhead_frac"] <= 0.05, out


def test_family_serving_tuning_table():
    """Each north-star family has its own ladder tuning, and the bars
    artifact records the per-family frac targets."""
    for fam in ("gqa", "mla", "gptoss"):
        assert {"burst", "budget_frac"} <= set(
            bench.FAMILY_SERVING[fam]
        )
        assert fam in bench.SERVING_BARS["frac_of_raw_decode"]
    assert bench.SERVING_BARS["frac_of_raw_decode"]["mla"] == 0.45
    assert bench.SERVING_BARS["frac_of_raw_decode"]["gptoss"] == 0.45


def test_bench_refuses_to_measure_without_a_chip(monkeypatch):
    """No silent CPU fallback under the device metrics' names: main()
    exits non-zero unless --cpu asked for the toy smoke, an unknown TPU
    kind is an error instead of a null roofline, and the CPU smoke's
    output carries no roofline field."""
    from types import SimpleNamespace as Dev

    monkeypatch.setattr("sys.argv", ["bench.py"])
    with pytest.raises(SystemExit, match="no TPU"):
        bench.main()
    v5e = bench.hbm_roofline(
        Dev(platform="tpu", device_kind="TPU v5 lite"), 409.5
    )
    assert v5e == {"achieved_hbm_gbps": 409.5, "hbm_roofline_frac": 0.5}
    with pytest.raises(SystemExit, match="TPU v99"):
        bench.hbm_roofline(Dev(platform="tpu", device_kind="TPU v99"), 1.0)
    assert bench.hbm_roofline(Dev(platform="cpu", device_kind="cpu"), 1.0) == {}


async def test_eager_readmission_fills_slot_in_same_cycle():
    """A finished slot's replacement must start its prefill in the SAME
    step cycle that processed the finishing burst, not wait for the next
    admission pass (the r5 ~700 ms re-admission gap). With one slot, B
    can only enter through the eager path the moment A's burst finishes
    — the engine counts those passes."""
    from dynamo_tpu.engine.core import InferenceEngine
    from dynamo_tpu.runtime.context import Context

    cfg = EngineConfig(
        page_size=4, num_pages=64, max_pages_per_seq=16,
        max_decode_slots=1, prefill_buckets=(16, 32),
        decode_steps_per_dispatch=2, pipeline_decode=True,
    )
    engine = InferenceEngine(TINY, cfg)
    await engine.start()

    async def collect(prompt, n):
        out = []
        async for item in engine.generate(
            {"token_ids": prompt,
             "stop_conditions": {"max_tokens": n, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context(),
        ):
            out.extend(item["token_ids"])
        return out

    outs = await asyncio.gather(
        collect([7, 11, 19], 6), collect([5, 13, 23], 6),
    )
    assert len(outs[0]) == 6 and len(outs[1]) == 6
    assert engine.eager_readmits >= 1
    assert engine.allocator.active_pages == 0
    await engine.close()

    # the knob is honored: with eager re-admission off, the same
    # workload admits only through the normal step phase
    cfg_off = EngineConfig(
        page_size=4, num_pages=64, max_pages_per_seq=16,
        max_decode_slots=1, prefill_buckets=(16, 32),
        decode_steps_per_dispatch=2, pipeline_decode=True,
        eager_readmit=False,
    )
    engine2 = InferenceEngine(TINY, cfg_off)
    await engine2.start()

    async def collect2(prompt, n):
        out = []
        async for item in engine2.generate(
            {"token_ids": prompt,
             "stop_conditions": {"max_tokens": n, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context(),
        ):
            out.extend(item["token_ids"])
        return out

    outs2 = await asyncio.gather(
        collect2([7, 11, 19], 6), collect2([5, 13, 23], 6),
    )
    assert [len(o) for o in outs2] == [6, 6]
    assert engine2.eager_readmits == 0
    await engine2.close()
    # same greedy tokens either way: eager admission is a latency
    # optimization, not a semantic change
    assert outs2 == outs


async def test_readmission_gap_attribution_phases():
    """EngineConfig.profile breaks the finish->first-token path into
    the named phases profile_engine.py reports: admit_wait (queue time),
    prefill_dispatch (prompt forward + fused sample), first_token
    (residual sample/d2h materialization)."""
    from benchmarks.profile_engine import readmission_attribution
    from dynamo_tpu.engine.core import InferenceEngine
    from dynamo_tpu.runtime.context import Context

    cfg = EngineConfig(
        page_size=4, num_pages=64, max_pages_per_seq=16,
        max_decode_slots=2, prefill_buckets=(16, 32),
        decode_steps_per_dispatch=2, pipeline_decode=True, profile=True,
    )
    engine = InferenceEngine(TINY, cfg)
    await engine.start()

    async def one(i):
        async for _ in engine.generate(
            {"token_ids": [3 + i, 5, 9],
             "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context(f"prof-{i}"),
        ):
            pass

    await asyncio.gather(*(one(i) for i in range(4)))
    snap = engine.profile_snapshot()
    await engine.close()
    for phase in (
        "readmit.admit_wait", "readmit.prefill_dispatch",
        "readmit.first_token",
    ):
        assert snap.get(phase, {}).get("calls", 0) > 0, phase
    attr = readmission_attribution(snap)
    for key in ("admit_wait", "prefill_dispatch", "first_token"):
        assert attr[key]["events"] > 0
        assert attr[key]["mean_ms"] is not None
    assert attr["engine_gap_ms"] > 0
