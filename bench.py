#!/usr/bin/env python
"""Decode-throughput benchmark. Prints ONE JSON line:

  {"metric": "decode_tokens_per_sec_per_chip", "value": N, "unit": "tok/s",
   "vs_baseline": R, ...roofline fields...}

Measures batched paged-decode steps with on-device sampling (the serving
hot loop) on a TPU chip — a ~1B-param llama-family model. Without a chip
it exits non-zero: a CPU run says nothing about the device. ``--cpu``
asks for the tiny-model CPU smoke of the code path on purpose; its output
carries no roofline field.
Decode runs through ``llama.decode_steps``: fused forward + sampling,
multiple steps per dispatch (the engine's multi-step decode mode), which is
what a TPU serving loop does to amortize host dispatch.

Roofline fields make the absolute quality of the number visible (the
reference publishes no absolute tok/s — BASELINE.md): bytes touched per
step (weights + KV read/write), achieved HBM GB/s, and the fraction of the
chip's peak HBM bandwidth. ``vs_baseline`` is the ratio against the newest
recorded ``BENCH_r*.json`` at the repo root, 1.0 when none exists.

The ``serving`` section is a sustained closed-loop concurrency LADDER
through the real engine (the aiperf-equivalent measurement the reference
uses — benchmarks/llm/perf.sh): per rung, N streams each keep one request
open; only tokens inside a steady-state window count; TTFT/ITL p50/p99 and
output tok/s per rung, plus the best rung's fraction of the matched-batch
raw-decode ceiling.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import time

import numpy as np

import jax

if "--cpu" in sys.argv:
    # the CPU smoke path, asked for by name (see main)
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp

from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.models.family import get_family

STEPS = 64
WARMUP = 8
STEPS_PER_DISPATCH = 8

# peak HBM bandwidth by device kind (GB/s)
PEAK_HBM = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5": 2765.0,  # v5p
    "TPU v6 lite": 1640.0,  # v6e / Trillium
}

# per-family serving-ladder tuning. Burst length is sized so device
# compute covers the host sync round-trip at that family's measured step
# time (gqa ~8 ms -> 24 swept best on v5e; mla's latent cache steps
# faster -> longer bursts amortize more; gptoss MoE steps slower ->
# shorter bursts keep admission latency bounded). budget_frac scales the
# per-step prefill admission budget relative to the ISL*SLOTS workload
# (gptoss gets more headroom: expert dispatch makes its prefill
# relatively more expensive, so starving re-admissions costs more).
# Starting points pending on-chip sweeps; env knobs override:
# DYNAMO_BENCH_BURST[_<FAM>], DYNAMO_BENCH_PREFILL_BUDGET[_<FAM>].
FAMILY_SERVING = {
    "gqa": {"burst": 24, "budget_frac": 0.5},
    "mla": {"burst": 32, "budget_frac": 0.5},
    "gptoss": {"burst": 16, "budget_frac": 0.75},
}

# on-chip acceptance bars, recorded in the artifact so every BENCH_r*
# json carries the criteria it was judged against (VERDICT r5 next #1/#2)
SERVING_BARS = {
    "frac_of_raw_decode": {"gqa": 0.60, "mla": 0.45, "gptoss": 0.45},
    "ttft_p99_over_p50_max": 2.0,
    "itl_p99_over_p50_max": 1.5,
}


def _fam_env(name: str, family: str, default):
    """Per-family env override (DYNAMO_BENCH_<NAME>_<FAM>), falling back
    to the global knob (DYNAMO_BENCH_<NAME>) then the tuning default."""
    v = os.environ.get(f"DYNAMO_BENCH_{name}_{family.upper()}")
    if v is None:
        v = os.environ.get(f"DYNAMO_BENCH_{name}")
    return type(default)(v) if v is not None else default


def family_spec(family: str, on_tpu: bool) -> ModelSpec:
    """~1B-scale spec per flagship model family (BASELINE.md north
    stars): 'gqa' (llama-shaped), 'mla' (deepseek-shaped latent
    attention), 'gptoss' (D=64 + sinks + sliding windows + biases +
    clamped swiglu + YaRN + MoE — exercises the lane-padded pool)."""
    if not on_tpu:
        return ModelSpec.dryrun()
    if family == "mla":
        return ModelSpec(
            name="mla-bench", vocab_size=32768, hidden_size=2048,
            intermediate_size=8192, num_layers=16, num_heads=16,
            num_kv_heads=16, head_dim=128, tie_embeddings=False,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, q_lora_rank=1536,
            rope_scaling_factor=40.0, rope_orig_max_pos=4096,
            rope_mscale=1.0, rope_mscale_all_dim=1.0, rope_interleave=True,
        )
    if family == "gptoss":
        return ModelSpec(
            name="gptoss-bench", vocab_size=32768, hidden_size=2048,
            intermediate_size=2048, num_layers=16, num_heads=32,
            num_kv_heads=8, head_dim=64, tie_embeddings=False,
            rope_theta=150000.0,
            num_experts=8, num_experts_per_token=2,
            moe_intermediate_size=2048,
            sliding_window=128,
            layer_types=tuple(
                "sliding_attention" if i % 2 == 0 else "full_attention"
                for i in range(16)
            ),
            attn_sinks=True, attn_bias=True, moe_bias=True,
            swiglu_limit=7.0, swiglu_alpha=1.702,
            rope_scaling_factor=32.0, rope_orig_max_pos=4096,
            rope_truncate=False,
        )
    return ModelSpec(
        name="llama-1b-bench", vocab_size=32768, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=16,
        num_kv_heads=8, head_dim=128, tie_embeddings=False,
    )


def bench_spec(on_tpu: bool, family: str = "gqa") -> tuple[ModelSpec, int, int, int]:
    """(spec, batch, page_size, pages_per_seq)."""
    spec = family_spec(family, on_tpu)
    if on_tpu:
        # same workload as BENCH_r01 (B=64, 256-token contexts) so
        # vs_baseline stays apples-to-apples; page=32 measured best on v5e
        # with the v3 deep-pipeline attention kernel (64 halves the DMA
        # count but its 16KB-per-head strided bursts measure slower
        # in-model). Env knobs for exploration.
        B = int(os.environ.get("DYNAMO_BENCH_BATCH", "64"))
        page = int(os.environ.get("DYNAMO_BENCH_PAGE", "32"))
        return spec, B, page, max(1, 256 // page)  # 256-token tables
    return spec, 8, 16, 8


def prior_value() -> float | None:
    best_round, value = -1, None
    for path in glob.glob(os.path.join(os.path.dirname(__file__), "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            data = json.loads(open(path).read())
            # driver files nest the printed JSON under "parsed"
            payload = data.get("parsed", data)
            if payload.get("family", "gqa") != "gqa":
                continue  # vs_baseline is a gqa-to-gqa ratio only
            v = float(payload.get("value"))
        except (ValueError, TypeError, AttributeError, OSError, json.JSONDecodeError):
            continue
        if int(m.group(1)) > best_round and v > 0:
            best_round, value = int(m.group(1)), v
    return value


def _median(xs: list) -> float | None:
    """Median of the non-None values (None when nothing measured)."""
    vals = sorted(x for x in xs if x is not None)
    return vals[len(vals) // 2] if vals else None


def aggregate_rung(reps: list[dict]) -> dict:
    """Collapse one rung's repeated windows into the artifact entry:
    MEDIAN output tok/s is the headline, spread_frac = (max-min)/median
    makes run-to-run noise visible (the serving extension of raw_decode's
    repeat protocol — VERDICT r5: without it, a 0.488->0.358 swing can't
    be told apart from one noisy window). Latency percentiles take the
    median across repeats; tail ratios are computed from those medians
    and checked against the recorded bars."""
    values = sorted(r["output_tok_per_s"] for r in reps)
    med = values[len(values) // 2]
    out = {
        "concurrency": reps[0]["concurrency"],
        "repeats": len(reps),
        "output_tok_per_s": med,
        "spread_frac": round(
            (values[-1] - values[0]) / max(med, 1e-9), 4
        ),
        "rep_values": [round(v, 1) for v in values],
    }
    for k in ("ttft_ms_p50", "ttft_ms_p99", "itl_ms_p50", "itl_ms_p99"):
        out[k] = _median([r[k] for r in reps])
    for name, p99, p50, bar in (
        ("ttft", out["ttft_ms_p99"], out["ttft_ms_p50"],
         SERVING_BARS["ttft_p99_over_p50_max"]),
        ("itl", out["itl_ms_p99"], out["itl_ms_p50"],
         SERVING_BARS["itl_p99_over_p50_max"]),
    ):
        ratio = round(p99 / p50, 2) if p99 and p50 else None
        out[f"{name}_p99_over_p50"] = ratio
        out[f"{name}_tail_ok"] = (ratio <= bar) if ratio is not None else None
    return out


def decode_step_bytes(
    param_bytes: int, kv_per_token: float, batch: int, mean_ctx: float
) -> int:
    """Analytic HBM bytes ONE decode step moves at ``batch`` live slots
    and ``mean_ctx`` tokens of context each: full param read + per-token
    KV read over the context + the new token's KV write.

    ``kv_per_token`` is priced from the ACTUAL pool arrays
    (``jax.tree.leaves`` over the pools covers both plain arrays and
    ops/quant.py QuantPools, where fp8 values + bf16 scales enter at
    their true widths) — so the fp8-vs-bf16 ladder delta in the artifact
    is attributable to pool dtype, not assumptions."""
    return int(param_bytes + kv_per_token * (mean_ctx + 1) * batch)


def attach_rung_roofline(
    out_rungs: list[dict], param_bytes: int, kv_per_token: float,
    isl: int, osl: int,
) -> None:
    """Per-rung bandwidth attribution (ROADMAP #2): analytic
    ``bytes_per_step`` at the rung's batch and the achieved-HBM-bandwidth
    estimate the median tok/s implies. steps/s = tok/s / concurrency
    (every live slot lands one token per step), so
    ``est_hbm_gbps = bytes_per_step * tok_s / concurrency / 1e9`` — on
    CPU a sanity number, on chip the roofline-fraction feed for the
    >=1.6x fp8 tok/s bar."""
    mean_ctx = isl + osl / 2
    for r in out_rungs:
        bps = decode_step_bytes(
            param_bytes, kv_per_token, r["concurrency"], mean_ctx
        )
        r["bytes_per_step"] = bps
        r["est_hbm_gbps"] = round(
            bps * r["output_tok_per_s"] / max(r["concurrency"], 1) / 1e9,
            3,
        )


def frac_of_raw(serving: dict, raw_value: float, batch: int) -> tuple[float, int]:
    """Serving efficiency vs the raw-decode ceiling, from rung MEDIANS.
    Prefers the rung whose concurrency matches the raw-decode batch;
    falls back to the top rung so the metric is always present."""
    rungs = serving["rungs"]
    top = next(
        (r for r in rungs if r["concurrency"] == batch),
        max(rungs, key=lambda r: r["concurrency"]),
    )
    return (
        round(top["output_tok_per_s"] / max(raw_value, 1e-9), 3),
        top["concurrency"],
    )


def serving_measurement(
    spec, page_size: int, on_tpu: bool,
    family: str = "gqa",
    rungs_override: list[int] | None = None,
    window_override: float | None = None,
    repeats: int | None = None,
) -> dict:
    """Sustained-load serving ladder through the REAL engine (scheduler +
    packed/chunked prefill + multi-step pipelined decode + sampling +
    streams) — the aiperf-equivalent measurement BASELINE.md calls for
    (ref benchmarks/llm/perf.sh concurrency sweeps).

    Closed-loop concurrency ladder: per rung, N streams each hold one
    request open at all times (finish -> immediately submit the next).
    Every rung runs a warmup phase (compile + fill the batch) and then a
    fixed steady-state window; only tokens/latencies inside the window
    count. The WHOLE ladder repeats ``repeats`` times (>=3 on chip) and
    each rung's artifact entry is the median + spread across its windows
    (aggregate_rung) — the serving-side variance protocol. Reported per
    rung: median output tok/s (per chip), TTFT/ITL p50/p99 medians, tail
    ratios vs the recorded bars. Random weights; latency/throughput
    don't care."""
    import asyncio

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import InferenceEngine
    from dynamo_tpu.runtime.context import Context

    tuning = FAMILY_SERVING.get(family, FAMILY_SERVING["gqa"])
    ISL, OSL = 128, 48
    if repeats is None:
        repeats = int(
            os.environ.get("DYNAMO_BENCH_LADDER_REPEATS", "3" if on_tpu else "2")
        )
    repeats = max(1, repeats)
    if on_tpu:
        # slots = 1.5x the top rung: closed-loop streams re-admit into
        # SPARE slots while the rest still decode, so a finished wave's
        # prefills overlap the running wave's bursts instead of the
        # whole ladder marching in lockstep (slots == streams leaves no
        # overlap slot and convoys the 64-rung — r5 ladder forensics)
        SLOTS = 96
        rungs = rungs_override or [8, 16, 32, 64]
        warm_s = float(os.environ.get("DYNAMO_BENCH_WARM_SECS", "6"))
        window_s = window_override or _fam_env("RUNG_SECS", family, 20.0)
    else:  # CPU smoke: tiny model, tiny ladder
        SLOTS = 8
        rungs = rungs_override or [2, 4]
        warm_s, window_s = 2.0, window_override or 4.0
    # table width sized to the workload: ISL+OSL = 176 tokens = 6 pages
    # at page 32 — a wider table would still be FETCHED only up to the
    # live length (the kernel's per-page seq_len guard), but block-table
    # padding rows cost host-side bytes per dispatch
    pps = max(1, (ISL + OSL + page_size - 1) // page_size + 2)
    cfg = EngineConfig(
        page_size=page_size,
        num_pages=SLOTS * pps + 64,
        max_pages_per_seq=pps,
        max_decode_slots=SLOTS,
        prefill_buckets=(128, 256),
        # bursts big enough that device compute covers the host sync
        # round-trip, pipelined so burst k+1 computes while k's tokens
        # cross back to the host; bursts shorten automatically while
        # admissions are pending (decode_steps_admit_pending). Per-family
        # lengths from FAMILY_SERVING (gqa 24 swept best at 64 streams
        # on v5e: 16 was -14%, 32 was -20%).
        decode_steps_per_dispatch=_fam_env("BURST", family, tuning["burst"]),
        pipeline_decode=True,
        # steady-state churn at S streams with OSL/burst-length ~2-cycle
        # requests re-admits ~S/2 prompts per cycle — a budget below
        # that equilibrium idles slots (the r4 0.49 ceiling was exactly
        # the 16-prompt default vs a 32-prompt arrival rate)
        max_prefill_tokens_per_step=_fam_env(
            "PREFILL_BUDGET", family,
            int(ISL * SLOTS * tuning["budget_frac"]),
        ),
        # dispatch.* attribution in the artifact (dispatch_overhead_frac,
        # compile events): per-phase perf_counter pairs, negligible vs
        # 6-10 ms steps
        profile=True,
    )

    async def run() -> dict:
        engine = InferenceEngine(spec, cfg)
        await engine.start()
        # pool/param byte totals for the per-rung roofline attribution —
        # captured now because the live arrays are donated through every
        # later dispatch. shape[1]/shape[-2] are num_pages/page_size on
        # both plain pools and QuantPool (.shape delegates to the values)
        pool_bytes = sum(
            int(x.size) * x.dtype.itemsize
            for x in jax.tree.leaves((engine.k_pages, engine.v_pages))
        )
        param_bytes = sum(
            int(x.size) * x.dtype.itemsize
            for x in jax.tree.leaves(engine.params)
        )
        kv_per_token = pool_bytes / (
            engine.k_pages.shape[1] * engine.k_pages.shape[-2]
        )
        rng = np.random.default_rng(0)

        async def one_rung(n_streams: int) -> dict:
            stop = asyncio.Event()
            state = {"w0": None, "w1": None}
            ttfts: list[float] = []
            itls: list[float] = []
            tok_times: list[float] = []

            async def stream(sid: int):
                while not stop.is_set():
                    toks = rng.integers(3, spec.vocab_size, ISL).tolist()
                    t0 = time.perf_counter()
                    last = None
                    async for item in engine.generate(
                        {"token_ids": toks,
                         "stop_conditions": {"max_tokens": OSL,
                                             "ignore_eos": True},
                         "sampling": {"temperature": 0.0}},
                        Context(f"bench-{n_streams}-{sid}"),
                    ):
                        n = len(item.get("token_ids") or ())
                        if not n:
                            continue
                        now = time.perf_counter()
                        w0 = state["w0"]
                        in_win = w0 is not None and now >= w0 and (
                            state["w1"] is None
                        )
                        if in_win:
                            if last is None:
                                ttfts.append(now - t0)
                            else:
                                itls.extend([(now - last) / n] * n)
                            tok_times.extend([now] * n)
                        last = now

            tasks = [asyncio.create_task(stream(i)) for i in range(n_streams)]
            await asyncio.sleep(warm_s)
            state["w0"] = time.perf_counter()
            await asyncio.sleep(window_s)
            state["w1"] = time.perf_counter()
            stop.set()
            await asyncio.gather(*tasks)
            w0, w1 = state["w0"], state["w1"]
            n_tok = sum(1 for t in tok_times if w0 <= t <= w1)

            def pct(xs, p):
                if not xs:
                    return None
                xs = sorted(xs)
                return round(
                    xs[min(len(xs) - 1, int(p * len(xs)))] * 1e3, 2
                )

            return {
                "concurrency": n_streams,
                "output_tok_per_s": round(n_tok / (w1 - w0), 1),
                "ttft_ms_p50": pct(ttfts, 0.5),
                "ttft_ms_p99": pct(ttfts, 0.99),
                "itl_ms_p50": pct(itls, 0.5),
                "itl_ms_p99": pct(itls, 0.99),
            }

        async def timed_ttft(tag: str) -> float | None:
            """First-token latency of ONE isolated request (ms)."""
            toks = rng.integers(3, spec.vocab_size, ISL).tolist()
            t0 = time.perf_counter()
            first = None
            async for item in engine.generate(
                {"token_ids": toks,
                 "stop_conditions": {"max_tokens": 2, "ignore_eos": True},
                 "sampling": {"temperature": 0.0}},
                Context(tag),
            ):
                if first is None and item.get("token_ids"):
                    first = round((time.perf_counter() - t0) * 1e3, 2)
            return first

        # cold TTFT: the very first request on this engine pays every
        # compile the precompile pass would have absorbed — the
        # cold-vs-warm delta IS the first-request tax (ROADMAP #4).
        # With DYN_COMPILE_CACHE_DIR set and populated, 'cold' measures
        # the CACHED restart instead (deserialize, not recompile) —
        # which is exactly the restarted-worker number the cache claims
        # to improve, so the artifact stays meaningful either way.
        cold_ttft_ms = await timed_ttft("bench-cold")

        # global warmup: compile every serving shape ONCE before rung 1
        # (packed + single prefill, the decode burst programs, the batched
        # first-token sample) so the first rung's window measures steady
        # state, not compilation
        async def warm_one(i: int):
            toks = rng.integers(3, spec.vocab_size, ISL).tolist()
            async for _ in engine.generate(
                {"token_ids": toks,
                 "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
                 "sampling": {"temperature": 0.0}},
                Context(f"bench-warm-{i}"),
            ):
                pass

        await asyncio.gather(*(warm_one(i) for i in range(max(rungs))))
        await warm_one(9999)  # straggler: the single-prompt program
        # trickle: low-occupancy closed loop compiles the ramp-up burst
        # program (decode_steps_admit_pending cap) the full wave never
        # hits — without this, rung 1's window starts with a compile
        for r in range(3):
            await asyncio.gather(
                *(warm_one(5000 + r * 10 + j) for j in range(4))
            )

        # warm TTFT: same isolated request with every shape compiled —
        # the cold/warm delta is what the compile cache + precompile
        # pass buys a restarted worker
        warm_ttft_ms = await timed_ttft("bench-warm-ttft")

        # dispatch attribution windows over the ladder only: drop the
        # warmup's compile noise from the dispatch.* counters
        engine.reset_profile_window()
        ladder_steps0 = engine.steps
        ladder_t0 = time.perf_counter()

        # the variance protocol: the FULL ladder repeats, so per-rung
        # medians also absorb slow drift across the run (a single rung
        # repeated back-to-back would share one noise window)
        rep_rungs: list[list[dict]] = [[] for _ in rungs]
        for _rep in range(repeats):
            for i, n in enumerate(rungs):
                rep_rungs[i].append(await one_rung(n))
        ladder_s = time.perf_counter() - ladder_t0
        snap = engine.profile_snapshot()
        ladder_steps = engine.steps - ladder_steps0
        await engine.close()
        from benchmarks.profile_engine import (
            dispatch_attribution,
            dispatch_overhead,
        )

        dispatch = dispatch_attribution(snap, ladder_steps)
        overhead = dispatch_overhead(snap, ladder_s, ladder_steps)
        out_rungs = [aggregate_rung(reps) for reps in rep_rungs]
        attach_rung_roofline(out_rungs, param_bytes, kv_per_token, ISL, OSL)
        best = max(out_rungs, key=lambda r: r["output_tok_per_s"])
        return {
            "mode": "closed-loop ladder",
            "family": family,
            "kv_dtype": engine.kv_dtype,
            "kv_bytes_per_token": round(kv_per_token, 2),
            "isl": ISL, "osl": OSL, "slots": SLOTS,
            "warmup_s": warm_s, "window_s": window_s,
            "repeats": repeats,
            "burst": cfg.decode_steps_per_dispatch,
            "prefill_budget": cfg.max_prefill_tokens_per_step,
            "rungs": out_rungs,
            "output_tok_per_s": best["output_tok_per_s"],
            "best_concurrency": best["concurrency"],
            # compile-and-dispatch evidence (ROADMAP #4): the cold/warm
            # first-request delta and the step thread's dispatch+readmit
            # overhead fraction across the ladder windows
            "cold_ttft_ms": cold_ttft_ms,
            "warm_ttft_ms": warm_ttft_ms,
            "dispatch_overhead_frac":
                overhead["dispatch_plus_readmit_frac_of_window"],
            "dispatch": dispatch,
            "bars": {
                "frac_of_raw_decode": SERVING_BARS["frac_of_raw_decode"].get(
                    family, SERVING_BARS["frac_of_raw_decode"]["gqa"]
                ),
                "ttft_p99_over_p50_max":
                    SERVING_BARS["ttft_p99_over_p50_max"],
                "itl_p99_over_p50_max":
                    SERVING_BARS["itl_p99_over_p50_max"],
            },
        }

    return asyncio.run(run())


def spec_decode_measurement(
    spec, page_size: int, on_tpu: bool,
    family: str = "gqa",
    concurrencies: tuple[int, ...] | None = None,
    osl: int | None = None,
    reqs_per_stream: int | None = None,
) -> dict:
    """Speculative-decoding micro-benchmark (ROADMAP #6 evidence): the
    SAME repetitive/agentic synthetic workload through two real engines,
    ``spec_mode=ngram`` vs ``off``, at low closed-loop concurrency (the
    regime speculation targets — per-stream latency, not saturated
    throughput).

    Per rung: ``per_stream_toks_s`` both modes + the ratio, the
    ``acceptance_rate`` of drafted tokens, and
    ``accepted_tokens_per_dispatch`` — tokens each verify dispatch
    landed (accepted drafts + the emitted target token) against the
    1.0/dispatch non-spec decode baseline. The last one is the CPU
    step-count proxy for the speedup claim: wall-clock on a shared CI
    host is noise, dispatch counts are exact. Engines run the
    latency-oriented config (burst 1, pipelined d2h, reprobe 16) —
    speculation composes with bursts for parked slots, but the claim
    under test is the low-concurrency one.

    Greedy outputs are bit-identical between the two engines by
    construction (accept-longest-prefix against the target argmax); the
    tier-1 golden suite (tests/test_spec_decode.py) pins that, so this
    measurement only reports speed."""
    import asyncio

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import InferenceEngine
    from dynamo_tpu.runtime.context import Context

    ISL = 64
    OSL = osl or 96
    reqs = reqs_per_stream or (4 if on_tpu else 2)
    rungs = list(concurrencies or ((1, 2, 3, 4) if on_tpu else (1, 2)))
    SLOTS = max(rungs) * 2
    pps = (ISL + OSL + page_size - 1) // page_size + 2

    def build(mode: str) -> EngineConfig:
        return EngineConfig(
            page_size=page_size,
            num_pages=SLOTS * pps + 64,
            max_pages_per_seq=pps,
            max_decode_slots=SLOTS,
            prefill_buckets=(64, 128),
            # latency mode: one decode step per dispatch — per-stream
            # tok/s is dispatch-floor-bound, which is exactly the floor
            # speculation amortizes
            decode_steps_per_dispatch=1,
            pipeline_decode=True,
            spec_mode=mode,
            spec_reprobe_tokens=16,
        )

    rng = np.random.default_rng(0)
    base = rng.integers(3, spec.vocab_size, 12).tolist()
    # incompressible control: a random-token prompt the drafter can't
    # predict — the adaptive-k decay must make spec mode cost ~nothing
    # here (the <5% overhead criterion, measured in exact dispatch
    # counts: a handful of decay verifies then pure burst decoding)
    random_prompt = rng.integers(3, spec.vocab_size, ISL).tolist()
    # repetitive/agentic shape: one phrase repeated (tool-loop /
    # quoted-context analogue); shared across streams like real agentic
    # traffic shares its system prefix — the prefix cache absorbing the
    # prefill repeats is part of the scenario, and both engines (spec
    # on and off) get the identical benefit
    the_prompt = (base * ((ISL // len(base)) + 1))[:ISL]

    def prompt(sid: int) -> list[int]:
        return the_prompt

    async def run() -> dict:
        out_rungs: list[dict] = []
        per_mode: dict[str, list[dict]] = {}
        for mode in ("ngram", "off"):
            engine = InferenceEngine(spec, build(mode))
            # full shape warmup incl. the verify grid: a rung window
            # must never eat a compile (the same contract serving gets
            # from --precompile)
            engine.precompile()
            await engine.start()

            async def one(sid: int, n: int, tag: str, eng=engine):
                async for _ in eng.generate(
                    {"token_ids": prompt(sid),
                     "stop_conditions": {"max_tokens": n,
                                         "ignore_eos": True},
                     "sampling": {"temperature": 0.0}},
                    Context(f"spec-{tag}-{sid}"),
                ):
                    pass

            # warm the eager host glue (feeds, stacks) precompile's
            # jitted-program warmup does not cover
            await asyncio.gather(
                *(one(sid, 4, "warm") for sid in range(max(rungs)))
            )
            rows: list[dict] = []
            for c in rungs:
                d0 = engine.dispatches
                v0, a0, r0 = (engine.spec_verifies, engine.spec_accepted,
                              engine.spec_rejected)
                t0 = time.perf_counter()

                async def stream(sid: int, eng=engine):
                    for _ in range(reqs):
                        await one(sid, OSL, "run")

                await asyncio.gather(*(stream(s) for s in range(c)))
                dt = time.perf_counter() - t0
                verifies = engine.spec_verifies - v0
                accepted = engine.spec_accepted - a0
                rejected = engine.spec_rejected - r0
                judged = accepted + rejected
                rows.append({
                    "concurrency": c,
                    "per_stream_toks_s": round(reqs * OSL / dt, 1),
                    "dispatches": engine.dispatches - d0,
                    "verifies": verifies,
                    "acceptance_rate": (
                        round(accepted / judged, 4) if judged else None
                    ),
                    "accepted_tokens_per_dispatch": (
                        round((accepted + verifies) / verifies, 3)
                        if verifies else None
                    ),
                })
            # incompressible control at concurrency 1: same engine,
            # random-token prompt — records the decayed-k overhead
            d0 = engine.dispatches
            t0 = time.perf_counter()
            async for _ in engine.generate(
                {"token_ids": random_prompt,
                 "stop_conditions": {"max_tokens": OSL,
                                     "ignore_eos": True},
                 "sampling": {"temperature": 0.0}},
                Context(f"spec-rand-{mode}"),
            ):
                pass
            rows.append({
                "concurrency": "incompressible-control",
                "per_stream_toks_s": round(
                    OSL / (time.perf_counter() - t0), 1
                ),
                "dispatches": engine.dispatches - d0,
            })
            await engine.close()
            per_mode[mode] = rows
        ctl_on = per_mode["ngram"].pop()
        ctl_off = per_mode["off"].pop()
        for on, off in zip(per_mode["ngram"], per_mode["off"]):
            out_rungs.append({
                **on,
                "per_stream_toks_s_nospec": off["per_stream_toks_s"],
                "dispatches_nospec": off["dispatches"],
                "speedup": round(
                    on["per_stream_toks_s"]
                    / max(off["per_stream_toks_s"], 1e-9), 2,
                ),
            })
        r1 = out_rungs[0]
        return {
            "mode": "prompt-lookup spec decode",
            "family": family,
            "workload": "repetitive-agentic synthetic",
            "isl": ISL, "osl": OSL, "reqs_per_stream": reqs,
            "k_max": build("ngram").spec_k_max,
            "rungs": out_rungs,
            # headline fields at concurrency 1 (the acceptance bar:
            # accepted tokens per verify dispatch >= 1.5 on this
            # workload, i.e. >= 1.5x the non-spec step-count proxy)
            "per_stream_toks_s": r1["per_stream_toks_s"],
            "acceptance_rate": r1["acceptance_rate"],
            "accepted_tokens_per_dispatch":
                r1["accepted_tokens_per_dispatch"],
            # decayed-k cost on a prompt speculation can't help: extra
            # dispatches as a fraction of the non-spec count (the <5%
            # overhead criterion, dispatch-exact on CPU)
            "incompressible_control": {
                "dispatches": ctl_on["dispatches"],
                "dispatches_nospec": ctl_off["dispatches"],
                "dispatch_overhead_frac": round(
                    ctl_on["dispatches"]
                    / max(ctl_off["dispatches"], 1) - 1.0, 4,
                ),
                "per_stream_toks_s": ctl_on["per_stream_toks_s"],
                "per_stream_toks_s_nospec": ctl_off["per_stream_toks_s"],
            },
            "bars": {
                "accepted_tokens_per_dispatch_min": 1.5,
                "incompressible_dispatch_overhead_max": 0.05,
            },
        }

    return asyncio.run(run())


def guided_measurement(
    spec, page_size: int, on_tpu: bool,
    family: str = "gqa",
    concurrency: int | None = None,
    osl: int | None = None,
) -> dict:
    """Guided-decoding bench rung (ROADMAP #5 evidence): constrained vs
    free ITL at MIXED concurrency through one real engine — half the
    closed-loop streams carry a json_schema grammar, half decode free,
    so both classes share the same engine cycles.

    The headline ``masking_overhead_frac`` is PAIRED: median constrained
    ITL over median free ITL *from the same mixed run* — the two classes
    ride the same dispatches, so the ratio isolates exactly what masking
    adds (host mask assembly + the on-device where) without CI wall-
    clock noise. A separate all-free baseline run is recorded for
    context (``free_itl_ms_baseline``), plus the grammar-compiler
    micro-bench (compile ms per grammar, LRU hit rate) so mask-compile
    cost is attributable in every artifact. Bar: masking ITL overhead
    < 5% (judged on the CPU rung in tier-1 and re-judged on chip).
    """
    import asyncio

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import InferenceEngine
    from dynamo_tpu.guided import TokenVocab, grammar_from_request
    from dynamo_tpu.runtime.context import Context

    ISL = 48
    OSL = osl or 64
    N = concurrency or (8 if on_tpu else 4)
    SLOTS = N
    pps = (ISL + OSL + page_size - 1) // page_size + 2
    cfg = EngineConfig(
        page_size=page_size,
        num_pages=SLOTS * pps + 64,
        max_pages_per_seq=pps,
        max_decode_slots=SLOTS,
        prefill_buckets=(64, 128),
        decode_steps_per_dispatch=1,
        pipeline_decode=True,
    )
    vocab = TokenVocab.ascii_json(spec.vocab_size)
    schema = {
        "type": "object",
        "properties": {
            "answer": {"type": "string", "maxLength": 24},
            "score": {"type": "integer"},
            "tags": {"type": "array", "items": {"type": "string"},
                     "maxItems": 4},
        },
        "required": ["answer", "score", "tags"],
    }
    grammar = grammar_from_request(
        {"response_format": {"type": "json_schema",
                             "json_schema": {"name": "bench",
                                             "schema": schema}}}
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, spec.vocab_size, ISL).tolist()
               for _ in range(N)]

    async def run_mode(guided_streams: int) -> tuple[dict, dict | None]:
        engine = InferenceEngine(spec, cfg, guided_vocab=vocab)
        engine.precompile()
        await engine.start()
        itls: dict[str, list[float]] = {"guided": [], "free": []}

        async def stream(sid: int):
            is_guided = sid < guided_streams
            req: dict = {
                "token_ids": prompts[sid],
                "stop_conditions": {"max_tokens": OSL},
                "sampling": {"temperature": 0.7, "seed": sid + 1},
            }
            if is_guided:
                req["guided"] = {**grammar, "prompt_len": ISL}
            else:
                req["stop_conditions"]["ignore_eos"] = True
            last = None
            async for item in engine.generate(req, Context(f"g{sid}")):
                if item.get("token_ids"):
                    now = time.perf_counter()
                    if last is not None:
                        itls["guided" if is_guided else "free"].append(
                            (now - last) / len(item["token_ids"])
                        )
                    last = now

        # warmup pass fills caches (grammar LRU + host glue), then the
        # measured pass
        await asyncio.gather(*(stream(s) for s in range(N)))
        for v in itls.values():
            v.clear()
        await asyncio.gather(*(stream(s) for s in range(N)))
        snap = engine.guided_snapshot()
        await engine.close()

        def ms(xs):
            return round(float(np.median(xs)) * 1e3, 4) if xs else None

        return {"guided_itl_ms": ms(itls["guided"]),
                "free_itl_ms": ms(itls["free"]),
                "guided_tokens": len(itls["guided"]),
                "free_tokens": len(itls["free"])}, snap

    async def run() -> dict:
        mixed, snap = await run_mode(guided_streams=N // 2)
        baseline, _ = await run_mode(guided_streams=0)
        overhead = None
        if mixed["guided_itl_ms"] and mixed["free_itl_ms"]:
            overhead = round(
                mixed["guided_itl_ms"] / mixed["free_itl_ms"] - 1.0, 4
            )
        return {
            "mode": "guided mixed-concurrency ITL",
            "family": family,
            "isl": ISL, "osl": OSL, "concurrency": N,
            "guided_streams": N // 2,
            "grammar_kind": grammar["kind"],
            **mixed,
            "free_itl_ms_baseline": baseline["free_itl_ms"],
            # the headline: constrained vs free slots SHARING the same
            # engine cycles — what masking itself costs
            "masking_overhead_frac": overhead,
            "grammar_compiler": snap,
            "bars": {"masking_itl_overhead_max": 0.05},
        }

    return asyncio.run(run())


def hbm_roofline(dev, gbps: float) -> dict:
    """Achieved HBM bandwidth and its share of the chip's peak. A TPU
    that is not in PEAK_HBM is an error, not a default; the ``--cpu``
    smoke gets no roofline field at all (a CPU number is never written
    under a device metric's name)."""
    if dev.platform != "tpu":
        return {}
    peak = next(
        (v for k, v in PEAK_HBM.items() if dev.device_kind.startswith(k)),
        None,
    )
    if peak is None:
        raise SystemExit(
            f"bench.py: no peak HBM bandwidth recorded for device kind "
            f"{dev.device_kind!r}; add it to PEAK_HBM with its source"
        )
    return {
        "achieved_hbm_gbps": round(gbps, 1),
        "hbm_roofline_frac": round(gbps / peak, 3),
    }


def raw_decode(
    spec: ModelSpec, B: int, page_size: int, pages_per_seq: int,
    repeats: int = 1,
) -> dict:
    """Matched-batch fused-decode throughput for one model family.

    Variance protocol (VERDICT r4 weak #3): the measurement repeats
    ``repeats`` times in one process and the MEDIAN is the headline;
    ``spread_frac`` = (max-min)/median makes run-to-run noise visible in
    the artifact instead of silently polluting cross-round comparisons."""
    fam = get_family(spec)
    num_pages = 1 + B * pages_per_seq

    key = jax.random.PRNGKey(0)
    params = fam.init_params(spec, key)
    from dynamo_tpu.ops.quant import resolve_kv_dtype

    # DYN_KV_DTYPE=fp8 runs the whole raw ladder quantized: cache_bytes
    # below then prices fp8 values + bf16 scales, so bytes_per_step and
    # the roofline fraction in the artifact reflect the real traffic
    kv_dtype = resolve_kv_dtype(None)
    k_pages, v_pages = fam.init_cache(
        spec, num_pages, page_size, kv_dtype=kv_dtype
    )
    cache_bytes = sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves((k_pages, v_pages))
    )

    bt = np.zeros((B, pages_per_seq), np.int32)
    for i in range(B):
        bt[i] = np.arange(1 + i * pages_per_seq, 1 + (i + 1) * pages_per_seq)
    block_tables = jnp.asarray(bt)
    active = jnp.ones((B,), bool)
    # leave room for every decoded token (warmup + timed) inside the table
    capacity = page_size * pages_per_seq
    start_len = capacity - (WARMUP + STEPS) - 2
    assert start_len > 0
    tokens = jnp.zeros((B,), jnp.int32)
    temps = jnp.zeros((B,), jnp.float32)  # greedy
    topk = jnp.zeros((B,), jnp.int32)
    topp = jnp.ones((B,), jnp.float32)
    seeds = jnp.zeros((B,), jnp.uint32)

    def run(n_steps: int, toks, lens, gen, k_pages, v_pages):
        done = 0
        while done < n_steps:
            n = min(STEPS_PER_DISPATCH, n_steps - done)
            out, k_pages, v_pages = fam.decode_steps(
                spec, params, toks, block_tables, lens, k_pages, v_pages,
                active, temps, topk, topp, seeds, gen, n_steps=n,
                n_logprobs=0, mesh=None,
            )
            toks = out[:, -1]
            lens = lens + n
            gen = gen + n
            done += n
        return toks, lens, gen, k_pages, v_pages

    lens0 = jnp.full((B,), start_len + 1, jnp.int32)
    gen0 = jnp.zeros((B,), jnp.int32)
    toks, lens, gen, k_pages, v_pages = run(
        WARMUP, tokens, lens0, gen0, k_pages, v_pages
    )  # compile
    toks.block_until_ready()

    # block_until_ready ends the timed window. Every repeat resets
    # lens/gen to the post-warmup values: the cache only has page room for
    # WARMUP+STEPS tokens, so continuing from advanced state would decode
    # past capacity (page content is timing-irrelevant garbage either way).
    toks0, lens0_t, gen0_t = toks, lens, gen
    values = []
    for _rep in range(max(1, repeats)):
        toks, lens, gen = toks0, lens0_t, gen0_t
        t0 = time.perf_counter()
        toks, lens, gen, k_pages, v_pages = run(
            STEPS, toks, lens, gen, k_pages, v_pages
        )
        toks.block_until_ready()
        values.append(B * STEPS / (time.perf_counter() - t0))
    values.sort()
    value = values[len(values) // 2]  # median rep
    dt = B * STEPS / value
    step_ms = dt / STEPS * 1e3

    # roofline: bytes each decode step must touch (family-generic: KV
    # bytes derive from the ACTUAL cache arrays — MLA's latent cache is
    # far smaller per token than a GQA cache)
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
    )
    mean_ctx = float(start_len + (WARMUP + STEPS) / 2)
    kv_per_token = cache_bytes / (num_pages * page_size)
    kv_read = kv_per_token * mean_ctx * B
    kv_write = kv_per_token * B
    bytes_per_step = param_bytes + kv_read + kv_write
    gbps = bytes_per_step / (dt / STEPS) / 1e9
    dev = jax.devices()[0]
    out = {
        "value": round(value, 2),
        "step_ms": round(step_ms, 3),
        "batch": B,
        "kv_dtype": kv_dtype,
        "bytes_per_step_gb": round(bytes_per_step / 1e9, 3),
        "device": dev.device_kind,
        **hbm_roofline(dev, gbps),
    }
    if len(values) > 1:
        out["repeats"] = len(values)
        out["spread_frac"] = round(
            (values[-1] - values[0]) / max(value, 1e-9), 4
        )
        out["rep_values"] = [round(v, 1) for v in values]
    return out


def main() -> None:
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and "--cpu" not in sys.argv:
        raise SystemExit(
            f"bench.py: JAX found no TPU (backend {jax.default_backend()!r}); "
            "a CPU run measures nothing about the device. Pass --cpu for "
            "the tiny-model smoke of the code path."
        )
    family = os.environ.get("DYNAMO_BENCH_FAMILY", "gqa")
    repeats = int(os.environ.get("DYNAMO_BENCH_REPEATS", "3" if on_tpu else "1"))
    spec, B, page_size, pages_per_seq = bench_spec(on_tpu, family)

    raw = raw_decode(spec, B, page_size, pages_per_seq, repeats=repeats)
    value = raw["value"]
    prior = prior_value()
    out = {
        "metric": "decode_tokens_per_sec_per_chip",
        "unit": "tok/s",
        "family": family,
        # vs_baseline compares against prior rounds' gqa artifacts; for
        # other families (or with no prior) there is no comparable
        # baseline — null, not a fake 1.0 that reads as "matched exactly"
        "vs_baseline": (
            round(value / prior, 4) if prior and family == "gqa" else None
        ),
        **raw,
    }
    if os.environ.get("DYNAMO_BENCH_SERVING", "1") not in ("0", "false"):
        out["serving"] = serving_measurement(
            spec, page_size, on_tpu, family=family
        )
        # serving efficiency vs the raw-decode ceiling this same run just
        # measured, from rung MEDIANS (VERDICT r3: >= 60% is the gqa bar;
        # the bar itself rides in serving["bars"]).
        frac, rung_c = frac_of_raw(out["serving"], value, B)
        out["serving"]["frac_of_raw_decode"] = frac
        out["serving"]["frac_rung_concurrency"] = rung_c
    if os.environ.get("DYNAMO_BENCH_SPEC", "1") not in ("0", "false"):
        # speculative decoding at low concurrency (ROADMAP #6): spec-on
        # vs spec-off per-stream tok/s + acceptance on the repetitive
        # synthetic workload, per family
        out["spec_decode"] = spec_decode_measurement(
            spec, page_size, on_tpu, family=family
        )
    if os.environ.get("DYNAMO_BENCH_GUIDED", "1") not in ("0", "false"):
        # guided decoding (ROADMAP #5): constrained vs free ITL at mixed
        # concurrency + grammar-compiler cost, judged against the <5%
        # masking-overhead bar
        out["guided"] = guided_measurement(
            spec, page_size, on_tpu, family=family
        )
    # the OTHER flagship families' on-chip numbers ride in the same
    # artifact (VERDICT r4 weak #2: BASELINE's deepseek-r1 and
    # gpt-oss-120b configs previously had no TPU evidence): raw decode
    # with the same repeat protocol + the SAME full serving ladder and
    # variance protocol gqa gets (VERDICT r5 next #2 — one 10s rung with
    # no tails was half the measurement coverage), on per-family
    # burst/budget tuning (FAMILY_SERVING)
    if family == "gqa" and on_tpu and os.environ.get(
        "DYNAMO_BENCH_FAMILIES", "1"
    ) not in ("0", "false"):
        out["families"] = {}
        for fam_name in ("mla", "gptoss"):
            fspec, fB, fpage, fpps = bench_spec(on_tpu, fam_name)
            fraw = raw_decode(fspec, fB, fpage, fpps, repeats=repeats)
            serving = serving_measurement(
                fspec, fpage, on_tpu, family=fam_name,
                window_override=_fam_env("RUNG_SECS", fam_name, 10.0),
            )
            fraw["serving"] = serving
            ffrac, frung_c = frac_of_raw(serving, fraw["value"], fB)
            fraw["serving_frac_of_raw"] = ffrac
            fraw["frac_rung_concurrency"] = frung_c
            if os.environ.get("DYNAMO_BENCH_SPEC", "1") not in (
                "0", "false"
            ):
                fraw["spec_decode"] = spec_decode_measurement(
                    fspec, fpage, on_tpu, family=fam_name
                )
            out["families"][fam_name] = fraw
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
