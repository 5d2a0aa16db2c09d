#!/usr/bin/env python3
"""perfbench/prove.py: the builder's proving script. Not run by the driver.

Set-up is minutes, so what needs many seeds or many rates runs in ONE
process here:

    python3 perfbench/prove.py correct --workload <cell> --seeds 1,2,3 [--controls fp8,int8] [--kv-fp8-seeds 1,2,3]
        for every seed: an engine built from the seed (no HTTP, no window),
        the output check's numbers for the program, and for each control
        (the reference computed in a lower precision, put in the program's
        place) the same numbers; on the --kv-fp8-seeds also for the program
        with its own fp8 KV cache switched on. The limits in the
        configuration's file are set from these readings.

    python3 perfbench/prove.py sweep --workload <cell> --rates 4,6,8 --seconds 20
        one stack, the cell's traffic at each rate in turn: requests due and
        finished, the waiting queue at the window's start and end, TTFT and
        TPOT. The knee is the highest rate whose backlog does not grow.

    python3 perfbench/prove.py trace --path <file.xplane.pb>
        planes, lines and first events of a trace, for reading one by hand.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(msg, flush=True)


def _numbers(correct, got, want, served) -> dict:
    """Every number of the comparison, with no limit applied."""
    return {
        "prefill_rel_rms": correct.rel_rms(got[0], want[0]),
        "decode_rel_rms": correct.rel_rms(got[1], want[1]),
        **{k: v for k, v in served.items() if k != "also"},
        **served["also"],
    }


def _engine_numbers(correct, stk, ref, cell, seed, **engine_kw) -> dict:
    """An engine built from the seed (no HTTP, no window) through both
    samples, against the reference; the rows as it decoded them come back
    for the controls."""
    from dynamo_tpu.engine.core import InferenceEngine

    config = dict(cell.config, engine=dict(cell.config["engine"], **engine_kw))
    cfg = stk.engine_config(config, seed, profile=False)
    engine = InferenceEngine(stk.model_spec(config), cfg)
    smp = correct.sample(config, cfg, list(engine._prefill_shapes), seed)
    rows = correct.served_sample(config, engine, seed)
    got = correct.engine_logits(engine, smp)
    packed, chosen = correct.served_outputs(engine, rows)
    wseed = stk.engine_seed(seed)
    # beside the live engine, as a run's check computes it
    want = correct.reference_logits(ref, config, wseed, smp)
    want_rows = correct.served_reference(ref, config, wseed, rows)
    engine.params = engine.k_pages = engine.v_pages = None
    del engine
    gc.collect()
    served = correct.served_numbers(packed, chosen, want_rows, rows["bursts"])
    return {
        "numbers": _numbers(correct, got, want, served),
        "smp": smp, "rows": rows, "want": want, "want_rows": want_rows,
    }


def prove_correct(args) -> int:
    import jax

    from lib import correct, spec as spec_mod
    from lib import stack as stk

    cell = spec_mod.load_cell(args.root, args.workload)
    stk.device_report(cell.chips, rehearsal=args.rehearse_cpu)
    ref = correct.load_reference(cell.config, cell.bench_dir)
    controls = [c for c in args.controls.split(",") if c]
    kv_seeds = [int(s) for s in args.kv_fp8_seeds.split(",") if s]
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for seed in seeds + [s for s in kv_seeds if s not in seeds]:
        t0 = time.monotonic()
        wseed = stk.engine_seed(seed)
        row = {"seed": seed}
        if seed in seeds:
            run = _engine_numbers(correct, stk, ref, cell, seed)
            row.update(lens=run["smp"]["lens"], program=run["numbers"])
            for quant in controls:
                # the reference in a lower precision, put in the program's
                # place: its logits, and its choice of token at the
                # positions of the sequences the engine decoded
                low = correct.reference_logits(
                    ref, cell.config, wseed, run["smp"], quant=quant
                )
                low_rows = correct.served_reference(
                    ref, cell.config, wseed, run["rows"], quant=quant
                )
                served = correct.served_numbers(
                    low_rows[:, 0], low_rows[:, 1:].argmax(-1),
                    run["want_rows"], run["rows"]["bursts"],
                )
                row[quant] = _numbers(correct, low, run["want"], served)
            del run
        if seed in kv_seeds:
            # the program's own lower-precision path: the KV cache in fp8
            try:
                row["kv_fp8"] = _engine_numbers(
                    correct, stk, ref, cell, seed, kv_dtype="fp8"
                )["numbers"]
            except Exception as e:  # noqa: BLE001 - a crash is a reading too
                row["kv_fp8_error"] = f"{type(e).__name__}: {e}"[:500]
        row["secs"] = round(time.monotonic() - t0, 1)
        rows.append(row)
        say("PROVE " + json.dumps(row))
        gc.collect()
    for who in ["program"] + controls + ["kv_fp8"]:
        have = [r[who] for r in rows if who in r]
        for key in (have[0] if have else ()):
            vals = [h[key] for h in have]
            say(f"PROVE {who} {key}: min {min(vals):.6g} max {max(vals):.6g} "
                f"over {len(vals)} seeds")
    peak = stk.memory_peak_bytes()
    say(f"PROVE memory peak {peak / 2**30:.2f} GiB; device "
        f"{jax.devices()[0].device_kind}")
    return 0


async def _sweep(args, cell) -> None:
    import run as run_mod
    from lib import stack as stk, stats, traffic

    args.trace = 0
    state = await run_mod.build(args, cell)
    say(f"SWEEP set-up {time.monotonic() - run_mod._T_START:.1f} s; precompile "
        + json.dumps({k: v['secs'] for k, v in
                      state['engine'].precompile_report.items()}))
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic)
        if mix["loop"] == "open":
            mix["rate_rps"] = rate
        else:
            mix["clients"] = int(rate)
        plan = traffic.make_plan(
            mix, args.seed + i, args.seconds,
            decode_slots=state["cfg"].max_decode_slots,
        )
        child = await run_mod.spawn_loadgen()
        run = await run_mod.window(state, plan, child, trace=False)
        recs = stats.windowed(run["records"])
        ok = [r for r in recs if r["ok"]]
        t0, sec = run["t0"], run["seconds"]
        rows = [r for r in run["samples"] if t0 <= r[0] <= t0 + sec]
        third = max(1, len(rows) // 3)

        def mean(rs, i):
            return sum(r[i] for r in rs) / max(1, len(rs))

        pct = lambda fn, q: stats.ms(stats.percentile(stats.pooled(ok, fn), q))  # noqa: E731
        say("SWEEP " + json.dumps({
            "rate": rate, "due": len(recs), "ok": len(ok),
            "waiting_first_third": round(mean(rows[:third], 1), 2),
            "waiting_last_third": round(mean(rows[-third:], 1), 2),
            "waiting_peak": max(r[1] for r in rows),
            "slots_mean": round(mean(rows, 3), 1),
            "pages_peak": max(r[2] for r in rows),
            "ttft_p50_ms": pct(stats.ttft_s, 0.5),
            "ttft_p95_ms": pct(stats.ttft_s, 0.95),
            "tpot_p50_ms": pct(stats.tpot_s, 0.5),
            "itl_p95_ms": pct(stats.gaps_s, 0.95),
            "late_p99_ms": pct(stats.late_s, 0.99),
            "out_tok_s": stats.tokens_in_window(run["records"], sec) / sec,
            "drain_s": round(max(
                (r["chunks"][-1] for r in ok), default=0.0) - sec, 2),
            "compiles": run["compiles_in_window"],
        }))
        for r in [r for r in recs if not r["ok"]][:5]:
            say(f"SWEEP failed {r['id']}: {r['error']} (prompt "
                f"{r['want_prompt_tokens']}, max_tokens {r['max_tokens']})")
        await asyncio.sleep(1.0)
    await stk.stop_stack(state["stack"])


def prove_sweep(args) -> int:
    from lib import spec as spec_mod
    from lib import stack as stk

    if args.rehearse_cpu:
        os.environ.setdefault("DYNAMO_PALLAS", "1")
    cell = spec_mod.load_cell(args.root, args.workload)
    stk.device_report(cell.chips, rehearsal=args.rehearse_cpu)
    asyncio.run(_sweep(args, cell))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("correct", "sweep", "trace"))
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--controls", default="fp8,int8")
    ap.add_argument("--kv-fp8-seeds", default="",
                    help="seeds on which an engine with an fp8 KV cache "
                         "is read too")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--path", default="")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.mode == "trace":
        from lib import trace

        say(trace.describe(args.path))
        return 0
    if args.rehearse_cpu:
        os.environ.setdefault("DYNAMO_PALLAS", "1")
    return prove_correct(args) if args.mode == "correct" else prove_sweep(args)


if __name__ == "__main__":
    sys.exit(main())
