#!/usr/bin/env python3
"""perfbench: one run of one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Builds the serving stack in this process from the seed, warms
what the cell's traffic dispatches (set-up), serves the cell's traffic from
a child process over real HTTP for ``--seconds`` seconds, checks the
outputs against the configuration's plain reference, and prints as the last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and, traced, ``breakdown``).
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics. See ``perfbench/README.md``.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # process start, as near as Python gets to it

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from lib import spec as spec_mod  # noqa: E402
from lib import stats, traffic  # noqa: E402

TRACE_START_SHARE = 0.3  # the traced part opens this far into the window
# and lasts this long. Stopping the profiler took 20 s for 3 s of trace
# (my chip runs, PR 24), off the event loop: 6 s still end inside the
# window's tail, 10 s would not
TRACE_SECONDS = 6.0


def say(msg: str) -> None:
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the benchmark's own tests only: a benchmark of toy sizes in
    # another directory, rehearsed on the CPU. A rehearsal prints no metric
    # that comes from the device.
    ap.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


async def spawn_loadgen():
    """The child that sends the traffic; started early, it waits on its
    standard input for the job."""
    return await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "lib", "loadgen.py"),
        stdin=asyncio.subprocess.PIPE, cwd=ROOT,
    )


async def warm_requests(stack, cell, overhead_box: list) -> None:
    """A few real requests through HTTP before the lead-in: they measure
    the chat template's tokens (the prompts' lengths are exact after it)
    and touch the request path once outside the window."""
    import aiohttp

    async def one(sess, n_prompt: int, n_out: int, overhead: int) -> dict:
        req = {
            "prompt_tokens": n_prompt, "content_seed": n_prompt,
            "prefix_tokens": 0, "prefix_group": 0,
        }
        body = {
            "model": stack.engine.spec.name,
            "messages": [{
                "role": "user",
                "content": traffic.content_for(req, overhead),
            }],
            "max_tokens": n_out, "temperature": 0, "ignore_eos": True,
        }
        async with sess.post(
            stack.base + "/v1/chat/completions", json=body
        ) as r:
            data = await r.json()
            if r.status != 200:
                raise SystemExit(f"perfbench: warm-up request: {data}")
        return data["usage"]

    async with aiohttp.ClientSession() as sess:
        usage = await one(sess, 64, 2, 0)
        overhead = usage["prompt_tokens"] - 64
        overhead_box.append(overhead)
        lo = int(cell.traffic["prompt_tokens"].get(
            "min", cell.traffic["prompt_tokens"].get("value", 64)))
        usage = await one(sess, max(lo, overhead + 2), 2, overhead)
        if usage["prompt_tokens"] != max(lo, overhead + 2):
            raise SystemExit(
                "perfbench: prompt lengths are not exact: asked "
                f"{max(lo, overhead + 2)}, the server counted "
                f"{usage['prompt_tokens']}"
            )


async def build(args, cell) -> dict:
    """Set-up: the stack from the seed, precompiled, and a few warm
    requests. Returns the state a window runs on."""
    from lib import stack as stk

    run_dir = os.path.join(args.root, ".perfbench_run", f"{os.getpid()}")
    os.makedirs(run_dir, exist_ok=True)
    model = stk.model_spec(cell.config)
    cfg = stk.engine_config(cell.config, args.seed, profile=bool(args.trace))
    # the traced run alone taps the engine's streams: the end-to-end
    # runs serve with nothing of the benchmark's in the path
    firsts = stk.tap_first_deltas() if args.trace else []
    stack = await stk.start_stack(model, cfg)
    refused = {
        k: v["error"] for k, v in stack.engine.precompile_report.items()
        if "error" in v
    }
    if refused:
        raise SystemExit(f"perfbench: precompile refused {refused}")
    overhead_box: list = []
    await warm_requests(stack, cell, overhead_box)
    return {
        "stack": stack, "engine": stack.engine, "model": model, "cfg": cfg,
        "overhead_tokens": overhead_box[0], "run_dir": run_dir,
        "firsts": firsts,
        "prefills": stk.tap_prefills(stack.engine) if args.trace else [],
    }


async def window(state: dict, plan: dict, child, *, trace: bool) -> dict:
    """Lead-in, the measured window, drain: the child sends ``plan`` and
    this process samples the engine's counts (and traces part of it)."""
    import jax
    import numpy as np

    from lib import stack as stk

    stack, engine, run_dir = state["stack"], state["engine"], state["run_dir"]
    seconds = plan["seconds"]
    plan_path = os.path.join(run_dir, "plan.json")
    out_path = os.path.join(run_dir, "records.json")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    gc.collect()
    gc.freeze()
    sampler = stk.Sampler(engine)
    firsts = state["firsts"]
    # the window opens a lead-in from now; the child needs a moment to
    # read the plan
    t0 = time.monotonic() + plan["lead_in_s"] + 0.5
    child.stdin.write((json.dumps({
        "url": stack.base, "model": state["model"].name,
        "plan": plan_path, "out": out_path, "t0": t0,
        "overhead_tokens": state["overhead_tokens"],
    }) + "\n").encode())
    await child.stdin.drain()
    child.stdin.close()
    await asyncio.sleep(max(0.0, t0 - time.monotonic()))
    # ---- the measured window opens
    compiles0 = stk.compile_snapshot()[0]
    prof0 = engine.profile_snapshot()
    sampler.start()
    trace_dir = os.path.join(run_dir, "trace")
    traced = None
    if trace:
        await asyncio.sleep(TRACE_START_SHARE * seconds)
        span = min(TRACE_SECONDS, 0.5 * seconds)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        # off the event loop: starting and stopping the profiler takes
        # a while, and the frontend serves from this loop
        await asyncio.to_thread(
            jax.profiler.start_trace, trace_dir, profiler_options=opts
        )
        a = time.monotonic()
        await asyncio.sleep(span)
        b = time.monotonic()
        await asyncio.to_thread(jax.profiler.stop_trace)
        traced = (a - t0, b - t0, time.monotonic() - t0)
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    # ---- and closes
    prof1 = engine.profile_snapshot()
    compiles1 = stk.compile_snapshot()[0]
    # the deployment's peak: set-up and serving, before the output check
    # brings the reference's own allocations
    memory_peak = stk.memory_peak_bytes()
    await sampler.stop()
    rc = await asyncio.wait_for(child.wait(), timeout=240)
    if rc != 0:
        raise SystemExit(f"perfbench: load generator exited {rc}")
    with open(out_path) as f:
        result = json.load(f)
    return {
        "engine": engine, "records": result["records"], "plan": plan,
        "t0": t0, "seconds": seconds, "setup_s": t0 - _T_START,
        "compiles_in_window": compiles1 - compiles0,
        "profile": (prof0, prof1), "samples": sampler.rows,
        "firsts": list(firsts), "fallbacks": stk.fallback_series(),
        "prefills": [
            (t, [int(n) for n in np.atleast_1d(np.asarray(ns))])
            for t, ns in state["prefills"]
        ],
        "memory_peak_bytes": memory_peak,
        "traced": traced, "trace_dir": trace_dir if trace else None,
        "overhead_tokens": state["overhead_tokens"], "run_dir": run_dir,
    }


async def serve(args, cell, device: dict) -> dict:
    """Set-up, lead-in, window, drain. Returns everything the metric
    readers read, with the engine closed and kept."""
    from lib import stack as stk

    child = await spawn_loadgen()
    try:
        state = await build(args, cell)
        plan = traffic.make_plan(
            cell.traffic, args.seed, float(args.seconds),
            decode_slots=state["cfg"].max_decode_slots,
        )
        run = await window(state, plan, child, trace=bool(args.trace))
        t_stop = time.monotonic()
        await stk.stop_stack(state["stack"])
        run["stop_s"] = time.monotonic() - t_stop
        run["device"] = device
        return run
    except BaseException:
        if child.returncode is None:
            child.kill()
            await child.wait()
        raise


def read_metrics(cell, run: dict, which: list[dict], rehearsal: bool) -> dict:
    """Each metric through its own reader. A reader that finds nothing to
    read returns None and the metric is left out. A rehearsal on the CPU
    never prints a metric that comes from the device."""
    out = {}
    for m in which:
        if rehearsal and m.get("source") == "device_trace":
            continue
        value = cell.readers[m["reader"]](run, cell)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if args.rehearse_cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("perfbench: a rehearsal needs JAX_PLATFORMS=cpu")
    if args.rehearse_cpu:
        os.environ.setdefault("DYNAMO_PALLAS", "1")  # interpret the kernels
    cell = spec_mod.load_cell(args.root, args.workload)

    from lib import correct, costs, trace
    from lib import stack as stk

    device = stk.device_report(cell.chips, rehearsal=args.rehearse_cpu)
    if not args.rehearse_cpu:
        costs.peaks_for(device["kind"])  # an unknown device is an error
    say(f"perfbench: {cell.name} seed {args.seed} on {device}")
    run = asyncio.run(serve(args, cell, device))
    engine = run["engine"]
    say(f"perfbench: set-up {run['setup_s']:.1f} s, stack stopped in "
        f"{run['stop_s']:.1f} s, served at "
        f"{time.monotonic() - _T_START:.1f} s; precompile "
        + json.dumps({k: v["secs"] for k, v in engine.precompile_report.items()}))
    if run["traced"]:
        say("perfbench: traced {:.2f}..{:.2f} s of the window, the profiler "
            "stopped at {:.2f} s".format(*run["traced"]))

    reduced = None
    if args.trace and not args.rehearse_cpu:
        path = trace.find_xplane(run["trace_dir"])
        if path is None:
            raise SystemExit("perfbench: the profiler wrote no trace")
        reduced = trace.reduce_file(path, cell.config["trace_names"]["programs"])
        if reduced is None or reduced["busy_s"] <= 0:
            raise SystemExit("perfbench: no operation ran on the device "
                             "inside the traced window")
    run["trace"] = reduced

    counted = stats.windowed(run["records"])
    failed = [r for r in counted if not r["ok"]]
    wrong_len = [
        r for r in counted
        if r["ok"] and r["prompt_tokens"] != r["want_prompt_tokens"]
    ]
    verdict = correct.check_engine(
        engine, cell.config, args.seed, stk.engine_seed(args.seed),
        cell.bench_dir,
    )
    for name, row in verdict["rows"].items():
        say(f"correct: {name} {row['value']:.6g} (limit {row['limit']})")
    say(f"correct: also {verdict['also']}, sample {verdict['sample_lens']}, "
        f"served {verdict['served_rows']}, seconds {verdict['secs']}")
    say(f"correct: requests failed {len(failed)} (limit 0) of {len(counted)}"
        f"; prompts of another length than asked {len(wrong_len)} (limit 0)")
    say(f"correct: compiles in the window {run['compiles_in_window']} "
        "(limit 0)")
    say(f"correct: fallback series {run['fallbacks']} (limit: none)")
    for r in failed[:5]:
        say(f"correct: failed request {r['id']}: {r['error']}")
    ok = (
        verdict["ok"] and not failed and not wrong_len and bool(counted)
        and run["compiles_in_window"] == 0 and not run["fallbacks"]
    )

    which = cell.per_layer if args.trace else cell.end_to_end
    metrics = read_metrics(cell, run, which, args.rehearse_cpu)
    device_out = dict(device)
    device_out["memory_peak_bytes"] = run["memory_peak_bytes"]
    say(f"perfbench: memory peak {run['memory_peak_bytes']} bytes when the "
        f"window closed, {stk.memory_peak_bytes()} after the output check")
    line = {
        "correct": bool(ok), "attempted": len(counted), "failed": len(failed),
        "metrics": metrics, "device": device_out,
    }
    if reduced is not None:
        device_out["busy_s"] = reduced["busy_s"]
        device_out["window_s"] = reduced["window_s"]
        line["breakdown"] = trace.breakdown(reduced)
    shutil.rmtree(run["run_dir"], ignore_errors=True)
    say(f"perfbench: done at {time.monotonic() - _T_START:.1f} s")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
