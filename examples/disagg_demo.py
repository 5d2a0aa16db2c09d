"""Disaggregated prefill/decode demo: hub + prefill worker + decode worker +
OpenAI frontend, all separate OS processes; the long-prompt request is
prefilled on the prefill worker, its KV pages transferred worker→worker over
TCP, and decoded on the decode worker.

Run: python examples/disagg_demo.py

A CPU demo: the workers are child processes on the CPU backend. On TPUs a
chip belongs to one process, so disaggregated prefill/decode needs one chip
per worker process; `chip_smoke.py` is the single-process path on a chip.
"""

import asyncio
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {
    **os.environ,
    "PYTHONPATH": REPO,
    "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS_DEMO", "cpu"),
}


def spawn(args, ready_prefix):
    p = subprocess.Popen(
        [sys.executable, *args], stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, cwd=REPO, env=ENV,
    )
    for line in p.stdout:
        line = line.strip()
        if line.startswith(ready_prefix):
            return p, line.split("=", 1)[-1] if "=" in line else line
    raise RuntimeError(f"{args}: exited before ready ({ready_prefix})")


async def main() -> int:
    procs = []
    ok = True
    try:
        hub, hub_addr = spawn(
            ["-m", "dynamo_tpu.runtime.hub_server", "--port", "0"], "DYNAMO_HUB="
        )
        procs.append(hub)
        print(f"[demo] hub: {hub_addr}")

        common = ["--hub", hub_addr, "--model", "tiny-test", "--page-size", "4",
                  "--num-pages", "256", "--max-pages-per-seq", "32",
                  "--max-decode-slots", "4"]
        prefill, _ = spawn(
            ["-m", "dynamo_tpu.engine.worker", *common, "--mode", "prefill"],
            "ENGINE_READY",
        )
        procs.append(prefill)
        print("[demo] prefill worker up")

        decode, _ = spawn(
            ["-m", "dynamo_tpu.engine.worker", *common, "--mode", "decode",
             "--max-local-prefill-length", "8"],
            "ENGINE_READY",
        )
        procs.append(decode)
        print("[demo] decode worker up (remote prefill beyond 8 tokens)")

        frontend, http_addr = spawn(
            ["-m", "dynamo_tpu.frontend", "--hub", hub_addr,
             "--host", "127.0.0.1", "--port", "0"],
            "DYNAMO_HTTP=",
        )
        procs.append(frontend)
        base = f"http://{http_addr}"
        print(f"[demo] frontend: {base}")

        import aiohttp

        async with aiohttp.ClientSession() as sess:
            for _ in range(200):
                async with sess.get(f"{base}/v1/models") as r:
                    models = (await r.json())["data"]
                if models:
                    break
                await asyncio.sleep(0.1)
            if not models:
                print("[demo] FAIL: no models discovered")
                return 1

            # long prompt -> remote prefill; greedy -> deterministic
            payload = {
                "model": "tiny-test",
                "messages": [{"role": "user",
                              "content": "a long prompt that should cross the "
                                         "local prefill threshold for sure"}],
                "max_tokens": 8, "temperature": 0.0, "ignore_eos": True,
            }
            async with sess.post(f"{base}/v1/chat/completions", json=payload) as r:
                assert r.status == 200, await r.text()
                body1 = await r.json()
            async with sess.post(f"{base}/v1/chat/completions", json=payload) as r:
                body2 = await r.json()
            c1 = body1["choices"][0]["message"]["content"]
            c2 = body2["choices"][0]["message"]["content"]
            print(f"[demo] disagg chat x2: {c1!r} / {c2!r} "
                  f"usage={body1['usage']}")
            ok &= body1["usage"]["completion_tokens"] == 8
            ok &= c1 == c2

            # streaming through the disagg path
            n_chunks = 0
            async with sess.post(
                f"{base}/v1/chat/completions", json={**payload, "stream": True}
            ) as r:
                async for line in r.content:
                    line = line.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        n_chunks += 1
            print(f"[demo] streamed: {n_chunks} SSE chunks")
            ok &= n_chunks >= 8

            # short prompt stays local on the decode worker
            async with sess.post(
                f"{base}/v1/completions",
                json={"model": "tiny-test", "prompt": "x",
                      "max_tokens": 4, "ignore_eos": True},
            ) as r:
                ok &= r.status == 200
            print("[demo] short prompt served locally")
    finally:
        for p in procs:
            p.terminate()
    print("[demo] PASS" if ok else "[demo] FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
