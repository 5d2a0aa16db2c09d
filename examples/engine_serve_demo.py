"""End-to-end JAX-engine serving demo: hub + engine worker + OpenAI frontend
as separate OS processes, driven through the HTTP API.

Run: python examples/engine_serve_demo.py          (pure-JAX decode path)
     DYNAMO_PALLAS=1 python examples/engine_serve_demo.py
                                    (Pallas paged-attention kernel; interpret
                                     mode off-TPU, compiled kernel on TPU)

Exercises: real continuous-batching engine (paged KV cache, prefix reuse),
model-card discovery, greedy determinism, SSE streaming.

A CPU demo: the worker is a child process on the CPU backend (a chip
belongs to one process; `chip_smoke.py` is the single-process path on one).
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

        worker, _ = spawn(
            ["-m", "dynamo_tpu.engine.worker", "--hub", hub_addr,
             "--model", "tiny-test", "--page-size", "4", "--num-pages", "256",
             "--max-pages-per-seq", "32", "--max-decode-slots", "4"],
            "ENGINE_READY",
        )
        procs.append(worker)
        print(f"[demo] JAX engine worker up (pallas="
              f"{ENV.get('DYNAMO_PALLAS', 'auto')})")

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
            print(f"[demo] models: {[m['id'] for m in models]}")
            if not models:
                print("[demo] FAIL: no models discovered")
                return 1

            payload = {
                "model": "tiny-test",
                "messages": [{"role": "user", "content": "hello tpu"}],
                "max_tokens": 6, "temperature": 0.0, "ignore_eos": True,
            }
            async with sess.post(f"{base}/v1/chat/completions", json=payload) as r:
                assert r.status == 200, await r.text()
                body1 = await r.json()
            async with sess.post(f"{base}/v1/chat/completions", json=payload) as r:
                body2 = await r.json()
            c1 = body1["choices"][0]["message"]["content"]
            c2 = body2["choices"][0]["message"]["content"]
            print(f"[demo] greedy chat x2: {c1!r} / {c2!r} "
                  f"usage={body1['usage']}")
            ok &= body1["usage"]["completion_tokens"] == 6
            ok &= c1 == c2  # greedy + prefix cache must be deterministic

            n_chunks = 0
            async with sess.post(
                f"{base}/v1/chat/completions",
                json={**payload, "stream": True},
            ) as r:
                async for line in r.content:
                    line = line.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        n_chunks += 1
            print(f"[demo] streamed chat: {n_chunks} SSE chunks")
            ok &= n_chunks >= 6

            async def one(i):
                async with sess.post(
                    f"{base}/v1/completions",
                    json={"model": "tiny-test", "prompt": f"req number {i}",
                          "max_tokens": 4, "ignore_eos": True},
                ) as r:
                    return r.status

            statuses = await asyncio.gather(*(one(i) for i in range(5)))
            print(f"[demo] 5 concurrent completions: {statuses}")
            ok &= set(statuses) == {200}
    finally:
        for p in procs:
            p.terminate()
    print("[demo] PASS" if ok else "[demo] FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
