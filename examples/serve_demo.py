"""End-to-end serving demo: hub + mock worker fleet + OpenAI frontend,
all as separate OS processes, driven through the HTTP API.

Run: python examples/serve_demo.py
Exercises: model-card discovery, chat + completions (aggregated and SSE),
KV-aware routing, /v1/models, /health, /metrics.

A CPU demo (mock workers as child processes; no accelerator involved).
"""

import asyncio
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": REPO}


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

        mockers, _ = spawn(
            ["-m", "dynamo_tpu.mocker", "--hub", hub_addr, "--num-workers", "3",
             "--speedup-ratio", "100", "--block-size", "8"],
            "MOCKERS_READY",
        )
        procs.append(mockers)
        print("[demo] 3 mock workers up")

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
            # wait for discovery
            for _ in range(100):
                async with sess.get(f"{base}/v1/models") as r:
                    models = (await r.json())["data"]
                if models:
                    break
                await asyncio.sleep(0.1)
            print(f"[demo] models: {[m['id'] for m in models]}")
            if not models:
                print("[demo] FAIL: no models discovered")
                return 1

            # aggregated chat
            async with sess.post(
                f"{base}/v1/chat/completions",
                json={"model": "mock-model",
                      "messages": [{"role": "user", "content": "hello world"}],
                      "max_tokens": 8},
            ) as r:
                body = await r.json()
            usage = body.get("usage", {})
            print(f"[demo] aggregated chat: finish={body['choices'][0]['finish_reason']} "
                  f"usage={usage}")
            ok &= usage.get("completion_tokens") == 8

            # streaming chat
            n_chunks = 0
            async with sess.post(
                f"{base}/v1/chat/completions",
                json={"model": "mock-model",
                      "messages": [{"role": "user", "content": "stream it"}],
                      "max_tokens": 6, "stream": True},
            ) as r:
                async for line in r.content:
                    line = line.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        n_chunks += 1
            print(f"[demo] streamed chat: {n_chunks} SSE chunks")
            ok &= n_chunks >= 6

            # completions
            async with sess.post(
                f"{base}/v1/completions",
                json={"model": "mock-model", "prompt": "abc", "max_tokens": 4},
            ) as r:
                comp = await r.json()
            print(f"[demo] completions: {len(comp['choices'][0]['text'])} chars, "
                  f"finish={comp['choices'][0]['finish_reason']}")

            # health + metrics
            async with sess.get(f"{base}/health") as r:
                health = await r.json()
            print(f"[demo] health: {health['status']} "
                  f"({health['models']['mock-model']['instances']} instances)")
            ok &= health["models"]["mock-model"]["instances"] == 3
            async with sess.get(f"{base}/metrics") as r:
                metrics = await r.text()
            ttft_lines = [l for l in metrics.splitlines()
                          if l.startswith("dynamo_time_to_first_token_seconds_count")]
            print(f"[demo] metrics: {ttft_lines[:1]}")
    finally:
        for p in procs:
            p.terminate()
    print("[demo] PASS" if ok else "[demo] FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
