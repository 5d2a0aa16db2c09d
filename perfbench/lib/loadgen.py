"""The load generator: a child process that never imports JAX.

The parent holds the chip and serves HTTP from its event loop; this process
sends the plan's requests over real HTTP and SSE and timestamps every
chunk, so the generator's interpreter is not the engine's. Both use
``time.monotonic()`` (CLOCK_MONOTONIC, one clock for every process of the
machine), so the parent can hand over the instant the window opens.

Protocol: the parent starts ``python loadgen.py`` early (interpreter and
aiohttp start-up then overlap the engine's build), and later writes ONE
JSON line to its standard input::

    {"url": ..., "model": ..., "plan": <path>, "out": <path>,
     "t0": <monotonic instant the window opens>, "overhead_tokens": n}

The child runs the plan, writes the records to ``out`` as JSON and exits 0.
Latencies are timed from the instant a request was DUE, every chunk that
carries a choice is one timestamp, tokens are read from the response's usage.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import sys
import time

import aiohttp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib.traffic import content_for  # noqa: E402


async def _one(sess, job: dict, req: dict, due_abs: float | None) -> dict:
    """Send one request and record it. Instants are absolute here and made
    relative to the window by the caller."""
    body = {
        "model": job["model"],
        "messages": [{
            "role": "user",
            "content": content_for(req, job["overhead_tokens"]),
        }],
        "max_tokens": req["max_tokens"],
        "temperature": job.get("temperature", 0.0),
        "ignore_eos": True,  # random weights may pick the EOS id
        "stream": True,
        "stream_options": {"include_usage": True},
    }
    rec = {
        "id": req["id"], "max_tokens": req["max_tokens"],
        "want_prompt_tokens": req["prompt_tokens"],
        "windowed": bool(req.get("windowed")), "ok": False,
        "chunks": [], "sent": None, "due": due_abs, "error": None,
        "completion_tokens": None, "prompt_tokens": None, "finish": None,
    }
    if due_abs is not None:
        delay = due_abs - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
    rec["sent"] = time.monotonic()
    if due_abs is None:
        rec["due"] = rec["sent"]
    closed = False
    try:
        async with sess.post(
            job["url"] + "/v1/chat/completions", json=body
        ) as resp:
            if resp.status != 200:
                rec["error"] = f"http {resp.status}"
                return rec
            async for line in resp.content:
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic()
                data = line[6:].strip()
                if data == b"[DONE]":
                    closed = True
                    continue
                event = json.loads(data)
                if event.get("usage"):
                    rec["completion_tokens"] = event["usage"]["completion_tokens"]
                    rec["prompt_tokens"] = event["usage"]["prompt_tokens"]
                choices = event.get("choices")
                if not choices:
                    continue
                if choices[0].get("finish_reason"):
                    rec["finish"] = choices[0]["finish_reason"]
                # one timestamp a chunk with a choice: the frontend sends
                # one for every delta of the engine, and leaves "content"
                # out where the tokens render as no text (random weights
                # mostly pick ids the mock tokenizer cannot print)
                rec["chunks"].append(now)
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    rec["ok"] = (
        closed and rec["completion_tokens"] == req["max_tokens"]
        and rec["finish"] == "length" and bool(rec["chunks"])
    )
    if not rec["ok"] and rec["error"] is None:
        rec["error"] = (
            f"closed={closed} tokens={rec['completion_tokens']} "
            f"finish={rec['finish']} chunks={len(rec['chunks'])}"
        )
    return rec


async def _open_loop(sess, job: dict, plan: dict) -> list[dict]:
    """Every request at its due instant, whatever the server does. Once
    every windowed request has ended, the tail is dropped."""
    t0 = job["t0"]
    tasks = {
        asyncio.ensure_future(_one(sess, job, r, t0 + r["due"])): r
        for r in plan["requests"]
    }
    must = [t for t, r in tasks.items() if not r["due"] >= plan["seconds"]]
    await asyncio.gather(*must)
    done = []
    for t in tasks:
        if t.done():
            done.append(t.result())
        else:
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return done


def cycling(pool: list[dict]):
    """The pool in its seeded order, again and again: a window then serves
    nearly the whole multiset whatever the seed. A request taken again gets
    new content, so it hits no cached prefix."""
    cycle = 0
    while True:
        for req in pool:
            if cycle:
                req = dict(
                    req, id=f"{req['id']}.{cycle}",
                    content_seed=req["content_seed"] + cycle * (1 << 48),
                )
            yield req
        cycle += 1


async def _closed_loop(sess, job: dict, plan: dict) -> list[dict]:
    """``clients`` callers, each sending its next request as the last one
    completes, from the lead-in until the window has closed."""
    t0 = job["t0"]
    start = t0 - plan["lead_in_s"]
    end = t0 + plan["seconds"]
    pool = plan["requests"]
    records: list[dict] = []
    clients = plan["clients"]
    source = cycling(pool)

    async def client(i: int) -> None:
        # the callers start spread over a quarter of the lead-in, so the
        # first prefills do not all fall into one step
        await asyncio.sleep(
            max(0.0, start + 0.25 * plan["lead_in_s"] * i / clients
                - time.monotonic())
        )
        while time.monotonic() < end:
            rec = await _one(sess, job, next(source), None)
            # counts if any of it fell into the window: a chunk, or a
            # failure after the window opened
            rec["windowed"] = any(t0 <= t < end for t in rec["chunks"]) or (
                not rec["ok"] and rec["sent"] >= t0
            )
            records.append(rec)

    await asyncio.gather(*(client(i) for i in range(clients)))
    return records


async def run(job: dict) -> dict:
    with open(job["plan"]) as f:
        plan = json.load(f)
    job["temperature"] = plan.get("temperature", 0.0)
    timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
    conn = aiohttp.TCPConnector(limit=0)
    gc.collect()
    gc.freeze()
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as sess:
        loop = _open_loop if plan["loop"] == "open" else _closed_loop
        records = await loop(sess, job, plan)
    t0 = job["t0"]
    for r in records:
        for key in ("due", "sent"):
            if r[key] is not None:
                r[key] -= t0
        r["chunks"] = [t - t0 for t in r["chunks"]]
    return {"records": records, "ended": time.monotonic() - t0}


def main() -> int:
    line = sys.stdin.readline()
    if not line.strip():
        return 2  # the parent gave up before the window
    job = json.loads(line)
    result = asyncio.run(run(job))
    tmp = job["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, job["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
