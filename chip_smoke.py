#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the serving path starts on the chip.

Run from the root of a plain copy of the tree (no git, no network):

    python chip_smoke.py             one TPU chip
    python chip_smoke.py --chips 4   the tp=4 path against tp=1, nothing else

It builds, in THIS one process (a chip belongs to one process at a time), the
aggregated stack a user starts with ``python -m dynamo_tpu.cli run --in http
--out engine``: in-memory hub, ``launch_engine_worker`` with ``precompile=True``,
the OpenAI HTTP frontend. The model is Llama-3-8B at its full widths
(``ModelSpec.llama3_8b``) with depth cut to LAYERS so one 16 GB chip holds the
weights beside the default 2,048-page cache; the weights are
``init_params`` from the engine seed. Then it

1. sends requests over real HTTP and checks every count,
2. checks the device did the work the design says (no fused-kernel fallback
   counted, no compile after precompile, no refused shape in the precompile
   report, a guided vocabulary of 128,256 entries built),
3. compares the engine's prefill logits with the plain ``reference_forward``
   on one prompt, and one decode step through the fused Pallas kernel with
   the same step through the XLA gather path, bf16 and fp8 pools,
4. runs the latent family's prefill attention at JoyAI-LLM-Flash's widths
   through its Mosaic kernel and through the XLA walk, same operands, and
   prints each one's time a call.

Every timing it prints is a smoke timing, not a metric. The last line of
standard output is ``{"ok": true, "device": {...}}`` on success; any failure
exits non-zero without it. It never sets or overrides the JAX platform, and it
sets no compile-cache path (engine/compile_cache.py owns that).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import time

import aiohttp
import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.compile_cache import cache_snapshot, compile_snapshot
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.engine.worker import launch_engine_worker
from dynamo_tpu.frontend.http import HttpFrontend
from dynamo_tpu.frontend.watcher import ModelManager, ModelWatcher
from dynamo_tpu.models import llama
from dynamo_tpu.ops.attention import decode_update_attention
from dynamo_tpu.ops.fallback import REGISTRY as FALLBACK_REGISTRY
from dynamo_tpu.ops.quant import QuantPool, quant_page_tiles
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.hub import InMemoryHub

LAYERS = 16  # of Llama-3-8B's 32: ~9.1 GB of bf16 weights + 2 GiB of cache
MODEL_NAME = "llama-3-8b-smoke"

# |delta| bounds, as a share of the reference logits' largest magnitude.
# bf16 keeps 8 bits of mantissa; through LAYERS layers two correct bf16
# programs that differ in reduction order drift by a percent or two.
PREFILL_VS_REFERENCE_TOL = 0.05
PALLAS_VS_XLA_TOL = 0.05
TP4_VS_TP1_TOL = 0.05
FP8_ATTN_TOL = 0.05  # fused fp8 kernel against the XLA quantized path
# greedy tokens tp=4 and tp=1 must share per stream, unless they part
# earlier at a tie of tp=1's own bf16 logits (streams_agree)
TP_LEADING_TOKENS = 4

_A = "The quick brown fox jumps over the lazy dog. "
REQUESTS = (
    # name, content, max_tokens, stream — contents differ from the first
    # character on, so no request rides another's cached prefix; 29
    # template tokens + one per byte puts "short" in the 128 bucket and
    # "long" in the 512 bucket
    ("plain", "alpha " + _A, 32, False),
    ("sse", "bravo " + _A * 7, 40, True),
    ("c0", "charlie " + _A, 32, False),
    ("c1", "delta " + _A * 6, 32, True),
    ("c2", "echo " + _A * 2, 33, True),
    ("c3", "foxtrot " + _A * 7, 32, False),
)
CONCURRENT_FROM = 2  # REQUESTS[2:] go out together


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info(chips: int) -> dict:
    """The device as JAX reports it; fails unless it is a TPU."""
    devs = jax.devices()
    check(
        devs[0].platform == "tpu",
        f"JAX found no TPU (platform {devs[0].platform!r}): the smoke "
        "proves the chip path and does not fall back",
    )
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def smoke_spec():

    return dataclasses.replace(
        ModelSpec.llama3_8b(), num_layers=LAYERS, name=MODEL_NAME
    )


def tree_bytes(tree) -> int:

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def memory_line(device) -> str:
    stats = device.memory_stats()
    return (
        f"bytes_in_use {gib(stats['bytes_in_use'])}, peak_bytes_in_use "
        f"{gib(stats['peak_bytes_in_use'])} of {gib(stats['bytes_limit'])}"
    )


# ------------------------------------------------------------ the stack


@dataclasses.dataclass
class Stack:
    drt: object
    engine: object
    watcher: object
    frontend: object
    base: str


@contextlib.contextmanager
def tapped_engine_streams():
    """Record every stream the engine serves while the block runs, as
    ``{prompt token ids: [token ids of each delta]}``. The HTTP surface
    carries text and counts, and the mock tokenizer drops ids past its
    272 from the text, so token ids are read here, in process, off the
    same generator the endpoint serves."""
    real = InferenceEngine.generate
    streams: dict = {}

    async def generate(self, request, context):
        out = streams.setdefault(tuple(request["token_ids"]), [])
        async for item in real(self, request, context):
            out.append(list(item.get("token_ids") or ()))
            yield item

    InferenceEngine.generate = generate
    try:
        yield streams
    finally:
        InferenceEngine.generate = real


async def start_stack(spec, cfg, *, precompile: bool = True) -> Stack:
    """What ``cli._arun`` wires for ``run --in http --out engine``, with
    the spec handed to ``launch_engine_worker`` directly."""
    drt = DistributedRuntime(InMemoryHub())
    engine, _ = await launch_engine_worker(
        drt, spec=spec, engine_config=cfg, precompile=precompile
    )
    manager = ModelManager()
    watcher = await ModelWatcher(drt, manager).start()
    await watcher.wait_for_model(spec.name, timeout=30)
    frontend = HttpFrontend(manager, host="127.0.0.1", port=0, drt=drt)
    host, port = await frontend.start()
    return Stack(drt, engine, watcher, frontend, f"http://{host}:{port}")


async def stop_stack(stack: Stack) -> None:
    """Frontend, watcher, engine thread and hub down, so the process can
    exit; the engine object keeps its weights for the later checks."""
    await stack.frontend.stop()
    await stack.watcher.close()
    await stack.engine.close()
    await stack.drt.close()


# ------------------------------------------------------- HTTP requests


async def _one_request(sess, stack: Stack, name, content, max_tokens, stream):
    body = {
        "model": stack.engine.spec.name,
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0,
        # random weights may well pick the tokenizer's EOS id
        "ignore_eos": True,
    }
    if stream:
        body["stream"] = True
        body["stream_options"] = {"include_usage": True}
    t0 = time.perf_counter()
    async with sess.post(
        f"{stack.base}/v1/chat/completions", json=body
    ) as r:
        raw = await r.read()
        status = r.status
    wall = time.perf_counter() - t0
    check(status == 200, f"{name}: HTTP {status}: {raw[:300]!r}")
    if stream:
        events = [
            json.loads(e[6:]) for e in raw.decode().split("\n\n")
            if e.startswith("data: ") and e[6:] != "[DONE]"
        ]
        check(raw.endswith(b"data: [DONE]\n\n"), f"{name}: stream not closed")
        with_choice = [e for e in events if e.get("choices")]
        finish = [
            e["choices"][0]["finish_reason"] for e in with_choice
            if e["choices"][0].get("finish_reason")
        ]
        usage = next(e["usage"] for e in events if e.get("usage"))
        chunks = len(with_choice)
    else:
        data = json.loads(raw)
        usage = data["usage"]
        finish = [data["choices"][0]["finish_reason"]]
        chunks = None
    check(
        usage["completion_tokens"] == max_tokens,
        f"{name}: completion_tokens {usage['completion_tokens']}, "
        f"want {max_tokens}",
    )
    check(finish == ["length"], f"{name}: finish_reason {finish}")
    return {
        "name": name, "stream": stream, "max_tokens": max_tokens,
        "prompt_tokens": usage["prompt_tokens"], "wall_s": round(wall, 3),
        "chunks": chunks,
    }


async def drive_requests(
    stack: Stack, streams: dict, requests=REQUESTS
) -> list[dict]:
    """One plain, one streamed, then the rest together; every response
    checked by its counts, and every stream's token ids by the tap."""
    cfg = stack.engine.config
    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as sess:
        results = [
            await _one_request(sess, stack, *req)
            for req in requests[:CONCURRENT_FROM]
        ]
        results += await asyncio.gather(*(
            _one_request(sess, stack, *req)
            for req in requests[CONCURRENT_FROM:]
        ))
    check(
        len(streams) == len(requests),
        f"{len(streams)} engine streams for {len(requests)} requests",
    )
    by_len = {len(p): deltas for p, deltas in streams.items()}
    check(len(by_len) == len(requests), "two prompts of one length")
    for res in results:
        deltas = by_len[res["prompt_tokens"]]
        ids = [t for d in deltas for t in d]
        # the frontend sends one SSE chunk per engine delta, and a delta
        # is one slot's share of a decode burst: every delta must arrive,
        # none merged or lost (one chunk per token at single-step bursts)
        check(
            res["chunks"] in (None, len(deltas)),
            f"{res['name']}: {res['chunks']} SSE chunks for "
            f"{len(deltas)} engine deltas",
        )
        check(
            len(ids) == res["max_tokens"]
            and all(0 <= t < stack.engine.spec.vocab_size for t in ids),
            f"{res['name']}: bad token ids from the engine: {ids}",
        )
        res["bucket"] = cfg.bucket_for(res["prompt_tokens"])
        res["token_ids"] = ids
        first_page = -(-res["prompt_tokens"] // cfg.page_size)
        last_page = -(-(res["prompt_tokens"] + len(ids)) // cfg.page_size)
        check(
            last_page > first_page,
            f"{res['name']}: decode crossed no page boundary",
        )
    check(
        len({r["bucket"] for r in results}) >= 2,
        "prompts hit fewer than two prefill buckets",
    )
    return results


def fallback_series() -> dict:
    """``dynamo_fused_fallback_total`` by reason, as /metrics renders it."""
    out = {}
    for line in FALLBACK_REGISTRY.exposition().decode().splitlines():
        if line.startswith("dynamo_fused_fallback_total{"):
            series, value = line.rsplit(" ", 1)
            out[series] = float(value)
    return out


async def serve_phase(spec, cfg, requests=REQUESTS) -> dict:
    """Build the stack, precompile, serve the requests, take the two
    readings (fallback counter, compile counter), shut down. Checks
    everything that holds on any backend; ``check_device_path`` adds what
    only holds on the chip."""
    t0 = time.perf_counter()
    cache0 = cache_snapshot()
    with tapped_engine_streams() as streams:
        stack = await start_stack(spec, cfg, precompile=True)
        try:
            build_s = time.perf_counter() - t0
            engine = stack.engine
            report = engine.precompile_report
            refused = {
                k: v["error"] for k, v in report.items() if "error" in v
            }
            check(not refused, f"precompile refused shapes: {refused}")
            check(bool(report), "precompile() reported nothing")
            cache1 = cache_snapshot()
            compiles_ready = compile_snapshot()[0]
            results = await drive_requests(stack, streams, requests)
            compiles_done = compile_snapshot()[0]
            fallbacks = fallback_series()
        finally:
            await stop_stack(stack)
    check(
        compiles_done == compiles_ready,
        f"{compiles_done - compiles_ready} compile(s) on the request path: "
        "a shape precompile() does not walk",
    )
    return {
        "engine": engine, "results": results,
        # {prompt token ids: generated token ids}
        "streams": {
            p: [t for d in deltas for t in d] for p, deltas in streams.items()
        },
        "precompile": report, "build_s": build_s, "fallbacks": fallbacks,
        "cache_lookups": cache1[0] - cache0[0],
        "cache_hits": cache1[1] - cache0[1],
    }


def check_device_path(phase: dict, *, guided: bool) -> None:
    check(
        not phase["fallbacks"],
        f"fused-kernel fallbacks were counted: {phase['fallbacks']}",
    )
    if guided:
        check(
            phase["engine"]._guided is not None,
            "guided decoding is off: the token vocabulary did not build",
        )


def print_phase(phase: dict, label: str) -> None:
    report = phase["precompile"]
    say(f"[{label}] build + precompile: {phase['build_s']:.1f} s wall")
    for name, rec in report.items():
        say(f"[{label}]   precompile {name}: {rec['secs']:.2f} s, "
            f"{rec['compiles']} compile(s)")
    total = sum(r["secs"] for r in report.values())
    lookups, hits = phase["cache_lookups"], phase["cache_hits"]
    say(f"[{label}] precompile total: {total:.1f} s over {len(report)} shapes")
    warmth = (
        "warm" if lookups and hits == lookups
        else "partly warm" if hits else "cold"
    )
    say(f"[{label}] compile cache: {hits} of {lookups} lookups hit: {warmth}")
    for r in phase["results"]:
        say(f"[{label}] smoke timing (not a metric): {r['name']:5s} "
            f"stream={r['stream']!s:5s} prompt={r['prompt_tokens']} "
            f"(bucket {r['bucket']}) out={r['max_tokens']} "
            f"wall={r['wall_s']} s first_ids={r['token_ids'][:4]}")


# ----------------------------------------------------- numeric checks


def engine_prefill_logits(engine, token_ids: list[int]):
    """Last-position logits of one prompt through the engine's own
    precompiled prefill program, on its live weights and pools (the
    engine must be closed: the call donates the pools)."""
    cfg = engine.config
    n = len(token_ids)
    bucket = cfg.bucket_for(n)
    tokens = np.zeros((bucket,), np.int32)
    tokens[:n] = token_ids
    table = np.zeros((cfg.max_pages_per_seq,), np.int32)
    pages = -(-n // cfg.page_size)
    table[:pages] = np.arange(1, pages + 1)
    logits, engine.k_pages, engine.v_pages, _ = engine.fam.prefill(
        engine.spec, engine.params, jnp.asarray(tokens), jnp.asarray(table),
        jnp.asarray(0, jnp.int32), engine.k_pages, engine.v_pages,
        jnp.asarray(n, jnp.int32), mesh=engine.mesh,
    )
    return np.asarray(logits, np.float32)


def compare_logits(got, want, tol: float, what: str) -> float:

    check(got.shape == want.shape, f"{what}: shape {got.shape} vs {want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite logits")
    scale = float(np.abs(want).max())
    diff = float(np.abs(got - want).max())
    say(f"{what}: max |delta| {diff:.4f} = {diff / scale:.4f} of the "
        f"reference's largest |logit| {scale:.3f} (bound {tol})")
    check(diff <= tol * scale, f"{what}: logits differ beyond {tol}")
    return diff


def prefill_vs_reference(phase: dict) -> None:
    """The engine's paged, bucketed prefill against the repo's plain
    full-attention ``reference_forward`` on the shortest prompt served;
    the stream's first token must sit at the reference's maximum."""
    engine = phase["engine"]
    prompt = min(phase["streams"], key=len)
    got = engine_prefill_logits(engine, list(prompt))
    ref = jax.jit(lambda p, t: llama.reference_forward(engine.spec, p, t))(
        engine.params, jnp.asarray(prompt, jnp.int32)
    )
    want = np.asarray(ref[-1], np.float32)
    diff = compare_logits(
        got, want, PREFILL_VS_REFERENCE_TOL,
        f"prefill vs reference_forward ({len(prompt)} tokens)",
    )
    first = phase["streams"][prompt][0]
    check(
        want[first] >= want.max() - 2 * max(diff, 1e-3),
        f"served first token {first} is not at the reference's maximum "
        f"({want[first]:.4f} vs {want.max():.4f})",
    )


def _decode_inputs(spec, page_size: int, pages_per_seq: int, batch: int,
                   seed: int):
    """Random pools + one decode step's arguments: contexts from one token
    to a full table, one of them ending exactly on a page boundary."""
    num_pages = 1 + batch * pages_per_seq
    shape = (spec.num_layers, num_pages, spec.num_kv_heads, page_size,
             spec.head_dim)
    kk, kv, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pages = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pages = jax.random.normal(kv, shape, jnp.bfloat16)
    tokens = jax.random.randint(kt, (batch,), 0, spec.vocab_size, jnp.int32)
    cap = page_size * pages_per_seq
    lens = [1, page_size, page_size + 1, cap // 3, cap // 2, cap - page_size,
            cap - 1, cap]
    lens = (lens * -(-batch // len(lens)))[:batch]
    tables = 1 + np.arange(batch * pages_per_seq, dtype=np.int32).reshape(
        batch, pages_per_seq
    )
    return (
        tokens, jnp.asarray(tables), jnp.asarray(lens, jnp.int32), k_pages,
        v_pages, jnp.ones((batch,), bool),
    )


@contextlib.contextmanager
def _xla_attention():
    """The existing DYNAMO_PALLAS switch, read at trace time: the decode
    step traced inside takes write_new_kv's scatter and
    paged_decode_attention's gather."""
    was = os.environ.get("DYNAMO_PALLAS")
    os.environ["DYNAMO_PALLAS"] = "0"
    try:
        yield
    finally:
        if was is None:
            del os.environ["DYNAMO_PALLAS"]
        else:
            os.environ["DYNAMO_PALLAS"] = was


def pallas_vs_xla_decode(engine) -> None:
    """One decode step of the served model through the fused Pallas
    kernel and through the XLA scatter + gather composition, same inputs."""
    spec, cfg = engine.spec, engine.config
    args = _decode_inputs(
        spec, cfg.page_size, cfg.max_pages_per_seq, cfg.max_decode_slots,
        cfg.seed + 1,
    )

    def step(params, *a):  # a fresh function per path: its own jit cache
        return llama.decode_forward_impl(spec, params, *a)[0]

    fused = np.asarray(jax.jit(step)(engine.params, *args), np.float32)
    with _xla_attention():
        xla = np.asarray(
            jax.jit(lambda p, *a: step(p, *a))(engine.params, *args),
            np.float32,
        )
    compare_logits(
        fused, xla, PALLAS_VS_XLA_TOL,
        f"decode step, fused Pallas vs XLA gather "
        f"(B={len(args[0])}, {cfg.max_context}-token tables)",
    )


def fp8_fused_vs_xla(spec, page_size: int, pages_per_seq: int) -> None:
    """The fp8 fused kernel (never lowered for a chip before) against the
    XLA quantized append + gather/dequant attention, same inputs: the
    attention output within a bound, and the pages both paths wrote back
    equal after dequantisation to within one fp8 step."""
    two = dataclasses.replace(spec, num_layers=2)
    batch = 8
    _, tables, lens, k_pages, v_pages, _ = _decode_inputs(
        two, page_size, pages_per_seq, batch, seed=7
    )

    def quantize(pool):
        flat = pool.reshape((-1,) + pool.shape[2:])
        vals, scale = quant_page_tiles(flat, True, (2, 3))
        return QuantPool(
            vals.reshape(pool.shape), scale.reshape(pool.shape[:3])
        )

    kq, vq = quantize(k_pages), quantize(v_pages)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    H, KH, D = two.num_heads, two.num_kv_heads, two.head_dim
    q = jax.random.normal(keys[0], (batch, H, D), jnp.bfloat16)
    k_new = jax.random.normal(keys[1], (batch, KH, D), jnp.bfloat16)
    v_new = jax.random.normal(keys[2], (batch, KH, D), jnp.bfloat16)
    pos = lens - 1
    dst_page = jnp.take_along_axis(
        tables, (pos // page_size)[:, None], axis=1
    )[:, 0]
    dst_off = pos % page_size

    def run(q, kq, vq):
        return decode_update_attention(
            q, kq, vq, k_new, v_new, tables, lens, dst_page, dst_off, layer=1
        )

    attn_f, kf, vf = jax.jit(run)(q, kq, vq)
    with _xla_attention():
        attn_x, kx, vx = jax.jit(lambda *a: run(*a))(q, kq, vq)
    attn_f = np.asarray(attn_f, np.float32)
    attn_x = np.asarray(attn_x, np.float32)
    check(bool(np.isfinite(attn_f).all()), "fp8 fused kernel: non-finite output")
    scale = float(np.abs(attn_x).max())
    diff = float(np.abs(attn_f - attn_x).max())
    say(f"fp8 fused kernel vs XLA quantized path: attention max |delta| "
        f"{diff:.4f} = {diff / scale:.4f} of the largest |value| {scale:.3f} "
        f"(bound {FP8_ATTN_TOL})")
    check(diff <= FP8_ATTN_TOL * scale, "fp8 attention differs beyond bound")
    for name, a, b in (("K", kf, kx), ("V", vf, vx)):
        sa = np.asarray(a.scale[1, dst_page], np.float32)
        sb = np.asarray(b.scale[1, dst_page], np.float32)
        check(bool((sa == sb).all()), f"fp8 {name}: grown scales differ")
        pa = np.asarray(a.vals[1, dst_page].astype(jnp.float32))
        pb = np.asarray(b.vals[1, dst_page].astype(jnp.float32))
        bits = float((pa != pb).mean())
        # e4m3 keeps 3 mantissa bits: neighbours differ by at most 1/8
        worst = float(np.abs(pa - pb).max() / max(np.abs(pb).max(), 1e-9))
        say(f"fp8 {name} pages written back: {bits:.6f} of values differ, "
            f"worst by {worst:.4f} of the largest")
        check(worst <= 0.125, f"fp8 {name}: written pages differ by > 1 step")


# ------------------------------------------------- latent prefill kernel

# JoyAI-LLM-Flash's attention widths as its cell runs them (perfbench/
# configs/joyai-llm-flash.json): 32 heads, K 128 + 64 roped, V 128, latent
# 512, rows of 576 values in 640 lanes, pages of 64 tokens, 160-page tables
LATENT = dict(H=32, dn=128, dr=64, dv=128, dc=512, lanes=640, page=64,
              pages_per_seq=160, rows=1024)
LATENT_KERNEL_VS_TWIN_TOL = 0.02  # same types; reduction order differs
# (start_pos, real rows) a member: a fresh chunk, chunks resumed at 1,024
# and 7,168, a pack of two of unequal length
LATENT_CASES = (((0, 1024),), ((1024, 1024),), ((7168, 1024),),
                ((0, 1024), (0, 600)))


def latent_prefill_inputs(members, seed: int = 5):
    """Random operands of one ``latent_prefill_attention`` call at the
    published widths: each member's context written to pages of its own
    (a shuffled table), the rest of the pool left as drawn."""
    w = LATENT
    N, T, P, page = len(members), w["rows"], w["pages_per_seq"], w["page"]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf16 = jnp.bfloat16
    pool = jax.random.normal(
        keys[0], (2, 1 + N * P, page, w["dc"] + w["dr"]), bf16)
    pool = jnp.pad(pool, [(0, 0)] * 3 + [(0, w["lanes"] - pool.shape[-1])])
    tables = 1 + jax.random.permutation(keys[1], N * P).reshape(N, P)
    H, dc = w["H"], w["dc"]
    return (
        jax.random.normal(keys[2], (N, T, H, w["dn"]), bf16),
        jax.random.normal(keys[3], (N, T, H, w["dr"]), bf16),
        pool, jnp.asarray(1, jnp.int32),
        (jax.random.normal(keys[4], (H, dc, w["dn"])) * dc ** -0.5).astype(bf16),
        (jax.random.normal(keys[5], (H, dc, w["dv"])) * dc ** -0.5).astype(bf16),
        tables.astype(jnp.int32),
        jnp.asarray([m[0] for m in members], jnp.int32),
        jnp.asarray([m[0] + m[1] for m in members], jnp.int32),
    )


LATENT_CHAIN = 8  # attention calls a timed program holds


def ms_a_call(attend, q_nope, *rest, repeats: int = 5):
    """(milliseconds an attention call, one call's result). A call alone
    is as long as its dispatch from Python, so the timed program chains
    ``LATENT_CHAIN`` calls as a model's layers do (each one's first query
    takes a zero from the result before it), compiled and run once, then
    ``repeats`` programs queued and the last waited for."""

    @jax.jit
    def chain(q_nope, *rest):
        def body(_, q):
            out = attend(q, *rest)
            return q.at[0, 0, 0, 0].add(out[0, 0, 0, 0] * 0)

        return jax.lax.fori_loop(0, LATENT_CHAIN, body, q_nope)

    jax.block_until_ready(chain(q_nope, *rest))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = chain(q_nope, *rest)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) * 1e3 / (repeats * LATENT_CHAIN)
    return ms, attend(q_nope, *rest)


def latent_prefill_kernel_vs_twin() -> None:
    """``ops/attention.latent_prefill_attention`` at JoyAI-LLM-Flash's
    widths: the Mosaic kernel against the XLA walk (its twin: the same
    types), same operands, with each one's time a call. Smoke timings of
    two attention calls alone, not a cell's metric."""
    from dynamo_tpu.ops.attention import latent_prefill_attention as attend

    scale = (LATENT["dn"] + LATENT["dr"]) ** -0.5
    call = functools.partial(attend, scale=scale)
    for members in LATENT_CASES:
        args = latent_prefill_inputs(members)
        attend.clear_cache()  # DYNAMO_PALLAS is read when a path is traced
        kernel_ms, got = ms_a_call(call, *args)
        with _xla_attention():
            attend.clear_cache()
            twin_ms, want = ms_a_call(call, *args)
        attend.clear_cache()
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        what = " + ".join(f"{nt} rows at {sp}" for sp, nt in members)
        for n, (_, nt) in enumerate(members):  # padded rows are not held
            compare_logits(
                got[n, :nt], want[n, :nt], LATENT_KERNEL_VS_TWIN_TOL,
                f"latent prefill attention, kernel vs XLA walk ({what}; "
                f"member {n})",
            )
        say(f"latent prefill attention ({what}): kernel {kernel_ms:.3f} ms "
            f"a call, XLA walk {twin_ms:.3f} ms")


# ------------------------------------------------------------ four chips


def streams_agree(engine, prompt, one, four) -> int:
    """tp=4's greedy stream ``four`` against tp=1's ``one`` (``engine``:
    the tp=1 engine, closed). They must share ``TP_LEADING_TOKENS`` tokens.
    A stream may part sooner only at a tie: where tp=1's own logit of
    tp=4's token stands no further under its best than one unit in the
    last place of a bfloat16, which the logits are. An argmax between two
    such tokens is decided by the order of a sum. Returns the tokens
    shared."""
    same = next(
        (i for i, (a, b) in enumerate(zip(one, four)) if a != b), len(one)
    )
    say(f"tp=4 vs tp=1 greedy stream ({len(prompt)}-token prompt): "
        f"first {same} of {len(one)} tokens agree")
    if same < min(TP_LEADING_TOKENS, len(one)):
        logits = engine_prefill_logits(engine, list(prompt) + one[:same])
        best = float(logits.max())
        gap = best - float(logits[four[same]])
        ulp = 2.0 ** (math.floor(math.log2(abs(best))) - 7)
        say(f"  they part at token {same}: tp=1 gives tp=4's choice "
            f"{gap:.5f} less than its best, {best:.4f}, where a bfloat16 "
            f"steps by {ulp:.5f}")
        check(
            gap <= ulp,
            f"streams part after {same} tokens (< {TP_LEADING_TOKENS}) "
            "and not at a tie",
        )
    return same


def shard_report(engine, device) -> tuple[int, int]:
    """(bytes ``device`` should hold, bytes of the whole model): weights
    and pools, what param_shardings / cache_shardings imply — replicated
    leaves counted whole."""
    spec, mesh = engine.spec, engine.mesh
    leaves = jax.tree.leaves((engine.params, engine.k_pages, engine.v_pages))
    whole = sum(x.size * x.dtype.itemsize for x in leaves)
    if mesh is None:
        return whole, whole
    shardings = jax.tree.leaves((
        engine.fam.param_shardings(spec, mesh),
        engine.fam.cache_shardings(mesh, engine.kv_dtype),
    ))
    check(len(shardings) == len(leaves), "sharding tree does not match")
    mine = 0
    for x, s in zip(leaves, shardings):
        check(x.sharding.is_equivalent_to(s, x.ndim),
              f"leaf {x.shape} is not placed as its sharding says")
        if device in s.device_set:
            mine += math.prod(s.shard_shape(x.shape)) * x.dtype.itemsize
    return mine, whole


def resident_bytes(engine, device) -> int:
    """Bytes of weights and pools actually resident on ``device``."""
    leaves = jax.tree.leaves((engine.params, engine.k_pages, engine.v_pages))
    return sum(
        s.data.size * s.data.dtype.itemsize
        for x in leaves for s in x.addressable_shards if s.device == device
    )


def four_chip_config(tp: int):
    """One config for both sides of the comparison: two prefill buckets,
    one burst length, no guided shapes — chip time goes to what exists
    only across chips."""
    return EngineConfig(
        tp=tp, pipeline_decode=True, decode_steps_per_dispatch=8,
        decode_steps_admit_pending=0, prefill_buckets=(128, 512),
        guided_mode="off",
    )


def four_chip_mode() -> None:

    spec = smoke_spec()
    say(f"spec: {spec.name}: Llama-3-8B widths, depth {LAYERS} of 32; "
        "tp=4 on the mesh, then tp=1 on device 0, same seed and prompts")
    dev0 = jax.devices()[0]
    sides = {}
    for tp in (4, 1):  # the mesh first: device 0 must never have held it all
        phase = asyncio.run(serve_phase(spec, four_chip_config(tp)))
        engine = phase["engine"]
        print_phase(phase, f"tp={tp}")
        check_device_path(phase, guided=False)
        want, whole = shard_report(engine, dev0)
        resident = resident_bytes(engine, dev0)
        built = engine.build_memory_stats
        say(f"[tp={tp}] device 0 share of weights + pools: {gib(want)} "
            f"implied by the shardings, {gib(resident)} resident, whole "
            f"model {gib(whole)}; after build bytes_in_use "
            f"{gib(built['bytes_in_use'])}, peak "
            f"{gib(built['peak_bytes_in_use'])}")
        check(resident == want, "device 0 holds other than its shard")
        if tp > 1:
            check(engine.mesh.shape["tp"] == tp, "no tp mesh was built")
            check(
                built["peak_bytes_in_use"] <= 1.5 * want,
                "device 0 peaked well above its shard while the model was "
                "built: parameters were not born sharded",
            )
            check(want < 0.3 * whole, "device 0's shard is not a quarter")
        sides[tp] = {
            "streams": phase["streams"],
            "logits": {
                p: engine_prefill_logits(engine, list(p))
                for p in sorted(phase["streams"], key=len)[:2]
            },
        }
        for d in jax.devices():
            say(f"[tp={tp}] device {d.id}: {memory_line(d)}")
        if tp > 1:
            # free the chips before the other side builds; the tp=1
            # engine stays for the streams' near-tie check
            engine.params = engine.k_pages = engine.v_pages = None
            del engine
        del phase
        gc.collect()
    for prompt, want in sides[1]["logits"].items():
        compare_logits(
            sides[4]["logits"][prompt], want, TP4_VS_TP1_TOL,
            f"tp=4 vs tp=1 prefill logits ({len(prompt)} tokens)",
        )
    for prompt, one in sides[1]["streams"].items():
        streams_agree(engine, prompt, one, sides[4]["streams"][prompt])


# ------------------------------------------------------------- one chip


def one_chip_mode() -> None:


    spec = smoke_spec()
    # what `cli run --out engine` builds, with 8-step decode bursts (and
    # the 4-step short burst) instead of single steps
    cfg = EngineConfig(pipeline_decode=True, decode_steps_per_dispatch=8)
    say(f"spec: {spec.name}: Llama-3-8B widths (hidden {spec.hidden_size}, "
        f"mlp {spec.intermediate_size}, {spec.num_heads} Q / "
        f"{spec.num_kv_heads} KV x {spec.head_dim}, vocab {spec.vocab_size}), "
        f"depth {LAYERS} of 32, random weights from seed {cfg.seed}")
    phase = asyncio.run(serve_phase(spec, cfg))
    engine = phase["engine"]
    say(f"weights {gib(tree_bytes(engine.params))}, cache "
        f"{gib(tree_bytes((engine.k_pages, engine.v_pages)))} "
        f"({cfg.num_pages} pages x {cfg.page_size} tokens, "
        f"{cfg.max_context}-token tables, {cfg.max_decode_slots} slots)")
    say(f"prefill shapes offered (bucket: pack width): "
        f"{engine._prefill_shapes}")
    print_phase(phase, "serve")
    check_device_path(phase, guided=True)
    say(f"guided vocabulary built: {spec.vocab_size} entries")
    say("fused-kernel fallbacks counted: none; compiles after precompile: 0")
    prefill_vs_reference(phase)
    say(f"device memory after serving: {memory_line(jax.devices()[0])}")
    # the served pools make room for the decode-step comparison's own
    engine.k_pages = engine.v_pages = None
    gc.collect()
    pallas_vs_xla_decode(engine)
    fp8_fused_vs_xla(spec, cfg.page_size, cfg.max_pages_per_seq)
    engine.params = None  # 9 GB of weights make room for a latent pool
    gc.collect()
    latent_prefill_kernel_vs_twin()
    say(f"device memory at exit: {memory_line(jax.devices()[0])}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = serve the same spec at tp=4 on a four-chip mesh and at "
             "tp=1 on device 0 and compare them; no other phase",
    )
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        device = device_info(args.chips)
        say(f"device: {device}")
        (four_chip_mode if args.chips == 4 else one_chip_mode)()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(f"smoke wall time: {time.perf_counter() - t0:.0f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
