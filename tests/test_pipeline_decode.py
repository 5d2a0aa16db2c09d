"""Pipelined decode bursts (engine/core.py pipeline_decode): dispatch k+1
device-chained before processing k, so ONE burst is queued behind the
running one. Must be invisible to clients — exact same tokens as the
unpipelined engine, under mixed sampling, mid-burst stops, admission
churn, admissions into a half-full batch, chunked prompts (each chunk
launched behind the burst in flight, as any admission's prefill is: under
load, beside a finishing stream, cancelled between chunks, on an idle
engine, two in a row), cancellation, and page pressure — and no prefill
may ever be launched behind more than one burst."""

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


def _cfg(pipeline: bool, *, num_pages=256, slots=3, **kw) -> EngineConfig:
    return EngineConfig(
        page_size=4, num_pages=num_pages, max_pages_per_seq=32,
        max_decode_slots=slots, prefill_buckets=(16, 32, 64),
        decode_steps_per_dispatch=4, pipeline_decode=pipeline, **kw,
    )


async def _collect(engine, prompt, max_tokens, *, temperature=0.0, seed=None,
                   ignore_eos=True, out=None, ctx=None):
    """The stream's tokens; into ``out`` as they arrive, where one is given
    for others to watch."""
    out = [] if out is None else out
    sampling = {"temperature": temperature}
    if seed is not None:
        sampling["seed"] = seed
    async for item in engine.generate(
        {"token_ids": list(prompt),
         "stop_conditions": {"max_tokens": max_tokens,
                             "ignore_eos": ignore_eos},
         "sampling": sampling},
        ctx or Context(),
    ):
        out.extend(item["token_ids"])
    return out


class _Watch:
    """What the step thread had in flight, seen from outside: ``ahead`` of
    every launch, the bursts in flight at every chunk of a chunked prefill
    (``chunk``), at a chunked request's cancellation (``cancel``) and at a
    stream's end beside an open partial (``finish``), and the bursts left
    in flight at every read of the oldest one, in the order they
    happened."""

    def __init__(self, engine):
        self.engine = engine
        self.log: list[tuple[str, int]] = []
        launch, process = engine._launch, engine._process_burst
        chunk, advance = engine._run_partial_chunk, engine._advance_partial
        finish = engine._finish

        def watched_launch(kind, **counts):
            if kind in ("prefill", "decode"):
                # the count the annotation carries, as the engine gave it
                self.log.append((kind, counts["ahead"]))
                assert counts["ahead"] == len(engine._pipeline)
            return launch(kind, **counts)

        def watched_process(pending):
            self.log.append(("read", len(engine._pipeline)))
            return process(pending)

        def watched_chunk(*args):
            self.log.append(("chunk", len(engine._pipeline)))
            return chunk(*args)

        def watched_advance():
            if engine._partial.waiting.context.is_stopped:
                self.log.append(("cancel", len(engine._pipeline)))
            return advance()

        def watched_finish(*args, **kw):
            if engine._partial is not None:
                self.log.append(("finish", len(engine._pipeline)))
            return finish(*args, **kw)

        engine._launch = watched_launch
        engine._process_burst = watched_process
        engine._run_partial_chunk = watched_chunk
        engine._advance_partial = watched_advance
        engine._finish = watched_finish

    def ahead(self, kind: str) -> list[int]:
        return [n for k, n in self.log if k == kind]


async def _after(n: int, outs: list[list[int]], coro):
    """Start ``coro`` once every stream of ``outs`` has ``n`` tokens."""
    while any(len(o) < n for o in outs):
        await asyncio.sleep(0.002)
    return await coro


async def _churn(engine):
    # more requests than slots -> admission churn + pipeline flushes;
    # budgets not divisible by the burst -> mid-burst length stops;
    # mixed greedy + seeded sampling
    return await asyncio.gather(
        _collect(engine, [5, 9, 13], 11),
        _collect(engine, [7, 11], 6, temperature=0.9, seed=42),
        _collect(engine, [3, 5, 9, 13], 9),
        _collect(engine, [17, 19], 5, temperature=0.7, seed=7),
        _collect(engine, [2, 4, 6], 13),
    )


async def _midstream(engine):
    # the chat cell's regime: two of four slots decode, and prompts
    # arrive one at a time while bursts are in flight — each prefill is
    # launched behind them, unflushed, and its first token feeds the next
    # burst's device chain
    a, b = [], []
    return await asyncio.gather(
        _collect(engine, [5, 9, 13], 61, out=a),
        _collect(engine, [7, 11, 2, 8], 58, out=b),
        _after(9, [a, b], _collect(engine, [3, 5, 9, 13, 4], 19)),
        _after(22, [a, b], _collect(
            engine, [17, 19], 10, temperature=0.8, seed=11)),
        _after(30, [a, b], _collect(engine, [2, 4, 6], 7)),
    )


def _long(n: int, salt: int = 7) -> list[int]:
    return [3 + (salt * i) % 200 for i in range(n)]


async def _chunked(engine):
    # a prompt over the chunk opens a partial prefill while two streams
    # decode: every chunk is launched behind the burst in flight, which
    # is read as in any other cycle
    a, b = [], []
    return await asyncio.gather(
        _collect(engine, [5, 9, 13], 41, out=a),
        _collect(engine, [7, 11, 2, 8], 37, out=b),
        _after(9, [a, b], _collect(engine, _long(27), 14)),
    )


async def _chunked_finish(engine):
    # all three at once: the cold wave admits the two short prompts, the
    # long one opens its partial in the next cycle, and the first stream
    # runs out of budget while six of its eight chunks are still to come.
    # The slot it frees stays free until the partial closes.
    return await asyncio.gather(
        _collect(engine, [5, 9, 13], 10),
        _collect(engine, [7, 11, 2, 8], 57),
        _collect(engine, _long(59), 14),
    )


async def _chunked_cancel(engine):
    # the chunked request is cancelled from the step thread itself, right
    # after its third chunk is launched: the next cycle's advance finds it
    # stopped and hands its pages back with a burst in flight
    ctx = Context()
    run_chunk, n = engine._run_partial_chunk, [0]

    def third_chunk_cancels(*args):
        out = run_chunk(*args)
        n[0] += 1
        if n[0] == 3:
            ctx.stop_generating()
        return out

    engine._run_partial_chunk = third_chunk_cancels
    a, b = [], []
    return await asyncio.gather(
        _collect(engine, [5, 9, 13], 41, out=a),
        _collect(engine, [7, 11, 2, 8], 37, out=b),
        _after(9, [a, b], _collect(engine, _long(59), 14, ctx=ctx)),
        # one admitted after the cancel: its pages may be the freed ones
        _after(30, [a, b], _collect(engine, _long(7, salt=11), 9)),
    )


async def _chunked_idle(engine):
    # nothing decodes: the chunks find the pipeline empty, one a cycle
    return [await _collect(engine, _long(27), 14)]


async def _chunked_twice(engine):
    # two long prompts queue behind each other: the second's partial
    # opens in the cycle after the first closes
    a, b = [], []
    return await asyncio.gather(
        _collect(engine, [5, 9, 13], 61, out=a),
        _collect(engine, [7, 11, 2, 8], 58, out=b),
        _after(9, [a, b], _collect(engine, _long(27), 14)),
        _after(9, [a, b], _collect(
            engine, _long(30, salt=13), 11, temperature=0.8, seed=5)),
    )


async def _chunked_closing(engine):
    # a stream runs out of budget in every cycle the first partial can
    # close in, so the burst read in that cycle frees a slot with the
    # second long prompt waiting: the cycle still admits nothing beside
    # its chunk, and the second partial opens in the next one
    return await asyncio.gather(
        *(_collect(engine, [5 + i, 9, 13], n)
          for i, n in enumerate((9, 13, 17, 21, 25, 29))),
        _collect(engine, _long(27), 14),
        _collect(engine, _long(30, salt=13), 11),
    )


CHUNKED = {"slots": 4, "max_prefill_chunk_tokens": 8}
WORKLOADS = {
    # name: (driver, engine options, tokens wanted of each stream)
    "churn": (_churn, {}, (11, 6, 9, 5, 13)),
    "midstream": (_midstream, {"slots": 4}, (61, 58, 19, 10, 7)),
    "chunked": (_chunked, CHUNKED, (41, 37, 14)),
    "chunked_finish": (_chunked_finish, CHUNKED, (10, 57, 14)),
    "chunked_cancel": (_chunked_cancel, CHUNKED, (41, 37, 0, 9)),
    "chunked_idle": (_chunked_idle, CHUNKED, (14,)),
    "chunked_twice": (_chunked_twice, CHUNKED, (61, 58, 14, 11)),
    "chunked_closing": (_chunked_closing, {**CHUNKED, "slots": 8},
                        (9, 13, 17, 21, 25, 29, 14, 11)),
}
# chunks of 8: the chunks each case's long prompts take
CHUNKS = {"chunked": 4, "chunked_finish": 8, "chunked_cancel": 3,
          "chunked_idle": 4, "chunked_twice": 8, "chunked_closing": 8}


async def _run_workload(name: str, pipeline: bool):
    drive, opts, _ = WORKLOADS[name]
    engine = InferenceEngine(SPEC, _cfg(pipeline, **opts))
    watch = _Watch(engine)
    await engine.start()
    try:
        outs = await drive(engine)
        assert engine.allocator.active_pages == 0
        return outs, watch
    finally:
        await engine.close()


@pytest.mark.parametrize("name", list(WORKLOADS))
async def test_pipelined_matches_unpipelined_exactly(name):
    want, plain = await _run_workload(name, False)
    got, watch = await _run_workload(name, True)
    assert got == want
    assert tuple(len(o) for o in got) == WORKLOADS[name][2]
    assert set(plain.ahead("prefill")) | set(plain.ahead("decode")) == {0}
    # one burst queued behind the running one, never two: a prompt's
    # prefill waits for one burst at most
    assert max(watch.ahead("prefill")) <= 1
    assert max(watch.ahead("decode")) == 1
    if name == "midstream":
        # those prefills really were launched behind a burst in flight
        assert watch.ahead("prefill").count(1) >= 3
    if name not in CHUNKS:
        return
    # a chunked prefill rides the pipeline: no flush for it, so under load
    # every chunk finds the one burst in flight, and the engine's counter
    # says what the launches carried
    chunks = watch.ahead("chunk")
    assert len(chunks) == len(plain.ahead("chunk")) == CHUNKS[name]
    assert set(plain.ahead("chunk")) == {0}
    assert watch.engine.chunked_prefill == {
        "chunks": len(chunks), "chunks_behind_burst": chunks.count(1),
    }
    if name == "chunked_idle":
        # nothing in flight and none invented: the prompt's chunks and
        # the first burst after them find the device drained
        assert set(chunks) == {0} and set(watch.ahead("prefill")) == {0}
        assert watch.ahead("decode")[0] == 0
    else:
        assert set(chunks) == {1}
        # one chunk a cycle and no admission beside it, the cycle that
        # closes a partial included: a burst is dispatched between any two
        # chunks (an admission pass behind the last chunk would open the
        # next partial there and run its second chunk with none between)
        beats = [k for k, _n in watch.log if k in ("chunk", "decode")]
        assert ("chunk", "chunk") not in set(zip(beats, beats[1:]))
    if name == "chunked_finish":
        # a stream ended beside the open partial, in both engines
        assert watch.ahead("finish") and plain.ahead("finish")
    if name == "chunked_cancel":
        # its pages went back under a burst in flight
        assert watch.ahead("cancel") == [1] and plain.ahead("cancel") == [0]


async def test_one_burst_in_flight_whenever_the_oldest_is_read():
    """Steady decode: every burst but the first is dispatched behind one
    burst, and every read that follows a dispatch leaves one burst in
    flight (by construction the device is never left without queued work);
    only the drain at the stream's end reads with nothing behind it."""
    engine = InferenceEngine(SPEC, _cfg(True, slots=2))
    watch = _Watch(engine)
    await engine.start()
    try:
        outs = await asyncio.gather(
            _collect(engine, [5, 9, 13], 49), _collect(engine, [7, 11], 49))
    finally:
        await engine.close()
    assert [len(o) for o in outs] == [49, 49]
    log = [e for e in watch.log if e[0] != "prefill"]
    assert watch.ahead("decode")[0] == 0
    assert set(watch.ahead("decode")[1:]) == {1}
    after_dispatch = [b for a, b in zip(log, log[1:])
                      if a[0] == "decode" and b[0] == "read"]
    assert len(after_dispatch) >= 10
    assert {n for _k, n in after_dispatch} == {1}
    drains = [b for a, b in zip(log, log[1:])
              if a[0] == "read" and b[0] == "read"]
    assert {n for _k, n in drains} <= {0}
    assert log[-1] == ("read", 0) and not engine._pipeline


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


async def test_eager_readmission_fills_slot_in_same_cycle():
    """A finished slot's replacement must start its prefill in the SAME
    step cycle that processed the finishing burst, not wait for the next
    admission pass. With one slot, B can only enter through the eager
    path the moment A's burst finishes: the engine counts those passes."""
    engine = InferenceEngine(SPEC, _cfg(True, num_pages=64, slots=1))
    await engine.start()
    outs = await asyncio.gather(
        _collect(engine, [7, 11, 19], 6), _collect(engine, [5, 13, 23], 6),
    )
    assert [len(o) for o in outs] == [6, 6]
    assert engine.eager_readmits >= 1
    assert engine.allocator.active_pages == 0
    await engine.close()
