"""The delivery path's spans and counters (EngineConfig.profile): the step
thread's ``stream.post``, the event loop's ``stream.take``, the flight
recorder's ``delta``, the ``stream`` counters; and the event loop's
heartbeat (runtime/loop_probe.py), which is always on. Toy engine, CPU."""

import asyncio
import glob
import os
import sys
import time
import types

import pytest

import jax

from dynamo_tpu.engine import core
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.engine.telemetry import REGISTRY, EngineCollector
from dynamo_tpu.engine.worker import launch_engine_worker
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.flight import FLIGHT
from dynamo_tpu.runtime.hub import InMemoryHub
from dynamo_tpu.runtime.loop_probe import INTERVAL_S, RING, LoopProbe

pytestmark = pytest.mark.integration

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TODAY = {"token_ids", "finish_reason"}  # an item's keys on the parent


def _cfg(**kw) -> EngineConfig:
    base = dict(
        page_size=4, num_pages=128, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(16, 32), prefill_pack_size=2,
        decode_steps_per_dispatch=2, pipeline_decode=True,
    )
    base.update(kw)
    return EngineConfig(**base)


def _request(i: int, max_tokens: int = 7, base: int = 3) -> dict:
    return {
        "token_ids": [base + (i + j) % 50 for j in range(5 + i % 7)],
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
        "sampling": {"temperature": 0.0},
    }


async def _serve(generate, n, tag, **kw) -> list[list[dict]]:
    """``n`` concurrent streams through ``generate``; every item of each."""
    async def one(i):
        return [item async for item in generate(
            _request(i, **kw), Context(f"{tag}-{i}"))]

    return await asyncio.gather(*(one(i) for i in range(n)))


def _stream_counts(engine) -> dict:
    return {k.removeprefix("stream."): v["calls"]
            for k, v in engine.profile_snapshot().items()
            if k.startswith("stream.")}


# -- profile off ---------------------------------------------------------


async def test_profile_off_an_item_is_today_s_and_no_clock_is_read_for_it(
        monkeypatch):
    """Unprofiled, the delivery path is the parent's: an item has exactly
    the keys it had, no span is built, and ``time.monotonic_ns``, the one
    clock the delivery path reads, is never read by the engine."""
    reads = []

    class Clock:
        def __getattr__(self, name):
            if name == "monotonic_ns":
                reads.append(name)
            return getattr(time, name)

    def boom(*a, **kw):
        raise AssertionError("a stream.post span was built with profile off")

    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    monkeypatch.setattr(core, "time", Clock())
    monkeypatch.setattr(core, "_PostSpan", boom)
    assert engine._stream_post(7) is core._NO_SPAN
    await engine.start()
    streams = await _serve(engine.generate, 5, "off")
    await engine.close()
    items = [item for s in streams for item in s]
    assert len(items) >= 5 * 4  # a first token and three bursts of two
    assert {frozenset(item) for item in items} == {frozenset(TODAY)}
    assert reads == []
    assert set(_stream_counts(engine).values()) == {0}
    # and the flight recorder holds no ``delta``: the timeline is today's
    for tl in FLIGHT.finished()[-5:]:
        assert "delta" not in [ev["name"] for ev in tl.events]


def test_profile_off_post_marks_nothing_even_inside_an_open_span():
    """The mark is the span's: without one ``_post`` leaves the item be,
    and a span marks only items that carry tokens."""
    q = asyncio.Queue()
    off = InferenceEngine(ModelSpec.tiny(), _cfg())
    item = {"token_ids": [1], "finish_reason": None}
    off._post(q, item)
    assert set(item) == TODAY
    on = InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))
    with on._stream_post(9):
        tokens = {"token_ids": [1, 2, 3], "finish_reason": None}
        empty = {"token_ids": [], "finish_reason": "error"}
        shed = {"_shed": 1.0}
        for it in (tokens, empty, shed, None):
            on._post(q, it)
    posted_ns = tokens.pop(core._POSTED)
    assert 0 <= time.monotonic_ns() - posted_ns < 5e9
    assert set(tokens) == TODAY and set(empty) == TODAY and set(shed) == {"_shed"}
    assert on._post_mark is None  # closed with its span
    after = {"token_ids": [4], "finish_reason": None}
    on._post(q, after)
    assert set(after) == TODAY


# -- profile on ----------------------------------------------------------


async def test_profile_on_every_token_item_is_counted_and_the_mark_stays_in():
    """Every item that carries tokens raises ``stream.items`` by one with
    a wait that is no time before its post, and what ``generate()`` yields
    is key for key what it yields unprofiled."""
    engine = InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))
    await engine.start()
    assert set(_stream_counts(engine).values()) == {0}
    streams = await _serve(engine.generate, 6, "on")
    counts = _stream_counts(engine)
    await engine.close()
    items = [item for s in streams for item in s]
    carrying = [item for item in items if item["token_ids"]]
    assert counts["items"] == len(carrying) >= 6 * 4
    assert counts["wait_us"] >= 0 and set(counts) == {"items", "wait_us"}
    assert {frozenset(item) for item in items} == {frozenset(TODAY)}
    assert engine._stream_rids == 6  # a running number a stream


async def test_the_mark_never_leaves_through_the_worker_endpoint():
    """The same through the worker's registered endpoint and a client of
    the runtime: what crosses the transport has no private key."""
    drt = DistributedRuntime(InMemoryHub())
    engine, _ = await launch_engine_worker(
        drt, spec=ModelSpec.tiny(), engine_config=_cfg(profile=True),
        model_name="tiny-test",
    )
    ep = drt.namespace("dynamo").component("backend").endpoint("generate")
    client = await ep.client().start()
    insts = await client.wait_for_instances(1, timeout=5)
    try:
        streams = await _serve(
            lambda req, ctx: client.call_instance(
                insts[0].instance_id, req, ctx),
            3, "wire")
        counts = _stream_counts(engine)
    finally:
        await engine.close()
        await drt.close()
    items = [item for s in streams for item in s]
    assert counts["items"] == sum(1 for it in items if it.get("token_ids")) > 0
    for item in items:
        assert not [k for k in item if k.startswith("_")], item


async def test_delta_coalesces_and_gives_the_engine_s_time_per_token():
    """``delta`` is one entry a stream with ``n`` = its token-carrying
    items after the first, and (last delta - first delta) / (generated - 1)
    is the stream's time per output token at ``generate()``: here against
    instants taken by hand around the same yields."""
    FLIGHT.clear()
    engine = InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))
    await engine.start()
    stamps: list[float] = []
    items = []
    async for item in engine.generate(_request(0, max_tokens=9),
                                      Context("tpot")):
        if item["token_ids"]:
            stamps.append(time.monotonic())
        items.append(item)
    await engine.close()
    tl = FLIGHT.lookup("tpot")
    names = [ev["name"] for ev in tl.events]
    assert names == ["admit", "prefill_dispatch", "first_token",
                     "first_delta", "delta"]
    delta = tl.last("delta")
    n_items = sum(1 for it in items if it["token_ids"])
    assert delta["n"] == n_items - 1 == len(stamps) - 1 >= 4
    assert tl.attrs["generated"] == 9
    first = tl.first("first_delta")["t"]
    got = (delta["t_last"] - first) / (tl.attrs["generated"] - 1)
    by_hand = (stamps[-1] - stamps[0]) / 8
    # the event is recorded right before the yield that the stamp follows
    assert got == pytest.approx(by_hand, abs=2e-3)
    assert first <= delta["t"] <= delta["t_last"]


async def test_reset_profile_window_zeroes_stream_and_leaves_no_key_behind():
    engine = InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))
    await engine.start()
    await _serve(engine.generate, 2, "reset")
    before = engine.profile_snapshot()
    assert before["stream.items"]["calls"] > 0
    engine.loop_probe._tick(time.monotonic_ns(), 60_000)
    assert engine.profile_snapshot()["event_loop.stalled_us"]["calls"] \
        >= 60_000
    engine.reset_profile_window()
    after = engine.profile_snapshot()
    await engine.close()
    families = ("stream.", "event_loop.")
    assert {k for k in before if k.startswith(families)} == {
        k for k in after if k.startswith(families)} == {
        "stream.items", "stream.wait_us", "event_loop.stalled_us"}
    assert {after[k]["calls"] for k in after if k.startswith(families)} == {0}
    # the ring is the collector's too: a reset leaves it and its cursor be
    assert engine.loop_probe.lags and engine.loop_probe.ticks >= 1


# -- the heartbeat -------------------------------------------------------


async def test_the_probe_books_a_blocked_loop_and_stops_with_close():
    """A callback that sleeps 170 ms on the loop is a lag of at least
    100 ms, profiled or not (the block begins somewhere inside a 50 ms
    sleep, so the wake-up comes 120 to 170 ms late; a loaded machine only
    adds to it); ``close()`` ends the ticking. What a tick books is held
    on instants made by hand, below."""
    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    await engine.start()
    await asyncio.sleep(3 * INTERVAL_S)
    probe = engine.loop_probe
    assert probe.ticks >= 1
    since = time.monotonic_ns()
    asyncio.get_running_loop().call_soon(time.sleep, 0.17)
    await asyncio.sleep(3 * INTERVAL_S)
    snap = engine.profile_snapshot()
    assert snap["event_loop.stalled_us"]["calls"] >= 100_000
    # the ring gives the window's latest wake-up to a reader
    assert max(lag for at, lag in probe.lags if at >= since) >= 100_000
    await engine.close()
    ticks = probe.ticks
    await asyncio.sleep(3 * INTERVAL_S)
    assert probe.ticks == ticks and probe._task is None


def test_the_probe_s_arithmetic_and_what_the_collector_exports():
    """``_tick`` by hand: the ring, the stalls' sum and their callback; the
    collector reads what is new in the ring into the one histogram, once,
    and the probe holds no Prometheus object."""
    stalls = []
    probe = LoopProbe(stalls.append)
    s = 1_000_000_000
    rows = [(7 * s, 120), (7 * s + 5, 50_000), (8 * s, 50_001),
            (8 * s + 9, 90), (10 * s, 2_000_000)]
    for at, lag in rows:
        probe._tick(at, lag)
    assert list(probe.lags) == rows and probe.ticks == 5
    assert probe.stalled_us == 50_001 + 2_000_000
    assert stalls == [50_001, 2_000_000]
    assert probe.since(0) == rows and probe.since(3) == rows[3:]
    assert probe.since(5) == []
    # a ring that turned over hands out what it still holds
    for i in range(RING):
        probe._tick(11 * s + i, 1)
    assert len(probe.lags) == RING and probe.ticks == RING + 5
    assert len(probe.since(2)) == RING and probe.since(RING + 3) == [
        (11 * s + RING - 2, 1), (11 * s + RING - 1, 1)]

    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    for at, lag in ((s, 100), (2 * s, 300_000), (3 * s, 2_000_000)):
        engine.loop_probe._tick(at, lag)
    collector = EngineCollector(engine)
    collector.sample()
    collector.sample()  # nothing new: nothing observed twice
    assert len(engine.loop_probe.lags) == 3  # read, not drained
    text = REGISTRY.exposition().decode()
    lbl = collector.label
    assert f'event_loop_lag_seconds_count{{engine="{lbl}"}} 3' in text
    assert f'event_loop_lag_seconds_bucket{{engine="{lbl}",le="0.5"}} 2' in text
    assert "engine_dispatch_overhead_frac" not in text


# -- the trace -----------------------------------------------------------


def _events(trace_dir, prefixes):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    profile = ProfileData.from_file(path)
    out = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        # threads' lines share a name ("python"): a line is its place
        for place, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append((place, e.name, float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns),
                                {k: v for k, v in dict(e.stats).items()}))
    return profile, sorted(out, key=lambda e: (e[2], -e[3]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A profiler trace of a profiled toy engine serving 5 streams, with
    the loop blocked 170 ms once: (profile, its engine/stream/loop events,
    the stream counts between the trace's edges)."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))

    async def go():
        FLIGHT.clear()
        engine = InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))
        await engine.start()
        await _serve(engine.generate, 2, "warm")  # compiles outside
        await asyncio.sleep(0.2)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        engine.reset_profile_window()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        await _serve(engine.generate, 5, "traced", base=100)
        asyncio.get_running_loop().call_soon(time.sleep, 0.17)
        await asyncio.sleep(4 * INTERVAL_S)
        counts = _stream_counts(engine)
        jax.profiler.stop_trace()
        await engine.close()
        return counts

    counts = asyncio.run(go())
    profile, events = _events(trace_dir, ("engine.", "stream.", "loop."))
    return profile, events, counts


def test_a_post_carries_its_burst_s_launch_number_and_a_take_its_post_s(
        traced):
    _profile, events, counts = traced
    decode = {int(s["seq"]) for _l, n, _a, _b, s in events
              if n == "engine.launch" and s["kind"] == "decode"}
    prefill = {int(s["seq"]) for _l, n, _a, _b, s in events
               if n == "engine.launch" and s["kind"] == "prefill"}
    posts = [(ln, a, b, s) for ln, n, a, b, s in events if n == "stream.post"]
    takes = [(ln, a, s) for ln, n, a, _b, s in events if n == "stream.take"]
    assert posts and takes
    # every post names a program that was launched: a decode burst's, or
    # the prefill's whose sample an admission wave landed
    seqs = {int(s["seq"]) for _ln, _a, _b, s in posts}
    assert seqs <= decode | prefill and seqs & decode and seqs & prefill
    # one thread posts, another takes
    assert len({ln for ln, *_ in posts}) == 1 == len({ln for ln, *_ in takes})
    assert {ln for ln, *_ in posts} != {ln for ln, *_ in takes}
    # the posts sit inside the step thread's phases, and are not of them
    phases = [(a, b) for _l, n, a, b, _s in events
              if n.startswith("engine.") and n not in (
                  "engine.launch", "engine.clock")]
    for _ln, a, b, s in posts:
        assert any(c <= a and b <= d for c, d in phases)
        assert set(s) == {"seq"}
    # every take is of a traced post's item or of one posted just before
    # the trace began; its wait is what the counters summed
    assert len(takes) == counts["items"]
    assert sum(int(s["wait_us"]) for _ln, _a, s in takes) == counts["wait_us"]
    assert {frozenset(s) for _ln, _a, s in takes} == {
        frozenset({"rid", "wait_us"})}
    # a stream's takes: 5 streams, each a first token and 3 bursts of 2
    by_rid = {}
    for _ln, a, s in takes:
        by_rid.setdefault(int(s["rid"]), []).append(a)
    assert len(by_rid) == 5 and {len(v) for v in by_rid.values()} == {4}
    # post and take agree on the clock: a take's own instant less its
    # wait is the instant its post began (or, for an item posted just
    # before the trace began, lies before every traced post)
    starts = [a for _ln, a, _b, _s in posts]
    for _ln, at, s in takes:
        posted = at - int(s["wait_us"]) * 1e3
        assert posted < min(starts) or min(
            abs(posted - a) for a in starts) < 1.5e6


def test_the_blocked_loop_is_a_loop_stall_on_the_event_loop_s_line(traced):
    _profile, events, _counts = traced
    stalls = [(ln, a, s) for ln, n, a, _b, s in events if n == "loop.stall"]
    takes = {ln for ln, n, *_ in events if n == "stream.take"}
    # the 170 ms block; a loaded machine may add a stall of its own
    assert stalls and {ln for ln, *_ in stalls} == takes  # the loop's thread
    assert max(int(s["lag_us"]) for _ln, _a, s in stalls) >= 100_000
    assert {frozenset(s) for _ln, _a, s in stalls} == {frozenset({"lag_us"})}


def test_the_step_thread_s_phases_read_the_same_with_the_new_events(traced):
    """``lib/spans.py: host_events`` takes every ``engine.*`` annotation
    for a step-thread phase: the new names are outside that prefix, so a
    trace with them gives the very phases, launches and clock samples of
    the same trace without them."""
    sys.path.insert(0, os.path.join(REPO, "perfbench"))
    from lib import spans

    profile, events, _counts = traced
    assert {n.split(".")[0] for _l, n, *_ in events} == {
        "engine", "stream", "loop"}

    def without(profile):
        return types.SimpleNamespace(planes=[
            types.SimpleNamespace(name=p.name, lines=[
                types.SimpleNamespace(name=ln.name, events=[
                    e for e in ln.events
                    if not e.name.startswith(("stream.", "loop."))])
                for ln in p.lines])
            for p in profile.planes])

    got, want = spans.host_events(profile), spans.host_events(without(profile))
    assert got["phases"] == want["phases"] and len(got["phases"]) > 20
    assert got["launches"] == want["launches"] and got["clock"] == want["clock"]
    assert not [p for p in got["phases"] if p[0].startswith(("post", "take"))]
