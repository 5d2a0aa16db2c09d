"""The engine's own spans (EngineConfig.profile): step-thread phases and
device launches as ``engine.*`` annotations in a jax.profiler trace, the
clock annotation, the flight recorder's time-to-first-token events, and the
``readmit.*`` sums computed from them. Toy engine, CPU."""

import asyncio
import glob
import os

import pytest

import jax

from dynamo_tpu.engine import core
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.flight import FLIGHT, FlightRecorder, Timeline

pytestmark = pytest.mark.integration


def _cfg(**kw) -> EngineConfig:
    base = dict(
        page_size=4, num_pages=128, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(16, 32), prefill_pack_size=2,
        decode_steps_per_dispatch=2, pipeline_decode=True,
    )
    base.update(kw)
    return EngineConfig(**base)


async def _serve(engine, n, tag, max_tokens=4, base=3) -> None:
    """``n`` concurrent requests of 5 to 11 prompt tokens; no two of one
    ``base`` share a first token, so nothing is served from the cache."""
    async def one(i):
        async for _ in engine.generate(
            {"token_ids": [base + (i + j) % 50 for j in range(5 + i % 7)],
             "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context(f"{tag}-{i}"),
        ):
            pass

    await asyncio.gather(*(one(i) for i in range(n)))


# -- profile off ---------------------------------------------------------


async def test_profile_off_records_nothing_and_creates_no_annotation(
        monkeypatch):
    """With ``profile`` off a phase and a launch are the one shared no-op:
    nothing is allocated, nothing summed, no annotation built."""
    def boom(*a, **kw):
        raise AssertionError("an annotation was created with profile off")

    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert engine._phase("idle") is engine._phase("dispatch") is core._NO_SPAN
    seq = engine._launch_seq
    assert engine._launch(
        "decode", steps=2, live=1, slots=4, ahead=1) is core._NO_SPAN
    assert engine._launch_seq == seq + 1  # numbered all the same
    await engine.start()
    await _serve(engine, 3, "off")
    snap = engine.profile_snapshot()
    await engine.close()
    assert engine._prof == {} and engine._prof_requests == {}
    assert set(snap) == {"dispatch.d2h_wait", "readmit.d2h_wait",
                         "dispatch.dispatches", "dispatch.compile",
                         "window.at",
                         "decode_kv.pages_live", "decode_kv.pages_fetched",
                         "decode_kv.pages_table",
                         "kv_pool.heads_per_lane_row", "kv_pool.bytes",
                         "prefill_kv.blocks_visited.full",
                         "prefill_kv.blocks_table.full",
                         "chunked_prefill.chunks",
                         "chunked_prefill.chunks_behind_burst",
                         "burst_hold.begun", "burst_hold.overran",
                         "burst_hold.admissions",
                         "burst_hold.admissions_held",
                         "decode_bursts.full", "decode_bursts.short",
                         "decode_bursts.single",
                         "first_tokens.in_hold", "first_tokens.at_step",
                         "first_tokens.on_burst",
                         "stream.items", "stream.wait_us",
                         "event_loop.stalled_us"}
    # three bursts' worth at least, always on: pages moved for real contexts
    kv = {k.removeprefix("decode_kv."): v["calls"]
          for k, v in snap.items() if k.startswith("decode_kv.")}
    assert 0 < kv["pages_live"] <= kv["pages_fetched"] <= kv["pages_table"]


def test_decode_kv_counts_what_the_kernel_fetches(monkeypatch):
    """``decode_kv.*`` over a hand-built burst: live pages, the pages of
    the live chunks by the kernel's own ``live_chunks`` and chunk, the
    tables' pages; nothing fetched for a batch of inactive slots."""
    import numpy as np

    from dynamo_tpu.ops.pallas import fused_decode

    # tiny-test's page of K and V is 1 KiB: chunks of two pages
    monkeypatch.setattr(fused_decode, "_CHUNK_BYTES", 2048)
    calls = []
    real = fused_decode.live_chunks
    monkeypatch.setattr(
        fused_decode, "live_chunks",
        lambda *a, **kw: calls.append(a[1:]) or real(*a, **kw))
    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    assert engine._kv_chunk_pages == 2 == fused_decode.pool_chunk_pages(
        engine.k_pages, engine.v_pages, 16)

    def burst(seq_lens, n_burst=2):
        lens = np.asarray(seq_lens, np.int32)
        return {"seq_lens": lens, "active": lens > 1, "n_burst": n_burst}

    def counts():
        return {k.removeprefix("decode_kv."): v["calls"]
                for k, v in engine.profile_snapshot().items()
                if k.startswith("decode_kv.")}

    # two steps a slot. Slot 1: 5 and 6 tokens in the pool, two pages and
    # one chunk each step. Slot 3: 17 and 18 tokens, five pages and three
    # chunks of two pages each step
    engine._count_decode_kv(burst([1, 6, 1, 18]))
    assert calls == [(4, 2)]  # page size, the kernel's chunk
    assert counts() == {"pages_live": 2 * 2 + 2 * 5,
                        "pages_fetched": 2 * 1 * 2 + 2 * 3 * 2,
                        "pages_table": 4 * 16 * 2}
    engine._count_decode_kv(burst([1, 1, 1, 1]))
    assert counts()["pages_fetched"] == 16 and counts()["pages_live"] == 14
    assert counts()["pages_table"] == 2 * 4 * 16 * 2
    engine.reset_profile_window()
    assert set(counts().values()) == {0}


def test_the_environment_no_longer_switches_the_profiler(monkeypatch):
    monkeypatch.setenv("DYNAMO_ENGINE_" + "PROFILE", "1")
    assert InferenceEngine(ModelSpec.tiny(), _cfg())._profiling is False
    assert InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))._profiling


# -- profile on: the trace -----------------------------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((line.name, e.name, float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda e: (e[2], -e[3]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A short profiler trace of a profiled toy engine serving 6 requests:
    (host ``engine.*`` events, profile snapshot, timelines, launch numbers
    before and after)."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))

    async def go():
        FLIGHT.clear()
        engine = InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))
        await engine.start()
        await _serve(engine, 2, "warm")  # compiles outside the trace
        # park the step thread, so that no phase straddles the trace's
        # edges and the sums can be held to the annotations
        await asyncio.sleep(0.3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        engine.reset_profile_window()
        seq0 = engine._launch_seq
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        await _serve(engine, 6, "traced", max_tokens=6, base=100)
        await asyncio.sleep(0.3)
        snap = engine.profile_snapshot()
        seq1 = engine._launch_seq
        jax.profiler.stop_trace()
        await engine.close()
        tls = [tl for tl in engine.flight.finished()
               if tl.request_id.startswith("traced-")]
        return snap, tls, seq0, seq1

    snap, tls, seq0, seq1 = asyncio.run(go())
    return _host_events(trace_dir), snap, tls, seq0, seq1


def test_phase_annotations_match_the_profile_sums(traced):
    """Every phase the snapshot counted while the trace ran is in the trace
    as ``engine.<phase>`` as many times (the ``idle`` waits open at the
    trace's edges aside), on the step thread's own line."""
    events, snap, _tls, _s0, _s1 = traced
    assert len({e[0] for e in events}) == 1  # one thread's line
    counted = {}
    for _line, name, *_ in events:
        if name not in ("engine.launch", "engine.clock"):
            counted[name[len("engine."):]] = counted.get(
                name[len("engine."):], 0) + 1
    synthesized = {"dispatch.dispatches", "dispatch.compile"}
    for phase, rec in snap.items():
        if (
            phase in synthesized
            # counts, not phases
            or phase.startswith(
                ("decode_kv.", "kv_pool.", "kv.", "prefill_kv.",
                 "chunked_prefill.",
                 "burst_hold.", "decode_bursts.", "first_tokens.",
                 "stream.", "event_loop."))
            or phase.startswith("readmit.") and phase != "readmit.d2h_wait"
        ):
            continue
        want = rec["calls"]
        got = counted.get(phase, 0)
        # the step thread was parked in an idle wait when the trace began
        # (summed, but an annotation open before the trace is not in it)
        # and when the snapshot was read (in the trace if it closed first)
        assert got == want or (phase == "idle" and abs(got - want) <= 1), (
            phase, got, want)
    for phase in ("admit_loop", "packed_prefill", "build_batch",
                  "dispatch", "process", "dispatch.d2h_wait"):
        assert counted.get(phase, 0) > 0, phase


def test_phase_annotations_nest_as_the_code_nests(traced):
    events = traced[0]
    spans = [(n, a, b) for _l, n, a, b, _s in events
             if n not in ("engine.launch", "engine.clock")]

    def parents(name):
        out = set()
        for n, a, b in spans:
            if n != name:
                continue
            holders = [m for m, c, d in spans
                       if (c, d) != (a, b) and c <= a and b <= d]
            out.add(tuple(holders))
        return out

    # the burst download waits inside process.d2h_sync inside process
    assert any("engine.process.d2h_sync" in p and "engine.process" in p
               for p in parents("engine.dispatch.d2h_wait"))
    assert all("engine.process" in p
               for p in parents("engine.process.d2h_sync"))
    # a thread's spans never straddle: each pair is disjoint or nested
    for i, (_n, a, b) in enumerate(spans):
        for _m, c, d in spans[i + 1:]:
            if c >= b:
                break
            assert d <= b, (a, b, c, d)


def test_launch_numbers_are_dense_and_carry_host_counts(traced):
    events, _snap, _tls, seq0, seq1 = traced
    launches = [s for _l, n, _a, _b, s in events if n == "engine.launch"]
    seqs = sorted(int(s["seq"]) for s in launches)
    assert seqs == list(range(seq0 + 1, seq1 + 1))
    kinds = {s["kind"] for s in launches}
    assert {"prefill", "decode", "sample", "feed"} <= kinds
    for s in launches:
        if s["kind"] == "decode":
            assert int(s["slots"]) == 4 and 1 <= int(s["live"]) <= 4
            assert int(s["steps"]) in (1, 2)
        if s["kind"] == "prefill":
            assert 1 <= int(s["rows"]) <= 2
            assert 5 <= int(s["tokens"]) <= 2 * 11
    # every launch annotation sits inside a phase annotation
    spans = [(a, b) for _l, n, a, b, _s in events
             if n not in ("engine.launch", "engine.clock")]
    for _l, n, a, b, _s in events:
        if n == "engine.launch":
            assert any(c <= a and b <= d for c, d in spans)


def test_prefill_and_decode_launches_carry_the_bursts_ahead(traced):
    """``ahead`` is the decode bursts in flight at the launch: one burst is
    kept queued behind the running one, so neither a prefill nor a burst is
    ever launched behind two; the other kinds do not carry it."""
    events = traced[0]
    ahead = {"prefill": [], "decode": []}
    for _l, n, _a, _b, s in events:
        if n != "engine.launch":
            continue
        if s["kind"] in ahead:
            ahead[s["kind"]].append(int(s["ahead"]))
        else:
            assert "ahead" not in s, s
    assert ahead["prefill"] and set(ahead["prefill"]) <= {0, 1}
    assert set(ahead["decode"]) == {0, 1}
    # 6 requests for 4 slots: some prompt was launched behind a burst
    assert 1 in ahead["prefill"]


def test_every_timeline_s_prefill_dispatch_names_a_launch_that_exists(traced):
    events, _snap, tls, _s0, _s1 = traced
    prefills = {int(s["seq"]): s for _l, n, _a, _b, s in events
                if n == "engine.launch" and s["kind"] == "prefill"}
    assert len(tls) == 6
    tokens = {}
    for tl in tls:
        names = [ev["name"] for ev in tl.events]
        chain = [n for n in names if n in (
            "admit", "prefill_dispatch", "first_token", "first_delta")]
        assert chain == ["admit", "prefill_dispatch", "first_token",
                         "first_delta"], names
        seq = tl.first("prefill_dispatch")["seq"]
        assert seq in prefills, (seq, sorted(prefills))
        tokens[seq] = tokens.get(seq, 0) + tl.attrs["prompt_tokens"]
    # a launch's token count is its requests' prompts (nothing cached)
    for seq, n in tokens.items():
        assert int(prefills[seq]["tokens"]) == n


def test_the_clock_annotation_fits_the_monotonic_clock(traced):
    """One ``engine.clock`` a loop cycle; its ``mono_ns`` against its own
    start gives one offset, within scheduling noise."""
    events = traced[0]
    diffs = sorted(a - int(s["mono_ns"]) for _l, n, a, _b, s in events
                   if n == "engine.clock")
    assert len(diffs) >= 6
    mid = diffs[len(diffs) // 2]
    inner = diffs[len(diffs) // 4: 3 * len(diffs) // 4 + 1]
    assert max(abs(d - mid) for d in inner) < 200e3  # 0.2 ms


def test_readmit_sums_equal_what_the_timelines_give(traced):
    _events, snap, tls, _s0, _s1 = traced
    want = {}
    for tl in tls:
        for name, dt in tl.admission_phases():
            rec = want.setdefault("readmit." + name, [0.0, 0])
            rec[0] += dt
            rec[1] += 1
    assert set(want) == {"readmit.admit_wait", "readmit.prefill_dispatch",
                         "readmit.first_token"}
    for name, (secs, calls) in want.items():
        assert snap[name]["calls"] == calls == 6
        assert snap[name]["secs"] == pytest.approx(secs, abs=1e-4)


# -- the timeline's arithmetic -------------------------------------------


def _tl(*events) -> Timeline:
    tl = Timeline("r", {})
    for name, t, t_last in events:
        tl.events.append({"name": name, "t": t, "t_last": t_last, "n": 1})
    return tl


@pytest.mark.parametrize("events,want", [
    pytest.param(
        [("admit", 0.01, 0.01), ("prefill_dispatch", 0.03, 0.03),
         ("first_token", 0.2, 0.2), ("first_delta", 0.21, 0.21)],
        [("admit_wait", 0.01), ("prefill_dispatch", 0.02),
         ("first_token", 0.17)], id="plain"),
    pytest.param(
        # requeued on page pressure: one coalesced admit, its last instant
        [("admit", 0.01, 0.5), ("prefill_chunk", 0.51, 0.6),
         ("prefill_dispatch", 0.61, 0.61), ("first_token", 0.7, 0.7)],
        [("admit_wait", 0.5), ("prefill_dispatch", 0.11),
         ("first_token", 0.09)], id="requeued-and-chunked"),
    pytest.param(
        # preempted and admitted again: the second wait runs from the
        # preemption, and each admission counts
        [("admit", 0.01, 0.01), ("prefill_dispatch", 0.02, 0.02),
         ("first_token", 0.1, 0.1), ("first_delta", 0.11, 0.11),
         ("preempt", 1.0, 1.0), ("admit", 1.5, 1.5),
         ("prefill_dispatch", 1.6, 1.6), ("first_token", 1.9, 1.9)],
        [("admit_wait", 0.01), ("prefill_dispatch", 0.01),
         ("first_token", 0.08), ("admit_wait", 0.5),
         ("prefill_dispatch", 0.1), ("first_token", 0.3)], id="preempted"),
    pytest.param(
        [("admit", 0.01, 0.01), ("disagg_resume", 0.02, 0.02)],
        [("admit_wait", 0.01)], id="disagg-resume"),
])
def test_admission_phases(events, want):
    got = _tl(*events).admission_phases()
    assert [n for n, _ in got] == [n for n, _ in want]
    assert [dt for _, dt in got] == pytest.approx([dt for _, dt in want])


# -- retention -----------------------------------------------------------


def _finish_many(fr: FlightRecorder, n: int) -> None:
    for i in range(n):
        fr.start(f"r{i}")
        fr.event(f"r{i}", "admit")
        fr.finish(f"r{i}", "length")


def test_the_recorder_keeps_a_profiled_window_whole_and_says_so():
    fr = FlightRecorder()
    fr.retain(core.PROFILE_TIMELINES)
    _finish_many(fr, 300)
    assert fr.complete
    assert [tl.request_id for tl in fr.finished()] == [
        f"r{i}" for i in range(300)]
    # bounded all the same, and then it says it is not whole
    fr.retain(310)  # never narrows
    _finish_many(fr, core.PROFILE_TIMELINES)
    assert not fr.complete
    assert len(fr.finished()) == core.PROFILE_TIMELINES
    fr.clear()
    assert fr.complete and fr.finished() == []


def test_the_recorder_without_profile_still_rotates_at_128():
    fr = FlightRecorder()
    _finish_many(fr, 128)
    assert fr.complete and len(fr.finished()) == 128
    _finish_many(fr, 172)
    assert not fr.complete
    assert len(fr.finished()) == 128
    assert fr.finished()[0].request_id == "r44"  # the second 172, last 128
    assert fr.snapshot()["recent"][-1]["request_id"] == "r171"


async def test_a_profiled_engine_widens_the_process_recorder():
    FLIGHT.clear()
    engine = InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))
    assert engine.flight is FLIGHT
    await engine.start()
    await _serve(engine, 5, "keep", max_tokens=2)
    await engine.close()
    assert FLIGHT.complete
    assert FLIGHT._capacity >= core.PROFILE_TIMELINES
    kept = [tl.request_id for tl in FLIGHT.finished()]
    assert sorted(kept) == [f"keep-{i}" for i in range(5)]
