"""The hold of the queued decode burst (engine/core.py _hold_queued_burst):
while an arrival could be admitted the moment it came, burst k+2 is launched
shortly before the running burst k+1 is expected to end, not at once, and a
request arriving meanwhile has its prefill launched directly behind k+1.

Nothing here sleeps to prove an order. The step thread's clock is a counter
the test owns, the device is a few lines of arithmetic on it (a burst takes
one unit, a prefill a fifth, in launch order), ``is_ready`` answers from
that arithmetic, and the wait of a hold either jumps the clock to its
deadline or, where a test scripts an arrival, blocks on the enqueue itself.

The hold lands too (_land_ready_waves): an admission's sample ends with its
prefill, on the same arithmetic, and a hold that sees it ready posts the
first token then and there; the hold looks every ``POLL`` of the test's
clock while a wave is on its way.

And how long a burst is (_short_burst, asked by _build_batch): on the
serving schedule, bursts of 8 with a compiled short length of 4, the length
follows what an arrival would meet; the hold then hides behind a 4-step
burst as it does behind any length it has timed.
"""

import asyncio
import collections
import threading

import numpy as np
import pytest

from dynamo_tpu.engine import core
from dynamo_tpu.engine.cache import SeqPages
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.guided import TokenVocab, grammar_from_request
from dynamo_tpu.runtime.context import PRIORITY_HEADER, Context

pytestmark = pytest.mark.integration

SPEC = ModelSpec(
    name="hold-test", vocab_size=272, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)
BURST, PREFILL, LAUNCH = 1.0, 0.2, 0.01  # units of the test's clock
POLL = 0.05  # a hold looks at a wave on its way this often, on that clock


def _cfg(*, slots=4, num_pages=256, steps=4, **kw) -> EngineConfig:
    return EngineConfig(
        page_size=4, num_pages=num_pages, max_pages_per_seq=32,
        max_decode_slots=slots, prefill_buckets=(16, 32, 64),
        decode_steps_per_dispatch=steps, pipeline_decode=True, **kw,
    )


async def _collect(engine, prompt, max_tokens, *, temperature=0.0, seed=None,
                   out=None, ctx=None, reasons=None, **more):
    out = [] if out is None else out
    sampling = {"temperature": temperature}
    if seed is not None:
        sampling["seed"] = seed
    async for item in engine.generate(
        {"token_ids": list(prompt),
         "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
         "sampling": sampling, **more},
        ctx or Context(),
    ):
        out.extend(item["token_ids"])
        if reasons is not None and item.get("finish_reason"):
            reasons.append(item["finish_reason"])
    return out


class _Wake(threading.Event):
    """The engine's wake event; a wait made inside a hold goes to the
    test."""

    sim = None

    def wait(self, timeout=None):
        if self.sim.in_hold:
            return self.sim.hold_wait(timeout)
        return super().wait(timeout)


class _Sim:
    """An engine whose step thread reads the test's clock and whose
    device is arithmetic on it. ``hold=False`` gives the parent's
    schedule: the same engine with no deadline ever known.

    ``at_hold[n]`` / ``at_read[n]`` script what happens at the n-th wait of
    a hold / the n-th read of a burst (from 1): a callable run on the step
    thread. ``arrive(coro_fn)`` inside one starts a request on the event
    loop and returns once it is enqueued. The short waits a hold makes
    while an admission wave is on its way (``POLL`` apart) are counted
    apart and scripted by ``at_poll[n]``: ``at_hold`` counts the waits to
    the deadline, every wait there was before a hold landed anything."""

    def __init__(self, monkeypatch, *, hold=True, guided_vocab=None, **cfg):
        self.engine = engine = InferenceEngine(
            SPEC, _cfg(**cfg), guided_vocab=guided_vocab)
        self.hold = hold
        self.t = 1000.0
        self.burst = BURST  # what a burst of the full length takes here
        self.prefill = PREFILL  # what a prefill takes here
        self.steps = []  # the steps of every burst launched, in order
        self.free_at = 0.0  # when the device has run all it was given
        self.ends = {}  # id(a burst's output) -> (output, its end)
        self.log = []  # (kind, ahead, held) of launches, ("read", n, _)
        self.landed = []  # (where, entries of the log before it, when) of
        #                    every first token an admission wave landed
        self.holds = []  # (begun, the queue was empty, a slot was free,
        #                   no partial, not draining) at every decision
        self.in_hold = False
        self.hold_waits = self.polls = 0
        self.reads = 0
        self.at_hold, self.at_poll, self.at_read = {}, {}, {}
        self.woken = []  # what every wait of a hold returned ...
        self.polled = []  # ... and every short one with a wave on its way
        self.enqueued = threading.Event()
        self.started = []  # futures of the requests that arrived by script
        self.loop = None

        engine._clock = lambda: self.t
        wake = _Wake()
        wake.sim = self
        engine._wake = wake
        if not hold:
            engine._hold_deadline = lambda: None
        monkeypatch.setattr(core, "_is_ready", self.is_ready)
        monkeypatch.setattr(core, "_WAVE_POLL_S", POLL)

        launch, dispatch = engine._launch, engine._dispatch_burst
        process, note = engine._process_burst, engine._note_burst_end
        holdfn, put = engine._hold_queued_burst, engine._waiting.put_nowait
        complete, land = (
            engine._complete_admissions_async, engine._land_first_token)

        def watched_launch(kind, **counts):
            if kind == "prefill":
                self.free_at = max(self.free_at, self.t) + self.prefill
            if kind in ("prefill", "decode"):
                self.log.append((kind, counts["ahead"], engine._holding))
            if kind == "decode":
                self.steps.append(counts["steps"])
            return launch(kind, **counts)

        def watched_dispatch(batch, chain):
            self.t += LAUNCH
            # a step takes what it takes: a shorter burst ends sooner
            took = (self.burst * batch["n_burst"]
                    / engine.config.decode_steps_per_dispatch)
            end = self.free_at = max(self.free_at, self.t) + took
            results = dispatch(batch, chain)
            self.ends[id(results[0])] = (results[0], end)
            return results

        def watched_process(pending):
            self.reads += 1
            self.log.append(("read", len(engine._pipeline), False))
            script = self.at_read.pop(self.reads, None)
            if script is not None:
                script()
            return process(pending)

        def watched_note(pending, blocked):
            # the read has returned: it is the burst's end, or later
            _, end = self.ends.pop(id(pending["results"][0]))
            self.t = max(self.t, end)
            return note(pending, blocked)

        def watched_hold():
            state = (
                engine._waiting.empty(),
                any(s is None for s in engine._slots),
                engine._partial is None, not engine._draining,
            )
            self.in_hold = True
            try:
                begun = holdfn()
            finally:
                self.in_hold = False
            self.holds.append((begun, *state))
            return begun

        def watched_put(item):
            put(item)
            self.enqueued.set()

        def watched_complete(pending):
            complete(pending)
            # an admission's sample ends with its prefill, the last the
            # device was given
            for wave in engine._admit_waves:
                self.ends.setdefault(
                    id(wave["dev"]), (wave["dev"], self.free_at))

        def watched_land(slot_idx, slot, tok, where):
            self.landed.append((where, len(self.log), self.t))
            return land(slot_idx, slot, tok, where)

        engine._launch = watched_launch
        engine._dispatch_burst = watched_dispatch
        engine._process_burst = watched_process
        engine._note_burst_end = watched_note
        engine._hold_queued_burst = watched_hold
        engine._waiting.put_nowait = watched_put
        engine._complete_admissions_async = watched_complete
        engine._land_first_token = watched_land

    def is_ready(self, dev) -> bool:
        # a burst's output at the burst's end, an admission's sample at
        # its prefill's
        known = self.ends.get(id(dev))
        return known is not None and self.t >= known[1]

    def hold_wait(self, timeout) -> bool:
        if self.engine._admit_waves:
            # a wave is on its way: one of the short waits between two
            # looks at it
            self.polls += 1
            script, told = self.at_poll.pop(self.polls, None), self.polled
        else:
            self.hold_waits += 1
            script, told = self.at_hold.pop(self.hold_waits, None), self.woken
        if script is None:
            self.t += timeout  # nothing arrives: the deadline, or a look
            woke = False
        else:
            woke = bool(script())
        told.append(woke)
        return woke

    @property
    def points(self) -> dict:
        """Where a test scripts what happens beside a running burst: the
        waits of holds, or on the parent's schedule, which holds nothing,
        the blocked reads."""
        return self.at_hold if self.hold else self.at_read

    def arrive(self, make_coro) -> bool:
        self.enqueued.clear()
        self.started.append(
            asyncio.run_coroutine_threadsafe(make_coro(), self.loop))
        assert self.enqueued.wait(30), "the scripted request never arrived"
        return True

    async def __aenter__(self):
        self.loop = asyncio.get_running_loop()
        await self.engine.start()
        return self

    async def __aexit__(self, *exc):
        await self.engine.close()

    async def arrivals(self):
        return [await asyncio.wrap_future(f) for f in self.started]

    def launches(self):
        return [e for e in self.log if e[0] != "read"]


async def _two_streams(sim, n=(61, 58)):
    """Two of the slots decode: a burst in flight, an empty queue and free
    slots, cycle after cycle."""
    return await asyncio.gather(
        _collect(sim.engine, [5, 9, 13], n[0]),
        _collect(sim.engine, [7, 11, 2, 8], n[1]),
    )


async def test_arrival_in_a_hold_is_prefilled_before_the_next_burst(
        monkeypatch):
    """An empty queue, a free slot, a burst in flight: the request that
    arrives during the hold has its prefill launched directly behind the
    running burst, BEFORE the next burst; one that arrives under the
    blocked read (the parent's only case) is launched after it."""
    async with _Sim(monkeypatch) as sim:
        eng = sim.engine
        sim.at_hold[3] = lambda: sim.arrive(
            lambda: _collect(eng, [3, 5, 9, 13, 4], 9))
        sim.at_read[8] = lambda: sim.arrive(
            lambda: _collect(eng, [17, 19, 4], 7))
        outs = await _two_streams(sim)
        arrived = await sim.arrivals()
    assert [len(o) for o in outs] == [61, 58]
    assert [len(o) for o in arrived] == [9, 7]
    launches = sim.launches()
    held = [i for i, e in enumerate(launches) if e == ("prefill", 1, True)]
    assert len(held) == 1
    # directly behind the running burst: one in flight, and the launch
    # before it was that burst's, the launch after it the held burst's
    assert launches[held[0] - 1][0] == launches[held[0] + 1][0] == "decode"
    assert not launches[held[0] + 1][2]  # the burst itself is not "held"
    # the other arrived while the thread was blocked in the read with the
    # next burst already launched: its prefill follows that launch, in a
    # pass of the next cycle (no hold was open: not held)
    reads = [j for j, e in enumerate(sim.log) if e[0] == "read"]
    assert sim.log[reads[7] - 1][0] == "decode"
    assert sim.log[reads[7] + 1] == ("prefill", 1, False)
    # each prefill stood before a burst as two launches (it and its
    # sample), counted off the launch sequence, and was timed so
    assert list(eng._side_secs) == pytest.approx([PREFILL / 2] * 2)
    assert eng.burst_hold["admissions_held"] == 1
    assert eng.burst_hold["admissions"] == 4
    assert eng.burst_hold["begun"] >= 8
    assert eng.burst_hold["overran"] == 0
    # every hold that began had an empty queue beside a free slot
    assert all(all(h[1:]) for h in sim.holds if h[0])


async def _gated(sim, coros):
    """Every request is enqueued before the step thread's first cycle, so
    that what each cycle admits does not depend on who was quicker."""
    go = threading.Event()
    step = sim.engine._step

    def gated_step():
        go.wait()
        return step()

    sim.engine._step = gated_step
    tasks = [asyncio.ensure_future(c) for c in coros]
    while sim.engine._waiting.qsize() < len(tasks):
        await asyncio.sleep(0.001)
    go.set()
    return await asyncio.gather(*tasks)


def _long(n: int, salt: int = 7) -> list[int]:
    return [3 + (salt * i) % 200 for i in range(n)]


async def _no_slot(sim):
    eng = sim.engine
    return await _gated(sim, [
        _collect(eng, [5 + i, 9, 13], n) for i, n in enumerate((21, 17, 9, 13))
    ])


async def _partial_open(sim):
    eng = sim.engine
    return await _gated(sim, [
        _collect(eng, [5, 9, 13], 41), _collect(eng, [7, 11, 2, 8], 37),
        _collect(eng, _long(59), 14),
    ])


async def _draining(sim):
    sim.at_read[3] = sim.engine.begin_drain
    return await _gated(sim, [
        _collect(sim.engine, [5, 9, 13], 41),
        _collect(sim.engine, [7, 11, 2, 8], 37),
    ])


async def _guided_live(sim):
    live = []
    sim.engine._guided_live = lambda: bool(live)
    sim.at_read[3] = lambda: live.append(1)
    return await _gated(sim, [
        _collect(sim.engine, [5, 9, 13], 41),
        _collect(sim.engine, [7, 11, 2, 8], 37),
    ])


CLOSED = {
    # name: (driver, engine options)
    "queue_and_no_slot": (_no_slot, {"slots": 2}),
    "partial_open": (_partial_open, {"max_prefill_chunk_tokens": 8}),
    "draining": (_draining, {}),
    "guided_live": (_guided_live, {}),
}


@pytest.mark.parametrize("name", list(CLOSED))
async def test_condition_false_keeps_the_parents_launch_order(
        name, monkeypatch):
    """Requests waiting with no slot for them, a partial open, a drain, a
    guided slot: no hold begins, and every launch and read comes in the
    order the parent's schedule gives them."""
    drive, opts = CLOSED[name]
    async with _Sim(monkeypatch, hold=False, **opts) as parent:
        want = await drive(parent)
    async with _Sim(monkeypatch, **opts) as sim:
        got = await drive(sim)
    assert got == want
    assert sim.log == parent.log
    assert not any(held for _k, _n, held in sim.log)
    assert not any(h[0] for h in parent.holds)
    # a hold began only with an empty queue, a free slot, no partial and
    # no drain; the case's own cycles began none
    begun = [h for h in sim.holds if h[0]]
    assert all(all(h[1:]) for h in begun)
    if name == "queue_and_no_slot":
        assert sum(not h[1] or not h[2] for h in sim.holds) >= 5
    if name == "partial_open":
        assert sum(not h[3] for h in sim.holds) >= 5
    if name == "draining":
        assert sum(not h[4] for h in sim.holds) >= 5
        # the drain came after estimates were there: holds before it only
        assert len(begun) <= 2
    if name == "guided_live":
        # the synchronous schedule from the third read on: nothing asked
        assert len(sim.holds) <= 4


async def test_closed_loop_begins_no_hold_after_warm_up(monkeypatch):
    """Twice as many clients as slots, each sending its next request when
    its last one ends: the queue is never empty, so nothing is held."""
    async with _Sim(monkeypatch, slots=3) as sim:
        eng = sim.engine
        done = [0]

        async def client(i):
            for r in range(6):
                await _collect(eng, [5 + i, 9 + r, 13], 9 + (i + r) % 5)
                done[0] += 1

        clients = [asyncio.ensure_future(client(i)) for i in range(6)]
        while done[0] < 6:
            await asyncio.sleep(0.002)
        # warm: every client has been served once, and the queue holds
        # the three that have no slot
        begun0, holds0 = eng.burst_hold["begun"], len(sim.holds)
        landed0 = dict(eng.first_tokens)
        while done[0] < 24:
            await asyncio.sleep(0.002)
        begun1, holds1 = eng.burst_hold["begun"], len(sim.holds)
        landed1 = dict(eng.first_tokens)
        await asyncio.gather(*clients)
    assert holds1 - holds0 >= 10  # cycles that asked
    assert begun1 - begun0 == 0
    # no hold, so none landed a first token: they came home at the top of
    # a cycle or on their slot's first burst, as they always did
    assert landed1["in_hold"] - landed0["in_hold"] == 0
    assert sum(landed1.values()) - sum(landed0.values()) >= 12
    assert eng.burst_hold["admissions_held"] == 0
    assert not any(held for _k, _n, held in sim.log)


async def _open_loop(sim, points: dict, when):
    """Seeded traffic arriving one request at a time beside two running
    streams; ``points`` (the waits of holds, or the reads of bursts) and
    ``when`` script their arrivals."""
    eng = sim.engine
    requests = [
        lambda: _collect(eng, [3, 5, 9, 13, 4], 19),
        lambda: _collect(eng, [17, 19], 10, temperature=0.8, seed=11),
        lambda: _collect(eng, [2, 4, 6], 7),
        lambda: _collect(eng, _long(13), 12, temperature=0.9, seed=3),
    ]
    for n, make in zip(when, requests):
        points[n] = (lambda make=make: sim.arrive(make))
    outs = await asyncio.gather(
        _collect(eng, [5, 9, 13], 61),
        _collect(eng, [7, 11, 2, 8], 58, temperature=0.7, seed=5),
    )
    return outs + await sim.arrivals()


async def test_open_loop_streams_the_parents_tokens(monkeypatch):
    """Request for request, the token ids of the parent's schedule: a
    slot's sampling depends on its seed and step, not on which cycle
    admitted it."""
    async with _Sim(monkeypatch, hold=False) as parent:
        want = await _open_loop(parent, parent.at_read, (3, 5, 8, 11))
    async with _Sim(monkeypatch) as sim:
        got = await _open_loop(sim, sim.at_hold, (2, 4, 7, 9))
    assert [len(o) for o in want] == [61, 58, 19, 10, 7, 12]
    assert got == want
    assert sim.engine.burst_hold["admissions_held"] == 4
    # and whichever way its first token came home: in the hold that saw
    # its prefill end, or on its slot's first burst; the parent's never in
    # a hold
    assert sim.engine.first_tokens["in_hold"] >= 2
    assert sum(sim.engine.first_tokens.values()) == 6
    assert parent.engine.first_tokens["in_hold"] == 0
    assert sum(parent.engine.first_tokens.values()) == 6
    assert parent.engine.burst_hold["admissions_held"] == 0
    assert parent.engine.burst_hold["begun"] == 0
    assert parent.engine.burst_hold["admissions"] == 6
    assert sim.engine.allocator.active_pages == 0


def _priority(priority) -> Context:
    return Context(headers={PRIORITY_HEADER: priority})


async def _preempting(sim, arrive, pressure):
    """A batch stream and an interactive one decode; ``arrive`` runs at
    the third wait of a hold."""
    eng = sim.engine
    seen = []  # (a hold was open, bursts in flight) at every preemption
    preempt = eng._preempt_batch_slot

    def watched_preempt(**kw):
        seen.append((eng._holding, len(eng._pipeline)))
        return preempt(**kw)

    eng._preempt_batch_slot = watched_preempt
    if pressure:
        sim.at_hold[3] = arrive
    reasons = []
    outs = await asyncio.gather(
        _collect(eng, [5, 9, 13], 29, ctx=_priority("batch"),
                 reasons=reasons),
        _collect(eng, [7, 11, 2, 8], 26, ctx=_priority("interactive"),
                 reasons=reasons),
    )
    return outs, await sim.arrivals(), reasons, seen


def _page_pressure(sim):
    # 40 tokens = 10 pages where the two streams have left fewer free
    return lambda: sim.arrive(lambda: _collect(
        sim.engine, _long(40), 5, ctx=_priority("interactive")))


def _slot_pressure(sim):
    # two arrivals in one pass, one free slot
    def both():
        for salt in (7, 11):
            sim.arrive(lambda salt=salt: _collect(
                sim.engine, _long(6, salt), 5, ctx=_priority("interactive")))
        return True
    return both


PREEMPTIONS = {
    # name: (what arrives, the reason counted, engine options)
    "pages": (_page_pressure, "interactive_pages", {"num_pages": 18}),
    "slots": (_slot_pressure, "interactive_admission", {"slots": 3}),
}


@pytest.mark.parametrize("name", list(PREEMPTIONS))
async def test_a_preemption_in_a_hold_ends_it_with_nothing_in_flight(
        name, monkeypatch):
    """An interactive arrival admitted in a hold that finds no pages (or,
    the second of two, no slot) pauses the batch stream, and a preemption
    flushes the pipeline. The hold ends there, the next burst is launched
    behind nothing, no overrun is read off a burst that is not there, and
    every stream finishes with the tokens it has without the pressure."""
    arrive, reason, opts = PREEMPTIONS[name]
    async with _Sim(monkeypatch) as easy:
        want = await _preempting(easy, arrive(easy), pressure=False)
    async with _Sim(monkeypatch, **opts) as sim:
        outs, arrived, reasons, seen = await _preempting(
            sim, arrive(sim), pressure=True)
    eng = sim.engine
    assert eng.preemptions == {reason: 1}
    # in a hold, under the one burst in flight
    assert seen[0] == (True, 1)
    assert reasons == ["length", "length"]  # no failed step
    assert outs == want[0]
    assert [len(o) for o in arrived] == [5] * len(arrived)
    # the burst after the flush was launched behind nothing, not held
    flushed = next(i for i, e in enumerate(sim.log) if e[0] == "read"
                   and sim.log[i - 1][0] != "decode")
    after = next(e for e in sim.log[flushed:] if e[0] == "decode")
    assert after == ("decode", 0, False)
    assert eng.burst_hold["overran"] == 0
    assert eng.burst_hold["admissions_held"] >= 1
    assert eng.allocator.active_pages == 0


GRAMMAR = grammar_from_request(
    {"response_format": {"type": "json_schema", "json_schema": {
        "name": "b", "schema": {"type": "boolean"}}}})
SYNC = {
    # name: what makes the arrival's admission read its logits on the host
    "logprobs": {"output_options": {"logprobs": 2}},
    "guided": {"guided": {**GRAMMAR, "prompt_len": 5}},
}


@pytest.mark.parametrize("name", list(SYNC))
async def test_an_arrival_that_reads_its_logits_on_the_host_is_not_held_for(
        name, monkeypatch):
    """Logprobs, a grammar: the pass would block the step thread on the
    prefill's logits behind the running burst, past the deadline. The
    arrival ends the hold unadmitted; the held burst is launched, the
    running one read, and the next cycle's pass admits it, as it does an
    arrival under the blocked read without a hold."""
    vocab = TokenVocab.ascii_json(SPEC.vocab_size)
    runs = []
    for hold, points in ((False, "at_read"), (True, "at_hold")):
        async with _Sim(monkeypatch, hold=hold, guided_vocab=vocab) as sim:
            eng = sim.engine
            mark = []

            def arrive(sim=sim, eng=eng, mark=mark):
                mark.append(len(sim.log))
                return sim.arrive(lambda: _collect(
                    eng, [3, 5, 9, 13, 4], 6, **SYNC[name]))

            getattr(sim, points)[4] = arrive
            outs = await _two_streams(sim, (41, 37))
            runs.append((outs, await sim.arrivals(), sim.log[mark[0]:][:4],
                         dict(eng.burst_hold), list(sim.woken)))
    (want, want_arrived, parent_log, _, _), (
        got, arrived, log, counted, woken) = runs
    assert (got, arrived) == (want, want_arrived)
    assert woken[3] is True and len(arrived[0]) >= 1
    # from the arrival on: the held burst's launch, the read, then the
    # prefill in the next cycle's pass, not in the hold
    assert [e[0] for e in log[:3]] == ["decode", "read", "prefill"]
    assert log[2] == ("prefill", 1, False)
    # the parent's order from an arrival under the blocked read, which
    # has the launch and the read's start behind it already
    assert parent_log[0] == log[2]
    assert counted["admissions_held"] == 0 and counted["overran"] == 0


async def test_close_during_a_hold_ends_it_at_once(monkeypatch):
    """close() raises its flag and sets the wake event: the hold ends on
    that wake, admits nothing more, and the thread leaves as it does from
    a blocked read: the held burst launched, the running one read."""
    closing = []
    sim = _Sim(monkeypatch)
    eng = sim.engine

    def close_now():
        closing.append(len(sim.log))
        fut = asyncio.run_coroutine_threadsafe(eng.close(), sim.loop)
        closing.append(fut)
        # the very wait a hold makes, on the real event: close() sets it
        return threading.Event.wait(eng._wake, 30)

    sim.at_hold[3] = close_now
    sim.loop = asyncio.get_running_loop()
    await eng.start()
    outs, reasons = [[], []], []
    await asyncio.gather(
        _collect(eng, [5, 9, 13], 61, out=outs[0], reasons=reasons),
        _collect(eng, [7, 11, 2, 8], 58, out=outs[1], reasons=reasons),
    )
    await asyncio.wrap_future(closing[1])
    assert sim.woken[2] is True  # woken, not timed out
    assert sim.hold_waits == 3  # and no wait after it
    assert not eng._thread.is_alive()
    # the cycle's remainder, then the loop's exit: one launch, the reads
    assert [e[0] for e in sim.log[closing[0]:]] == ["decode", "read", "read"]
    assert reasons == ["error", "error"]  # failed over, as any close does
    assert all(0 < len(o) < 58 for o in outs)


async def test_cancel_during_a_hold_lands_in_the_next_cycle(monkeypatch):
    """A client's cancel sets no event: the hold runs to its deadline and
    the cycle after it flushes and finishes the stream. From the cancel on:
    the held burst's launch, the reads, the finish; as from a cancel under
    the blocked read, which had that launch behind it already."""
    ctx, mark = Context(), []
    async with _Sim(monkeypatch) as sim:
        eng = sim.engine

        def cancel():
            ctx.stop_generating()
            mark.append(len(sim.log))
            sim.t += 1e-3  # and nothing wakes the thread
            return False

        sim.at_hold[3] = cancel
        a, b, reasons = [], [], []
        await asyncio.gather(
            _collect(eng, [5, 9, 13], 61, out=a, ctx=ctx, reasons=reasons),
            _collect(eng, [7, 11, 2, 8], 30, out=b),
        )
        after = [e[0] for e in sim.log[mark[0]:mark[0] + 3]]
    assert reasons == ["cancelled"] and len(b) == 30
    # no second wait in that hold (a stopped slot closes it), one launch,
    # then the flush of both bursts in flight
    assert sim.woken[2] is False and sim.woken[:2] == [False, False]
    assert after == ["decode", "read", "read"]
    assert len(a) < 61


async def test_a_deadlines_stop_wakes_the_hold(monkeypatch):
    """The stop of a request past its deadline sets the wake event (it is
    the event loop's, generate()): the hold ends there and then."""
    ctx = Context()
    async with _Sim(monkeypatch) as sim:
        eng = sim.engine

        def stop():
            ctx.stop_generating()
            eng._wake.set()  # what generate() does on a deadline
            return threading.Event.wait(eng._wake, 30)

        sim.at_hold[3] = stop
        reasons = []
        await asyncio.gather(
            _collect(eng, [5, 9, 13], 61, ctx=ctx, reasons=reasons),
            _collect(eng, [7, 11, 2, 8], 30),
        )
    assert reasons == ["cancelled"]
    assert sim.woken[2] is True


async def test_no_estimate_no_hold(monkeypatch):
    """A read that never blocks says nothing of when a burst ends, so no
    burst is ever timed and none is held."""
    async with _Sim(monkeypatch) as sim:
        monkeypatch.setattr(core, "_is_ready", lambda dev: True)
        outs = await _two_streams(sim, (33, 30))
    assert [len(o) for o in outs] == [33, 30]
    assert sim.engine._burst_secs == {}
    assert sim.engine._burst_ended is None
    assert len(sim.holds) >= 5 and not any(h[0] for h in sim.holds)
    assert sim.engine.burst_hold["begun"] == 0
    assert sim.hold_waits == 0


async def test_overrun_is_counted_and_the_burst_is_timed_anew(monkeypatch):
    """A hold at whose end the running burst is already done: the device
    idles while the held burst is launched, and the counter says so. The
    times kept for that burst length go: were bursts shorter than they
    say, every second cycle would overrun again and no two reads in a row
    would block to correct them. The two cycles after an overrun hold
    nothing and time a burst; then holds begin again."""
    async with _Sim(monkeypatch) as sim:
        decided = []  # how many cycles had asked when the thread overslept

        def oversleep():
            decided.append(len(sim.holds))
            sim.t += 5 * BURST  # the thread was away far past the deadline
            return False

        sim.at_hold[3] = sim.at_hold[5] = oversleep
        await _two_streams(sim)
    assert sim.engine.burst_hold["overran"] == 2
    for n in decided:
        assert [h[0] for h in sim.holds[n:n + 4]] == [
            True, False, False, True]
    assert sim.engine._burst_secs[4]


async def test_bursts_grown_shorter_overrun_once(monkeypatch):
    """Fewer live slots, a shorter context: a burst takes half of what the
    times kept say. The first hold after that overruns; the times go, the
    next two cycles time the shorter burst, and no hold overruns again."""
    async with _Sim(monkeypatch) as sim:
        sim.at_read[6] = lambda: setattr(sim, "burst", BURST / 2)
        await _two_streams(sim)
    eng = sim.engine
    assert eng.burst_hold["overran"] == 1
    assert list(eng._burst_secs[4]) == pytest.approx([BURST / 2] * 4)
    assert eng.burst_hold["begun"] >= 10


async def _flushed(sim):
    ctx = Context()
    sim.at_read[4] = ctx.stop_generating
    return await asyncio.gather(
        _collect(sim.engine, [5, 9, 13], 41, ctx=ctx),
        _collect(sim.engine, [7, 11, 2, 8], 37),
    )


BEHIND_NOTHING = {
    "after_a_flush": _flushed,
    "the_synchronous_schedule": _guided_live,
}


@pytest.mark.parametrize("name", list(BEHIND_NOTHING))
async def test_a_burst_launched_behind_nothing_times_no_burst(
        name, monkeypatch):
    """Behind nothing a burst starts at its launch, not where the burst
    before it ended (the first after a flush, every burst of the
    synchronous schedule): the time from the last known end to its own is
    no burst's length, and would read the thread's time between the two
    into the estimate. Every time kept is a burst's."""
    monkeypatch.setattr(core, "_BURST_SAMPLES", 1000)  # keep them all
    async with _Sim(monkeypatch) as sim:
        await BEHIND_NOTHING[name](sim)
        kept = [x for v in sim.engine._burst_secs.values() for x in v]
    launched_bare = sum(e[:2] == ("decode", 0) for e in sim.log)
    assert launched_bare >= 2 and len(kept) >= 2
    assert kept == pytest.approx([BURST] * len(kept))


# -- the hold lands ---------------------------------------------------------


def _held_prefills(sim):
    """Where in the log the prefills launched in a hold stand."""
    return [i for i, e in enumerate(sim.log) if e == ("prefill", 1, True)]


async def _arrival_held(sim, n=9, at=3, **more):
    """Two streams decode and one request arrives at the ``at``-th wait of
    a hold: its prefill stands behind the running burst, and ends early in
    the hold of the burst after."""
    eng = sim.engine
    sim.points[at] = lambda: sim.arrive(
        lambda: _collect(eng, [3, 5, 9, 13, 4], n, **more))
    outs = await _two_streams(sim, (41, 37))
    return outs, (await sim.arrivals())[0]


async def test_a_first_token_comes_home_in_the_hold_that_sees_its_prefill_end(
        monkeypatch):
    """The prefill of an arrival admitted in a hold stands before the held
    burst and ends a fifth of a burst into the next hold: that hold posts
    the first token from the wave's own download, a look after the
    sample's end, and not a burst later from the read of the slot's first
    burst; then it waits on to its deadline as any hold does."""
    async with _Sim(monkeypatch, hold=False) as parent:
        want = await _arrival_held(parent)
    async with _Sim(monkeypatch) as sim:
        got = await _arrival_held(sim)
    assert got == want
    eng = sim.engine
    (_where, before, when), = [x for x in sim.landed if x[0] == "in_hold"]
    # the launch before it is the burst the slot was fed into, the held
    # one; its read is two reads on (the running burst's comes first)
    prefill, = _held_prefills(sim)
    assert [e[0] for e in sim.log[prefill:before]] == [
        "prefill", "decode", "read"]
    assert [e[0] for e in sim.log[before:before + 3]] == [
        "decode", "read", "decode"]
    # posted within a look of the sample's end; the read that carries it
    # on the parent returns a burst after that end
    ended = [end for _dev, end in sim.ends.values() if end <= when][-1]
    assert 0 <= when - ended <= POLL
    assert eng.first_tokens == {"in_hold": 1, "at_step": 0, "on_burst": 2}
    assert parent.engine.first_tokens == {
        "in_hold": 0, "at_step": 0, "on_burst": 3}
    # the hold went on to its deadline: nothing overran, no look was made
    # with no wave on its way, and the first token was not landed twice
    assert eng.burst_hold["overran"] == 0
    assert sim.polls >= 4 and not any(sim.polled)
    assert eng._admit_waves == []
    assert eng.allocator.active_pages == 0


async def test_a_sample_not_ready_at_the_deadline_lands_from_the_fed_column(
        monkeypatch):
    """A prompt whose prefill outlasts the next hold: every look finds the
    sample on its way, the hold ends at its deadline as it would have, and
    the first token comes home as column 0 of the read of its slot's first
    burst, as on the parent."""
    runs = []
    for hold in (False, True):
        async with _Sim(monkeypatch, hold=hold) as sim:
            sim.points[2] = lambda sim=sim: setattr(
                sim, "prefill", 1.5 * BURST)
            runs.append((await _arrival_held(sim), sim))
    (want, parent), (got, sim) = runs
    assert got == want
    assert sim.engine.first_tokens == {
        "in_hold": 0, "at_step": 0, "on_burst": 3}
    assert parent.engine.first_tokens == sim.engine.first_tokens
    # looked for through two holds (its own, and the next to its deadline)
    assert sim.polls >= 8 and not any(sim.polled)
    assert sim.engine.burst_hold["overran"] == 0
    _where, before, _when = sim.landed[-1]
    assert sim.log[before - 1][0] == "read"


async def test_a_prefill_admitted_in_this_hold_is_never_read_by_force(
        monkeypatch):
    """No burst covers a slot admitted in this hold (its burst is the held
    one, not yet built) and the hold looks at its wave many times: were
    looking to age the wave, the second look would read it by force and
    block the thread behind the running burst, past the deadline. Every
    read of a wave's own download finds it ready, and the wave is as young
    at the hold's end as at its admission."""
    async with _Sim(monkeypatch) as sim:
        eng = sim.engine
        reads, ages = [], []
        direct = eng._materialize_one

        def watched_one(ap, **kw):
            if kw.get("fed_col") is None:
                reads.append((sim.is_ready(ap["dev"]), sim.in_hold))
            return direct(ap, **kw)

        eng._materialize_one = watched_one
        looks = {}

        def look():
            ages.append([w["age"] for w in eng._admit_waves])
            looks[len(sim.holds)] = looks.get(len(sim.holds), 0) + 1
            sim.t += POLL
            return False

        def arrive():
            for n in range(1, 40):
                sim.at_poll[sim.polls + n] = look
            return sim.arrive(lambda: _collect(eng, [3, 5, 9, 13, 4], 9))

        sim.at_hold[3] = arrive
        await _two_streams(sim, (41, 37))
        await sim.arrivals()
    # the hold that admitted it looked many times and read nothing
    assert max(looks.values()) >= 5
    first = min(looks)
    assert ages[:looks[first]] == [[0]] * looks[first]
    assert reads == [(True, True)]  # the next hold's, the sample ready
    held, = _held_prefills(sim)
    assert sim.log[held + 1][0] == "decode"  # launched, nothing read between
    assert eng.burst_hold["overran"] == 0


async def test_an_arrival_and_a_ready_wave_at_one_wake_the_arrival_first(
        monkeypatch):
    """A wake that finds a request waiting AND a wave whose sample has
    ended: the admission pass comes first (its prefill is launched behind
    the running burst), the first token is posted after it, in the same
    hold."""
    async with _Sim(monkeypatch, slots=5) as sim:  # one stays free
        eng = sim.engine

        def both():
            wave, = eng._admit_waves
            sim.t = max(sim.t, sim.ends[id(wave["dev"])][1])
            return sim.arrive(lambda: _collect(eng, [17, 19, 4], 7))

        def first():
            # its prefill stands behind the running burst: the next read
            # is that burst's, and the first look after it is made in the
            # hold that will see the sample end
            sim.at_read[sim.reads + 1] = lambda: sim.at_poll.update(
                {sim.polls + 1: both})
            return sim.arrive(lambda: _collect(eng, [3, 5, 9, 13, 4], 9))

        sim.at_hold[3] = first
        outs = await _two_streams(sim, (41, 37))
        arrived = await sim.arrivals()
    assert [len(o) for o in outs + arrived] == [41, 37, 9, 7]
    assert sim.polled.count(True) == 1
    where, before, _when = sim.landed[2]
    assert where == "in_hold"
    # the launch before the landing is the second arrival's prefill, made
    # in that hold, and the launch after it the held burst
    assert _held_prefills(sim)[1] == before - 1
    assert sim.log[before][0] == "decode"
    assert eng.burst_hold["admissions_held"] == 2
    assert eng.first_tokens["in_hold"] == 2  # the second's, a hold later
    assert eng.burst_hold["overran"] == 0


ENDS_AT_ONCE = {
    # name: what ends the stream at its first token, given that token
    "a_stop_id": lambda tok: {"stop_conditions": {
        "max_tokens": 9, "ignore_eos": True, "stop_token_ids": [tok]}},
    "a_budget_of_one_token": lambda tok: {"stop_conditions": {
        "max_tokens": 1, "ignore_eos": True}},
}


@pytest.mark.parametrize("name", list(ENDS_AT_ONCE))
async def test_a_first_token_that_ends_its_stream_in_a_hold_frees_the_slot(
        name, monkeypatch):
    """A stop id, a budget of one token: the stream is finished in the
    hold as it is at the top of a cycle, its slot and pages are free from
    then on, and what the burst in flight computed for the slot is
    discarded at its read (the request id guards it)."""
    async with _Sim(monkeypatch, hold=False) as parent:
        want, full = await _arrival_held(parent)
    reasons, seen = [], []
    async with _Sim(monkeypatch) as sim:
        eng = sim.engine
        land = eng._land_first_token

        def watched_land(slot_idx, slot, tok, where):
            pages = eng.allocator.active_pages
            land(slot_idx, slot, tok, where)
            seen.append((where, eng._slots[slot_idx] is None,
                         len(eng._pipeline),
                         pages - eng.allocator.active_pages))

        eng._land_first_token = watched_land
        got, ended = await _arrival_held(
            sim, reasons=reasons, **ENDS_AT_ONCE[name](full[0]))
    assert got == want and ended == full[:1]
    assert reasons == ["stop" if name == "a_stop_id" else "length"]
    where, freed, in_flight, released = seen[2]
    assert (where, freed, in_flight) == ("in_hold", True, 1)
    assert released >= 1  # its pages went with it
    assert eng.first_tokens["in_hold"] == 1
    assert eng.burst_hold["overran"] == 0
    assert eng.allocator.active_pages == 0


def test_the_landing_of_a_hold_asks_and_never_insists():
    """On a stopped engine: a wave that says ready lands, one that does
    not is kept as young as it was, one whose slot is gone is dropped
    unread; what is returned is whether a live wave is still on its way."""
    engine, _now = _engine_at(0.0)

    class Sample:
        def __init__(self, ready):
            self.ready, self.reads = ready, 0

        def is_ready(self):
            return self.ready

        def __array__(self, dtype=None, copy=None):
            self.reads += 1
            return np.asarray([7, 9], np.int32)

    landed = []

    def land(i, slot, tok, where):
        landed.append((i, tok, where))
        slot.first_pending = False

    engine._land_first_token = land
    slots = [_StubSlot() for _ in range(3)]
    for i, s in enumerate(slots):
        s.first_pending = True
        engine._slots[i] = s
    gone = _StubSlot()
    gone.first_pending = True
    ready, late, dead = Sample(True), Sample(False), Sample(True)
    engine._admit_waves = [
        {"dev": ready, "recs": [(0, slots[0], 1), (1, slots[1], 0)],
         "fed": set(), "age": 0},
        {"dev": late, "recs": [(2, slots[2], 0)], "fed": set(), "age": 1},
        {"dev": dead, "recs": [(3, gone, 0)], "fed": set(), "age": 5},
    ]
    assert engine._land_ready_waves() is True
    assert landed == [(0, 9, "in_hold"), (1, 7, "in_hold")]
    assert (ready.reads, late.reads, dead.reads) == (1, 0, 0)
    assert [(w["dev"], w["age"]) for w in engine._admit_waves] == [(late, 1)]
    for _ in range(5):
        assert engine._land_ready_waves() is True
    assert (late.reads, engine._admit_waves[0]["age"]) == (0, 1)
    late.ready = True
    assert engine._land_ready_waves() is False
    assert landed[-1] == (2, 7, "in_hold") and engine._admit_waves == []


# -- the arithmetic, on a stopped engine ------------------------------------


def _engine_at(t):
    engine = InferenceEngine(SPEC, _cfg())
    now = [t]
    engine._clock = lambda: now[0]
    return engine, now


def _burst(n_burst=4, side=0):
    return {"batch": {"n_burst": n_burst}, "side": side}


NOTES = {
    # name: (burst, the read blocked, the read before it ended at,
    #        the sample taken, what the newest end then reads)
    "timed": (_burst(), True, 10.0, 1.5, 11.5),
    "the_read_found_it_done": (_burst(), False, 10.0, None, None),
    "no_end_before_it": (_burst(), True, None, None, 11.5),
    "a_prefill_between": (_burst(side=2), True, 10.0, None, 11.5),
}


@pytest.mark.parametrize("name", list(NOTES))
def test_a_burst_is_timed_only_between_two_known_ends(name):
    pending, blocked, before, sample, ended = NOTES[name]
    engine, _now = _engine_at(11.5)
    engine._burst_ended = before
    engine._note_burst_end(pending, blocked)
    assert engine._burst_ended == ended
    assert {n: list(v) for n, v in engine._burst_secs.items()} == (
        {4: [sample]} if sample is not None else {})


def test_the_estimate_is_the_smallest_of_the_last_few():
    engine, now = _engine_at(0.0)
    engine._burst_ended = 0.0
    for dt in (1.0, 1.3, 0.9, 1.2, 1.1, 1.4):
        now[0] += dt
        engine._note_burst_end(_burst(), True)
    assert list(engine._burst_secs[4]) == pytest.approx([0.9, 1.2, 1.1, 1.4])
    engine._pipeline = [_burst()]
    engine._launch_secs.extend((0.02, 0.05, 0.03))
    engine._admit_secs.extend((0.04, 0.01, 0.9, 0.02))  # one stall among them
    # the end before it + the smallest burst - (the median launch + the
    # median pass + a step)
    assert engine._hold_deadline() == pytest.approx(
        now[0] + 0.9 - (0.03 + 0.03 + 0.9 / 4))


def test_what_stands_before_a_burst_is_timed_a_launch_and_added():
    """A burst behind two launches (a prefill and its sample): what the
    interval holds beyond the shortest burst, a launch; the running burst
    ends that much later a launch of its own, by the least seen lately."""
    engine, now = _engine_at(10.0)
    engine._burst_ended = 10.0
    now[0] = 11.3
    engine._note_burst_end(_burst(side=2), True)
    assert not engine._side_secs  # no burst of that length timed yet
    engine._burst_secs[4] = collections.deque([1.0, 1.1])
    for dt, side in ((1.3, 2), (1.2, 2), (1.9, 4), (0.95, 2)):
        now[0] += dt
        engine._note_burst_end(_burst(side=side), True)
    # the last left nothing over the shortest burst: no sample of it
    assert list(engine._side_secs) == pytest.approx([0.15, 0.1, 0.225])
    assert set(engine._burst_secs) == {4} and len(engine._burst_secs[4]) == 2
    engine._launch_secs.append(0.0)
    engine._pipeline = [_burst(side=0)]
    bare = engine._hold_deadline()
    engine._pipeline = [_burst(side=2)]
    assert engine._hold_deadline() == pytest.approx(bare + 2 * 0.1)


DEADLINES = {
    "nothing_in_flight": lambda e: e._pipeline.clear(),
    "the_last_read_did_not_block": lambda e: setattr(e, "_burst_ended", None),
    "a_length_not_timed": lambda e: e._pipeline.__setitem__(0, _burst(8)),
    "no_launch_timed": lambda e: e._launch_secs.clear(),
}


@pytest.mark.parametrize("name", [None, *DEADLINES])
def test_no_deadline_without_its_measurements(name):
    engine, _now = _engine_at(20.0)
    engine._pipeline = [_burst()]
    engine._burst_ended = 19.5
    engine._burst_secs[4] = collections.deque([1.0])
    engine._launch_secs.append(0.01)
    if name is None:
        assert engine._hold_deadline() == pytest.approx(
            19.5 + 1.0 - 0.01 - 0.25)
        return
    DEADLINES[name](engine)
    assert engine._hold_deadline() is None
    assert engine._hold_queued_burst() is False


class _StubSlot:
    spec = guided = None
    first_pending = False

    def __init__(self, stopped=False):
        self.context = Context()
        if stopped:
            self.context.stop_generating()


class _ActiveSpec:
    active = True


def _spec_managed():
    slot = _StubSlot()
    slot.spec = _ActiveSpec()
    return slot


SHUT = {
    "nothing_in_flight": lambda e: e._pipeline.clear(),
    "partial_open": lambda e: setattr(e, "_partial", object()),
    "the_cycle_that_closed_a_partial": lambda e: setattr(
        e, "_chunk_cycle", True),
    "closed": lambda e: setattr(e, "_closed", True),
    "draining": lambda e: e.begin_drain(),
    "clear_cache_requested": lambda e: e.request_clear_cache(),
    "spmd_followers": lambda e: setattr(e, "spmd", object()),
    "no_free_slot": lambda e: e._slots.__setitem__(
        slice(None), [_StubSlot() for _ in e._slots]),
    "a_cancelled_slot": lambda e: e._slots.__setitem__(
        1, _StubSlot(stopped=True)),
    "a_spec_managed_slot": lambda e: e._slots.__setitem__(1, _spec_managed()),
    "a_guided_slot": lambda e: setattr(e, "_guided_live", lambda: True),
}


@pytest.mark.parametrize("name", [None, *SHUT])
def test_the_hold_is_open_only_while_an_arrival_would_be_admitted(name):
    engine, _now = _engine_at(20.0)
    engine._pipeline = [_burst()]
    engine._slots[0] = _StubSlot()
    assert engine._hold_open()
    if name is not None:
        SHUT[name](engine)
        assert not engine._hold_open()


def test_synchronous_admissions_are_not_held_for():
    engine = InferenceEngine(SPEC, _cfg(async_admissions=False))
    engine._pipeline = [_burst()]
    assert not engine._hold_open()


COUNTERS = {
    "burst_hold": {"begun": 7, "overran": 1, "admissions": 5,
                   "admissions_held": 3},
    "decode_bursts": {"full": 2, "short": 9, "single": 4},
    "first_tokens": {"in_hold": 6, "at_step": 1, "on_burst": 2},
}


@pytest.mark.parametrize("family", list(COUNTERS))
def test_the_counters_are_in_the_snapshot_and_reset_with_it(family):
    engine, _now = _engine_at(0.0)
    assert set(getattr(engine, family)) == set(COUNTERS[family])
    getattr(engine, family).update(COUNTERS[family])
    snap = engine.profile_snapshot()
    assert {k: v["calls"] for k, v in snap.items()
            if k.startswith(family + ".")} == {
        f"{family}.{k}": n for k, n in COUNTERS[family].items()}
    engine.reset_profile_window()
    assert set(getattr(engine, family).values()) == {0}


def test_a_launch_made_in_a_hold_says_so(monkeypatch):
    """``held=1`` on the ``engine.launch`` annotation of a program launched
    during a hold, and on no other."""
    notes = []

    class Note:
        def __init__(self, name, **kw):
            notes.append((name, kw))

    engine = InferenceEngine(SPEC, _cfg(profile=True))
    monkeypatch.setattr(core.jax.profiler, "TraceAnnotation", Note)
    engine._launch("prefill", tokens=5, rows=1, ahead=1)
    engine._holding = True
    engine._launch("prefill", tokens=5, rows=1, ahead=1)
    engine._holding = False
    engine._launch("decode", steps=4, live=2, slots=4, ahead=1)
    assert [kw.get("held") for _n, kw in notes] == [None, 1, None]
    assert {n for n, _kw in notes} == {"engine.launch"}


# -- how long a burst is, on the serving schedule ---------------------------


class _Constraining:
    """A grammar cursor that constrains: its mask is good for one token."""

    constraining = True

    def mask(self):
        return np.ones((SPEC.vocab_size,), bool)


def _live_slot(i, *, seq_len=10, guided=None) -> core._Slot:
    rid = f"r{i}"
    return core._Slot(
        request_id=rid, context=Context(), out_q=None, seq=None,
        pages=SeqPages(rid), seq_len=seq_len, remaining=50, guided=guided,
    )


CAP = _cfg().max_context
RULE = {
    # name: (live slots of 4, a request waits, engine options, what the
    #        slots are made with, the steps the burst takes)
    "empty_queue_free_slot": (3, False, {}, {}, 4),
    "empty_queue_one_stream": (1, False, {}, {}, 4),
    "empty_queue_no_free_slot": (4, False, {}, {}, 8),
    "waiter_under_half": (1, True, {}, {}, 4),
    "waiter_at_half": (2, True, {}, {}, 8),
    "waiter_over_half": (3, True, {}, {}, 8),
    "waiter_no_free_slot": (4, True, {}, {}, 8),
    "no_short_length": (1, False, {"decode_steps_admit_pending": 0}, {}, 8),
    "no_short_length_waiter": (
        1, True, {"decode_steps_admit_pending": 0}, {}, 8),
    "a_constraining_guided_slot": (
        2, False, {}, {"guided": _Constraining()}, 1),
    # six tokens of room under a full batch: 8 rounds down to 4, and three
    # under a short burst to 1: a length that was compiled, never 6 or 3
    "room_to_the_cap_rounds_a_full_burst_down": (
        4, False, {}, {"seq_len": CAP - 6}, 4),
    "room_to_the_cap_rounds_a_short_burst_down": (
        2, False, {}, {"seq_len": CAP - 3}, 1),
}


@pytest.mark.parametrize("name", list(RULE))
def test_a_burst_is_as_long_as_what_an_arrival_would_meet(name):
    """Short (4 of 8) while the queue is empty beside a free slot or a
    request waits under half occupancy; full with a backlog beside half
    the slots or more, or with no free slot; then cut to a compiled
    length by a grammar's mask and by the room to the context cap."""
    live, waiter, opts, made, want = RULE[name]
    engine = InferenceEngine(SPEC, _cfg(steps=8, **opts))
    assert engine._burst_lengths == (
        [1, 8] if opts.get("decode_steps_admit_pending") == 0 else [1, 4, 8])
    for i in range(live):
        # what a case makes its slots with goes to the last of them
        engine._slots[i] = _live_slot(i, **(made if i == live - 1 else {}))
    if waiter:
        engine._waiting.put_nowait(
            core._Waiting(request={}, context=Context(), out_q=None))
    batch = engine._build_batch(None)
    assert batch["n_burst"] == want
    assert int(batch["active"].sum()) == live
    assert (batch["allowed"] is not None) == ("guided" in made)


async def test_trickled_arrivals_ride_short_bursts_and_holds_go_on(
        monkeypatch):
    """Arrivals one at a time beside two running streams, on bursts of 8
    with a short length of 4: request for request the tokens of the engine
    that knows only 8 (the same programs' steps, cut elsewhere), the
    counters say what was dispatched at each length, and once a 4-step
    burst has been timed the hold begins behind it and admits in it."""
    async with _Sim(monkeypatch, hold=False, steps=8,
                    decode_steps_admit_pending=0) as eight:
        want = await _open_loop(eight, eight.at_read, (2, 3, 5, 7))
    async with _Sim(monkeypatch, steps=8) as sim:
        got = await _open_loop(sim, sim.at_hold, (2, 3, 6, 8))
    assert [len(o) for o in want] == [61, 58, 19, 10, 7, 12]
    assert got == want
    assert sim.engine.first_tokens["in_hold"] >= 2
    assert eight.engine._burst_lengths == [1, 8]
    assert set(eight.steps) == {8}
    assert eight.engine.decode_bursts == {
        "full": len(eight.steps), "short": 0, "single": 0}
    eng = sim.engine
    assert eng._burst_lengths == [1, 4, 8]
    assert eng.decode_bursts == {
        "full": sim.steps.count(8), "short": sim.steps.count(4),
        "single": sim.steps.count(1)}
    assert sum(eng.decode_bursts.values()) == len(sim.steps)
    # two streams of four slots and a queue that empties on the wake:
    # short; all four slots taken for a while: full
    assert eng.decode_bursts["short"] >= 8
    assert eng.decode_bursts["full"] >= 1
    assert eng.decode_bursts["single"] == 0
    # the 4-step burst was timed at what four steps take here, holds
    # began behind it and every scripted arrival was admitted in one: its
    # prefill stands directly behind a running 4-step burst
    assert list(eng._burst_secs[4]) == pytest.approx(
        [BURST / 2] * len(eng._burst_secs[4]))
    assert eng.burst_hold["begun"] >= 8
    assert eng.burst_hold["admissions_held"] == 4
    assert eng.burst_hold["overran"] == 0
    launches = sim.launches()
    held = [i for i, e in enumerate(launches) if e == ("prefill", 1, True)]
    assert len(held) == 4
    for i in held:
        n_before = sum(e[0] == "decode" for e in launches[:i])
        assert sim.steps[n_before - 1] == 4  # the running burst
    assert eng.allocator.active_pages == 0
