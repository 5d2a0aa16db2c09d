"""Deterministic fault injection, end-to-end deadlines, graceful drain.

Tier-1 coverage for the robustness layer (runtime/faults.py):
  - the acceptance contract: same DYN_FAULTS spec + seed => identical
    fault schedule; different seed => different schedule;
  - injection sites behave like the real failure (transport drop ==
    connection death -> StreamError -> migration re-drives);
  - end-to-end deadlines propagate frontend -> wire -> worker and bound
    admission, generation, and migration retries;
  - draining/saturated workers refuse with ServiceUnavailable -> HTTP
    503 + Retry-After; deadline exhaustion -> 504;
  - EndpointServer.stop force-cancels streams that outlive the drain
    timeout instead of hanging;
  - fault-trip counters are visible on every /metrics surface.
"""

import asyncio
import time

import aiohttp
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.runtime.context import (
    Context,
    DeadlineExceeded,
    ServiceUnavailable,
    StreamError,
)
from dynamo_tpu.runtime.faults import (
    FAULTS,
    FaultInjected,
    FaultRegistry,
    parse_spec,
)
from dynamo_tpu.runtime.metrics import MetricsRegistry
from dynamo_tpu.runtime.transport import EndpointServer, InstanceChannel

TINY = ModelSpec.tiny()


def _engine_cfg(**kw) -> EngineConfig:
    base = dict(
        page_size=4, num_pages=128, max_pages_per_seq=16,
        max_decode_slots=2, prefill_buckets=(16, 32),
    )
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(autouse=True)
def _clean_global_faults():
    """Every test leaves the process-wide registry empty."""
    yield
    FAULTS.clear()


# -- spec + schedule determinism (acceptance criterion) ----------------------


def test_spec_parsing_grammar():
    rules = parse_spec(
        "transport.send:drop@0.02,hub.fsync:delay=50ms,"
        "engine.step:error@0.001,disagg.pull:error@1x1"
    )
    by_site = {r.site: r for r in rules}
    assert by_site["transport.send"].action == "drop"
    assert by_site["transport.send"].prob == 0.02
    assert by_site["hub.fsync"].action == "delay"
    assert by_site["hub.fsync"].delay_s == pytest.approx(0.05)
    assert by_site["engine.step"].prob == 0.001
    assert by_site["disagg.pull"].limit == 1
    with pytest.raises(ValueError):
        parse_spec("site_without_action")
    with pytest.raises(ValueError):
        parse_spec("x:explode")
    with pytest.raises(ValueError):
        parse_spec("x:delay")  # delay needs =duration


def test_same_spec_and_seed_reproduce_identical_schedule():
    spec = "transport.send:drop@0.3,engine.step:error@0.1"

    def schedule(seed):
        reg = FaultRegistry(spec, seed=seed)
        return [
            (
                reg.decide("transport.send") is not None,
                reg.decide("engine.step") is not None,
            )
            for _ in range(300)
        ]

    a, b = schedule(42), schedule(42)
    assert a == b, "same spec+seed must replay the same fault schedule"
    assert sum(s for s, _ in a) > 0, "p=0.3 over 300 draws must trip"
    assert schedule(43) != a, "a different seed must give a different schedule"


def test_partition_spec_parsing_and_matching():
    """transport.partition grammar: address-pair scoped, symmetric (A|B)
    or one-way (A>B), round-trips through spec(), and only matching
    directed links are cut."""
    reg = FaultRegistry(
        "transport.partition:drop=10.0.0.1:7701|10.0.0.2:7701,"
        "transport.partition:drop=a:1>b:2",
        seed=1,
    )
    site = "transport.partition"
    # symmetric: both directions cut
    assert reg.link_blocked(site, "10.0.0.1:7701", "10.0.0.2:7701")
    assert reg.link_blocked(site, "10.0.0.2:7701", "10.0.0.1:7701")
    # one-way: a->b cut, b->a flows
    assert reg.link_blocked(site, "a:1", "b:2")
    assert not reg.link_blocked(site, "b:2", "a:1")
    # unrelated pairs untouched
    assert not reg.link_blocked(site, "c:3", "b:2")
    specs = {r.spec() for rs in reg._rules.values() for r in rs}
    assert "transport.partition:drop=10.0.0.1:7701|10.0.0.2:7701" in specs
    assert "transport.partition:drop=a:1>b:2" in specs
    # trips are counted like every other fault (chaos runs assert on them)
    assert reg.trip_counts[(site, "drop")] >= 3
    # pair-scoped rules never fire through the pairless decide() path
    assert reg.decide(site) is None
    # grammar errors are loud
    with pytest.raises(ValueError):
        parse_spec("transport.partition:drop")  # needs a pair
    with pytest.raises(ValueError):
        parse_spec("transport.partition:delay=5ms")  # drop only
    with pytest.raises(ValueError):
        parse_spec("transport.partition:drop=a:1>")  # both addresses


def test_partition_probabilistic_schedule_is_seeded():
    """A flaky link (prob < 1) draws from the same seeded per-site stream
    as every other rule: same spec+seed => same block schedule."""
    spec = "transport.partition:drop=a:1|b:2@0.4"

    def schedule(seed):
        reg = FaultRegistry(spec, seed=seed)
        return [
            reg.link_blocked("transport.partition", "a:1", "b:2")
            for _ in range(200)
        ]

    a = schedule(9)
    assert a == schedule(9)
    assert any(a) and not all(a)
    assert schedule(10) != a


async def test_fire_link_raises_drop():
    from dynamo_tpu.runtime.faults import FaultDrop

    reg = FaultRegistry("transport.partition:drop=a:1|b:2", seed=0)
    with pytest.raises(FaultDrop):
        await reg.fire_link("transport.partition", "b:2", "a:1")
    # a healthy link passes through untouched
    await reg.fire_link("transport.partition", "a:1", "c:3")


def test_schedule_per_site_is_interleaving_independent():
    """The decision stream at one site is a pure function of (spec, seed,
    call index at that site) — calls at OTHER sites must not shift it."""
    spec = "a.site:drop@0.5,b.site:drop@0.5"
    reg1 = FaultRegistry(spec, seed=7)
    seq1 = [reg1.decide("a.site") is not None for _ in range(100)]
    reg2 = FaultRegistry(spec, seed=7)
    seq2 = []
    for i in range(100):
        if i % 3 == 0:
            reg2.decide("b.site")  # interleaved traffic at another site
        seq2.append(reg2.decide("a.site") is not None)
    assert seq1 == seq2


def test_limit_and_trip_counters():
    reg = FaultRegistry("x.y:error@1x2", seed=0)
    for _ in range(2):
        with pytest.raises(FaultInjected):
            reg.fire_sync("x.y")
    reg.fire_sync("x.y")  # limit exhausted: clean
    assert reg.trip_counts[("x.y", "error")] == 2
    assert reg.snapshot()["trips"] == {"x.y:error": 2}


def test_fault_trips_visible_in_metrics_exposition():
    """Satellite: fault-trip counters on the /metrics surface (tier-1)."""
    import dynamo_tpu.frontend.migration  # noqa: F401 - registers provider

    FAULTS.configure("hub.fsync:delay=1ms")
    FAULTS.fire_sync("hub.fsync")
    text = MetricsRegistry().exposition().decode()
    assert 'dynamo_fault_trips_total{site="hub.fsync",action="delay"}' in text
    # migration recovery counters ride the same global-provider surface
    assert "dynamo_migrations_total" in text


# -- transport sites + deadline propagation ----------------------------------


async def _echo_server(handler=None):
    server = EndpointServer()

    async def echo(payload, ctx):
        yield {"echo": payload, "remaining": ctx.remaining_s()}

    server.register("svc/echo", handler or echo)
    await server.start()
    return server


async def test_transport_recv_drop_is_stream_death():
    server = await _echo_server()
    ch = InstanceChannel(server.host, server.port)
    await ch.connect()
    try:
        FAULTS.configure("transport.recv:drop@1x1")
        with pytest.raises(StreamError):
            async for _ in ch.call("svc/echo", {"a": 1}, Context()):
                pass
        FAULTS.clear()
        # the channel died like a real connection loss: marked closed
        assert not ch.connected
    finally:
        await ch.close()
        await server.stop(drain=False)


async def test_transport_recv_error_is_stream_death():
    """An injected ``error`` at transport.recv must kill the channel like
    a connection loss (sentinels delivered, channel marked closed) — not
    strand in-flight calls waiting on a dead rx loop."""
    server = await _echo_server()
    ch = InstanceChannel(server.host, server.port)
    await ch.connect()
    try:
        FAULTS.configure("transport.recv:error@1x1")
        with pytest.raises(StreamError):
            async for _ in ch.call("svc/echo", {"a": 1}, Context()):
                pass
        FAULTS.clear()
        assert not ch.connected
    finally:
        await ch.close()
        await server.stop(drain=False)


async def test_deadline_propagates_over_the_wire():
    server = await _echo_server()
    ch = InstanceChannel(server.host, server.port)
    await ch.connect()
    try:
        ctx = Context(deadline=time.monotonic() + 5.0)
        items = [i async for i in ch.call("svc/echo", {}, ctx)]
        remaining = items[0]["remaining"]
        assert remaining is not None and 0 < remaining <= 5.0
        # no deadline set => no budget on the worker side
        items = [i async for i in ch.call("svc/echo", {}, Context())]
        assert items[0]["remaining"] is None
        # expired before dispatch => DeadlineExceeded, nothing sent
        with pytest.raises(DeadlineExceeded):
            async for _ in ch.call(
                "svc/echo", {}, Context(deadline=time.monotonic() - 1)
            ):
                pass
    finally:
        await ch.close()
        await server.stop(drain=False)


async def test_draining_server_sends_typed_unavailable():
    server = await _echo_server()
    ch = InstanceChannel(server.host, server.port)
    await ch.connect()
    try:
        server.draining = True
        with pytest.raises(ServiceUnavailable) as ei:
            async for _ in ch.call("svc/echo", {}, Context()):
                pass
        assert ei.value.retry_after_s > 0
    finally:
        await ch.close()
        await server.stop(drain=False)


async def test_stop_force_cancels_streams_past_drain_timeout():
    """Satellite: stop(drain=True) must force-cancel wedged in-flight
    streams after the timeout (and count them), not hang or leak."""
    started = asyncio.Event()

    async def wedge(payload, ctx):
        started.set()
        await asyncio.sleep(600)
        yield {}

    server = await _echo_server(wedge)
    ch = InstanceChannel(server.host, server.port)
    await ch.connect()

    async def call():
        with pytest.raises(StreamError):
            async for _ in ch.call("svc/echo", {}, Context()):
                pass

    task = asyncio.ensure_future(call())
    await started.wait()
    t0 = time.monotonic()
    await server.stop(drain=True, timeout=0.3)
    assert time.monotonic() - t0 < 10, "stop must not wait out the handler"
    assert server.aborted_inflight == 1
    assert server.num_inflight == 0
    await asyncio.wait_for(task, 5)
    await ch.close()


# -- engine: drain, saturation, deadlines ------------------------------------


async def test_engine_draining_and_saturation_refuse_typed():
    engine = InferenceEngine(TINY, _engine_cfg(max_waiting=1))
    # saturated: a queue at the bound refuses BEFORE enqueue (the step
    # thread is not even started by the check path)
    engine._waiting.put_nowait(object())
    with pytest.raises(ServiceUnavailable, match="saturated"):
        async for _ in engine.generate(
            {"token_ids": [1, 2]}, Context()
        ):
            pass
    engine._waiting.get_nowait()
    # draining: same typed refusal
    engine.begin_drain()
    assert engine.draining
    with pytest.raises(ServiceUnavailable, match="draining"):
        async for _ in engine.generate({"token_ids": [1, 2]}, Context()):
            pass
    # never started; nothing to close, but close() must be safe
    await engine.close()


async def test_engine_rejects_expired_deadline_at_admission():
    engine = InferenceEngine(TINY, _engine_cfg())
    with pytest.raises(DeadlineExceeded):
        async for _ in engine.generate(
            {"token_ids": [1, 2]}, Context(deadline=time.monotonic() - 0.1)
        ):
            pass
    await engine.close()


async def test_engine_deadline_bounds_generation(decode_schedule):
    """A request whose deadline passes mid-flight ends promptly as
    'cancelled' (not a hang, not a full-length stream) and leaks no
    pages."""
    engine = InferenceEngine(
        TINY, _engine_cfg(max_pages_per_seq=64, **decode_schedule)
    )
    try:
        # tight deadline: expires during prefill compile / early decode
        items = []
        async for item in engine.generate(
            {"token_ids": [1, 2, 3],
             "stop_conditions": {"max_tokens": 200, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context(deadline=time.monotonic() + 0.05),
        ):
            items.append(item)
        assert items, "stream must end with a finish item"
        assert items[-1]["finish_reason"] == "cancelled"
        n_tokens = sum(len(i.get("token_ids") or ()) for i in items)
        assert n_tokens < 200, "deadline must cut generation short"
        # wait for the step loop to retire the slot, then: no leaks
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and engine.allocator.active_pages:
            await asyncio.sleep(0.05)
        assert engine.allocator.active_pages == 0
    finally:
        await engine.close()


async def test_engine_step_fault_fails_inflight_then_recovers(decode_schedule):
    """engine.step:error exercises the fail-everything-then-keep-serving
    recovery: the faulted step errors in-flight requests, the NEXT
    request (fault exhausted) serves normally on the same engine."""
    engine = InferenceEngine(TINY, _engine_cfg(**decode_schedule))
    try:
        FAULTS.configure("engine.step:error@1x1")
        items = [
            i async for i in engine.generate(
                {"token_ids": [1, 2],
                 "stop_conditions": {"max_tokens": 4, "ignore_eos": True}},
                Context(),
            )
        ]
        assert items[-1]["finish_reason"] == "error"
        FAULTS.clear()
        items = [
            i async for i in engine.generate(
                {"token_ids": [1, 2],
                 "stop_conditions": {"max_tokens": 4, "ignore_eos": True}},
                Context(),
            )
        ]
        assert items[-1]["finish_reason"] in ("length", "stop")
        assert not engine.is_dead
    finally:
        await engine.close()


# -- admin RPC: flip faults live ---------------------------------------------


async def test_admin_rpc_flips_faults_live():
    from dynamo_tpu.engine.worker import launch_engine_worker
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.hub import InMemoryHub

    drt = DistributedRuntime(InMemoryHub())
    engine, _served = await launch_engine_worker(
        drt, model="tiny-test", spec=TINY, engine_config=_engine_cfg(),
        model_name="tiny-test",
    )
    try:
        admin = drt.namespace("dynamo").component("backend").endpoint("admin")
        client = await admin.client().start()
        insts = await client.wait_for_instances(1, timeout=5)
        aid = insts[0].instance_id

        async def rpc(req):
            async for item in client.call_instance(aid, req, Context()):
                return item

        out = await rpc({"op": "faults", "spec": "engine.admit:delay=1ms",
                         "seed": 9})
        assert out["ok"] and out["rules"] == ["engine.admit:delay=1ms"]
        assert FAULTS.enabled and FAULTS.seed == 9
        # read-back reports trips after traffic
        items = [
            i async for i in engine.generate(
                {"token_ids": [1],
                 "stop_conditions": {"max_tokens": 1, "ignore_eos": True}},
                Context(),
            )
        ]
        assert items
        out = await rpc({"op": "faults"})
        assert out["trips"].get("engine.admit:delay") == 1
        out = await rpc({"op": "faults", "spec": ""})
        assert out["ok"] and not out["enabled"]
        out = await rpc({"op": "faults", "spec": "not-a-spec"})
        assert not out["ok"]
        await client.close()
    finally:
        await engine.close()
        await drt.close()


# -- migration: backoff, budget, deadlines, caps -----------------------------


class _FlakyEngine:
    """Dies with ``errors[i]`` on attempt i (after yielding ``emit``
    tokens), then serves attempts past the error list to completion."""

    def __init__(self, errors, emit=2, total=6):
        self.errors = list(errors)
        self.emit = emit
        self.total = total
        self.requests: list[dict] = []

    async def generate(self, request, context):
        self.requests.append(request)
        attempt = len(self.requests) - 1
        if attempt < len(self.errors):
            err = self.errors[attempt]
            for t in range(self.emit):
                yield {"token_ids": [100 * (attempt + 1) + t]}
            raise err
        budget = (request.get("stop_conditions") or {}).get("max_tokens")
        for t in range(budget):
            yield {"token_ids": [t],
                   "finish_reason": "length" if t == budget - 1 else None}


async def test_migration_resumes_with_backoff_and_counts():
    from dynamo_tpu.frontend.migration import STATS, Migration

    eng = _FlakyEngine([StreamError("worker died")])
    import random as _random

    mig = Migration(eng, migration_limit=3, retry_delay_s=0.001,
                    rng=_random.Random(0))
    before = STATS["migrations"]
    items = [
        i async for i in mig.generate(
            {"token_ids": [1, 2],
             "stop_conditions": {"max_tokens": 6}}, Context()
        )
    ]
    assert items[-1]["finish_reason"] == "length"
    assert STATS["migrations"] == before + 1
    # resume request: prompt grew by the 2 pre-crash tokens, budget shrank
    resumed = eng.requests[1]
    assert resumed["token_ids"] == [1, 2, 100, 101]
    assert resumed["stop_conditions"]["max_tokens"] == 4
    assert resumed["backend_instance_id"] is None


async def test_migration_backoff_is_jittered_exponential():
    import random as _random

    from dynamo_tpu.frontend.migration import Migration

    mig = Migration(object(), retry_delay_s=0.2, backoff_max_s=10.0,
                    rng=_random.Random(1))
    d0, d1, d2 = mig._backoff_s(0), mig._backoff_s(1), mig._backoff_s(2)
    assert 0.1 <= d0 < 0.3  # 0.2 * [0.5, 1.5)
    assert 0.2 <= d1 < 0.6
    assert 0.4 <= d2 < 1.2
    # deterministic under a seeded rng
    mig2 = Migration(object(), retry_delay_s=0.2, backoff_max_s=10.0,
                     rng=_random.Random(1))
    assert [mig2._backoff_s(i) for i in range(3)] == [d0, d1, d2]


async def test_migration_does_not_retry_non_retryable():
    from dynamo_tpu.frontend.migration import Migration

    # validation-style RuntimeError: not a StreamError, no retry
    eng = _FlakyEngine([RuntimeError("bad request"), StreamError("x")])
    mig = Migration(eng, retry_delay_s=0.001)
    with pytest.raises(RuntimeError, match="bad request"):
        async for _ in mig.generate(
            {"token_ids": [1], "stop_conditions": {"max_tokens": 3}},
            Context(),
        ):
            pass
    assert len(eng.requests) == 1

    # client-cancelled: no retry
    eng = _FlakyEngine([StreamError("died")])
    mig = Migration(eng, retry_delay_s=0.001)
    ctx = Context()
    ctx.stop_generating()
    with pytest.raises(StreamError):
        async for _ in mig.generate(
            {"token_ids": [1], "stop_conditions": {"max_tokens": 3}}, ctx
        ):
            pass
    assert len(eng.requests) == 1


async def test_migration_honors_deadline_and_budget():
    from dynamo_tpu.frontend.migration import Migration

    # expired deadline after failure => DeadlineExceeded, no retry
    eng = _FlakyEngine([StreamError("died")])
    mig = Migration(eng, retry_delay_s=0.001)
    with pytest.raises(DeadlineExceeded):
        async for _ in mig.generate(
            {"token_ids": [1], "stop_conditions": {"max_tokens": 3}},
            Context(deadline=time.monotonic() - 0.01),
        ):
            pass
    assert len(eng.requests) == 1

    # retry budget: a backoff larger than the remaining budget stops the
    # retry loop immediately (no 10s sleep in this test)
    eng = _FlakyEngine([StreamError("died")] * 5)
    mig = Migration(eng, migration_limit=5, retry_delay_s=10.0,
                    retry_budget_s=0.05, backoff_max_s=30.0)
    t0 = time.monotonic()
    with pytest.raises(StreamError):
        async for _ in mig.generate(
            {"token_ids": [1], "stop_conditions": {"max_tokens": 3}},
            Context(),
        ):
            pass
    assert time.monotonic() - t0 < 5.0
    assert len(eng.requests) == 1


async def test_migration_caps_resume_prompt_growth():
    from dynamo_tpu.frontend.migration import Migration

    eng = _FlakyEngine([StreamError("died")] * 10, emit=3)
    mig = Migration(eng, migration_limit=10, retry_delay_s=0.001,
                    max_resume_tokens=7)
    with pytest.raises(StreamError, match="resume prompt"):
        async for _ in mig.generate(
            {"token_ids": [1, 2], "stop_conditions": {"max_tokens": 64}},
            Context(),
        ):
            pass
    # 2 prompt + 3 emitted = 5 resumes once; 5 + 3 = 8 > 7 stops the next
    assert len(eng.requests) == 2


# -- HTTP: 503 + Retry-After / 504 -------------------------------------------


async def test_http_503_retry_after_and_504_deadline():
    from dynamo_tpu.engine.worker import launch_engine_worker
    from dynamo_tpu.frontend.http import HttpFrontend
    from dynamo_tpu.frontend.watcher import ModelManager, ModelWatcher
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.hub import InMemoryHub

    drt = DistributedRuntime(InMemoryHub())
    engine, _served = await launch_engine_worker(
        drt, model="tiny-test", spec=TINY, engine_config=_engine_cfg(),
        model_name="tiny-test",
    )
    manager = ModelManager()
    watcher = await ModelWatcher(drt, manager).start()
    await watcher.wait_for_model("tiny-test", timeout=10)
    frontend = HttpFrontend(manager, host="127.0.0.1", port=0)
    await frontend.start()
    base = f"http://127.0.0.1:{frontend.port}"
    body = {"model": "tiny-test", "prompt": "drain me", "max_tokens": 4,
            "ignore_eos": True}
    try:
        async with aiohttp.ClientSession() as sess:
            # healthy baseline
            async with sess.post(f"{base}/v1/completions", json=body) as r:
                assert r.status == 200

            # drain the only worker; shrink the migration retry budget so
            # the 503 surfaces fast instead of after the 5s default
            engine.begin_drain()
            mig = manager.get("tiny-test").engine.downstream
            mig.retry_delay_s, mig.retry_budget_s = 0.01, 0.05

            async with sess.post(f"{base}/v1/completions", json=body) as r:
                assert r.status == 503, await r.text()
                assert int(r.headers["Retry-After"]) >= 1
                payload = await r.json()
                assert payload["error"]["code"] == "service_unavailable"

            # a tight per-request deadline on the draining stack: the
            # retry path has no deadline budget left => 504
            async with sess.post(
                f"{base}/v1/completions", json=body,
                headers={"x-dyn-timeout-ms": "40"},
            ) as r:
                assert r.status in (503, 504), await r.text()
    finally:
        await frontend.stop()
        await watcher.close()
        await engine.close()
        await drt.close()
