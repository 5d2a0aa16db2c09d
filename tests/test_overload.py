"""Overload resilience (the robustness PR's acceptance surface):
per-tenant weighted-fair admission + token-bucket quotas (typed 429s),
priority preemption with KV offload-to-host and bit-identical resume,
policy-ordered load shedding, live Retry-After derivation, hub
retry_after hints, and the EPP circuit breaker."""

import asyncio
import os
import time

import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine, _Waiting
from dynamo_tpu.engine.tenancy import (
    TenantQuota,
    TenantScheduler,
    TokenBucket,
    parse_tenant_quotas,
)
from dynamo_tpu.gateway.breaker import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    BreakerConfig,
    CircuitBreaker,
)
from dynamo_tpu.runtime.context import (
    PRIORITY_HEADER,
    TENANT_HEADER,
    Context,
    OverQuota,
    ServiceUnavailable,
)

pytestmark = pytest.mark.unit

SPEC = ModelSpec(
    vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)


def small_config(**kw):
    defaults = dict(
        page_size=4, num_pages=256, max_pages_per_seq=64,
        max_decode_slots=2, prefill_buckets=(8, 16, 32),
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def _ctx(tenant=None, priority=None):
    headers = {}
    if tenant:
        headers[TENANT_HEADER] = tenant
    if priority:
        headers[PRIORITY_HEADER] = priority
    return Context(headers=headers)


async def _collect(engine, request, ctx=None):
    out = []
    async for item in engine.generate(request, ctx or Context()):
        out.append(item)
    return out


def _tokens(items):
    return [t for i in items for t in (i.get("token_ids") or [])]


# ------------------------------------------------------------ quota parsing


def test_parse_tenant_quotas_grammar():
    q = parse_tenant_quotas(
        "alpha:weight=4,rate=1000,burst=2000;beta:rate=50;*:rate=200"
    )
    assert q["alpha"].weight == 4 and q["alpha"].burst == 2000
    assert q["beta"].rate == 50 and q["beta"].burst == 200  # 4x rate
    assert q["*"].rate == 200
    assert parse_tenant_quotas("") == {}
    with pytest.raises(ValueError):
        parse_tenant_quotas("a:frobnicate=1")
    with pytest.raises(ValueError):
        parse_tenant_quotas("a:rate=abc")
    with pytest.raises(ValueError):
        parse_tenant_quotas(":rate=1")


def test_token_bucket_refill_and_retry_after():
    b = TokenBucket(TenantQuota(rate=10, burst=20), now=0.0)
    assert b.try_take(20, now=0.0)  # full burst
    assert not b.try_take(5, now=0.0)  # drained
    # retry hint derives from the deficit / refill rate
    assert b.retry_after_s(5, now=0.0) == pytest.approx(0.5)
    assert b.over_quota(now=0.0)
    assert b.try_take(5, now=1.0)  # 10 tokens refilled
    # a request larger than the whole burst charges the full burst
    # instead of being permanently unadmittable
    b2 = TokenBucket(TenantQuota(rate=10, burst=20), now=0.0)
    assert b2.try_take(500, now=0.0)
    assert not b2.try_take(1, now=0.0)
    # unmetered tenants never refuse and never read as over quota
    b3 = TokenBucket(TenantQuota(), now=0.0)
    assert b3.try_take(10**9, now=0.0) and not b3.over_quota(now=0.0)


# ------------------------------------------------------- fair scheduler unit


def _w(tenant, priority="interactive", cost=10.0, tag=None):
    w = _Waiting(
        {"token_ids": [1], "tag": tag or tenant}, Context(), asyncio.Queue(),
        tenant=tenant, priority=priority, cost=cost,
    )
    return w


def test_scheduler_interactive_class_strictly_first():
    s = TenantScheduler()
    s.put_nowait(_w("bt", "batch"))
    s.put_nowait(_w("bt", "batch"))
    s.put_nowait(_w("it", "interactive"))
    assert s.qsize() == 3
    assert s.get_nowait().tenant == "it"
    assert s.get_nowait().priority == "batch"


def test_scheduler_weighted_fair_within_class():
    # heavy (weight 4) should drain ~4x the token volume of light
    # (weight 1) under contention
    s = TenantScheduler({"heavy": TenantQuota(weight=4.0),
                         "light": TenantQuota(weight=1.0)})
    for _ in range(20):
        s.put_nowait(_w("heavy", cost=10.0))
        s.put_nowait(_w("light", cost=10.0))
    first16 = [s.get_nowait().tenant for _ in range(16)]
    heavy = first16.count("heavy")
    assert heavy >= 11, f"weighted share not honored: {first16}"
    # both tenants still make progress (no starvation)
    assert first16.count("light") >= 2


def test_scheduler_idle_tenant_banks_no_credit():
    s = TenantScheduler()
    # tenant a drains a lot of volume first
    for _ in range(8):
        s.put_nowait(_w("a", cost=100.0))
        s.get_nowait()
    # b arrives fresh: it must not get an unbounded run of the lane
    # just because a's vtime is high — a re-joins at the class clock
    for _ in range(4):
        s.put_nowait(_w("b", cost=10.0))
        s.put_nowait(_w("a", cost=10.0))
    order = [s.get_nowait().tenant for _ in range(8)]
    assert "a" in order[:4], f"idle-credit banking detected: {order}"


def test_scheduler_shed_policy_lowest_priority_most_over_quota_newest():
    s = TenantScheduler({"greedy": TenantQuota(rate=10, burst=10),
                         "modest": TenantQuota(rate=10, burst=1000)})
    s.charge("greedy", 500)  # drains greedy's bucket -> most over quota
    s.charge("modest", 5)
    first = _w("greedy", "batch", tag="greedy-old")
    second = _w("greedy", "batch", tag="greedy-new")
    s.put_nowait(first)
    s.put_nowait(second)
    s.put_nowait(_w("modest", "batch", tag="modest-1"))
    s.put_nowait(_w("it", "interactive", tag="it-1"))
    # batch arrival sheds nothing (no strictly-lower class)
    assert not s.sheddable_below("batch")
    assert s.shed_victim("batch") is None
    # interactive arrival sheds: batch class, greedy (over-quota) lane,
    # NEWEST entry of it
    assert s.sheddable_below("interactive")
    v = s.shed_victim("interactive")
    assert v is not None and v.request["tag"] == "greedy-new"
    assert s.qsize() == 3
    assert s.token_counts.get(("greedy", "shed"), 0) > 0


def test_scheduler_charge_outcomes_counted():
    s = TenantScheduler({"t": TenantQuota(rate=10, burst=30)})
    assert s.charge("t", 20) is None
    retry = s.charge("t", 20)
    assert retry is not None and retry > 0
    assert s.token_counts[("t", "admitted")] == 20
    assert s.token_counts[("t", "rejected")] == 20


# ----------------------------------------------------------- breaker unit


def test_breaker_open_halfopen_close_transitions():
    cfg = BreakerConfig(
        window=8, min_samples=4, failure_threshold=0.5,
        open_cooldown_s=10.0, half_open_probes=1, close_after=2,
    )
    b = CircuitBreaker(cfg)
    t = 0.0
    for _ in range(3):
        b.record(False, now=t)
    assert b.state == CLOSED  # under min_samples: no verdict
    b.record(False, now=t)
    assert b.state == OPEN  # 4 failures / 4 samples
    assert not b.allow(now=t + 1.0)  # inside cooldown: ejected
    assert b.allow(now=t + 11.0)  # cooldown elapsed: half-open probe
    assert b.state == HALF_OPEN
    assert not b.allow(now=t + 11.0)  # probe budget (1) spent
    b.record(True, now=t + 12.0)  # probe succeeded (1/2)
    assert b.state == HALF_OPEN
    assert b.allow(now=t + 12.0)
    b.record(True, now=t + 13.0)  # 2/2: closes
    assert b.state == CLOSED
    assert b.allow(now=t + 13.0)


def test_breaker_failing_probe_reopens_with_fresh_cooldown():
    cfg = BreakerConfig(
        window=8, min_samples=2, failure_threshold=0.5,
        open_cooldown_s=5.0, half_open_probes=1, close_after=1,
    )
    b = CircuitBreaker(cfg)
    b.record(False, now=0.0)
    b.record(False, now=0.0)
    assert b.state == OPEN
    assert b.allow(now=6.0)  # half-open probe
    b.record(False, now=6.0)  # probe fails
    assert b.state == OPEN
    assert not b.allow(now=7.0)  # fresh cooldown from t=6
    assert b.allow(now=11.5)


def test_breaker_latency_slo_counts_as_failure():
    cfg = BreakerConfig(
        window=8, min_samples=4, failure_threshold=0.5,
        latency_slo_s=0.1,
    )
    b = CircuitBreaker(cfg)
    for _ in range(4):
        b.record(True, latency_s=5.0, now=0.0)  # "ok" but way over SLO
    assert b.state == OPEN


# ------------------------------------------------- engine: quotas and 429s


async def test_engine_over_quota_typed_429_with_bucket_retry_after():
    cfg = small_config(tenants="bt:rate=1,burst=60")
    eng = InferenceEngine(SPEC, cfg)
    try:
        req = {"token_ids": list(range(30)),
               "stop_conditions": {"max_tokens": 8, "ignore_eos": True}}
        await _collect(eng, dict(req), _ctx("bt", "batch"))  # drains bucket
        with pytest.raises(OverQuota) as ei:
            await _collect(eng, dict(req), _ctx("bt", "batch"))
        # deficit/refill at rate 1 tok/s: a real, state-derived hint
        assert ei.value.retry_after_s > 1.0
        assert eng.admission_rejects["over_quota"] == 1
        # other tenants are unaffected (per-tenant buckets)
        out = await _collect(eng, dict(req), _ctx("other", "batch"))
        assert _tokens(out)
    finally:
        await eng.close()


async def test_engine_saturation_retry_after_tracks_queue_depth():
    eng = InferenceEngine(SPEC, small_config())
    try:
        eng.step_times.extend([0.1] * 16)
        shallow = eng._saturation_retry_after()
        for _ in range(40):
            eng._waiting.put_nowait(_w("t", "batch", cost=5.0))
        deep = eng._saturation_retry_after()
        assert deep > shallow, (shallow, deep)
        assert deep == pytest.approx(40 * 0.1 / 2, rel=0.01)
    finally:
        await eng.close()


async def test_drain_retry_after_prices_remaining_window():
    eng = InferenceEngine(SPEC, small_config())
    try:
        eng.begin_drain(deadline_s=25.0)
        hint = eng._drain_retry_after()
        assert 20.0 < hint <= 25.0
        with pytest.raises(ServiceUnavailable) as ei:
            await _collect(eng, {"token_ids": [1, 2]})
        assert ei.value.retry_after_s == pytest.approx(hint, abs=1.0)
    finally:
        await eng.close()


async def test_saturation_sheds_lower_priority_in_interactive_favor():
    """max_waiting overflow with a batch entry waiting: the interactive
    arrival sheds it (typed retryable bounce) instead of bouncing the
    newcomer — degradation by priority, not arrival order."""
    cfg = small_config(max_decode_slots=1, max_waiting=1, preemption=False)
    eng = InferenceEngine(SPEC, cfg)
    try:
        hold = {"token_ids": [1, 2, 3],
                "stop_conditions": {"max_tokens": 120, "ignore_eos": True}}
        t_hold = asyncio.create_task(
            _collect(eng, dict(hold), _ctx("bt", "batch"))
        )
        # wait until the holder occupies the slot
        for _ in range(400):
            if any(s is not None for s in eng._slots):
                break
            await asyncio.sleep(0.01)
        # fills the one-deep waiting queue
        t_waiter = asyncio.create_task(
            _collect(eng, dict(hold), _ctx("bt", "batch"))
        )
        for _ in range(400):
            if eng._waiting.qsize() >= 1:
                break
            await asyncio.sleep(0.01)
        # another batch arrival: nothing ranks below it -> bounced itself
        with pytest.raises(ServiceUnavailable):
            await _collect(eng, dict(hold), _ctx("bt2", "batch"))
        # interactive arrival: the waiting batch entry is shed in its favor
        it = asyncio.create_task(_collect(
            eng,
            {"token_ids": [7, 8],
             "stop_conditions": {"max_tokens": 2, "ignore_eos": True}},
            _ctx("it", "interactive"),
        ))
        with pytest.raises(ServiceUnavailable, match="shed"):
            await t_waiter
        out = await it
        assert len(_tokens(out)) == 2
        assert eng.admission_rejects["shed"] == 1
        await t_hold
        assert eng.allocator.active_pages == 0
    finally:
        await eng.close()


# ------------------------------------- preemption: continuity + host tier


async def test_mixed_tenant_overload_acceptance():
    """The PR's acceptance bar: with a batch tenant submitting unbounded
    work, an interactive tenant's admissions never bounce and its TTFT
    stays bounded; >= 1 batch stream is preempted and later resumes with
    a BIT-IDENTICAL continuation; sustained over-quota traffic gets
    typed 429 + Retry-After; pool accounting shows zero leaked pages."""
    from dynamo_tpu.kvbm import KvBlockManager, KvbmConfig

    cfg = small_config(tenants="batch-tenant:rate=40,burst=600")
    kvbm = KvBlockManager(KvbmConfig(host_bytes=64 * 1024 * 1024))
    eng = InferenceEngine(SPEC, cfg, kvbm=kvbm)
    ref = InferenceEngine(SPEC, small_config())
    try:
        # warmup (compiles) + uncontended interactive TTFT baseline
        inter_req = {"token_ids": [7, 8, 9],
                     "stop_conditions": {"max_tokens": 4,
                                         "ignore_eos": True}}
        await _collect(eng, dict(inter_req), _ctx("it"))
        base_ttfts = []
        for _ in range(3):
            t0 = time.monotonic()
            first_seen = None
            async for item in eng.generate(dict(inter_req), _ctx("it")):
                if first_seen is None and (item.get("token_ids") or []):
                    first_seen = time.monotonic() - t0
            base_ttfts.append(first_seen)
        p50_uncontended = sorted(base_ttfts)[len(base_ttfts) // 2]

        # the batch tenant saturates both slots with long streams...
        batch_req = {"token_ids": [1, 2, 3, 4, 5],
                     "stop_conditions": {"max_tokens": 240,
                                         "ignore_eos": True}}
        batch_tasks = [
            asyncio.create_task(_collect(
                eng, dict(batch_req), _ctx("batch-tenant", "batch")
            ))
            for _ in range(2)
        ]
        for _ in range(600):
            if sum(s is not None for s in eng._slots) == 2:
                break
            await asyncio.sleep(0.01)
        # ... and keeps submitting unbounded work: sustained over-quota
        # traffic gets the typed 429 with a bucket-derived Retry-After
        quota_bounces = 0
        for _ in range(4):
            try:
                await _collect(
                    eng, dict(batch_req), _ctx("batch-tenant", "batch")
                )
            except OverQuota as e:
                quota_bounces += 1
                assert e.retry_after_s > 0
        assert quota_bounces >= 3, "quota storm was not refused"

        # interactive requests under full batch saturation: never bounce,
        # TTFT bounded by preemption (not by the batch streams' runtime)
        contended = []
        for _ in range(4):
            t0 = time.monotonic()
            first_seen = None
            async for item in eng.generate(dict(inter_req), _ctx("it")):
                if first_seen is None and (item.get("token_ids") or []):
                    first_seen = time.monotonic() - t0
            assert first_seen is not None
            contended.append(first_seen)
        p99_contended = max(contended)
        # dynarace's schedule explorer (DYN_RACE_SCHED) injects seeded
        # sleeps at every sync boundary; under perturbation the ordering
        # invariants below still hold but wall-clock SLO bars do not —
        # dilate the TTFT bound instead of skipping the assertion.
        dilate = 10.0 if os.environ.get("DYN_RACE_SCHED") else 1.0
        assert p99_contended <= dilate * max(2 * p50_uncontended, 0.35), (
            f"interactive TTFT not held: contended {contended} vs "
            f"uncontended p50 {p50_uncontended:.4f}"
        )
        assert sum(eng.preemptions.values()) >= 1, eng.preemptions
        assert eng.admission_rejects["saturated"] == 0
        assert eng.admission_rejects["deadline"] == 0

        # the preempted batch streams resume and finish BIT-IDENTICALLY
        outs = await asyncio.gather(*batch_tasks)
        await _collect(ref, dict(batch_req))  # warm ref compiles
        ref_out = await _collect(ref, dict(batch_req))
        for out in outs:
            assert [i.get("finish_reason") for i in out if
                    i.get("finish_reason")] == ["length"]
            assert _tokens(out) == _tokens(ref_out), "continuity broken"

        # pool accounting: zero leaked pages after the run
        assert eng.allocator.active_pages == 0
        # the preempted stream's sealed blocks went through the G1->G2
        # offload path (host tier populated)
        await asyncio.to_thread(eng.offload.flush)
        assert kvbm.stats.offloaded > 0
    finally:
        await eng.close()
        await ref.close()


async def test_preempted_stream_onboards_from_host_tier_after_g1_evict():
    """Preempt -> evict G1 -> resume: the continuation must onboard its
    sealed blocks from the KVBM host tier (G2), proving the offload-to-
    host path carries real state, and still be bit-identical."""
    from dynamo_tpu.kvbm import KvBlockManager, KvbmConfig

    cfg = small_config(max_decode_slots=1)
    kvbm = KvBlockManager(KvbmConfig(host_bytes=64 * 1024 * 1024))
    eng = InferenceEngine(SPEC, cfg, kvbm=kvbm)
    ref = InferenceEngine(SPEC, small_config(max_decode_slots=1))
    try:
        warm = {"token_ids": [9, 9, 9],
                "stop_conditions": {"max_tokens": 2, "ignore_eos": True}}
        await _collect(eng, dict(warm))
        batch_req = {"token_ids": [1, 2, 3, 4, 5],
                     "stop_conditions": {"max_tokens": 160,
                                         "ignore_eos": True}}
        t_batch = asyncio.create_task(
            _collect(eng, dict(batch_req), _ctx("bt", "batch"))
        )
        for _ in range(600):
            if any(s is not None for s in eng._slots):
                break
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.2)  # let it decode into a few pages
        # interactive holds the ONE slot while we evict G1 below, so the
        # batch resume cannot re-admit before the eviction lands
        t_inter = asyncio.create_task(_collect(
            eng,
            {"token_ids": [7, 8],
             "stop_conditions": {"max_tokens": 96, "ignore_eos": True}},
            _ctx("it", "interactive"),
        ))
        for _ in range(600):
            if sum(eng.preemptions.values()) >= 1:
                break
            await asyncio.sleep(0.01)
        assert sum(eng.preemptions.values()) >= 1
        # wait for the offload thread to land the preempted blocks, then
        # drop every inactive G1 page: the resume MUST go through G2
        await asyncio.to_thread(eng.offload.flush)
        assert kvbm.stats.offloaded > 0
        eng.request_clear_cache()
        it_out = await t_inter
        assert len(_tokens(it_out)) == 96
        out = await t_batch
        ref_warm = dict(warm)
        await _collect(ref, ref_warm)
        ref_out = await _collect(ref, dict(batch_req))
        assert _tokens(out) == _tokens(ref_out), "continuity broken"
        assert kvbm.stats.onboard_hits_host > 0, (
            "resume never touched the host tier", kvbm.stats.to_dict(),
        )
        assert eng.allocator.active_pages == 0
    finally:
        await eng.close()
        await ref.close()


async def test_preempt_fault_site_skips_preemption_cleanly():
    """engine.preempt chaos: an injected error must SKIP the preemption
    (interactive waits; batch victim keeps running) with no client
    errors and clean page accounting."""
    from dynamo_tpu.runtime.faults import FAULTS

    eng = InferenceEngine(SPEC, small_config(max_decode_slots=1))
    try:
        await _collect(eng, {"token_ids": [9, 9],
                             "stop_conditions": {"max_tokens": 2,
                                                 "ignore_eos": True}})
        FAULTS.configure("engine.preempt:error", seed=1)
        batch_req = {"token_ids": [1, 2, 3],
                     "stop_conditions": {"max_tokens": 80,
                                         "ignore_eos": True}}
        t_batch = asyncio.create_task(
            _collect(eng, dict(batch_req), _ctx("bt", "batch"))
        )
        for _ in range(600):
            if any(s is not None for s in eng._slots):
                break
            await asyncio.sleep(0.01)
        out = await _collect(
            eng,
            {"token_ids": [7],
             "stop_conditions": {"max_tokens": 2, "ignore_eos": True}},
            _ctx("it", "interactive"),
        )
        # interactive still completes (after waiting out the batch
        # stream), nothing was preempted, nobody errored
        assert len(_tokens(out)) == 2
        assert eng.preemptions == {}
        bout = await t_batch
        assert len(_tokens(bout)) == 80
        assert not [i for i in bout if i.get("error")]
        assert eng.allocator.active_pages == 0
        trips = FAULTS.snapshot()["trips"]
        assert trips.get("engine.preempt:error", 0) >= 1
    finally:
        FAULTS.clear()
        await eng.close()


async def test_page_pressure_preemption_frees_pages_for_interactive(
    decode_schedule,
):
    """OutOfPages at an interactive prefill must preempt a batch stream
    (reason=interactive_pages) and retry — NOT bounce the interactive
    request with 'kv pages exhausted' (review-found: the free-slot scan
    used to match the admitting request's own empty slot and no-op)."""
    # 15 usable pages (allocator adds the trash page): the batch
    # stream's clamped budget needs 16, so it must stall
    cfg = small_config(num_pages=15, max_pages_per_seq=16,
                       max_decode_slots=2, prefill_buckets=(8, 16, 32),
                       **decode_schedule)
    eng = InferenceEngine(SPEC, cfg)
    try:
        # budget (clamped to the 64-token context) EXCEEDS the 15-page
        # pool: the batch stream exhausts it and STALLS on backpressure
        # holding every page — deterministic pressure, no race against
        # its natural finish. (Bit-identical resume continuity is
        # asserted by the slot-pressure tests above; here the claim is
        # the PAGES path: preempt instead of bouncing the interactive.)
        bctx = _ctx("bt", "batch")
        batch_req = {"token_ids": [1, 2, 3, 4, 5],
                     "stop_conditions": {"max_tokens": 200,
                                         "ignore_eos": True}}
        t_batch = asyncio.create_task(
            _collect(eng, dict(batch_req), bctx)
        )
        for _ in range(2000):
            if eng.allocator.free_pages == 0:
                break
            await asyncio.sleep(0.01)
        assert eng.allocator.free_pages == 0, "pool never saturated"
        # a free SLOT exists (slots=2, one batch stream), but pages do
        # not — every page is pinned by the stalled batch stream
        t_inter = asyncio.create_task(_collect(
            eng,
            {"token_ids": [30, 31, 32, 33, 34, 35, 36, 37],
             "stop_conditions": {"max_tokens": 2, "ignore_eos": True}},
            _ctx("it", "interactive"),
        ))
        for _ in range(2000):
            if eng.preemptions.get("interactive_pages", 0) >= 1:
                break
            if t_inter.done() and t_batch.done():
                break
            await asyncio.sleep(0.01)
        assert eng.preemptions.get("interactive_pages", 0) >= 1, (
            eng.preemptions, t_inter.done(), t_batch.done(),
            t_batch.result() if t_batch.done() else None,
            eng.allocator.free_pages,
            [s and s.request_id for s in eng._slots],
        )
        # end the batch stream as a client would. Its resume prompt
        # (prompt + everything generated) genuinely cannot EVER fit
        # this undersized pool, so depending on who wins the race the
        # stream ends either cancelled (our stop) or with the explicit
        # cannot-ever-fit bounce — both are correct terminal states;
        # what must NOT happen is a hang or a page leak.
        bctx.stop_generating()
        out = await t_inter
        assert not [i for i in out if i.get("error")], out
        assert len(_tokens(out)) == 2
        bout = await t_batch
        for item in bout:
            if item.get("error"):
                assert "pool can never hold it" in item["error"], item
        for _ in range(400):
            if eng.allocator.active_pages == 0:
                break
            await asyncio.sleep(0.01)
        assert eng.allocator.active_pages == 0
    finally:
        await eng.close()


def test_breaker_unreported_probes_expire():
    """Half-open probe slots whose outcome is never reported (feedback
    is best-effort) must expire, not wedge the breaker HALF-OPEN
    denying forever (review-found)."""
    cfg = BreakerConfig(
        window=8, min_samples=2, failure_threshold=0.5,
        open_cooldown_s=1.0, half_open_probes=2, close_after=1,
        probe_timeout_s=5.0,
    )
    b = CircuitBreaker(cfg)
    b.record(False, now=0.0)
    b.record(False, now=0.0)
    assert b.state == OPEN
    assert b.allow(now=2.0) and b.allow(now=2.0)  # both probes out
    assert not b.allow(now=3.0)  # budget spent, nothing reported
    # probes time out: new probes admitted, recovery still possible
    assert b.allow(now=8.0)
    b.record(True, now=8.5)
    assert b.state == CLOSED


def test_scheduler_dynamic_tenant_cap_overflows_shared_lane():
    s = TenantScheduler({"vip": TenantQuota(weight=4)})
    s.MAX_DYNAMIC_TENANTS = 4
    for i in range(10):
        t = s.resolve(f"key-{i:04x}")
        s.charge(t, 1)
    # configured tenants always resolve to themselves
    assert s.resolve("vip") == "vip"
    # bucket count bounded: 4 dynamic + overflow (+vip on demand)
    assert len(s._buckets) <= 6
    assert s.resolve("key-ffff") == TenantScheduler.OVERFLOW_TENANT


async def test_bounced_after_charge_is_refunded():
    """A charged request bounced without service (saturation re-check /
    shed) must get its bucket credit back — otherwise bounce-and-retry
    double-charges and 503s decay into 429s (review-found)."""
    cfg = small_config(max_decode_slots=1, max_waiting=1,
                       tenants="bt:rate=1,burst=1000")
    eng = InferenceEngine(SPEC, cfg)
    try:
        hold = {"token_ids": [1, 2, 3],
                "stop_conditions": {"max_tokens": 150, "ignore_eos": True}}
        t_hold = asyncio.create_task(
            _collect(eng, dict(hold), _ctx("bt", "batch"))
        )
        for _ in range(400):
            if any(s is not None for s in eng._slots):
                break
            await asyncio.sleep(0.01)
        t_wait = asyncio.create_task(
            _collect(eng, dict(hold), _ctx("bt", "batch"))
        )
        for _ in range(400):
            if eng._waiting.qsize() >= 1:
                break
            await asyncio.sleep(0.01)
        level_before = eng._waiting.bucket_level("bt")
        # shed the waiting batch entry in an interactive's favor: its
        # charge must come back (modulo trickle refill)
        it = asyncio.create_task(_collect(
            eng,
            {"token_ids": [7],
             "stop_conditions": {"max_tokens": 2, "ignore_eos": True}},
            _ctx("it", "interactive"),
        ))
        with pytest.raises(ServiceUnavailable):
            await t_wait
        level_after = eng._waiting.bucket_level("bt")
        shed_cost = 3 + 150
        assert level_after >= level_before + shed_cost - 5, (
            level_before, level_after,
        )
        await it
        await t_hold
    finally:
        await eng.close()


def test_scheduler_requeue_restores_head_and_vtime():
    """A page-stall requeue is zero service: the entry returns to its
    LANE HEAD with the dequeue's vtime advance undone — stall cycles
    must neither burn fair share nor let later same-tenant arrivals
    jump the stalled request (review-found)."""
    s = TenantScheduler()
    first = _w("t", cost=100.0, tag="first")
    s.put_nowait(first)
    s.put_nowait(_w("t", cost=100.0, tag="second"))
    vt_before = s._lanes["interactive"]["t"].vtime
    got = s.get_nowait()
    assert got.request["tag"] == "first"
    s.requeue(got)
    assert s._lanes["interactive"]["t"].vtime == pytest.approx(vt_before)
    assert s.get_nowait().request["tag"] == "first"  # head restored


async def test_never_fitting_prompt_refunds_quota():
    """A charged request bounced with ZERO service (prompt can never
    fit the pool) must get its bucket credit back (review-found)."""
    cfg = small_config(num_pages=8, max_pages_per_seq=16,
                       prefill_buckets=(8, 16, 32, 64),
                       tenants="t:rate=1,burst=500")
    eng = InferenceEngine(SPEC, cfg)
    try:
        # 40-token prompt needs 10 pages; the pool holds 8 — bounced
        # as an explicit cannot-ever-fit error
        out = await _collect(
            eng,
            {"token_ids": list(range(1, 41)),
             "stop_conditions": {"max_tokens": 2, "ignore_eos": True}},
            _ctx("t", "batch"),
        )
        assert any(
            "pool can never hold it" in (i.get("error") or "")
            for i in out
        ), out
        # trickle refill at rate=1 is negligible: the 42-token charge
        # must be back
        assert eng._waiting.bucket_level("t") >= 495
    finally:
        await eng.close()


def test_scheduler_emptied_lanes_are_dropped():
    """Dequeue scans must stay proportional to ACTIVE tenants: an
    emptied lane leaves the dict (and a requeue right after the drop
    still restores exact vtime via the class clock) (review-found)."""
    s = TenantScheduler()
    for i in range(50):
        s.put_nowait(_w(f"t{i}", cost=10.0))
    while not s.empty():
        s.get_nowait()
    assert not any(s._lanes[p] for p in s._lanes)
    # requeue after lane drop: exact head restore, no negative-vtime
    # scheduling advantage
    w = _w("t0", cost=10.0)
    s.put_nowait(w)
    got = s.get_nowait()
    s.requeue(got)
    assert s.get_nowait() is got


def test_breaker_board_forget_drops_gauge_series():
    from dynamo_tpu.gateway.breaker import BreakerBoard

    forgotten = []
    board = BreakerBoard(
        BreakerConfig(), on_forget=forgotten.append,
    )
    board.record(1, ok=True)
    board.record(2, ok=True)
    board.forget({2})
    assert forgotten == [1]
    assert set(board._breakers) == {2}


# ------------------------------------------------------ transport + HTTP


async def test_transport_carries_over_quota_code_and_retry_after():
    from dynamo_tpu.runtime.transport import EndpointServer, InstanceChannel

    server = EndpointServer()

    async def handler(payload, ctx):
        raise OverQuota("tenant 'x' over token quota", retry_after_s=3.5)
        yield  # pragma: no cover

    server.register("svc/ep", handler)
    host, port = await server.start()
    chan = InstanceChannel(host, port)
    await chan.connect()
    try:
        with pytest.raises(OverQuota) as ei:
            async for _ in chan.call("svc/ep", {}, Context()):
                pass
        assert ei.value.retry_after_s == pytest.approx(3.5)
    finally:
        await chan.close()
        await server.stop(drain=False)


async def test_http_maps_over_quota_to_429_and_validates_tenancy():
    import aiohttp

    from dynamo_tpu.frontend.http import HttpFrontend
    from dynamo_tpu.frontend.model_card import ModelDeploymentCard
    from dynamo_tpu.frontend.preprocessor import OpenAIPreprocessor
    from dynamo_tpu.frontend.tokenizer import MockTokenizer
    from dynamo_tpu.frontend.watcher import ModelManager, ModelPipeline

    class QuotaEngine:
        def __init__(self):
            self.seen_headers = {}

        async def generate(self, request, context):
            self.seen_headers = dict(context.headers)
            raise OverQuota("tenant 'bt' over token quota",
                            retry_after_s=7.2)
            yield  # pragma: no cover

    engine = QuotaEngine()
    manager = ModelManager()
    manager.add(ModelPipeline(
        card=ModelDeploymentCard(
            name="m", namespace="dyn", component="backend",
            endpoint="generate",
        ),
        preprocessor=OpenAIPreprocessor(
            MockTokenizer(), model_name="m", context_length=512
        ),
        engine=engine, push_router=None, kv_router=None,
    ))
    fe = HttpFrontend(manager, host="127.0.0.1", port=0)
    await fe.start()
    base = f"http://127.0.0.1:{fe.port}"
    body = {"model": "m",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4}
    try:
        async with aiohttp.ClientSession() as sess:
            async with sess.post(
                f"{base}/v1/chat/completions", json=body,
                headers={"x-dyn-tenant": "bt", "x-dyn-priority": "batch"},
            ) as r:
                assert r.status == 429
                assert r.headers["Retry-After"] == "8"  # ceil(7.2)
                payload = await r.json()
                assert payload["error"]["code"] == "over_quota"
            # the validated tenancy rode the baggage headers to the engine
            assert engine.seen_headers.get(TENANT_HEADER) == "bt"
            assert engine.seen_headers.get(PRIORITY_HEADER) == "batch"
            # malformed tenancy headers: typed 400s naming the header
            async with sess.post(
                f"{base}/v1/chat/completions", json=body,
                headers={"x-dyn-tenant": "bad tenant!!"},
            ) as r:
                assert r.status == 400
                assert "x-dyn-tenant" in (await r.json())["error"]["message"]
            async with sess.post(
                f"{base}/v1/chat/completions", json=body,
                headers={"x-dyn-priority": "urgent"},
            ) as r:
                assert r.status == 400
            # api-key traffic gets a stable opaque per-key tenant
            async with sess.post(
                f"{base}/v1/chat/completions", json=body,
                headers={"Authorization": "Bearer sk-test-123"},
            ) as r:
                assert r.status == 429
            assert engine.seen_headers[TENANT_HEADER].startswith("key-")
    finally:
        await fe.stop()


def test_validate_tenancy_unit():
    from dynamo_tpu.frontend.validation import (
        RequestValidationError,
        validate_tenancy,
    )

    assert validate_tenancy({}) == ("default", "interactive")
    assert validate_tenancy({"x-dyn-tenant": "a.b-c_1",
                             "x-dyn-priority": "BATCH"}) == \
        ("a.b-c_1", "batch")
    t1, _ = validate_tenancy({"Authorization": "Bearer sk-k1"})
    t2, _ = validate_tenancy({"Authorization": "Bearer sk-k1"})
    t3, _ = validate_tenancy({"Authorization": "Bearer sk-k2"})
    assert t1 == t2 != t3 and t1.startswith("key-")
    with pytest.raises(RequestValidationError):
        validate_tenancy({"x-dyn-tenant": "x" * 65})
    with pytest.raises(RequestValidationError):
        validate_tenancy({"x-dyn-tenant": "no spaces"})
    with pytest.raises(RequestValidationError):
        validate_tenancy({"x-dyn-priority": "urgent"})


# --------------------------------------------------------- hub retry hints


async def test_hub_client_honors_no_quorum_retry_after_hint():
    """A no_quorum bounce carrying retry_after must hold the client off
    for ~the hinted interval before its retry — not the default 50ms
    exponential-backoff first step."""
    import itertools

    from dynamo_tpu.runtime import framing
    from dynamo_tpu.runtime.hub_client import RemoteHub

    calls = itertools.count()

    async def handle(reader, writer):
        while True:
            msg = await framing.read_frame(reader)
            if msg is None:
                break
            if msg.get("op") == "put":
                n = next(calls)
                if n == 0:
                    await framing.write_frame(writer, {
                        "id": msg["id"], "ok": False,
                        "error": "no_quorum", "retry_after": 0.4,
                    })
                else:
                    await framing.write_frame(writer, {
                        "id": msg["id"], "ok": True, "result": True,
                    })
        writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    hub = await RemoteHub.connect(f"127.0.0.1:{port}")
    try:
        t0 = time.monotonic()
        await hub.put("k", 1)
        elapsed = time.monotonic() - t0
        # 0.4 hint with +-10% jitter: must dominate the 50ms default
        assert elapsed >= 0.3, f"hint ignored (elapsed {elapsed:.3f}s)"
        assert next(calls) >= 2
    finally:
        await hub.close()
        server.close()


# ------------------------------------------------------------ EPP breaker


async def _epp_stack(breaker_config=None, num_workers=2):
    from dynamo_tpu.gateway.epp import EndpointPicker
    from dynamo_tpu.kv_router.protocols import RouterConfig
    from dynamo_tpu.mocker.__main__ import launch_mock_worker
    from dynamo_tpu.mocker.engine import MockEngineConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.hub import InMemoryHub

    drt = DistributedRuntime(InMemoryHub())
    cfg = MockEngineConfig(block_size=4, speedup_ratio=1000.0)
    served = []
    for _ in range(num_workers):
        _eng, s = await launch_mock_worker(
            drt, "dyn", "backend", "generate", cfg,
        )
        served.append(s)
    epp = await EndpointPicker(
        drt, namespace="dyn", target_component="backend",
        config=RouterConfig(block_size=4), host="127.0.0.1", port=0,
        breaker_config=breaker_config,
    ).start()
    return drt, epp, [s.instance.instance_id for s in served]


async def _pick_until_ok(sess, base, payload, timeout_s=8.0):
    """First picks can 503 while the router is still discovering the
    fleet (instance watch + metrics subscription): poll to 200."""
    deadline = time.monotonic() + timeout_s
    while True:
        async with sess.post(f"{base}/pick", json=payload) as r:
            if r.status == 200:
                return await r.json()
            assert time.monotonic() < deadline, await r.text()
        await asyncio.sleep(0.05)


async def test_epp_breaker_ejects_sick_worker_and_readmits():
    import aiohttp

    bc = BreakerConfig(
        window=8, min_samples=4, failure_threshold=0.5,
        open_cooldown_s=0.3, half_open_probes=2, close_after=1,
    )
    drt, epp, ids = await _epp_stack(bc)
    base = f"http://127.0.0.1:{epp.port}"
    try:
        async with aiohttp.ClientSession() as sess:
            # pick once to learn who the router favors for this prompt
            body = await _pick_until_ok(
                sess, base, {"token_ids": list(range(16))}
            )
            sick = body["worker_id"]
            # the gateway reports failing outcomes for it
            for _ in range(6):
                async with sess.post(f"{base}/report", json={
                    "worker_id": sick, "ok": False, "latency_ms": 50,
                }) as r:
                    assert r.status == 200
            assert epp.breakers.state(sick) == OPEN
            # arbitrary ids must not mint breaker state (cardinality)
            async with sess.post(f"{base}/report", json={
                "worker_id": 0xdeadbeef, "ok": False,
            }) as r:
                assert r.status == 404
            # while OPEN, picks exclude it (the healthy peer serves)
            for _ in range(5):
                async with sess.post(
                    f"{base}/pick", json={"token_ids": list(range(16))}
                ) as r:
                    assert r.status == 200
                    assert (await r.json())["worker_id"] != sick
            # breaker state is on /metrics
            async with sess.get(f"{base}/metrics") as r:
                text = await r.text()
            assert "dynamo_epp_breaker_state" in text
            assert f'instance="{sick:x}"' in text
            # recovery: cooldown elapses, a probe goes through and
            # succeeds -> closed, worker re-admitted to the pick pool
            await asyncio.sleep(0.35)
            assert epp.breakers.allow(sick)  # half-open probe admission
            async with sess.post(f"{base}/report", json={
                "worker_id": sick, "ok": True, "latency_ms": 5,
            }) as r:
                assert (await r.json())["state"] == "closed"
            assert epp.breakers.state(sick) == CLOSED
            seen = set()
            for _ in range(12):
                async with sess.post(
                    f"{base}/pick", json={"token_ids": list(range(16))}
                ) as r:
                    seen.add((await r.json())["worker_id"])
            assert sick in seen, "recovered worker never re-admitted"
    finally:
        await epp.close()
        await drt.close()


async def test_epp_breaker_fault_site_forces_outcomes():
    """epp.breaker chaos: injected errors at the pick path record
    failure outcomes against the picked instance, opening its breaker
    without a genuinely sick worker."""
    import aiohttp

    from dynamo_tpu.runtime.faults import FAULTS

    bc = BreakerConfig(window=8, min_samples=4, failure_threshold=0.5,
                       open_cooldown_s=30.0)
    drt, epp, ids = await _epp_stack(bc, num_workers=1)
    base = f"http://127.0.0.1:{epp.port}"
    FAULTS.configure("epp.breaker:error", seed=3)
    try:
        async with aiohttp.ClientSession() as sess:
            # poll through router discovery, then drive injected picks:
            # each one is answered (the outcome is recorded AFTER the
            # decision) and with ONE worker the ejection fails open
            await _pick_until_ok(sess, base, {"token_ids": list(range(16))})
            for _ in range(6):
                async with sess.post(
                    f"{base}/pick", json={"token_ids": list(range(16))}
                ) as r:
                    assert r.status == 200
        assert epp.breakers.state(ids[0]) == OPEN
        trips = FAULTS.snapshot()["trips"]
        assert trips.get("epp.breaker:error", 0) >= 4
    finally:
        FAULTS.clear()
        await epp.close()
        await drt.close()


# ------------------------------------------------------------- slow soak


@pytest.mark.slow
async def test_soak_overload_quota_storm():
    """Quota storm at soak length: a batch tenant floods the engine for
    the soak window while an interactive tenant pings steadily. The
    interactive tenant must see ZERO errors, the batch tenant a steady
    stream of typed 429s, preemptions must actually happen, and the
    pool must account to zero at the end."""
    soak_s = float(os.environ.get("DYN_SOAK_SECS", "15"))
    cfg = small_config(tenants="storm:rate=60,burst=700")
    eng = InferenceEngine(SPEC, cfg)
    try:
        await _collect(eng, {"token_ids": [9, 9],
                             "stop_conditions": {"max_tokens": 2,
                                                 "ignore_eos": True}})
        stop_at = time.monotonic() + soak_s
        stats = {"it_ok": 0, "it_err": 0, "b_ok": 0, "b_429": 0}

        async def batch_storm():
            while time.monotonic() < stop_at:
                try:
                    await _collect(
                        eng,
                        {"token_ids": [1, 2, 3, 4],
                         "stop_conditions": {"max_tokens": 120,
                                             "ignore_eos": True}},
                        _ctx("storm", "batch"),
                    )
                    stats["b_ok"] += 1
                except OverQuota:
                    stats["b_429"] += 1
                    await asyncio.sleep(0.05)

        async def interactive_pings():
            while time.monotonic() < stop_at:
                try:
                    out = await _collect(
                        eng,
                        {"token_ids": [7, 8],
                         "stop_conditions": {"max_tokens": 4,
                                             "ignore_eos": True}},
                        _ctx("vip", "interactive"),
                    )
                    assert not [i for i in out if i.get("error")]
                    stats["it_ok"] += 1
                except Exception:  # noqa: BLE001 - counted, asserted below
                    stats["it_err"] += 1
                await asyncio.sleep(0.02)

        await asyncio.gather(
            batch_storm(), batch_storm(), batch_storm(),
            interactive_pings(),
        )
        assert stats["it_err"] == 0, stats
        assert stats["it_ok"] > 0, stats
        assert stats["b_429"] > 0, stats
        assert stats["b_ok"] > 0, stats  # batch makes progress too
        # storm pressure kept both slots busy: interactive admissions
        # came from preemptions at least once
        assert sum(eng.preemptions.values()) >= 1, (
            stats, eng.preemptions,
        )
        for _ in range(200):
            if eng.inflight() == 0:
                break
            await asyncio.sleep(0.05)
        assert eng.allocator.active_pages == 0
    finally:
        await eng.close()
