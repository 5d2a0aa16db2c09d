"""End-to-end KV integrity (runtime/integrity.py) + SDC canary quarantine.

The gray-failure contract under test: a flipped bit anywhere a KV payload
crosses a process boundary — disagg pull, KVBM tier onboard (packed fp8
included), migration resume — is DETECTED by the receiver's content
checksum and recovered through the path's existing machinery (local
prefill fallback / tier miss / operator re-drive), never decoded into
garbage tokens. And a worker that answers its canary confidently but
WRONG (silent data corruption) is quarantined immediately, then
re-admitted only after ``readmit_threshold`` consecutive clean canaries.
"""

import asyncio
import random
import zlib

import numpy as np
import pytest

from dynamo_tpu.runtime.context import Context, StreamError
from dynamo_tpu.runtime.faults import FAULTS, FaultRegistry, parse_spec
from dynamo_tpu.runtime.integrity import (
    IntegrityError,
    corrupt_token_ids,
    integrity_snapshot,
    kv_checksum,
    token_checksum,
    verify_checksum,
    verify_resume_tokens,
)

pytestmark = pytest.mark.integration


def _bits_differ(a: bytes, b: bytes) -> int:
    return sum(bin(x ^ y).count("1") for x, y in zip(a, b))


# --------------------------------------------------------- checksum goldens


def test_kv_checksum_chaining_and_numpy_equivalence():
    """The checksum is chained crc32 — part boundaries don't matter, and
    numpy blocks hash to the same value as their raw bytes (the zero-copy
    path and the strided-fallback path agree)."""
    a, b = b"hello kv", b" payload bytes"
    assert kv_checksum(a, b) == zlib.crc32(a + b) & 0xFFFFFFFF
    assert kv_checksum(a, b) == kv_checksum(a + b)
    assert kv_checksum(None, a, None, b) == kv_checksum(a, b)

    arr = np.arange(2 * 3 * 4 * 8, dtype=np.float32).reshape(2, 3, 4, 8)
    assert kv_checksum(arr) == kv_checksum(arr.tobytes())
    # non-contiguous slice: strided view must hash as its contiguous copy
    view = arr[:, ::2]
    assert not view.flags["C_CONTIGUOUS"]
    assert kv_checksum(view) == kv_checksum(np.ascontiguousarray(view))

    # packed fp8 tier payload (uint8 data + scale bytes, the shape the
    # quantized KVBM tiers store): sender-side k+v stamp == receiver-side
    k = (np.arange(2 * 64, dtype=np.uint8) % 251).reshape(2, 64)
    v = (k + 100) % 251
    assert kv_checksum(k, v) == kv_checksum(k.tobytes(), v.tobytes())

    # a single flipped bit anywhere changes the sum
    flipped = bytearray(arr.tobytes())
    flipped[17] ^= 0x10
    assert kv_checksum(bytes(flipped)) != kv_checksum(arr)


def test_token_checksum_order_value_and_container():
    assert token_checksum([1, 2, 3]) == token_checksum((1, 2, 3))
    assert token_checksum([1, 2, 3]) != token_checksum([3, 2, 1])
    assert token_checksum([1, 2, 3]) != token_checksum([1, 2, 4])
    assert token_checksum([]) == 0 and token_checksum(None) == 0
    # negative ids (sentinels) are representable, not a crash
    assert token_checksum([-1, 5]) != token_checksum([1, 5])


def test_verify_checksum_unstamped_passes_mismatch_raises_and_counts():
    """None expected = unstamped payload from an older sender (rolling
    upgrade): verifies trivially. A mismatch raises IntegrityError (a
    StreamError — it must ride existing recovery) and counts the path."""
    verify_checksum(None, b"anything", path="unit.test")  # no raise
    before = integrity_snapshot().get("unit.test", 0)
    with pytest.raises(IntegrityError) as ei:
        verify_checksum(kv_checksum(b"good") ^ 1, b"good", path="unit.test")
    assert isinstance(ei.value, StreamError)
    assert integrity_snapshot()["unit.test"] == before + 1


# ------------------------------------- corrupt fault grammar + ~instance


def test_corrupt_spec_parsing_roundtrip_and_param_validation():
    r = parse_spec("disagg.pull:corrupt=3x1")[0]
    assert (r.action, r.flips, r.limit) == ("corrupt", 3, 1)
    assert parse_spec("kvbm.onboard:corrupt")[0].flips == 1
    r2 = parse_spec("kvbm.onboard:corrupt=3@0.5x2~w-*")[0]
    assert r2.instance == "w-*"
    assert r2.spec() == "kvbm.onboard:corrupt=3@0.5x2~w-*"
    assert r2.instance_matches("w-3") and not r2.instance_matches("x-3")

    # typed param validation: anything but a positive int is a spec error
    for bad in ("health.canary:corrupt=50ms", "kvbm.onboard:corrupt=0",
                "kvbm.onboard:corrupt=-2", "kvbm.onboard:corrupt=lots"):
        with pytest.raises(ValueError):
            parse_spec(bad)
    with pytest.raises(ValueError):
        parse_spec("engine.step:delay=5ms~")  # ~ needs a pattern


def test_corrupt_bytes_is_sticky_scoped_seeded_and_never_fires():
    """corrupt is a payload action: per-instance sticky (the gray worker
    flips bits on EVERY matching payload), bit-flips at seeded positions
    (same spec+seed replays bit-for-bit), and it never raises at
    fire()/fire_sync() sites — only corrupt_bytes() call sites see it."""
    reg = FaultRegistry("kvbm.onboard:corrupt=2~w1", seed=7)
    payload = bytes(range(64))
    # non-matching identity: the exact same object back, zero copies
    assert reg.corrupt_bytes("kvbm.onboard", payload, instance="w2") \
        is payload
    out1 = reg.corrupt_bytes("kvbm.onboard", payload, instance="w1")
    assert out1 != payload and _bits_differ(out1, payload) in (1, 2)
    # sticky: the same worker keeps getting corrupted payloads
    out2 = reg.corrupt_bytes("kvbm.onboard", payload, instance="w1")
    assert out2 != payload
    # deterministic replay: same spec + seed -> identical flip positions
    reg_b = FaultRegistry("kvbm.onboard:corrupt=2~w1", seed=7)
    assert reg_b.corrupt_bytes("kvbm.onboard", payload, instance="w1") \
        == out1

    # corrupt rules are invisible to fire()/fire_sync(): no raise, no trip
    reg2 = FaultRegistry("engine.step:corrupt", seed=1)
    reg2.fire_sync("engine.step")
    assert ("engine.step", "corrupt") not in reg2.trip_counts


def test_corrupt_token_ids_flips_exactly_one_token():
    """Token corruption goes through the same 8-byte lanes the checksum
    hashes, so one flipped bit lands in exactly one token value."""
    toks = list(range(100, 116))
    FAULTS.configure("migration.resume:corrupt=1x1")
    try:
        out = corrupt_token_ids("migration.resume", list(toks))
        assert len(out) == len(toks)
        assert sum(a != b for a, b in zip(out, toks)) == 1
        # fault exhausted (x1): the next payload passes through untouched
        again = corrupt_token_ids("migration.resume", list(toks))
        assert again == toks
    finally:
        FAULTS.clear()


# ---------------------------------------------------- disagg pull path


async def test_disagg_pull_corrupt_detected_never_decoded():
    """A bit flipped on the transfer wire is caught by the receiver's
    checksum BEFORE the bytes become KV: pull raises IntegrityError, and
    once the fault exhausts a fresh pull round-trips bit-exactly."""
    from dynamo_tpu.disagg.transfer import (
        _LOCAL_SOURCES,
        KvTransferSource,
        pull_kv_blocks,
    )

    src = await KvTransferSource().start()
    k = np.arange(2 * 3 * 4 * 2 * 8, dtype=np.float32).reshape(2, 3, 4, 2, 8)
    v = k + 1000.0
    before = integrity_snapshot().get("disagg.pull", 0)
    try:
        params = src.export(k, v, num_tokens=11, page_size=4)
        hidden = _LOCAL_SOURCES.pop(src.uid)  # force the socket route
        trips0 = FAULTS.trip_counts.get(("disagg.pull", "corrupt"), 0)
        FAULTS.configure("disagg.pull:corrupt=1x1")
        try:
            with pytest.raises(IntegrityError):
                await asyncio.to_thread(pull_kv_blocks, params)
            assert FAULTS.trip_counts[("disagg.pull", "corrupt")] \
                == trips0 + 1
            assert integrity_snapshot()["disagg.pull"] == before + 1
            # fault exhausted: the next export pulls clean over the same
            # wire, checksum verified
            params2 = src.export(k, v, num_tokens=11, page_size=4)
            k2, v2, _ = await asyncio.to_thread(pull_kv_blocks, params2)
            np.testing.assert_array_equal(k, k2)
            np.testing.assert_array_equal(v, v2)
        finally:
            FAULTS.clear()
            _LOCAL_SOURCES[src.uid] = hidden
    finally:
        await src.close()


async def test_disagg_e2e_corrupt_pull_falls_back_bit_identical():
    """The full contract: decode worker's remote-prefill pull is
    corrupted on the wire — the engine must detect it, fall back to a
    LOCAL prefill, and stream EXACTLY the aggregated greedy tokens
    (continuity), with zero client-visible errors."""
    from dynamo_tpu.disagg.transfer import _LOCAL_SOURCES
    from dynamo_tpu.engine.config import EngineConfig, ModelSpec
    from dynamo_tpu.engine.worker import launch_engine_worker
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.hub import InMemoryHub

    spec = ModelSpec(
        name="tiny-test", vocab_size=272, hidden_size=32,
        intermediate_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=8, dtype="float32",
    )

    def cfg():
        return EngineConfig(
            page_size=4, num_pages=128, max_pages_per_seq=32,
            max_decode_slots=4, prefill_buckets=(32, 64, 128),
        )

    def req(token_ids):
        return {
            "token_ids": list(token_ids),
            "sampling": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": 8, "ignore_eos": True},
            "eos_token_ids": [2],
        }

    async def collect(agen):
        toks = []
        async for item in agen:
            assert item.get("finish_reason") != "error", item
            toks.extend(item.get("token_ids") or [])
        return toks

    prompt = list(range(40, 40 + 23))

    # aggregated ground truth
    drt_a = DistributedRuntime(InMemoryHub())
    agg, _ = await launch_engine_worker(
        drt_a, spec=spec, engine_config=cfg(), model_name="agg",
    )
    want = await collect(agg.generate(req(prompt), Context()))
    await agg.close()
    await drt_a.close()

    drt = DistributedRuntime(InMemoryHub())
    pre, _ = await launch_engine_worker(
        drt, spec=spec, engine_config=cfg(), model_name="tiny-test",
        mode="prefill",
    )
    dec, _ = await launch_engine_worker(
        drt, spec=spec, engine_config=cfg(), model_name="tiny-test",
        mode="decode", always_remote_prefill=True,
    )
    handler = dec.frontdoor
    await handler.wait_for_prefill_pool()
    saved = dict(_LOCAL_SOURCES)
    try:
        # force the socket route (same-process tests shortcut through the
        # local registry, which the wire-corruption fault can't touch)
        _LOCAL_SOURCES.clear()
        trips0 = FAULTS.trip_counts.get(("disagg.pull", "corrupt"), 0)
        FAULTS.configure("disagg.pull:corrupt=2x1")
        got = await collect(handler.generate(req(prompt), Context()))
        assert got == want, "token continuity broken across corrupt pull"
        assert dec.disagg_fallbacks == 1
        assert FAULTS.trip_counts[("disagg.pull", "corrupt")] == trips0 + 1
    finally:
        FAULTS.clear()
        _LOCAL_SOURCES.update(saved)
        await pre.close()
        await dec.close()
        await drt.close()
    assert dec.allocator.active_pages == 0


# ------------------------------------------------------- KVBM tier paths


def _fp8_block(fill=0, num_layers=2, nbytes=64):
    """Packed quantized payload (uint8 fp8 data + scale bytes)."""
    k = np.arange(num_layers * nbytes, dtype=np.uint8).reshape(
        num_layers, nbytes)
    return (k + fill) % 251, (k + fill + 100) % 251


def test_kvbm_host_tier_corrupt_is_evicted_miss_then_recovers():
    """DRAM rot on a G2 block (packed fp8 payload): the checksum catches
    it at onboard, the poisoned block is EVICTED, and the engine sees a
    plain miss — re-prefill, never a poisoned page."""
    from dynamo_tpu.kvbm import KvBlockManager, KvbmConfig

    mgr = KvBlockManager(KvbmConfig(host_bytes=1 << 20))
    k, v = _fp8_block(3)
    mgr.offer(0xA1, k, v)
    before = integrity_snapshot().get("kvbm.host", 0)
    FAULTS.configure("kvbm.onboard:corrupt=1x1")
    try:
        assert mgr.get(0xA1) is None
        assert 0xA1 not in mgr.host  # evicted, not left to poison again
        assert mgr.stats.onboard_misses == 1
        assert integrity_snapshot()["kvbm.host"] == before + 1
    finally:
        FAULTS.clear()
    # recovery: a re-offered block (the re-prefill reseal) serves clean
    mgr.offer(0xA1, k, v)
    got = mgr.get(0xA1)
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)


def test_kvbm_disk_tier_corrupt_is_miss(tmp_path):
    from dynamo_tpu.kvbm import KvBlockManager, KvbmConfig

    mgr = KvBlockManager(KvbmConfig(
        host_bytes=1 << 20, disk_bytes=1 << 20,
        disk_dir=str(tmp_path / "kv"),
    ))
    k, v = _fp8_block(9)
    mgr.disk.put(0xD1, k, v)
    before = integrity_snapshot().get("kvbm.disk", 0)
    FAULTS.configure("kvbm.onboard:corrupt=1x1")
    try:
        assert mgr.get(0xD1) is None
        assert integrity_snapshot()["kvbm.disk"] == before + 1
        # the poisoned block was evicted from G3 outright — a flipped
        # at-rest file must not be re-served on the next probe
        assert 0xD1 not in mgr.disk
    finally:
        FAULTS.clear()
    # recovery: the re-prefill reseal re-writes the tier; onboard verifies
    # clean and promotes to G2
    mgr.disk.put(0xD1, k, v)
    got = mgr.get(0xD1)
    np.testing.assert_array_equal(got[0], k)
    assert 0xD1 in mgr.host


async def test_kvbm_remote_tier_corrupt_is_miss_cross_worker():
    """G4: a bit flipped in the hub object store payload (or on its way
    back) is caught by the in-payload checksum on the ONBOARDING worker —
    cross-process detection, the tier the sender can't re-verify."""
    from dynamo_tpu.kvbm.manager import KvbmConfig, KvBlockManager
    from dynamo_tpu.runtime.hub import InMemoryHub

    hub = InMemoryHub()
    loop = asyncio.get_running_loop()
    cfg = KvbmConfig(host_bytes=1 << 20, remote_max_blocks=8)
    a = KvBlockManager(cfg, hub=hub, loop=loop, namespace="it")
    b = KvBlockManager(cfg, hub=hub, loop=loop, namespace="it")
    k, v = _fp8_block(5)
    assert await asyncio.to_thread(a.remote.put, 0xC4, k, v)

    before = integrity_snapshot().get("kvbm.remote", 0)
    FAULTS.configure("kvbm.onboard:corrupt=1x1")
    try:
        assert await asyncio.to_thread(b.get, 0xC4) is None
        assert integrity_snapshot()["kvbm.remote"] == before + 1
        assert b.stats.onboard_misses == 1
    finally:
        FAULTS.clear()
    got = await asyncio.to_thread(b.get, 0xC4)
    assert got is not None
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)


# ----------------------------------------------------- migration resume


class _VerifyingFlakyEngine:
    """Mirrors the real engine's intake contract: verify the resume
    stamp, die once with a StreamError after emitting 2 tokens, then
    serve to completion."""

    def __init__(self):
        self.requests: list[dict] = []
        self.served_past_verify = 0

    async def generate(self, request, context):
        self.requests.append(request)
        verify_resume_tokens(request)  # raises IntegrityError on poison
        self.served_past_verify += 1
        if len(self.requests) == 1:
            yield {"token_ids": [100]}
            yield {"token_ids": [101]}
            raise StreamError("worker died")
        budget = request["stop_conditions"]["max_tokens"]
        for t in range(budget):
            yield {"token_ids": [t],
                   "finish_reason": "length" if t == budget - 1 else None}


async def test_migration_resume_corrupt_redrives_from_pristine_copy():
    """The operator stamps the resume prompt; a bit flipped in transit
    raises IntegrityError at the receiving engine's intake — BEFORE any
    prefill — and the operator re-drives from its pristine copy. The
    client sees one uninterrupted stream."""
    from dynamo_tpu.frontend.migration import Migration

    eng = _VerifyingFlakyEngine()
    mig = Migration(eng, migration_limit=3, retry_delay_s=0.001,
                    rng=random.Random(0))
    before = integrity_snapshot().get("migration.resume", 0)
    FAULTS.configure("migration.resume:corrupt=1x1")
    try:
        items = [
            i async for i in mig.generate(
                {"token_ids": [1, 2], "stop_conditions": {"max_tokens": 6}},
                Context(),
            )
        ]
    finally:
        FAULTS.clear()
    assert items[-1]["finish_reason"] == "length"
    # three attempts: original, poisoned resume (rejected at intake,
    # never served), clean re-drive
    assert len(eng.requests) == 3
    assert eng.served_past_verify == 2
    resume_tokens = [1, 2, 100, 101]
    assert eng.requests[1]["token_ids"] == resume_tokens
    assert eng.requests[2]["token_ids"] == resume_tokens
    assert eng.requests[2]["token_checksum"] == token_checksum(resume_tokens)
    assert integrity_snapshot()["migration.resume"] == before + 1


async def test_migration_resume_engine_intake_bit_identical(decode_schedule):
    """Real-engine leg: a stamped resume prompt that arrives corrupted is
    refused (IntegrityError, no prefill of poison); the same pristine
    request then continues BIT-IDENTICAL to the uninjected greedy run."""
    from dynamo_tpu.engine.config import EngineConfig, ModelSpec
    from dynamo_tpu.engine.worker import launch_engine_worker
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.hub import InMemoryHub

    spec = ModelSpec(
        name="tiny-test", vocab_size=272, hidden_size=32,
        intermediate_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=8, dtype="float32",
    )
    drt = DistributedRuntime(InMemoryHub())
    eng, _ = await launch_engine_worker(
        drt, spec=spec,
        engine_config=EngineConfig(
            page_size=4, num_pages=128, max_pages_per_seq=32,
            max_decode_slots=4, prefill_buckets=(32, 64), **decode_schedule,
        ),
        model_name="tiny-test",
    )
    prompt = list(range(50, 50 + 17))

    async def run(request):
        toks = []
        async for item in eng.generate(request, Context()):
            assert item.get("finish_reason") != "error", item
            toks.extend(item.get("token_ids") or [])
        return toks

    try:
        want = await run({
            "token_ids": prompt, "sampling": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": 8, "ignore_eos": True},
        })
        # the resume request the migration operator would build after the
        # first 2 tokens, integrity stamp included
        resume_tokens = prompt + want[:2]
        resume = {
            "token_ids": resume_tokens, "sampling": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": 6, "ignore_eos": True},
            "token_checksum": token_checksum(resume_tokens),
        }
        FAULTS.configure("migration.resume:corrupt=1x1")
        try:
            with pytest.raises(IntegrityError):
                await run(dict(resume))
        finally:
            FAULTS.clear()
        # pristine re-drive: greedy continuation matches the reference
        assert await run(dict(resume)) == want[2:]
    finally:
        await eng.close()
        await drt.close()
    assert eng.allocator.active_pages == 0


# ------------------------------------------- SDC canary quarantine cycle


async def test_sdc_canary_mismatch_quarantines_then_clean_readmit():
    """The canary is a known-answer test: the first clean canary's tokens
    are the golden; a mismatch (injected via the health.canary corrupt
    fault) quarantines IMMEDIATELY — soft-withdrawal, the card stays in
    the hub flagged quarantined — and ``readmit_threshold`` consecutive
    clean canaries re-admit. A dirty canary mid-quarantine resets the
    streak (both directions of the readmit contract)."""
    import aiohttp

    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.health import (
        HealthCheckConfig,
        HealthCheckManager,
        SystemStatusServer,
        is_quarantined,
    )
    from dynamo_tpu.runtime.hub import InMemoryHub

    async def handler(request, context):
        yield {"token_ids": [5, 6, 7], "finish_reason": "stop"}

    drt = DistributedRuntime(InMemoryHub())
    ep = drt.namespace("dyn").component("backend").endpoint("generate")
    served = await ep.serve(handler)
    client = await ep.client().start()
    await client.wait_for_instances(1, timeout=5)

    health = HealthCheckManager(drt, HealthCheckConfig(
        interval_s=0.02, timeout_s=1.0, failure_threshold=2,
        readmit_threshold=3,
    ))
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    h = health.register(served)
    server = await SystemStatusServer(
        health=health, metrics=MetricsRegistry(), port=0
    ).start()

    async def wait_for(pred, what, timeout=5.0):
        deadline = asyncio.get_event_loop().time() + timeout
        while asyncio.get_event_loop().time() < deadline:
            if pred():
                return
            await asyncio.sleep(0.01)
        raise AssertionError(f"timed out waiting for {what}")

    try:
        # golden recorded at the first clean canary
        await wait_for(lambda: h.status == "ready", "initial ready")

        # one silently-corrupted canary answer -> immediate quarantine
        FAULTS.configure("health.canary:corrupt=1x1")
        await wait_for(lambda: h.status == "quarantined", "quarantine")
        FAULTS.clear()
        assert h.quarantine_reason == "sdc" and h.quarantines == 1
        assert "sdc" in (h.last_error or "")

        # soft-withdrawal: the card is still in the hub, flagged — this
        # is what routers exclude on and the autoscaler replaces
        card = await drt.hub.get(served.instance.path)
        assert is_quarantined(card)
        await wait_for(
            lambda: any(is_quarantined(i) for i in client.instances()),
            "client sees quarantined card",
        )

        # the quarantine counter rides the REAL /metrics surface
        async with aiohttp.ClientSession() as sess:
            async with sess.get(
                f"http://127.0.0.1:{server.port}/metrics"
            ) as r:
                body = await r.text()
        assert 'dynamo_worker_quarantines_total{reason="sdc"}' in body

        # direction 1 of readmission: a dirty canary RESETS the clean
        # streak — quarantine does not decay through corruption
        await wait_for(lambda: h.clean_streak >= 1, "streak starts")
        FAULTS.configure("health.canary:corrupt=1x1")
        await wait_for(lambda: h.clean_streak == 0, "streak reset")
        FAULTS.clear()
        assert h.status == "quarantined"
        assert h.quarantines == 1  # still the same quarantine episode

        # direction 2: N consecutive clean canaries re-admit
        await wait_for(lambda: h.status == "ready", "readmission")
        card = await drt.hub.get(served.instance.path)
        assert not is_quarantined(card)
        await wait_for(
            lambda: not any(is_quarantined(i) for i in client.instances()),
            "client sees re-admitted card",
        )
    finally:
        FAULTS.clear()
        await server.stop()
        await health.close()
        await client.close()
        await drt.close()
