"""KVBM tiered KV cache: pool semantics, engine offload/onboard, determinism.

Mirrors the reference's KVBM test posture (SURVEY.md §4: lib/llm/tests/
block_manager.rs + tests/kvbm determinism tests): outputs must be identical
with and without offloading, and a G1-evicted prefix must be served from
host/disk tiers without recompute.
"""

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.kvbm import DiskBlockPool, HostBlockPool, KvBlockManager, KvbmConfig
from dynamo_tpu.runtime.context import Context

pytestmark = pytest.mark.unit

SPEC = ModelSpec(
    vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)


def small_config(**kw):
    defaults = dict(
        page_size=4, num_pages=64, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(8, 16, 32, 64),
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def block(fill, nbytes=256):
    """A fake KV block pair of roughly nbytes total."""
    n = max(nbytes // 8, 2)
    k = np.full((n,), fill, np.float32)
    return k, k + 0.5


def request(token_ids, max_tokens=6):
    return {
        "token_ids": list(token_ids),
        "sampling": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
        "eos_token_ids": [2],
    }


async def run(engine, token_ids, max_tokens=6):
    out = []
    async for item in engine.generate(request(token_ids, max_tokens), Context()):
        out.extend(item.get("token_ids") or [])
        assert item.get("finish_reason") != "error", item
    return out


# ------------------------------------------------------------------- pools


def test_host_pool_lru_and_budget():
    evicted = []
    pool = HostBlockPool(1000, on_evict=lambda sh, k, v: evicted.append(sh))
    k, v = block(1.0, 400)
    per = k.nbytes + v.nbytes
    cap = 1000 // per  # how many fit
    for i in range(cap):
        assert pool.put(i, *block(float(i), 400))
    assert len(pool) == cap and not evicted
    pool.get(0)  # touch 0 -> 1 becomes LRU
    pool.put(99, *block(9.9, 400))
    assert 1 in set(evicted) and 0 in pool and 99 in pool
    # oversize block is rejected
    assert not pool.put(500, np.zeros(2000, np.float32), np.zeros(2000, np.float32))
    pool.clear()
    assert len(pool) == 0 and pool.used_bytes == 0


def test_disk_pool_persistence(tmp_path):
    d = str(tmp_path / "kv")
    pool = DiskBlockPool(d, 1 << 20)
    k, v = block(3.25)
    assert pool.put(42, k, v)
    got = pool.get(42)
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)
    # new pool over the same dir sees the block (restart survival)
    pool2 = DiskBlockPool(d, 1 << 20)
    assert 42 in pool2
    got2 = pool2.get(42)
    np.testing.assert_array_equal(got2[0], k)


def test_manager_promotes_disk_hits(tmp_path):
    mgr = KvBlockManager(KvbmConfig(
        host_bytes=1 << 20, disk_bytes=1 << 20, disk_dir=str(tmp_path / "kv"),
    ))
    k, v = block(7.0)
    mgr.disk.put(5, k, v)
    assert 5 not in mgr.host
    got = mgr.get(5)
    np.testing.assert_array_equal(got[0], k)
    assert 5 in mgr.host  # promoted G3 -> G2
    assert mgr.stats.onboard_hits_disk == 1


def test_host_evictions_cascade_to_disk(tmp_path):
    mgr = KvBlockManager(KvbmConfig(
        host_bytes=800, disk_bytes=1 << 20, disk_dir=str(tmp_path / "kv"),
    ))
    for i in range(6):
        mgr.offer(i, *block(float(i), 400))
    # early blocks fell off G2 into G3
    assert len(mgr.host) < 6
    assert all((i in mgr.host) or (i in mgr.disk) for i in range(6))


# ------------------------------------------------- engine offload + onboard


async def test_engine_offload_then_onboard_after_g1_eviction(decode_schedule):
    kvbm = KvBlockManager(KvbmConfig(host_bytes=1 << 20))
    engine = InferenceEngine(SPEC, small_config(**decode_schedule), kvbm=kvbm)
    prompt = list(range(30, 30 + 13))  # 3 complete blocks of 4
    want = await run(engine, prompt)

    engine.offload.flush()
    assert kvbm.stats.offloaded >= 3  # prompt blocks written through to G2

    # wipe G1's prefix cache entirely -> only KVBM has the blocks
    evicted = engine.allocator.clear_cache()
    assert evicted > 0
    from dynamo_tpu.tokens import TokenBlockSequence

    hashes = TokenBlockSequence.from_tokens(prompt, 4).sequence_hashes()
    assert engine.allocator.match_prefix(hashes) == []  # G1 empty
    # but the policy probe still sees the host-tier coverage
    assert engine.prefix_hit_tokens(prompt) == 12

    got = await run(engine, prompt)
    assert got == want  # determinism across tiers
    assert kvbm.stats.onboard_hits_host >= 3
    # onboarded blocks re-entered G1's prefix cache
    assert engine.prefix_hit_tokens(prompt) >= 8
    await engine.close()


async def test_kvbm_disk_tier_roundtrip(tmp_path, decode_schedule):
    """Blocks pushed all the way to disk still serve onboards."""
    kvbm = KvBlockManager(KvbmConfig(
        host_bytes=4096,  # tiny G2: prompt blocks spill to disk quickly
        disk_bytes=1 << 20, disk_dir=str(tmp_path / "kv"),
    ))
    engine = InferenceEngine(SPEC, small_config(**decode_schedule), kvbm=kvbm)
    prompt = list(range(40, 40 + 13))
    want = await run(engine, prompt)
    engine.offload.flush()

    # churn G2 with other prompts until the first prompt's blocks hit disk
    for base in range(5):
        await run(engine, list(range(60 + base * 13, 60 + base * 13 + 13)), 2)
    engine.offload.flush()

    engine.allocator.clear_cache()
    got = await run(engine, prompt)
    assert got == want
    await engine.close()


async def test_kvbm_output_parity_with_and_without(decode_schedule):
    """Offloading must never change outputs (reference determinism tests).
    The plain engine keeps the default schedule: the one under test must
    agree with it token for token on either."""
    prompt = list(range(50, 50 + 11))
    plain = InferenceEngine(SPEC, small_config())
    want = await run(plain, prompt)
    await plain.close()

    with_kvbm = InferenceEngine(
        SPEC, small_config(**decode_schedule),
        kvbm=KvBlockManager(KvbmConfig(host_bytes=1 << 20)),
    )
    got = await run(with_kvbm, prompt)
    assert got == want
    # and again through the onboard path
    with_kvbm.offload.flush()
    with_kvbm.allocator.clear_cache()
    got2 = await run(with_kvbm, prompt)
    assert got2 == want
    await with_kvbm.close()


# ----------------------------------------------- quantized (fp8) blocks


def fp8_block(num_layers=2, nbytes_per_page=130, fill=3):
    """A fake PACKED quantized block pair: uint8 [L, X] per page, exactly
    the payload llama.extract_kv_pages emits for a QuantPool (fp8 value
    bytes ++ bf16 scale bytes). Byte payloads are what the tiers must
    preserve EXACTLY — any dtype coercion shows up as corruption."""
    k = np.arange(
        num_layers * nbytes_per_page, dtype=np.uint8
    ).reshape(num_layers, nbytes_per_page)
    return (k + fill) % 251, (k + fill + 100) % 251


def test_quantized_blocks_roundtrip_host_and_disk(tmp_path):
    """fp8 payload + scales survive host AND disk tiers byte-exactly (no
    silent upcast: the pools only ever see uint8)."""
    mgr = KvBlockManager(KvbmConfig(
        host_bytes=1 << 20, disk_bytes=1 << 20,
        disk_dir=str(tmp_path / "kv"),
    ))
    k, v = fp8_block()
    mgr.offer(11, k, v)
    got = mgr.get(11)
    assert got[0].dtype == np.uint8 and got[1].dtype == np.uint8
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)
    # force the disk path: push straight to G3, then onboard
    mgr2 = KvBlockManager(KvbmConfig(
        host_bytes=1 << 20, disk_bytes=1 << 20,
        disk_dir=str(tmp_path / "kv2"),
    ))
    mgr2.disk.put(12, k, v)
    got = mgr2.get(12)
    assert got[0].dtype == np.uint8
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)
    assert mgr2.stats.onboard_hits_disk == 1


async def test_quantized_blocks_roundtrip_remote_tier():
    """G4: the packed uint8 payload round-trips the hub object store's
    single-dtype header byte-exactly, cross-manager."""
    import asyncio

    from dynamo_tpu.runtime.hub import InMemoryHub

    hub = InMemoryHub()
    loop = asyncio.get_running_loop()
    cfg = KvbmConfig(host_bytes=1 << 20, remote_max_blocks=8)
    a = KvBlockManager(cfg, hub=hub, loop=loop, namespace="q")
    b = KvBlockManager(cfg, hub=hub, loop=loop, namespace="q")
    k, v = fp8_block()
    await asyncio.to_thread(a.offer, 0xF8, k, v)
    got = None
    for _ in range(100):
        got = await asyncio.to_thread(b.get, 0xF8)
        if got is not None:
            break
        await asyncio.sleep(0.02)
    assert got is not None
    assert got[0].dtype == np.uint8 and got[1].dtype == np.uint8
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)
    # the footprint gauge counts this process's G4 writes
    assert a.tier_bytes()["remote"] > 0


async def test_fp8_engine_offload_onboard_and_corrupt_scale_miss():
    """End-to-end quantized KVBM: an fp8 engine's sealed pages offload as
    packed blocks, onboard after G1 eviction with identical outputs, and
    a block whose SCALE bytes decode non-finite is treated as a tier
    MISS (truncating the consecutive prefix) instead of poisoning a
    page — the g4 corrupt-payload posture, at the dequant boundary."""
    import jax.numpy as jnp

    import ml_dtypes

    kvbm = KvBlockManager(KvbmConfig(host_bytes=1 << 20))
    engine = InferenceEngine(
        SPEC, small_config(kv_dtype="fp8"), kvbm=kvbm
    )
    assert engine.kv_dtype == "fp8"
    # a prompt whose greedy stream does not hang on a near-tie: the second
    # run reads its prefix back in fp8 where the first scored exact rows,
    # and on two prompts of four a tie of the tiny model's logits then
    # falls the other way (a bf16 pool gives equal streams on all four)
    prompt = list(range(40, 40 + 13))
    want = await run(engine, prompt)
    engine.offload.flush()
    assert kvbm.stats.offloaded >= 3

    # offloaded blocks are PACKED uint8 payloads of the quantized width
    from dynamo_tpu.ops.quant import packed_bytes_per_page

    sh = next(iter(kvbm.host._blocks))
    blk_k, blk_v = kvbm.host.get(sh)
    assert blk_k.dtype == np.uint8
    assert blk_k.shape == (
        engine.k_pages.shape[0], packed_bytes_per_page(engine.k_pages)
    )

    engine.allocator.clear_cache()
    got = await run(engine, prompt)
    assert got == want  # tier round-trip preserves fp8 + scales exactly
    assert kvbm.stats.onboard_hits_host >= 3

    # corrupted-scale guard: NaN out one block's scale bytes — the
    # validator must cut the prefix THERE and count a miss
    good = (blk_k.copy(), blk_v.copy())
    bad_k = blk_k.copy()
    nan_bf16 = np.array([np.nan], dtype=ml_dtypes.bfloat16).view(np.uint8)
    bad_k[0, -2:] = nan_bf16
    misses0 = kvbm.stats.onboard_misses
    kept = engine._validate_quant_blocks(
        [good, (bad_k, blk_v), good], [0x111, sh, 0x222]
    )
    assert len(kept) == 1  # the corrupt block and everything after drop
    assert kvbm.stats.onboard_misses == misses0 + 1
    # the corrupt block was EVICTED from the host tier: the next admission
    # refetches (or genuinely misses) instead of looping fetch->reject
    assert kvbm.host.get(sh) is None
    # wrong payload length is equally a miss (hash absent from tiers: the
    # eviction is a tolerated no-op)
    kept = engine._validate_quant_blocks([(blk_k[:, :-1], blk_v)], [0x333])
    assert kept == []
    await engine.close()


async def test_fp8_mla_engine_onboard_not_rejected():
    """MLA blocks carry an inert v slot (the latent IS the cache); the
    quantized-onboard validator must judge only the parts whose engine
    pool is actually quantized, or every MLA+fp8 onboard is spuriously
    rejected as corrupt (prefix reuse silently dead for the family)."""
    kvbm = KvBlockManager(KvbmConfig(host_bytes=1 << 20))
    engine = InferenceEngine(
        ModelSpec.tiny_deepseek(), small_config(kv_dtype="fp8"), kvbm=kvbm
    )
    # a prompt whose greedy stream does not hang on a near-tie: the second
    # run reads its prefix back in fp8 where the first scored exact rows,
    # and on two prompts of four a tie of the tiny model's logits then
    # falls the other way (a bf16 pool gives equal streams on all four)
    prompt = list(range(40, 40 + 13))
    want = await run(engine, prompt)
    engine.offload.flush()
    assert kvbm.stats.offloaded >= 3

    engine.allocator.clear_cache()
    misses0 = kvbm.stats.onboard_misses
    got = await run(engine, prompt)
    assert got == want
    assert kvbm.stats.onboard_hits_host >= 3
    assert kvbm.stats.onboard_misses == misses0  # no spurious corruption
    await engine.close()


async def test_kvbm_tier_bytes_gauge_exported():
    """dynamo_kvbm_tier_bytes{tier} renders on the PR 10 telemetry
    registry with the pools' live byte footprints."""
    from dynamo_tpu.engine.telemetry import REGISTRY, EngineCollector

    kvbm = KvBlockManager(KvbmConfig(host_bytes=1 << 20))
    engine = InferenceEngine(SPEC, small_config(), kvbm=kvbm)
    await engine.start()
    try:
        await run(engine, list(range(30, 43)))
        engine.offload.flush()
        assert kvbm.tier_bytes()["host"] > 0
        collector = EngineCollector(engine)
        collector.sample()
        text = REGISTRY.exposition().decode()
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("dynamo_kvbm_tier_bytes{")
            and 'tier="host"' in ln
            and f'engine="{collector.label}"' in ln
        )
        assert float(line.split()[-1]) == float(
            kvbm.tier_bytes()["host"]
        )
    finally:
        await engine.close()


async def test_g4_remote_tier_cross_worker():
    """G4 (hub object store): a block offloaded by one manager onboards on
    ANOTHER manager sharing the hub — the cross-worker prefix story the
    reference's remote tier exists for (CacheLevel::G4)."""
    import asyncio

    import numpy as np

    from dynamo_tpu.kvbm.manager import KvbmConfig, KvBlockManager
    from dynamo_tpu.runtime.hub import InMemoryHub

    hub = InMemoryHub()
    loop = asyncio.get_running_loop()
    cfg = KvbmConfig(host_bytes=1 << 20, remote_max_blocks=8)
    a = KvBlockManager(cfg, hub=hub, loop=loop, namespace="t")
    b = KvBlockManager(cfg, hub=hub, loop=loop, namespace="t")

    k = np.arange(2 * 2 * 4 * 8, dtype=np.float32).reshape(2, 2, 4, 8)
    v = k + 7.0
    await asyncio.to_thread(a.offer, 0xABC, k, v)

    # B has never seen the block locally; G4 writes land via a background
    # writer thread, so poll
    assert 0xABC not in b
    got = None
    for _ in range(100):
        got = await asyncio.to_thread(b.get, 0xABC)
        if got is not None:
            break
        await asyncio.sleep(0.02)
    assert got is not None
    np.testing.assert_array_equal(got[0], k)
    np.testing.assert_array_equal(got[1], v)
    assert b.stats.onboard_hits_remote == 1
    # promoted into B's host tier: next get hits G2
    await asyncio.to_thread(b.get, 0xABC)
    assert b.stats.onboard_hits_host == 1

    # the per-process write cap holds
    small = KvBlockManager(
        KvbmConfig(host_bytes=1 << 20, remote_max_blocks=1),
        hub=hub, loop=loop, namespace="t2",
    )
    await asyncio.to_thread(small.offer, 1, k, v)
    await asyncio.to_thread(small.offer, 2, k, v)
    fresh = KvBlockManager(
        KvbmConfig(host_bytes=1 << 20, remote_max_blocks=8),
        hub=hub, loop=loop, namespace="t2",
    )
    got1 = None
    for _ in range(100):
        got1 = await asyncio.to_thread(fresh.get, 1)
        if got1 is not None:
            break
        await asyncio.sleep(0.02)
    assert got1 is not None
    assert await asyncio.to_thread(fresh.get, 2) is None
    # batched consecutive onboard across workers (the admission-path call)
    both = KvBlockManager(
        KvbmConfig(host_bytes=1 << 20, remote_max_blocks=8),
        hub=hub, loop=loop, namespace="t",
    )
    blocks = await asyncio.to_thread(both.get_consecutive, [0xABC, 0xDEF])
    assert len(blocks) == 1  # stops at the first miss
    np.testing.assert_array_equal(blocks[0][0], k)
