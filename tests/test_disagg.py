"""Disaggregated prefill/decode: transfer plane, policy, e2e vs aggregated.

Port of the reference's disagg behaviors (SURVEY.md §3 call stack C) onto
the JAX engine: the decode worker delegates long prompts to a prefill pool,
pulls the KV pages, and must produce *exactly* the tokens the aggregated
path produces (greedy, same seed/params).
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.disagg.policy import DisaggPolicy
from dynamo_tpu.disagg.transfer import (
    _LOCAL_SOURCES,
    KvTransferSource,
    pull_kv_blocks,
    release_kv_blocks,
)
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.worker import launch_engine_worker
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.hub import InMemoryHub

pytestmark = pytest.mark.integration

SPEC = ModelSpec(
    name="tiny-test",
    vocab_size=272,
    hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)


def engine_config(**kw):
    defaults = dict(
        page_size=4, num_pages=128, max_pages_per_seq=32,
        max_decode_slots=4, prefill_buckets=(32, 64, 128),
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def request(token_ids, max_tokens=8, **kw):
    return {
        "token_ids": list(token_ids),
        "sampling": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
        "eos_token_ids": [2],
        **kw,
    }


async def collect(agen):
    toks, items = [], []
    async for item in agen:
        items.append(item)
        toks.extend(item.get("token_ids") or [])
    return toks, items


# ------------------------------------------------------------- transfer plane


async def test_transfer_roundtrip_tcp_and_local():
    src = await KvTransferSource().start()
    k = np.arange(2 * 3 * 4 * 2 * 8, dtype=np.float32).reshape(2, 3, 4, 2, 8)
    v = k + 1000.0
    try:
        # in-process zero-copy path
        params = src.export(k, v, num_tokens=11, page_size=4)
        k2, v2, meta = pull_kv_blocks(params)
        assert meta["num_tokens"] == 11
        np.testing.assert_array_equal(k, k2)
        np.testing.assert_array_equal(v, v2)
        # pulled exports are one-shot
        with pytest.raises(KeyError):
            pull_kv_blocks(params)

        # TCP path: hide the local registry entry to force the socket route
        params = src.export(k, v, num_tokens=11, page_size=4)
        hidden = _LOCAL_SOURCES.pop(src.uid)
        try:
            # blocking client must run off the event-loop thread (as the
            # engine does): the source's asyncio server shares this loop
            k3, v3, meta = await asyncio.to_thread(pull_kv_blocks, params)
        finally:
            _LOCAL_SOURCES[src.uid] = hidden
        np.testing.assert_array_equal(k, k3)
        np.testing.assert_array_equal(v, v3)

        # release drops the export without pulling
        released = []
        params = src.export(k, v, num_tokens=11, page_size=4,
                            on_done=lambda: released.append(1))
        release_kv_blocks(params)
        assert released == [1]
        with pytest.raises(KeyError):
            pull_kv_blocks(params)
    finally:
        await src.close()


async def test_transfer_device_to_device_path(monkeypatch):
    """PJRT device pull (jax.experimental.transfer): a jax-array export is
    pulled into device memory without host numpy staging.

    CPU-backend constraint: PJRT transfer targets TPU DCN; on CPU a second
    in-process transfer server aborts, so the test dials through the
    source's own server (single-server loopback — the only arrangement
    jaxlib supports off-TPU) by priming the connection cache. Production
    never dials in-process: the zero-copy registry path wins there.
    """
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.disagg import transfer as tmod

    monkeypatch.setenv("DYNAMO_DEVICE_TRANSFER", "1")
    src = await KvTransferSource().start()
    try:
        if src.device_addr is None:
            pytest.skip("PJRT transfer server unsupported on this backend")
        k = jnp.arange(2 * 2 * 3 * 2 * 8, dtype=jnp.float32).reshape(
            2, 2, 3, 2, 8
        )
        v = k + 500.0
        params = src.export(k, v, num_tokens=5, page_size=2)
        assert params.get("device_addr")

        # prime the conn cache with a loopback via the source's own server
        monkeypatch.setitem(
            tmod._DEVICE_CONNS, params["device_addr"],
            src._txs.connect(src.device_addr),
        )
        # force the remote (device) route
        hidden = _LOCAL_SOURCES.pop(src.uid)
        try:
            k2, v2, meta = await asyncio.to_thread(pull_kv_blocks, params)
        finally:
            _LOCAL_SOURCES[src.uid] = hidden
        assert isinstance(k2, jax.Array)
        assert meta["num_tokens"] == 5
        np.testing.assert_array_equal(np.asarray(k), np.asarray(k2))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(v2))
        # the pull released the export on the source
        assert params["transfer_id"] not in src._exports

        # a device export also serves the TCP host-staging route (fallback
        # when a peer cannot dial the PJRT plane)
        params2 = src.export(k, v, num_tokens=5, page_size=2)
        params2.pop("device_addr")
        hidden = _LOCAL_SOURCES.pop(src.uid)
        try:
            k3, _v3, _ = await asyncio.to_thread(pull_kv_blocks, params2)
        finally:
            _LOCAL_SOURCES[src.uid] = hidden
        np.testing.assert_array_equal(np.asarray(k), np.asarray(k3))
    finally:
        await src.close()


# -------------------------------------------------------------------- policy


async def test_disagg_policy_live_update():
    hub = InMemoryHub()
    policy = DisaggPolicy(max_local_prefill_length=10)
    assert not policy.prefill_remote(10)
    assert policy.prefill_remote(11)
    # prefix hits shrink the effective prefill
    assert not policy.prefill_remote(14, prefix_hit_len=4)

    await policy.watch(hub, "dynamo")
    await hub.put("v1/config/disagg/dynamo", {"max_local_prefill_length": 2})
    await asyncio.sleep(0.05)
    assert policy.prefill_remote(3)
    policy.close()
    await hub.close()


# ------------------------------------------------------------------ e2e parity


async def test_disagg_matches_aggregated_greedy(decode_schedule):
    """prefill worker + decode worker == aggregated worker, token for token."""
    prompt = list(range(40, 40 + 23))  # 23 tokens -> crosses page boundaries

    # aggregated ground truth
    drt_a = DistributedRuntime(InMemoryHub())
    agg, _ = await launch_engine_worker(
        drt_a, spec=SPEC, engine_config=engine_config(**decode_schedule), model_name="agg",
    )
    want, _ = await collect(agg.generate(request(prompt), Context()))
    await agg.close()
    await drt_a.close()
    assert len(want) == 8

    # disagg pair on a fresh hub
    drt = DistributedRuntime(InMemoryHub())
    pre, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(**decode_schedule), model_name="tiny-test",
        mode="prefill",
    )
    dec, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(**decode_schedule), model_name="tiny-test",
        mode="decode", always_remote_prefill=True,
    )
    handler = dec.frontdoor
    await handler.wait_for_prefill_pool()
    assert handler.can_prefill()
    try:
        got, items = await collect(handler.generate(request(prompt), Context()))
        assert got == want
        # the prompt really was prefilled remotely: the prefill engine sealed
        # the prompt's pages into its prefix cache, the decode engine ran
        # decode steps but never a full prefill forward
        assert pre.allocator.evictable_pages >= len(prompt) // 4
        assert dec.steps >= len(want) - 1

        # second request, same prompt: decode-side prefix cache now holds the
        # prompt (sealed during resume), so policy keeps it local
        hit = dec.prefix_hit_tokens(prompt)
        assert hit >= (len(prompt) // 4) * 4 - 4
        got2, _ = await collect(handler.generate(request(prompt), Context()))
        assert got2 == want
    finally:
        await pre.close()
        await dec.close()
        await drt.close()
    assert pre.allocator.active_pages == 0
    assert dec.allocator.active_pages == 0


async def test_disagg_kv_dtype_mismatch_rejected_loudly():
    """A bf16 prefill worker paired with an fp8 decode worker must fail the
    request with an error naming the knob — not die on a shape error inside
    the decode worker's donated insert jit."""
    prompt = list(range(40, 40 + 23))
    drt = DistributedRuntime(InMemoryHub())
    pre, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(), model_name="tiny-test",
        mode="prefill",
    )
    dec, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(kv_dtype="fp8"),
        model_name="tiny-test", mode="decode", always_remote_prefill=True,
    )
    handler = dec.frontdoor
    await handler.wait_for_prefill_pool()
    try:
        _, items = await collect(handler.generate(request(prompt), Context()))
        errs = [i for i in items if i.get("finish_reason") == "error"]
        assert errs, f"expected an error item, got {items}"
        assert "kv_dtype mismatch" in errs[-1].get("error", "")
    finally:
        await pre.close()
        await dec.close()
        await drt.close()
    assert dec.allocator.active_pages == 0


async def test_prefill_death_mid_kv_transfer_completes_with_continuity():
    """Migration × disagg (robustness PR): the prefill worker dies
    mid-KV-handoff — the remote first token was emitted but the KV pull
    fails. The decode worker must complete the request itself (local
    prefill of prompt + first token) producing EXACTLY the aggregated
    greedy token stream, and a later request must survive the prefill
    worker being gone entirely."""
    from dynamo_tpu.runtime.faults import FAULTS

    prompt = list(range(40, 40 + 23))

    # aggregated ground truth
    drt_a = DistributedRuntime(InMemoryHub())
    agg, _ = await launch_engine_worker(
        drt_a, spec=SPEC, engine_config=engine_config(), model_name="agg",
    )
    want, _ = await collect(agg.generate(request(prompt), Context()))
    await agg.close()
    await drt_a.close()

    drt = DistributedRuntime(InMemoryHub())
    pre, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(), model_name="tiny-test",
        mode="prefill",
    )
    dec, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(), model_name="tiny-test",
        mode="decode", always_remote_prefill=True,
    )
    handler = dec.frontdoor
    await handler.wait_for_prefill_pool()
    try:
        # the violence: the KV pull fails exactly once, as if the prefill
        # worker died between exporting the pages and serving the pull
        FAULTS.configure("disagg.pull:error@1x1")
        got, _ = await collect(handler.generate(request(prompt), Context()))
        assert got == want, "token continuity broken across the failed pull"
        assert dec.disagg_fallbacks == 1
        assert FAULTS.trip_counts[("disagg.pull", "error")] == 1
        FAULTS.clear()

        # now the prefill worker dies OUTRIGHT; the next request (fresh
        # prompt so the decode prefix cache can't shortcut the remote
        # path) must still complete locally
        await pre.close()
        prompt2 = list(range(70, 70 + 23))
        drt_b = DistributedRuntime(InMemoryHub())
        agg2, _ = await launch_engine_worker(
            drt_b, spec=SPEC, engine_config=engine_config(),
            model_name="agg2",
        )
        want2, _ = await collect(agg2.generate(request(prompt2), Context()))
        await agg2.close()
        await drt_b.close()
        got2, _ = await collect(handler.generate(request(prompt2), Context()))
        assert got2 == want2
    finally:
        FAULTS.clear()
        await pre.close()
        await dec.close()
        await drt.close()
    assert dec.allocator.active_pages == 0


async def test_disagg_fallback_without_prefill_pool():
    """No live prefill workers -> decode worker serves locally."""
    drt = DistributedRuntime(InMemoryHub())
    dec, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(), model_name="tiny-test",
        mode="decode", always_remote_prefill=True,
    )
    try:
        assert not dec.frontdoor.can_prefill()
        got, _ = await collect(
            dec.frontdoor.generate(request(list(range(50, 70))), Context())
        )
        assert len(got) == 8
    finally:
        await dec.close()
        await drt.close()


async def test_disagg_short_prompt_stays_local():
    drt = DistributedRuntime(InMemoryHub())
    pre, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(), model_name="tiny-test",
        mode="prefill",
    )
    dec, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(), model_name="tiny-test",
        mode="decode", max_local_prefill_length=64,
    )
    try:
        await dec.frontdoor.wait_for_prefill_pool()
        got, _ = await collect(
            dec.frontdoor.generate(request(list(range(40, 52))), Context())
        )
        assert len(got) == 8
        # prefill pool untouched: its engine never ran a step
        assert pre.steps == 0 and pre.allocator.used_pages == 0
    finally:
        await pre.close()
        await dec.close()
        await drt.close()


async def test_disagg_max_tokens_one():
    """A 1-token request through disagg finishes after the remote token."""
    drt = DistributedRuntime(InMemoryHub())
    pre, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(), model_name="tiny-test",
        mode="prefill",
    )
    dec, _ = await launch_engine_worker(
        drt, spec=SPEC, engine_config=engine_config(), model_name="tiny-test",
        mode="decode", always_remote_prefill=True,
    )
    try:
        await dec.frontdoor.wait_for_prefill_pool()
        got, items = await collect(
            dec.frontdoor.generate(
                request(list(range(40, 60)), max_tokens=1), Context()
            )
        )
        assert len(got) == 1
        assert items[-1]["finish_reason"] == "length"
        # nothing left pending on the transfer source
        await asyncio.sleep(0.05)
        assert not pre.transfer_source._exports
    finally:
        await pre.close()
        await dec.close()
        await drt.close()


async def test_shard_layout_detection_and_per_shard_staging():
    """TP-sharded KV blocks export per shard (VERDICT r2 weak #4): layout
    detection finds the single tiled axis, export advertises the shard
    table, and stage_device registers one pullable entry per shard."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.disagg.transfer import shard_layout, _dest_tp_devices
    from dynamo_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tp=2)
    # [L, n_pages, KH, page, D] sharded over kv heads (axis 2)
    k = jnp.arange(2 * 3 * 2 * 2 * 8, dtype=jnp.float32).reshape(2, 3, 2, 2, 8)
    ks = jax.device_put(k, NamedSharding(mesh, P(None, None, "tp", None, None)))
    lay = shard_layout(ks)
    assert lay is not None
    axis, parts = lay
    assert axis == 2
    assert [s for s, _p in parts] == [0, 1]
    assert all(p.shape == (2, 3, 1, 2, 8) for _s, p in parts)
    # replicated arrays are NOT per-shard exportable
    rep = jax.device_put(k, NamedSharding(mesh, P()))
    assert shard_layout(rep) is None

    # destination selection: tp width must match, other axes must be 1
    assert _dest_tp_devices(mesh, 2) is not None
    assert _dest_tp_devices(mesh, 4) is None
    assert _dest_tp_devices(None, 2) is None
    assert _dest_tp_devices(make_mesh(tp=2, dp=2), 2) is None

    class FakeTxs:
        def __init__(self):
            self.regs = []

        def await_pull(self, uuid_int, arrays):
            self.regs.append((uuid_int, [tuple(a.shape) for a in arrays]))

    src = await KvTransferSource().start()
    try:
        src._txs = FakeTxs()
        src.device_addr = "fake:0"
        vs = jax.device_put(
            k + 100.0, NamedSharding(mesh, P(None, None, "tp", None, None))
        )
        params = src.export(ks, vs, num_tokens=5, page_size=2)
        assert params["shard_axis"] == 2
        assert len(params["shards"]) == 2
        assert params["shards"][0]["k_shape"] == [2, 3, 1, 2, 8]

        from dynamo_tpu.disagg.transfer import _tcp_request

        staged = await asyncio.to_thread(
            _tcp_request, params["addr"],
            {"op": "stage_device", "transfer_id": params["transfer_id"],
             "uuid_int": params["uuid_int"]},
        )
        assert staged["ok"]
        # one registration per shard, consecutive uuid offsets
        assert [u for u, _s in src._txs.regs] == [
            params["uuid_int"] + 1, params["uuid_int"] + 2
        ]
        assert src._txs.regs[0][1] == [(2, 3, 1, 2, 8), (2, 3, 1, 2, 8)]

        # the same export still serves the TCP host-staging fallback
        hidden = _LOCAL_SOURCES.pop(src.uid)
        try:
            k2, v2, _meta = await asyncio.to_thread(
                pull_kv_blocks, {k_: v_ for k_, v_ in params.items()
                                 if k_ not in ("device_addr",)}
            )
        finally:
            _LOCAL_SOURCES[src.uid] = hidden
        np.testing.assert_array_equal(np.asarray(k), np.asarray(k2))
        np.testing.assert_array_equal(np.asarray(k + 100.0), np.asarray(v2))
    finally:
        await src.close()
