"""Endpoint picker (gateway/epp.py): the GIE EPP role — KV-aware
routing decisions over HTTP with model-aware tokenization (ref
deploy/inference-gateway/ dyn-kv plugin semantics)."""

import aiohttp
import pytest

from dynamo_tpu.gateway.epp import EndpointPicker
from dynamo_tpu.kv_router.protocols import RouterConfig
from dynamo_tpu.mocker.__main__ import launch_mock_worker
from dynamo_tpu.mocker.engine import MockEngineConfig
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.hub import InMemoryHub

pytestmark = pytest.mark.integration


async def test_epp_picks_kv_warm_worker_with_gie_header():
    drt = DistributedRuntime(InMemoryHub())
    cfg = MockEngineConfig(block_size=4, speedup_ratio=1000.0)
    engines = []
    served = []
    for _ in range(2):
        eng, s = await launch_mock_worker(
            drt, "dyn", "backend", "generate", cfg,
        )
        engines.append(eng)
        served.append(s)
    epp = await EndpointPicker(
        drt, namespace="dyn", target_component="backend",
        config=RouterConfig(block_size=4), host="127.0.0.1", port=0,
    ).start()
    base = f"http://127.0.0.1:{epp.port}"
    try:
        async with aiohttp.ClientSession() as sess:
            async with sess.get(f"{base}/healthz") as r:
                assert r.status == 200

            # warm worker A with a prefix (through the real mock engine:
            # its KV events flow to the router the EPP consumes)
            warm_tokens = list(range(40, 72))
            target = served[0].instance
            async for _ in engines[0].generate(
                {"token_ids": warm_tokens,
                 "stop_conditions": {"max_tokens": 2}},
                Context("warm"),
            ):
                pass
            # poll until the router indexed the events
            picked = None
            for _ in range(100):
                async with sess.post(
                    f"{base}/pick", json={"token_ids": warm_tokens}
                ) as r:
                    if r.status == 200:
                        body = await r.json()
                        if body["overlap_blocks"] > 0:
                            picked = (body, dict(r.headers))
                            break
                import asyncio

                await asyncio.sleep(0.05)
            assert picked is not None, "router never saw the warm prefix"
            body, headers = picked
            assert body["worker_id"] == target.instance_id
            assert body["endpoint"]
            # the GIE convention: gateways copy this header to the route
            assert (
                headers["x-gateway-destination-endpoint"]
                == body["endpoint"]
            )

            # prompt path: model-aware tokenization via the model card's
            # tokenizer (mock tokenizer here) — the card must exist; a
            # named model without one 404s below
            from dynamo_tpu.frontend.model_card import (
                ModelDeploymentCard,
            )

            card = ModelDeploymentCard(
                name="mock-model", namespace="dyn",
                component="backend", endpoint="generate",
            )
            await drt.hub.put(
                card.key_for(target.instance_id), card.to_dict()
            )
            async with sess.post(
                f"{base}/pick",
                json={"model": "mock-model", "prompt": "hello epp"},
            ) as r:
                assert r.status == 200
                body2 = await r.json()
                assert body2["endpoint"]

            # validation + no-worker behavior
            async with sess.post(f"{base}/pick", json={}) as r:
                assert r.status == 400

            # unknown model name: 404, NOT a silent mock-tokenizer
            # fallback that returns confidently wrong overlap estimates
            async with sess.post(
                f"{base}/pick",
                json={"model": "no-such-model", "prompt": "hi"},
            ) as r:
                assert r.status == 404
                assert "no-such-model" in (await r.json())["error"]
            # omitted model still defaults to the first card
            async with sess.post(
                f"{base}/pick", json={"prompt": "hi"}
            ) as r:
                assert r.status == 200
    finally:
        await epp.close()
        await drt.close()


async def test_epp_metrics_expose_pick_latency_and_cache_outcomes():
    """The EPP /metrics surface (PR-10 satellite): every pick lands in
    dynamo_epp_pick_seconds, and pick-path prefix-cache lookups count
    hits vs misses per cache — the scrapeable complement of the
    hub_scans healthz field."""
    drt = DistributedRuntime(InMemoryHub())
    cfg = MockEngineConfig(block_size=4, speedup_ratio=1000.0)
    await launch_mock_worker(drt, "dyn", "backend", "generate", cfg)
    epp = await EndpointPicker(
        drt, namespace="dyn", target_component="backend",
        config=RouterConfig(block_size=4), host="127.0.0.1", port=0,
    ).start()
    base = f"http://127.0.0.1:{epp.port}"
    try:
        import asyncio

        async with aiohttp.ClientSession() as sess:
            ok = 0
            for _ in range(100):
                async with sess.post(
                    f"{base}/pick", json={"token_ids": [1, 2, 3, 4]}
                ) as r:
                    if r.status == 200:
                        ok += 1
                if ok >= 3:
                    break
                await asyncio.sleep(0.05)
            assert ok >= 3
            async with sess.get(f"{base}/metrics") as r:
                assert r.status == 200
                text = await r.text()
        lines = text.splitlines()
        count = next(
            ln for ln in lines
            if ln.startswith("dynamo_epp_pick_seconds_count")
        )
        # every pick attempt observed (failed 503 probes count too —
        # latency of a bad pick is still pick latency)
        assert float(count.split()[-1]) >= 3
        hits = [
            ln for ln in lines
            if ln.startswith("dynamo_epp_cache_lookups_total")
            and 'outcome="hit"' in ln
        ]
        misses = [
            ln for ln in lines
            if ln.startswith("dynamo_epp_cache_lookups_total")
            and 'outcome="miss"' in ln
        ]
        # first instance resolution misses (cold cache), repeats hit
        assert any(float(ln.split()[-1]) > 0 for ln in misses), text
        assert any(float(ln.split()[-1]) > 0 for ln in hits), text
    finally:
        await epp.close()
        await drt.close()


async def test_epp_503_when_no_workers():
    drt = DistributedRuntime(InMemoryHub())
    epp = await EndpointPicker(
        drt, namespace="dyn", target_component="backend",
        config=RouterConfig(block_size=4), host="127.0.0.1", port=0,
    ).start()
    try:
        async with aiohttp.ClientSession() as sess:
            async with sess.post(
                f"http://127.0.0.1:{epp.port}/pick",
                json={"token_ids": [1, 2, 3]},
            ) as r:
                assert r.status == 503
    finally:
        await epp.close()
        await drt.close()


async def test_prefix_cache_ttl_backstop():
    """_PrefixCache without its watch loop: the TTL bounds staleness
    (the hub-watch-down fallback) and expiry forces exactly one
    re-scan."""
    import asyncio

    from dynamo_tpu.gateway.epp import _PrefixCache

    hub = InMemoryHub()
    cache = _PrefixCache(hub, "x/", ttl_s=0.05)
    assert await cache.get() == {}
    await hub.put("x/a", {"v": 1})
    assert await cache.get() == {}  # inside the TTL: served from cache
    assert cache.scans == 1
    await asyncio.sleep(0.06)
    assert (await cache.get()).get("x/a") == {"v": 1}
    assert cache.scans == 2


async def test_epp_cached_pick_does_zero_hub_scans():
    """Pick-path micro-benchmark (ROADMAP #7 EPP slice): after the
    first pick warms the card + instance caches, steady-state picks do
    ZERO hub round-trips — the scan counter stays flat while picks
    grow."""
    import time

    from dynamo_tpu.frontend.model_card import ModelDeploymentCard

    drt = DistributedRuntime(InMemoryHub())
    cfg = MockEngineConfig(block_size=4, speedup_ratio=1000.0)
    _eng, served = await launch_mock_worker(
        drt, "dyn", "backend", "generate", cfg,
    )
    card = ModelDeploymentCard(
        name="mock-model", namespace="dyn",
        component="backend", endpoint="generate",
    )
    await drt.hub.put(card.key_for(served.instance.instance_id),
                      card.to_dict())
    epp = await EndpointPicker(
        drt, namespace="dyn", target_component="backend",
        config=RouterConfig(block_size=4), host="127.0.0.1", port=0,
        card_ttl_s=30.0,  # long TTL: the watch is the invalidator
    ).start()
    base = f"http://127.0.0.1:{epp.port}"
    try:
        import asyncio

        async with aiohttp.ClientSession() as sess:
            # first pick warms the caches (poll: the KV router needs a
            # beat to index the worker's registration watch events)
            for _ in range(100):
                async with sess.post(
                    f"{base}/pick",
                    json={"model": "mock-model",
                          "prompt": "warm the caches"},
                ) as r:
                    if r.status == 200:
                        break
                await asyncio.sleep(0.05)
            else:
                raise AssertionError("router never learned the worker")
            warm_scans = epp._cards.scans + epp._instances.scans
            assert warm_scans >= 1  # the first pick paid the scans

            t0 = time.perf_counter()
            n_picks = 20
            for i in range(n_picks):
                async with sess.post(
                    f"{base}/pick",
                    json={"model": "mock-model", "prompt": f"pick {i}"},
                ) as r:
                    assert r.status == 200
            elapsed = time.perf_counter() - t0
            assert epp._cards.scans + epp._instances.scans == warm_scans, (
                "steady-state picks paid hub round-trips"
            )
            # generous wall bound: 20 local cached picks in well under
            # the old per-pick scan regime (sanity, not a perf gate)
            assert elapsed < 10.0
            async with sess.get(f"{base}/healthz") as r:
                health = await r.json()
                assert health["hub_scans"] == warm_scans
                assert health["picks"] >= n_picks + 1
    finally:
        await epp.close()
        await drt.close()


async def test_epp_card_add_and_remove_invalidate_within_window():
    """Regression: a NEW model card becomes pickable (and a removed one
    stops resolving) within the invalidation window — the hub watch
    fires immediately; the TTL is only the watch-down backstop."""
    import asyncio

    from dynamo_tpu.frontend.model_card import ModelDeploymentCard

    drt = DistributedRuntime(InMemoryHub())
    cfg = MockEngineConfig(block_size=4, speedup_ratio=1000.0)
    _eng, served = await launch_mock_worker(
        drt, "dyn", "backend", "generate", cfg,
    )
    epp = await EndpointPicker(
        drt, namespace="dyn", target_component="backend",
        config=RouterConfig(block_size=4), host="127.0.0.1", port=0,
        card_ttl_s=30.0,
    ).start()
    base = f"http://127.0.0.1:{epp.port}"

    async def pick_status(sess, model):
        async with sess.post(
            f"{base}/pick", json={"model": model, "prompt": "hi"}
        ) as r:
            return r.status

    try:
        async with aiohttp.ClientSession() as sess:
            # cache a (card-less) snapshot first: unknown model 404s
            assert await pick_status(sess, "late-model") == 404
            # new card: the watch event must invalidate the cached scan
            card = ModelDeploymentCard(
                name="late-model", namespace="dyn",
                component="backend", endpoint="generate",
            )
            key = card.key_for(served.instance.instance_id)
            await drt.hub.put(key, card.to_dict())
            for _ in range(40):
                if await pick_status(sess, "late-model") == 200:
                    break
                await asyncio.sleep(0.05)
            else:
                raise AssertionError(
                    "new card never became pickable (watch invalidation "
                    "lost and TTL not honored)"
                )
            # removed card: stops resolving within the window too
            await drt.hub.delete(key)
            for _ in range(40):
                if await pick_status(sess, "late-model") == 404:
                    break
                await asyncio.sleep(0.05)
            else:
                raise AssertionError("removed card kept resolving")
    finally:
        await epp.close()
        await drt.close()


# ----------------------------------------------- pickline fast path


async def test_pickline_fast_path_matches_http_pick():
    """The persistent-connection pickline transport serves the SAME
    decision as POST /pick (one pick_decision core, two transports):
    pipelined picks answer in order with id echo, a malformed line gets
    an in-band 400 without killing the connection, and the latency
    histogram records both transports."""
    import asyncio

    from dynamo_tpu.gateway.pickline import PickLineClient

    drt = DistributedRuntime(InMemoryHub())
    cfg = MockEngineConfig(block_size=4, speedup_ratio=1000.0)
    for _ in range(3):
        await launch_mock_worker(drt, "dyn", "backend", "generate", cfg)
    epp = await EndpointPicker(
        drt, namespace="dyn", target_component="backend",
        config=RouterConfig(block_size=4), host="127.0.0.1", port=0,
        pick_port=0, shard_id=1, shards=2,
    ).start()
    try:
        deadline = 100
        while len(epp.kv.scheduler.workers()) < 3 and deadline:
            await asyncio.sleep(0.02)
            deadline -= 1
        assert epp.pick_port, "pickline never started"
        cl = await PickLineClient("127.0.0.1", epp.pick_port).connect()
        toks = list(range(16))
        rs = await asyncio.gather(*(
            cl.pick({"token_ids": toks, "request_id": f"pl-{i}"})
            for i in range(8)
        ))
        assert all(r["status"] == 200 for r in rs)
        assert all(r["endpoint"] and "worker_id" in r for r in rs)
        # sharded processes stamp their shard id on the payload
        assert all(r["shard"] == 1 for r in rs)
        # ids echo back in request order
        assert [r["id"] for r in rs] == sorted(r["id"] for r in rs)

        # same decision as the HTTP route (fresh rid; temp-0 determinism)
        async with aiohttp.ClientSession() as sess:
            async with sess.post(
                f"http://127.0.0.1:{epp.port}/pick",
                json={"token_ids": toks},
            ) as r:
                http_body = await r.json()
        assert http_body["worker_id"] == rs[0]["worker_id"]

        # a malformed request body answers 400 in-band, connection lives
        bad = await cl.pick({"token_ids": "not-a-list"})
        assert bad["status"] == 503  # scheduler bounced the bad tokens
        ok = await cl.pick({"token_ids": toks})
        assert ok["status"] == 200
        await cl.close()

        # both transports observed into the pick histogram
        async with aiohttp.ClientSession() as sess:
            async with sess.get(
                f"http://127.0.0.1:{epp.port}/metrics"
            ) as r:
                text = await r.text()
        assert "dynamo_epp_pick_seconds" in text
        assert "dynamo_router_pick_seconds" in text
        assert 'dynamo_router_shard_id 1.0' in text
    finally:
        await epp.close()
        await drt.close()


async def test_pickline_malformed_line_keeps_connection():
    import asyncio
    import json as _json

    from dynamo_tpu.gateway.pickline import PickLineServer

    class FakePicker:
        async def pick_decision(self, body):
            return 200, {"worker_id": 1, "echo": body.get("x")}, {}

        def observe_pick(self, s):
            pass

    srv = await PickLineServer(FakePicker(), port=0).start()
    try:
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", srv.port
        )
        writer.write(b"this is not json\n")
        writer.write(_json.dumps({"id": 7, "x": "y"}).encode() + b"\n")
        await writer.drain()
        bad = _json.loads(await reader.readline())
        good = _json.loads(await reader.readline())
        assert bad["status"] == 400 and bad["id"] is None
        assert good == {"id": 7, "status": 200, "worker_id": 1,
                        "echo": "y"}
        writer.close()
    finally:
        await srv.close()


def test_shard_child_argv_fanout():
    """The --shards supervisor's child argv: explicit shard ids, ports
    offset per shard, deployment knobs forwarded."""
    import argparse

    from dynamo_tpu.gateway.epp import shard_child_argv

    args = argparse.Namespace(
        hub="h:1", namespace="n", component="c", endpoint="e",
        block_size=16, host="0.0.0.0", port=9100, pick_port=9200,
        shards=4,
    )
    argv2 = shard_child_argv(args, 2)
    assert argv2[1:3] == ["-m", "dynamo_tpu.gateway"]
    s = " ".join(argv2)
    assert "--shard-id 2" in s and "--shards 4" in s
    assert "--port 9102" in s and "--pick-port 9202" in s
    assert "--hub h:1" in s
    # port 0 (ephemeral) stays 0 for every shard
    args.port, args.pick_port = 0, 0
    s0 = " ".join(shard_child_argv(args, 3))
    assert "--port 0" in s0 and "--pick-port 0" in s0


async def test_pickline_client_close_fails_pending_picks():
    """Review regression: close() cancels the rx task; in-flight pick()
    callers must get ConnectionError, not hang forever."""
    import asyncio

    async def silent(reader, writer):
        try:
            await reader.read()  # never answers
        finally:
            # Python 3.12's wait_closed() waits for every connection: the
            # handler closes its side once the client has hung up
            writer.close()

    srv = await asyncio.start_server(silent, "127.0.0.1", 0)
    port = srv.sockets[0].getsockname()[1]
    from dynamo_tpu.gateway.pickline import PickLineClient

    cl = await PickLineClient("127.0.0.1", port).connect()
    try:
        task = asyncio.ensure_future(cl.pick({"token_ids": [1, 2]}))
        await asyncio.sleep(0.05)
        await cl.close()
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(task, 5)
    finally:
        srv.close()
        await srv.wait_closed()


async def test_pickline_decision_error_is_in_band_500():
    """Review regression: an unexpected pick_decision failure answers an
    in-band 500 — the connection (and pipelined neighbors) survive."""
    import asyncio
    import json as _json

    from dynamo_tpu.gateway.pickline import PickLineServer

    class FlakyPicker:
        def __init__(self):
            self.calls = 0

        async def pick_decision(self, body):
            self.calls += 1
            if self.calls == 1:
                raise RuntimeError("boom")
            return 200, {"worker_id": 7}, {}

        def observe_pick(self, s):
            pass

    srv = await PickLineServer(FlakyPicker(), port=0).start()
    try:
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", srv.port
        )
        writer.write(b'{"id": 1}\n{"id": 2}\n')
        await writer.drain()
        r1 = _json.loads(await reader.readline())
        r2 = _json.loads(await reader.readline())
        assert r1["status"] == 500 and "boom" in r1["error"]
        assert r2 == {"id": 2, "status": 200, "worker_id": 7}
        writer.close()
    finally:
        await srv.close()


async def test_pickline_unserializable_body_does_not_desync():
    """Review regression: a body json.dumps rejects must fail THAT call
    without enqueueing an orphan future — the next pick on the same
    connection still gets ITS OWN response."""
    import asyncio
    import json as _json

    from dynamo_tpu.gateway.pickline import PickLineClient

    async def echo(reader, writer):
        while True:
            line = await reader.readline()
            if not line:
                break
            body = _json.loads(line)
            writer.write(_json.dumps(
                {"id": body["id"], "status": 200, "tag": body["tag"]}
            ).encode() + b"\n")
            await writer.drain()
        writer.close()

    srv = await asyncio.start_server(echo, "127.0.0.1", 0)
    port = srv.sockets[0].getsockname()[1]
    cl = await PickLineClient("127.0.0.1", port).connect()
    try:
        with pytest.raises(TypeError):
            await cl.pick({"tag": b"bytes are not json"})
        r = await asyncio.wait_for(cl.pick({"tag": "ok"}), 5)
        assert r["status"] == 200 and r["tag"] == "ok"
    finally:
        await cl.close()
        srv.close()
        await srv.wait_closed()


async def test_pickline_pick_after_server_hangup_raises():
    """Review regression: once the server hangs up (rx loop saw EOF and
    drained), a later pick() must raise ConnectionError immediately —
    not enqueue a future nothing will ever resolve and hang."""
    import asyncio

    from dynamo_tpu.gateway.pickline import PickLineClient

    async def hangup(reader, writer):
        writer.close()

    srv = await asyncio.start_server(hangup, "127.0.0.1", 0)
    port = srv.sockets[0].getsockname()[1]
    cl = await PickLineClient("127.0.0.1", port).connect()
    try:
        # wait for the rx loop to observe the EOF
        for _ in range(100):
            if cl._closed:
                break
            await asyncio.sleep(0.01)
        assert cl._closed
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(cl.pick({"token_ids": [1]}), 5)
    finally:
        await cl.close()
        srv.close()
        await srv.wait_closed()


async def test_pickline_server_close_with_live_peer_returns():
    """Review regression: close() must actively close accepted
    connections — pickline peers are long-lived by design, and on
    py3.12.1+ Server.wait_closed() blocks until every handler ends."""
    import asyncio

    from dynamo_tpu.gateway.pickline import PickLineClient, PickLineServer

    class P:
        async def pick_decision(self, body):
            return 200, {"worker_id": 1}, {}

        def observe_pick(self, s):
            pass

    srv = await PickLineServer(P(), port=0).start()
    cl = await PickLineClient("127.0.0.1", srv.port).connect()
    r = await cl.pick({"token_ids": [1]})
    assert r["status"] == 200
    assert len(srv._conns) == 1
    # the client stays connected; close() must not wait on it
    await asyncio.wait_for(srv.close(), 5)
    assert not srv._conns
    await cl.close()
