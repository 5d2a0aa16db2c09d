"""Leader-driven SPMD mirroring: one logical worker across many hosts.

Multi-controller JAX requires EVERY process of a multi-host mesh to issue
the same compiled programs in the same order — a follower that merely
joins ``jax.distributed`` and parks would deadlock the leader's first
collective. This module closes that loop (SURVEY §7 hard part (d); the
reference leans on engine-internal NCCL/MPI worlds for the same job,
e.g. components/backends/trtllm/multinode/):

- The LEADER runs the full serving engine (scheduler, paged-cache
  bookkeeping, sampling, streaming). Before every device dispatch on the
  serving path it broadcasts a step descriptor — op tag + the host-side
  arrays the jit call consumes.
- Every FOLLOWER holds an identical engine shell (same spec, config,
  deterministic params, same mesh over the same global device set) and
  replays each descriptor with the SAME jitted entry points, so the
  compiled SPMD programs and their collectives line up across processes.
  Followers keep only the device state (their parameter + KV-cache
  shards); all logits/token results are discarded — the leader is the
  single identity routers and clients see.

TRANSPORT: a dedicated leader->follower TCP stream with binary msgpack
framing (runtime/framing.py) — array payloads travel as raw bytes, no
base64, no hub round-trip on the dispatch path. The hub carries only the
leader's descriptor address (``spmd/<group>/addr``); per-connection FIFO
gives ordering, and a bounded ring buffer replays the backlog to
followers that connect late (beyond the window, the follower fails
loudly instead of silently desyncing).

PIPELINED decode replays too: burst descriptors carry the chain-validity
masks, and each follower chains fed tokens from ITS OWN pending burst
results exactly as the leader does on its shards — multi-host decode
keeps the deep-pipeline throughput. (Async admissions stay leader-local:
their first tokens reach followers through the next burst's host token
array.)
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from typing import Any

import numpy as np

from dynamo_tpu.runtime.framing import read_frame, write_frame

log = logging.getLogger("dynamo.spmd")

ADDR_KEY_FMT = "spmd/{group}/addr"
RING_FRAMES = 1024  # catch-up window cap (descriptors)
RING_BYTES = 64 * 1024 * 1024  # catch-up window cap (payload bytes)
SYNC_CHUNK_BYTES = 64 * 1024 * 1024  # rejoin snapshot chunk (< MAX_FRAME)
# how long a rejoiner may overflow its (bounded) sync queue without
# latching the strict-mode plane broken: dropping it forces a clean
# re-sync, which is recoverable — unlike a live follower losing frames
SYNC_DRAIN_GRACE_S = 300.0

# queue sentinel: the leader dropped this follower (stopped draining);
# closing its stream makes the loss VISIBLE so it re-syncs
_DROPPED = object()


def _enc(arr: np.ndarray) -> dict[str, Any]:
    arr = np.ascontiguousarray(arr)
    return {
        "dtype": arr.dtype.name,
        "shape": list(arr.shape),
        "data": arr.tobytes(),  # raw bytes: msgpack bin, no base64
    }


def _dec(d: dict[str, Any]) -> np.ndarray:
    return np.frombuffer(
        d["data"], dtype=np.dtype(d["dtype"])
    ).reshape(d["shape"])


class SpmdLeader:
    """Streams step descriptors to followers over direct TCP.

    ``publish`` is called from the engine's step THREAD and never blocks:
    it appends to the ring and hands the frame to each connection's
    writer queue on the event loop. A follower that disconnects after
    joining, or that asks for history beyond the ring, breaks lockstep
    permanently — the plane latches broken (surfaced via engine.is_dead).
    """

    def __init__(self, hub, loop: asyncio.AbstractEventLoop, group: str,
                 host: str = "127.0.0.1", strict: bool | None = None):
        self.hub = hub
        self.loop = loop
        self.group = group
        self.host = host
        self.publish_failures = 0
        self.publish_count = 0  # monotonic; lets callers scope failures
        self._broken = False
        # STRICT mode: any follower loss latches the plane broken. This
        # is the only honest policy when the mesh SPANS processes
        # (jax.distributed is not elastic — a dead process hangs the next
        # collective; ranks restart together, exactly like the
        # reference's NCCL/MPI worlds). In MIRROR topologies (each
        # process runs its own local mesh and replays descriptors), a
        # lost follower is recoverable: the leader keeps serving and the
        # restarted follower re-joins with a state sync (hello
        # {"sync": true} -> quiesced KV snapshot -> live stream).
        if strict is None:
            try:
                import jax

                strict = jax.process_count() > 1
            except Exception:  # noqa: BLE001
                # jax absent/uninitialized: single-process default. Log it
                # — a mis-probed multi-host run silently losing strictness
                # is exactly the lockstep bug class (dynalint DL003)
                log.debug("jax process_count probe failed; strict=False",
                          exc_info=True)
                strict = False
        self.strict = strict
        # rejoin state-sync requests parked until the engine reaches a
        # step boundary (serve_sync); count readable cross-thread. Each
        # entry carries its connection's writer so _resolve can skip
        # requesters that died while parked (crash-looping followers)
        self._sync_waiting: list[tuple[asyncio.Future, Any]] = []
        self._sync_pending = 0
        self.on_sync_request = None  # engine wake hook (set by engine)
        # catch-up ring: bounded by frames AND payload bytes (decode
        # descriptors are tens of KB at production batch shapes; an
        # unbounded byte footprint would pin hundreds of MB per worker)
        self._ring: deque[tuple[int, dict, int]] = deque()
        self._ring_bytes = 0
        # highest seq visible ON THE EVENT LOOP (mutated only in
        # _enqueue): the join handshake must not race the step thread's
        # publish_count, which increments before the loop callback runs
        self._loop_seq = 0
        self._conns: list[asyncio.Queue] = []
        self._server: asyncio.AbstractServer | None = None

    async def start(self) -> "SpmdLeader":
        self._server = await asyncio.start_server(
            self._serve_conn, self.host, 0
        )
        port = self._server.sockets[0].getsockname()[1]
        await self.hub.put(
            ADDR_KEY_FMT.format(group=self.group), f"{self.host}:{port}"
        )
        log.info("spmd leader descriptor plane on %s:%d", self.host, port)
        return self

    @property
    def healthy(self) -> bool:
        return not self._broken

    def mark_broken(self, reason: str) -> None:
        """Latch the plane broken: a lost/failed descriptor (or a local
        dispatch that failed after its descriptor went out) leaves
        followers permanently out of lockstep — there is no re-sync
        protocol, so it must be VISIBLE, not a silent deadlock."""
        if not self._broken:
            log.error("spmd plane broken: %s", reason)
        self._broken = True

    async def _serve_conn(self, reader, writer) -> None:
        peer = writer.get_extra_info("peername")
        hello = await read_frame(reader)
        if hello is None:
            writer.close()
            return
        if hello.get("sync"):
            # REJOIN: instead of a descriptor backlog, this follower gets
            # a quiesced state snapshot. Park until the engine reaches a
            # step boundary and calls serve_sync (on_sync_request wakes
            # an idle step loop), then stream the snapshot + live frames.
            # (A requester that dies while parked costs the engine one
            # wasted quiesce — bounded per connection attempt.)
            fut: asyncio.Future = self.loop.create_future()
            self._sync_waiting.append((fut, writer))
            self._sync_pending += 1
            if self.on_sync_request is not None:
                self.on_sync_request()
            log.info("spmd follower %s requested rejoin sync", peer)
            try:
                sync_frames, q = await fut
            except asyncio.CancelledError:
                writer.close()
                raise
            await self._stream_to(peer, writer, q, sync_frames)
            return
        from_seq = int(hello.get("from_seq", 0))
        oldest = self._ring[0][0] if self._ring else self._loop_seq + 1
        if from_seq + 1 < oldest:
            # history beyond the catch-up window: joining would silently
            # desync — refuse loudly (the follower falls back to a sync
            # rejoin)
            await write_frame(writer, {
                "op": "__reject__",
                "scalars": {"reason": f"catch-up window exceeded "
                            f"(need {from_seq + 1}, oldest {oldest})"},
                "arrays": {},
            })
            writer.close()
            if self.strict:
                self.mark_broken(
                    f"follower {peer} beyond catch-up window"
                )
            return
        # bounded to the SAME window as the catch-up ring: a join within
        # the advertised window must never be broken by publishes landing
        # during its backlog drain, while a follower that stops draining
        # latches loudly once it falls a full window behind (and the
        # bound caps the payload bytes a slow follower can pin)
        q: asyncio.Queue = asyncio.Queue(maxsize=RING_FRAMES)
        # backlog + live, no gap: single-threaded event loop between the
        # ring snapshot and the queue registration
        backlog = [f for s, f, _n in self._ring if s > from_seq]
        self._conns.append(q)
        log.info("spmd follower %s joined (%d backlog frames)",
                 peer, len(backlog))
        await self._stream_to(peer, writer, q, backlog)

    async def _stream_to(self, peer, writer, q: asyncio.Queue,
                         first_frames) -> None:
        """Shared send loop for both join paths: initial frames (backlog
        or sync snapshot), then live queue frames until the connection
        ends or the leader dropped this follower (_DROPPED sentinel —
        closing the stream makes the drop visible so it re-syncs)."""
        try:
            for f in first_frames:
                await write_frame(writer, f)
            while True:
                frame = await q.get()
                if frame is _DROPPED:
                    break
                await write_frame(writer, frame)
        except asyncio.CancelledError:
            raise  # orderly teardown, not a broken plane
        except (ConnectionError, OSError) as e:
            self._follower_lost(peer, e)
        finally:
            if q in self._conns:
                self._conns.remove(q)
            writer.close()

    def _follower_lost(self, peer, err) -> None:
        """Connection-loss policy: spanning mesh -> latch broken (the
        next collective would hang anyway); mirror topology -> keep
        serving, the follower re-syncs when it comes back."""
        if self.strict:
            self.mark_broken(f"follower {peer} connection lost: {err}")
        else:
            log.warning(
                "spmd follower %s lost (%s); serving continues, "
                "awaiting rejoin", peer, err,
            )

    @property
    def sync_pending(self) -> int:
        """Rejoin syncs waiting for the engine's next step boundary."""
        return self._sync_pending

    def serve_sync(self, chunks: list[tuple]) -> None:
        """Resolve every parked rejoin with a quiesced state snapshot.
        Called from the engine's step THREAD at a step boundary (pipeline
        flushed, admission waves landed) so the snapshot is exact; the
        queue registration happens on the loop BEFORE any later
        publish's _enqueue callback, so the follower sees snapshot ->
        every subsequent descriptor with no gap.

        ``chunks`` is a list of (page_ids, k, v) numpy chunks, already
        sized under SYNC_CHUNK_BYTES at extraction (a production cache
        runs to GBs, far past the wire codec's MAX_FRAME and far past
        what the leader host should materialize at once); the follower
        installs chunks as they arrive (the final carries ``last``)."""
        seq = self.publish_count
        frames: list[dict] = []
        if not chunks:
            frames.append({
                "op": "__sync__",
                "scalars": {"seq": seq, "last": True},
                "arrays": {"page_ids": _enc(np.zeros((0,), np.int32))},
            })
        else:
            for i, (ids, k, v) in enumerate(chunks):
                frames.append({
                    "op": "__sync__",
                    "scalars": {"seq": seq, "last": i == len(chunks) - 1},
                    "arrays": {
                        "page_ids": _enc(ids),
                        "k": _enc(k),
                        "v": _enc(v),
                    },
                })
        self._sync_pending = 0

        def _resolve() -> None:
            waiting, self._sync_waiting = self._sync_waiting, []
            for fut, writer in waiting:
                if fut.done():
                    continue
                if writer.is_closing():
                    # the requester died while parked (crash-looping
                    # follower): cancelling sends its handler to the
                    # close path instead of registering an orphan queue
                    # that would absorb every descriptor until the next
                    # failed write discovered the corpse
                    fut.cancel()
                    continue
                # live queue bounded at 4x the catch-up window: a
                # GB-scale snapshot takes tens of seconds to cross the
                # wire while the leader keeps publishing, so the sync
                # queue gets generous headroom — but NOT unbounded, so a
                # follower that died (or stalled) mid-snapshot hits the
                # normal overflow path (drop backlog + _DROPPED) instead
                # of pinning leader memory forever. The grace deadline
                # exempts that overflow from the strict-mode broken
                # latch: a rejoiner drowning in its own snapshot is a
                # recoverable re-sync, not a lost-lockstep event.
                q = asyncio.Queue(maxsize=4 * RING_FRAMES)
                q.sync_grace_until = (
                    time.monotonic() + SYNC_DRAIN_GRACE_S
                )
                self._conns.append(q)
                fut.set_result((frames, q))

        try:
            self.loop.call_soon_threadsafe(_resolve)
        except RuntimeError:
            pass  # loop closed during shutdown

    def publish(self, op: str, scalars: dict[str, Any] | None = None,
                arrays: dict[str, np.ndarray] | None = None) -> None:
        msg = {
            "op": op,
            "scalars": scalars or {},
            "arrays": {
                k: _enc(np.asarray(v)) for k, v in (arrays or {}).items()
            },
        }
        self.publish_count += 1
        seq = self.publish_count

        nbytes = sum(
            len(v["data"]) for v in msg["arrays"].values()
        ) + 256

        def _enqueue() -> None:
            self._loop_seq = seq
            self._ring.append((seq, msg, nbytes))
            self._ring_bytes += nbytes
            while self._ring and (
                len(self._ring) > RING_FRAMES
                or self._ring_bytes > RING_BYTES
            ):
                _s, _m, n = self._ring.popleft()
                self._ring_bytes -= n
            for q in list(self._conns):
                try:
                    q.put_nowait(msg)
                except asyncio.QueueFull:
                    self._conns.remove(q)
                    backlog = q.qsize()
                    # make the drop VISIBLE to the follower: flush the
                    # backlog and leave only the sentinel, so its stream
                    # closes at a clean frame boundary (applying frames
                    # past a gap would diverge its replay; a silently-
                    # frozen stream would never trigger the rejoin)
                    try:
                        while True:
                            q.get_nowait()
                    except asyncio.QueueEmpty:
                        pass
                    q.put_nowait(_DROPPED)
                    in_sync_grace = (
                        getattr(q, "sync_grace_until", 0.0)
                        > time.monotonic()
                    )
                    if self.strict and not in_sync_grace:
                        self.mark_broken(
                            "follower stopped draining descriptors "
                            f"({backlog} backlogged)"
                        )
                    else:
                        log.warning(
                            "spmd follower stopped draining; dropped "
                            "(it will rejoin with a state sync)"
                        )

        try:
            self.loop.call_soon_threadsafe(_enqueue)
        except RuntimeError as e:  # loop closed
            self.publish_failures += 1
            self.mark_broken(f"descriptor publish failed: {e}")

    def stop(self) -> None:
        self.publish("stop")

    async def close(self) -> None:
        if self._server is not None:
            self._server.close()
        try:
            # drop the advertised address: a follower from a later run
            # must not connect to this dead leader
            await self.hub.delete(ADDR_KEY_FMT.format(group=self.group))
        # dynalint: disable=DL003 -- best-effort address withdrawal during
        # close; the hub being already gone is the expected failure here
        except Exception:  # noqa: BLE001 - hub may already be gone
            pass


class SpmdFollower:
    """Replays the leader's step descriptors against a local engine shell.

    The engine shell must be constructed EXACTLY as the leader's (spec,
    EngineConfig, mesh, params init) — descriptor replay only drives the
    jitted entry points; any divergence in static shapes would compile a
    different program and desynchronize the collectives.
    """

    def __init__(self, hub, group: str, engine, rejoin: bool | None = None):
        self.hub = hub
        self.group = group
        self.engine = engine
        # follower-side pipeline mirror: device results of the last
        # decode bursts, for chain replay (oldest first). The leader
        # chains from at most one in-flight burst (core._decode_step); a
        # mirror shorter than the leader's chain would misalign every
        # mask, so it holds a few more
        self._pending: deque = deque(maxlen=8)
        # rejoin: on stream loss, reconnect with a state-sync join
        # instead of dying. Only valid in MIRROR topologies (local mesh
        # per process); a spanning jax.distributed mesh is not elastic.
        if rejoin is None:
            try:
                import jax

                rejoin = jax.process_count() == 1
            except Exception:  # noqa: BLE001
                # jax absent/uninitialized: mirror-topology default; log
                # the probe failure (see SpmdLeader.strict — dynalint DL003)
                log.debug("jax process_count probe failed; rejoin=True",
                          exc_info=True)
                rejoin = True
        self.rejoin = rejoin
        self.rejoins = 0  # completed state-sync rejoins (test hook)
        self._sync_pages = 0  # pages installed across the current sync
        # pre-restart tier hashes that already bought one re-sync: a
        # second miss zero-fills loudly instead of looping quiesces
        self._tier_missed: set[int] = set()

    async def _leader_addr(self, timeout: float = 60.0) -> str:
        key = ADDR_KEY_FMT.format(group=self.group)
        deadline = asyncio.get_running_loop().time() + timeout
        while True:
            addr = await self.hub.get(key)
            if addr:
                return addr
            if asyncio.get_running_loop().time() > deadline:
                raise TimeoutError(f"no spmd leader address at {key}")
            await asyncio.sleep(0.2)

    async def run(self) -> None:
        """Replay forever; in rejoin mode a lost stream (leader dropped
        us, network blip, or we restarted) reconnects with a state-sync
        join and resumes lockstep from the snapshot."""
        import os

        # a RESTARTED follower process can skip the backlog attempt and
        # go straight to the snapshot (a fresh process's from_seq=0 only
        # works while the leader's ring still reaches back to seq 1)
        sync_join = os.environ.get("DYNAMO_SPMD_SYNC_JOIN") == "1"
        while True:
            try:
                await self._run_once(sync_join)
                return  # leader sent "stop": orderly end
            except ConnectionError as e:
                if not self.rejoin:
                    raise
                log.warning(
                    "spmd stream lost (%s); rejoining with state sync", e
                )
                self._pending.clear()
                sync_join = True
                await asyncio.sleep(0.2)

    async def _run_once(self, sync_join: bool) -> None:
        # the hub key may briefly hold a PREVIOUS leader's address
        # (leader restarting): retry connect, re-reading the key
        deadline = asyncio.get_running_loop().time() + 60.0
        while True:
            addr = await self._leader_addr()
            host, port = addr.rsplit(":", 1)
            try:
                reader, writer = await asyncio.open_connection(
                    host, int(port)
                )
                break
            except OSError as e:
                if asyncio.get_running_loop().time() > deadline:
                    raise ConnectionError(
                        f"spmd leader at {addr} unreachable: {e}"
                    ) from e
                await asyncio.sleep(0.3)
        await write_frame(writer, {"from_seq": 0, "sync": sync_join})
        log.info(
            "spmd follower replaying from %s%s", addr,
            " (sync join)" if sync_join else "",
        )
        try:
            await self._replay(reader, writer)
        finally:
            writer.close()  # a replay abort must not leak the socket

    async def _replay(self, reader, writer) -> None:
        import os
        import time as _time

        import jax.numpy as jnp

        eng = self.engine
        fam = eng.fam  # family adapter: replay works for GQA AND MLA
        spec, mesh = eng.spec, eng.mesh
        trace = os.environ.get("DYNAMO_SPMD_TRACE") == "1"
        t_prev = _time.perf_counter()
        while True:
            msg = await read_frame(reader)
            t_recv = _time.perf_counter()
            if msg is None:
                raise ConnectionError(
                    "spmd descriptor stream closed by leader"
                )
            op = msg["op"]
            if trace:
                print(
                    f"SPMDTRACE wait={_time.perf_counter() - t_prev:.4f} "
                    f"op={op}", flush=True,
                )
            sc = msg["scalars"]
            ar = {k: _dec(v) for k, v in msg["arrays"].items()}
            if op == "stop":
                log.info("spmd follower: leader stopped")
                writer.close()
                return
            if op == "__reject__":
                if self.rejoin:
                    # beyond the catch-up window: fall back to a fresh
                    # state-sync join instead of dying
                    raise ConnectionError(
                        f"join rejected ({sc.get('reason')})"
                    )
                raise RuntimeError(
                    f"spmd leader rejected join: {sc.get('reason')}"
                )
            if op == "__sync__":
                # rejoin snapshot (possibly one of several chunks):
                # install the leader's quiesced KV pages. Params are
                # deterministic — same init/checkpoint — and the leader
                # flushed its pipeline, so the chain mirror starts empty.
                ids = ar["page_ids"].astype(np.int32)
                if ids.size:
                    eng.k_pages, eng.v_pages = fam.insert_pages(
                        eng.k_pages, eng.v_pages, jnp_i32(ids),
                        jnp.asarray(ar["k"]), jnp.asarray(ar["v"]),
                    )
                self._sync_pages += int(ids.size)
                if sc.get("last", True):
                    self._pending.clear()
                    self.rejoins += 1
                    log.info(
                        "spmd rejoin complete: %d pages synced at seq %s",
                        self._sync_pages, sc.get("seq"),
                    )
                    self._sync_pages = 0
                t_prev = _time.perf_counter()
                continue
            # every branch matches one leader dispatch site in
            # engine/core.py; keep in lockstep with it. All model calls
            # go through the family adapter so the compiled programs are
            # the leader's exact entry points for this architecture.
            if op == "prefill":
                mm_kwargs = {}
                if "mm_embeds" in ar:
                    mm_kwargs = {
                        "mm_embeds": jnp.asarray(
                            ar["mm_embeds"].astype(np.float32)
                        ),
                        "mm_pos": jnp_i32(ar["mm_pos"]),
                    }
                _logits, eng.k_pages, eng.v_pages, _d = fam.prefill(
                    spec, eng.params,
                    jnp_i32(ar["tokens"]), jnp_i32(ar["block_table"]),
                    jnp_scalar(sc["start"]), eng.k_pages, eng.v_pages,
                    jnp_scalar(sc["num_tokens"]), mesh=mesh, **mm_kwargs,
                )
            elif op == "ring_prefill":
                (_logits, eng.k_pages, eng.v_pages,
                 _d) = fam.prefill_ring(
                    spec, eng.params,
                    jnp_i32(ar["tokens"]), jnp_i32(ar["block_table"]),
                    eng.k_pages, eng.v_pages,
                    jnp_scalar(sc["num_tokens"]), mesh=mesh,
                )
            elif op == "prefill_batch":
                (_lg, eng.k_pages, eng.v_pages,
                 _d) = fam.prefill_batch(
                    spec, eng.params,
                    jnp_i32(ar["tokens"]), jnp_i32(ar["block_tables"]),
                    jnp_i32(ar["start"]), eng.k_pages, eng.v_pages,
                    jnp_i32(ar["num_tokens"]), mesh=mesh,
                )
            elif op == "kv_offload":
                # mirror the leader's tier offload: extract the SAME pages
                # (this process keeps its shard) and offer them to the
                # local KVBM tiers (ref KvbmWorker, distributed/worker.rs)
                ids = jnp_i32(ar["page_ids"])
                kb, vb = fam.extract_pages(eng.k_pages, eng.v_pages, ids)
                try:
                    kb.copy_to_host_async()
                    vb.copy_to_host_async()
                except AttributeError:
                    pass
                if eng.offload is not None:
                    eng.offload.submit(
                        [int(h) for h in sc["hashes"]], kb, vb
                    )
            elif op == "kv_onboard":
                hashes = [int(h) for h in sc["hashes"]]
                missing = (
                    [h for h in hashes if h not in eng.kvbm]
                    if self.rejoins and eng.kvbm is not None else []
                )
                fresh_miss = [
                    h for h in missing if h not in self._tier_missed
                ]
                if fresh_miss:
                    # this process's tier copy died with the pre-restart
                    # incarnation; a fresh state sync recovers the
                    # leader's post-onboard DEVICE pages exactly. ONE
                    # re-sync per hash: tier content itself is
                    # unrecoverable (it died with the old process), so a
                    # second miss of the same hash falls through to the
                    # loud zero-fill instead of looping quiesces forever.
                    self._tier_missed.update(fresh_miss)
                    raise ConnectionError(
                        f"kvbm tier miss after rejoin "
                        f"({len(fresh_miss)} blocks); re-syncing"
                    )
                if missing:
                    log.error(
                        "kvbm onboard of %d pre-restart blocks after "
                        "re-sync: tier data unrecoverable, shard "
                        "zero-fills (mirror fidelity degraded until the "
                        "blocks cycle out)", len(missing),
                    )
                eng.onboard_from_tiers(
                    hashes, ar["page_ids"].astype(np.int32),
                )
            elif op == "decode":
                tokens_in = jnp_i32(ar["tokens"])
                n_chain = int(sc.get("n_chain", 0))
                if n_chain:
                    # chain replay: same masks the leader used, against
                    # THIS process's pending burst results (its shards)
                    prevs = list(self._pending)[-n_chain:]
                    if len(prevs) < n_chain:
                        raise RuntimeError(
                            f"chain replay misaligned: leader chained "
                            f"{n_chain} bursts, mirror holds {len(prevs)}"
                        )
                    for i, prev in enumerate(prevs):
                        valid = jnp.asarray(
                            ar[f"chain_valid_{i}"].astype(bool)
                        )
                        tokens_in = jnp.where(
                            valid, prev[:, -1], tokens_in
                        )
                result = fam.decode_steps(
                    spec, eng.params,
                    tokens_in, jnp_i32(ar["block_tables"]),
                    jnp_i32(ar["seq_lens"]), eng.k_pages, eng.v_pages,
                    jnp.asarray(ar["active"].astype(bool)),
                    jnp.asarray(ar["temps"]), jnp_i32(ar["topk"]),
                    jnp.asarray(ar["topp"]),
                    jnp.asarray(ar["seeds"].astype(np.uint32)),
                    jnp_i32(ar["steps"]),
                    n_steps=int(sc["n_steps"]), n_logprobs=int(sc["n_lp"]),
                    mesh=mesh,
                )
                eng.k_pages, eng.v_pages = result[-2], result[-1]
                self._pending.append(result[0])  # sampled [B, n]
            else:  # pragma: no cover - protocol drift guard
                raise RuntimeError(f"unknown spmd op {op!r}")
            if trace:
                # n_steps lets tests assert descriptor amortization (one
                # frame covering N decode steps) without timing anything
                extra = (
                    f" n_steps={int(sc['n_steps'])}" if op == "decode" else ""
                )
                print(
                    f"SPMDTRACE apply={_time.perf_counter() - t_recv:.4f} "
                    f"op={op}{extra}", flush=True,
                )
            t_prev = _time.perf_counter()


def jnp_i32(a: np.ndarray):
    import jax.numpy as jnp

    return jnp.asarray(a.astype(np.int32))


def jnp_scalar(v):
    import jax.numpy as jnp

    return jnp.asarray(int(v), jnp.int32)
