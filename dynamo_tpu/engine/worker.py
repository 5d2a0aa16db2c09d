"""JAX engine worker process: ``python -m dynamo_tpu.engine.worker``.

The TPU-native counterpart of the reference's engine workers
(components/src/dynamo/vllm/main.py:69 ``worker``): build the engine (model
+ mesh + paged cache), register the model card, serve ``generate``, publish
KV events + metrics. ``--mode prefill|decode|aggregated`` selects the
disaggregation role (ref: init/init_prefill, vllm/main.py:175-280):

  aggregated — one engine does prefill + decode (default)
  prefill    — serves 1-token prefills, exports KV via the transfer plane;
               registers on the prefill component (no model card: the
               frontend only discovers decode workers)
  decode     — fronted by DecodeWorkerHandler; conditionally delegates long
               prompts to the prefill pool and resumes from transferred KV
"""

from __future__ import annotations

import argparse
import asyncio
import logging

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.frontend.model_card import register_llm
from dynamo_tpu.kv_router.publisher import KvEventPublisher, WorkerMetricsPublisher
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.hub_client import connect_hub
from dynamo_tpu.runtime.logging_util import setup_logging

log = logging.getLogger("dynamo.engine.worker")

PREFILL_COMPONENT = "prefill"


async def launch_engine_worker(
    drt: DistributedRuntime,
    *,
    namespace: str = "dynamo",
    component: str = "backend",
    endpoint: str = "generate",
    model: str = "tiny-test",
    model_path: str | None = None,
    model_name: str | None = None,
    model_type: str = "chat",
    tokenizer: str = "mock",
    engine_config: EngineConfig | None = None,
    spec: ModelSpec | None = None,
    router_mode: str = "kv",
    tool_call_parser: str | None = None,
    reasoning_parser: str | None = None,
    mode: str = "aggregated",
    mm_tokens_per_image: int = 0,
    image_token_id: int = 0,
    mm_video_frames: int = 8,
    prefill_component: str = PREFILL_COMPONENT,
    prefill_router_mode: str = "kv",
    max_local_prefill_length: int = 128,
    always_remote_prefill: bool = False,
    kvbm_config=None,
    health=None,  # HealthCheckManager: canary-probe this worker's endpoint
    spmd=None,  # SpmdLeader: multi-host dispatch broadcast (leader only)
    precompile: bool = False,  # compile every serving shape before serve
) -> tuple[InferenceEngine, object]:
    """Build + register one engine worker in this process.

    The serving front door (engine or disagg handler) is attached as
    ``engine.frontdoor``.
    """
    cfg = engine_config or EngineConfig()
    mesh = None
    if cfg.tp > 1 or cfg.dp > 1 or cfg.sp > 1 or cfg.ep > 1:
        from dynamo_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(tp=cfg.tp, dp=cfg.dp, sp=cfg.sp, ep=cfg.ep)

    params = None
    if model_path:
        # real checkpoint: spec comes from config.json, params stream from
        # safetensors straight onto the mesh (ref local_model.rs:323 build)
        if spec is not None:
            raise ValueError(
                "pass either spec= or model_path=, not both: with a "
                "checkpoint the spec must come from its config.json"
            )
        from dynamo_tpu.models.loader import load_model_dir

        spec, params = load_model_dir(model_path, mesh=mesh)
        if tokenizer == "mock" and _has_tokenizer_files(model_path):
            tokenizer = model_path
    else:
        spec = spec or ModelSpec.preset(model)

    transfer_source = None
    if mode == "prefill":
        from dynamo_tpu.disagg.transfer import KvTransferSource

        transfer_source = await KvTransferSource().start()

    kvbm = None
    if kvbm_config is not None:
        import asyncio as _aio

        from dynamo_tpu.kvbm import KvBlockManager

        import jax as _jax

        kvbm_ns = namespace
        if _jax.process_count() > 1:
            kvbm_ns = f"{namespace}.s{_jax.process_index()}"
        kvbm = KvBlockManager(
            kvbm_config, hub=drt.hub, loop=_aio.get_running_loop(),
            namespace=kvbm_ns,
        )

    guided_vocab = None
    if cfg.guided_mode != "off" and spmd is None:
        # guided decoding needs the token -> surface-string table; build
        # it once from the SAME tokenizer the frontend registers for
        # this model, so the mask automaton and the detokenizer agree
        try:
            from dynamo_tpu.frontend.tokenizer import load_tokenizer
            from dynamo_tpu.guided import TokenVocab

            guided_vocab = TokenVocab.from_tokenizer(
                load_tokenizer(tokenizer), spec.vocab_size
            )
        except Exception as e:  # noqa: BLE001
            log.warning(
                "guided decoding disabled: vocab build failed (%s)", e
            )

    engine = InferenceEngine(
        spec, cfg, mesh=mesh, params=params,
        transfer_source=transfer_source, kvbm=kvbm, spmd=spmd,
        guided_vocab=guided_vocab,
    )

    if precompile:
        # shape warmup BEFORE registration: no request ever eats a
        # compile, and per-shape compile time lands in the startup log
        # (engine.precompile logs each shape; a restarted worker mostly
        # replays the persistent compile cache here).
        # Off the event loop: a cold compile pass can take minutes on
        # TPU and must not starve the hub keepalives sharing this loop.
        import asyncio as _aio

        await _aio.to_thread(engine.precompile)

    if mode == "prefill":
        from dynamo_tpu.disagg.handlers import PrefillWorkerHandler

        handler = PrefillWorkerHandler(engine)
        ep = drt.namespace(namespace).component(prefill_component).endpoint(endpoint)
        served = await ep.serve(
            handler.generate,
            metadata={"model": model_name or spec.name, "role": "prefill"},
        )
        comp_path = f"{namespace}/{prefill_component}"
    else:
        if mode == "decode":
            from dynamo_tpu.disagg.handlers import DecodeWorkerHandler
            from dynamo_tpu.disagg.policy import DisaggPolicy

            prefill_router = await _build_prefill_router(
                drt, namespace, prefill_component, endpoint,
                prefill_router_mode, cfg.page_size,
            )
            policy = DisaggPolicy(
                max_local_prefill_length=max_local_prefill_length,
                always_remote=always_remote_prefill,
            )
            await policy.watch(drt.hub, namespace)
            handler = DecodeWorkerHandler(
                engine, prefill_router=prefill_router, policy=policy
            )
        else:
            handler = engine
        ep = drt.namespace(namespace).component(component).endpoint(endpoint)
        served, _card = await register_llm(
            drt, ep, handler.generate,
            model_name=model_name or spec.name,
            model_type=model_type,
            tokenizer=tokenizer,
            context_length=cfg.max_context,
            kv_block_size=cfg.page_size,
            router_mode=router_mode,
            tool_call_parser=tool_call_parser,
            reasoning_parser=reasoning_parser,
            mm_tokens_per_image=mm_tokens_per_image,
            image_token_id=image_token_id,
            mm_video_frames=(mm_video_frames if mm_tokens_per_image else 0),
            runtime_config={"engine": "jax", "tp": cfg.tp, "mode": mode},
            metadata={"engine": "jax", "role": mode},
        )
        comp_path = f"{namespace}/{component}"

    # admin endpoint: control-plane ops (ref block_manager controller.rs /
    # the HTTP clear_kv_blocks route); endpoint-scoped instance keys keep
    # it invisible to generate-routing clients
    async def admin_handler(request, context):
        if request.get("op") == "clear_kv_blocks":
            engine.request_clear_cache()
            yield {"ok": True}
        elif request.get("op") == "faults":
            # flip the process-wide fault registry live (runtime/faults.py):
            # {"op": "faults", "spec": "...", "seed": N} reconfigures;
            # {"op": "faults"} reports active rules + trip counters
            from dynamo_tpu.runtime.faults import FAULTS

            if "spec" in request:
                try:
                    FAULTS.configure(
                        request.get("spec") or "", request.get("seed")
                    )
                except ValueError as e:
                    yield {"ok": False, "error": str(e)}
                    return
            yield {"ok": True, **FAULTS.snapshot()}
        elif request.get("op") == "drain":
            # operator-triggered drain: same withdraw-and-stop-admitting
            # sequence as SIGTERM, but the process stays up — exiting is
            # the operator's call
            await _withdraw_and_begin_drain(drt, engine, served)
            yield {"ok": True, "inflight": engine.inflight()}
        elif request.get("op") == "timeline":
            # flight recorder (runtime/flight.py): one request's full
            # event timeline by id, or the summary view (active + recent
            # + retained errors/slowest) — the live "why was THIS
            # request slow" query, also fanned out by the frontend's
            # GET /debug/timeline route
            from dynamo_tpu.runtime.flight import FLIGHT

            try:
                n = int(request.get("n") or 16)
            except (TypeError, ValueError):
                n = 16
            yield {
                "ok": True,
                **FLIGHT.snapshot(request.get("request_id"), n=n),
            }
        elif request.get("op") == "cache_status":
            yield {
                "ok": True,
                "active_pages": engine.allocator.active_pages,
                "cached_pages": engine.allocator.evictable_pages,
                "free_pages": engine.allocator.free_pages,
                "kvbm": (
                    engine.kvbm.stats.to_dict()
                    if engine.kvbm is not None else None
                ),
            }
        else:
            yield {"ok": False, "error": f"unknown op {request.get('op')!r}"}

    admin_component = prefill_component if mode == "prefill" else component
    admin_ep = drt.namespace(namespace).component(admin_component).endpoint("admin")
    await admin_ep.serve(admin_handler, metadata={"role": "admin"})

    engine.frontdoor = handler
    wid = served.instance.instance_id
    engine.events = KvEventPublisher(drt.hub, comp_path, wid).start()
    engine.metrics = WorkerMetricsPublisher(drt.hub, comp_path, wid).start()
    # worker telemetry registry (engine/telemetry.py): periodic sampler
    # feeding step/burst histograms + pool/queue gauges onto every
    # /metrics surface — closed via engine.close()
    from dynamo_tpu.engine.telemetry import EngineCollector

    engine.telemetry = EngineCollector(engine).start()
    await engine.start()
    if health is not None:
        health.register(served)
        from dynamo_tpu.runtime.health import EngineMonitor

        engine.monitor = EngineMonitor(drt, engine)
    engine._publish_metrics()
    log.info(
        "engine worker %x up: mode=%s model=%s pages=%d slots=%d tp=%d",
        wid, mode, spec.name, cfg.num_pages, cfg.max_decode_slots, cfg.tp,
    )
    return engine, served


async def _build_prefill_router(
    drt: DistributedRuntime,
    namespace: str,
    prefill_component: str,
    endpoint: str,
    router_mode: str,
    page_size: int,
):
    """Router over the prefill pool: KV-aware by default (a long prompt with
    a warm prefix should land on the prefill worker that has it cached)."""
    from dynamo_tpu.runtime.push import PushRouter, RouterMode

    ep = drt.namespace(namespace).component(prefill_component).endpoint(endpoint)
    if router_mode == "kv":
        from dynamo_tpu.kv_router.protocols import RouterConfig
        from dynamo_tpu.kv_router.router import KvPushRouter, KvRouter

        push = await PushRouter.from_endpoint(ep, RouterMode.DIRECT)
        # block_size must match the engines' KV-event page granularity or
        # radix overlap silently never matches
        kv = await KvRouter(
            drt.hub, f"{namespace}/{prefill_component}",
            RouterConfig(block_size=page_size),
        ).start()
        return KvPushRouter(push, kv)
    mode = RouterMode.RANDOM if router_mode == "random" else RouterMode.ROUND_ROBIN
    return await PushRouter.from_endpoint(ep, mode)


def _has_tokenizer_files(model_path: str) -> bool:
    import os

    return any(
        os.path.exists(os.path.join(model_path, f))
        for f in ("tokenizer.json", "tokenizer_config.json", "tokenizer.model")
    )


def _build_engine_shell(args: argparse.Namespace, ecfg: EngineConfig, hub=None):
    """Follower-side engine: identical spec/config/mesh/params to the
    leader's (deterministic init), but its step loop never starts — the
    SPMD replay drives the jitted entry points directly. With KVBM
    enabled the follower holds its OWN tier pools: the replayed
    kv_offload/kv_onboard ops move this process's shard of every block
    (ref KvbmWorker, block_manager/distributed/worker.rs)."""
    import asyncio as _aio

    mesh = None
    if ecfg.tp > 1 or ecfg.dp > 1 or ecfg.sp > 1 or ecfg.ep > 1:
        from dynamo_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(tp=ecfg.tp, dp=ecfg.dp, sp=ecfg.sp, ep=ecfg.ep)
    params = None
    if args.model_path:
        from dynamo_tpu.models.loader import load_model_dir

        spec, params = load_model_dir(args.model_path, mesh=mesh)
    else:
        spec = ModelSpec.preset(args.model)
    kvbm = None
    kvbm_cfg = _kvbm_config_from_args(args)
    if kvbm_cfg is not None:
        import jax as _jax

        from dynamo_tpu.kvbm import KvBlockManager

        kvbm = KvBlockManager(
            kvbm_cfg, hub=hub, loop=_aio.get_event_loop() if hub else None,
            # per-shard G4 namespace: each process's remote blocks are its
            # own shard, keyed apart
            namespace=f"{args.namespace}.s{_jax.process_index()}",
        )
    return InferenceEngine(spec, ecfg, mesh=mesh, params=params, kvbm=kvbm)


def _kvbm_config_from_args(args: argparse.Namespace):
    if args.kvbm_host_mb <= 0:
        return None
    from dynamo_tpu.kvbm import KvbmConfig

    return KvbmConfig(
        host_bytes=args.kvbm_host_mb * 1024 * 1024,
        disk_bytes=args.kvbm_disk_mb * 1024 * 1024,
        disk_dir=args.kvbm_disk_dir,
        remote_max_blocks=args.kvbm_remote_blocks,
    )


async def _amain(args: argparse.Namespace) -> None:
    from dynamo_tpu.parallel.multihost import initialize_multihost, is_leader

    # speculative decoding: the CLI flag wins, then the DYN_SPEC_* env /
    # config layer, then the EngineConfig defaults. Multi-host workers
    # force it off in the engine (verify is not in the follower replay
    # protocol), so the flag is safe to leave set in shared recipe env.
    env_cfg = RuntimeConfig.from_env()
    spec_mode = args.spec if args.spec is not None else (
        env_cfg.spec_mode or "off"
    )
    spec_k_max = args.spec_k_max or env_cfg.spec_k_max or 8
    # guided decoding: CLI flag > DYN_GUIDED_MODE > default auto
    guided_mode = args.guided if args.guided is not None else (
        env_cfg.guided_mode or "auto"
    )

    ecfg = EngineConfig(
        page_size=args.page_size,
        num_pages=args.num_pages,
        max_pages_per_seq=args.max_pages_per_seq,
        max_decode_slots=args.max_decode_slots,
        decode_steps_per_dispatch=args.decode_steps_per_dispatch,
        # serving workers ALWAYS pipeline (even at burst 1 = pure
        # double-buffering): burst N+1 dispatches with device-chained
        # tokens before burst N is read, so the device always has the
        # next burst queued while the step thread streams N's tokens
        # and admits. Cost: stops detected one burst late (overshoot
        # discarded); cancels/admin ops still flush first.
        pipeline_decode=True,
        max_prefill_chunk_tokens=args.max_prefill_chunk_tokens,
        tp=args.tp,
        sp=args.sp,
        ep=args.ep,
        spec_mode=spec_mode,
        spec_k_max=spec_k_max,
        spec_ngram_min=args.spec_ngram_min,
        spec_ngram_max=args.spec_ngram_max,
        guided_mode=guided_mode,
        # overload plane: CLI flag > DYN_TENANT_QUOTAS / YAML layer >
        # unmetered; admission bound + preemption ride along
        tenants=(
            args.tenant_quotas if args.tenant_quotas is not None
            else (env_cfg.tenant_quotas or "")
        ),
        max_waiting=args.max_waiting,
        preemption=args.preemption,
    )
    spmd_leader = None
    if args.mirror == "follower":
        # MIRROR follower: its own local mesh/devices, replaying the
        # leader's descriptor stream. Unlike the spanning-mesh follower
        # below, this one survives restarts: on stream loss it rejoins
        # with a state sync (parallel/spmd.py rejoin protocol).
        from dynamo_tpu.parallel.spmd import SpmdFollower

        rcfg = RuntimeConfig.from_env()
        if args.hub:
            rcfg.override_hub(args.hub)
        hub = await connect_hub(rcfg.hub_target())
        engine = _build_engine_shell(args, ecfg, hub=hub)
        group = f"{args.namespace}/{args.component}/{args.endpoint}"
        print("MIRROR_FOLLOWER_READY", flush=True)
        await SpmdFollower(hub, group, engine, rejoin=True).run()
        return
    multihost = initialize_multihost(
        args.coordinator_address, args.num_processes, args.process_id
    )
    if multihost:
        if args.mode != "aggregated":
            raise SystemExit(
                "multi-host workers support aggregated mode (disagg "
                "export is not in the follower replay protocol yet)"
            )
        if ecfg.tp * ecfg.dp * ecfg.sp * ecfg.ep <= 1:
            raise SystemExit(
                "multi-host workers need mesh axes spanning the slice "
                "(e.g. --tp 2); a 1-device mesh would leave the follower "
                "hosts idle"
            )
        group = f"{args.namespace}/{args.component}/{args.endpoint}"
        if not is_leader():
            # Follower: one logical worker = many hosts with a single
            # leader identity (SURVEY §7 hard part (d)). The follower
            # holds identical device state and REPLAYS the leader's
            # dispatch stream so the SPMD collectives line up — it never
            # registers, serves, or samples (parallel/spmd.py).
            from dynamo_tpu.parallel.spmd import SpmdFollower

            rcfg = RuntimeConfig.from_env()
            if args.hub:
                rcfg.override_hub(args.hub)
            hub = await connect_hub(rcfg.hub_target())
            engine = _build_engine_shell(args, ecfg, hub=hub)
            print("MULTIHOST_FOLLOWER_READY", flush=True)
            await SpmdFollower(hub, group, engine).run()
            return
    rcfg = RuntimeConfig.from_env()
    if args.hub:
        rcfg.override_hub(args.hub)
    drt = DistributedRuntime(await connect_hub(rcfg.hub_target()), rcfg)
    if multihost or args.mirror == "leader":
        import asyncio as _aio

        from dynamo_tpu.parallel.spmd import SpmdLeader

        group = f"{args.namespace}/{args.component}/{args.endpoint}"
        spmd_leader = await SpmdLeader(
            drt.hub, _aio.get_running_loop(), group,
            host=drt.config.host,
            # mirror topology: follower loss is recoverable (rejoin),
            # spanning mesh: strict fail-loud (auto-detected)
            strict=None if multihost else False,
        ).start()
    health = None
    status_server = None
    if args.health_port >= 0:
        from dynamo_tpu.runtime.health import (
            HealthCheckConfig,
            HealthCheckManager,
            SystemStatusServer,
        )

        health = HealthCheckManager(
            drt,
            HealthCheckConfig(
                interval_s=args.health_interval,
                timeout_s=args.health_timeout,
            ),
        )
        # a registry on the status server turns its /metrics on; the
        # exposition also renders every registered global provider —
        # the engine telemetry registry first among them — so operators
        # scrape worker step/pool/queue metrics here (ref
        # system_status_server.rs + metrics.rs)
        from dynamo_tpu.runtime.metrics import MetricsRegistry

        status_server = await SystemStatusServer(
            health=health, metrics=MetricsRegistry(),
            port=args.health_port,
        ).start()
        print(f"SYSTEM_STATUS_PORT={status_server.port}", flush=True)

    engine, served = await launch_engine_worker(
        drt,
        health=health,
        namespace=args.namespace,
        component=args.component,
        endpoint=args.endpoint,
        model=args.model,
        model_path=args.model_path,
        model_name=args.model_name,
        model_type=args.model_type,
        tokenizer=args.tokenizer,
        engine_config=ecfg,
        router_mode=args.router_mode,
        tool_call_parser=args.tool_call_parser,
        reasoning_parser=args.reasoning_parser,
        mode=args.mode,
        mm_tokens_per_image=args.mm_tokens_per_image,
        image_token_id=args.image_token_id,
        mm_video_frames=args.mm_video_frames,
        prefill_component=args.prefill_component,
        prefill_router_mode=args.prefill_router_mode,
        max_local_prefill_length=args.max_local_prefill_length,
        always_remote_prefill=args.always_remote_prefill,
        kvbm_config=_kvbm_config_from_args(args),
        spmd=spmd_leader,
        precompile=args.precompile,
    )
    print("ENGINE_READY", flush=True)
    _install_drain_handler(drt, engine, served)
    try:
        await drt.runtime.wait_for_shutdown()
    finally:
        if spmd_leader is not None:
            # signal followers + withdraw the advertised address so a
            # later follower run cannot connect to this dead leader
            spmd_leader.stop()
            await spmd_leader.close()


def _install_drain_handler(drt, engine, served) -> None:
    """SIGTERM => graceful drain (k8s preStop / pod deletion path)."""
    import signal as _signal

    state: dict = {"task": None}

    def on_sigterm() -> None:
        if state["task"] is not None:
            return  # second SIGTERM while draining: let the first finish
        # keep a strong reference: the loop only holds tasks weakly, and a
        # GC'd drain task means kubelet SIGKILLs us at the grace period
        state["task"] = asyncio.get_running_loop().create_task(
            graceful_drain(drt, engine, served)
        )

    try:
        asyncio.get_running_loop().add_signal_handler(
            _signal.SIGTERM, on_sigterm
        )
    except (NotImplementedError, RuntimeError):  # pragma: no cover
        pass  # non-unix event loop


async def _withdraw_and_begin_drain(
    drt, engine, served, deadline_s: float | None = None
) -> None:
    """Steps 1-2 of the drain contract, shared by the SIGTERM path and the
    admin ``drain`` RPC: WITHDRAW the instance key from the hub (lease kept
    alive, so routers stop picking this worker within one watch event),
    then STOP ADMITTING (new generates refuse with ServiceUnavailable,
    whose Retry-After is the remaining drain window when known)."""
    try:
        await drt.hub.delete(served.instance.path)
    except (ConnectionError, RuntimeError) as e:
        log.warning("drain: instance withdrawal failed (%s)", e)
    engine.begin_drain(
        drt.config.drain_timeout_s if deadline_s is None else deadline_s
    )


async def graceful_drain(
    drt, engine, served, timeout_s: float | None = None
) -> None:
    """Hardened worker drain (ROADMAP #7 / k8s preStop contract):

    1. WITHDRAW this worker's instance key from the hub (lease kept
       alive) so routers stop picking it within one watch event — the
       same mechanism health.py uses for unhealthy endpoints;
    2. STOP ADMITTING: new generates refuse with ServiceUnavailable
       (retryable -> migration re-drives on a live worker, or the
       frontend answers 503 + Retry-After);
    3. FINISH IN-FLIGHT work under the drain deadline;
    4. EXIT: runtime shutdown force-cancels whatever outlived the
       deadline (transport.stop logs the abandoned count).
    """
    timeout_s = (
        drt.config.drain_timeout_s if timeout_s is None else timeout_s
    )
    log.warning(
        "SIGTERM: graceful drain (%d in flight, timeout %.0fs)",
        engine.inflight(), timeout_s,
    )
    await _withdraw_and_begin_drain(drt, engine, served, timeout_s)
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout_s
    server = drt._server
    while loop.time() < deadline:
        if engine.inflight() == 0 and (
            server is None or server.num_inflight == 0
        ):
            break
        await asyncio.sleep(0.1)
    leftover = engine.inflight()
    if leftover:
        log.warning("drain deadline: %d request(s) still in flight", leftover)
    # past the deadline, the transport stop force-cancels immediately —
    # and COUNTS/logs the abandoned streams (aborted_inflight)
    await drt.shutdown(
        drain=True, drain_timeout=5.0 if leftover == 0 else 0.0
    )
    await engine.close()
    print(f"ENGINE_DRAINED leftover={leftover}", flush=True)


def main() -> None:
    p = argparse.ArgumentParser(description="dynamo-tpu JAX engine worker")
    p.add_argument("--hub", default=None)
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--component", default="backend")
    p.add_argument("--endpoint", default="generate")
    p.add_argument("--model", default="tiny-test", help="model preset name")
    p.add_argument("--model-path", default=None,
                   help="local checkpoint dir (config.json + *.safetensors); "
                        "overrides --model")
    p.add_argument("--model-name", default=None, help="served model name")
    p.add_argument("--model-type", default="chat",
                   choices=["chat", "completions", "embeddings"])
    p.add_argument("--tokenizer", default="mock")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=2048)
    p.add_argument("--max-pages-per-seq", type=int, default=64)
    p.add_argument("--max-decode-slots", type=int, default=8)
    p.add_argument("--decode-steps-per-dispatch", type=int, default=1,
                   help="decode steps fused per dispatch; bursts are "
                        "pipelined, one queued behind the running one")
    p.add_argument("--max-prefill-chunk-tokens", type=int, default=512,
                   help="chunked-prefill dispatch cap; multimodal prompts "
                        "must fit ONE dispatch (a 576-row CLIP-L image "
                        "span needs >= 1024)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel ring-attention prefill width")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel width (MoE models)")
    p.add_argument("--router-mode", default="kv",
                   choices=["kv", "round_robin", "random"])
    p.add_argument("--tool-call-parser", default=None,
                   help="tool-call parser name (hermes, llama3_json, "
                        "mistral, pythonic, ...)")
    p.add_argument("--reasoning-parser", default=None,
                   help="reasoning parser name (basic, deepseek_r1, granite)")
    p.add_argument("--mm-tokens-per-image", type=int, default=0,
                   help="placeholder tokens per image (0 = text-only); "
                        "requires an encode worker on the namespace")
    p.add_argument("--image-token-id", type=int, default=0)
    p.add_argument("--mm-video-frames", type=int, default=8,
                   help="frames sampled per video attachment (matches the "
                        "encode worker's --video-frames)")
    p.add_argument("--mode", default="aggregated",
                   choices=["aggregated", "prefill", "decode"])
    p.add_argument("--prefill-component", default=PREFILL_COMPONENT)
    p.add_argument("--prefill-router-mode", default="kv",
                   choices=["kv", "round_robin", "random"])
    p.add_argument("--max-local-prefill-length", type=int, default=128)
    p.add_argument("--always-remote-prefill", action="store_true")
    p.add_argument("--kvbm-host-mb", type=int, default=0,
                   help="host-DRAM KV tier budget in MiB (0 = KVBM off)")
    p.add_argument("--kvbm-disk-mb", type=int, default=0,
                   help="disk KV tier budget in MiB (0 = no disk tier)")
    p.add_argument("--kvbm-disk-dir", default=None)
    p.add_argument("--kvbm-remote-blocks", type=int, default=0,
                   help="G4 remote-tier block cap in the hub object store "
                        "(0 = off); shared across workers")
    p.add_argument("--spec", default=None, choices=["off", "ngram"],
                   help="speculative decoding: 'ngram' enables the "
                        "prompt-lookup drafter + batched verify "
                        "(bit-identical greedy output, >=1.5x per-stream "
                        "tok/s on repetitive/agentic prompts; k adapts "
                        "per slot). Default from DYN_SPEC_MODE, else off")
    p.add_argument("--spec-k-max", type=int, default=0,
                   help="max draft tokens per verify dispatch (0 = "
                        "DYN_SPEC_K_MAX, else 8)")
    p.add_argument("--spec-ngram-min", type=int, default=1,
                   help="shortest suffix n-gram the drafter matches")
    p.add_argument("--spec-ngram-max", type=int, default=4,
                   help="longest suffix n-gram (tried first)")
    p.add_argument("--tenant-quotas", default=None,
                   help="per-tenant fairness/quota spec "
                        "('tenantA:weight=4,rate=1000,burst=2000;"
                        "*:rate=200'); weight = fair share under "
                        "contention, rate = token-bucket refill/s "
                        "(over-quota requests get a typed 429 + "
                        "Retry-After), '*' = default tenant. Default "
                        "from DYN_TENANT_QUOTAS, else unmetered")
    p.add_argument("--max-waiting", type=int, default=0,
                   help="admission queue bound: beyond this the engine "
                        "sheds lowest-priority waiting work or answers "
                        "503 + live Retry-After (0 = unbounded)")
    p.add_argument("--preemption", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="pause batch streams (over-quota tenants "
                        "first; KV offloaded to the host tier, "
                        "transparently resumed) when an interactive "
                        "request cannot admit")
    p.add_argument("--guided", default=None, choices=["auto", "off"],
                   help="guided decoding: 'auto' (default) serves "
                        "response_format / forced tool_choice with "
                        "on-device grammar masks (schema-conformant "
                        "output guaranteed at any temperature); 'off' "
                        "rejects guided requests. Default from "
                        "DYN_GUIDED_MODE, else auto")
    p.add_argument("--precompile", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="compile every serving shape (prefill buckets x "
                        "pack widths, decode bursts, sample widths) before "
                        "registering, logging per-shape compile time — no "
                        "request ever eats a compile. Default ON in the "
                        "serving recipes; restarts replay the persistent "
                        "compile cache (engine/compile_cache.py)")
    p.add_argument("--health-port", type=int, default=-1,
                   help="system status server port (0 = ephemeral, "
                        "-1 = health subsystem off)")
    p.add_argument("--health-interval", type=float, default=5.0,
                   help="canary probe interval (s)")
    p.add_argument("--health-timeout", type=float, default=5.0,
                   help="canary probe timeout (s)")
    p.add_argument("--mirror", default=None, choices=["leader", "follower"],
                   help="descriptor-mirror topology WITHOUT a spanning "
                        "jax.distributed mesh: each process runs its own "
                        "local mesh and followers replay + state-sync "
                        "rejoin after restarts")
    p.add_argument("--coordinator-address", default=None,
                   help="multi-host jax.distributed coordinator "
                        "(or DYN_COORDINATOR); all hosts of one worker "
                        "slice run this process")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    args = p.parse_args()
    if (args.kvbm_disk_mb > 0 or args.kvbm_disk_dir) and args.kvbm_host_mb <= 0:
        p.error("--kvbm-disk-* requires --kvbm-host-mb > 0 (KVBM is off)")
    if args.kvbm_disk_mb > 0 and not args.kvbm_disk_dir:
        p.error("--kvbm-disk-mb requires --kvbm-disk-dir")
    if args.kvbm_disk_dir and args.kvbm_disk_mb <= 0:
        p.error("--kvbm-disk-dir requires --kvbm-disk-mb > 0")
    setup_logging()
    from dynamo_tpu.runtime.eventloop import maybe_install_uvloop

    maybe_install_uvloop()
    try:
        asyncio.run(_amain(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
