"""The system under test, built through the program's own entry points.

What ``python -m dynamo_tpu.cli run --in http --out engine`` wires, in this
one process (a chip belongs to one process at a time): in-memory hub ->
``launch_engine_worker(precompile=True)`` -> ``InferenceEngine`` -> OpenAI
HTTP frontend. From the program the benchmark takes this stack and its
counters (compile events, the fallback registry, ``profile_snapshot``,
allocator and queue sizes); everything else is the benchmark's own.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import time

import jax

from dynamo_tpu.engine.compile_cache import compile_snapshot  # noqa: F401
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.engine.worker import launch_engine_worker
from dynamo_tpu.frontend.http import HttpFrontend
from dynamo_tpu.frontend.watcher import ModelManager, ModelWatcher
from dynamo_tpu.ops.fallback import REGISTRY as FALLBACK_REGISTRY
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.hub import InMemoryHub

# public config.json key -> ModelSpec field, for the dense llama-family
# models; a configuration whose family needs more gives the rest under
# its own "model_spec" key
_HF_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def model_spec(config: dict) -> ModelSpec:
    kw = {ours: config[hf] for hf, ours in _HF_KEYS.items() if hf in config}
    kw.setdefault("head_dim", config["hidden_size"] // config["num_attention_heads"])
    kw["dtype"] = config.get("torch_dtype", "bfloat16")
    kw["name"] = config["name"]
    kw.update(config.get("model_spec", {}))
    return ModelSpec(**kw)


def engine_config(config: dict, seed: int, *, profile: bool) -> EngineConfig:
    """The deployment's engine settings, from the configuration's file.
    The weights come from ``seed`` (the engine draws them from
    ``PRNGKey(config.seed)``)."""
    kw = dict(config["engine"])
    if "prefill_buckets" in kw:
        kw["prefill_buckets"] = tuple(kw["prefill_buckets"])
    return EngineConfig(seed=engine_seed(seed), profile=profile, **kw)


def engine_seed(seed: int) -> int:
    """``--seed`` may pass 2**31; the engine also uses its seed as a
    32-bit sampling counter."""
    return int(seed) % (2**31 - 1)


@dataclasses.dataclass
class Stack:
    drt: object
    engine: InferenceEngine
    watcher: object
    frontend: object
    base: str


async def start_stack(spec: ModelSpec, cfg: EngineConfig) -> Stack:
    drt = DistributedRuntime(InMemoryHub())
    engine, _ = await launch_engine_worker(
        drt, spec=spec, engine_config=cfg, precompile=True
    )
    manager = ModelManager()
    watcher = await ModelWatcher(drt, manager).start()
    await watcher.wait_for_model(spec.name, timeout=30)
    frontend = HttpFrontend(manager, host="127.0.0.1", port=0, drt=drt)
    host, port = await frontend.start()
    return Stack(drt, engine, watcher, frontend, f"http://{host}:{port}")


async def stop_stack(stack: Stack) -> None:
    """Frontend, watcher, engine thread and hub down; the engine object
    keeps its weights and pools for the output check."""
    await stack.frontend.stop()
    await stack.watcher.close()
    await stack.engine.close()
    await stack.drt.close()


def fallback_series() -> dict:
    """``dynamo_fused_fallback_total`` by reason, as /metrics renders it."""
    out = {}
    for line in FALLBACK_REGISTRY.exposition().decode().splitlines():
        if line.startswith("dynamo_fused_fallback_total{"):
            series, value = line.rsplit(" ", 1)
            out[series] = float(value)
    return out


def tap_first_deltas() -> list:
    """Record, for every stream the engine serves from now on, the instant
    of its first delta that carries a token, with the prompt's length: the
    engine's side of time to first token, on the clock the client uses.
    Must run before the worker registers its endpoint, which binds
    ``engine.generate``; the process is one run, so nothing is restored."""
    real = InferenceEngine.generate
    firsts: list = []

    async def generate(self, request, context):
        seen = False
        async for item in real(self, request, context):
            if not seen and item.get("token_ids"):
                seen = True
                firsts.append((len(request["token_ids"]), time.monotonic()))
            yield item

    InferenceEngine.generate = generate
    return firsts


def tap_prefills(engine) -> list:
    """Record every prefill dispatch the engine makes from now on: the
    instant, and the real token counts as the device array the engine
    passed (read after the window: reading it here would wait for the
    device). The process is one run, so nothing is restored."""
    fam = engine.fam
    single, packed = fam.prefill, fam.prefill_batch
    taps: list = []

    def prefill(spec, params, tokens, bt, start, k, v, n, **kw):
        taps.append((time.monotonic(), n))
        return single(spec, params, tokens, bt, start, k, v, n, **kw)

    def prefill_batch(spec, params, tokens, bts, starts, k, v, ns, **kw):
        taps.append((time.monotonic(), ns))
        return packed(spec, params, tokens, bts, starts, k, v, ns, **kw)

    fam.prefill, fam.prefill_batch = prefill, prefill_batch
    return taps


class Sampler:
    """Reads the engine's counts a few times a second from the event loop:
    requests waiting, pages in use, slots live and the tokens of context
    they hold. Counts, not times."""

    def __init__(self, engine: InferenceEngine, interval_s: float = 0.05):
        self.engine = engine
        self.interval_s = interval_s
        self.rows: list[tuple] = []
        self._task: asyncio.Task | None = None

    def read(self) -> tuple:
        eng = self.engine
        slots = [s for s in list(eng._slots) if s is not None]
        page = eng.config.page_size
        return (
            time.monotonic(), eng._waiting.qsize(),
            eng.allocator.active_pages, len(slots),
            sum(-(-s.seq_len // page) for s in slots),
        )

    async def _loop(self) -> None:
        while True:
            self.rows.append(self.read())
            await asyncio.sleep(self.interval_s)

    def start(self) -> None:
        self._task = asyncio.ensure_future(self._loop())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._task


def device_report(chips: int, *, rehearsal: bool) -> dict:
    """The device as JAX reports it. Without ``rehearsal`` anything but a
    TPU, or fewer chips than the cell asks for, is an error."""
    devs = jax.devices()
    if not rehearsal:
        if devs[0].platform != "tpu":
            raise SystemExit(
                f"perfbench: JAX found no TPU (platform {devs[0].platform!r})"
                ": the benchmark measures the chip and does not fall back"
            )
        if len(devs) < chips:
            raise SystemExit(
                f"perfbench: the cell needs {chips} chips, JAX sees {len(devs)}"
            )
    return {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }


def memory_peak_bytes() -> int:
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def memory_limit_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 0))
