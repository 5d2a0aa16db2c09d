"""InferenceEngine: continuous batching over the paged JAX model.

The engine is the TPU-native replacement for the reference's delegated
engines (vLLM et al.). One dedicated step THREAD owns the device (no
per-step event-loop round-trips — dispatch latency goes straight to ITL):

  admit -> prefill (token-budgeted batch of waiting prompts per step)
        -> decode (all active slots, one fixed-shape step)
        -> sample on device -> stream tokens to per-request queues

Prefix caching is page-granular and keyed by the same sequence-hash chain
the KV router indexes, so the router's cache view and the engine's actual
reuse agree. Cache events + ForwardPassMetrics publish through the standard
worker publishers, making this engine a drop-in behind the same frontend /
router / planner stack as the mocker.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import logging
import queue
import statistics
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, AsyncIterator

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.cache import OutOfPages, PageAllocator, SeqPages
from dynamo_tpu.engine.compile_cache import (
    compile_snapshot,
    enable_compile_cache,
    thread_compile_snapshot,
)
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.sampling import (
    sample_tokens,
    sample_tokens_masked,
    token_logprobs,
)
from dynamo_tpu.engine.spec import SPEC_TOKENS, SlotSpec
from dynamo_tpu.engine.tenancy import TenantScheduler
from dynamo_tpu.guided.runtime import GUIDED_REQUESTS
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics
from dynamo_tpu.models import llama
from dynamo_tpu.models.family import get_family
from dynamo_tpu.models.regions import SCOPE_FEED
from dynamo_tpu.runtime.context import (
    Context,
    DeadlineExceeded,
    OverQuota,
    ServiceUnavailable,
    tenancy_from_headers,
)
from dynamo_tpu.runtime.faults import FAULTS
from dynamo_tpu.runtime.integrity import verify_resume_tokens
from dynamo_tpu.runtime import race, tracing
from dynamo_tpu.runtime.flight import FLIGHT, emit_request_spans
from dynamo_tpu.runtime.loop_probe import LoopProbe
from dynamo_tpu.tokens import TokenBlockSequence

log = logging.getLogger("dynamo.engine")


# The burst feed path's device-side glue (_dispatch_burst). Each is ONE
# jitted program over a bounded shape set — burst lengths and admission
# wave widths — that precompile() walks; the same work as loose jnp ops
# compiled a dozen tiny programs on the first requests, and a new one for
# every new shape, on the step thread.


@jax.jit
@jax.named_scope(SCOPE_FEED)
def _chain_feed(valid, prev_combined, tokens_in):
    """Rows still live in an in-flight burst take its last sampled token
    (``prev_combined`` is that burst's [B, 1 + n] fed-column + samples)."""
    return jnp.where(valid, prev_combined[:, -1], tokens_in)


@jax.jit
@jax.named_scope(SCOPE_FEED)
def _wave_feed(mask, idx, wave, tokens_in):
    """Freshly admitted rows take their first token from the admission
    wave's device-side sample ``wave[idx]``."""
    return jnp.where(mask, wave[idx], tokens_in)


@jax.jit
@jax.named_scope(SCOPE_FEED)
def _with_fed_column(tokens_in, sampled):
    """[B, 1 + n]: the fed tokens ride along as column 0 of the burst's
    samples, so one download carries both."""
    return jnp.concatenate([tokens_in[:, None], sampled], axis=1)


def _is_ready(dev) -> bool:
    """The device has finished computing ``dev`` (anything that cannot say
    counts as finished)."""
    return getattr(dev, "is_ready", lambda: True)()


@partial(jax.jit, static_argnums=0)
def _init_params(spec: ModelSpec, key):
    """Random weights as ONE program: tensor by tensor a cold start
    compiles a draw a shape (13 s of a 50 s init on a v5e, PR 34)."""
    return get_family(spec).init_params(spec, key)


@dataclass
class _Slot:
    request_id: str
    context: Context
    out_q: asyncio.Queue
    seq: TokenBlockSequence  # prompt + generated tokens
    pages: SeqPages
    seq_len: int  # tokens currently in the KV cache
    remaining: int  # decode budget left
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    ignore_eos: bool = False
    stop_token_ids: frozenset[int] = frozenset()
    eos_ids: frozenset[int] = frozenset((2,))
    min_tokens: int = 0
    generated: int = 0
    last_token: int = 0
    sample_seed: int = 0  # per-request PRNG seed (reproducible if client-set)
    stalled_steps: int = 0  # consecutive steps skipped waiting for pages
    logprobs: int | None = None  # None=off, N=sampled+top-N per token
    # async admission: the first sampled token is still ON DEVICE (it
    # feeds the next decode burst there); the host value materializes one
    # step later without ever blocking the step thread on the d2h RTT
    first_pending: bool = False
    # speculative decoding (engine/spec.py): per-slot drafter + adaptive
    # k; None = this slot never speculates (spec off, temperature > 0,
    # logprobs requested)
    spec: SlotSpec | None = None
    # guided decoding (guided/runtime.py): host-side grammar cursor; the
    # step thread advances it as tokens land and ships its allowed-token
    # mask into every sampling dispatch this slot participates in
    guided: Any | None = None
    # tenancy (engine/tenancy.py): who this stream belongs to + its
    # priority class, and the original request dict so a preemption can
    # rebuild a resume request (prompt + generated, shrunk budget)
    tenant: str = "default"
    priority: str = "interactive"
    request: dict[str, Any] | None = None
    admitted_seq: int = 0  # monotonic admission order (preempt newest first)


@dataclass
class _Waiting:
    request: dict[str, Any]
    context: Context
    out_q: asyncio.Queue
    # running launch number (_launch) of the prefill program that took
    # this request's last prompt tokens: the flight recorder's
    # prefill_dispatch event carries it, as the profiler trace does
    prefill_seq: int = 0
    # tenancy routing keys (read by TenantScheduler): priority class
    # picks the lane group, tenant the lane, cost the WFQ vtime advance
    tenant: str = "default"
    priority: str = "interactive"
    cost: float = 1.0
    # True when generate() charged the tenant's bucket for this entry —
    # a bounce (shed, step-loop failure) refunds ONLY charged entries
    # (preemption resumes re-enter uncharged)
    charged: bool = False
    # admission passes this entry bounced on OutOfPages and was
    # requeued (page backpressure at admission = WAIT, like decode
    # backpressure): bounded so a pool that can never fit the prompt
    # still errors instead of parking forever
    page_stalls: int = 0


_REQUEUED = object()  # _prefill sentinel: entry went back to the queue

# finished timelines a profiled engine asks the flight recorder to keep:
# a measured window's worth (a minute at 50 requests a second)
PROFILE_TIMELINES = 4096

# Timeline.admission_phases() name -> the profile sum it feeds
READMIT_SUMS = {
    "admit_wait": "readmit.admit_wait",
    "prefill_dispatch": "readmit.prefill_dispatch",
    "first_token": "readmit.first_token",
}

# the engine's always-on host counters, each a dict of ints under the
# attribute of its name: profile_snapshot() carries them as
# ``<family>.<name>`` and reset_profile_window() zeroes them
_COUNTER_FAMILIES = (
    "decode_kv", "prefill_kv", "chunked_prefill", "burst_hold",
    "decode_bursts", "first_tokens", "kda", "ssd", "recurrent_state",
    "stream", "kv", "prefill",
)

# undisturbed burst times kept a burst length (their smallest is the
# estimate), and launch / admission costs kept (their medians are the
# guard): the hold of the queued burst, _hold_queued_burst
_BURST_SAMPLES = 4
_COST_SAMPLES = 8

# the step thread's three bounded waits on the wake event. Idle: slots or a
# partial are live but the cycle did no work. Readmit: a closed-loop
# client's resubmission crossing the event loop right after its finish
# item posted (finish -> client resubmit -> generate enqueue is ~a ms of
# loop latency), hidden behind the in-flight burst's device execution.
# Wave poll: a hold of the queued burst (_hold_queued_burst) with an
# admission wave's sample still on its way looks again this often, so a
# first token is posted within about a ms of reaching the host
_STEP_IDLE_SLEEP_S = 0.002
_READMIT_WAIT_S = 0.002
_WAVE_POLL_S = 0.001

# what _phase and _launch hand out with profiling off: one shared object
# whose enter and exit do nothing
_NO_SPAN = contextlib.nullcontext()


class _PhaseSpan:
    """One step-thread phase of a profiled engine: its wall time goes to
    the phase sums, and it is an ``engine.<name>`` annotation in a
    jax.profiler trace (a no-op of the profiler's while none is taken),
    so the phases share the device events' clock."""

    __slots__ = ("_prof", "_name", "_t0", "_note")

    def __init__(self, prof: dict[str, list[float]], name: str):
        self._prof = prof
        self._name = name
        self._note = jax.profiler.TraceAnnotation("engine." + name)

    def __enter__(self) -> None:
        self._note.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._note.__exit__(*exc)
        rec = self._prof.setdefault(self._name, [0.0, 0])
        rec[0] += dt
        rec[1] += 1


# the ONE private key of a profiled engine's items: the post instant in ns
# of time.monotonic, put on by _post inside a stream.post span and taken
# off by generate() before the yield
_POSTED = "_posted"


class _PostSpan:
    """A profiled engine's ``stream.post``: the step thread hands the
    streams the tokens of one device program (a decode burst, or the
    prefill whose sample an admission wave lands). An annotation on the
    profiler's clock carrying the program's launch number; while it is open
    ``_post`` marks every item that carries tokens with the post instant
    on ``time.monotonic``, read once a span."""

    __slots__ = ("_engine", "_mark", "_note")

    def __init__(self, engine: "InferenceEngine", seq: int):
        self._engine = engine
        self._mark = time.monotonic_ns()
        self._note = jax.profiler.TraceAnnotation("stream.post", seq=seq)

    def __enter__(self) -> None:
        self._note.__enter__()
        self._engine._post_mark = self._mark

    def __exit__(self, *exc) -> None:
        # spans of posts never nest: a wave lands before or after a burst
        self._engine._post_mark = None
        self._note.__exit__(*exc)


@dataclass
class _PartialPrefill:
    """A long prompt mid-way through chunked prefill (ref: vLLM's
    max_num_batched_tokens chunking — here the engine owns the loop, so
    chunks interleave with decode steps explicitly)."""

    slot_idx: int
    waiting: _Waiting
    seq: TokenBlockSequence
    sp: SeqPages
    token_ids: list[int]
    done: int  # prompt tokens already in the KV cache
    max_tokens: int


class InferenceEngine:
    def __init__(
        self,
        spec: ModelSpec,
        config: EngineConfig | None = None,
        *,
        mesh=None,
        params=None,
        event_publisher=None,
        metrics_publisher=None,
        transfer_source=None,
        kvbm=None,
        spmd=None,
        guided_vocab=None,
    ):
        self.spec = spec
        self.transfer_source = transfer_source
        self.kvbm = kvbm
        # persistent XLA compilation cache: wired here so EVERY engine
        # process shares it (worker, follower shell, bench, smoke) — a
        # restarted worker reloads serving programs from disk instead of
        # paying cold-start TTFT recompiling them
        enable_compile_cache()
        # multi-host: SpmdLeader broadcasting every serving-path dispatch
        # so follower processes replay the same SPMD programs
        # (parallel/spmd.py). Pipelined decode replays too (descriptors
        # carry the chain masks; followers chain from their own pending
        # results). Async admissions stay leader-local — their device-
        # side first-token feed has no follower counterpart, so the sync
        # admission path runs instead (first tokens reach followers via
        # the next burst's host token array).
        self.spmd = spmd
        if spmd is not None and config is not None:
            config.async_admissions = False
        self.offload = None
        if kvbm is not None:
            from dynamo_tpu.kvbm.offload import OffloadEngine

            self.offload = OffloadEngine(kvbm).start()
        # (sequence_hash, page, block_index) sealed this step, pending offload
        self._pending_offload: list[tuple[int, int, int]] = []
        self.config = config or EngineConfig()
        self.mesh = mesh
        self.events = event_publisher
        self.metrics = metrics_publisher

        self.fam = get_family(spec)
        if self.fam.recurrent:
            # a sequence's pages hold none of its recurrent state, so what
            # moves or reuses pages is off for this model; what was asked
            # for anyway is counted (_note_recurrent_gate)
            if self.kvbm is not None:
                self._note_recurrent_gate("page_offload")
                self.offload.close()
                self.kvbm = self.offload = None
            if self.config.spec_mode != "off":
                self._note_recurrent_gate("spec_decode")
            if self.config.sp > 1:
                self._note_recurrent_gate("ring_prefill")
        if mesh is not None and not self.fam.supports_mesh:
            raise ValueError(
                f"{type(self.fam).__name__} does not support meshes yet; "
                "run this model family single-device"
            )
        key = jax.random.PRNGKey(self.config.seed)
        # one process drives the whole mesh: weights and pools are BORN
        # sharded, since a model that needs the mesh does not fit the
        # default device first (random bits do not depend on the
        # sharding, so tp=N holds tp=1's weights). A mesh spanning
        # processes keeps build-locally-then-place: its init would be a
        # multi-process computation, which the CPU backend the multi-host
        # tests run on does not implement.
        born_sharded = mesh is not None and not mesh.is_multi_process
        if mesh is not None:
            shardings = self.fam.param_shardings(spec, mesh)
            if params is None and born_sharded:
                params = jax.jit(
                    lambda k: self.fam.init_params(spec, k),
                    out_shardings=shardings,
                )(key)
            else:
                if params is None:
                    params = self.fam.init_params(spec, key)
                params = jax.tree.map(jax.device_put, params, shardings)
        elif params is None:
            params = _init_params(spec, key)
        self.params = params

        # KV storage dtype (ops/quant.py): fp8 pools halve decode HBM
        # reads and the KVBM tier footprint. Combinations whose pool
        # plumbing is not quantization-aware yet fail LOUDLY here rather
        # than corrupting state mid-serving.
        self.kv_dtype = self.config.kv_dtype
        if self.kv_dtype == "fp8":
            if spmd is not None:
                raise ValueError(
                    "kv_dtype=fp8 is not in the SPMD follower replay "
                    "protocol yet; run multi-host workers with bf16"
                )
        # +1 page: index 0 is the trash page

        def init_cache():
            # a live sequence owns one state row, so one a decode slot
            rows = (
                {"state_rows": self.config.max_decode_slots}
                if self.fam.recurrent else {}
            )
            return self.fam.init_cache(
                spec, self.config.num_pages + 1, self.config.page_size,
                kv_dtype=self.kv_dtype,
                tp=mesh.shape.get("tp", 1) if mesh is not None else 1,
                **rows,
            )

        if born_sharded:
            init_cache = jax.jit(
                init_cache,
                out_shardings=self.fam.cache_shardings(
                    mesh, self.kv_dtype, spec
                ),
            )
        self.k_pages, self.v_pages = init_cache()
        if mesh is not None and not born_sharded:
            ks, vs = self.fam.cache_shardings(mesh, self.kv_dtype, spec)
            self.k_pages = jax.device_put(self.k_pages, ks)
            self.v_pages = jax.device_put(self.v_pages, vs)
        # what this process's first (mesh) device holds now that weights
        # and pools are built; chip_smoke.py checks its peak against its
        # shard
        dev = (mesh.local_devices if mesh is not None else jax.devices())[0]
        self.build_memory_stats: dict = dev.memory_stats() or {}
        # where host-built fed tokens go (_feed_array)
        self._fed_sharding = None
        if mesh is not None and spmd is None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._fed_sharding = NamedSharding(mesh, PartitionSpec())
        # {bucket: pack width}: the prefill shapes this engine offers —
        # what max_context reaches and what fits the device beside the
        # weights and pools just built (EngineConfig.prefill_shapes)
        self._prefill_shapes = self.config.prefill_shapes(
            spec, self._free_device_bytes(),
            tp=mesh.shape.get("tp", 1) if mesh is not None else 1,
        )

        # pages let go of since the last prefill, whose state rows the
        # next one frees before it claims its own (_flush_state_releases)
        self._state_released: collections.deque = collections.deque()
        self.allocator = PageAllocator(
            self.config.num_pages + 1,
            self.config.page_size,
            on_store=self._on_store,
            on_evict=self._on_evict,
            on_release=(
                self._state_released.extend if self.fam.recurrent else None
            ),
            prefix_cache=self.fam.supports_prefix_reuse,
        )
        self._slots: list[_Slot | None] = [None] * self.config.max_decode_slots
        # the decode burst lengths this engine dispatches — the full
        # burst, the short one (_short_burst) and the single step (guided
        # masks, the last tokens before the context cap): one compiled
        # program each, all walked by precompile()
        full = max(1, self.config.decode_steps_per_dispatch)
        self._burst_lengths = sorted({
            1, full,
            min(full, self.config.decode_steps_admit_pending or full),
        })
        # fair admission (engine/tenancy.py): weighted-fair per-tenant
        # lanes + token buckets replacing the old single FIFO — same
        # qsize/empty/put_nowait/get_nowait surface the sweeps use
        self._waiting: TenantScheduler = TenantScheduler(
            self.config.tenants if isinstance(self.config.tenants, dict)
            else None
        )
        self._seed_counter = self.config.seed
        self._thread: threading.Thread | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._loop_thread: int | None = None
        self._wake = race.Event("engine.wake")
        if spmd is not None:
            # a rejoining follower parks until the step loop serves its
            # state sync; wake an idle loop the moment one arrives
            spmd.on_sync_request = self._wake.set
        self._closed = False
        # SIGTERM drain: stop admitting (generate refuses with
        # ServiceUnavailable) while in-flight slots run to completion;
        # the deadline (when known) prices the refusal's Retry-After
        self._draining = False
        self._drain_deadline: float | None = None
        # priority preemption (overload plane): paused-batch-stream
        # counters by reason, sampled into
        # dynamo_engine_preemptions_total{reason} (engine/telemetry.py)
        self.preemptions: dict[str, int] = {}
        self._admit_seq = 0  # monotonic admission order for victim ranking
        # disagg KV pulls that failed and fell back to a local prefill
        self.disagg_fallbacks = 0
        self.steps = 0
        # eager re-admission passes that filled a slot in the SAME step
        # cycle that freed it (observability for the serving-latency work)
        self.eager_readmits = 0
        # speculative decoding (engine/spec.py): gated to single-host —
        # the verify dispatch is not in the SPMD follower replay protocol
        self._spec_on = (
            self.config.spec_mode == "ngram"
            and spmd is None
            and getattr(self.fam, "supports_spec_decode", False)
        )
        self.spec_verifies = 0  # verify dispatches issued
        self.spec_drafted = 0  # draft tokens proposed into verifies
        self.spec_accepted = 0  # drafts the target's argmax confirmed
        self.spec_rejected = 0  # drafts cut by accept-longest-prefix
        # guided decoding (guided/): grammar compiler + per-(grammar,
        # vocab) mask cache. Needs a token vocabulary (the worker builds
        # one from its tokenizer; tests/bench pass one explicitly) and is
        # gated off under SPMD — the mask arrays are not in the follower
        # replay protocol.
        self._guided = None
        if (
            guided_vocab is not None
            and self.config.guided_mode != "off"
            and spmd is None
        ):
            from dynamo_tpu.guided.runtime import GrammarCompiler

            self._guided = GrammarCompiler(
                guided_vocab,
                vocab_size=spec.vocab_size,
                # LRU of compiled grammars, keyed (grammar, vocab):
                # agentic traffic reuses a handful of schemas, so steady
                # state is all hits
                cache_entries=32,
            )
        self._partial: _PartialPrefill | None = None
        # this step-loop cycle ran a chunk of a partial that was open when
        # it began (_step): such a cycle admits nothing else
        self._chunk_cycle = False
        self._clear_cache_requested = False
        # dispatched-but-unprocessed decode bursts, oldest first: one
        # between cycles when pipeline_decode, two from a dispatch until
        # the older one is read (_decode_step)
        self._pipeline: list[dict] = []
        # the hold of the queued burst (_hold_queued_burst) rests on what
        # this thread has measured, on ``_clock``: the instant the newest
        # read of a burst returned HAVING BLOCKED, i.e. when that burst
        # ended on the device (None where the read found it done, and
        # while the running burst was launched behind nothing); the last
        # few times between two such instants with nothing but the burst
        # launched between them, a burst length apart; where other
        # programs (prefills, their samples) stood between, what is left
        # of that time a launch of theirs; what building and launching a
        # burst, and an admission pass that admitted, have lately cost
        # this thread; and ``_launch_seq`` as the newest burst left it
        self._clock = time.monotonic
        self._burst_ended: float | None = None
        self._burst_secs: dict[int, collections.deque] = {}
        self._side_secs: collections.deque = collections.deque(
            maxlen=_BURST_SAMPLES)
        self._launch_secs: collections.deque = collections.deque(
            maxlen=_COST_SAMPLES)
        self._admit_secs: collections.deque = collections.deque(
            maxlen=_COST_SAMPLES)
        self._seq_at_burst = 0
        self._holding = False
        # async first-token waves, oldest first: each holds a device
        # sample whose host copy is in flight; waves touch disjoint live
        # slots (slot-identity guards handle reuse), so they materialize
        # independently as their copies land
        self._admit_waves: list[dict] = []
        # the expert layers' counters (``fam.moe_counts``: KindPools.counts
        # of a GQA model with layer kinds, the latent family's v slot;
        # added to on the device by the programs): the last host copy,
        # and the device copy on its way (_refresh_moe_counts)
        self.moe_counts: np.ndarray | None = None
        self._moe_counts_dev = None
        self._metrics_publishes = 0
        # step-thread phase profiler (EngineConfig.profile, the one
        # switch): wall seconds + call counts per phase, read via
        # profile_snapshot(), and every phase, device launch and loop
        # cycle as an ``engine.*`` annotation in a jax.profiler trace
        self._profiling = bool(self.config.profile)
        self._prof: dict[str, list[float]] = {}
        # per-request sums (readmit.*), added where a stream finishes (the
        # event loop) from its flight-recorder timeline: kept apart from
        # _prof, which only the step thread writes
        self._prof_requests: dict[str, list[float]] = {}
        # running number of the device programs the step thread has
        # launched (_launch): always on, one int add a launch
        self._launch_seq = 0
        # the delivery path (profiled only): the mark an open stream.post
        # span holds out to _post, and the running number of the streams
        # that took a marked item (``rid`` on stream.take)
        self._post_mark: int | None = None
        self._stream_rids = 0
        # the flight recorder the step thread records into; profiled, it
        # keeps every finished timeline of a measured window
        self.flight = FLIGHT
        if self._profiling:
            FLIGHT.retain(PROFILE_TIMELINES)
        # dispatch accounting (always on — one int add per device
        # dispatch): jitted programs issued by the step thread, plus the
        # process-wide compile-event baseline so profile_snapshot can
        # attribute compiles that happened on THIS engine's watch
        self.dispatches = 0
        self._compile_base = compile_snapshot()
        # what the decode kernel of the full-attention layers moved, in
        # pages a layer, over the dispatched bursts' steps (always on: a
        # few integer operations on [slots, steps] a burst): pages that
        # hold context, pages its live chunks fetched, pages of the tables
        self._kv_chunk_pages = self._full_table_chunk_pages()
        # how the page pools were laid out: fixed at the build, so no
        # window zeroes them
        self.kv_pool = self._kv_pool_layout()
        self.decode_kv = {"pages_live": 0, "pages_fetched": 0,
                          "pages_table": 0}
        # what the window layers' pages hold that no later query can see
        # (always on where the model has window layers, from the lengths
        # the step thread holds: no device work): see _count_decode_kv
        # {window: layers of it}
        kinds = map(self.spec.kind, range(self.spec.num_layers))
        self._window_layers = collections.Counter(
            kd.window for kd in kinds if kd.window)
        self.kv = {"window_layer_tokens": 0, "window_dead_tokens": 0} if (
            self._window_layers) else {}
        # a model whose upper layers write no cache (SambaY's
        # cross-decoder; always on for it alone, from the lengths the step
        # thread holds: no device work): the layers that read ANOTHER
        # layer's pages, whose reads of the one pool ``kv.
        # shared_read_tokens`` sums (live tokens x those layers, a decode
        # step); and the prompt tokens the prefill programs took through
        # the layers below (``prefill.rows``) beside the rows, one a
        # sequence, they took through those above (``prefill.cross_rows``)
        self._shared_readers = sum(
            bool(self.spec.kind(li).reads)
            for li in range(self.spec.num_layers))
        if self._shared_readers:
            self.kv["shared_read_tokens"] = 0
        self.prefill = (
            {"rows": 0, "cross_rows": 0}
            if self.spec.carried_from < self.spec.num_layers else {}
        )
        # what the prefill walk visited, in blocks of pages a layer, over
        # the dispatched prefills and verifies (always on: integer
        # arithmetic on [rows, tiles] a dispatch), a layer kind apart,
        # beside what a walk of the table's whole width would have
        # the latent family also counts its dispatches and those its
        # Mosaic kernel served (``kernel_calls / dispatches``: the hit
        # share of ops/attention.latent_prefill_attention's choice)
        self._prefill_walks = self._prefill_walk_windows()
        self.prefill_kv = {
            f"{what}.{kind}": 0
            for kind in self._prefill_walks
            for what in ("blocks_visited", "blocks_table") + (
                ("dispatches", "kernel_calls") if kind == "latent" else ())
        }
        # how a chunked prefill's chunks met the decode pipeline (always
        # on: two int adds a chunk): every chunk launched, the first
        # included, and those launched with a burst in flight, i.e. queued
        # behind device work and not after a drained device
        self.chunked_prefill = {"chunks": 0, "chunks_behind_burst": 0}
        # how often the queued burst was held for arrivals (always on, an
        # int add a cycle): holds begun; those at whose end the running
        # burst had already finished when the held one was launched (each
        # a few ms of idle device); requests admitted, and those of them
        # admitted during a hold, their prefill launched directly behind
        # the running burst (``held=1`` on their ``engine.launch``)
        self.burst_hold = {"begun": 0, "overran": 0, "admissions": 0,
                           "admissions_held": 0}
        # decode bursts dispatched at each compiled length (always on):
        # the full burst, the short one (_short_burst) and the single
        # step (a guided mask, the last tokens before the context cap);
        # a length that is two of them counts as full, else as single
        self.decode_bursts = {"full": 0, "short": 0, "single": 0}
        # where async admissions' first tokens came home (always on):
        # from the wave's own download while the queued burst was held
        # (_hold_queued_burst) or at the top of a cycle (_step), or from
        # the fed column of their slot's first burst (_process_burst)
        self.first_tokens = {"in_hold": 0, "at_step": 0, "on_burst": 0}
        # a token-carrying item's way from the step thread's post to
        # generate()'s take (a profiled engine only; the event loop alone
        # writes them): items taken and their waits summed
        self.stream = {"items": 0, "wait_us": 0}
        # the event loop's heartbeat (runtime/loop_probe.py; always on,
        # 20 wake-ups a second between start() and close()): its sum of
        # the lags over 50 ms is ``event_loop.stalled_us``; profiled, such
        # a lag is a ``loop.stall`` annotation too
        self.loop_probe = LoopProbe(
            self._note_loop_stall if self._profiling else None)
        # what the KDA kernels were asked to do, a layer's worth (always
        # on, a model with recurrent layers only): state rows a kda_step
        # call updated, over the dispatched bursts' steps; blocks of
        # tokens kda_chunk carried a state through and rows it resumed
        # (start_pos > 0), over the prefills
        self.kda = (
            {"decode_rows": 0, "prefill_blocks": 0, "rows_resumed": 0}
            if "kda" in spec.mixers else {}
        )
        # and the SSD mixer's, a layer's worth (a model with SSD layers
        # only): state rows an ssd_step call updated, over the dispatched
        # bursts' steps; chunks of tokens the chunk form carried a state
        # through and rows it resumed (start_pos > 0), over the prefills
        self.ssd = (
            {"decode_rows": 0, "prefill_chunks": 0, "rows_resumed": 0}
            if "ssd" in spec.mixers else {}
        )
        # and what every recurrent mixer shares (a model with recurrent
        # layers only; beside state_counters() under ``recurrent_state``):
        # members of the prefill programs with tokens, and those of them
        # that resumed a state or a tail (start_pos > 0)
        self.recurrent_state = (
            {"prefill_chunks": 0, "rows_resumed": 0}
            if self.fam.recurrent else {}
        )
        # the state directory's device-side counters [clock, claims, rows
        # missing]: the last host copy and the one on its way
        self.state_stats: np.ndarray | None = None
        self._state_stats_dev = None
        # worker telemetry feeds (engine/telemetry.py EngineCollector):
        # the step thread only appends to bounded deques / bumps ints;
        # the collector turns them into /metrics histograms+counters
        self.step_times: collections.deque = collections.deque(maxlen=4096)
        self.burst_fills: collections.deque = collections.deque(maxlen=4096)
        # degradation fingerprint: EWMA of work-cycle step latency (ms),
        # published in ForwardPassMetrics and scored peer-relative by the
        # fleet-side DegradationDetector (runtime/health.py)
        self.step_time_ewma_ms = 0.0
        self.admission_rejects = {
            "draining": 0, "saturated": 0, "deadline": 0,
            "over_quota": 0, "shed": 0,
        }
        self.telemetry = None  # EngineCollector, attached by the worker
        self.precompile_report: dict[str, dict] = {}  # set by precompile()

    def _feed_array(self, host_tokens) -> jax.Array:
        """A burst's fed tokens, built on the host, placed where the feed
        glue's results live — replicated over the mesh — so a decode
        program sees ONE placement of its tokens whether they came from
        the host or from an in-flight burst (under a mesh the two would
        otherwise be two compiled programs). Identity on one device, and
        under SPMD, whose followers feed from their own host copies."""
        x = jnp.asarray(host_tokens)
        if self._fed_sharding is None:
            return x
        return jax.device_put(x, self._fed_sharding)

    def _free_device_bytes(self) -> int | None:
        """Device memory left for a step program's temporaries once the
        weights and pools are built, with a tenth of the device held back
        for the decode burst's own and the allocator's fragmentation.
        None where the backend reports no limit (the CPU)."""
        stats = self.build_memory_stats
        limit = stats.get("bytes_limit")
        if not limit:
            return None
        return max(0, limit - stats.get("bytes_in_use", 0) - limit // 10)

    def _prof_requests_add(self, tl) -> None:
        """The re-admission gap attribution of one finished request, from
        its flight-recorder timeline (runtime/flight.py
        ``admission_phases``): ``readmit.admit_wait`` /
        ``readmit.prefill_dispatch`` / ``readmit.first_token`` break the
        finish->next-first-token path into named phases
        (benchmarks/profile_engine.py). Event loop only."""
        for name, dt in tl.admission_phases():
            rec = self._prof_requests.setdefault(READMIT_SUMS[name], [0.0, 0])
            rec[0] += dt
            rec[1] += 1

    def _phase(self, name: str):
        """A step-thread phase. Profiled: its wall time is summed under
        ``name`` and it is an ``engine.<name>`` annotation on the
        profiler's clock. Unprofiled: the one shared no-op."""
        if not self._profiling:
            return _NO_SPAN
        return _PhaseSpan(self._prof, name)

    def _launch(self, kind: str, **counts: int):
        """Around one device program the step thread issues: numbers it
        and, profiled, wraps the issue in an ``engine.launch`` annotation
        carrying ``kind``, ``seq`` and ``counts``. The device runs one
        stream in launch order, so a trace's k-th launch of a kind is its
        k-th execution of that kind. ``counts`` are host values only: an
        annotation never reads a device array. A prefill and a decode
        burst carry ``ahead``, the decode bursts in flight at the launch:
        how many bursts the program is queued behind at most; a program
        launched while the queued burst is held (_hold_queued_burst)
        carries ``held=1``: it stands directly behind the running burst."""
        self._launch_seq += 1
        if not self._profiling:
            return _NO_SPAN
        if self._holding:
            counts["held"] = 1
        return jax.profiler.TraceAnnotation(
            "engine.launch", kind=kind, seq=self._launch_seq, **counts
        )

    def _stream_post(self, seq: int):
        """Around the posts of one device program's tokens (phase 2 of
        _process_burst; the landing of an admission wave). ``seq`` is that
        program's launch number: a decode burst's, noted on its batch at
        the launch, or the prefill's whose sample the wave is. Profiled: a
        ``stream.post`` annotation, and every item posted inside it leaves
        marked for generate()'s ``stream.take``. Unprofiled: the one
        shared no-op, and no clock is read."""
        if not self._profiling:
            return _NO_SPAN
        return _PostSpan(self, seq)

    def _stream_take(self, item: dict, request_id: str, rid: int,
                     first: bool) -> int:
        """generate() takes a token-carrying item off its queue (profiled
        engine, event loop): the flight recorder's ``delta`` for every
        such item after the first, and for an item a stream.post span
        marked the ``stream`` counts and a ``stream.take`` annotation
        (``rid`` the stream's running number, ``wait_us`` take minus
        post). The mark comes off here: nothing
        downstream of generate() sees it. Returns the stream's ``rid``,
        handed out at its first marked item."""
        if not first:
            FLIGHT.event(request_id, "delta")
        posted_ns = item.pop(_POSTED, None)
        if posted_ns is None:
            return rid
        if not rid:
            self._stream_rids = rid = self._stream_rids + 1
        wait_us = max(0, time.monotonic_ns() - posted_ns) // 1000
        c = self.stream
        c["items"] += 1
        c["wait_us"] += wait_us
        with jax.profiler.TraceAnnotation(
            "stream.take", rid=rid, wait_us=wait_us
        ):
            pass
        return rid

    @staticmethod
    def _note_loop_stall(lag_us: int) -> None:
        """A profiled engine's ``loop.stall``: the event loop's heartbeat
        (runtime/loop_probe.py) woke ``lag_us`` late, over 50 ms: the loop
        stood still for that long up to this annotation's instant."""
        with jax.profiler.TraceAnnotation("loop.stall", lag_us=lag_us):
            pass

    def profile_snapshot(self) -> dict[str, dict[str, float]]:
        """Per-phase accumulated step-thread wall time (profiling mode),
        plus the always-on dispatch accounting:

        - ``dispatch.dispatches``: jitted device programs issued by the
          step thread (calls; secs stays 0 — issue time is inside the
          existing dispatch/prefill phases).
        - ``dispatch.d2h_wait``: wall time the step thread spent BLOCKED
          on device->host transfers (burst token sync, sync-admission
          device_get, aged admission-wave materialization).
        - ``dispatch.compile``: backend compile events (+ seconds) since
          this engine was built, from the process-wide jax.monitoring
          listener (engine/compile_cache.py) — nonzero during a steady
          serving window means a shape escaped the warmup set.
        - ``decode_kv.pages_live`` / ``.pages_fetched`` / ``.pages_table``
          (calls): see ``_count_decode_kv``.
        - ``kv_pool.heads_per_lane_row`` / ``.bytes`` (calls): see
          ``_kv_pool_layout``; fixed at the build, no window zeroes them.
        - ``kv.window_layer_tokens`` / ``.window_dead_tokens`` (calls; a
          model with window layers only): see ``_count_decode_kv``.
        - ``kv.shared_read_tokens`` (calls; a model with layers that read
          another layer's pages only): live tokens x those layers, summed
          over the decode steps: what the ONE shared pool is read for
          beyond its own layer.
        - ``prefill.rows`` / ``.cross_rows`` (calls; a model whose upper
          layers write no cache only): prompt tokens the prefill programs
          took through the layers below ``ModelSpec.carried_from``, and
          rows (one a sequence with tokens) through those above. Their
          ratio is ~1 / the prompts' length; 1 would say the upper half
          ran for every row.
        - ``prefill_kv.blocks_visited.<kind>`` / ``.blocks_table.<kind>``
          (calls; kind ``full`` or ``window``, or ``latent`` for the
          latent family's walk, which also reports
          ``prefill_kv.dispatches.latent`` and ``.kernel_calls.latent``:
          its dispatches and those its Mosaic kernel served): see
          ``_count_prefill_kv``.
        - ``window.at``: the instant this snapshot was taken (secs =
          ``time.monotonic()``), so that the difference of two snapshots
          is divided by the time that really lay between them.
        - ``chunked_prefill.chunks`` / ``.chunks_behind_burst`` (calls):
          the chunks of chunked prefills launched, and those of them
          launched with a decode burst in flight (``ahead`` >= 1 on their
          ``engine.launch``). Their ratio is how often a long prompt's
          next chunk rode the decode pipeline instead of finding the
          device drained; near 1 on a saturated engine.
        - ``burst_hold.begun`` / ``.overran`` / ``.admissions`` /
          ``.admissions_held`` (calls): cycles in which the queued burst
          was held for arrivals (``_hold_queued_burst``), those of them in
          which the running burst had already ended when the held one was
          launched (the device idled for the launch), requests admitted,
          and those admitted during a hold. ``admissions_held /
          admissions`` is how often a prompt's prefill stood directly
          behind the running burst; ~0 where the queue is never empty.
        - ``decode_bursts.full`` / ``.short`` / ``.single`` (calls): decode
          bursts dispatched at ``decode_steps_per_dispatch`` steps, at
          ``decode_steps_admit_pending`` steps (``_short_burst``: an empty
          queue beside a free slot, or a queue under half occupancy) and
          at one step (a guided mask, the last tokens before the context
          cap). ``short / (full + short + single)`` between two snapshots
          is how often a burst was shortened for an arrival's sake; 0
          where no short program is compiled.
        - ``first_tokens.in_hold`` / ``.at_step`` / ``.on_burst`` (calls):
          async admissions' first tokens posted from their wave's own
          download during a hold of the queued burst
          (``_hold_queued_burst``), from it at the top of a cycle
          (``_step``; the forced reads of a flush or a close too), and
          from the fed column of their slot's first burst
          (``_process_burst``). ``in_hold`` over the three is how often a
          first token came home the moment its prefill ended, not a burst
          later; ~0 where the queue is never empty.
        - ``stream.items`` / ``.wait_us`` (calls; a profiled engine only,
          else 0): token-carrying items generate() took off its queue and
          the time they lay between the step thread's post and that take,
          summed (``_stream_take``): the one over the other is the mean
          wait of a window.
        - ``event_loop.stalled_us`` (calls; always on): the event loop's
          heartbeat (runtime/loop_probe.py), the sum of the lags over
          50 ms of a 50 ms sleep's wake-ups. Between two snapshots over
          ``window.at``'s difference it is the share of the time the loop
          stood still; every wake-up's own lag is in the probe's ring
          (``loop_probe.lags``).
        """
        snap = {
            k: {"secs": round(v[0], 4), "calls": int(v[1])}
            for k, v in sorted(
                (*self._prof.items(), *self._prof_requests.items()),
                key=lambda kv: -kv[1][0],
            )
        }
        snap.setdefault("dispatch.d2h_wait", {"secs": 0.0, "calls": 0})
        snap.setdefault("readmit.d2h_wait", {"secs": 0.0, "calls": 0})
        snap["dispatch.dispatches"] = {"secs": 0.0, "calls": self.dispatches}
        snap["window.at"] = {"secs": time.monotonic(), "calls": 0}
        c, s = compile_snapshot()
        snap["dispatch.compile"] = {
            "secs": round(s - self._compile_base[1], 4),
            "calls": c - self._compile_base[0],
        }
        # the expert layers' device-side counters, as of their last
        # refresh (moe_counters): counts, so ``calls``
        for name, n in self.moe_counters().items():
            snap[f"moe.{name}"] = {"secs": 0.0, "calls": n}
        for name, n in self.state_counters().items():
            snap[f"recurrent_state.{name}"] = {"secs": 0.0, "calls": n}
        for family in (*_COUNTER_FAMILIES, "kv_pool"):
            for name, n in getattr(self, family).items():
                snap[f"{family}.{name}"] = {"secs": 0.0, "calls": n}
        # the heartbeat's one count that two snapshots can difference
        for name, n in (("stalled_us", self.loop_probe.stalled_us),):
            snap[f"event_loop.{name}"] = {"secs": 0.0, "calls": n}
        return snap

    def reset_profile_window(self) -> None:
        """Zero the profiling counters so the next profile_snapshot
        covers only work from this point on (drop warmup/compile noise
        before a measured window — profile_engine.py)."""
        self._prof.clear()
        self._prof_requests.clear()
        self.dispatches = 0
        for family in _COUNTER_FAMILIES:
            setattr(self, family, dict.fromkeys(getattr(self, family), 0))
        self.loop_probe.stalled_us = 0
        self._compile_base = compile_snapshot()

    def _full_table_chunk_pages(self) -> int | None:
        """Pages a chunk of the decode kernel holds on this cache's
        full-attention layers, from the pools' own shapes
        (``ops/pallas/fused_decode.chunk_pages``; the latent family's
        kernel, ``latent_decode.latent_chunk_pages``, which also reads a
        latent KIND's pool); None where the cache is not one that a kernel
        reads."""
        from dynamo_tpu.ops.pallas.fused_decode import pool_chunk_pages
        from dynamo_tpu.ops.pallas.latent_decode import latent_chunk_pages
        from dynamo_tpu.ops.quant import is_quant

        k, v = self.k_pages, self.v_pages
        if self.spec.is_mla:
            k = self._latent_pool()
            if is_quant(k):
                return None  # an fp8 latent pool keeps the XLA walk
            return latent_chunk_pages(k, self.config.max_pages_per_seq)
        if hasattr(k, "pools"):  # a pool a layer kind (llama.KindPools)
            from dynamo_tpu.models.llama import kind_pages

            full = next(
                (i for i, kd in enumerate(self.spec.layer_kinds)
                 if not kd.window and kd.paged), None,
            )
            if full is None:
                return None
            k = kind_pages(self.spec, k, full)
            if self.spec.layer_kinds[full].latent:
                return latent_chunk_pages(k, self.config.max_pages_per_seq)
            v = kind_pages(self.spec, v, full)
        if len(k.shape) != 5:
            return None
        return pool_chunk_pages(k, v, self.config.max_pages_per_seq)

    def _kv_pool_layout(self) -> dict[str, int]:
        """``kv_pool.*``, read off the pools' own shapes against the
        model's heads, as every reader of a pool does
        (``ops/attention.pool_head_dim``): ``heads_per_lane_row``, the KV
        heads a row of an attention kind's K pool holds (2 where 64-wide
        heads are packed two a 128-lane row; 1 for a plain or a
        zero-padded pool and for latent rows; the most over a model's
        kinds), and ``bytes``, the page pools' in all (both sides, every
        kind that keeps pages, an fp8 pool's scales with it)."""
        spec, k, v = self.spec, self.k_pages, self.v_pages
        if spec.is_mla:
            pools, a_row = [k], 1
        elif hasattr(k, "pools"):  # a pool a layer kind (llama.KindPools)
            from dynamo_tpu.models.llama import kind_pages

            paged = [
                (ki, kd) for ki, kd in enumerate(spec.layer_kinds) if kd.paged
            ]
            pools = [kind_pages(spec, s, ki) for ki, _ in paged for s in (k, v)]
            a_row = max(
                (kd.num_kv_heads // kind_pages(spec, k, ki).shape[2]
                 for ki, kd in paged if not kd.latent), default=1,
            )
        else:
            pools, a_row = [k, v], spec.num_kv_heads // k.shape[2]
        return {
            "heads_per_lane_row": a_row,
            "bytes": sum(
                x.size * x.dtype.itemsize for x in jax.tree.leaves(pools)
            ),
        }

    def _count_decode_kv(self, batch: dict) -> None:
        """Add a dispatched burst to ``decode_kv``: over its live slots
        and steps, the pages that hold a sequence's context
        (``pages_live``), the pages of the chunks the kernel fetches and
        scores for it (``pages_fetched``: live chunks by the kernel's own
        ``live_chunks``, times its chunk) and, over all slots, the pages
        of the tables (``pages_table``), which a kernel that followed
        the table would move. One layer's worth, as a full-attention
        layer sees them. ``pages_fetched / pages_table`` is how far the
        kernel's work follows the contexts; ``pages_live /
        pages_fetched`` is what the chunk's size wastes.

        And to ``kv`` where the model has window layers, which keep every
        page of a sequence while it lives: the tokens its live slots hold
        at each step times the window layers (``window_layer_tokens``)
        and, of those, the ones more than the layer's window behind their
        row's length, which no later query of the row can see
        (``window_dead_tokens``). Their ratio is the share of the window
        layers' pool that pages found by layer kind would give back."""
        from dynamo_tpu.ops.pallas.fused_decode import live_chunks

        for counters in (self.kda, self.ssd):
            if counters:
                counters["decode_rows"] += (
                    int(batch["active"].sum()) * batch["n_burst"]
                )
        n_burst = batch["n_burst"]
        # a live slot's length grows by one a step; the pool holds all
        # of it but the step's own token
        lens = (
            batch["seq_lens"][batch["active"]][:, None]
            + np.arange(n_burst, dtype=np.int32)[None, :]
        )
        for window, layers in self._window_layers.items():
            self.kv["window_layer_tokens"] += int(lens.sum()) * layers
            self.kv["window_dead_tokens"] += (
                int((lens - window).clip(0).sum()) * layers)
        if self._shared_readers:
            self.kv["shared_read_tokens"] += (
                int(lens.sum()) * self._shared_readers)
        chunk = self._kv_chunk_pages
        if chunk is None:
            return
        page = self.config.page_size
        _, chunks = live_chunks(lens, page, chunk)
        kv = self.decode_kv
        kv["pages_live"] += int((-(-(lens - 1) // page)).sum())
        kv["pages_fetched"] += int(chunks.sum()) * chunk
        kv["pages_table"] += (
            len(batch["active"]) * self.config.max_pages_per_seq * n_burst
        )

    def _prefill_walk_windows(self) -> dict[str, int]:
        """The kinds of attention layer whose prefill is a walk over
        pages, each with its window: ``full`` (0) and ``window``, as a
        GQA model has them (``ops/attention.paged_prefill_attention``);
        ``latent`` for the latent family's and a latent kind's
        (``latent_prefill_attention``). A kind that keeps no pages walks
        none."""
        if self.spec.is_mla:
            return {"latent": 0}
        kinds = [kd for kd in self.spec.kinds if kd.paged]
        walks = {
            "window" if w else "full": w
            for w in sorted({kd.window for kd in kinds if not kd.latent})
        }
        if any(kd.latent for kd in kinds):
            walks["latent"] = 0
        return walks

    def _count_prefill_kv(self, rows: int, pages: int, starts, nts) -> None:
        """Add a dispatched walk over pages (a prefill, a pack of them or
        a speculative verify: ``rows`` padded rows a sequence against a
        table ``pages`` wide as the program was handed it; ``starts``,
        ``nts``: each sequence's first position and real rows, 0 for a
        padded member) to ``prefill_kv``: the blocks of pages the walk
        visits (``blocks_visited``: by the walk's own ``prefill_blocks`` a
        query tile, a pack running each tile to its longest member) and
        the blocks a walk of the whole table would (``blocks_table``), one
        layer's worth a layer kind. Their ratio is how far prefill
        attention follows the prompts. The latent family's tiling is that
        of the implementation the dispatch got: its kernel's tiles, each
        member its own blocks (a grid axis, not a ``vmap``), or the XLA
        walk's one tile of all the rows."""
        from dynamo_tpu.ops.attention import (
            latent_kernel_serves, latent_prefill_tiling, prefill_blocks,
            prefill_tiling,
        )

        page = self.config.page_size
        starts = np.asarray(starts, np.int32).reshape(-1, 1)
        nts = np.asarray(nts, np.int32).reshape(-1, 1)
        # members that continued a state or a tail: a chunk behind a
        # prompt's first
        resumed = int(((starts > 0) & (nts > 0)).sum())
        if self.kda:
            from dynamo_tpu.ops.attention import kda_prefill_blocks

            self.kda["prefill_blocks"] += kda_prefill_blocks(nts)
            self.kda["rows_resumed"] += resumed
        if self.ssd:
            from dynamo_tpu.ops.attention import ssd_prefill_chunks

            self.ssd["prefill_chunks"] += ssd_prefill_chunks(
                nts, self.spec.ssm_chunk)
            self.ssd["rows_resumed"] += resumed
        if self.recurrent_state:
            self.recurrent_state["prefill_chunks"] += int((nts > 0).sum())
            self.recurrent_state["rows_resumed"] += resumed
        if self.prefill:
            self.prefill["rows"] += int(nts.sum())
            self.prefill["cross_rows"] += int((nts > 0).sum())
        kv = self.prefill_kv
        for kind, window in self._prefill_walks.items():
            kernel = kind == "latent" and latent_kernel_serves(
                self._latent_pool(), self.mesh)
            if kind == "latent":
                tq, bp = latent_prefill_tiling(rows, pages, page, kernel)
                kv["dispatches.latent"] += 1
                kv["kernel_calls.latent"] += kernel
            else:
                tq, bp = prefill_tiling(rows, pages, page, window)
            tiles = np.arange(-(-rows // tq), dtype=np.int32)[None, :]
            _, count = prefill_blocks(starts, nts, tiles, tq, window, page, bp)
            kv[f"blocks_visited.{kind}"] += int(count.sum()) if kernel else (
                int(count.max(axis=0).sum()) * len(starts)
            )
            kv[f"blocks_table.{kind}"] += (
                len(starts) * tiles.size * -(-pages // bp)
            )

    def _latent_pool(self):
        """The pool of latent rows: the latent family's cache (the first
        of a double layer's two, which share a shape), or a latent kind's
        pool among the kinds'."""
        if self.spec.is_mla:
            from dynamo_tpu.models.mla import sub_pools

            return sub_pools(self.k_pages)[0]
        from dynamo_tpu.models.llama import latent_pool

        return latent_pool(self.spec, self.k_pages)

    # -- precompile (startup warmup) ---------------------------------------

    def precompile(self) -> dict[str, dict]:
        """Compile every serving-shape program BEFORE traffic so no
        request ever eats a compile (with the persistent cache enabled,
        a restarted worker loads most of these from disk): per-bucket
        single + packed prefill, the decode burst programs (full and
        short lengths), and the first-token sample widths. All
        warmup dispatches write only the trash page (zero block tables,
        inactive slots) against the LIVE pools, so device state is
        exactly as if the engine had served and finished requests.

        Must run before the step thread starts (the dispatches donate and
        reassign the live KV pools); workers call it before serve. Skipped
        under SPMD (followers would not replay the warmup descriptors).
        Returns ``{shape: {"secs": s, "compiles": n[, "error": e]}}`` and
        logs per-shape compile time (the worker startup contract)."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError(
                "precompile() must run before the engine starts serving"
            )
        if self.spmd is not None:
            log.info("precompile skipped: SPMD followers would not replay")
            return {}
        cfg = self.config
        report: dict[str, dict] = {}

        t_start = time.perf_counter()

        def timed(name: str, fn, ahead: tuple[float, int] = (0.0, 0)) -> None:
            c0, s0 = thread_compile_snapshot()
            t0 = time.perf_counter()
            try:
                if FAULTS.enabled:
                    # injectable slow/failing compile (site
                    # engine.compile): a delay models a cold cache /
                    # slow XLA; an error models a warmup miss — serving
                    # must still come up and eat the compile at first
                    # use instead
                    FAULTS.fire_sync("engine.compile")
                fn()
            except Exception as e:  # noqa: BLE001
                log.warning("precompile %s failed (%s); first request "
                            "pays this compile instead", name, e)
                report[name] = {
                    "secs": round(time.perf_counter() - t0 + ahead[0], 3),
                    "compiles": thread_compile_snapshot()[0] - c0 + ahead[1],
                    "error": str(e),
                }
                return
            dt = time.perf_counter() - t0
            c1, s1 = thread_compile_snapshot()
            report[name] = {
                "secs": round(dt + ahead[0], 3),
                "compiles": c1 - c0 + ahead[1],
            }
            if ahead[0]:
                # of ``secs``, lowering and compiling beside the others
                report[name]["ahead_secs"] = round(ahead[0], 3)
            log.info(
                "precompile %s: %.0f ms (%d compiles, %.0f ms in XLA)",
                name, dt * 1e3, c1 - c0, (s1 - s0) * 1e3,
            )

        # Device results that feed later programs (prefill logits into the
        # first-token sampler, burst samples into the feed glue) are kept
        # and fed on exactly as serving feeds them: under a mesh a program
        # compiled for a host-built argument is NOT the program for the
        # same argument committed to the mesh.
        first_logits: dict[int, jax.Array] = {}  # sample width -> [w, V]
        burst_out: dict[int, jax.Array] = {}  # burst length -> [B, n]

        # first-token sample widths: packed-dispatch fused samples (the
        # offered pack widths), the single-prompt program (1), and the
        # stacked admission batch (max_decode_slots) — here on host-built
        # logits, as the sync admission path feeds them; feed() below
        # warms the device-fed forms (one program on a single device).
        # They touch no pool, and a sampler compiles for 6-14 s a width on
        # a v5e (PR 34): a thread of their own warms them while this one
        # compiles the model's programs.
        B = cfg.max_decode_slots

        def sample_widths():
            for w in sorted({1, B, *self._prefill_shapes.values()}):
                def sample(w=w):
                    out = sample_tokens(
                        jnp.zeros((w, self.spec.vocab_size), jnp.float32),
                        jnp.zeros((w,), jnp.float32),
                        jnp.zeros((w,), jnp.int32),
                        jnp.ones((w,), jnp.float32),
                        jnp.zeros((w,), jnp.uint32),
                        jnp.zeros((w,), jnp.int32),
                    )
                    jax.block_until_ready(out)

                timed(f"sample[{w}]", sample)

        samplers = threading.Thread(
            target=sample_widths, name="precompile-samplers"
        )
        samplers.start()

        # the model's programs, in the order their warm-up dispatches run:
        # compiled ahead beside one another (_compile_ahead), dispatched
        # one after the other below (each donates the live pools)
        programs: list[tuple[str, Any]] = []

        # every prefill shape the engine offers (chunked prefill
        # re-enters through the same bucketed shapes)
        bt1 = jnp.zeros((cfg.max_pages_per_seq,), jnp.int32)
        for bucket, nb in self._prefill_shapes.items():
            def one_prefill(bucket=bucket):
                logits, self.k_pages, self.v_pages, _ = self.fam.prefill(
                    self.spec, self.params,
                    jnp.zeros((bucket,), jnp.int32), bt1,
                    jnp.asarray(0, jnp.int32),
                    self.k_pages, self.v_pages,
                    jnp.asarray(bucket, jnp.int32), mesh=self.mesh,
                )
                jax.block_until_ready(logits)
                if 1 not in first_logits:
                    # as _single_prefill_record hands it to the sampler,
                    # and as unsampled admissions stack it to slot width
                    first_logits[1] = logits[None, :]
                    first_logits.setdefault(B, jnp.stack([logits] * B))

            programs.append((f"prefill[{bucket}]", one_prefill))
            if self.fam.supports_packed_prefill and nb > 1:

                def packed(bucket=bucket, nb=nb):
                    logits, self.k_pages, self.v_pages, _ = (
                        self.fam.prefill_batch(
                            self.spec, self.params,
                            jnp.zeros((nb, bucket), jnp.int32),
                            jnp.zeros((nb, cfg.max_pages_per_seq), jnp.int32),
                            jnp.zeros((nb,), jnp.int32),
                            self.k_pages, self.v_pages,
                            jnp.zeros((nb,), jnp.int32), mesh=self.mesh,
                        )
                    )
                    jax.block_until_ready(logits)
                    first_logits.setdefault(nb, logits)
                    # as _logits_row slices a row for the sync and
                    # unsampled admission paths
                    jax.block_until_ready(logits[0])

                programs.append((f"prefill_packed[{nb}x{bucket}]", packed))

        # decode burst programs: every length _build_batch dispatches
        zB = jnp.zeros((B,), jnp.int32)
        fed0 = self._feed_array(np.zeros((B,), np.int32))
        for n in self._burst_lengths:
            def burst(n=n):
                out, self.k_pages, self.v_pages = self.fam.decode_steps(
                    self.spec, self.params, fed0,
                    jnp.zeros((B, cfg.max_pages_per_seq), jnp.int32),
                    jnp.ones((B,), jnp.int32),
                    self.k_pages, self.v_pages,
                    jnp.zeros((B,), bool),
                    jnp.zeros((B,), jnp.float32), zB,
                    jnp.ones((B,), jnp.float32),
                    jnp.zeros((B,), jnp.uint32), zB,
                    n_steps=n, n_logprobs=0, mesh=self.mesh,
                )
                burst_out[n] = jax.block_until_ready(out)

            programs.append((f"decode[{B}x{n}]", burst))

        # speculative-verify grid (spec mode): one program per
        # power-of-two row count at the static k+1 token width — the
        # exact shape set _spec_phase dispatches, so spec serving does
        # ZERO new compiles after warmup. num_tokens=0 rows write only
        # the trash page, like every other warmup dispatch.
        if self._spec_on:
            W = cfg.spec_k_max + 1
            widths = {1}
            w = 1
            while w < B:
                w *= 2
                widths.add(w)
            for nrows in sorted(widths):
                def verify(nrows=nrows, W=W):
                    out, self.k_pages, self.v_pages, _ = self.fam.verify(
                        self.spec, self.params,
                        jnp.zeros((nrows, W), jnp.int32),
                        jnp.zeros(
                            (nrows, cfg.max_pages_per_seq), jnp.int32
                        ),
                        jnp.zeros((nrows,), jnp.int32),
                        self.k_pages, self.v_pages,
                        jnp.zeros((nrows,), jnp.int32), mesh=self.mesh,
                    )
                    jax.block_until_ready(out)

                programs.append((f"verify[{nrows}x{W}]", verify))
                if self._guided is not None:
                    # guided x spec: the MASKED verify program is its own
                    # compiled shape per row tier — warm it too, or the
                    # first constrained greedy request on a spec worker
                    # eats the compile mid-serving
                    def verify_masked(nrows=nrows, W=W):
                        out, self.k_pages, self.v_pages, _ = (
                            self.fam.verify(
                                self.spec, self.params,
                                jnp.zeros((nrows, W), jnp.int32),
                                jnp.zeros(
                                    (nrows, cfg.max_pages_per_seq),
                                    jnp.int32,
                                ),
                                jnp.zeros((nrows,), jnp.int32),
                                self.k_pages, self.v_pages,
                                jnp.zeros((nrows,), jnp.int32),
                                mesh=self.mesh,
                                allowed=jnp.ones(
                                    (nrows, W, self.spec.vocab_size), bool
                                ),
                            )
                        )
                        jax.block_until_ready(out)

                    programs.append(
                        (f"verify_masked[{nrows}x{W}]", verify_masked))

        ahead = self._compile_ahead(programs)
        for name, fn in programs:
            timed(name, fn, ahead.get(name, (0.0, 0)))

        samplers.join()

        # the burst feed path, on the real device results: the first-token
        # sampler on prefill logits, then the feed glue — chain feed and
        # fed column per burst length, admission-wave feed per wave width
        def feed():
            z = jnp.zeros((B,), jnp.int32)
            mask = jnp.zeros((B,), bool)
            for n, out in burst_out.items():
                fed = _chain_feed(mask, _with_fed_column(fed0, out), fed0)
            for w, logits in first_logits.items():
                wave = sample_tokens(
                    logits, jnp.zeros((w,), jnp.float32),
                    jnp.zeros((w,), jnp.int32), jnp.ones((w,), jnp.float32),
                    jnp.zeros((w,), jnp.uint32), jnp.zeros((w,), jnp.int32),
                )
                fed = _wave_feed(mask, z, wave, fed)
            jax.block_until_ready(fed)

        timed("burst_feed", feed)

        # guided-decoding shapes (when this worker can serve them): the
        # masked admission sample and the masked single-step burst — the
        # exact programs a constrained slot dispatches, so the first
        # guided request eats no compile either
        if self._guided is not None:
            V = self.spec.vocab_size

            def masked_sample(w=B):
                out = sample_tokens_masked(
                    jnp.zeros((w, V), jnp.float32),
                    jnp.ones((w, V), bool),
                    jnp.zeros((w,), jnp.float32),
                    jnp.zeros((w,), jnp.int32),
                    jnp.ones((w,), jnp.float32),
                    jnp.zeros((w,), jnp.uint32),
                    jnp.zeros((w,), jnp.int32),
                )
                jax.block_until_ready(out)

            timed(f"sample_masked[{B}]", masked_sample)

            def masked_burst():
                out, self.k_pages, self.v_pages = self.fam.decode_steps(
                    self.spec, self.params, fed0,
                    jnp.zeros((B, cfg.max_pages_per_seq), jnp.int32),
                    jnp.ones((B,), jnp.int32),
                    self.k_pages, self.v_pages,
                    jnp.zeros((B,), bool),
                    jnp.zeros((B,), jnp.float32), zB,
                    jnp.ones((B,), jnp.float32),
                    jnp.zeros((B,), jnp.uint32), zB,
                    n_steps=1, n_logprobs=0, mesh=self.mesh,
                    allowed=jnp.ones((B, V), bool),
                )
                jax.block_until_ready(out)

            timed(f"decode_masked[{B}x1]", masked_burst)

        if self.fam.recurrent:
            def release():
                self._state_released.append(-1)
                self._flush_state_releases()
                jax.block_until_ready(self.k_pages)

            timed("release_state_rows", release)

        # by the clock: the shapes' own seconds overlap
        total = time.perf_counter() - t_start
        compiles = sum(r["compiles"] for r in report.values())
        misses = sum(1 for r in report.values() if "error" in r)
        log.info(
            "precompile done: %d shapes, %d compiles, %.1f s total%s",
            len(report), compiles, total,
            f" ({misses} MISSED — compiled at first use)" if misses else "",
        )
        # kept for launch_engine_worker's callers: a start-up that must
        # not serve with a refused shape fails on any "error" entry
        self.precompile_report = report
        return report

    def _compile_ahead(self, programs) -> dict[str, tuple[float, int]]:
        """Compile the model's programs beside one another, before their
        warm-up dispatches run one after the other. A dispatch donates the
        live pools, so the dispatches cannot overlap, and compiling inside
        them made a cold start the SUM of the programs' compile times
        (124 s of a 245 s set-up over four programs on a v5e, and the
        cache's loads one after the other when warm: PERF.md section 6,
        PR 53; ROADMAP.md S7 (e)). Each program is traced and lowered HERE,
        from the very call its dispatch makes (``lowered_calls``: tracing
        holds the interpreter, and what a trace leaves behind it leaves
        once), then compiled, or loaded from the persistent cache, on a
        thread of its own, at most four at a time (a compile holds
        gigabytes of the host's memory). ``jit``'s call path and
        ``.lower().compile()`` share their caches, so the dispatch that
        follows finds its program and compiles nothing. Returns ``{name:
        (seconds, compiles)}``; a program that does not lower or compile
        here is left to its dispatch, which reports why."""
        from dynamo_tpu.models.family import Lowered, lowered_calls

        done: dict[str, tuple[float, int]] = {}
        room = threading.Semaphore(4)

        def compile_one(name: str, lowered, t0: float) -> None:
            with room:
                c0 = thread_compile_snapshot()[0]
                try:
                    lowered.compile()
                except Exception as e:  # noqa: BLE001
                    log.debug("compile ahead of %s: %s", name, e)
                    return
                done[name] = (
                    time.perf_counter() - t0,
                    thread_compile_snapshot()[0] - c0,
                )

        threads = []
        for name, fn in programs:
            t0 = time.perf_counter()
            try:
                with lowered_calls(self.fam):
                    fn()
            except Lowered as e:
                th = threading.Thread(
                    target=compile_one, args=(name, e.lowered, t0),
                    name=f"precompile-{name}",
                )
                th.start()
                threads.append(th)
            except Exception as e:  # noqa: BLE001
                log.debug("lowering ahead of %s: %s", name, e)
        for th in threads:
            th.join()
        return done

    # -- events ------------------------------------------------------------

    def _on_store(self, sh: int, parent: int) -> None:
        if self.events is not None:
            self.events.block_stored(sh, parent)

    def _on_evict(self, shs: list[int]) -> None:
        if self.events is not None and shs:
            self.events.blocks_removed(shs)

    def _refresh_moe_counts(self) -> None:
        """Bring the expert layers' device-side counters to the host
        without waiting on the device: every 16th call takes the copy
        started 16 calls ago (landed long since) and starts the next.
        The copy is a program of its own behind whatever is queued, so
        it reads the counters as of this call; the pools' own leaf would
        be donated away by the next launch."""
        counts = self.fam.moe_counts(self.k_pages, self.v_pages)
        if counts is None or not counts.shape[-1]:
            return
        if self._metrics_publishes % 16 != 1:
            return
        if self._moe_counts_dev is not None:
            self.moe_counts = np.asarray(self._moe_counts_dev)
        self._moe_counts_dev = jnp.copy(counts)

    def _note_recurrent_gate(self, what: str) -> None:
        """Count a feature that was asked of a model with recurrent layers
        and is off for it (``page_offload``, ``page_transfer``,
        ``spec_decode``, ``ring_prefill``): its pages hold none of the
        state, so they cannot be moved, reused or rolled back alone."""
        from dynamo_tpu.ops.fallback import note_fallback

        note_fallback(
            f"recurrent_no_{what}",
            detail="a model with recurrent layers keeps state no page holds",
        )

    def _flush_state_releases(self) -> None:
        """Free the state rows of the pages released since the last call
        (a model with recurrent layers): a program of its own, queued
        behind the bursts that still name those rows and before the
        prefill that is about to claim one. One shape: the table's width
        rounded up to a power of two, padded with -1."""
        if not self._state_released:
            return
        width = 1 << max(6, (self.config.max_pages_per_seq - 1).bit_length())
        pages = []
        with contextlib.suppress(IndexError):
            while True:
                pages.append(self._state_released.popleft())
        for at in range(0, len(pages), width):
            chunk = np.full((width,), -1, np.int32)
            chunk[: len(pages[at: at + width])] = pages[at: at + width]
            self.k_pages, self.v_pages = self.fam.release_state_rows(
                self.k_pages, self.v_pages, jnp.asarray(chunk)
            )

    def _refresh_state_stats(self) -> None:
        """The state directory's counters to the host, as
        ``_refresh_moe_counts`` brings the experts': without waiting on
        the device. A row that went missing makes the run a degraded one:
        it joins the fallback series."""
        if not self.fam.recurrent or self._metrics_publishes % 16 != 1:
            return
        stats = self.fam.state_stats(self.k_pages, self.v_pages)
        if self._state_stats_dev is not None:
            before = self.state_stats
            self.state_stats = np.asarray(self._state_stats_dev)
            if self.state_stats[2] > (0 if before is None else before[2]):
                from dynamo_tpu.ops.fallback import note_fallback

                note_fallback(
                    "recurrent_state_row_missing",
                    detail="a sequence's state row was not where its "
                           "block table says: its output is wrong",
                )
        self._state_stats_dev = jnp.copy(stats)

    def state_counters(self) -> dict[str, int]:
        """A model with recurrent layers: the state rows held, those a
        live sequence owns now (a decode slot or the open chunked
        prefill), and the directory's device-side counters as of their
        last refresh (rows claimed, rows that were missing). Empty
        otherwise."""
        if not self.fam.recurrent:
            return {}
        stats = self.state_stats if self.state_stats is not None else (0, 0, 0)
        return {
            "rows": self.config.max_decode_slots,
            "rows_live": sum(s is not None for s in self._slots)
            + (self._partial is not None),
            "claims": int(stats[1]),
            "row_missing": int(stats[2]),
        }

    def moe_counters(self) -> dict[str, int]:
        """The counters as of the last refresh, summed over the expert
        layers: by phase (``prefill``, ``decode``) the assignments of
        real tokens to each held expert (``<phase>.expert.<i>``), their
        assignments in all (``<phase>.assignments``) and those that
        landed on an expert held here (``<phase>.assignments_held``: under
        group-limited routing, the tokens routed to this chip's group),
        the held experts
        touched, a layer a step (``<phase>.experts_touched``) and the
        phase's steps (``<phase>.steps``: prefill programs, decode model
        steps). A model with identity experts splits the assignments in
        two more: the picks that were identity experts
        (``<phase>.zero_picks``) and those that were FFN experts, held
        here or not (``<phase>.ffn_picks``). Empty where the cache keeps
        none."""
        if self.moe_counts is None:
            return {}
        c = self.moe_counts.astype(np.int64)
        n_held = self.spec.experts_here[0]
        out = {"layers": int((c[:, :, -1].sum(axis=1) > 0).sum())}
        for phase, name in enumerate(("prefill", "decode")):
            out[f"{name}.steps"] = int(c[:, phase, -1].max())
            out[f"{name}.experts_touched"] = int(c[:, phase, -2].sum())
            out[f"{name}.assignments"] = int(c[:, phase, -3].sum())
            out[f"{name}.assignments_held"] = int(c[:, phase, :n_held].sum())
            for i, n in enumerate(c[:, phase, :n_held].sum(axis=0)):
                out[f"{name}.expert.{i}"] = int(n)
            if self.spec.zero_experts:
                out[f"{name}.zero_picks"] = int(c[:, phase, n_held].sum())
                out[f"{name}.ffn_picks"] = int(c[:, phase, n_held + 1].sum())
        return out

    def _publish_metrics(self) -> None:
        self._metrics_publishes += 1
        self._refresh_moe_counts()
        self._refresh_state_stats()
        if self.metrics is not None:
            self.metrics.publish(
                ForwardPassMetrics(
                    active_kv_blocks=self.allocator.active_pages,
                    total_kv_blocks=self.allocator.num_pages - 1,
                    waiting_requests=self._waiting.qsize(),
                    running_requests=sum(s is not None for s in self._slots),
                    step_time_ms=self.step_time_ewma_ms,
                )
            )

    def _spmd_mark(self) -> int:
        """Publish-count watermark for scoping failures to actual sends."""
        return self.spmd.publish_count if self.spmd is not None else 0

    def _spmd_broken(self, reason: str, since: int | None = None) -> None:
        """A device dispatch failed AFTER its descriptor went out: the
        followers replayed a program the leader abandoned, so multi-host
        lockstep is gone — latch the plane broken (surfaced by is_dead)
        instead of deadlocking the next collective. With ``since`` (a
        _spmd_mark watermark), only latch if something was actually
        published after it — failures before any publish are recoverable
        and must NOT kill the worker."""
        if self.spmd is None:
            return
        if since is not None and self.spmd.publish_count == since:
            return
        self.spmd.mark_broken(reason)

    def _post(self, q: asyncio.Queue, item: Any) -> None:
        """Thread-safe queue put: compute threads must not touch asyncio
        primitives directly. Inside a profiled engine's stream.post span an
        item that carries tokens leaves marked (_PostSpan)."""
        if self._post_mark is not None and item and item.get("token_ids"):
            item[_POSTED] = self._post_mark
        race.release(q, "engine.out_q")
        if self._loop is None or threading.get_ident() == self._loop_thread:
            q.put_nowait(item)
        else:
            self._loop.call_soon_threadsafe(q.put_nowait, item)

    # -- public API --------------------------------------------------------

    async def start(self) -> "InferenceEngine":
        if self._thread is None or not self._thread.is_alive():
            self._loop = asyncio.get_running_loop()
            self._loop_thread = threading.get_ident()
            self._thread = threading.Thread(
                target=self._thread_loop, name="engine-step", daemon=True
            )
            race.fork(self._thread)
            self._thread.start()
            self.loop_probe.start()
        return self

    @property
    def is_dead(self) -> bool:
        """True when the step thread exited WITHOUT an orderly close —
        the watchdog signal (ref VllmEngineMonitor / EngineDeadError).
        A broken SPMD broadcast plane counts: once a descriptor publish
        is lost, followers are out of lockstep and the next multi-host
        collective would hang — surface it instead of deadlocking."""
        if self.spmd is not None and not self.spmd.healthy and not self._closed:
            return True
        return (
            self._thread is not None
            and not self._thread.is_alive()
            and not self._closed
        )

    def begin_drain(self, deadline_s: float | None = None) -> None:
        """Graceful-drain entry (worker SIGTERM path): refuse NEW requests
        with ServiceUnavailable — retryable, so the frontend's migration
        operator re-drives them on a live worker — while admitted work
        runs to completion. The step loop keeps running until close().
        ``deadline_s``: seconds until the drain force-cancels; refusals
        carry it as Retry-After so clients come back when this worker is
        actually gone (or its replacement is up), not at a constant."""
        self._draining = True
        if deadline_s is not None:
            self._drain_deadline = time.monotonic() + max(deadline_s, 0.0)
        self._wake.set()

    @property
    def draining(self) -> bool:
        return self._draining

    def _drain_retry_after(self) -> float:
        """Retry-After for draining refusals: the remaining drain window
        when known (clamped to [1, 60]), else the 1 s legacy hint."""
        if self._drain_deadline is None:
            return 1.0
        return min(max(self._drain_deadline - time.monotonic(), 1.0), 60.0)

    def _saturation_retry_after(self) -> float:
        """Retry-After for saturation bounces, derived from LIVE state:
        queue depth x recent mean step time / slot count estimates how
        long until this backlog drains a slot's worth of work. Clamped
        to [0.25, 30] so a cold engine (no step samples yet) still gives
        a sane hint."""
        depth = self._waiting.qsize()
        race.read("engine.step_times")
        samples = list(self.step_times)[-64:]
        mean_step = (sum(samples) / len(samples)) if samples else 0.05
        est = depth * mean_step / max(len(self._slots), 1)
        return min(max(est, 0.25), 30.0)

    def _request_tenancy(
        self, request: dict[str, Any], context: Context
    ) -> tuple[str, str]:
        """(tenant, priority) for one request: validated wire headers
        first (the frontend edge stamped them into Context.headers),
        request-dict fields as the direct-caller fallback."""
        from dynamo_tpu.runtime.context import PRIORITY_HEADER, TENANT_HEADER

        tenant, priority = tenancy_from_headers(context.headers)
        if TENANT_HEADER not in context.headers and request.get("tenant"):
            tenant = str(request["tenant"])
        if (
            PRIORITY_HEADER not in context.headers
            and request.get("priority") in ("interactive", "batch")
        ):
            priority = str(request["priority"])
        # cardinality bound: past the dynamic-tenant cap, fresh ids
        # collapse into the shared overflow tenant (engine/tenancy.py)
        return self._waiting.resolve(tenant), priority

    def inflight(self) -> int:
        """Admitted-but-unfinished work (drain-completion signal)."""
        return (
            sum(s is not None for s in self._slots)
            + self._waiting.qsize()
            + (1 if self._partial is not None else 0)
        )

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        await self.loop_probe.stop()
        if self.telemetry is not None:
            await self.telemetry.close()
        if self._thread is not None and self._thread.is_alive():
            # the thread exits at the next step boundary
            await asyncio.to_thread(self._thread.join, 10.0)
            if not self._thread.is_alive():
                race.join(self._thread)
        if self.offload is not None:
            # blocking join (may wait on an in-flight DMA) — keep it off
            # the event loop
            await asyncio.to_thread(self.offload.close)

    async def generate(
        self, request: dict[str, Any], context: Context
    ) -> AsyncIterator[dict[str, Any]]:
        """AsyncEngine surface: stream token deltas for one request."""
        if self._closed:
            # closed-engine race (worker deregistration): error loudly so
            # the frontend's migration op re-drives on a live worker —
            # enqueueing would hang the client (soak-found)
            yield {"token_ids": [], "finish_reason": "error",
                   "error": "engine closed"}
            return
        tenant, priority = self._request_tenancy(request, context)
        if self._draining:
            # SIGTERM drain: typed refusal rides the transport as a
            # retryable 503-mappable error (another worker may accept);
            # Retry-After prices the remaining drain window when known
            self.admission_rejects["draining"] += 1
            raise ServiceUnavailable(
                "worker draining", retry_after_s=self._drain_retry_after()
            )
        if (
            self.config.max_waiting
            and self._waiting.qsize() >= self.config.max_waiting
            and not self._waiting.sheddable_below(priority)
        ):
            # full queue and nothing outranked: bounce NOW, before any
            # expensive staging; with a sheddable lower-priority entry
            # present the enqueue-point check below does the shed
            self.admission_rejects["saturated"] += 1
            raise ServiceUnavailable(
                f"engine saturated ({self._waiting.qsize()} waiting)",
                retry_after_s=self._saturation_retry_after(),
            )
        if context.deadline_expired:
            self.admission_rejects["deadline"] += 1
            raise DeadlineExceeded(
                f"request {context.id} deadline passed before admission"
            )
        if FAULTS.enabled:
            try:
                await FAULTS.fire("engine.admit")
            except ConnectionError as e:
                # a 'drop' at admission = this worker vanished before
                # taking the request; keep the drop contract (retryable,
                # migration re-drives on another instance) rather than
                # surfacing a non-retryable 500
                raise ServiceUnavailable(f"injected admit drop: {e}") from e
        await self.start()
        # migration resume prompts arrive stamped with a token checksum;
        # a mismatch (bit flip in transit) raises IntegrityError — a
        # StreamError — so the migration operator re-drives from its
        # pristine copy instead of this engine prefilling poison
        verify_resume_tokens(request)
        token_ids = list(request.get("token_ids") or [])
        if not token_ids:
            yield {"token_ids": [], "finish_reason": "error",
                   "error": "empty token_ids"}
            return
        if request.get("embedding_request"):
            if not self.fam.supports_embeddings:
                yield {"token_ids": [], "finish_reason": "error",
                       "error": f"embeddings unsupported for {self.spec.name}"}
                return
            if self.spmd is not None:
                # embed_forward is not in the follower replay protocol
                yield {"token_ids": [], "finish_reason": "error",
                       "error": "embeddings unsupported on multi-host workers"}
                return
            # standalone forward (no KV pages touched): safe to dispatch
            # off the step loop; JAX serializes device execution
            try:
                emb = await asyncio.to_thread(self._embed, token_ids)
            except Exception as e:  # noqa: BLE001
                yield {"token_ids": [], "finish_reason": "error",
                       "error": f"embedding failed: {e}"}
                return
            yield {"token_ids": [], "embedding": emb,
                   "finish_reason": "stop"}
            return
        if len(token_ids) >= self.config.max_context:
            yield {"token_ids": [], "finish_reason": "error",
                   "error": f"prompt exceeds max context {self.config.max_context}"}
            return
        # token-bucket quota (engine/tenancy.py): charged with the
        # request's full token cost (prompt + decode budget) BEFORE any
        # staging. Over-quota is a typed, non-retryable bounce whose
        # Retry-After comes from bucket state — HTTP maps it to 429.
        # (Preemption resumes re-enter via the internal queue, never
        # here, so a paused stream is not double-charged.)
        cost = float(
            len(token_ids) + self._decode_budget(request, len(token_ids))
        )
        quota_retry = self._waiting.charge(tenant, cost)
        if quota_retry is not None:
            self.admission_rejects["over_quota"] += 1
            raise OverQuota(
                f"tenant {tenant!r} over token quota "
                f"(cost {cost:.0f} tokens)",
                retry_after_s=quota_retry,
            )
        if request.get("guided"):
            # compile (or LRU-fetch) the grammar BEFORE admission, off
            # the step thread: a bad grammar bounces here as a typed
            # invalid_request (-> HTTP 400) with zero slots or pages
            # touched, and a good one is a warm cache hit by the time
            # _make_slot builds the per-slot cursor.
            err = outcome = None
            if self._guided is None:
                outcome = "unavailable"
                err = (
                    "guided decoding unavailable on this worker "
                    "(guided_mode=off, multi-host SPMD, or no tokenizer "
                    "vocabulary)"
                )
            else:
                try:
                    with tracing.span(
                        "engine.guided_compile", request_id=context.id
                    ):
                        await asyncio.to_thread(
                            self._guided.compile, request["guided"]
                        )
                except Exception as e:  # noqa: BLE001
                    outcome = "compile_error"
                    err = f"guided grammar rejected: {e}"
            if err is not None:
                GUIDED_REQUESTS.labels(outcome=outcome).inc()
                # zero service rendered: the quota charge comes back
                self._waiting.refund(tenant, cost)
                yield {"token_ids": [], "finish_reason": "error",
                       "error": f"invalid_request: {err}"}
                return
        disagg = request.get("disagg") or {}
        if disagg.get("mode") == "decode" and disagg.get("kv_transfer"):
            # Stage the remote KV payload HERE (event loop, thread pool),
            # before admission: _step awaits the admission thread, so a
            # slow/hung transfer there would stall decode for every active
            # slot. The reference keeps NIXL transfers off the scheduling
            # path the same way (vllm/handlers.py kv_transfer_params flow).
            from dynamo_tpu.disagg.transfer import (
                pull_kv_blocks,
                release_kv_blocks,
            )

            kvp = {
                k: v for k, v in disagg["kv_transfer"].items()
                if k != "first_token"
            }
            if self._decode_budget(request, len(token_ids)) <= 1:
                # the remote-prefill token (already emitted by the handler)
                # was the whole budget; don't pull KV we'd never use —
                # and THIS engine rendered no service, so its charge
                # comes back (the prefill worker billed its own side)
                self._waiting.refund(tenant, cost)
                await asyncio.to_thread(release_kv_blocks, kvp)
                yield {"token_ids": [], "finish_reason": "length"}
                return
            try:
                # one span per KV staging attempt: the disagg hop is the
                # classic "why was THIS request slow" suspect, so its
                # duration (and failure) joins the request's trace
                with tracing.span("disagg.pull", request_id=context.id):
                    if not self.fam.supports_page_transfer:
                        self._note_recurrent_gate("page_transfer")
                        raise RuntimeError(
                            "transferred pages carry no recurrent state"
                        )
                    disagg["_staged_kv"] = await asyncio.to_thread(
                        lambda: pull_kv_blocks(kvp, mesh=self.mesh)
                    )
            except Exception as e:  # noqa: BLE001
                # transfer-plane failure (prefill worker died between
                # export and pull, link cut, injected disagg.pull fault):
                # fall back to a FULL LOCAL prefill instead of erroring
                # the stream — disagg stays strictly an optimization. The
                # handler already emitted the remote first token, so
                # continuity = prompt + first_token, budget shrunk by one
                # (mirrors _resume_from_remote's remaining=max_tokens-1).
                log.warning(
                    "kv transfer pull failed (%s); falling back to local "
                    "prefill for %s", e, context.id,
                )
                self.disagg_fallbacks += 1
                try:
                    # best-effort: unpin the exported pages on a still-
                    # alive prefill worker instead of waiting out the
                    # export TTL (the dead-worker case just fails again)
                    await asyncio.to_thread(release_kv_blocks, kvp)
                # dynalint: disable=DL003 -- best-effort release toward a
                # likely-dead worker; TTL reclaim is the backstop
                except Exception:  # noqa: BLE001
                    pass
                first = disagg["kv_transfer"].get("first_token")
                request = dict(request)
                request["disagg"] = None
                disagg = {}  # nothing staged/exported remains to release
                if first is not None:
                    token_ids = token_ids + [int(first)]
                    request["token_ids"] = token_ids
                    stop = dict(request.get("stop_conditions") or {})
                    if stop.get("max_tokens") is not None:
                        stop["max_tokens"] = max(
                            int(stop["max_tokens"]) - 1, 1
                        )
                    request["stop_conditions"] = stop
                if len(token_ids) >= self.config.max_context:
                    # zero service on this engine: refund the charge
                    self._waiting.refund(tenant, cost)
                    yield {"token_ids": [], "finish_reason": "error",
                           "error": f"prompt exceeds max context "
                                    f"{self.config.max_context}"}
                    return
        if self._closed:
            # re-check right before the enqueue with NO awaits in between
            # (close() flips the flag on this same event loop): a request
            # that parked in an await above (e.g. the disagg KV pull)
            # while the engine closed must error, not enqueue into a
            # queue no step thread will ever read
            self._waiting.refund(tenant, cost)
            yield {"token_ids": [], "finish_reason": "error",
                   "error": "engine closed"}
            return
        if (
            self.config.max_waiting
            and self._waiting.qsize() >= self.config.max_waiting
        ):
            # re-check at the enqueue: the awaits above (start, disagg KV
            # pull) let a burst of concurrent admissions pass the early
            # check together and blow past the bound. Shedding policy
            # (engine/tenancy.py): bounce the lowest-priority most-over-
            # quota NEWEST waiting entry in this request's favor when one
            # ranks below it — degradation by priority, not arrival order.
            victim = self._waiting.shed_victim(priority)
            if victim is not None:
                self.admission_rejects["shed"] += 1
                # zero service rendered: the victim's bucket charge
                # comes back (its client retries and is re-charged)
                self._refund_if_charged(victim)
                self._release_waiting_disagg(victim)
                FLIGHT.event(victim.context.id, "shed")
                self._post(
                    victim.out_q,
                    {"_shed": self._saturation_retry_after()},
                )
            else:
                if disagg.get("mode") == "decode" and disagg.get("kv_transfer"):
                    # the bounce must not strand the pulled payload or leave
                    # the prefill worker's exported pages pinned to TTL
                    self._drop_staged_kv(request)
                    from dynamo_tpu.disagg.transfer import release_kv_blocks

                    kvp = {
                        k: v for k, v in disagg["kv_transfer"].items()
                        if k != "first_token"
                    }
                    try:
                        await asyncio.to_thread(release_kv_blocks, kvp)
                    # dynalint: disable=DL003 -- best-effort unpin before the
                    # saturation bounce; TTL reclaim is the backstop
                    except Exception:  # noqa: BLE001
                        pass
                self.admission_rejects["saturated"] += 1
                self._waiting.refund(tenant, cost)
                raise ServiceUnavailable(
                    f"engine saturated ({self._waiting.qsize()} waiting)",
                    retry_after_s=self._saturation_retry_after(),
                )
        # flight-recorder timeline + worker-side trace identity: the
        # caller's span (bound by the transport, or live in-context for
        # in-proc calls) parents this request's worker.request span; the
        # step thread records lifecycle events against the timeline and
        # the spans are derived + emitted at finish (runtime/flight.py)
        caller_tc = tracing.current_trace() or tracing.parse_traceparent(
            context.headers.get(tracing.TRACEPARENT)
        )
        wr_tc = caller_tc.child() if caller_tc else tracing.new_trace()
        FLIGHT.start(
            context.id, trace=wr_tc,
            parent_span_id=caller_tc.span_id if caller_tc else None,
            model=self.spec.name, prompt_tokens=len(token_ids),
        )
        out_q: asyncio.Queue = asyncio.Queue()
        self._waiting.put_nowait(
            _Waiting(
                request, context, out_q,
                tenant=tenant, priority=priority, cost=cost, charged=True,
            )
        )
        self._wake.set()
        deadline_hit = False
        finish_reason: str | None = None
        finish_error: str | None = None
        n_generated = 0
        rid = 0  # the stream's running number (_stream_take), profiled
        try:
            while True:
                # after the deadline every wait is bounded (2s per item):
                # a stuck step must not turn a deadline into a hang (the
                # Orca stuck-request-stalls-the-batch failure mode)
                remaining = 2.0 if deadline_hit else context.remaining_s()
                if remaining is None:
                    item = await out_q.get()
                    race.acquire(out_q, "engine.out_q")
                else:
                    try:
                        item = await asyncio.wait_for(out_q.get(), remaining)
                        race.acquire(out_q, "engine.out_q")
                    except asyncio.TimeoutError:
                        if deadline_hit:
                            finish_reason = "cancelled"
                            finish_error = "deadline exceeded"
                            yield {"token_ids": [],
                                   "finish_reason": "cancelled",
                                   "error": "deadline exceeded"}
                            return
                        # end-to-end deadline passed mid-generation: stop
                        # the slot (the step loop finishes it as
                        # 'cancelled')
                        deadline_hit = True
                        context.stop_generating()
                        self._wake.set()
                        continue
                if item is None:
                    return
                if "_shed" in item:
                    # this request was shed from the waiting queue in a
                    # higher-priority arrival's favor: surface it as the
                    # retryable typed refusal (another worker may take
                    # it; the frontend maps exhaustion to 503)
                    finish_reason = "shed"
                    raise ServiceUnavailable(
                        "shed under overload (outranked while waiting)",
                        retry_after_s=float(item["_shed"]),
                    )
                toks = item.get("token_ids")
                if toks:
                    if n_generated == 0:
                        FLIGHT.event(context.id, "first_delta")
                    if self._profiling:
                        rid = self._stream_take(
                            item, context.id, rid, n_generated == 0)
                    n_generated += len(toks)
                # record BEFORE the yield: downstream operators stop
                # iterating once they see the finish item, so this
                # generator may never be resumed past it (it gets a
                # GeneratorExit at the yield instead)
                if item.get("finish_reason") is not None:
                    finish_reason = item["finish_reason"]
                    finish_error = item.get("error")
                yield item
                if finish_reason is not None:
                    return
        finally:
            tl = FLIGHT.finish(
                context.id,
                finish_reason or "abandoned",  # consumer broke the stream
                error=finish_error,
                generated=n_generated,
            )
            if tl is not None:
                emit_request_spans(tl)
                if self._profiling:
                    self._prof_requests_add(tl)

    # -- step loop ---------------------------------------------------------

    def _thread_loop(self) -> None:
        """The step thread: owns the device, never touches the event loop
        except via thread-safe _post. Blocking waits are fine here."""
        while not self._closed:
            try:
                step_mark = self._spmd_mark()
                if FAULTS.enabled and (
                    self._partial is not None
                    or not self._waiting.empty()
                    or any(s is not None for s in self._slots)
                ):
                    # engine.step error lands INSIDE this try: the fail-
                    # every-in-flight-then-keep-serving recovery below is
                    # exactly what the fault exercises; delay = stalled
                    # step. Idle cycles don't fire: a device step only
                    # happens when there is work, and an idle trip would
                    # silently consume limit-based specs (xN) before any
                    # request is in flight.
                    FAULTS.fire_sync("engine.step")
                if self._profiling:
                    # once a cycle, the clock FLIGHT and the clients use
                    # beside the profiler's: a trace reader fits the
                    # offset from these
                    with jax.profiler.TraceAnnotation(
                        "engine.clock", mono_ns=time.monotonic_ns()
                    ):
                        pass
                step_t0 = time.perf_counter()
                did_work = self._step()
                if did_work:
                    # telemetry feed: work cycles only (idle polls would
                    # drown the latency histogram in wake-timeout noise)
                    dt = time.perf_counter() - step_t0
                    race.write("engine.step_times")
                    self.step_times.append(dt)
                    self.step_time_ewma_ms = (
                        dt * 1000.0 if self.step_time_ewma_ms == 0.0
                        else 0.8 * self.step_time_ewma_ms + 0.2 * dt * 1000.0
                    )
                if not did_work:
                    self._wake.clear()
                    if (
                        self._waiting.empty()
                        and not any(self._slots)
                        and self._partial is None
                    ):
                        with self._phase("idle"):
                            self._wake.wait()
                    else:
                        with self._phase("idle"):
                            self._wake.wait(_STEP_IDLE_SLEEP_S)
            except Exception:  # noqa: BLE001
                # fail every in-flight request, then KEEP SERVING: one bad
                # step must not brick the worker
                log.exception("engine step failed; failing in-flight requests")
                self._spmd_broken(
                    "step failed after descriptors published", since=step_mark
                )
                # queued offloads may reference pages about to be released
                self._pending_offload.clear()
                self._pipeline = []  # discard in-flight bursts
                self._admit_waves.clear()  # slots error out in the sweep
                if self._partial is not None:
                    p, self._partial = self._partial, None
                    self.allocator.release(p.sp.pages)
                    self._post(
                        p.waiting.out_q,
                        {"token_ids": [], "finish_reason": "error",
                         "error": "engine step failure"},
                    )
                for i, slot in enumerate(self._slots):
                    if slot is not None:
                        self._finish(i, slot, "error", error="engine step failure")
                for w in self._waiting.drain():
                    self._refund_if_charged(w)
                    self._drop_staged_kv(w.request)
                    self._post(
                        w.out_q,
                        {"token_ids": [], "finish_reason": "error",
                         "error": "engine step failure"},
                    )
                # dynalint: disable=DL001 -- step-thread-only backoff after
                # a failed step; _thread_loop never runs on the event loop
                time.sleep(0.05)
        # orderly exit: land any in-flight burst and admission wave so
        # streaming clients get their final items instead of hanging
        try:
            self._flush_pipeline()
            self._materialize_waves(force=True)
        except Exception:  # noqa: BLE001
            log.exception("final flush on close failed")
        # ... then FAIL whatever is still live. A request that raced the
        # close into _waiting (or a slot mid-decode) would otherwise hang
        # its client forever — soak-found (tests/test_soak.py); the
        # frontend's migration op re-drives errored streams on another
        # worker, so erroring here is the recoverable path.
        try:
            if self._partial is not None:
                p, self._partial = self._partial, None
                self.allocator.release(p.sp.pages)
                self._post(
                    p.waiting.out_q,
                    {"token_ids": [], "finish_reason": "error",
                     "error": "engine closed"},
                )
            for i, slot in enumerate(self._slots):
                if slot is not None:
                    self._finish(i, slot, "error", error="engine closed")
            for w in self._waiting.drain():
                self._drop_staged_kv(w.request)
                self._post(
                    w.out_q,
                    {"token_ids": [], "finish_reason": "error",
                     "error": "engine closed"},
                )
        except Exception:  # noqa: BLE001
            log.exception("final drain on close failed")

    def request_clear_cache(self) -> None:
        """Admin: drop every inactive prefix-cache page (ref the HTTP
        service's clear_kv_blocks route + block-manager controller). The
        flag is honored on the step loop — the allocator's owner — so no
        locking against in-flight decode."""
        self._clear_cache_requested = True
        self._wake.set()

    def _step(self) -> bool:
        did = False
        if self.spmd is not None and self.spmd.sync_pending:
            # follower rejoin: quiesce at this step boundary (land every
            # in-flight burst and admission wave so the KV cache exactly
            # reflects the descriptors published so far), then hand the
            # rejoining follower a snapshot of every used page. Lockstep
            # resumes from the next descriptor (parallel/spmd.py).
            with self._phase("spmd_sync"):
                self._flush_pipeline()
                self._materialize_waves(force=True)
                self.spmd.serve_sync(self._spmd_sync_state())
            did = True
        if self._admit_waves:
            # land admission waves LAZILY: each once its device value is
            # ready (the d2h then costs just the residual RTT), or after
            # a bounded age so first tokens never stall forever. Blocking
            # the step thread on a download still queued behind device
            # work would serialize the whole pipeline.
            with self._phase("materialize"):
                did |= self._materialize_waves()
        if self._pipeline:
            # cancels and admin cache ops need exact slot state: land the
            # in-flight burst first. Plain ADMISSIONS do not: the device
            # stream is in-order (prefills enqueue behind the burst), page
            # eviction only touches refcount-0 pages (active slots hold
            # refs), a known-free slot stays free until burst processing,
            # and _build_batch/_process_burst guard by active mask +
            # request id — so admitting without a flush keeps the decode
            # pipeline deep instead of paying a host sync per admission
            # wave. The same four facts cover an admission made while the
            # queued burst is held (_hold_queued_burst): it differs only
            # in WHEN the next burst is launched, after the prefill and
            # not before it, so the prefill stands directly behind the
            # running burst; that burst's active mask was built before
            # the slot existed, and the held burst, built after, takes
            # the slot's first token through _wave_feed like any first
            # burst. An open chunked prefill needs no flush either: its next
            # chunk is launched behind the running burst like any other
            # admission's prefill, and the same four facts cover it.
            # (1) Nothing can take its slot: _admit_phase and
            # _eager_readmit admit nothing while _partial is set, so the
            # index stays None, and a slot that a finishing stream frees
            # meanwhile stays free until the partial closes. (2) Its
            # pages are referenced from _acquire_prompt_pages until a
            # slot or a release takes them over, so nothing evicts them
            # under a running burst. (3) A cancel of the chunked request
            # (_advance_partial) releases those pages with bursts in
            # flight: no burst's block table names them, the chunks that
            # wrote them were launched earlier, and whoever is handed
            # them next writes them by a later program of the one stream.
            # (4) The slot installed at the last chunk is absent from the
            # in-flight burst's active mask (the index was None when that
            # burst was built) and _process_burst guards by request id;
            # its first token reaches the next burst through _wave_feed.
            # A failed step and close() drop _partial and the pipeline
            # together (_thread_loop).
            stopped = any(
                s is not None and s.context.is_stopped for s in self._slots
            )
            if stopped or self._clear_cache_requested:
                with self._phase("flush"):
                    self._flush_pipeline()
                did = True
        if self._clear_cache_requested:
            self._clear_cache_requested = False
            n = self.allocator.clear_cache()
            log.info("admin clear_kv_blocks: evicted %d cached pages", n)
            self._publish_metrics()
            did = True
        # 1) advance an in-flight chunked prefill (one chunk a cycle,
        # queued behind the burst in flight), or admit waiting requests
        # up to a per-step token budget (ref: vLLM max_num_batched_tokens
        # scheduling — many short prompts enter in ONE step instead of
        # serializing one admission behind every decode step); decode still
        # runs below, so prefills steal at most a budget's worth of device
        # time per step
        # A cycle that advances a partial admits nothing beside its chunk,
        # the last chunk's cycle included (_eager_readmit): one chunk a
        # cycle and admissions in the cycles between partials, the cadence
        # the closed loops' occupancy and time per token rest on.
        self._chunk_cycle = self._partial is not None
        if self._chunk_cycle:
            with self._phase("advance_partial"):
                self._advance_partial_safe()
            did = True
            self._publish_metrics()
        else:
            did |= self._admit_phase()

        # 1.5) speculative verify over spec-managed slots (engine/spec.py):
        # each one lands 1..k+1 tokens in ONE packed short-prefill
        # dispatch; non-spec slots still take the decode burst below
        if self._spec_on:
            did |= self._spec_phase()

        # 2) one decode step over active slots. _decode_step reports
        # whether it actually dispatched/processed anything: an
        # all-stalled batch (every slot waiting on pages) must NOT spin
        # this loop hot — it would burn a core AND exhaust the
        # MAX_STALL patience budget in ~0.2s instead of seconds, erroring
        # page-stalled streams preemption could still save
        if any(s is not None for s in self._slots):
            did |= self._decode_step()
        elif self._pipeline:
            # every participant finished early (e.g. lazy-materialized
            # first tokens exhausting 1-token budgets): drain stale bursts
            self._flush_pipeline()
            did = True
        return did

    def _admit_phase(self) -> bool:
        """Admit waiting requests into free slots, up to a per-step token
        budget (ref: vLLM max_num_batched_tokens scheduling — many short
        prompts enter in ONE step instead of serializing one admission
        behind every decode step). Shared by the normal step phase and the
        eager re-admission pass (_eager_readmit). Returns True when any
        waiting entry was handled.

        The budget exists to bound how long prefills stall RUNNING decode
        streams — but it must not serialize WARM re-admissions: at >= half
        occupancy the queue is closed-loop churn replacing just-finished
        slots, each admission un-idles a slot immediately, and the total
        prefill work is bounded by the free-slot count anyway, so the
        budget check is skipped there (the r4 0.49 serving ceiling was
        exactly a 16-prompt budget against a 32-prompt arrival rate).

        On a COLD batch (nothing decoding) the budget only serializes
        admissions across steps and inflates TTFT — admit up to HALF the
        slots in one step instead. The half cap is a convoy breaker:
        admitting a whole cold wave at once locks closed-loop clients
        into lockstep (every request starts, decodes, and finishes
        together, so tokens clump at wave boundaries and throughput
        halves — measured as the 1.8k-tok/s attractor in the r5 ladder);
        two staggered cohorts interleave their prefills and decode
        bursts instead."""
        t_pass = self._clock()
        budget = self.config.max_prefill_tokens_per_step
        n_active = sum(s is not None for s in self._slots)
        decoding = n_active > 0
        warm = n_active * 2 >= len(self._slots)
        cold_cap = max(1, (len(self._slots) + 1) // 2)
        n_admitted = 0
        admitted = False
        did = False
        pending: list[tuple] = []
        preps: list[dict] = []
        reserved: set[int] = set()
        with self._phase("admit_loop"):
            while self._partial is None:
                free_idx = next(
                    (
                        i
                        for i, s in enumerate(self._slots)
                        if s is None and i not in reserved
                    ),
                    None,
                )
                if self._holding and self._head_needs_sync_admission():
                    # its admission reads logits on the host, behind the
                    # running burst and past the hold's deadline: it ends
                    # the hold and is the next cycle's, as without one
                    break
                if free_idx is None and not self._waiting.empty():
                    # no free slot for a waiting INTERACTIVE request: pause
                    # an over-quota batch stream instead of making the
                    # interactive user wait out the batch tenant's backlog
                    free_idx = self._preempt_for_admission(reserved)
                if free_idx is None or self._waiting.empty():
                    break
                cost = len(
                    self._peek_waiting_tokens() or ()
                ) or 1
                cost = min(cost, self._prefill_chunk_max())
                if admitted and cost > budget and decoding and not warm:
                    break  # first admission always proceeds
                if not decoding and n_admitted >= cold_cap:
                    break  # stagger the cold wave (convoy breaker)
                try:
                    waiting = self._waiting.get_nowait()
                except queue.Empty:
                    # a concurrent shed (event loop) emptied the queue
                    # between the check and the dequeue
                    break
                FLIGHT.event(waiting.context.id, "admit")
                if waiting.context.is_stopped:
                    self._drop_staged_kv(waiting.request)
                    self._post(
                        waiting.out_q,
                        {"token_ids": [], "finish_reason": "cancelled"},
                    )
                else:
                    out = self._prefill_safe(free_idx, waiting)
                    if out is _REQUEUED:
                        # page backpressure: the entry went back to its
                        # lane; nothing else can admit this pass either
                        # (the pool is the shared constraint) — retry next
                        # step. NOT counted as work: when the whole engine
                        # is page-stalled the loop must pace on the idle
                        # wait, not hot-spin OutOfPages retries.
                        break
                    if isinstance(out, dict):
                        preps.append(out)
                        reserved.add(free_idx)
                    elif out is not None:
                        pending.append(out)
                        reserved.add(free_idx)
                    budget -= cost
                    admitted = True
                    n_admitted += 1
                did = True
        # packed prefill: all same-bucket preps in ONE dispatch each
        with self._phase("packed_prefill"):
            pending.extend(self._run_packed_prefills(preps))
        if pending:
            with self._phase("complete_admissions"):
                self._complete_admissions(pending)
        if n_admitted:
            # what a pass that admits costs this thread: a share of the
            # guard of a hold (_hold_deadline), from every pass, so that
            # the guard follows the traffic whether or not holds begin
            self._admit_secs.append(self._clock() - t_pass)
            self.burst_hold["admissions"] += n_admitted
            if self._holding:
                self.burst_hold["admissions_held"] += n_admitted
        if did:
            self._publish_metrics()
        return did

    def _eager_readmit(self, freed: int) -> None:
        """Fill slots freed by the burst that just processed WITHIN the
        same step cycle, instead of leaving them idle until the next
        _step's admission phase — at serving burst lengths one skipped
        admission pass costs a full burst of slot idleness (~200 ms at
        burst 24, the arithmetic behind the r5 TTFT p50 of 733 ms for a
        128-token prefill).

        When the waiting queue is momentarily empty right after a finish,
        the closed-loop client's NEXT request is usually already crossing
        the event loop (finish item -> client resubmit -> generate
        enqueue); a bounded wait on the wake event catches it while the
        in-flight burst still has a full burst of device execution ahead,
        so the wait is hidden. Control signals (close, cancel, admin ops)
        are level-checked flags re-read every step, so clearing the wake
        event here delays them by at most _READMIT_WAIT_S."""
        if (
            freed <= 0
            or self._partial is not None
            # the cycle that closed a partial: its burst is read here now
            # that no flush lands it first, and an admission pass behind
            # the last chunk would put two cycles' prefill into one
            or self._chunk_cycle
            or self._closed
        ):
            return
        if self._waiting.empty() and self._pipeline:
            # only wait while a dispatched burst is still executing on
            # device (the wait hides behind it); with no burst in flight
            # — non-pipelined mode, or the drain branch just emptied the
            # pipeline — a timeout here would be dead step-thread time
            # added to every open-loop finish
            with self._phase("readmit_wait"):
                self._wake.clear()
                self._wake.wait(_READMIT_WAIT_S)
        if self._waiting.empty():
            return
        with self._phase("eager_readmit"):
            if self._admit_phase():
                self.eager_readmits += 1

    # -- priority preemption (runs in thread) ------------------------------

    def _preempt_for_admission(self, reserved: set[int]) -> int | None:
        """Slot-pressure preemption: the head of the waiting queue is
        interactive and no slot is free — pause a batch stream and hand
        its slot to the admission loop. Returns the freed index, or
        None (no eligible victim / preemption off / head not
        interactive)."""
        if not self.config.preemption:
            return None
        head = self._waiting.peek()
        if head is None or head.priority != "interactive":
            return None
        return self._preempt_batch_slot(
            reason="interactive_admission", reserved=reserved
        )

    def _victim_slot(self) -> tuple[int, _Slot] | None:
        """Preemption victim policy: batch-class slots only, over-quota
        tenants first, newest admission first (the oldest batch stream
        keeps its progress). Slots whose resume would not be a plain
        text re-prefill (guided/multimodal/disagg) and slots with their
        first token still in flight are not eligible."""
        best: tuple[tuple[int, int], int, _Slot] | None = None
        for i, slot in enumerate(self._slots):
            if slot is None or slot.priority != "batch":
                continue
            if slot.first_pending or slot.context.is_stopped:
                continue
            if slot.request is None or slot.remaining < 1:
                continue
            req = slot.request
            if req.get("guided") or req.get("multimodal") or req.get("disagg"):
                continue
            over = self._waiting.tenant_over_quota(slot.tenant)
            key = (0 if over else 1, -slot.admitted_seq)
            if best is None or key < best[0]:
                best = (key, i, slot)
        if best is None:
            return None
        return best[1], best[2]

    def _preempt_batch_slot(
        self, *, reason: str, reserved: set[int] | None = None,
        free_slot_ok: bool = True,
    ) -> int | None:
        """Pause one batch stream to make room (slots AND pages):

        1. fire the ``engine.preempt`` fault site (an injected error
           skips the preemption — serving degrades to waiting, never
           breaks);
        2. flush the decode pipeline + land admission waves so slot
           state is exact (in-flight bursts reference the victim's
           pages);
        3. seal the victim's complete blocks and force-offload them
           through the KVBM G1->G2 host-tier path (depth filter
           bypassed: the resume must be able to onboard even after G1
           eviction);
        4. release pages + slot, and re-enqueue ``prompt + generated``
           with the shrunk budget as a batch-lane waiting entry — the
           client stream pauses, then resumes bit-identically (greedy)
           through the normal prefix-cache/KVBM admission path, exactly
           the migration-resume continuity contract.

        Returns the freed slot index (also when the flush alone freed
        one — then nobody pays), or None."""
        victim = self._victim_slot()
        if victim is None:
            return None
        if FAULTS.enabled:
            try:
                FAULTS.fire_sync("engine.preempt")
            except Exception as e:  # noqa: BLE001 - injected failure
                log.warning(
                    "engine.preempt fault: skipping preemption (%s)", e
                )
                FLIGHT.event(
                    victim[1].context.id, "fault", site="engine.preempt"
                )
                return None
        with self._phase("preempt"):
            self._flush_pipeline()
            self._materialize_waves(force=True)
            if free_slot_ok:
                # slot-pressure callers are satisfied by ANY free slot
                # the flush produced. PAGE-pressure callers are not
                # (free_slot_ok=False): the admitting request's own
                # still-empty slot would match here and the preemption
                # would silently no-op without freeing a single page.
                free_idx = next(
                    (
                        i for i, s in enumerate(self._slots)
                        if s is None and i not in (reserved or ())
                    ),
                    None,
                )
                if free_idx is not None:
                    # the flush landed a finish: a slot freed itself
                    return free_idx
            i, slot = victim
            if self._slots[i] is not slot or slot.context.is_stopped:
                return None  # victim finished/cancelled during the flush
            self._maybe_seal(slot)
            if self.kvbm is not None and self.offload is not None:
                queued = {(s, p) for s, p, _b in self._pending_offload}
                for bi, (pg, h) in enumerate(
                    zip(slot.pages.pages, slot.pages.hashes)
                ):
                    if h is not None and (h, pg) not in queued:
                        self._pending_offload.append((h, pg, bi))
            self._drain_offload()
            resume = self._build_resume_request(slot)
            FLIGHT.event(
                slot.context.id, "preempt",
                generated=slot.generated, reason=reason,
            )
            self.preemptions[reason] = self.preemptions.get(reason, 0) + 1
            pages, slot.pages.pages = slot.pages.pages, []
            self.allocator.release(pages)
            self._slots[i] = None
            self._waiting.put_nowait(_Waiting(
                resume, slot.context, slot.out_q,
                tenant=slot.tenant, priority=slot.priority,
                cost=float(len(resume["token_ids"]) + slot.remaining),
            ))
            self._publish_metrics()
            log.info(
                "preempted %s (tenant=%s, %d generated) for %s",
                slot.request_id, slot.tenant, slot.generated, reason,
            )
            return i

    @staticmethod
    def _build_resume_request(slot: _Slot) -> dict[str, Any]:
        """Resume request for a preempted stream: prompt + everything
        already streamed becomes the new prompt (sealed blocks rehit the
        prefix cache / KVBM tiers; only the unsealed tail re-prefills),
        the decode budget shrinks to what was left, and the sampling
        seed is pinned so the slot's RNG identity survives the pause."""
        req = dict(slot.request or {})
        req["token_ids"] = [int(t) for t in slot.seq.tokens()]
        stop = dict(req.get("stop_conditions") or {})
        stop["max_tokens"] = max(int(slot.remaining), 1)
        if stop.get("min_tokens"):
            stop["min_tokens"] = max(
                int(stop["min_tokens"]) - slot.generated, 0
            )
        req["stop_conditions"] = stop
        sampling = dict(req.get("sampling") or {})
        sampling["seed"] = slot.sample_seed
        req["sampling"] = sampling
        req["disagg"] = None
        return req

    def _spmd_sync_state(self) -> list[tuple]:
        """Quiesced KV snapshot for a rejoining follower, as a list of
        ``(page_ids, k, v)`` numpy chunks. Chunked at EXTRACTION, not
        just on the wire: materializing a multi-GB cache to host in one
        asarray would double host RAM and stall the step thread for the
        whole transfer — each chunk bounds the host copy to the wire
        codec's chunk budget. Params are not shipped — engine shells
        init them deterministically from the same seed/checkpoint."""
        from dynamo_tpu.parallel.spmd import SYNC_CHUNK_BYTES

        ids = np.asarray(self.allocator.used_page_ids(), np.int32)
        if ids.size == 0:
            return []
        cache_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree.leaves((self.k_pages, self.v_pages))
        )
        per_page = max(1, cache_bytes // max(1, self.config.num_pages + 1))
        step = max(1, int(SYNC_CHUNK_BYTES // per_page))
        chunks: list[tuple] = []
        for i0 in range(0, int(ids.size), step):
            sub = ids[i0: i0 + step]
            # pad to a power-of-two width by repeating the last id: one
            # compiled extract shape per size tier, not per used-page
            # count (each fresh jit shape costs seconds on TPU; the
            # duplicate rows re-insert identical content harmlessly)
            bucket = 1 << max(0, int(sub.size) - 1).bit_length()
            padded = np.concatenate(
                [sub, np.full((bucket - sub.size,), sub[-1], np.int32)]
            )
            kb, vb = self.fam.extract_pages(
                self.k_pages, self.v_pages, jnp.asarray(padded)
            )
            chunks.append((padded, np.asarray(kb), np.asarray(vb)))
        return chunks

    def _peek_waiting_tokens(self) -> list | None:
        """Prompt tokens of the next waiting request without dequeuing (the
        step thread is the only consumer, so the head is stable)."""
        head = self._waiting.peek()
        return None if head is None else head.request.get("token_ids")

    def _head_needs_sync_admission(self) -> bool:
        head = self._waiting.peek()
        return head is not None and self._needs_sync_admission(head.request)

    def _refund_if_charged(self, waiting: _Waiting) -> None:
        """Credit back a charged entry's quota when it is bounced with
        ZERO service (admission page-pressure give-up, prefill failure):
        a tenant must not burn bucket on requests it was never served —
        without this, page-pressure episodes decay retryable errors
        into 429s for metered tenants."""
        if getattr(waiting, "charged", False):
            waiting.charged = False  # at most one refund per entry
            self._waiting.refund(waiting.tenant, waiting.cost)

    def _release_waiting_disagg(self, waiting: _Waiting) -> None:
        """Shed-victim cleanup (event-loop side): drop the staged KV
        host copy AND best-effort unpin the prefill worker's exported
        pages — the same must-not-pin-to-TTL contract the saturation
        bounce path keeps for the incoming request."""
        disagg = waiting.request.get("disagg") or {}
        self._drop_staged_kv(waiting.request)
        kvt = disagg.get("kv_transfer")
        if disagg.get("mode") == "decode" and kvt:
            from dynamo_tpu.disagg.transfer import release_kv_blocks
            from dynamo_tpu.runtime.context import spawn

            kvp = {k: v for k, v in kvt.items() if k != "first_token"}

            async def _release() -> None:
                try:
                    await asyncio.to_thread(release_kv_blocks, kvp)
                except Exception as e:  # noqa: BLE001 - TTL backstop
                    log.debug("shed kv release failed (%s)", e)

            spawn(_release(), name="shed-kv-release")

    @staticmethod
    def _drop_staged_kv(request: dict[str, Any]) -> None:
        """Free a pre-staged disagg KV payload for a request that will never
        be admitted (cancel / step-loop failure): the handler keeps the
        request dict alive for the stream's lifetime, so the multi-MB host
        copy must be popped here, not left for GC."""
        disagg = request.get("disagg")
        if disagg:
            disagg.pop("_staged_kv", None)

    # -- prefill (runs in thread) ------------------------------------------

    def _prefill_safe(
        self, slot_idx: int, waiting: _Waiting
    ) -> tuple | dict | None:
        """Per-request error isolation: a bad request must not kill the loop.

        Returns a prep dict (forward deferred to _run_packed_prefills), a
        pending-admission record (ring path: forward already ran), the
        ``_REQUEUED`` sentinel (OutOfPages backpressure: the entry went
        back to its lane, the admission pass should stop), or None when
        handled fully (disagg resume, chunked start, error)."""
        try:
            disagg = waiting.request.get("disagg") or {}
            if disagg.get("mode") == "decode" and disagg.get("kv_transfer"):
                self._resume_from_remote(slot_idx, waiting)
                return None
            return self._prefill(slot_idx, waiting)
        except Exception as e:  # noqa: BLE001
            log.exception("prefill failed for %s", waiting.context.id)
            self._refund_if_charged(waiting)
            self._post(
                waiting.out_q,
                {"token_ids": [], "finish_reason": "error",
                 "error": f"prefill failed: {e}"},
            )
            return None

    def _embed(self, token_ids: list[int]) -> list[float]:
        """Pooled sequence embedding (bucketed pad for compile reuse)."""
        bucket = self.config.bucket_for(len(token_ids))
        padded = np.zeros((bucket,), np.int32)
        padded[: len(token_ids)] = token_ids
        emb = self.fam.embed_forward(
            self.spec, self.params, jnp.asarray(padded),
            jnp.asarray(len(token_ids), jnp.int32),
        )
        return np.asarray(emb, np.float32).tolist()

    def prefix_hit_tokens(self, token_ids: list[int]) -> int:
        """How many leading prompt tokens are locally cached — G1 device
        pages plus KVBM host/disk tiers the admission path can onboard from
        (policy probe for conditional disagg).

        Advisory and intentionally unlocked: called from the event-loop
        thread while the step loop mutates the allocator/KVBM pools, so the
        answer can be stale by the time it's used. That's fine for a
        routing hint (the admission path re-checks under its own control);
        a shared lock here would serialize routing against every decode
        step."""
        seq = TokenBlockSequence.from_tokens(token_ids, self.config.page_size)
        hashes = seq.sequence_hashes()
        n = len(self.allocator.match_prefix(hashes))
        if self.kvbm is not None:
            while n < len(hashes) and hashes[n] in self.kvbm:
                n += 1
        return n * self.config.page_size

    # -- admission helpers (shared by local prefill and disagg resume) -----

    @staticmethod
    def _opt(d: dict, key: str, default):
        v = d.get(key)
        return default if v is None else v

    def _decode_budget(self, req: dict, n_prompt: int) -> int:
        stop = req.get("stop_conditions") or {}
        max_tokens = stop.get("max_tokens")
        max_tokens = 16 if max_tokens is None else int(max_tokens)
        return max(min(max_tokens, self.config.max_context - n_prompt - 1), 1)

    def _acquire_prompt_pages(
        self,
        request_id: str,
        seq: TokenBlockSequence,
        needed_pages: int,
        *,
        n_tokens: int,
        full_prefix_ok: bool,
    ) -> SeqPages:
        """Prefix-cache take (G1, then KVBM onboard from host/disk tiers) +
        allocation to cover the prompt. Raises OutOfPages (with nothing
        held) if the pool is exhausted.

        ``full_prefix_ok=False`` keeps >=1 token uncached (local prefill
        needs last-position logits); the disagg resume path computes
        nothing, so full coverage is fine there.
        """
        hashes = seq.sequence_hashes()
        page_size = self.config.page_size
        cached = self.allocator.take_prefix(hashes)
        if not full_prefix_ok:
            while cached and len(cached) * page_size >= n_tokens:
                self.allocator.release([cached.pop()])

        # KVBM onboard: consecutive blocks beyond the G1 hit that live in
        # host/disk/remote tiers get pulled back into fresh device pages
        # (get_consecutive batches any G4 hub I/O into one round)
        onboard: list[tuple[Any, Any]] = []
        if self.kvbm is not None:
            limit = needed_pages if full_prefix_ok else (n_tokens - 1) // page_size
            wanted = hashes[len(cached) : min(limit, len(hashes))]
            onboard = self.kvbm.get_consecutive(wanted)
            if onboard and self.kv_dtype == "fp8":
                onboard = self._validate_quant_blocks(onboard, wanted)

        sp = SeqPages(request_id=request_id)
        sp.pages = list(cached)
        sp.hashes = [hashes[i] for i in range(len(cached))]
        sp.cached_prefix_pages = len(cached)
        try:
            while sp.num_pages < needed_pages:
                sp.pages.append(self.allocator.alloc_page())
                sp.hashes.append(None)
        except OutOfPages:
            self.allocator.release(sp.pages)
            raise

        if onboard:
            idxs = range(len(cached), len(cached) + len(onboard))
            try:
                page_ids = np.asarray(
                    [sp.pages[i] for i in idxs], np.int32
                )
                hs = [hashes[i] for i in idxs]
                if self.spmd is not None:
                    # every process of the logical worker installs its own
                    # shard of these blocks (ref KvbmLeader coordinating
                    # workers, distributed/leader.rs:126)
                    self.spmd.publish(
                        "kv_onboard", {"hashes": hs}, {"page_ids": page_ids}
                    )
                self.onboard_from_tiers(hs, page_ids, blocks=onboard)
            except Exception:
                self.allocator.release(sp.pages)
                raise
            # onboarded content came FROM kvbm: seal without re-offloading
            self._seal_prompt_blocks(
                sp, seq, start=len(cached), end=len(cached) + len(onboard),
                offload=False,
            )
            sp.cached_prefix_pages = len(cached) + len(onboard)
        return sp

    def _seal_prompt_blocks(
        self,
        sp: SeqPages,
        seq: TokenBlockSequence,
        start: int | None = None,
        end: int | None = None,
        *,
        offload: bool = True,
    ) -> None:
        """Seal complete prompt blocks [start, end) into the prefix cache."""
        start = sp.cached_prefix_pages if start is None else start
        end = len(seq.blocks) if end is None else end
        for i in range(start, end):
            blk = seq.blocks[i]
            self.allocator.seal_page(
                sp.pages[i], blk.sequence_hash, blk.parent_sequence_hash
            )
            sp.hashes[i] = blk.sequence_hash
            if offload:
                self._queue_offload(blk.sequence_hash, sp.pages[i], i)

    def _validate_quant_blocks(self, blocks: list, hashes: list) -> list:
        """Quantized-onboard guard: a tier block whose payload length is
        wrong or whose SCALE bytes decode non-finite would dequantize a
        whole page to NaN/inf and poison every later step — treat it (and
        everything after: onboard prefixes are consecutive) as a tier
        MISS, logged like the g4 corrupt-payload path, and EVICT it from
        the local tiers so the next admission refetches (or genuinely
        misses) instead of looping fetch->reject forever. ``engine.quant``
        is the injectable fault site: chaos schedules corrupt the dequant
        here to prove serving survives on a re-prefill.

        Validation is per pool: only parts whose engine pool is actually
        quantized carry a packed payload — MLA blocks ship an inert v
        slot (family.MlaFamily.extract_pages) that must not be judged as
        a payload."""
        from dynamo_tpu.ops.quant import (
            is_quant,
            packed_block_ok,
            packed_bytes_per_page,
            packed_scale_bytes,
        )

        checks = []
        for pool in (self.k_pages, self.v_pages):
            if not is_quant(pool):
                checks.append(None)  # inert slot: nothing to validate
                continue
            checks.append(
                (packed_bytes_per_page(pool), packed_scale_bytes(pool))
            )
        for i, blk in enumerate(blocks):
            bad = None
            try:
                if FAULTS.enabled:
                    FAULTS.fire_sync("engine.quant")
            except Exception as e:  # noqa: BLE001 - injected corruption
                bad = f"injected dequant corruption: {e}"
            if bad is None:
                for part, chk in zip(blk, checks):
                    if chk is not None and not packed_block_ok(
                        (part,), chk[0], chk[1]
                    ):
                        bad = "payload length or scale bytes invalid"
                        break
            if bad is not None:
                log.error(
                    "kvbm quantized onboard: block %d/%d corrupt (%s); "
                    "treating the remaining prefix as a miss",
                    i, len(blocks), bad,
                )
                if self.kvbm is not None:
                    sh = hashes[i] if i < len(hashes) else None
                    if sh is not None:
                        # G4 is shared/best-effort and left alone: a
                        # re-fetch from remote re-validates here
                        self.kvbm.host.remove(sh)
                        if self.kvbm.disk is not None:
                            self.kvbm.disk.remove(sh)
                    with self.kvbm._lock:
                        self.kvbm.stats.onboard_misses += 1
                return blocks[:i]
        return blocks

    def onboard_from_tiers(
        self, hashes: list[int], page_ids: np.ndarray, blocks=None
    ) -> None:
        """Install tier-cached blocks into device pages. On a multi-host
        worker each process holds (and installs) only ITS SHARD; the
        global block array assembles from process-local data so the one
        jitted insert runs identically everywhere. A follower tier miss
        zero-fills that shard LOUDLY — tiers are deterministic mirrors of
        the same offload stream, so a miss means lost state (e.g. a
        restarted follower), and hanging the slice would be worse."""
        if blocks is None:
            blocks = []
            for h in hashes:
                b = self.kvbm.get(h) if self.kvbm is not None else None
                if b is None:
                    log.error(
                        "kvbm onboard MISS for %x: zero-filling this "
                        "process's shard", h,
                    )
                blocks.append(b)
            if all(b is None for b in blocks):
                template = None
            else:
                template = next(b for b in blocks if b is not None)
            if template is None:
                if self.kv_dtype == "fp8":
                    # packed quant block: zero bytes unpack to fp8 zeros
                    # with zero scales — exact zero pages
                    from dynamo_tpu.ops.quant import packed_bytes_per_page

                    zshape = (
                        self.k_pages.shape[0],
                        packed_bytes_per_page(self.k_pages),
                    )
                    template = (np.zeros(zshape, np.uint8),) * 2
                else:
                    shard = (
                        self.k_pages.addressable_shards[0].data
                        if not getattr(
                            self.k_pages, "is_fully_addressable", True
                        )
                        else self.k_pages
                    )
                    zshape = (shard.shape[0], shard.shape[2],
                              shard.shape[3], shard.shape[4])
                    template = (
                        np.zeros(zshape, np.dtype(self.spec.dtype)),
                    ) * 2
            blocks = [
                b if b is not None else (np.zeros_like(np.asarray(template[0])),
                                         np.zeros_like(np.asarray(template[1])))
                for b in blocks
            ]
        log.info("kvbm onboard n=%d pages=%s", len(blocks),
                 page_ids[: 4].tolist())
        # tier blocks are [L, KH(local), page, D]; insert wants the n
        # stacked pages on axis 1: [L, n, KH, page, D] (page-major)
        k_stack = np.stack([np.asarray(b[0]) for b in blocks], axis=1)
        v_stack = np.stack([np.asarray(b[1]) for b in blocks], axis=1)
        if self.k_pages is not None and not getattr(
            self.k_pages, "is_fully_addressable", True
        ):
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(
                self.mesh, P(None, None, "tp", None, None)
            )
            kb = jax.make_array_from_process_local_data(sharding, k_stack)
            vb = jax.make_array_from_process_local_data(sharding, v_stack)
        else:
            kb, vb = jnp.asarray(k_stack), jnp.asarray(v_stack)
        self.k_pages, self.v_pages = self.fam.insert_pages(
            self.k_pages, self.v_pages, jnp.asarray(page_ids), kb, vb
        )

    # -- KVBM offload (device -> host tiers) -------------------------------

    def _queue_offload(self, sh: int, page: int, block_index: int) -> None:
        if self.kvbm is not None and self.kvbm.should_offload(block_index):
            self._pending_offload.append((sh, page, block_index))

    def _drain_offload(self) -> None:
        """One batched device gather for all pages sealed this step; the
        device->host copy runs async and lands in the offload thread.

        MUST run before any queued page can be released/evicted (callers:
        right after sealing, before emit/finish) — extraction reads the live
        page pool. Page ids pad to bucket sizes with the trash page so the
        jitted gather compiles once per bucket, not per batch size.
        """
        if not self._pending_offload:
            return
        batch, self._pending_offload = self._pending_offload, []
        n = len(batch)
        bucket = 4
        while bucket < n:
            bucket *= 2
        ids = np.zeros((bucket,), np.int32)  # pad with trash page 0
        ids[:n] = [p for _s, p, _i in batch]
        if self.spmd is not None:
            # followers extract the same pages and offload THEIR shards
            self.spmd.publish(
                "kv_offload",
                {"hashes": [s for s, _p, _i in batch]},
                {"page_ids": ids},
            )
        kb, vb = self.fam.extract_pages(self.k_pages, self.v_pages, jnp.asarray(ids))
        try:
            kb.copy_to_host_async()
            vb.copy_to_host_async()
        except AttributeError:
            pass
        self.offload.submit([s for s, _p, _i in batch], kb, vb)

    def _sampling_params(self, req: dict) -> tuple[float, int, float, int]:
        """(temperature, top_k, top_p, seed) for a request, allocating the
        per-request seed. Used by the fused prefill-time first-token sample
        (the seed must be FIXED before the sample dispatch) and then handed
        to _make_slot so slot and sample agree."""
        sampling = req.get("sampling") or {}
        self._seed_counter += 1
        return (
            float(self._opt(sampling, "temperature", 0.0)),
            int(self._opt(sampling, "top_k", 0)),
            float(self._opt(sampling, "top_p", 1.0)),
            int(self._opt(sampling, "seed", self._seed_counter)) & 0xFFFFFFFF,
        )

    def _make_slot(
        self,
        waiting: _Waiting,
        seq: TokenBlockSequence,
        sp: SeqPages,
        *,
        seq_len: int,
        remaining: int,
        generated: int = 0,
        last_token: int,
        sample_seed: int | None = None,
    ) -> _Slot:
        req = waiting.request
        sampling = req.get("sampling") or {}
        stop = req.get("stop_conditions") or {}
        if sample_seed is None:
            self._seed_counter += 1
            sample_seed = (
                int(self._opt(sampling, "seed", self._seed_counter))
                & 0xFFFFFFFF
            )
        temperature = float(self._opt(sampling, "temperature", 0.0))
        logprobs = self._clamp_logprobs(
            (req.get("output_options") or {}).get("logprobs")
        )
        # speculative decoding is GREEDY-only (accept-longest-prefix
        # against the target argmax is exact at temperature 0; sampled
        # streams would need full rejection sampling) and logprob-free
        # (the verify returns token ids, not per-position logits)
        slot_spec = None
        if self._spec_on and temperature <= 0.0 and logprobs is None:
            slot_spec = SlotSpec.for_config(self.config)
        guided_state = None
        g = req.get("guided")
        if g and self._guided is not None:
            # per-slot grammar cursor (LRU-warm: generate() compiled it).
            # End-of-stream ids join the mask at accepting states only —
            # the grammar can't stop early and must stop when complete.
            # prompt_len marks where the ORIGINAL prompt ended: tokens
            # past it are completions a migration/disagg resume folded
            # into the prompt, and the cursor advances over them so a
            # resumed stream continues mid-grammar (continuity contract).
            token_ids = req.get("token_ids") or []
            guided_state = self._guided.state_for(
                g,
                eos_ids=(
                    frozenset(req.get("eos_token_ids") or (2,))
                    | frozenset(stop.get("stop_token_ids") or ())
                ),
                prefix_tokens=token_ids[int(g.get("prompt_len") or len(token_ids)):],
            )
        self._admit_seq += 1
        return _Slot(
            request_id=waiting.context.id,
            context=waiting.context,
            out_q=waiting.out_q,
            seq=seq,
            pages=sp,
            seq_len=seq_len,
            remaining=remaining,
            tenant=waiting.tenant,
            priority=waiting.priority,
            request=req,
            admitted_seq=self._admit_seq,
            temperature=temperature,
            top_k=int(self._opt(sampling, "top_k", 0)),
            top_p=float(self._opt(sampling, "top_p", 1.0)),
            ignore_eos=bool(stop.get("ignore_eos", False)),
            stop_token_ids=frozenset(stop.get("stop_token_ids") or ()),
            eos_ids=frozenset(req.get("eos_token_ids") or (2,)),
            min_tokens=int(self._opt(stop, "min_tokens", 0)),
            generated=generated,
            last_token=last_token,
            sample_seed=sample_seed,
            logprobs=logprobs,
            spec=slot_spec,
            guided=guided_state,
        )

    def _clamp_logprobs(self, n) -> int | None:
        """Single chokepoint for the logprob width: the OpenAI surface caps
        at 20, direct engine callers get clamped (top_k needs k <= V, and
        emit indexing must stay inside the computed arrays)."""
        if n is None or not self.fam.supports_logprobs:
            return None
        return max(0, min(int(n), 20, self.spec.vocab_size - 1))

    def _prefill_chunk_max(self) -> int:
        """Most prompt tokens one prefill dispatch takes: the configured
        chunk, capped by the largest bucket the engine offers."""
        return min(
            self.config.max_prefill_chunk_tokens, max(self._prefill_shapes)
        )

    def _decode_multimodal(self, req: dict) -> dict | None:
        """Validate + decode the request's multimodal payload (encoder
        rows + placeholder positions). Returns None for text requests."""
        mm = req.get("multimodal")
        if not mm:
            return None
        if "embeds_b64" not in mm:
            raise ValueError(
                "multimodal request reached the engine without embeddings "
                "(is an encode worker registered?)"
            )
        if not getattr(self.fam, "supports_multimodal", False):
            raise ValueError(
                f"{type(self.fam).__name__} does not support image input"
            )
        from dynamo_tpu.multimodal.worker import (
            embeds_from_wire,
            salt_from_wire,
        )

        embeds = embeds_from_wire(mm).astype(np.float32)
        positions = np.asarray(mm.get("positions") or (), np.int32)
        if embeds.ndim != 2 or embeds.shape[0] != positions.shape[0]:
            raise ValueError(
                f"multimodal rows {embeds.shape} do not match "
                f"{positions.shape[0]} placeholder positions"
            )
        if embeds.shape[1] != self.spec.hidden_size:
            raise ValueError(
                f"multimodal embedding width {embeds.shape[1]} != model "
                f"hidden size {self.spec.hidden_size}"
            )
        # cache-partitioning salt: identical prompts with DIFFERENT images
        # share placeholder token ids, so unsalted block hashes would
        # alias across images (and against text prompts). Salting by the
        # embedding digest keeps prefix reuse exact: same prompt + same
        # image rehits, anything else misses. (ref tokens.rs SaltHash)
        return {
            "embeds": embeds,
            "positions": positions,
            "salt": mm.get("salt") or salt_from_wire(mm),
        }

    def _prefill(self, slot_idx: int, waiting: _Waiting) -> tuple | None:
        cfg = self.config
        req = waiting.request
        token_ids = list(req["token_ids"])
        max_tokens = self._decode_budget(req, len(token_ids))
        mm = self._decode_multimodal(req)

        seq = TokenBlockSequence.from_tokens(
            token_ids, cfg.page_size, salt=mm["salt"] if mm else None
        )
        needed_pages = (len(token_ids) + cfg.page_size - 1) // cfg.page_size
        sp = None
        try:
            sp = self._acquire_prompt_pages(
                waiting.context.id, seq, needed_pages,
                n_tokens=len(token_ids), full_prefix_ok=False,
            )
        except OutOfPages:
            # PAGE-pressure preemption: an interactive prompt that
            # cannot get pages may pause a batch stream (its released
            # pages become evictable/free) and retry ONCE — the other
            # half of the overload contract, where the pool rather than
            # the slot table is what the batch tenant exhausted
            if (
                cfg.preemption
                and waiting.priority == "interactive"
                and self._preempt_batch_slot(
                    reason="interactive_pages", free_slot_ok=False
                ) is not None
            ):
                try:
                    sp = self._acquire_prompt_pages(
                        waiting.context.id, seq, needed_pages,
                        n_tokens=len(token_ids), full_prefix_ok=False,
                    )
                except OutOfPages:
                    sp = None
        if sp is None:
            # page BACKPRESSURE, not a hard error: a neighbor finishing
            # (or a later preemption) frees pages, so the entry waits in
            # its lane exactly like a decode-stalled slot waits — the
            # transparent-resume contract for preempted streams depends
            # on this. Bounded patience (MAX_WAIT_PAGE_STALLS admission
            # passes, ~2ms apart when the engine is otherwise idle), and
            # a prompt that could NEVER fit errors immediately.
            if needed_pages >= self.allocator.num_pages - 1:
                self._refund_if_charged(waiting)
                self._post(
                    waiting.out_q,
                    {"token_ids": [], "finish_reason": "error",
                     "error": f"kv pages exhausted (prompt needs "
                              f"{needed_pages} pages; pool can never "
                              "hold it)"},
                )
                return None
            if waiting.page_stalls >= 2000:
                self._refund_if_charged(waiting)
                self._post(
                    waiting.out_q,
                    {"token_ids": [], "finish_reason": "error",
                     "error": "kv pages exhausted (admission waited "
                              f"{waiting.page_stalls} passes)"},
                )
                return None
            waiting.page_stalls += 1
            # lane-head requeue with the vtime advance undone: a stall
            # retry is zero service and must not burn fair share or
            # drop behind later same-tenant arrivals
            self._waiting.requeue(waiting)
            return _REQUEUED
        start_pos = sp.cached_prefix_pages * cfg.page_size
        tail = len(token_ids) - start_pos

        try:
            return self._prefill_with_pages(
                slot_idx, waiting, seq, sp, token_ids, max_tokens,
                start_pos, tail, mm=mm,
            )
        except BaseException:
            # anything after acquisition failing must hand the pages back
            # (handed-off paths clear sp.pages first, so this is a no-op
            # once ownership moved to a slot/export)
            self.allocator.release(sp.pages)
            sp.pages = []
            raise

    def _prefill_with_pages(
        self, slot_idx, waiting, seq, sp, token_ids, max_tokens,
        start_pos, tail, mm: dict | None = None,
    ) -> tuple | None:
        """Run the prompt's forward. Returns a pending-admission record
        ``(slot_idx, waiting, seq, sp, token_ids, max_tokens, logits)``
        with logits still ON DEVICE — first-token sampling is batched
        across all admissions of the step (_complete_admissions) so the
        step pays ONE device->host sync, not one per prompt. Returns None
        when a chunked prefill was started instead."""
        cfg = self.config
        if mm is not None:
            # multimodal: ONE immediate dispatch with embedding injection
            # (no packed batching, no ring; chunking would split the
            # placeholder span across dispatches)
            if start_pos + self._prefill_chunk_max() < len(token_ids):
                raise ValueError(
                    "multimodal prompt exceeds a single prefill dispatch "
                    f"({len(token_ids) - start_pos} uncached tokens > "
                    f"max_prefill_chunk_tokens {self._prefill_chunk_max()})"
                )
            logits = self._run_prefill_chunk(
                sp, token_ids, start_pos, len(token_ids), mm=mm
            )
            waiting.prefill_seq = self._launch_seq
            self._seal_prompt_blocks(sp, seq)  # salted hashes: cache-safe
            self._drain_offload()
            return (
                slot_idx, waiting, seq, sp, token_ids, max_tokens,
                (logits, None), None,
            )
        use_ring = (
            self.mesh is not None
            and self.fam.supports_ring_prefill
            and self.mesh.shape.get("sp", 1) > 1
            and start_pos == 0
            and tail <= cfg.prefill_buckets[-1]
            and cfg.bucket_for(tail) % self.mesh.shape["sp"] == 0
        )
        if use_ring:
            # cold long prompt: sequence-parallel ring-attention prefill —
            # the whole prompt in one shot, split across the sp axis (the
            # multi-chip answer to long prefills; chunking is the
            # single-chip one)
            bucket = cfg.bucket_for(tail)
            padded = np.zeros((bucket,), np.int32)
            padded[:tail] = token_ids[start_pos:]
            block_table = np.zeros((cfg.max_pages_per_seq,), np.int32)
            block_table[: sp.num_pages] = sp.pages
            if self.spmd is not None:
                self.spmd.publish(
                    "ring_prefill",
                    {"num_tokens": tail},
                    {"tokens": padded, "block_table": block_table},
                )
            with self._launch(
                "prefill", tokens=tail, rows=1, ahead=len(self._pipeline)
            ):
                logits, self.k_pages, self.v_pages, _ = (
                    self.fam.prefill_ring(
                        self.spec,
                        self.params,
                        jnp.asarray(padded),
                        jnp.asarray(block_table),
                        self.k_pages,
                        self.v_pages,
                        jnp.asarray(tail, jnp.int32),
                        mesh=self.mesh,
                    )
                )
            waiting.prefill_seq = self._launch_seq
            self.dispatches += 1
            self._seal_prompt_blocks(sp, seq)
            self._drain_offload()
            return (
                slot_idx, waiting, seq, sp, token_ids, max_tokens,
                (logits, None), None,
            )

        chunk_max = self._prefill_chunk_max()
        if start_pos + chunk_max >= len(token_ids):
            # fits one dispatch: defer the forward to the PACKED prefill
            # stage, which lands every same-bucket admission of this step
            # in a single jit call (_run_packed_prefills)
            return {
                "slot_idx": slot_idx, "waiting": waiting, "seq": seq,
                "sp": sp, "token_ids": token_ids, "max_tokens": max_tokens,
                "start_pos": start_pos, "tail": tail,
            }
        # long prompt: remaining chunks advance on subsequent steps,
        # interleaved with decode (_step)
        end = start_pos + chunk_max
        self._run_partial_chunk(waiting, sp, token_ids, start_pos, end)
        self._partial = _PartialPrefill(
            slot_idx, waiting, seq, sp, token_ids, end, max_tokens
        )
        return None

    def _run_packed_prefills(self, preps: list[dict]) -> list[tuple]:
        """Execute deferred admissions: same-bucket prompts batch into one
        ``prefill_forward_batch`` dispatch (N padded to a power of two so
        the compiled-shape set stays bounded); singletons take the
        already-compiled single-prompt program. Returns pending-admission
        records for _complete_admissions."""
        if not preps:
            return []
        cfg = self.config
        records: list[tuple] = []
        groups: dict[int, list[dict]] = {}
        for p in preps:
            groups.setdefault(cfg.bucket_for(p["tail"]), []).append(p)
        slices: list[tuple[int, list[dict]]] = []
        for bucket, group in sorted(groups.items()):
            # ONE packed width per bucket (jit compiles cost seconds on
            # TPU, so organic group sizes would stall serving every time
            # a new size appeared): chunk to the bucket's offered pack
            # width, pad the remainder
            pack = (
                self._prefill_shapes[bucket]
                if self.fam.supports_packed_prefill else 1
            )
            for i in range(0, len(group), pack):
                slices.append((bucket, group[i : i + pack]))
        for bucket, group in slices:
            if len(group) == 1:
                rec = self._single_prefill_record(group[0])
                if rec is not None:
                    records.append(rec)
                continue
            nb = self._prefill_shapes[bucket]
            tails = [p["token_ids"][p["start_pos"]:] for p in group]
            if len(group) == nb and all(len(t) == bucket for t in tails):
                # full pack of exact-bucket prompts: stack directly, no
                # zero-fill + row-copy re-pad
                tokens = np.asarray(tails, np.int32)
            else:
                tokens = np.zeros((nb, bucket), np.int32)
                for i, t in enumerate(tails):
                    tokens[i, : len(t)] = t
            bts = np.zeros((nb, cfg.max_pages_per_seq), np.int32)
            starts = np.zeros((nb,), np.int32)
            nts = np.zeros((nb,), np.int32)  # padded rows: 0 -> trash page
            for i, p in enumerate(group):
                bts[i, : p["sp"].num_pages] = p["sp"].pages
                starts[i] = p["start_pos"]
                nts[i] = p["tail"]
            pmark = self._spmd_mark()
            try:
                if self.spmd is not None:
                    self.spmd.publish(
                        "prefill_batch", {},
                        {"tokens": tokens, "block_tables": bts,
                         "start": starts, "num_tokens": nts},
                    )
                self._flush_state_releases()
                with self._launch(
                    "prefill", tokens=sum(p["tail"] for p in group),
                    rows=len(group), ahead=len(self._pipeline),
                ):
                    logits, self.k_pages, self.v_pages, _ = (
                        self.fam.prefill_batch(
                            self.spec, self.params, jnp.asarray(tokens),
                            jnp.asarray(bts), jnp.asarray(starts),
                            self.k_pages, self.v_pages, jnp.asarray(nts),
                            mesh=self.mesh,
                        )
                    )
                for p in group:
                    p["waiting"].prefill_seq = self._launch_seq
                self.dispatches += 1
                self._count_prefill_kv(bucket, bts.shape[1], starts, nts)
            except Exception as e:  # noqa: BLE001
                log.exception("packed prefill failed (%d prompts)", len(group))
                self._spmd_broken(
                    "packed prefill failed after publish", since=pmark
                )
                for p in group:
                    self.allocator.release(p["sp"].pages)
                    p["sp"].pages = []
                    self._refund_if_charged(p["waiting"])
                    self._post(
                        p["waiting"].out_q,
                        {"token_ids": [], "finish_reason": "error",
                         "error": f"prefill failed: {e}"},
                    )
                continue
            pres = self._fused_first_tokens(
                logits, [p["waiting"] for p in group]
            )
            for i, p in enumerate(group):
                self._seal_prompt_blocks(p["sp"], p["seq"])
                records.append((
                    p["slot_idx"], p["waiting"], p["seq"], p["sp"],
                    p["token_ids"], p["max_tokens"], (logits, i),
                    pres[i] if pres else None,
                ))
        self._drain_offload()
        return records

    def _fused_first_tokens(
        self, logits: jax.Array, waitings: list[_Waiting]
    ) -> list[tuple] | None:
        """Sample the dispatch's first tokens straight off its [nb, V]
        logits — no per-row slicing, no cross-dispatch stack, and the
        host copy starts immediately. Returns per-row
        ``(samples, row, seed)`` handles for the async admission path,
        or None when these records need host-side logits anyway
        (sync admissions: logprobs, disagg handoff, SPMD lockstep)."""
        if (
            not self.config.async_admissions
            or self.spmd is not None
            or any(self._needs_sync_admission(w.request) for w in waitings)
        ):
            return None
        nb = logits.shape[0]
        temps = np.zeros((nb,), np.float32)
        topk = np.zeros((nb,), np.int32)
        topp = np.ones((nb,), np.float32)
        seeds = np.zeros((nb,), np.uint32)
        params = [self._sampling_params(w.request) for w in waitings]
        for i, (t, k, p, s) in enumerate(params):
            temps[i], topk[i], topp[i], seeds[i] = t, k, p, s
        with self._launch("sample", rows=len(waitings)):
            samples = sample_tokens(
                logits, jnp.asarray(temps), jnp.asarray(topk),
                jnp.asarray(topp), jnp.asarray(seeds),
                jnp.zeros((nb,), jnp.int32),  # first token: RNG step 0
            )
        self.dispatches += 1
        # NO host copy here: the dispatch's samples become one admission
        # wave with a single async copy (_complete_admissions_async), and
        # the burst download's fed column is the no-extra-transfer
        # backstop.
        return [
            (samples, i, params[i][3]) for i in range(len(waitings))
        ]

    def _needs_sync_admission(self, req: dict) -> bool:
        """True when this request's admission must read logits/tokens on
        the host immediately (logprob entries, disagg prefill handoff)."""
        if (
            (req.get("output_options") or {}).get("logprobs") is not None
            and self.fam.supports_logprobs
        ):
            return True
        if req.get("guided"):
            # the FIRST sampled token must already respect the grammar's
            # start state, and the automaton must advance on its host
            # value before the next mask is built — the async path's
            # deferred materialization breaks both
            return True
        kvt = (req.get("disagg") or {}).get("kv_transfer") or {}
        return bool(
            kvt.get("do_remote_decode") and self.transfer_source is not None
        )

    def _single_prefill_record(self, p: dict) -> tuple | None:
        pmark = self._spmd_mark()
        try:
            logits = self._run_prefill_chunk(
                p["sp"], p["token_ids"], p["start_pos"], len(p["token_ids"])
            )
            p["waiting"].prefill_seq = self._launch_seq
            self._seal_prompt_blocks(p["sp"], p["seq"])
            pres = self._fused_first_tokens(logits[None, :], [p["waiting"]])
            return (
                p["slot_idx"], p["waiting"], p["seq"], p["sp"],
                p["token_ids"], p["max_tokens"], (logits, None),
                pres[0] if pres else None,
            )
        except Exception as e:  # noqa: BLE001
            log.exception("prefill failed for %s", p["waiting"].context.id)
            self._spmd_broken("prefill failed after publish", since=pmark)
            self.allocator.release(p["sp"].pages)
            p["sp"].pages = []
            self._refund_if_charged(p["waiting"])
            self._post(
                p["waiting"].out_q,
                {"token_ids": [], "finish_reason": "error",
                 "error": f"prefill failed: {e}"},
            )
            return None

    def _complete_admissions(self, pending: list[tuple]) -> None:
        """Sample every admitted prompt's first token in ONE batched call.

        Default (async) path: the sampled tokens STAY ON DEVICE — they
        feed the next decode burst through a device-side gather
        (_dispatch_burst admit feed) while their host copy rides a
        copy_to_host_async and materializes at the NEXT step
        (_materialize_admissions). The step thread never blocks on the
        d2h round-trip (what that buys beside the chip is not measured
        on current code — ROADMAP D3).

        Sync fallback (host needs the token value NOW): multi-host SPMD
        (logits pulled host-side anyway), logprob requests, and disagg
        remote-prefill handoffs.

        Batch width pads to one static width (max_decode_slots) so
        sample_tokens keeps a single compiled shape: every extra jit
        compile costs whole seconds on TPU and would stall serving the
        first time each admission count appears."""
        use_async = (
            self.config.async_admissions
            and self.spmd is None
            and not any(
                self._needs_sync_admission(r[1].request) for r in pending
            )
        )
        if use_async:
            self._complete_admissions_async(pending)
            return
        recs: list[tuple] = []
        try:
            for (
                slot_idx, waiting, seq, sp, token_ids, max_tokens,
                logits_ref, pre,
            ) in pending:
                # a mixed round can carry presampled records onto the sync
                # path (their fused sample goes unused); reuse their
                # already-allocated seed so the slot's RNG stream matches
                # what the same round would produce un-mixed
                slot = self._make_slot(
                    waiting, seq, sp,
                    seq_len=len(token_ids), remaining=max_tokens,
                    last_token=token_ids[-1],
                    sample_seed=pre[2] if pre is not None else None,
                )
                recs.append((slot_idx, waiting, slot, logits_ref, token_ids, sp))
            stacked, sample_args = self._admission_sample_inputs(
                [r[2] for r in recs],
                [self._logits_row(r[3]) for r in recs],
                on_device=self.spmd is None,
            )
            gmask = self._admission_guided_mask(
                [r[2] for r in recs], stacked.shape[0]
            )
            with self._launch("sample", rows=len(recs)):
                if gmask is not None:
                    sampled_dev = sample_tokens_masked(
                        stacked, jnp.asarray(gmask), *sample_args
                    )
                else:
                    sampled_dev = sample_tokens(stacked, *sample_args)
            self.dispatches += 1
            # logprobs, when any admitted prompt wants them, batch over the
            # same stacked logits: one more fused sync, not one per record
            lp = top_i = top_v = None
            if any(r[2].logprobs is not None for r in recs):
                n_lp = min(20, self.spec.vocab_size - 1)
                with self._launch("logprobs", rows=len(recs)):
                    picked, ti, tv = token_logprobs(stacked, sampled_dev, n_lp)
                self.dispatches += 1
                # readmit.d2h_wait, NOT dispatch.d2h_wait: this span
                # nests inside the complete_admissions phase the
                # overhead fraction already sums (profile_engine
                # READMIT_PHASES) — one name per accounting bucket
                with self._phase("readmit.d2h_wait"):
                    toks, lp, top_i, top_v = jax.device_get(
                        (sampled_dev, picked, ti, tv)
                    )
            else:
                with self._phase("readmit.d2h_wait"):
                    toks = np.asarray(sampled_dev)
        except Exception as e:  # noqa: BLE001
            log.exception("batched admission completion failed")
            for _si, waiting, _seq, sp, _t, _m, _lr, _pre in pending:
                self.allocator.release(sp.pages)
                sp.pages = []
                self._post(
                    waiting.out_q,
                    {"token_ids": [], "finish_reason": "error",
                     "error": f"prefill failed: {e}"},
                )
            return

        self._record_prefill_dispatch(r[1] for r in recs)
        for i, (slot_idx, waiting, slot, _logits_ref, token_ids, sp) in enumerate(recs):
            # per-record isolation: one bad emit (disagg export, handoff)
            # must not strand the step's other admissions
            try:
                tok = int(toks[i])
                entry = None
                if slot.logprobs is not None and lp is not None:
                    entry = {
                        "id": tok,
                        "logprob": float(lp[i]),
                        "top": [
                            {"id": int(top_i[i, t]),
                             "logprob": float(top_v[i, t])}
                            for t in range(slot.logprobs)
                        ],
                    }
                disagg = waiting.request.get("disagg") or {}
                remote = (disagg.get("kv_transfer") or {}).get(
                    "do_remote_decode") and self.transfer_source is not None
                if remote and not self.fam.supports_page_transfer:
                    # exported pages would carry no recurrent state: the
                    # stream is decoded here instead, and that is counted
                    self._note_recurrent_gate("page_transfer")
                elif remote:
                    # disagg prefill: stage KV to host, hand off, free pages
                    self._export_and_finish(slot, sp, token_ids, tok, entry)
                    continue
                self._emit_token(slot_idx, slot, tok, logprob_entry=entry,
                                 seq=waiting.prefill_seq)
            except Exception as e:  # noqa: BLE001
                log.exception(
                    "admission emit failed for %s", waiting.context.id
                )
                if self._slots[slot_idx] is slot:
                    self._finish(
                        slot_idx, slot, "error",
                        error=f"admission failed: {e}",
                    )
                else:
                    self.allocator.release(sp.pages)
                    sp.pages = []
                    self._post(
                        waiting.out_q,
                        {"token_ids": [], "finish_reason": "error",
                         "error": f"admission failed: {e}"},
                    )

    @staticmethod
    def _record_prefill_dispatch(waitings) -> None:
        """The flight recorder's ``prefill_dispatch``: these requests'
        prefill and first-token sample are on the device's queue."""
        for waiting in waitings:
            FLIGHT.event(
                waiting.context.id, "prefill_dispatch",
                seq=waiting.prefill_seq,
            )

    def _admission_guided_mask(
        self, slots: list, width: int
    ) -> np.ndarray | None:
        """[width, V] allowed mask for a first-token sample batch, or
        None when no admitted slot is constrained (the all-free batch
        then never pays the masked program). Free and padded rows are
        all-True — identity under the mask."""
        if not any(
            s.guided is not None and s.guided.constraining for s in slots
        ):
            return None
        with self._phase("guided.mask"):
            allowed = np.ones((width, self.spec.vocab_size), bool)
            for i, slot in enumerate(slots):
                if slot.guided is not None and slot.guided.constraining:
                    allowed[i] = slot.guided.mask()
        return allowed

    def _admission_sample_inputs(self, slots: list, logits_rows: list,
                                 *, on_device: bool):
        """Shared first-token sample batch for BOTH admission paths:
        logits rows padded to one static width (max_decode_slots) plus
        the per-slot sampling params. The RNG step is always 0 — these
        are first tokens (the async path pre-advances ``generated`` for
        burst bookkeeping, which must not shift the sample stream).
        ``on_device=False`` stacks on host: under multi-host SPMD the
        replicated logits must not become a collective program the
        followers don't replay."""
        n = len(slots)
        bucket = max(n, self.config.max_decode_slots)
        if on_device:
            stacked = jnp.stack(
                list(logits_rows) + [logits_rows[0]] * (bucket - n)
            )
        else:
            rows = [np.asarray(r, np.float32) for r in logits_rows]
            stacked = np.stack(rows + [rows[0]] * (bucket - n))
        temps = np.zeros((bucket,), np.float32)
        topk = np.zeros((bucket,), np.int32)
        topp = np.ones((bucket,), np.float32)
        seeds = np.zeros((bucket,), np.uint32)
        gens = np.zeros((bucket,), np.int32)  # first token: RNG step 0
        for i, slot in enumerate(slots):
            temps[i] = slot.temperature
            topk[i] = slot.top_k
            topp[i] = slot.top_p
            seeds[i] = slot.sample_seed
        return stacked, (
            jnp.asarray(temps), jnp.asarray(topk), jnp.asarray(topp),
            jnp.asarray(seeds), jnp.asarray(gens),
        )

    @staticmethod
    def _logits_row(logits_ref: tuple) -> jax.Array:
        """Resolve a record's ``(array, row)`` logits handle to a [V] row.
        Packed dispatches share one [nb, V] array (row = index); single
        dispatches carry the [V] row directly (row = None)."""
        arr, row = logits_ref
        return arr if row is None else arr[row]

    def _complete_admissions_async(self, pending: list[tuple]) -> None:
        """Async admission completion: first tokens sampled on device,
        d2h copies in flight, slots installed with ``first_pending`` set —
        the step thread never waits. The next decode burst feeds the new
        slots' tokens straight from the device samples (_dispatch_burst
        admit feed); host values materialize later (_land_ready_waves /
        _materialize_waves / _process_burst ordering).

        Most records arrive PRESAMPLED: the packed/single prefill stage
        fused the first-token sample onto its own dispatch
        (_fused_first_tokens), so no per-row logits slicing or cross-
        dispatch stacking happens here — one admission wave per source
        dispatch, its width that dispatch's pack width (a bounded set: the
        wave feed compiles once per width; waves cover disjoint slots and
        land independently). Records without a presample (multimodal,
        ring) batch into one extra stacked sample."""
        recs: list[tuple] = []
        waves: dict[int, dict] = {}
        unsampled: list[tuple] = []
        try:
            for (
                slot_idx, waiting, seq, sp, token_ids, max_tokens,
                logits_ref, pre,
            ) in pending:
                # counters PRE-advanced past the first token (its value is
                # still in flight): bursts built before materialization
                # see the same generated/remaining the sync path would
                slot = self._make_slot(
                    waiting, seq, sp,
                    seq_len=len(token_ids), remaining=max_tokens - 1,
                    generated=1, last_token=token_ids[-1],
                    sample_seed=pre[2] if pre is not None else None,
                )
                slot.first_pending = True
                recs.append((slot_idx, slot))
                if pre is not None:
                    arr, row, _seed = pre
                    # seq: the prefill whose fused sample the wave is
                    # (stream.post carries it when the wave lands)
                    wave = waves.setdefault(
                        id(arr), {"dev": arr, "recs": [], "fed": set(),
                                  "age": 0, "seq": waiting.prefill_seq}
                    )
                    wave["recs"].append((slot_idx, slot, row))
                else:
                    unsampled.append((slot_idx, slot, logits_ref))
            if unsampled:
                stacked, sample_args = self._admission_sample_inputs(
                    [s for _, s, _ in unsampled],
                    [self._logits_row(lr) for _, _, lr in unsampled],
                    on_device=True,
                )
                with self._launch("sample", rows=len(unsampled)):
                    sampled_dev = sample_tokens(stacked, *sample_args)
                self.dispatches += 1
                waves[id(sampled_dev)] = {
                    "dev": sampled_dev,
                    "recs": [
                        (si, s, i) for i, (si, s, _lr) in enumerate(unsampled)
                    ],
                    "fed": set(),
                    "age": 0,
                    "seq": self._launch_seq,  # the sample program's own
                }
            for w in waves.values():
                # start the host copy NOW: the wave can land from host
                # memory (is_ready) the moment its prefill has ended, in
                # the hold of the queued burst that sees it ready
                # (_land_ready_waves) or at the top of the next cycle —
                # a burst earlier than the burst-processing backstop,
                # which is what keeps closed-loop clients resubmitting
                # and the batch full
                try:
                    w["dev"].copy_to_host_async()
                except AttributeError:
                    pass
        except Exception as e:  # noqa: BLE001
            log.exception("async admission completion failed")
            for _si, waiting, _seq, sp, _t, _m, _lr, _pre in pending:
                self.allocator.release(sp.pages)
                sp.pages = []
                self._post(
                    waiting.out_q,
                    {"token_ids": [], "finish_reason": "error",
                     "error": f"prefill failed: {e}"},
                )
            return
        self._record_prefill_dispatch(r[1] for r in pending)
        for slot_idx, slot in recs:
            self._slots[slot_idx] = slot
        self._admit_waves.extend(waves.values())

    def _live_recs(self, ap: dict) -> list[tuple]:
        """The records of a wave whose first token is still to land: the
        slot is the one admitted (not finished, cancelled or reused
        since) and nothing has landed it yet."""
        return [
            (si, s, row) for si, s, row in ap["recs"]
            if self._slots[si] is s and s.first_pending
        ]

    def _materialize_waves(self, force: bool = False) -> bool:
        """Land admission waves whose device sample is ready. Waves cover
        disjoint LIVE slots, so landing one never depends on another —
        slot-identity guards skip records whose slot was reused since.

        Who lands a first token: this, at the top of a cycle (_step), a
        wave that says ready; _land_ready_waves, while the queued burst
        is held, the same; _process_burst, from the fed column of the
        slot's first burst, whatever neither saw ready before that burst
        was read. Each of the first two reads the wave's own download,
        started at the admission (_complete_admissions_async).

        A wave whose pending slots are COVERED by an in-flight decode
        burst is left alone even when aged: _process_burst force-lands it
        right before that burst's (already device-complete) tokens sync,
        where the asarray is nearly free. Forcing here instead would
        block the step thread on device work still queued behind a full
        burst — measured at ~60 ms/cycle of stall under admission churn
        (the round-5 profile, benchmarks/profile_engine.py). The age
        fallback only catches waves NO burst will ever process (e.g. a
        one-token budget exhausted by the first token). It counts calls
        of THIS function, a cycle apart: a hold asks many times a cycle,
        of waves no burst covers yet (a prefill admitted in that hold:
        its burst is the held one), so it must neither age nor force."""
        did = False
        keep: list[dict] = []
        covered: set[int] = set()
        if not force:
            for pb in self._pipeline:
                covered.update(
                    si for si in pb["batch"]["participants"]
                    if pb["batch"]["active"][si]
                )
        for ap in self._admit_waves:
            ap["age"] += 1
            ready = _is_ready(ap["dev"])
            live = self._live_recs(ap)
            if not live:
                # every record finished/cancelled since admission: nothing
                # to land — drop the wave without touching the device
                did = True
                continue
            in_burst = all(si in covered for si, _s, _row in live)
            if force or ready or (ap["age"] >= 2 and not in_burst):
                self._materialize_one(ap)
                did = True
            else:
                keep.append(ap)
        self._admit_waves = keep
        return did

    def _land_ready_waves(self) -> bool:
        """The landing of a hold (_hold_queued_burst): post the first
        tokens of every wave whose device sample says ready, from the
        wave's own download, and return whether a live wave is still on
        its way. Asks and never insists: no wave is aged and none is read
        by force (_materialize_waves says why), so this thread never
        blocks on the device here."""
        keep: list[dict] = []
        for ap in self._admit_waves:
            if not self._live_recs(ap):
                continue  # finished/cancelled since admission: dropped
            if _is_ready(ap["dev"]):
                with self._phase("materialize"):
                    self._materialize_one(ap, where="in_hold")
            else:
                keep.append(ap)
        self._admit_waves = keep
        return bool(keep)

    def _materialize_one(
        self,
        ap: dict,
        *,
        where: str = "at_step",
        fed_col: np.ndarray | None = None,
        fed: set | None = None,
        part: np.ndarray | None = None,
        participants: dict | None = None,
    ) -> dict | None:
        """Land an async admission wave's first tokens.

        Direct mode (``fed_col`` is None): read the wave's own device
        sample — one d2h transfer; ``where`` is the ``first_tokens``
        counter it lands under. Burst mode (_process_burst): slots
        that were FED into the burst being processed take their token
        from the burst download's fed column — no extra transfer; any
        record not covered (a page-stalled slot that joined a later
        burst) stays in a residual wave, returned for re-queueing.

        The ``participants`` request-id check is load-bearing: a burst
        dispatched before this slot's admission can have its INDEX
        active under the PREVIOUS request — its fed column carries the
        dead request's chained token, not this wave's sample. Only the
        burst whose participant at the index IS this request may land
        the first token."""
        if fed_col is None:
            try:
                # nests inside the materialize phase (a READMIT_PHASES
                # member): readmit bucket, not dispatch (see
                # _complete_admissions)
                with self._phase("readmit.d2h_wait"):
                    toks = np.asarray(ap["dev"])
            except Exception as e:  # noqa: BLE001
                log.exception("admission materialization failed")
                for slot_idx, slot, _row in ap["recs"]:
                    if self._slots[slot_idx] is slot:
                        self._finish(
                            slot_idx, slot, "error",
                            error=f"admission failed: {e}",
                        )
                return None
            with self._stream_post(ap.get("seq", 0)):
                for slot_idx, slot, row in ap["recs"]:
                    if self._slots[slot_idx] is not slot:
                        continue  # finished/cancelled since admission
                    self._land_first_token(
                        slot_idx, slot, int(toks[row]), where)
            return None
        rest: list[tuple] = []
        with self._stream_post(ap.get("seq", 0)):
            for slot_idx, slot, row in ap["recs"]:
                if (
                    self._slots[slot_idx] is not slot
                    or not slot.first_pending
                ):
                    continue  # finished/cancelled since admission
                if (
                    slot_idx in fed
                    and part[slot_idx]
                    and participants is not None
                    and participants.get(slot_idx) == slot.request_id
                ):
                    self._land_first_token(
                        slot_idx, slot, int(fed_col[slot_idx]), "on_burst"
                    )
                else:
                    rest.append((slot_idx, slot, row))
        if rest:
            return {**ap, "recs": rest}
        return None

    def _land_first_token(
        self, slot_idx: int, slot: _Slot, tok: int, where: str
    ) -> None:
        """Record + stream an async admission's first token (stop
        semantics of _accept_token, with counters pre-advanced), counted
        under ``first_tokens[where]``."""
        self.first_tokens[where] += 1
        FLIGHT.event(slot.context.id, "first_token")
        slot.seq.append(tok)
        slot.last_token = tok
        slot.first_pending = False
        finish = None
        if (
            not slot.ignore_eos
            and slot.generated >= slot.min_tokens
            and tok in slot.eos_ids
        ):
            finish = "stop"
        elif tok in slot.stop_token_ids and slot.generated >= slot.min_tokens:
            finish = "stop"
        elif slot.remaining <= 0:
            finish = "length"
        if finish is not None:
            self._finish(slot_idx, slot, finish, emit=False)
        self._post(
            slot.out_q, {"token_ids": [tok], "finish_reason": finish}
        )

    def _run_prefill_chunk(
        self, sp: SeqPages, token_ids: list[int], start: int, end: int,
        mm: dict | None = None,
    ) -> jax.Array:
        """One bucketed prefill forward over token positions [start, end).
        ``mm``: multimodal embedding rows injected at their (window-
        relative) placeholder positions; rows covered by the cached
        prefix are skipped (the salted cache already holds their KV)."""
        cfg = self.config
        new_tokens = token_ids[start:end]
        bucket = cfg.bucket_for(len(new_tokens))
        if len(new_tokens) == bucket:
            # exact bucket fit (every mid-prompt chunk of a chunked
            # prefill, and any prompt landing on a bucket boundary):
            # skip the zero-fill + copy re-pad
            padded = np.asarray(new_tokens, np.int32)
        else:
            padded = np.zeros((bucket,), np.int32)
            padded[: len(new_tokens)] = new_tokens
        block_table = np.zeros((cfg.max_pages_per_seq,), np.int32)
        block_table[: sp.num_pages] = sp.pages
        mm_kwargs: dict[str, Any] = {}
        mm_arrays: dict[str, np.ndarray] = {}
        if mm is not None:
            rel = mm["positions"] - start
            keep = rel >= 0
            rel = rel[keep]
            rows = mm["embeds"][keep]
            # pad to a power-of-two width (>= 8): one compiled shape per
            # width tier; padded positions point past the bucket -> the
            # injection scatter drops them
            m = max(8, 1 << max(0, int(rel.shape[0]) - 1).bit_length())
            pos_pad = np.full((m,), bucket, np.int32)
            pos_pad[: rel.shape[0]] = rel
            emb_pad = np.zeros((m, self.spec.hidden_size), np.float32)
            emb_pad[: rows.shape[0]] = rows
            mm_arrays = {"mm_embeds": emb_pad, "mm_pos": pos_pad}
            mm_kwargs = {
                "mm_embeds": jnp.asarray(emb_pad),
                "mm_pos": jnp.asarray(pos_pad),
            }
        if self.spmd is not None:
            self.spmd.publish(
                "prefill",
                {"start": start, "num_tokens": len(new_tokens)},
                {"tokens": padded, "block_table": block_table, **mm_arrays},
            )
        self._flush_state_releases()
        with self._launch(
            "prefill", tokens=len(new_tokens), rows=1,
            ahead=len(self._pipeline),
        ):
            logits, self.k_pages, self.v_pages, _ = self.fam.prefill(
                self.spec,
                self.params,
                jnp.asarray(padded),
                jnp.asarray(block_table),
                jnp.asarray(start, jnp.int32),
                self.k_pages,
                self.v_pages,
                jnp.asarray(len(new_tokens), jnp.int32),
                mesh=self.mesh,
                **mm_kwargs,
            )
        self.dispatches += 1
        self._count_prefill_kv(
            bucket, len(block_table), start, len(new_tokens)
        )
        return logits

    def _advance_partial_safe(self) -> None:
        p = self._partial
        try:
            self._advance_partial()
        except Exception as e:  # noqa: BLE001
            log.exception("chunked prefill failed for %s", p.waiting.context.id)
            self._partial = None
            self.allocator.release(p.sp.pages)
            self._post(
                p.waiting.out_q,
                {"token_ids": [], "finish_reason": "error",
                 "error": f"prefill failed: {e}"},
            )

    def _run_partial_chunk(
        self, waiting: _Waiting, sp: SeqPages, token_ids: list[int],
        start: int, end: int,
    ) -> jax.Array:
        """One chunk of a chunked prefill, counted by whether a decode
        burst was in flight at its launch (the ``ahead`` its
        ``engine.launch`` carries): ``chunked_prefill`` in
        profile_snapshot()."""
        FLIGHT.event(waiting.context.id, "prefill_chunk")
        self.chunked_prefill["chunks"] += 1
        if self._pipeline:
            self.chunked_prefill["chunks_behind_burst"] += 1
        return self._run_prefill_chunk(sp, token_ids, start, end)

    def _advance_partial(self) -> None:
        """Run the next chunk of the in-flight chunked prefill: behind
        the burst in flight when there is one (_step's comment says why
        that is sound). The last chunk completes as a single-prompt
        prefill does (_single_prefill_record): its row sampled at width
        1 off the chunk's own logits, so the slot joins the next burst
        through the wave feed like every other admission."""
        p = self._partial
        assert p is not None
        if p.waiting.context.is_stopped:
            self._partial = None
            self.allocator.release(p.sp.pages)
            self._post(
                p.waiting.out_q, {"token_ids": [], "finish_reason": "cancelled"}
            )
            self._publish_metrics()
            return
        end = min(p.done + self._prefill_chunk_max(), len(p.token_ids))
        logits = self._run_partial_chunk(
            p.waiting, p.sp, p.token_ids, p.done, end
        )
        p.waiting.prefill_seq = self._launch_seq
        p.done = end
        if end == len(p.token_ids):
            self._partial = None
            self._seal_prompt_blocks(p.sp, p.seq)
            self._drain_offload()
            pres = self._fused_first_tokens(logits[None, :], [p.waiting])
            self._complete_admissions([
                (p.slot_idx, p.waiting, p.seq, p.sp, p.token_ids,
                 p.max_tokens, (logits, None), pres[0] if pres else None)
            ])

    def _export_and_finish(
        self, slot: _Slot, sp: SeqPages, token_ids: list[int], tok: int,
        logprob_entry: dict | None = None,
    ) -> None:
        """Prefill-worker handoff: export prompt KV pages for remote decode."""
        page_ids = jnp.asarray(np.asarray(sp.pages, np.int32))
        kb, vb = self.fam.extract_pages(self.k_pages, self.v_pages, page_ids)
        # device arrays go straight to the transfer plane: with a live PJRT
        # transfer server the decode worker pulls device-to-device and the
        # payload never stages through host numpy
        params = self.transfer_source.export(
            kb,
            vb,
            num_tokens=len(token_ids),
            page_size=self.config.page_size,
        )
        # ride the handshake params so the decode side can refuse a
        # mismatched pool dtype before installing blocks (the packed fp8
        # and bf16 block layouts are not interconvertible in insert_pages)
        params["kv_dtype"] = self.kv_dtype
        pages, sp.pages = sp.pages, []  # ownership ends here (see _prefill)
        self.allocator.release(pages)
        item: dict[str, Any] = {
            "token_ids": [tok], "finish_reason": "length",
            "kv_transfer_params": params,
        }
        if logprob_entry is not None:
            # the decode handler relays this first-token item to the
            # client, so its logprob entry must ride along
            item["logprobs"] = [logprob_entry]
        self._post(slot.out_q, item)
        self._publish_metrics()

    def _resume_from_remote(self, slot_idx: int, waiting: _Waiting) -> None:
        """Decode-worker resume: pull prefilled KV, install, enter decode."""
        from dynamo_tpu.disagg.transfer import pull_kv_blocks, release_kv_blocks

        cfg = self.config
        req = waiting.request
        disagg = req.get("disagg") or {}
        kvp = dict(disagg.get("kv_transfer") or {})
        first_token = int(kvp.pop("first_token"))
        token_ids = list(req["token_ids"])
        max_tokens = self._decode_budget(req, len(token_ids))
        if max_tokens <= 1:
            # the remote-prefill token (already emitted by the handler) was
            # the whole budget; don't pull KV we'd never use
            release_kv_blocks(kvp)
            self._post(waiting.out_q, {"token_ids": [], "finish_reason": "length"})
            return

        # pop: the handler holds the request dict alive for the whole
        # decode; leaving the payload here would pin the prompt KV in host
        # RAM after it's installed into device pages
        staged = disagg.pop("_staged_kv", None)
        if staged is not None:
            # generate() already pulled the payload off the step path
            k_blocks, v_blocks, meta = staged
        else:
            # direct callers (tests, bypassing generate): blocking pull on
            # this admission thread
            k_blocks, v_blocks, meta = pull_kv_blocks(kvp, mesh=self.mesh)
        if int(meta.get("page_size", cfg.page_size)) != cfg.page_size:
            raise ValueError("page_size mismatch between prefill and decode")
        export_dtype = str(kvp.get("kv_dtype", "bf16"))
        if export_dtype != self.kv_dtype:
            # fail the request here, with a message naming the knob, rather
            # than letting insert_pages die on a shape error inside a
            # donated jit (exports from pre-kv_dtype builds default bf16)
            raise ValueError(
                f"disagg kv_dtype mismatch: prefill exported {export_dtype} "
                f"KV but this decode worker runs kv_dtype={self.kv_dtype} "
                "(set DYN_KV_DTYPE / EngineConfig.kv_dtype identically on "
                "both sides)"
            )

        # multimodal resume: the sealed blocks hold IMAGE-conditioned KV —
        # hash them under the same image salt the prefill side used, or
        # identical placeholder token ids would alias across images.
        # Prefer the salt the encode operator attached (only the digest
        # is needed here, not an MB-scale payload decode).
        mm_req = req.get("multimodal") or {}
        mm_salt = mm_req.get("salt")
        if mm_salt is None and mm_req:
            mm = self._decode_multimodal(req)
            mm_salt = mm["salt"] if mm else None
        seq = TokenBlockSequence.from_tokens(
            token_ids, cfg.page_size, salt=mm_salt
        )
        needed_pages = (len(token_ids) + cfg.page_size - 1) // cfg.page_size
        try:
            sp = self._acquire_prompt_pages(
                waiting.context.id, seq, needed_pages,
                n_tokens=len(token_ids), full_prefix_ok=True,
            )
        except OutOfPages:
            self._post(
                waiting.out_q,
                {"token_ids": [], "finish_reason": "error",
                 "error": "kv pages exhausted"},
            )
            return

        try:
            install = list(range(sp.cached_prefix_pages, needed_pages))
            if install:
                page_ids = jnp.asarray(
                    np.asarray([sp.pages[i] for i in install], np.int32)
                )
                self.k_pages, self.v_pages = self.fam.insert_pages(
                    self.k_pages, self.v_pages, page_ids,
                    jnp.asarray(k_blocks[:, install]),
                    jnp.asarray(v_blocks[:, install]),
                )
            self._seal_prompt_blocks(sp, seq)
            self._drain_offload()
        except Exception:
            self._pending_offload.clear()
            self.allocator.release(sp.pages)
            raise

        slot = self._make_slot(
            waiting, seq, sp,
            seq_len=len(token_ids),
            remaining=max_tokens - 1,
            generated=1,  # the remote-prefill token (emitted by the handler)
            last_token=first_token,
        )
        slot.seq.append(first_token)
        self._slots[slot_idx] = slot
        # the remote prefill already produced the first token: this is
        # the request's decode start for the flight timeline/spans
        FLIGHT.event(waiting.context.id, "disagg_resume")
        self._publish_metrics()

    # -- speculative decoding (runs in thread) -----------------------------

    def spec_snapshot(self) -> dict[str, Any]:
        """Speculation counters for bench/profile attribution: verify
        dispatches, draft outcomes, and the live acceptance rate."""
        judged = self.spec_accepted + self.spec_rejected
        return {
            "verifies": self.spec_verifies,
            "drafted": self.spec_drafted,
            "accepted": self.spec_accepted,
            "rejected": self.spec_rejected,
            "acceptance_rate": (
                round(self.spec_accepted / judged, 4) if judged else None
            ),
        }

    def guided_snapshot(self) -> dict[str, Any] | None:
        """Grammar compile-cache stats (compiles, hit rate, compile ms)
        for bench/profile attribution; None when guided is off."""
        return self._guided.snapshot() if self._guided is not None else None

    def _spec_managed(self, slot: _Slot) -> bool:
        """True while the slot takes the verify path INSTEAD of decode
        bursts. first_pending slots stay burst-managed: their first
        token is still on device, so the drafter has no host-side
        suffix to match yet (and the burst feed lands it for free)."""
        return (
            slot.spec is not None
            and slot.spec.active
            and not slot.first_pending
        )

    def _spec_phase(self) -> bool:
        """Draft + batched verify for every spec-managed slot not covered
        by an in-flight decode burst.

        Scheduling contract with the pipeline: a slot is EITHER
        burst-managed or spec-managed in any given cycle. _build_batch
        skips spec-managed slots, so their burst coverage drains within
        a cycle or two of the flag flipping, after which every
        cycle runs one packed verify (1..k+1 tokens per slot per
        dispatch). A slot whose drafter finds nothing still verifies at
        width 1 — it must emit a token this cycle — and the no-match
        counts into the acceptance EWMA, so persistently incompressible
        slots decay to k=0 and rejoin the bursts within a handful of
        one-token verifies (the <5% overhead story for random prompts).
        """
        cfg = self.config
        B = len(self._slots)
        covered = [False] * B
        for pb in self._pipeline:
            pbb = pb["batch"]
            for i in range(B):
                if pbb["active"][i] and self._slot_matches(i, pbb):
                    covered[i] = True
        cands: list[tuple[int, _Slot, list[int]]] = []
        with self._phase("spec.draft"):
            for i, slot in enumerate(self._slots):
                if slot is None or not self._spec_managed(slot):
                    continue
                if slot.context.is_stopped or covered[i]:
                    # stopped slots cancel through _build_batch; covered
                    # ones verify once their in-flight burst processes
                    continue
                if cfg.max_context - slot.seq_len < 2:
                    # defensive (unreachable: _decode_budget clamps
                    # remaining below the context cap): no room to write
                    # even the fed token safely
                    continue
                k_cap = min(
                    slot.remaining - 1,
                    cfg.max_context - slot.seq_len - 2,
                    cfg.spec_k_max,
                )
                slot.spec.sync_from_seq(slot.seq)
                draft = (
                    slot.spec.propose(k_cap) if k_cap > 0 else []
                )
                draft = [int(t) for t in draft]
                masks = None
                if slot.guided is not None and slot.guided.constraining:
                    # guided x spec: walk the draft on a SCRATCH cursor —
                    # the grammar-legal prefix becomes the draft (an
                    # off-grammar draft token could never be accepted
                    # against masked verify logits anyway) and the
                    # per-position masks ship into the verify dispatch.
                    # The real cursor is untouched, so a rejected tail
                    # needs no rollback by construction.
                    with self._phase("guided.lookahead"):
                        draft, masks = slot.guided.lookahead(draft)
                cands.append((i, slot, draft, masks))
        if not cands:
            return False

        # page room for the fed token + drafts (same backpressure story
        # as _build_batch: OutOfPages trims the draft to the pages held;
        # a slot that can't even hold its fed token stalls this cycle)
        ready: list[tuple] = []
        for i, slot, draft, masks in cands:
            m = 1 + len(draft)
            base_pages = slot.pages.num_pages
            while (slot.seq_len + m - 1) // cfg.page_size >= (
                slot.pages.num_pages
            ):
                try:
                    slot.pages.pages.append(self.allocator.alloc_page())
                    slot.pages.hashes.append(None)
                except OutOfPages:
                    m = min(
                        m,
                        slot.pages.num_pages * cfg.page_size - slot.seq_len,
                    )
                    break
            if m < 1:
                # not even the fed token fits: stall this cycle; a long
                # stall hands the slot back to the burst path, whose
                # backpressure accounting owns the give-up decision
                slot.stalled_steps += 1
                if slot.stalled_steps > 200:
                    slot.spec.disable()
                continue
            slot.stalled_steps = 0
            # page trimming only SHORTENS the draft; the lookahead masks
            # are per-position prefixes, so they stay aligned
            ready.append((i, slot, draft[: m - 1], base_pages, masks))
        if not ready:
            return False

        if FAULTS.enabled:
            try:
                # injected verify failure (site engine.spec_verify): the
                # contract is transparent per-slot fallback — rejected
                # BEFORE any KV write, so rollback is pure allocator
                # bookkeeping and the request decodes on untouched state
                FAULTS.fire_sync("engine.spec_verify")
            except Exception as e:  # noqa: BLE001
                with self._phase("spec.rollback"):
                    for _i, slot, _draft, base_pages, _masks in ready:
                        self.allocator.release(
                            slot.pages.truncate(base_pages)
                        )
                        slot.spec.disable()
                        # fault trips land on the affected timelines: the
                        # flight recorder is where "this request went
                        # non-spec mid-stream" becomes explainable
                        FLIGHT.event(
                            slot.context.id, "fault",
                            site="engine.spec_verify",
                        )
                log.warning(
                    "spec verify fault (%s): %d slot(s) fall back to "
                    "non-spec decode", e, len(ready),
                )
                return True

        # ONE packed dispatch: rows pad to a power of two (bounded
        # compiled-shape set, warmed by precompile's verify grid), token
        # width is the static spec_k_max+1; padded rows have
        # num_tokens=0 so every write lands on the trash page
        W = cfg.spec_k_max + 1
        n = 1
        while n < len(ready):
            n *= 2
        tokens = np.zeros((n, W), np.int32)
        bts = np.zeros((n, cfg.max_pages_per_seq), np.int32)
        starts = np.zeros((n,), np.int32)
        nts = np.zeros((n,), np.int32)
        allowed = None
        if any(masks is not None for _i, _s, _d, _bp, masks in ready):
            # [n, W, V] guided masks: row r position j constrains the
            # target's choice AFTER consuming draft[:j] — so a rejected
            # draft's correction token is itself grammar-legal. Free and
            # padded rows stay all-True.
            allowed = np.ones((n, W, self.spec.vocab_size), bool)
        for r, (_i, slot, draft, _bp, masks) in enumerate(ready):
            row = [slot.last_token, *draft]
            tokens[r, : len(row)] = row
            bts[r, : slot.pages.num_pages] = slot.pages.pages
            starts[r] = slot.seq_len
            nts[r] = len(row)
            if allowed is not None and masks is not None:
                for j in range(min(len(row), len(masks))):
                    allowed[r, j] = masks[j]
        with self._phase("spec.verify"):
            with self._launch("verify", tokens=int(nts.sum()), rows=len(ready)):
                targets, self.k_pages, self.v_pages, _ = self.fam.verify(
                    self.spec, self.params, jnp.asarray(tokens),
                    jnp.asarray(bts), jnp.asarray(starts),
                    self.k_pages, self.v_pages, jnp.asarray(nts),
                    mesh=self.mesh,
                    allowed=(
                        jnp.asarray(allowed) if allowed is not None else None
                    ),
                )
            self.dispatches += 1
            self._count_prefill_kv(W, bts.shape[1], starts, nts)
            with self._phase("dispatch.d2h_wait"):
                targets = np.asarray(targets)
        self.spec_verifies += 1
        for r, (i, slot, draft, _bp, _masks) in enumerate(ready):
            if self._slots[i] is not slot:
                continue  # defensive: slot replaced mid-phase
            self._process_verify(i, slot, draft, targets[r])
        self._publish_metrics()
        return True

    def _process_verify(
        self, slot_idx: int, slot: _Slot, draft: list[int],
        targets: np.ndarray,
    ) -> None:
        """Greedy accept-longest-prefix over one slot's verify row.

        ``targets[j]`` is the target's argmax AFTER consuming
        [last_token, draft[:j]] — so drafts are accepted while they
        equal the target's own choice, and ``targets[n_acc]`` (the
        correction on a mismatch, the bonus token when everything
        matched) always emits. Every emitted token runs through
        _accept_token, the single source of stop semantics: a
        max_tokens/EOS/stop boundary mid-verify cuts the stream at the
        exact boundary token, never into the rejected tail."""
        n_acc = 0
        while n_acc < len(draft) and int(targets[n_acc]) == draft[n_acc]:
            n_acc += 1
        drafted = len(draft)
        self.spec_drafted += drafted
        self.spec_accepted += n_acc
        self.spec_rejected += drafted - n_acc
        FLIGHT.event(slot.context.id, "spec_verify", accepted=n_acc)
        if drafted:
            SPEC_TOKENS.labels(outcome="accepted").inc(n_acc)
            SPEC_TOKENS.labels(outcome="rejected").inc(drafted - n_acc)
        slot.spec.observe(drafted, n_acc)

        # the emitted tokens run through the burst path's stop-semantics
        # loop (single source: _accept_token via _decide_burst), so a
        # max_tokens/EOS/stop boundary cuts at the exact token
        toks, finish = self._decide_burst(slot, targets[: n_acc + 1])
        # the fed token + the consumed accepted drafts are now cache
        # state (mirrors _process_burst's seq_len advance: the LAST
        # emitted token's KV write belongs to the next dispatch)
        slot.seq_len += len(toks)
        with self._phase("spec.rollback"):
            # release pages past the accepted prefix: rejected-tail
            # positions are beyond seq_len (masked, overwritten by the
            # next real write), but their PAGES must not stay pinned
            keep = (
                slot.seq_len + self.config.page_size - 1
            ) // self.config.page_size
            released = slot.pages.truncate(max(keep, 1))
            if released:
                self.allocator.release(released)
        self._maybe_seal(slot)
        self._drain_offload()
        item: dict[str, Any] = {"token_ids": toks, "finish_reason": finish}
        if finish is not None:
            self._finish(slot_idx, slot, finish, emit=False)
        self._post(slot.out_q, item)

    # -- decode (runs in thread) -------------------------------------------

    def _decode_step(self) -> bool:
        """One decode dispatch: ``decode_steps_per_dispatch`` model steps +
        on-device sampling fused into a single jit call (host dispatch and
        the device->host token sync amortize over the burst — the TPU
        analogue of vLLM's multi-step scheduling). Tokens sampled past a
        mid-burst EOS/stop are discarded host-side; their cache writes land
        either on the trash page or in pages released when the slot
        finishes.

        HOW LONG a burst is: ``decode_steps_admit_pending`` steps, not
        the full length, while a shorter burst lets somebody in sooner
        (_short_burst; PERF.md, PR 46). Every length is a program of its
        own, compiled ahead (_burst_lengths), and they compute the same
        tokens; ``decode_bursts.*`` counts the bursts of each.

        ``pipeline_decode=True`` keeps ONE burst queued behind the
        running one: burst k+1 dispatches with its fed tokens CHAINED ON
        DEVICE from burst k's sampled outputs, and only then is burst k's
        host copy read. The read returns when k ends, so k+1 has just
        started and is the only burst in flight while the thread streams
        k's tokens and re-admits. (A second queued burst buys nothing
        unless the device-to-host copy takes longer than a burst, and
        costs every prompt a burst of time to first token: PERF.md, PR 27.)

        WHEN k+2 is launched: as late as is safe, not as early as
        possible (_hold_queued_burst). The device needs k+2 only when k+1
        ends, and all an early launch covers is the few ms this thread
        takes to build and launch it; what it costs is that every prompt
        arriving under k+1 finds k+2 already in the device's in-order
        queue and the thread blocked on k+1's read, so its prefill runs
        a burst later (PERF.md, PR 43). So while an arrival could be
        admitted the moment it came (an empty queue beside a free slot,
        nothing else pending) the thread waits on the wake event until
        shortly before k+1 is expected to end, admits each arrival as it
        comes (its prefill stands directly behind the RUNNING burst), and
        only then builds k+2, with every slot admitted meanwhile fed
        through _wave_feed, and goes to block on k+1's read. With a
        queue that is never empty (closed loops) nothing is held and the
        launch is at once.

        WHEN a first token comes home: the moment its prefill has ended,
        where a hold is there to see it. An admission's sample is made
        inside its prefill's dispatch and its host copy started at once;
        the prefill of an admission made under k stands before k+1, so
        it ends early in the hold of k+2, and that hold posts the token
        from the wave's own download (_land_ready_waves; PERF.md, PR 47).
        Where no hold begins, the token comes home at the top of a cycle
        if its wave is ready then (_materialize_waves), else as column 0
        of its slot's first burst's download, a burst after it existed
        (_process_burst); ``first_tokens.*`` counts the three. The
        blocked read of a burst is not split to fetch a wave first.

        Stops are detected one burst late (discarded garbage, as with
        mid-burst EOS); cancels and admin ops flush the pipeline first
        (_step).

        Guided slots opt the engine out of pipelining for the cycles
        they are live: a pipelined burst would dispatch with a mask
        computed BEFORE the in-flight burst's tokens advanced the host
        automaton — a stale mask is a broken guarantee. Free-only
        batches keep the full pipeline.

        Returns True when device/stream work actually happened this
        cycle; False when nothing could be built (every live slot
        page-stalled or spec-managed) so the caller paces the loop with
        the idle wait instead of spinning hot."""
        if self.config.pipeline_decode and self._guided_live():
            # flush any in-flight bursts, then FALL THROUGH to the
            # synchronous single-step schedule below (guided slots need
            # fresh masks per dispatch)
            if self._pipeline:
                with self._phase("flush"):
                    self._flush_pipeline()
        elif self.config.pipeline_decode:
            held = self._hold_queued_burst()
            t_launch = self._clock()
            with self._phase("build_batch"):
                batch = self._build_batch(self._pipeline)
            if batch is None:
                if self._pipeline:
                    before = sum(s is not None for s in self._slots)
                    with self._phase("process"):
                        self._process_burst(self._pipeline.pop(0))
                    self._eager_readmit(
                        before - sum(s is not None for s in self._slots)
                    )
                    return True
                return False
            if (
                held
                and self._pipeline  # a preemption in the hold flushed it
                and _is_ready(self._pipeline[-1]["results"][0])
            ):
                # held too long: the running burst ended before this one
                # was launched, and the device idles for the launch. If
                # the thread was not just late, bursts have become shorter
                # than the times kept say (fewer live slots, a shorter
                # context), and no read after an overrun blocks twice in
                # a row to say so: forget the times, and the next two
                # cycles, not held, take them anew
                self.burst_hold["overran"] += 1
                self._burst_secs.pop(
                    self._pipeline[-1]["batch"]["n_burst"], None)
            # the programs launched since the last burst (prefills, their
            # samples) stand before this one on the device: with none, the
            # time between the two reads is the burst's own
            # (_note_burst_end)
            side = self._launch_seq - self._seq_at_burst
            with self._phase("dispatch"):
                results = self._dispatch_burst(
                    batch, chain=self._pipeline or None
                )
            self._seq_at_burst = self._launch_seq
            self._launch_secs.append(self._clock() - t_launch)
            self._pipeline.append(
                {"batch": batch, "results": results, "side": side})
            if len(self._pipeline) > 1:
                before = sum(s is not None for s in self._slots)
                with self._phase("process"):
                    self._process_burst(self._pipeline.pop(0))
                # slots the burst just freed re-fill NOW — their packed
                # prefill dispatches behind the in-flight burst and their
                # first tokens feed the NEXT burst's device chain, so a
                # replacement stream loses zero decode cycles
                self._eager_readmit(
                    before - sum(s is not None for s in self._slots)
                )
            return True
        with self._phase("build_batch"):
            batch = self._build_batch(None)
        if batch is None:
            return False
        before = sum(s is not None for s in self._slots)
        with self._phase("dispatch"):
            results = self._dispatch_burst(batch, chain=None)
        with self._phase("process"):
            self._process_burst({"batch": batch, "results": results})
        self._eager_readmit(
            before - sum(s is not None for s in self._slots)
        )
        return True

    def _hold_queued_burst(self) -> bool:
        """Between reading burst k and building burst k+2 (pipelined
        schedule only; see _decode_step): while an arrival could be
        admitted the moment it came, wait for arrivals until shortly
        before the running burst k+1 is expected to end, and admit each as
        it comes. Returns True when a hold was begun.

        Everything is observed, nothing configured. The hold is open
        (_hold_open) while a burst is in flight to hide behind, a slot is
        free, no partial is open and nothing else wants the thread; it is
        entered only with an empty queue, and ends the moment a pass
        leaves a request waiting (no slot, a partial opened, the budget
        spent): there is nothing to hold for then. An arrival whose
        admission reads its logits on the host (logprobs, a grammar, a
        remote-decode handoff: _needs_sync_admission) is left waiting so:
        its pass would block this thread behind the running burst, past
        the deadline. A pass that had to preempt a stream for its arrival
        (_preempt_batch_slot) has flushed the pipeline: the hold ends
        with nothing in flight, and the next burst is launched as after
        any flush. The deadline (_hold_deadline) is what this thread has
        measured; with no measurement there is no hold.

        A wake that is no arrival (close, drain, an admin op, a deadline's
        stop, an SPMD sync request) ends the hold: the flags are read
        after the event is cleared and every setter raises its flag
        first, so none is missed, and none waits longer than for the read
        of k+1 that would block this thread anyway.

        The hold lands as well as admits. The prefill of an admission
        made under burst k (in the hold before this one, or in the pass
        at the top of that cycle) stands before k+1 and ends early in
        this wait, and its sample is on the host a fraction of a ms later
        (_complete_admissions_async started the copy): with nothing to
        admit the thread posts every first token whose wave says ready
        (_land_ready_waves) and, while a live wave is still on its way,
        waits ``_WAVE_POLL_S`` at a time and looks again, until the same
        deadline. It asks and never reads by force: a prefill admitted in
        THIS hold stands behind k+1, no burst covers it yet, and a forced
        read would block this thread past the deadline. An arrival comes
        first: a wake with a request waiting goes to the admission pass,
        and the landing is made once the queue is empty again. What no
        hold saw ready still comes home at the top of _step
        (_materialize_waves) or on its slot's first burst
        (_process_burst), as without a hold. The wait is the ``idle``
        phase, the passes are the admission phases they always are, a
        landing is ``materialize``."""
        if not self._waiting.empty():
            return False
        deadline = self._hold_deadline()
        if deadline is None:
            return False
        begun = False
        while True:
            self._wake.clear()
            now = self._clock()
            if now >= deadline or not self._hold_open():
                break
            if not begun:
                begun = True
                self.burst_hold["begun"] += 1
            if self._waiting.empty():
                wait = deadline - now
                polling = self._land_ready_waves()
                if polling:
                    wait = min(wait, _WAVE_POLL_S)
                with self._phase("idle"):
                    woken = self._wake.wait(wait)
                if self._waiting.empty() and (woken or not polling):
                    break  # the deadline, or a wake that is no arrival
                continue
            self._holding = True
            try:
                self._admit_phase()
            finally:
                self._holding = False
            if not self._pipeline or not self._waiting.empty():
                # the pass preempted a stream for the arrival and flushed
                # the pipeline (nothing is left to hide behind), or
                # nothing more can be admitted
                break
        return begun

    def _hold_open(self) -> bool:
        """A request arriving now would be admitted at once, behind a burst
        in flight, and nothing else wants the step thread."""
        return (
            bool(self._pipeline)
            and self._partial is None
            and not self._chunk_cycle
            and not self._closed
            and not self._draining
            and not self._clear_cache_requested
            and self.spmd is None
            and self.config.async_admissions
            and any(s is None for s in self._slots)
            and not any(
                s is not None
                and (s.context.is_stopped or self._spec_managed(s))
                for s in self._slots
            )
            and not self._guided_live()
        )

    def _hold_deadline(self) -> float | None:
        """When the held burst must be built, on ``_clock``: the running
        burst's expected end less a guard. The end is the instant its
        predecessor's read returned, plus the programs that stand before
        it (as many launches at the least a launch has lately taken), plus
        the shortest of the last few undisturbed bursts of its length:
        every term errs early. (Without the middle term a hold behind a
        prefill ends ~13 ms early and ``ttft_p50_ms`` reads ~4% higher;
        asking the prefill's sample ``is_ready()`` at the hold's start
        reads the same, the answer is mostly yes by then: PERF.md, PR 43.)
        The guard is what building and launching a
        burst and one admission pass have lately cost this thread (the
        medians: a stall of the thread foretells nothing, and the largest
        would shut the hold for the eight cycles after one), and one
        decode step. None without those measurements: a burst length not
        yet timed, a read that did not block."""
        if not self._pipeline or self._burst_ended is None:
            return None
        running = self._pipeline[-1]
        n_burst = running["batch"]["n_burst"]
        secs = self._burst_secs.get(n_burst)
        if not secs or not self._launch_secs:
            return None
        burst = min(secs)
        before = running["side"] * min(self._side_secs, default=0.0)
        guard = (
            statistics.median(self._launch_secs)
            + (statistics.median(self._admit_secs) if self._admit_secs else 0.0)
            + burst / n_burst
        )
        return self._burst_ended + before + burst - guard

    def _note_burst_end(self, pending: dict, blocked: bool) -> None:
        """The read of a burst has returned. Where it blocked, now is when
        the burst ended. Where the read before it blocked too, and this
        burst was queued behind that one (_dispatch_burst), the time
        between the two instants is what stood between them on the
        device: the burst alone, or the burst behind the ``side`` programs
        launched before it, which leaves a time a launch of those once a
        burst of its length has been timed."""
        now = self._clock()
        before, self._burst_ended = self._burst_ended, (
            now if blocked else None)
        if not blocked or before is None:
            return
        n_burst, side = pending["batch"]["n_burst"], pending.get("side")
        if not side:
            self._burst_secs.setdefault(
                n_burst, collections.deque(maxlen=_BURST_SAMPLES)
            ).append(now - before)
        elif n_burst in self._burst_secs:
            rest = now - before - min(self._burst_secs[n_burst])
            if rest > 0:
                self._side_secs.append(rest / side)

    def _guided_live(self) -> bool:
        """True while any live slot is grammar-constrained (those cycles
        run the synchronous dispatch-process schedule)."""
        return any(
            s is not None
            and s.guided is not None
            and s.guided.constraining
            and not s.context.is_stopped
            for s in self._slots
        )

    def _flush_pipeline(self) -> None:
        """Process every in-flight burst (pipelined mode) so slot state is
        exact before cancels/admin mutate the batch."""
        pending, self._pipeline = self._pipeline, []
        for pb in pending:
            self._process_burst(pb)

    def _short_burst(self) -> bool:
        """Whether the next burst takes the short compiled length
        (``decode_steps_admit_pending``) and not the full one: while a
        shorter burst would let somebody in sooner, from what the thread
        observes as it builds the burst.

        An empty queue beside a free slot: arrivals pace the engine and
        each is admitted as it comes (the state in which
        _hold_queued_burst holds), so every ms of burst in flight is a
        ms the next prompt's prefill waits for the device, and as much
        again for a first token that no hold lands (_land_ready_waves)
        and that comes home on its slot's first burst.
        A queue that is not empty under half occupancy: the ramp-up, the
        next admission wave gets in sooner. Otherwise full bursts: a
        backlog beside a batch at least half full (a closed loop at
        saturation, an open loop past its knee: the host's cycle is paid
        half as often), or no free slot (nobody can be admitted sooner
        whatever the burst). Not occupancy alone: an open loop below its
        knee may well run over half full with nobody waiting."""
        n_active = sum(s is not None for s in self._slots)
        if self._waiting.empty():
            return n_active < len(self._slots)
        return n_active * 2 < len(self._slots)

    def _build_batch(self, pending: list[dict] | None) -> dict | None:
        """Assemble host-side arrays for the next burst.

        ``pending`` (pipelined mode) holds the dispatched-but-unprocessed
        bursts, oldest first: their participants have ``extra`` tokens
        already scheduled on device, so sequence lengths/pages/RNG-steps
        advance past them."""
        cfg = self.config
        B = cfg.max_decode_slots
        tokens = np.zeros((B,), np.int32)
        block_tables = np.zeros((B, cfg.max_pages_per_seq), np.int32)
        seq_lens = np.ones((B,), np.int32)
        active = np.zeros((B,), bool)
        temps = np.zeros((B,), np.float32)
        topk = np.zeros((B,), np.int32)
        topp = np.ones((B,), np.float32)
        seeds = np.zeros((B,), np.uint32)
        steps = np.zeros((B,), np.int32)

        MAX_STALL = 2000  # steps a slot may wait for a free page
        capacity = cfg.max_context

        extra = np.zeros((B,), np.int32)
        for p in pending or ():
            pb = p["batch"]
            for i in range(B):
                if pb["active"][i] and self._slot_matches(i, pb):
                    extra[i] += pb["n_burst"]

        # burst size: what an arrival would meet (_short_burst), then
        # bounded by every ready slot's room to the context cap (an
        # overshooting position would clamp-index into a LIVE page)
        n_burst = cfg.decode_steps_per_dispatch
        if cfg.decode_steps_admit_pending and self._short_burst():
            n_burst = max(1, min(n_burst, cfg.decode_steps_admit_pending))
        for i, slot in enumerate(self._slots):
            if (
                slot is not None
                and not slot.context.is_stopped
                and not self._spec_managed(slot)
            ):
                room = max(1, capacity - slot.seq_len - int(extra[i]))
                # rounded down to a compiled length (1 is always one)
                n_burst = max(
                    n for n in self._burst_lengths if n <= min(n_burst, room)
                )
                if slot.guided is not None and slot.guided.constraining:
                    # a constrained slot's mask is valid for exactly ONE
                    # token (the host automaton advances as tokens land),
                    # so the whole batch runs single-step — constrained
                    # and free slots still share the one dispatch
                    n_burst = 1

        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            if slot.context.is_stopped:
                if not pending:
                    self._finish(i, slot, "cancelled")
                # pipelined: _step flushed before cancels normally; a race
                # here just skips the slot — the next (flushed) step
                # finishes it
                continue
            if self._spec_managed(slot):
                # spec-managed: this slot's tokens come from the verify
                # path (_spec_phase); keeping it out of new bursts is
                # what lets speculation and bursts share one engine cycle
                continue
            if slot.remaining <= extra[i]:
                # the in-flight burst already covers this slot's budget
                continue
            # pages for every token this burst will EMIT (overshoot beyond
            # ``remaining`` scatters to the trash page via the zero-padded
            # block-table row)
            sched_len = slot.seq_len + int(extra[i])
            need = min(slot.remaining - int(extra[i]), n_burst)
            last_page = (sched_len + need - 1) // cfg.page_size
            stalled = False
            while last_page >= slot.pages.num_pages:
                try:
                    slot.pages.pages.append(self.allocator.alloc_page())
                    slot.pages.hashes.append(None)
                except OutOfPages:
                    # backpressure: stall this slot; a neighbor finishing
                    # will free pages. Only give up after a long stall.
                    slot.stalled_steps += 1
                    if slot.stalled_steps > MAX_STALL:
                        self._finish(
                            i, slot, "error",
                            error="kv pages exhausted (decode stalled "
                                  f"{slot.stalled_steps} steps)",
                        )
                    stalled = True
                    break
            if stalled:
                continue
            slot.stalled_steps = 0
            active[i] = True
            tokens[i] = slot.last_token  # chained on device when pipelined
            block_tables[i, : slot.pages.num_pages] = slot.pages.pages
            seq_lens[i] = sched_len + 1  # including the new token
            temps[i] = slot.temperature
            topk[i] = slot.top_k
            topp[i] = slot.top_p
            seeds[i] = slot.sample_seed
            steps[i] = slot.generated + int(extra[i])

        if not active.any():
            return None

        # one fixed logprob width when ANY slot asks: n_logprobs is a
        # static jit arg, so per-batch widths would recompile the fused
        # decode program every time the mix changes
        wants_lp = self.fam.supports_logprobs and any(
            s is not None and s.logprobs is not None for s in self._slots
        )
        n_lp = min(20, self.spec.vocab_size - 1) if wants_lp else 0

        # guided-decoding constraint mask for this burst: None unless a
        # participating slot is constrained (the all-free fast path pays
        # nothing — the unmasked program dispatches unchanged)
        allowed = None
        if any(
            active[i]
            and self._slots[i].guided is not None
            and self._slots[i].guided.constraining
            for i in range(B)
        ):
            with self._phase("guided.mask"):
                allowed = np.ones((B, self.spec.vocab_size), bool)
                for i in range(B):
                    slot = self._slots[i]
                    if (
                        active[i]
                        and slot.guided is not None
                        and slot.guided.constraining
                    ):
                        allowed[i] = slot.guided.mask()

        return {
            "n_burst": n_burst,
            "allowed": allowed,
            "n_lp": n_lp,
            "active": active,
            "participants": {
                i: self._slots[i].request_id
                for i in range(B)
                if active[i]
            },
            "tokens": tokens,
            "block_tables": block_tables,
            "seq_lens": seq_lens,
            "temps": temps,
            "topk": topk,
            "topp": topp,
            "seeds": seeds,
            "steps": steps,
        }

    def _slot_matches(self, i: int, batch: dict) -> bool:
        slot = self._slots[i]
        return slot is not None and slot.request_id == batch["participants"].get(i)

    def _dispatch_burst(self, batch: dict, chain: list[dict] | None):
        """Issue the fused decode; feed tokens from the in-flight bursts'
        device outputs when chaining (no host sync on the feed path).
        ``chain`` is oldest-first; newer bursts override older rows, so a
        slot inactive in the newest burst (page-stalled for one burst)
        still feeds from its latest on-device token."""
        if not chain:
            # launched behind nothing, it starts now and not where a
            # predecessor ends: its end times no burst (_note_burst_end)
            self._burst_ended = None
        # chain-validity masks: guard rows by request identity, exactly
        # like _build_batch's `extra` accumulation — a slot freed (EOS in
        # an older burst) and reused by a NEW request must not have the
        # dead request's stale in-flight token override its first token.
        # Computed ONCE and shipped in the descriptor so followers chain
        # with bit-identical masks.
        chain_valids = [
            np.fromiter(
                (
                    prev["batch"]["active"][i]
                    and self._slot_matches(i, prev["batch"])
                    for i in range(len(self._slots))
                ),
                dtype=bool, count=len(self._slots),
            )
            for prev in chain or ()
        ]
        if self.spmd is not None:
            arrays = {
                "tokens": batch["tokens"],
                "block_tables": batch["block_tables"],
                "seq_lens": batch["seq_lens"],
                "active": batch["active"].astype(np.int8),
                "temps": batch["temps"],
                "topk": batch["topk"],
                "topp": batch["topp"],
                "seeds": batch["seeds"],
                "steps": batch["steps"],
            }
            for i, v in enumerate(chain_valids):
                arrays[f"chain_valid_{i}"] = v.astype(np.int8)
            self.spmd.publish(
                "decode",
                {"n_steps": batch["n_burst"], "n_lp": batch["n_lp"],
                 "n_chain": len(chain_valids)},
                arrays,
            )
        tokens_in = self._feed_array(batch["tokens"])
        for valid, prev in zip(chain_valids, chain or ()):
            with self._launch("feed"):
                tokens_in = _chain_feed(
                    jnp.asarray(valid), prev["results"][0], tokens_in
                )
        for ap in self._admit_waves:
            # freshly admitted slots: feed their first token from the
            # device-side admission sample (its host copy is still in
            # flight — see _complete_admissions_async). Feed each slot's
            # FIRST burst only: later bursts dispatched before the wave
            # materializes must chain from the newer on-device samples,
            # not re-feed token 0.
            B = len(self._slots)
            mask = np.zeros((B,), bool)
            idx = np.zeros((B,), np.int32)
            for slot_idx, slot, row in ap["recs"]:
                if (
                    self._slots[slot_idx] is slot
                    and slot.first_pending
                    and batch["active"][slot_idx]
                    and slot_idx not in ap["fed"]
                ):
                    mask[slot_idx] = True
                    idx[slot_idx] = row
                    ap["fed"].add(slot_idx)
            if mask.any():
                with self._launch("feed"):
                    tokens_in = _wave_feed(
                        jnp.asarray(mask), jnp.asarray(idx), ap["dev"],
                        tokens_in,
                    )
        self.dispatches += 1
        n = batch["n_burst"]
        self.decode_bursts[
            "full" if n == self._burst_lengths[-1]
            else "single" if n == 1 else "short"
        ] += 1
        self._count_decode_kv(batch)
        allowed = batch.get("allowed")
        with self._launch(
            "decode", steps=batch["n_burst"],
            live=len(batch["participants"]), slots=len(self._slots),
            ahead=len(self._pipeline),
        ):
            # whose tokens _process_burst will post: stream.post's seq
            batch["seq"] = self._launch_seq
            result = self.fam.decode_steps(
                self.spec,
                self.params,
                tokens_in,
                jnp.asarray(batch["block_tables"]),
                jnp.asarray(batch["seq_lens"]),
                self.k_pages,
                self.v_pages,
                jnp.asarray(batch["active"]),
                jnp.asarray(batch["temps"]),
                jnp.asarray(batch["topk"]),
                jnp.asarray(batch["topp"]),
                jnp.asarray(batch["seeds"]),
                jnp.asarray(batch["steps"]),
                n_steps=batch["n_burst"],
                n_logprobs=batch["n_lp"],
                mesh=self.mesh,
                allowed=jnp.asarray(allowed) if allowed is not None else None,
            )
        if batch["n_lp"] > 0:
            sampled, lp, top_i, top_v, self.k_pages, self.v_pages = result
        else:
            sampled, self.k_pages, self.v_pages = result
            lp = top_i = top_v = None
        self.steps += batch["n_burst"]
        # the FED tokens ride along as column 0: freshly admitted slots'
        # first tokens (still device-only — _fused_first_tokens makes no
        # host copy) materialize from THIS download when the burst
        # processes, keeping the whole cycle at ONE device->host
        # transfer
        with self._launch("feed"):
            combined = _with_fed_column(tokens_in, sampled)
        # start the d2h NOW: by processing time (a cycle later) the copy
        # has landed and the host asarray is free — the fresh download
        # RTT rides under the next burst's execution
        try:
            combined.copy_to_host_async()
        except AttributeError:
            pass
        return (combined, lp, top_i, top_v)

    def _process_burst(self, pending: dict) -> None:
        """Sync a dispatched burst's tokens to host; apply stop semantics,
        seal pages, stream items. Participant request-ids guard against a
        slot that finished (and was discarded) between dispatch and
        processing."""
        batch = pending["batch"]
        sampled_dev, lp_dev, ti_dev, tv_dev = pending["results"]
        n_burst = batch["n_burst"]
        active = batch["active"]
        blocked = not _is_ready(sampled_dev)
        with self._phase("process.d2h_sync"), self._phase("dispatch.d2h_wait"):
            combined = np.asarray(sampled_dev)  # [B, 1 + n_burst]
        self._note_burst_end(pending, blocked)
        # column 0 is the burst's FED tokens (_dispatch_burst): the first
        # tokens of slots admitted into this burst land from this same
        # download — sequence order (first token before burst tokens)
        # holds because the wave lands before phase 1 below, and the
        # cycle needs no second device->host transfer
        fed_col, sampled = combined[:, 0], combined[:, 1:]
        if self._admit_waves:
            part = batch["active"]
            keep = []
            for ap in self._admit_waves:
                if any(
                    self._slots[si] is s and s.first_pending and part[si]
                    for si, s, _row in ap["recs"]
                ):
                    rest = self._materialize_one(
                        ap, fed_col=fed_col, fed=ap["fed"], part=part,
                        participants=batch["participants"],
                    )
                    if rest is not None:
                        keep.append(rest)
                else:
                    keep.append(ap)
            self._admit_waves = keep
        if lp_dev is not None:
            with self._phase("dispatch.d2h_wait"):
                lp = np.asarray(lp_dev)
                top_i = np.asarray(ti_dev)
                top_v = np.asarray(tv_dev)
        else:
            lp = top_i = top_v = None

        # phase 1: decide per-slot emit counts, advance cache state, seal.
        # Must fully precede phase 2: a finishing neighbor releases pages,
        # and a later alloc could evict a just-sealed page before the
        # offload extraction reads it.
        burst: dict[int, tuple[list[int], str | None]] = {}
        for i, slot in enumerate(self._slots):
            if slot is None or not active[i] or not self._slot_matches(i, batch):
                continue
            toks, finish = self._decide_burst(slot, sampled[i, :n_burst])
            burst[i] = (toks, finish)
            slot.seq_len += len(toks)  # the fed tokens are now in the cache
            if slot.spec is not None:
                # parked spec slot (k decayed to 0): count burst tokens
                # toward the next k=1 reprobe (engine/spec.py)
                slot.spec.on_tokens(len(toks))
            self._maybe_seal(slot)
        self._drain_offload()
        if burst:
            # telemetry feed: tokens this dispatch actually landed across
            # all participating slots (stops cut bursts short)
            race.write("engine.burst_fills")
            self.burst_fills.append(
                sum(len(toks) for toks, _f in burst.values())
            )

        # phase 2: stream tokens, finish slots
        with self._stream_post(batch.get("seq", 0)):
            for i, (toks, finish) in burst.items():
                slot = self._slots[i]
                item: dict[str, Any] = {
                    "token_ids": toks, "finish_reason": finish}
                if slot.logprobs is not None and lp is not None:
                    item["logprobs"] = [
                        {
                            "id": int(sampled[i, j]),
                            "logprob": float(lp[i, j]),
                            "top": [
                                {"id": int(top_i[i, j, t]),
                                 "logprob": float(top_v[i, j, t])}
                                for t in range(slot.logprobs)
                            ],
                        }
                        for j in range(len(toks))
                    ]
                if finish is not None:
                    self._finish(i, slot, finish, emit=False)
                self._post(slot.out_q, item)

        if self.steps % 16 < n_burst:
            self._publish_metrics()

    def _accept_token(self, slot: _Slot, tok: int) -> str | None:
        """Record one sampled token on the slot; return its finish reason
        (None = keep decoding). The single source of stop semantics for
        both the prefill first token and decode bursts."""
        slot.seq.append(tok)
        slot.generated += 1
        slot.remaining -= 1
        slot.last_token = tok
        if slot.guided is not None and not slot.guided.advance(tok):
            # defensive: every sampling path this slot touches is masked,
            # so an off-grammar token marks an unmasked escape hatch —
            # fail OPEN (free decoding, outcome=violation at finish)
            # rather than wedging or erroring a live stream
            log.warning(
                "guided slot %s emitted off-grammar token %d; "
                "constraint released", slot.request_id, tok,
            )
        if (
            not slot.ignore_eos
            and tok in slot.eos_ids
            and (
                slot.generated >= slot.min_tokens
                # a completed grammar leaves ONLY eos legal — honoring
                # min_tokens here would stream eos padding at the client
                # (done + not violated = eos landed on an accepting
                # state; an off-grammar eos keeps min_tokens semantics)
                or (
                    slot.guided is not None
                    and slot.guided.done
                    and not slot.guided.violated
                )
            )
        ):
            return "stop"
        if tok in slot.stop_token_ids and (
            slot.generated >= slot.min_tokens
            # stop tokens are folded into the grammar cursor's eos set
            # (_make_slot), so a completed grammar overrides min_tokens
            # here exactly as on the eos branch above
            or (
                slot.guided is not None
                and slot.guided.done
                and not slot.guided.violated
            )
        ):
            return "stop"
        if slot.remaining <= 0:
            return "length"
        return None

    def _decide_burst(
        self, slot: _Slot, sampled: np.ndarray
    ) -> tuple[list[int], str | None]:
        """Apply stop conditions token-by-token over a sampled burst;
        records accepted tokens on the slot and returns (tokens, finish)."""
        toks: list[int] = []
        finish: str | None = None
        for tok in sampled:
            tok = int(tok)
            toks.append(tok)
            finish = self._accept_token(slot, tok)
            if finish is not None:
                break
        return toks, finish

    # -- helpers -----------------------------------------------------------

    def _maybe_seal(self, slot: _Slot) -> None:
        """Seal the page whose block just completed (if any)."""
        n_complete = slot.seq_len // self.config.page_size
        for i in range(n_complete):
            if i < len(slot.pages.hashes) and slot.pages.hashes[i] is None:
                if i < len(slot.seq.blocks):
                    blk = slot.seq.blocks[i]
                    self.allocator.seal_page(
                        slot.pages.pages[i],
                        blk.sequence_hash,
                        blk.parent_sequence_hash,
                    )
                    slot.pages.hashes[i] = blk.sequence_hash
                    self._queue_offload(blk.sequence_hash, slot.pages.pages[i], i)

    def _emit_token(
        self, slot_idx: int, slot: _Slot, tok: int,
        logprob_entry: dict | None = None, seq: int = 0,
    ) -> None:
        """Record + stream one sampled token; place slot or finish.
        ``seq``: the launch number of the request's prefill, for the
        post's stream.post."""
        FLIGHT.event(slot.context.id, "first_token")
        finish = self._accept_token(slot, tok)
        if finish is not None:
            # release resources BEFORE posting the finish item, so a client
            # observing the end of stream sees the engine's pages freed.
            # (The finishing token was never written to the cache - it would
            # be written on the next step - which is fine: the request is over.)
            self._finish(slot_idx, slot, finish, emit=False)
        else:
            self._slots[slot_idx] = slot
        item: dict[str, Any] = {"token_ids": [tok], "finish_reason": finish}
        if logprob_entry is not None:
            item["logprobs"] = [logprob_entry]
        with self._stream_post(seq):
            self._post(slot.out_q, item)

    def _finish(
        self, slot_idx: int, slot: _Slot, reason: str,
        *, error: str | None = None, emit: bool = True,
    ) -> None:
        if emit:
            item: dict[str, Any] = {"token_ids": [], "finish_reason": reason}
            if error:
                item["error"] = error
            self._post(slot.out_q, item)
        if slot.guided is not None:
            # "ok" strictly means conformance DELIVERED: the grammar
            # reached acceptance before the stream ended. max_tokens or
            # a stop sequence can cut a legally-masked stream mid-
            # grammar — that is "truncated" (the client got a prefix,
            # not a document), and cancels/engine errors are "aborted";
            # neither may inflate the conformance count.
            if slot.guided.violated:
                outcome = "violation"
            elif reason in ("stop", "length"):
                outcome = "ok" if slot.guided.conformant else "truncated"
            else:
                outcome = "aborted"
            GUIDED_REQUESTS.labels(outcome=outcome).inc()
        pages, slot.pages.pages = slot.pages.pages, []
        self.allocator.release(pages)
        self._slots[slot_idx] = None
        self._publish_metrics()
