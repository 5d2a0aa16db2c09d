"""The reviewable registries DL006 checks against.

Adding a fault site or metric is a two-line diff *here* plus the code —
which is the point: the catalog shows up in review, chaos schedules and
dashboards reference these exact strings, and dynalint fails on drift in
either direction (unknown name used, or catalogued name unused).

``FAULT_SITES`` mirrors ``dynamo_tpu.runtime.faults.KNOWN_SITES`` — the
runtime complement that warns when a ``DYN_FAULTS`` spec names a site no
code declares. tests/test_static_analysis.py asserts the two sets match.
"""

from __future__ import annotations

# site -> where it fires / what failure it simulates
FAULT_SITES: dict[str, str] = {
    "transport.connect": "runtime/transport.py dial — peer unreachable",
    "transport.send": "runtime/transport.py request send — cut connection",
    "transport.recv": "runtime/transport.py rx loop — channel dies mid-stream",
    "transport.partition": "runtime/hub_replica.py replica links — "
                           "address-pair-scoped partition (drop=A|B "
                           "symmetric, A>B one-way): refuses dials, kills "
                           "sync streams, eats follower acks",
    "hub.dial": "runtime/hub_client.py connect — hub unreachable",
    "hub.call": "runtime/hub_client.py RPC — lossy hub link",
    "hub.wal_append": "runtime/hub_store.py WAL append — disk write fails",
    "hub.fsync": "runtime/hub_store.py per-append fsync — slow/failing "
                 "durable disk on the mutation path",
    "hub.snap_fsync": "runtime/hub_store.py snapshot fsync — compaction "
                      "failure (counted, survived on the uncompacted WAL)",
    "engine.step": "engine/core.py step thread — device step fails/stalls",
    "engine.admit": "engine/core.py admission — worker vanishes pre-admit",
    "engine.compile": "engine/core.py precompile — slow/failing shape "
                      "warmup (serving must come up and eat the compile "
                      "at first use)",
    "engine.spec_verify": "engine/core.py speculative verify — dispatch "
                          "failure must fall back to non-spec decode for "
                          "the affected slots (pages rolled back, no "
                          "client-visible error)",
    "engine.guided_compile": "guided/runtime.py grammar compile — a "
                             "failing grammar->mask compile must bounce "
                             "the request as a typed 400 (no slot, no "
                             "page, counter trip), never wedge a stream",
    "engine.quant": "engine/core.py quantized-onboard validation — "
                    "corrupt fp8 tier block (bad scale bytes): must be "
                    "treated as a tier miss + re-prefill, never a "
                    "NaN-poisoned page",
    "engine.preempt": "engine/core.py priority preemption — an injected "
                      "error SKIPS the preemption (the interactive "
                      "request waits; the batch victim keeps running): "
                      "serving degrades, never breaks, and page "
                      "accounting stays clean",
    "epp.breaker": "gateway/epp.py pick path — an injected error records "
                   "a FAILURE outcome against the picked instance, so "
                   "chaos schedules can drive a breaker through "
                   "eject -> half-open -> recovery without a genuinely "
                   "sick worker",
    "disagg.pull": "disagg/transfer.py KV pull — transfer plane failure",
    "kvbm.onboard": "kvbm/pool.py + manager.py tier block on receipt — "
                    "silent bit flips in offloaded KV (corrupt action): "
                    "checksum must catch it as a tier miss, never decode "
                    "a poisoned page",
    "migration.resume": "runtime/integrity.py resume-prompt intake — "
                        "corrupt the migrated token ids on the wire: the "
                        "checksum mismatch must re-drive the migration, "
                        "never prefill a poisoned prompt",
    "health.canary": "runtime/health.py SDC canary — corrupt the "
                     "known-answer probe's output tokens: the golden "
                     "mismatch must quarantine the worker "
                     "(dynamo_worker_quarantines_total{reason=\"sdc\"})",
}

# engine step-thread profiler phase names (engine/core.py _phase /
# READMIT_SUMS / profile_snapshot) -> meaning. With
# EngineConfig.profile every _phase name is also an ``engine.<name>``
# annotation in a jax.profiler trace (PROFILER_ANNOTATIONS below). DL006-style registry for the
# SAME reason as METRIC_NAMES: benchmarks/profile_engine.py's
# attribution sections, perfbench's span readers, and the
# dashboards built on profile snapshots reference these exact strings —
# a renamed phase silently zeroes every consumer. Two-way sync with the
# code is test-enforced (tests/test_dispatch_profile.py).
PROFILE_PHASES: dict[str, str] = {
    "idle": "step thread parked waiting for work",
    "spmd_sync": "rejoining follower state-sync service",
    "materialize": "async admission-wave first-token landings",
    "flush": "pipeline flush before cancels/admin ops",
    "admit_loop": "admission dequeue + page acquisition (every pass, "
                  "admitting or not)",
    "packed_prefill": "packed prefill dispatch(es) for the step",
    "advance_partial": "the next chunk of an in-flight chunked prefill "
                       "(and its admission, on the last chunk)",
    "complete_admissions": "first-token sample + emit for admissions",
    "eager_readmit": "same-cycle re-admission pass after a burst freed slots",
    "readmit_wait": "bounded wait for a closed-loop resubmission",
    "build_batch": "host-side burst assembly",
    "dispatch": "decode burst dispatch (host issue time)",
    "process": "burst processing (stop semantics, seal, stream)",
    "process.d2h_sync": "burst token download sync inside process",
    # the three per-request sums come from the flight recorder's timeline
    # at finish (runtime/flight.py Timeline.admission_phases)
    "readmit.admit_wait": "enqueue (or preemption) -> admit event",
    "readmit.prefill_dispatch": "admit -> prefill_dispatch event "
                                "(prefill+sample dispatched)",
    "readmit.first_token": "prefill_dispatch -> first_token event (the "
                           "host has the first token)",
    "dispatch.d2h_wait": "step thread blocked on device->host transfers "
                         "(outside admission phases)",
    "readmit.d2h_wait": "d2h blocks nested inside admission phases "
                        "(sync-admission device_get, aged wave "
                        "materialization) — already inside the readmit "
                        "phase sums",
    "dispatch.dispatches": "jitted device programs issued (count)",
    "dispatch.compile": "backend compile events since engine build",
    "window.at": "the instant the snapshot was taken (secs = "
                 "time.monotonic()): two snapshots say how far apart "
                 "they are, whatever the caller meant the window to be",
    "spec.draft": "prompt-lookup drafting over spec-managed slots",
    "spec.verify": "packed speculative-verify dispatch + target sync",
    "spec.rollback": "page release of rejected draft tails (and the "
                     "injected-verify-failure fallback)",
    "guided.mask": "host-side [B, V] allowed-mask assembly for "
                   "constrained slots (burst + admission sampling)",
    "guided.lookahead": "scratch-cursor draft walk for guided x spec "
                        "verify (per-position masks, no state mutation)",
    "preempt": "priority preemption: pipeline flush + seal/offload + "
               "resume-request rebuild for one paused batch stream",
}

# always-on host counters profile_snapshot() carries beside the phases
# (counts in ``calls``, zeroed by reset_profile_window()) -> what they
# count. Readers (perfbench/readers/latent.py, a builder's traced pair in
# PERF.md) reference these exact strings; two-way sync with what engines
# of the three families report is test-enforced
# (tests/test_dispatch_profile.py). ``moe.*`` are device-side and named
# in docs/OBSERVABILITY.md.
PROFILE_COUNTERS: dict[str, str] = {
    "decode_kv.pages_live": "pages holding a live slot's context, over "
                            "the dispatched bursts' slots and steps",
    "decode_kv.pages_fetched": "pages of the chunks the decode kernel "
                               "fetched and scored for them",
    "decode_kv.pages_table": "slots x table width x steps: what a kernel "
                             "that followed the table would move",
    "kv.window_layer_tokens": "tokens the live slots hold at each decode "
                              "step x the model's window layers (a model "
                              "with window layers only)",
    "kv.window_dead_tokens": "of those, the tokens more than the layer's "
                             "window behind their row's length: what "
                             "pages found by layer kind would free",
    # a model whose upper layers write no cache (SambaY's cross-decoder:
    # the regions gmu, attn_cross and attn_diff beside scan, whose prefill
    # kernel scan_chunk is a LEAF of that region, models/regions.py:
    # LEAVES, and no region of its own) only
    "kv.shared_read_tokens": "tokens the live slots hold at each decode "
                             "step x the layers that read ANOTHER layer's "
                             "pages (attn_cross): what the one shared pool "
                             "is read for beyond its own layer",
    "prefill.rows": "prompt tokens the prefill programs took through the "
                    "layers below ModelSpec.carried_from (scan, attn_window, "
                    "attn_full)",
    "prefill.cross_rows": "rows, one a sequence with tokens, they took "
                          "through the layers above (gmu, attn_cross): "
                          "cross_rows / rows is ~1 / the prompts' length",
    "kv_pool.heads_per_lane_row": "KV heads a row of an attention kind's K "
                                  "pool holds (ops/attention.pool_head_dim: "
                                  "2 where 64-wide heads pack two a "
                                  "128-lane row, else 1); a gauge, fixed at "
                                  "the build",
    "kv_pool.bytes": "bytes of the page pools, both sides and every "
                     "kind; a gauge, fixed at the build",
    "prefill_kv.blocks_visited.full": "block steps the prefill walk ran "
                                      "on full-attention layers",
    "prefill_kv.blocks_table.full": "block steps a walk of the whole "
                                    "table would run there",
    "prefill_kv.blocks_visited.window": "the same on window layers",
    "prefill_kv.blocks_table.window": "and a whole-table walk's there",
    "prefill_kv.blocks_visited.latent": "the same for the latent "
                                        "family's walk, and for a latent "
                                        "kind's beside other kinds",
    "prefill_kv.blocks_table.latent": "and a whole-table walk's there",
    "prefill_kv.dispatches.latent": "prefills, packs and verifies "
                                    "dispatched on the latent family",
    "prefill_kv.kernel_calls.latent": "those whose attention the Mosaic "
                                      "kernel served (latent_prefill.py), "
                                      "not the XLA walk",
    "chunked_prefill.chunks": "chunks of chunked prefills launched (a "
                              "long prompt's first chunk included)",
    "chunked_prefill.chunks_behind_burst": "those launched with a decode "
                                           "burst in flight (ahead >= 1): "
                                           "queued behind device work, "
                                           "not after a drained device",
    "burst_hold.begun": "cycles in which the queued decode burst was held "
                        "for arrivals (_hold_queued_burst)",
    "burst_hold.overran": "those at whose end the running burst had "
                          "already finished: the device idled while the "
                          "held burst was launched",
    "burst_hold.admissions": "requests admitted (an admission pass's "
                             "count, in a hold or not)",
    "burst_hold.admissions_held": "those admitted during a hold: their "
                                  "prefill was launched directly behind "
                                  "the running burst (held=1)",
    "decode_bursts.full": "decode bursts dispatched at "
                          "decode_steps_per_dispatch steps",
    "decode_bursts.short": "those dispatched at decode_steps_admit_pending "
                           "steps: the queue was empty beside a free slot, "
                           "or not empty under half occupancy (_short_burst)",
    "decode_bursts.single": "those of one step (a guided mask, the last "
                            "tokens before the context cap)",
    "first_tokens.in_hold": "async admissions' first tokens posted from "
                            "their wave's own download while the queued "
                            "burst was held (_land_ready_waves): the "
                            "moment the prefill had ended",
    "first_tokens.at_step": "those posted from it at the top of a cycle "
                            "(_materialize_waves; a flush's or a close's "
                            "forced reads too)",
    "first_tokens.on_burst": "those posted from the fed column of their "
                             "slot's first burst's download "
                             "(_process_burst): a burst after they existed",
    # a model with recurrent (KDA) layers only
    "kda.decode_rows": "state rows a kda_step call updated (live slots), "
                       "over the dispatched bursts' steps; a layer's worth",
    "kda.prefill_blocks": "blocks of 64 tokens with a real token that "
                          "kda_chunk carried a state through, over the "
                          "dispatched prefills; a layer's worth",
    "kda.rows_resumed": "rows of prefill programs that continued a KDA "
                        "state (start_pos > 0): a chunk behind a prompt's "
                        "first",
    # a model with SSD (Mamba-2) layers only
    "ssd.decode_rows": "state rows an ssd_step call updated (live slots), "
                       "over the dispatched bursts' steps; a layer's worth",
    "ssd.prefill_chunks": "chunks of mamba_chunk_size tokens with a real "
                          "token that the SSD chunk form carried a state "
                          "through, over the dispatched prefills; a "
                          "layer's worth",
    "ssd.rows_resumed": "rows of prefill programs that continued a state "
                        "(start_pos > 0): a chunk behind a prompt's first",
    "recurrent_state.rows": "state rows the engine holds (a gauge)",
    "recurrent_state.rows_live": "rows a live sequence owns now: decode "
                                 "slots and the open chunked prefill",
    "recurrent_state.claims": "rows claimed by a prefill at position 0, "
                              "by the device-side directory's own count",
    "recurrent_state.row_missing": "sequences whose row was not where "
                                   "their block table says (their output "
                                   "is wrong): must read 0",
    "recurrent_state.prefill_chunks": "members of prefill programs with a "
                                      "real token (a prompt or a chunk of "
                                      "one), over the dispatched prefills; "
                                      "host-side, a layer's worth",
    "recurrent_state.rows_resumed": "those of them that resumed a state or "
                                    "a tail (start_pos > 0): a chunk "
                                    "behind a prompt's first",
    # the delivery path: a profiled engine only (0 otherwise), written on
    # the event loop (engine/core.py _stream_take)
    "stream.items": "token-carrying items generate() took off its queue "
                    "that a stream.post span had marked",
    "stream.wait_us": "the time they lay between the step thread's post "
                      "and that take, summed (us): over stream.items, a "
                      "window's mean wait",
    # the event loop's heartbeat (runtime/loop_probe.py): always on
    "event_loop.stalled_us": "the sum of the 50 ms sleep's lags over 50 ms "
                             "(us): over the time between two snapshots "
                             "(window.at), the share of it the loop stood "
                             "still; each wake-up's lag is in the probe's "
                             "ring (LoopProbe.lags)",
}

# jax.profiler.TraceAnnotation names the profiled engine writes into a
# profiler trace besides ``engine.<phase>`` for every _phase name above
# (engine/core.py, EngineConfig.profile) -> what they mark and the
# attributes they carry. perfbench/lib/spans.py reads these exact
# strings; two-way sync with the code is test-enforced
# (tests/test_dispatch_profile.py).
PROFILER_ANNOTATIONS: dict[str, str] = {
    "engine.launch": "one device program the step thread issues: kind "
                     "(prefill | decode | verify | sample | logprobs | "
                     "feed), seq (running launch number), and the host "
                     "counts it was built from (prefill/verify: tokens, "
                     "rows; decode: steps, live, slots; sample: rows; "
                     "prefill and decode: ahead, the decode bursts in "
                     "flight at the launch)",
    "engine.clock": "once a step-loop cycle: mono_ns = "
                    "time.monotonic_ns(), to fit the profiler's clock to "
                    "the flight recorder's and the clients'",
    # the delivery path. NOT under ``engine.``: perfbench/lib/spans.py
    # takes every ``engine.*`` annotation for a step-thread phase
    "stream.post": "step thread, around the posts of one device "
                   "program's tokens (phase 2 of _process_burst; an "
                   "admission wave's landing): seq (the launch number of "
                   "that decode burst, or of the prefill whose sample the "
                   "wave is)",
    "stream.take": "event loop, generate() takes a marked item off its "
                   "queue: rid (the stream's running number), wait_us "
                   "(take minus post: the take's instant less it is its "
                   "stream.post's)",
    "loop.stall": "event loop, the heartbeat woke over 50 ms late: "
                  "lag_us: the loop stood still for that long up to the "
                  "annotation's instant",
}

# flight-recorder event names (runtime/flight.py FLIGHT.event) the engine
# records -> the instant they mark. /debug/timeline consumers and
# perfbench/lib/spans.py reference these exact strings; two-way sync with
# engine/core.py is test-enforced (tests/test_dispatch_profile.py).
FLIGHT_EVENTS: dict[str, str] = {
    "admit": "the step thread took the request off the waiting queue",
    "prefill_chunk": "one chunk of a chunked prefill dispatched",
    "prefill_dispatch": "the prefill that took the last prompt tokens and "
                        "the first-token sample are dispatched; seq = the "
                        "engine.launch number of that prefill program",
    "first_token": "the host has the first token's value (step thread)",
    "first_delta": "generate() hands the stream its first tokens (event "
                   "loop): the engine's side of time to first token",
    "delta": "a profiled engine: generate() hands the stream later tokens; "
             "repeats coalesce (n = items after the first, t_last = the "
             "last of them), and with ``generated`` on the finish the "
             "LAST delta entry gives the engine's side of a stream's "
             "time per output token",
    "disagg_resume": "decode-side resume from remotely prefilled KV",
    "spec_verify": "one speculative verify landed (accepted = n)",
    "preempt": "paused for a higher-priority admission, re-queued",
    "shed": "bounced from the waiting queue under overload",
    "fault": "an injected fault fired on this request's path",
}

# span name (runtime/tracing.py span()/emit_span()) -> what it times.
# Same two-way discipline as FAULT_SITES/METRIC_NAMES (DL006): a span
# name not catalogued here fails the scan (dashboards and the e2e trace
# tests reference these exact strings), and a catalogued name no code
# emits warns as stale. tests/test_observability.py asserts the whole
# catalog is emitted by the instrumented smoke path.
SPAN_NAMES: dict[str, str] = {
    "http.request": "frontend route handling, admission -> stream "
                    "complete (chat/completions/responses/embeddings)",
    "http.preprocess": "render + tokenize on the compute pool",
    "epp.pick": "EPP routing decision (tokenize, KV score, resolve)",
    "transport.call": "client-side endpoint call, dispatch -> "
                      "end-of-stream (runtime/component.py)",
    "migration.resume": "backoff wait after a stream death; the "
                        "re-driven attempt is the next transport.call "
                        "span in the same trace (frontend/migration.py)",
    "disagg.pull": "decode-side staging of remote prefill KV",
    "worker.request": "worker-side request lifecycle, enqueue -> "
                      "finish (runtime/flight.py, child of the "
                      "caller's transport.call)",
    "engine.queue_wait": "admission-queue wait, enqueue -> step-thread "
                         "dequeue",
    "engine.prefill": "admit -> first token (prefill chunk count attr)",
    "engine.decode": "first token -> finish, aggregated per request",
    "engine.spec": "speculative-verify activity, first -> last verify",
    "engine.guided_compile": "grammar -> token-mask automaton compile "
                             "(or LRU fetch) before admission "
                             "(engine/core.py generate)",
}

# step-thread / hot-loop roots for the DL010 host-sync analysis, spelled
# "path/suffix.py::Qualified.name". The jit registry ALSO discovers hot
# roots structurally (any ``threading.Thread(target=...)`` entry point);
# this catalog pins the ones the serving SLO actually rides on, so a
# refactor that loses the structural marker still keeps the closure rooted.
HOT_PATH_ROOTS: dict[str, str] = {
    "dynamo_tpu/engine/core.py::InferenceEngine._thread_loop":
        "the engine step thread — owns the device; every unaccounted "
        "host<->device sync here is serial time added to EVERY decode "
        "step",
}

# capability gates whose False branch downgrades a fused/quantized path
# to a slower generic one. DL014 requires the downgrade branch to account
# for itself (ops.fallback.note_fallback / a log call) — ROADMAP #7's
# "fp8 + tp>1 silently takes the XLA path" is the incident class.
FALLBACK_GATES: dict[str, str] = {
    "use_pallas": "ops/attention.py — Pallas kernels enabled "
                  "(DYNAMO_PALLAS / on-TPU default)",
    "supports_fused": "generic capability probe spelling",
}

# cross-thread shared state the concurrency tooling tracks, spelled
# "owner.attr". This is the SAME registry as tools/dynarace/registry.py
# SHARED_STATE — dynalint's static DL005 layer and dynarace's dynamic
# happens-before layer must agree on what the cross-thread state IS, so
# the two copies are test-enforced identical (tests/test_dynarace.py,
# the DL006 fault-site discipline). DL005 findings whose attribute
# matches a catalogued suffix cite the entry's documented discipline.
SHARED_STATE: dict[str, str] = {
    "engine.step_times": (
        "engine/core.py step-latency deque — step thread appends, "
        "telemetry sampler (event loop) drains via popleft; GIL-atomic "
        "bounded deque, no lock (suppressed, see suppressions.py)"
    ),
    "engine.burst_fills": (
        "engine/core.py burst-fill deque — same single-appender/"
        "single-drainer deque discipline as engine.step_times"
    ),
    "flight.timeline": (
        "runtime/flight.py timeline ring (events/attrs/retention "
        "buckets) — step thread and event loop both enter; EVERY access "
        "must hold FlightRecorder._lock (flight.lock), including "
        "snapshot reads (the pre-dynarace snapshot-outside-lock race)"
    ),
    "kvbm.checksums": (
        "kvbm/manager.py block-checksum dict — offload thread stamps on "
        "offer, step thread reads on onboard and pops on corruption; "
        "guarded by kvbm.manager.lock (the pre-dynarace unguarded-dict "
        "race)"
    ),
    "hub.capture_log": (
        "runtime/hub_store.py compaction capture list — event-loop-only "
        "mutation; the snapshot worker thread sees state only through "
        "the hub.snapshot to_thread hand-off edge"
    ),
}

# metric name (without the dynamo_ prefix MetricsRegistry adds) -> meaning
METRIC_NAMES: dict[str, str] = {
    "http_requests_total": "HTTP requests by model/route/status",
    "time_to_first_token_seconds": "TTFT histogram by model",
    "inter_token_latency_seconds": "ITL histogram by model",
    "request_duration_seconds": "end-to-end request duration by model",
    "output_tokens_total": "generated tokens by model",
    "input_tokens_total": "prompt tokens by model",
    "requests_completed_total": "requests that reached the backend",
    "inflight_requests": "in-flight request gauge by model",
    "hub_compaction_failures_total": "hub snapshot-compaction failures "
                                     "(serving continues on the "
                                     "uncompacted WAL)",
    "hub_elections_total": "hub replica election rounds by outcome "
                           "(won/lost/pre_lost)",
    "hub_term": "current fencing epoch (election term) per hub replica",
    "hub_redirects_total": "hub client write bounces by reason "
                           "(not_leader | no_quorum | unavailable) — a "
                           "redirect-chase storm during failover is a "
                           "first-class signal, not an inference from "
                           "latency (sim leader-kill scenario asserts "
                           "on it)",
    "hub_backoff_seconds": "seconds the hub client slept between "
                           "redirect hops (server-hinted and "
                           "exponential backoff alike)",
    "spec_tokens_total": "speculative draft tokens by verify outcome "
                         "(accepted | rejected) — the live acceptance "
                         "rate of prompt-lookup decoding",
    "guided_requests_total": "guided-decoding requests by outcome "
                             "(ok | truncated | violation | aborted | "
                             "compile_error | unavailable) — conformance "
                             "delivered vs cut mid-grammar vs bounced at "
                             "the grammar compiler",
    # stream plane (runtime/transport.py, every /metrics surface via the
    # module registry)
    "transport_frames_total": "data-plane frames sent by kind "
                              "(open | data | end | err | cancel) — a "
                              "coalesced data frame counts ONCE however "
                              "many payloads it carries, so frames/token "
                              "< 1 is the coalescing win the STREAM_r0x "
                              "artifacts assert",
    "transport_flush_bytes": "bytes handed to the transport per corked "
                             "flush (batch-size histogram of the "
                             "one-flush-per-tick writer)",
    # EPP pick-path telemetry (gateway/epp.py /metrics)
    "epp_pick_seconds": "EPP pick-path latency histogram",
    # KV-router data plane (kv_router/router.py, on every /metrics
    # surface via the module registry)
    "router_pick_seconds": "KV routing decision latency by phase "
                           "(hash | overlap | select) — the per-pick "
                           "attribution the ROUTER_r0x artifacts and "
                           "router panels read",
    "router_shard_id": "prefix-hash shard this router process serves "
                       "(0-based; 0 when unsharded) — joins a shard's "
                       "metrics to its slice of the shard map",
    "epp_cache_lookups_total": "EPP prefix-cache lookups by cache "
                               "(cards | instances) and outcome "
                               "(hit | miss)",
    # worker telemetry registry (engine/telemetry.py, on every /metrics
    # surface incl. the worker status server)
    "engine_step_seconds": "engine step-thread cycle latency histogram "
                           "(work cycles only)",
    "engine_burst_tokens": "tokens landed per processed decode burst",
    "engine_pages": "KV page pool gauge by state "
                    "(active | cached | free)",
    "engine_slots_active": "decode slots currently running",
    "engine_batch_occupancy": "active slots / max_decode_slots (0..1)",
    "engine_waiting_requests": "admission queue depth",
    "engine_dispatches_total": "jitted device programs issued",
    "engine_moe_counts_total": "expert layers' device-side counters by "
                               "phase and what (steps, assignments, "
                               "experts_touched, expert.<i>; zero_picks, "
                               "ffn_picks with identity experts)",
    "engine_window_tokens_total": "a model with window layers: tokens "
                                  "its live slots held a decode step x "
                                  "window layers (held) and those past "
                                  "their window (dead)",
    "engine_carried_rows_total": "a model whose upper layers write no "
                                 "cache: prompt tokens through the lower "
                                 "layers (rows), rows through the upper "
                                 "(cross_rows), live tokens x layers that "
                                 "read another's pages a decode step "
                                 "(shared_read_tokens)",
    "engine_admission_rejects_total": "requests refused at admission by "
                                      "reason (draining | saturated | "
                                      "deadline) — the 503/504 feeders",
    "event_loop_lag_seconds": "how late the worker's event loop woke a "
                              "50 ms sleep (runtime/loop_probe.py, the "
                              "engine's heartbeat), histogram",
    "engine_spec_acceptance_rate": "cumulative speculative-draft "
                                   "acceptance rate",
    # fused-kernel fallback accounting (ops/fallback.py, on every
    # /metrics surface via the module registry)
    "grouped_product_total": "expert-layer grouped products compiled "
                             "on the chip by path (resident: "
                             "ops/pallas/grouped.py | streamed: "
                             "megablox) — counted at TRACE time",
    "fused_fallback_total": "fused/quantized fast-path downgrades by "
                            "reason (quant_tp_shardmap | "
                            "no_pallas_backend | latent_fp8_xla | "
                            "latent_tp_xla | latent_prefill_fp8_xla | "
                            "latent_prefill_tp_xla | "
                            "recurrent_no_page_offload | "
                            "recurrent_no_page_transfer | "
                            "recurrent_no_spec_decode | "
                            "recurrent_no_ring_prefill | "
                            "recurrent_state_row_missing) "
                            "— counted at TRACE time, so each compiled "
                            "specialization bumps it once, not once per "
                            "step; nonzero quant_tp_shardmap on a TP>1 "
                            "fp8 deployment is the ROADMAP #7 silent "
                            "XLA-path regression made visible",
    "kvbm_tier_bytes": "KVBM tier footprint gauge by tier "
                       "(host | disk | remote) — quantized blocks "
                       "(kv_dtype=fp8) land at packed fp8+scale width, "
                       "so the tier halving vs bf16 is observable here",
    # overload-control plane (engine/tenancy.py + gateway/breaker.py)
    "engine_preemptions_total": "batch streams paused to the host tier "
                                "by reason (interactive_admission | "
                                "interactive_pages) — the priority-"
                                "preemption activity counter",
    "tenant_tokens_total": "admission-charged token cost by tenant and "
                           "outcome (admitted | rejected | shed) — "
                           "rejected feeds the 429s, shed the "
                           "overload-policy bounces",
    "epp_breaker_state": "per-instance circuit-breaker state gauge "
                         "(0 closed, 1 half-open, 2 open) — a sick "
                         "worker browning out is visible AS a brownout",
    # closed-loop SLA autoscaler (autoscaler/metrics.py, on the /metrics
    # surface of whatever process hosts the controller)
    "autoscaler_plan_revisions_total": "ScalePlans emitted (each revision "
                                       "is one actuated fleet change)",
    "autoscaler_actuation_seconds": "backend.apply latency histogram — "
                                    "plan emission to acknowledged "
                                    "actuation",
    "autoscaler_replicas_desired": "latest plan's target per dimension "
                                   "(workers | prefill | router_shards)",
    "autoscaler_replicas_actual": "backend-observed replicas per "
                                  "dimension — desired vs actual gap is "
                                  "the convergence debt",
    "autoscaler_predictor_error": "matured forecast error (predicted - "
                                  "observed demand) at the pre-scale "
                                  "horizon; systematic bias here means "
                                  "the predictor is mis-tuned",
    "autoscaler_convergence_ticks": "ticks from plan emission until "
                                    "observed counts matched it",
}
