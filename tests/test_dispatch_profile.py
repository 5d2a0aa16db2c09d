"""Compile-and-dispatch instrumentation (ROADMAP #4): precompile
coverage, dispatch.* profile phases, compile-cache wiring, the
engine.compile fault site, and the PROFILE_PHASES catalog sync."""

import ast
import asyncio
import re
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from benchmarks.profile_engine import (
    READMIT_PHASES,
    dispatch_attribution,
    dispatch_overhead,
    readmission_attribution,
)
from dynamo_tpu.engine.compile_cache import compile_snapshot
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.faults import FAULTS

pytestmark = pytest.mark.integration

_TINY_F32 = ModelSpec(
    name="tiny-f32", vocab_size=272, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)


def _cfg(**kw) -> EngineConfig:
    base = dict(
        page_size=4, num_pages=128, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(16, 32),
        prefill_pack_size=2, max_prefill_chunk_tokens=32,
        # sync admissions: the zero-new-compiles assertion needs a
        # deterministic shape set (async wave coalescing concatenates
        # run-length-dependent widths)
        async_admissions=False,
        profile=True,
    )
    base.update(kw)
    return EngineConfig(**base)


async def _serve(engine, isls, tag) -> None:
    async def one(i, isl):
        toks = [3 + (i + j) % 50 for j in range(isl)]
        async for _ in engine.generate(
            {"token_ids": toks,
             "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context(f"{tag}-{i}"),
        ):
            pass

    await asyncio.gather(*(one(i, isl) for i, isl in enumerate(isls)))


async def test_precompile_then_mixed_isl_batch_zero_new_compiles():
    """After the precompile pass + one warm traffic round, a mixed-ISL
    batch (different lengths, same buckets) must trigger ZERO new
    compiles — asserted via the jax.monitoring compile-event counter."""
    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    report = engine.precompile()
    assert report, "precompile produced no shapes"
    # warm traffic: compiles the eager glue (feeds, stacks) precompile's
    # jitted-program warmup does not cover
    await _serve(engine, [5, 12, 20], "warm")
    c0, _s0 = compile_snapshot()
    await _serve(engine, [7, 14, 25], "mixed")
    c1, _s1 = compile_snapshot()
    assert c1 - c0 == 0, (
        f"{c1 - c0} compiles during warmed serving — a shape escaped "
        "the precompile set"
    )
    await engine.close()


async def test_fp8_engine_precompile_then_zero_new_compiles():
    """Satellite of the fp8 KV-cache PR: precompile() dispatches against
    the LIVE pools, so a kv_dtype=fp8 engine's warmup walks the same
    shape grid over QuantPool programs — warmed fp8 serving must also
    do ZERO new compiles (the quantized pools ride the existing
    donated argument slots; a pytree mismatch would show up here as a
    retrace)."""
    engine = InferenceEngine(ModelSpec.tiny(), _cfg(kv_dtype="fp8"))
    assert engine.kv_dtype == "fp8"
    report = engine.precompile()
    assert report, "precompile produced no shapes"
    await _serve(engine, [5, 12, 20], "warm-fp8")
    c0, _s0 = compile_snapshot()
    await _serve(engine, [7, 14, 25], "mixed-fp8")
    c1, _s1 = compile_snapshot()
    assert c1 - c0 == 0, (
        f"{c1 - c0} compiles during warmed fp8 serving — a shape "
        "escaped the precompile set"
    )
    await engine.close()


async def test_spec_engine_precompile_then_zero_new_compiles():
    """Satellite of the speculative-decoding PR: precompile() walks the
    verify-shape grid (power-of-two row counts x the static k+1 width),
    so a spec-enabled engine serves REPETITIVE traffic — drafts
    accepted, verifies at multiple widths — with zero new compiles
    after warmup."""
    engine = InferenceEngine(
        ModelSpec.tiny(), _cfg(spec_mode="ngram", spec_k_max=4),
    )
    report = engine.precompile()
    # the verify grid rode along: rows 1,2,4 (max_decode_slots=4) at
    # width k_max+1
    assert {"verify[1x5]", "verify[2x5]", "verify[4x5]"} <= set(report)

    def rep(i):  # repetitive prompt per stream: spec engages
        return [3 + (i + j) % 4 for j in range(16)]

    async def serve(tag):
        async def one(i):
            async for _ in engine.generate(
                {"token_ids": rep(i),
                 "stop_conditions": {"max_tokens": 12, "ignore_eos": True},
                 "sampling": {"temperature": 0.0}},
                Context(f"{tag}-{i}"),
            ):
                pass

        await asyncio.gather(*(one(i) for i in range(3)))

    await serve("warm")
    assert engine.spec_verifies > 0, "spec never engaged in warm traffic"
    c0, _s0 = compile_snapshot()
    await serve("steady")
    c1, _s1 = compile_snapshot()
    assert c1 - c0 == 0, (
        f"{c1 - c0} compiles during warmed spec serving — a verify "
        "shape escaped the precompile grid"
    )
    await engine.close()


async def test_precompile_report_covers_serving_shapes():
    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    c0 = compile_snapshot()[0]
    report = engine.precompile()
    # the samplers warm on a thread of their own: a compile is counted
    # for the shape whose thread made it, once
    assert sum(r["compiles"] for r in report.values()) <= (
        compile_snapshot()[0] - c0)
    names = set(report)
    assert {"prefill[16]", "prefill[32]", "prefill_packed[2x16]",
            "prefill_packed[2x32]", "decode[4x1]", "sample[1]",
            "sample[2]", "sample[4]"} <= names
    for rec in report.values():
        assert rec["secs"] >= 0 and "compiles" in rec
    # calling precompile after the engine started serving is a bug
    await engine.start()
    with pytest.raises(RuntimeError, match="before the engine starts"):
        engine.precompile()
    await engine.close()


@pytest.mark.parametrize("spec", ["tiny", "tiny_deepseek", "tiny_trinity"])
def test_precompile_compiles_each_program_once_and_ahead(spec, caplog):
    """The model's programs are lowered and compiled AHEAD, beside one
    another (``_compile_ahead``), and their warm-up dispatches find them
    in jit's own cache: each program is compiled once, not once ahead and
    once at its dispatch, in either family."""
    import collections
    import logging
    import re

    import jax

    # a pool of its own size: programs no earlier test of this process
    # has compiled (jit would answer those from its cache, compiling none)
    engine = InferenceEngine(getattr(ModelSpec, spec)(), _cfg(num_pages=96))
    # (the flag process-wide: a ``with jax.log_compiles()`` is the calling
    # thread's alone, and the compiles happen on threads of their own)
    was = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level(logging.WARNING, logger="jax"):
            report = engine.precompile()
    finally:
        jax.config.update("jax_log_compiles", was)
    made = collections.Counter(re.findall(
        r"Finished XLA compilation of jit\((\w+)\)", caplog.text))
    programs = {k: r for k, r in report.items()
                if k.startswith(("prefill", "decode["))}
    assert len(programs) >= 4
    model = {k: n for k, n in made.items()
             if k.startswith(("prefill_forward", "decode_steps"))}
    assert sum(model.values()) == len(programs), (model, sorted(programs))
    for name, rec in programs.items():
        assert 0 < rec["ahead_secs"] <= rec["secs"], (name, rec)
        assert rec["compiles"] >= 1 and "error" not in rec


async def test_precompile_warmup_miss_fault_keeps_serving():
    """Injected engine.compile failures (DYN_FAULTS site) = warmup
    misses: precompile reports them and serving still works, eating the
    compile at first use."""
    FAULTS.configure("engine.compile:error@1.0x2", seed=7)
    try:
        engine = InferenceEngine(ModelSpec.tiny(), _cfg())
        report = engine.precompile()
        missed = [n for n, r in report.items() if "error" in r]
        assert len(missed) == 2, report
        await _serve(engine, [5, 20], "after-miss")
        await engine.close()
    finally:
        FAULTS.configure("")
    # delay action: slow-compile simulation parses and fires too
    FAULTS.configure("engine.compile:delay=1ms@1.0x1", seed=7)
    try:
        engine = InferenceEngine(ModelSpec.tiny(), _cfg())
        report = engine.precompile()
        assert not any("error" in r for r in report.values())
        await engine.close()
    finally:
        FAULTS.configure("")


async def test_dispatch_phases_and_attribution():
    """profile_snapshot carries the dispatch.* phases; the profile_engine
    attribution helpers compute the overhead fraction from them."""
    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    await _serve(engine, [5, 12], "prof")
    snap = engine.profile_snapshot()
    await engine.close()
    assert snap["dispatch.dispatches"]["calls"] > 0
    assert "dispatch.d2h_wait" in snap
    assert snap["dispatch.compile"]["calls"] >= 0

    disp = dispatch_attribution(snap, model_steps=max(engine.steps, 1))
    for key in ("dispatches", "dispatches_per_step", "d2h_wait_s",
                "compile_events", "compile_s", "issue_s"):
        assert key in disp
    assert disp["dispatches"] == snap["dispatch.dispatches"]["calls"]

    over = dispatch_overhead(snap, window_s=10.0, model_steps=engine.steps)
    assert over["target_frac_max"] == 0.15
    assert over["dispatch_plus_readmit_frac_of_window"] is not None
    # the fraction is (dispatch_s + readmit_s) / window, each of the three
    # rounded to four places on its own: the two terms' rounding is worth
    # 1e-4 / 10 at most, the fraction's own 5e-5
    want = (over["dispatch_s"] + over["readmit_s"]) / 10.0
    assert over["dispatch_plus_readmit_frac_of_window"] == pytest.approx(
        want, abs=6e-5)


async def test_readmission_gap_attribution_phases():
    """EngineConfig.profile breaks the finish->first-token path into
    the named phases profile_engine.py reports: admit_wait (queue time),
    prefill_dispatch (prompt forward + fused sample), first_token
    (residual sample/d2h materialization)."""
    engine = InferenceEngine(_TINY_F32, EngineConfig(
        page_size=4, num_pages=64, max_pages_per_seq=16,
        max_decode_slots=2, prefill_buckets=(16, 32),
        decode_steps_per_dispatch=2, pipeline_decode=True, profile=True,
    ))
    await engine.start()
    await _serve(engine, [3, 3, 3, 3], "prof")
    snap = engine.profile_snapshot()
    await engine.close()
    for phase in (
        "readmit.admit_wait", "readmit.prefill_dispatch",
        "readmit.first_token",
    ):
        assert snap.get(phase, {}).get("calls", 0) > 0, phase
    attr = readmission_attribution(snap)
    for key in ("admit_wait", "prefill_dispatch", "first_token"):
        assert attr[key]["events"] > 0
        assert attr[key]["mean_ms"] is not None
    assert attr["engine_gap_ms"] > 0


def test_dispatch_overhead_fraction_math():
    snap = {
        "dispatch": {"secs": 1.0, "calls": 10},
        "dispatch.d2h_wait": {"secs": 0.5, "calls": 5},
        "dispatch.compile": {"secs": 0.25, "calls": 1},
        "admit_loop": {"secs": 0.25, "calls": 4},
        "readmit_wait": {"secs": 0.5, "calls": 2},
        # NOT summed — its time already lives inside the admit phases
        "eager_readmit": {"secs": 0.75, "calls": 2},
    }
    over = dispatch_overhead(snap, window_s=10.0, model_steps=100)
    assert over["dispatch_s"] == 1.75
    assert over["readmit_s"] == 0.75
    assert over["dispatch_plus_readmit_frac_of_window"] == 0.25
    assert set(READMIT_PHASES) >= {"admit_loop", "readmit_wait"}
    assert "eager_readmit" not in READMIT_PHASES


@pytest.mark.parametrize(
    "placed,backend,want",
    [
        pytest.param(True, "cpu", "{tmp}", id="env"),
        pytest.param(False, "tpu", "{repo}/.jax_cache", id="default"),
        pytest.param(False, "cpu", None, id="default-cpu-off"),
    ],
)
def test_compile_cache_placement(tmp_path, placed, backend, want):
    """Where the persistent compile cache lives is decided OUTSIDE the
    program: with JAX_COMPILATION_CACHE_DIR set, jax's own handling of
    the variable is the whole story (the engine chokepoint never touches
    the directory option); unset, every engine process on an accelerator
    uses the one fixed <checkout>/.jax_cache, and on the CPU backend the
    cache stays off. Subprocess: jax's cache config is process-global;
    the backend the chokepoint asks about is steered here, in the test."""
    repo = __file__.rsplit("/tests/", 1)[0]
    if want is not None:
        want = want.format(tmp=tmp_path, repo=repo)
    code = (
        "import jax\n"
        f"jax.default_backend = lambda: {backend!r}\n"
        "updates = []\n"
        "real = jax.config.update\n"
        "jax.config.update = lambda k, v: (updates.append(k), real(k, v))\n"
        "from dynamo_tpu.engine import compile_cache as cc\n"
        f"assert cc.enable_compile_cache() == {want!r}\n"
        f"assert jax.config.jax_compilation_cache_dir == {want!r}\n"
        "assert ('jax_compilation_cache_dir' in updates) is "
        f"{not placed and want is not None}\n"
        # the zeroed thresholds stay wherever the cache is on
        "assert (jax.config.jax_persistent_cache_min_compile_time_secs == 0"
        f") is {want is not None}\n"
        "assert (jax.config.jax_persistent_cache_min_entry_size_bytes == -1"
        f") is {want is not None}\n"
        "print('WIRED')\n"
    )
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "PYTHONPATH": "."}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env, cwd=repo,
    )
    assert "WIRED" in out.stdout, out.stderr


def test_profile_phase_catalog_sync():
    """catalog.PROFILE_PHASES <-> engine/core.py phase names, BOTH
    directions (the DL006 pattern): an uncatalogued phase silently
    zeroes every consumer of profile snapshots; a catalogued phase no
    code emits is drift."""
    from tools.dynalint import catalog

    core_path = InferenceEngine.__module__.replace(".", "/") + ".py"
    src = open(core_path).read()
    used: set[str] = set()
    for node in ast.walk(ast.parse(src)):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "_phase"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            used.add(node.args[0].value)
    # profile_snapshot's synthesized keys + direct _prof accumulators,
    # and the per-request sums generate() adds from the timelines
    used.update(re.findall(
        r'(?:snap|self\._prof)(?:\.setdefault\(|\[)"([a-z_.0-9]+)"', src
    ))
    from dynamo_tpu.engine.core import READMIT_SUMS
    used.update(READMIT_SUMS.values())
    catalogued = set(catalog.PROFILE_PHASES)
    assert used - catalogued == set(), (
        f"phases missing from catalog.PROFILE_PHASES: {used - catalogued}"
    )
    assert catalogued - used == set(), (
        f"stale catalog phases no code emits: {catalogued - used}"
    )


def test_profile_counter_catalog_sync():
    """catalog.PROFILE_COUNTERS <-> the always-on counters engines report
    beside their phases, both directions, over the families (a prefill
    walk's kinds differ by model; recurrent layers bring their own): a renamed counter silently
    zeroes whoever reads the snapshot."""
    from tools.dynalint import catalog

    reported: set[str] = set()
    for spec in (ModelSpec.tiny(), ModelSpec.tiny_deepseek(),
                 ModelSpec.tiny_gpt_oss(), ModelSpec.tiny_solar(),
                 ModelSpec.tiny_falcon_h1(), ModelSpec.tiny_ling3(),
                 ModelSpec.tiny_lfm2(), ModelSpec.tiny_phi4flash()):
        engine = InferenceEngine(spec, _cfg(profile=False))
        reported |= {
            k for k in engine.profile_snapshot()
            if k not in catalog.PROFILE_PHASES and not k.startswith("moe.")
        }
    assert reported == set(catalog.PROFILE_COUNTERS), (
        reported ^ set(catalog.PROFILE_COUNTERS)
    )


async def test_chunked_prefill_counter_in_the_snapshot_and_reset():
    """``chunked_prefill.chunks`` / ``.chunks_behind_burst`` from
    profile_snapshot(): a 70-token prompt in chunks of 32 on an idle
    engine is three chunks, none behind a burst; the same prompt beside a
    stream decoding in pipelined bursts has the chunks after its first
    behind one; reset_profile_window() clears both."""
    engine = InferenceEngine(_TINY_F32, _cfg(
        profile=False, async_admissions=True, pipeline_decode=True,
        decode_steps_per_dispatch=2, max_pages_per_seq=32))
    await engine.start()

    def counts():
        snap = engine.profile_snapshot()
        return (snap["chunked_prefill.chunks"],
                snap["chunked_prefill.chunks_behind_burst"])

    assert counts() == ({"secs": 0.0, "calls": 0},) * 2
    await _serve(engine, [70], "idle")
    assert [c["calls"] for c in counts()] == [3, 0]
    engine.reset_profile_window()
    assert [c["calls"] for c in counts()] == [0, 0]

    got: list[int] = []

    async def stream():
        async for item in engine.generate(
            {"token_ids": [5, 9, 13],
             "stop_conditions": {"max_tokens": 60, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context("load"),
        ):
            got.extend(item["token_ids"])

    async def later():
        while len(got) < 6:
            await asyncio.sleep(0.002)
        # other tokens than the idle prompt's: nothing of it is cached
        async for _ in engine.generate(
            {"token_ids": [200 - j for j in range(70)],
             "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context("loaded"),
        ):
            pass

    await asyncio.gather(stream(), later())
    chunks, behind = (c["calls"] for c in counts())
    assert chunks == 3 and 2 <= behind <= 3
    await engine.close()


def _core_source() -> str:
    return open(InferenceEngine.__module__.replace(".", "/") + ".py").read()


def _literal_calls(src: str, attr: str, arg: int) -> set[str]:
    """String literals passed as positional argument ``arg`` of every
    ``<x>.<attr>(...)`` call in ``src``."""
    found: set[str] = set()
    for node in ast.walk(ast.parse(src)):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == attr
            and len(node.args) > arg
            and isinstance(node.args[arg], ast.Constant)
            and isinstance(node.args[arg].value, str)
        ):
            found.add(node.args[arg].value)
    return found


def test_profiler_annotation_catalog_sync():
    """catalog.PROFILER_ANNOTATIONS <-> the TraceAnnotation names
    engine/core.py writes, both directions; the per-phase annotation is
    built from the phase name and nowhere else."""
    from tools.dynalint import catalog

    src = _core_source()
    used = _literal_calls(src, "TraceAnnotation", 0)
    assert '"engine." + name' in src  # _PhaseSpan: engine.<phase>
    assert used == set(catalog.PROFILER_ANNOTATIONS), (
        used ^ set(catalog.PROFILER_ANNOTATIONS)
    )


def test_flight_event_catalog_sync():
    """catalog.FLIGHT_EVENTS <-> the events engine/core.py records, both
    directions: timeline readers reference these exact strings."""
    from tools.dynalint import catalog

    used = _literal_calls(_core_source(), "event", 1)
    assert used == set(catalog.FLIGHT_EVENTS), (
        used ^ set(catalog.FLIGHT_EVENTS)
    )
