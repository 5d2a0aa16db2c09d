"""The program's regions (``dynamo_tpu/models/regions.py``): the registry
is the one list of the ``jax.named_scope`` names the program opens; every
instruction of the compiled prefill and decode programs of the four
families resolves through the table a trace's reader builds from the
program's own HLO (``perfbench/lib/regions.py``: the HLO a profiler trace
stores is the executable's); what stays without a region is listed and is
bookkeeping; a real trace of a toy engine stores one program a warmed
shape, variants of one jit name apart; an unprofiled engine runs nothing
new. Toy sizes, CPU."""

import asyncio
import glob
import importlib
import json
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import core
from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import regions
from dynamo_tpu.models.family import get_family

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_engine_spans import _cfg, _serve  # noqa: E402 - the toy engine


def _lib():
    """``perfbench/lib/regions.py``, as the benchmark loads it."""
    bench = os.path.join(REPO, "perfbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    return importlib.import_module("lib.regions")


def _spec(family: str) -> ModelSpec:
    if family == "dense":
        return ModelSpec.tiny()
    if family == "kda":
        return ModelSpec.tiny_solar(held_experts=(4, 2))
    if family == "linlat":  # KDA and latent kinds in one model
        return ModelSpec.tiny_ling3(held_experts=(4, 4))
    if family == "shortconv":  # a tail-only recurrent kind beside GQA
        return ModelSpec.tiny_lfm2()
    mod = {"mimo": "test_mimo", "latent": "test_joyai"}[family]
    return importlib.import_module(mod).SPEC


FAMILIES = ("dense", "mimo", "latent", "kda", "linlat", "shortconv")
PAGE, PAGES, B, T = 4, 8, 4, 16


@pytest.fixture(autouse=True)
def kernels_interpreted(monkeypatch):
    """The kernels' paths, interpreted (``DYNAMO_PALLAS`` is read when a
    program is traced): the regions around the kernels are the chip's."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")


def _lowered(spec: ModelSpec, program: str, n_steps: int = 2):
    """The prefill or the decode-burst program of ``spec``, lowered on
    shapes a toy engine would warm."""
    fam = get_family(spec)
    params = fam.init_params(spec, jax.random.PRNGKey(0))
    rows = {"state_rows": B} if getattr(fam, "recurrent", False) else {}
    k, v = fam.init_cache(spec, 1 + B * PAGES, PAGE, **rows)
    i32 = jnp.int32
    zb = jnp.zeros((B,), i32)
    burst = (jnp.zeros((B,), bool), jnp.zeros((B,), jnp.float32), zb,
             jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.uint32), zb)
    m = fam.mla if spec.is_mla else fam.m
    cache = (k,) if spec.is_mla else (k, v)
    extra = {"counts": v} if spec.is_mla else {}
    if program == "prefill":
        return m.prefill_forward.lower(
            spec, params, jnp.zeros((T,), i32), jnp.zeros((PAGES,), i32),
            jnp.asarray(0, i32), *cache, jnp.asarray(T, i32), mesh=None,
            **extra)
    return m.decode_steps.lower(
        spec, params, zb, jnp.zeros((B, PAGES), i32), jnp.ones((B,), i32),
        *cache, *burst, n_steps=n_steps, n_logprobs=0, mesh=None, **extra)


_TABLES: dict = {}


def _table(family: str, program: str):
    """(table, {computation id: instructions}, entry id, HLO text) of a
    toy program, compiled once a module."""
    key = (family, program)
    if key not in _TABLES:
        lib = _lib()
        compiled = _lowered(_spec(family), program).compile()
        proto = memoryview(compiled.runtime_executable().hlo_modules()[
            0].as_serialized_hlo_module_proto())
        span = (0, len(proto))
        _name, comps, entry = lib.hlo_instructions(proto, span)
        _TABLES[key] = (lib.program_table(proto, span, regions), comps,
                        entry, compiled.as_text())
    return _TABLES[key]


def _executed(comps: dict, entry: int) -> list[dict]:
    """The instructions a device would run as operations of their own:
    the entry computation's and those of the loops, branches and calls
    beneath it (not a fusion's or a reducer's insides)."""
    seen, todo, out = set(), [entry], []
    while todo:
        cid = todo.pop()
        if cid in seen:
            continue
        seen.add(cid)
        for ins in comps[cid]:
            out.append(ins)
            if ins["opcode"] in ("while", "conditional", "call"):
                todo += ins["calls"]
    return out


# -- the registry ----------------------------------------------------------


def test_registry_groups_every_scope_constant():
    names = {v for k, v in vars(regions).items() if k.startswith("SCOPE_")}
    # a kernel's name that is a LEAF of a region is no region itself
    assert names == set(regions.REGIONS) | set(regions.LEAVES)
    assert not set(regions.REGIONS) & set(regions.LEAVES)
    assert set(regions.LEAVES.values()) <= set(regions.REGIONS)
    assert set(regions.REGIONS.values()) == set(regions.GROUPS)
    assert set(regions.KERNEL_SCOPES) <= names
    assert all(regions.group_of(n) == g for n, g in regions.REGIONS.items())
    assert regions.group_of(None) == regions.REST == regions.group_of("?")


def test_registry_holds_every_name_a_configuration_reads_by():
    """A configuration's ``trace_names`` hold the names the ``kernels.*``
    metrics find operations by: each is a kernel scope of the registry."""
    held = set()
    for path in glob.glob(os.path.join(REPO, "perfbench/configs/*.json")):
        with open(path) as f:
            names = json.load(f)["trace_names"]
        for key, ops in names.items():
            if key != "programs":
                held |= set(ops)
    assert held and held <= set(regions.KERNEL_SCOPES), (
        held - set(regions.KERNEL_SCOPES))


def test_the_program_opens_only_names_of_the_registry():
    """Every ``jax.named_scope`` in the program is opened with a
    ``SCOPE_*`` constant of the registry, or a kernel's ``scope``
    argument, which its caller fills with one: no module spells a name of
    its own any more."""
    opened = re.compile(r"named_scope\(([^)]*)\)")
    bad = []
    for path in glob.glob(os.path.join(REPO, "dynamo_tpu/**/*.py"),
                          recursive=True):
        src = open(path).read()
        for arg in opened.findall(src):
            if not (arg.startswith("SCOPE_") or arg in ("scope", "name")):
                bad.append((os.path.relpath(path, REPO), arg))
        if not path.endswith("models/regions.py"):
            bad += [(os.path.relpath(path, REPO), line)
                    for line in re.findall(r'^SCOPE_\w+ = "', src, re.M)]
    assert not bad, bad


@pytest.mark.parametrize("op_name, want", [
    ("jit(decode_steps_impl)/while/body/closed_call/attn_qkv/dot_general",
     ("attn_qkv", "dot_general")),
    ("jit(f)/attn_kv/jit(fused_decode_attention)/pallas_call",
     ("fused_decode_attention", "pallas_call")),
    ("jit(f)/mlp/moe_experts/moe_grouped/jit(gmm)/pallas_call",
     ("gmm", "pallas_call")),
    ("jit(f)/attn_kv/vmap(attn_full)/dot_general",
     ("attn_full", "dot_general")),
    # a kernel that is a leaf of its region, not a region
    ("jit(f)/attn_kv/scan/jit(scan_chunk)/pallas_call",
     ("scan", "scan_chunk")),
    ("jit(f)/attn_kv/scan/mul", ("scan", "mul")),
    ("jit(f)/while/body/add", (None, "add")),
    ("", (None, "")),
])
def test_resolve_takes_the_innermost_name_of_the_registry(op_name, want):
    assert regions.resolve(op_name) == want


# -- the compiled programs -------------------------------------------------


def _bookkeeping(ins: dict) -> bool:
    """An executed instruction that may stay without a region: it hands
    values on (parameters, tuples, constants, copies and bitcasts of
    arguments), it is the burst loop's own counter, or the compiler made
    it from no line of the program (no ``op_name``, or an argument's)."""
    if ins["opcode"] in ("parameter", "tuple", "get-tuple-element",
                         "constant", "copy", "bitcast", "while",
                         "conditional", "call"):
        return True
    name = ins["op_name"]
    if not name or name.startswith("params["):
        return True
    parts = [p for p in name.split("/") if p]

    def frame(p):
        return p.startswith("jit(") or p in (
            "while", "body", "cond", "closed_call")

    # a layout change the compiler hoisted carries the bare path of the
    # jit or the loop; jit(...)/while/{cond/lt, body/add} is fori_loop's
    # own counter
    return all(frame(p) for p in parts) or (
        all(frame(p) for p in parts[:-1]) and parts[-1] in (
            "lt", "add", "transpose", "reshape", "broadcast_in_dim",
            "convert_element_type", "squeeze", "slice", "concatenate",
            "iota", "select_n"))


@pytest.mark.parametrize("program", ("prefill", "decode"))
@pytest.mark.parametrize("family", FAMILIES)
def test_every_instruction_resolves_and_the_unnamed_are_bookkeeping(
        family, program):
    table, comps, entry, text = _table(family, program)
    names = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", text, re.M))
    assert names and names <= set(table["ops"])
    executed = _executed(comps, entry)
    unnamed = [i for i in executed if table["ops"][i["name"]][0] is None]
    named = len(executed) - len(unnamed)
    stray = [(i["name"], i["opcode"], i["op_name"]) for i in unnamed
             if not _bookkeeping(i)]
    assert not stray, stray
    # and what computes is named: the matmuls, the attention, the norms
    computing = [i for i in executed if i["opcode"] in (
        "dot", "convolution", "fusion", "custom-call", "scatter", "gather",
        "sort", "reduce")]
    named_computing = [
        i for i in computing if table["ops"][i["name"]][0] is not None]
    assert named > 0 and len(named_computing) >= 0.9 * len(computing), (
        len(named_computing), len(computing))
    found = {table["ops"][i["name"]][0] for i in executed} - {None}
    # a region whose operations the CPU's compiler fused into a neighbour's
    # fusion (the expert layer's gather-and-add into the residual's; the
    # chip's compiler keeps that gather a fusion of its own) is found
    # inside the fusion: the table marks such a fusion ``mixed``
    found |= {
        regions.resolve(j["op_name"])[0]
        for i in executed if i["opcode"] == "fusion"
        for c in i["calls"] for j in comps[c]} - {None}
    want = {"embed", "norm", "head", "page_index", "attn_out"}
    want |= {"sampler", "burst_glue"} if program == "decode" else set()
    want |= {
        "dense": {"attn_qkv", "mlp"},
        "mimo": {"attn_qkv", "moe_route", "moe_dispatch", "moe_grouped",
                 "moe_combine", "attn_full", "attn_window"},
        "latent": {"latent_q", "latent_kv", "moe_shared", "moe_combine"},
        "kda": {"kda_proj", "kda_gates", "state_rows", "moe_shared"},
        # one program holds KDA's names as Solar's give them AND the
        # latent layer's as JoyAI's do
        "linlat": {"kda_proj", "kda_gates", "state_rows", "latent_q",
                   "latent_kv", "moe_shared", "moe_route", "moe_combine",
                   "mlp"},
        # the short convolution's two regions beside the QK-normed GQA
        # layer's (its norm rides in attn_qkv) and the experts'
        "shortconv": {"conv_proj", "conv_mix", "attn_qkv", "attn_full",
                      "state_rows", "moe_route", "moe_dispatch",
                      "moe_grouped", "moe_combine", "mlp"},
    }[family]
    if family in ("latent", "linlat") and program == "decode":
        want |= {"latent_absorb", "attn_latent"}
    if family == "linlat" and program == "decode":
        want |= {"latent_schedule"}
    if family == "linlat" and program == "prefill":
        want |= {"prefill_latent"}
    if family in ("kda", "linlat"):
        # the kernel forms a block's operands itself: ``kda_chunk`` alone
        # (``kda_chunk_operands`` is the pad of a ragged row, and XLA's
        # half off the chip: the test below)
        want |= {"kda_chunk"} if program == "prefill" else {"kda_step"}
        # ``kda_conv`` (tails, taps, norms in XLA) is a prefill region
        # only: a decode step hands ``kda_step`` the projections, and the
        # kernel reads each slot's tail through its own block, so nothing
        # under ``attn_qkv`` gathers the tails' rows
        if program == "prefill":
            want |= {"kda_conv"}
        else:
            inside = executed + [
                j for i in executed if i["opcode"] == "fusion"
                for c in i["calls"] for j in comps[c]]
            assert "kda_conv" not in found
            assert not [j["op_name"] for j in inside
                        if "attn_qkv" in j["op_name"].split("/")
                        and j["opcode"] in ("gather", "dynamic-slice")]
    assert want <= found, want - found


@pytest.mark.parametrize("route", ("kernel", "xla"))
def test_the_kda_chunk_form_names_what_each_route_runs(monkeypatch, route):
    """``kda_chunk`` on both routes; ``kda_chunk_operands`` is the pad of a
    ragged row where the kernel forms a block's operands itself (no
    triangular solve in the program) and XLA's batched half where it does
    not."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.attention import kda_chunk_prefill

    monkeypatch.setenv("DYNAMO_PALLAS", "1" if route == "kernel" else "0")
    N, T_, H, D = 1, 70, 2, 16
    x = jnp.ones((N, T_, H, D), jnp.float32)
    text = jax.jit(
        lambda q, b, pool: kda_chunk_prefill(
            q, q, q, -q, b, pool, jnp.zeros((N,), jnp.int32),
            jnp.ones((N,), bool), layer=0)
    ).lower(x, x[..., 0], jnp.zeros((1, 2, H, D, D), jnp.float32)
            ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    found = {regions.resolve(n)[0] for n in names} - {None}
    assert {"kda_chunk", "kda_chunk_operands"} <= found, found
    solves = [n for n in names if "triangular_solve" in n]
    if route == "kernel":
        assert not solves
        assert {n.split("/")[-1] for n in names
                if "kda_chunk_operands" in n.split("/")} <= {
            "pad", "reshape", "convert_element_type"}
    else:
        assert solves and all("kda_chunk_operands" in n for n in solves)


def test_moe_experts_sums_back_by_a_gather_and_scatters_nothing():
    """Beneath ``moe_experts`` no scatter is left, floating-point or
    integer: the groups' sizes are a compare and a sum (the inverse of the
    sort is a second sort), and the sum back into the tokens reads
    ``moe_experts / moe_combine / gather`` and ``/ add`` from the table,
    under the layer's third step. Nor does the router sort, gather or
    call ``top_k``: its picks are rounds of reductions."""
    for program in ("prefill", "decode"):
        _table_, comps, _entry, _text = _table("mimo", program)
        ops = [i for instrs in comps.values() for i in instrs]
        beneath = [i for i in ops if "moe_experts" in i["op_name"].split("/")]
        assert beneath
        assert not [i for i in beneath if i["opcode"] == "scatter"]
        leaves = {leaf for region, leaf in (
            regions.resolve(i["op_name"]) for i in ops) if region == "moe_route"}
        assert {"reduce_max", "reduce_min"} <= leaves, leaves
        assert not leaves & {"top_k", "sort", "gather", "scatter-add"}
        combine = {
            regions.resolve(i["op_name"]): i["op_name"] for i in beneath
            if regions.resolve(i["op_name"])[0] == "moe_combine"}
        leaves = {leaf for _region, leaf in combine}
        assert "gather" in leaves and "add" in leaves, leaves
        assert not any(leaf.startswith("scatter") for leaf in leaves)
        path = combine[("moe_combine", "gather")].split("/")
        assert path.index("mlp") < path.index("moe_experts") < path.index(
            "moe_combine")


def test_a_fusion_of_two_regions_is_marked_mixed():
    lib = _lib()

    @jax.jit
    def f(x, w):
        with jax.named_scope(regions.SCOPE_NORM):
            y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
        with jax.named_scope(regions.SCOPE_RESIDUAL):
            return y + x

    compiled = f.lower(jnp.ones((8, 8)), jnp.ones((8, 8))).compile()
    proto = memoryview(compiled.runtime_executable().hlo_modules()[
        0].as_serialized_hlo_module_proto())
    ops = lib.program_table(proto, (0, len(proto)), regions)["ops"]
    fusions = {n: r for n, r in ops.items()
               if r[0] in ("norm", "residual") and "fusion" in n}
    assert any(mixed for _r, _leaf, mixed, _inh in fusions.values()), ops


# -- a real trace of a toy engine ------------------------------------------


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The trace file of a profiled toy engine serving a few requests, and
    the shapes its precompile warmed."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))

    async def go():
        engine = InferenceEngine(ModelSpec.tiny(), _cfg(profile=True))
        engine.precompile()
        await engine.start()
        await _serve(engine, 2, "warm")
        jax.profiler.start_trace(trace_dir)
        await _serve(engine, 6, "traced", max_tokens=7, base=100)
        jax.profiler.stop_trace()
        await engine.close()
        return sorted(engine.precompile_report)

    warmed = asyncio.run(go())
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return path, warmed


def test_a_trace_stores_one_program_a_shape_that_ran(traced):
    """The trace stores the HLO of every program that ran while it was
    taken, under the name and id its executions carry: the bursts of 1 and
    2 steps share a jit name and stay apart; each resolves through the
    registry."""
    lib = _lib()
    path, warmed = traced
    tables = lib.tables_of(path, regions)

    def found_in(n):
        return {r[0] for r in tables[n]["ops"].values()} - {None}

    # (on the CPU the trace also stores what the process compiled before
    # it: the other families' toys of this module, told by their regions)
    others = {"moe_route", "latent_q", "kda_proj"}
    bursts = [n for n in tables if n.startswith("jit_decode_steps_impl(")
              and not found_in(n) & others]
    assert len(bursts) == len(set(bursts)) >= 2, sorted(tables)
    assert {"decode[4x1]", "decode[4x2]", "prefill[16]", "burst_feed"} <= set(
        warmed)
    prefills = [n for n in tables if n.startswith("jit_prefill_forward")]
    assert prefills
    ids = {re.search(r"\((\d+)\)$", n).group(1) for n in bursts}
    assert len(ids) == len(bursts)
    # bursts of different lengths number their instructions differently,
    # and each resolves: never joined by jit name alone
    assert len({frozenset(tables[n]["ops"]) for n in bursts}) >= 2
    for n in bursts:
        assert {"mlp", "head", "sampler"} <= found_in(n)
    # (which small operations the CPU's compiler fuses into which follows
    # the kernels' path a cached jit was traced with: the finer names are
    # held over all the stored programs)
    everywhere = {r[0] for t in tables.values() for r in t["ops"].values()}
    assert {"attn_qkv", "attn_out", "mlp", "head", "sampler", "norm",
            "embed", "page_index"} <= everywhere
    feed = [n for n in tables if "_with_fed_column" in n or "_chain_feed" in n]
    assert all(
        "feed" in {r[0] for r in tables[n]["ops"].values()} for n in feed)


# -- profile off -----------------------------------------------------------


async def test_an_unprofiled_engine_runs_nothing_new(monkeypatch):
    """With ``EngineConfig.profile`` off there is no table to build
    (``InferenceEngine`` has no ``region_table``: the join reads the
    trace), ``_phase`` / ``_launch`` hand out the shared no-op, and once
    the programs are warm serving opens no ``jax.named_scope``: a scope
    runs when a program is traced, not when it runs."""
    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    assert not hasattr(engine, "region_table")
    assert engine._phase("idle") is engine._phase("dispatch") is core._NO_SPAN
    assert engine._launch("decode", steps=2, live=1, slots=4) is core._NO_SPAN
    engine.precompile()
    await engine.start()
    await _serve(engine, 3, "warm")
    opened = []
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: opened.append(name) or real(name))
    await _serve(engine, 3, "served", base=60)
    await engine.close()
    assert opened == []
    assert engine._prof == {}


def test_a_snapshot_says_when_it_was_taken():
    import time

    engine = InferenceEngine(ModelSpec.tiny(), _cfg())
    t0 = time.monotonic()
    a = engine.profile_snapshot()["window.at"]
    b = engine.profile_snapshot()["window.at"]
    assert t0 <= a["secs"] <= b["secs"] <= time.monotonic()
    assert a["calls"] == b["calls"] == 0
