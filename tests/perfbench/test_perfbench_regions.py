"""Device time by the program's own regions (``perfbench/lib/regions.py``,
``perfbench/readers/regions.py``, ``perfbench/readers/step_thread.py``):
the trace's stored programs read off the wire, the join on a small
hand-made plane, the readers inert where there is nothing to read, and the
metric files against ``BENCHMARK.json``."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from lib import regions, spans, trace  # noqa: E402
from lib import spec as spec_mod  # noqa: E402

READERS = spec_mod.load_readers([os.path.join(REPO, "perfbench", "readers")])
NEW = sorted(k for k in READERS if k.startswith("regions:"))
BUSY = "step_thread:step_thread_busy_share"
PROGRAMS = {"decode": ["decode_steps"], "prefill": ["prefill_forward"]}
REGISTRY = regions.load_registry()
GROUPS = ("attn_proj", "attn_ctx", "ffn", "head", "rest")
CELLS = ["mistral7b.chat", "nemo12b.batch", "mimo25.longtail",
         "mistral7b.batch", "joyai-flash.reasoning", "nemo12b.chat"]


def _cell(**readers):
    return types.SimpleNamespace(
        config={"trace_names": {"programs": PROGRAMS}},
        readers={**READERS, **readers},
    )


# -- protobuf, written by hand ----------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _instruction(iid, name, opcode, op_name="", operands=(), calls=()):
    msg = _bytes(1, name.encode()) + _bytes(2, opcode.encode())
    if op_name:
        msg += _bytes(7, _bytes(2, op_name.encode()))
    msg += _int(35, iid)
    if operands:  # packed, as the compiler writes them
        msg += _bytes(36, b"".join(_varint(o) for o in operands))
    for c in calls:
        msg += _int(38, c)
    return msg


def _module(name, computations, entry):
    """A serialized HloProto: ``computations`` = {id: [instructions]}."""
    mod = _bytes(1, name.encode())
    for cid, instrs in computations.items():
        comp = _bytes(1, f"comp{cid}".encode())
        for ins in instrs:
            comp += _bytes(2, ins)
        mod += _bytes(3, comp + _int(5, cid))
    return _bytes(1, mod + _int(6, entry))


def _space(programs: dict) -> bytes:
    """An XSpace: a device plane (skipped by name), then the metadata
    plane with one program an entry."""
    device = _int(1, 2) + _bytes(2, b"/device:TPU:0") + _bytes(3, b"\x08\x01")
    meta = _int(1, 0) + _bytes(2, regions.METADATA_PLANE.encode())
    for i, (name, proto) in enumerate(programs.items()):
        stat = _int(1, 1) + _bytes(6, proto)
        md = _int(1, i + 1) + _bytes(2, name.encode()) + _bytes(5, stat)
        meta += _bytes(4, _int(1, i + 1) + _bytes(2, md))
    meta += _bytes(5, _int(1, 1) + _bytes(
        2, _int(1, 1) + _bytes(2, regions.HLO_STAT.encode())))
    return _bytes(1, device) + _bytes(1, meta)


def _burst(first: str, second: str) -> bytes:
    """A decode program of one loop: ``fusion.1`` is of region ``first``,
    ``fusion.2`` of ``second``; a weight's prefetch and a layout copy carry
    no name of their own."""
    j = "jit(decode_steps_impl)/while/body/closed_call/"
    body = [
        _instruction(10, "param", "parameter"),
        _instruction(11, "slice-start.3", "async-start", operands=[10]),
        _instruction(12, "slice-done.3", "async-done", operands=[11]),
        _instruction(13, "fusion.1", "fusion", j + first + "/dot_general",
                     operands=[12], calls=[3]),
        _instruction(14, "copy.7", "copy", operands=[13]),
        _instruction(15, "fusion.2", "fusion", j + second + "/dot_general",
                     operands=[14], calls=[4]),
        _instruction(16, "fusion.9", "fusion", j + "reshape", operands=[15]),
    ]
    fused_a = [_instruction(30, "dot.1", "dot", j + first + "/dot_general")]
    fused_b = [
        _instruction(40, "dot.2", "dot", j + second + "/dot_general"),
        _instruction(41, "add.2", "add", j + "residual/add"),
    ]
    entry = [
        _instruction(1, "tokens", "parameter"),
        _instruction(2, "while.5", "while",
                     "jit(decode_steps_impl)/while", operands=[1], calls=[2]),
    ]
    return _module("jit_decode_steps_impl",
                   {3: fused_a, 4: fused_b, 2: body, 1: entry}, 1)


ONE = "jit_decode_steps_impl(11)"
FOUR = "jit_decode_steps_impl(22)"


def _tables(**extra):
    space = _space({ONE: _burst("mlp", "attn_out"),
                    FOUR: _burst("attn_qkv", "head"), **extra})
    buf = memoryview(space)
    return {
        name: regions.program_table(buf, span, REGISTRY)
        for name, span in regions.stored_programs(buf).items()
    }


def _op(instr: str, a: float, b: float):
    return (f"%{instr} = bf16[8]{{0}} fusion(bf16[8]{{0}} %x)", a, b)


# -- the wire and the table ------------------------------------------------


def test_the_stored_programs_are_found_by_the_names_the_modules_line_gives():
    buf = memoryview(_space({ONE: _burst("mlp", "attn_out"),
                             FOUR: _burst("attn_qkv", "head")}))
    found = regions.stored_programs(buf)
    assert sorted(found) == [ONE, FOUR]
    name, comps, entry = regions.hlo_instructions(buf, found[ONE])
    assert name == "jit_decode_steps_impl" and entry == 1
    assert sorted(comps) == [1, 2, 3, 4]
    loop = next(i for i in comps[1] if i["opcode"] == "while")
    assert loop["calls"] == [2] and loop["operands"] == [1]


def test_a_table_resolves_regions_fusions_and_the_compiler_s_own():
    ops = _tables()[ONE]["ops"]
    assert ops["fusion.1"] == ("mlp", "dot_general", False, False)
    # fused instructions of two regions: the fusion's own op_name, mixed
    assert ops["fusion.2"] == ("attn_out", "dot_general", True, False)
    # no op_name at all: booked to what it moves, else to what takes it
    assert ops["copy.7"] == ("mlp", "copy", False, True)
    assert ops["slice-done.3"] == ("mlp", "slice-done", False, True)
    # the program's own instruction outside every scope stays unnamed
    assert ops["fusion.9"][0] is None and ops["fusion.9"][1] == "reshape"
    assert ops["while.5"][0] is None and ops["tokens"][0] is None


# -- the join ----------------------------------------------------------------


def test_variants_of_one_jit_are_resolved_by_module_not_by_name():
    """``fusion.1`` is the MLP's in the burst of one step and the qkv
    projection's in the burst of four: each execution reads its own."""
    modules = [(0.0, 100.0, ONE), (100.0, 300.0, FOUR)]
    ops = trace.self_times([
        _op("fusion.1", 10, 40), _op("fusion.2", 40, 60),
        _op("fusion.1", 110, 160), _op("fusion.2", 160, 290),
    ])
    j = regions.join(ops, modules, _tables(), REGISTRY, PROGRAMS)
    rows = {k: v[0] for k, v in j["rows"].items()}
    assert rows == pytest.approx({
        ("decode", "mlp", "dot_general"): 30e-9,
        ("decode", "attn_out", "dot_general"): 20e-9,
        ("decode", "attn_qkv", "dot_general"): 50e-9,
        ("decode", "head", "dot_general"): 130e-9,
    })
    assert j["ambiguous_s"] == j["unnamed_s"] == 0.0
    assert j["named_s"] == pytest.approx(230e-9)
    assert j["mixed_s"] == pytest.approx(150e-9)  # the two fusion.2


def test_an_operation_in_no_table_is_unnamed_and_variants_that_disagree_ambiguous():
    tables = _tables()
    # a third variant ran whose program the trace does not store
    modules = [(0.0, 100.0, "jit_decode_steps_impl(33)"),
               (100.0, 200.0, "jit_other(5)")]
    ops = trace.self_times([
        _op("fusion.1", 0, 30),  # mlp in one variant, attn_qkv in the other
        _op("fusion.77", 30, 50),  # in neither
        _op("fusion.9", 50, 60),  # None in both: no region, not ambiguous
        _op("fusion.1", 100, 140),  # a program no table is kept for
        _op("fusion.1", 250, 260),  # while no program ran
    ])
    j = regions.join(ops, modules, tables, REGISTRY, PROGRAMS)
    assert j["ambiguous_s"] == pytest.approx(30e-9)
    assert j["unnamed_s"] == pytest.approx((20 + 10 + 40 + 10) * 1e-9)
    assert j["named_s"] == 0.0
    assert j["by_kind"]["decode"]["groups"]["rest"] == pytest.approx(60e-9)
    assert set(j["by_kind"]) == {"decode", "other", "none"}
    # variants that agree are read, whichever ran
    agree = {ONE: tables[ONE], "jit_decode_steps_impl(44)": tables[ONE]}
    j = regions.join(ops[:1], modules, agree, REGISTRY, PROGRAMS)
    assert j["named_s"] == pytest.approx(30e-9) and j["ambiguous_s"] == 0.0


def test_a_loop_s_operations_are_counted_once_and_groups_sum_to_the_kind():
    """A ``while`` holds its body's operations: by self time the program's
    100 ns are counted once, and the five groups sum to the kind's total."""
    modules = [(0.0, 100.0, ONE), (100.0, 150.0, "jit_prefill_forward_impl(7)")]
    ops = trace.self_times([
        ("%while.5 = (s32[]) while(%tokens)", 0, 100),
        _op("slice-done.3", 5, 10), _op("fusion.1", 10, 40),
        _op("copy.7", 40, 45), _op("fusion.2", 50, 90),
        _op("fusion.9", 90, 95), _op("fusion.1", 100, 150),
    ])
    j = regions.join(ops, modules, _tables(), REGISTRY, PROGRAMS)
    decode = j["by_kind"]["decode"]
    assert decode["secs"] == pytest.approx(100e-9)
    assert sum(decode["groups"].values()) == pytest.approx(decode["secs"])
    assert decode["groups"] == pytest.approx({
        "attn_proj": 40e-9, "attn_ctx": 0.0, "ffn": 40e-9, "head": 0.0,
        # the loop's own 15 ns and the unscoped reshape's 5
        "rest": 20e-9,
    })
    assert j["inherited_s"] == pytest.approx(10e-9)
    assert j["unnamed_ops"][("decode", "while")][0] == pytest.approx(15e-9)
    # the prefill program's fusion.1 is in no table: unnamed, under rest
    assert j["by_kind"]["prefill"]["groups"]["rest"] == pytest.approx(50e-9)
    assert j["per_module"][0.0] == pytest.approx(decode["groups"])
    total = j["named_s"] + j["unnamed_s"] + j["ambiguous_s"]
    assert total == pytest.approx(150e-9)
    assert any("decode" in line and "mlp / dot_general" in line
               for line in regions.describe(dict(
                   j, busy_s=150e-9, window_s=150e-9)))


# -- the readers -------------------------------------------------------------


def _joined(decode_groups, prefill_modules):
    by_kind = {"decode": {
        "secs": sum(decode_groups.values()), "groups": decode_groups,
        "regions": {}}}
    secs = sum(sum(g.values()) for g in prefill_modules.values())
    by_kind["prefill"] = {"secs": secs, "groups": {}, "regions": {}}
    return {
        "rows": {}, "by_kind": by_kind, "per_module": prefill_modules,
        "named_s": 5.4, "unnamed_s": 0.3, "ambiguous_s": 0.0, "mixed_s": 0.0,
        "inherited_s": 0.0, "unnamed_ops": {}, "busy_s": 5.7, "window_s": 6.0,
    }


def test_the_metrics_from_a_joined_trace():
    """A decode step's five groups sum to ``model.decode_step_ms``; a
    prefilled token's five to the inverse of the paired rate."""
    groups = dict(zip(GROUPS, (0.6, 0.3, 2.4, 0.2, 0.1)))
    prefills = {
        100.0: dict(zip(GROUPS, (0.1, 0.05, 0.3, 0.01, 0.04))),
        900.0: dict(zip(GROUPS, (0.2, 0.1, 0.6, 0.02, 0.08))),
        5000.0: dict(zip(GROUPS, (0.1, 0.1, 0.1, 0.1, 0.1))),  # not paired
    }
    launch = spans.Launch("prefill", 1, 0, 0, {"tokens": 1000})
    late = spans.Launch("prefill", 3, 0, 0, {"tokens": 7})
    pairs = [
        (launch, spans.Module("prefill", "p", 100.0, 600.0)),
        (launch._replace(seq=2, counts={"tokens": 2000}),
         spans.Module("prefill", "p", 900.0, 2400.0)),
        (spans.Launch("decode", 4, 0, 0, {}),
         spans.Module("decode", "d", 2400.0, 3000.0)),
        # ran past the window's end: not counted, as the paired rate's
        (late, spans.Module("prefill", "p", 5000.0, 7000.0)),
    ]
    run = {
        "_regions": _joined(groups, prefills), "_registry": REGISTRY,
        "trace": {"by_kind": {"decode": {"secs": 3.6}}},
        "_spans": {"pairs": pairs, "window": (0.0, 6000.0)},
    }
    cell = _cell(**{
        "device:decode_step_ms": lambda run, cell: 12.0,
        "spans:prefill_paired_tok_s": lambda run, cell: None,
    })
    step = [READERS[f"regions:decode_region_ms_{g}"](run, cell)
            for g in GROUPS]
    assert step == pytest.approx([2.0, 1.0, 8.0, 2 / 3, 1 / 3])
    assert sum(step) == pytest.approx(12.0)  # 3.6 s over 300 steps
    per_tok = [READERS[f"regions:prefill_region_us_tok_{g}"](run, cell)
               for g in GROUPS]
    assert per_tok == pytest.approx([100.0, 50.0, 300.0, 10.0, 40.0])
    assert 1e6 / sum(per_tok) == pytest.approx(3000 / 1.5)
    assert READERS["regions:region_named_share"](run, cell) == pytest.approx(
        100 * 5.4 / 5.7)
    assert READERS["regions:prefill_device_share"](run, cell) == (
        pytest.approx(100 * 2.0 / 6.0))


def test_a_trace_whose_launches_cannot_be_paired_still_reads_a_token(capsys):
    """The tapped tokens over all the window's prefill programs, and a
    line that says so: the metric is on the line in every traced run."""
    groups = dict(zip(GROUPS, (0.2, 0.1, 0.6, 0.02, 0.08)))
    j = _joined(dict.fromkeys(GROUPS, 0.1), {1.0: groups})
    j["by_kind"]["prefill"]["groups"] = groups
    run = {
        "_regions": j, "_registry": REGISTRY, "_spans": None, "t0": 100.0,
        "traced": (15.0, 21.0, 50.0), "trace": {"by_kind": {}},
        "prefills": [(114.0, [500]), (116.0, [1000, 500]), (120.9, [500])],
    }
    cell = _cell(**{"spans:prefill_paired_tok_s": lambda run, cell: None})
    per_tok = [READERS[f"regions:prefill_region_us_tok_{g}"](run, cell)
               for g in GROUPS]
    assert per_tok == pytest.approx([100.0, 50.0, 300.0, 10.0, 40.0])
    assert capsys.readouterr().out.count("no launch is paired") == 1
    bare = {k: v for k, v in run.items() if k != "_regions_prefills"}
    bare["prefills"] = []
    assert READERS["regions:prefill_region_us_tok_ffn"](bare, cell) is None


def _bare_run(tmp_path, with_trace: bool) -> dict:
    trace_dir = None
    if with_trace:
        d = tmp_path / "trace" / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        shutil.copy(os.path.join(HERE, "data", "v5e_chat_slice.xplane.pb"),
                    d / "vm.xplane.pb")
        trace_dir = str(tmp_path / "trace")
    return {
        "engine": types.SimpleNamespace(config=None), "records": [],
        "t0": 100.0, "seconds": 51.0, "trace_dir": trace_dir,
        "trace": {"by_kind": {}} if with_trace else None,
        "traced": (15.0, 21.0, 50.0) if with_trace else None,
        "profile": ({}, {}),
    }


@pytest.mark.parametrize("reader", NEW + [BUSY])
def test_a_reader_finds_nothing_on_a_checkout_without_the_registry(
        tmp_path, capsys, monkeypatch, reader):
    """The parent's program under this benchmark: no registry, no
    ``window.at``; a trace that stores no program. None, and not a word."""
    monkeypatch.setattr(regions, "REGISTRY", str(tmp_path / "absent.py"))
    for with_trace in (False, True):
        run = _bare_run(tmp_path / str(with_trace), with_trace)
        assert READERS[reader](run, _cell()) is None
    out = capsys.readouterr().out
    assert "regions:" not in out and "step_thread:" not in out


def test_a_trace_that_stores_no_program_or_a_rehearsal_gives_nothing(
        tmp_path, capsys):
    """The recorded slice holds device events and no metadata plane; a CPU
    rehearsal has no reduced trace at all."""
    path = os.path.join(HERE, "data", "v5e_chat_slice.xplane.pb")
    assert regions.tables_of(path, REGISTRY) == {}
    assert regions.reduce_file(path, PROGRAMS, REGISTRY) is None
    for with_trace in (False, True):
        run = _bare_run(tmp_path / str(with_trace), with_trace)
        assert all(READERS[r](run, _cell()) is None for r in NEW)
    assert "regions:" not in capsys.readouterr().out


def test_a_reader_raises_nothing(capsys):
    for reader in NEW + [BUSY]:
        assert READERS[reader]({"trace_dir": "/nowhere", "trace": {}},
                               _cell()) is None
        assert READERS[reader]({}, types.SimpleNamespace(
            config={}, readers={})) is None


def test_the_step_thread_s_busy_share_from_its_annotations(capsys):
    """Inside the device's window: parked and blocked on the device do not
    count; between phases counts; nothing before the first annotation."""
    phases = sorted([
        ("idle", 0.0, 100.0), ("dispatch", 100.0, 400.0),
        ("dispatch.d2h_wait", 200.0, 350.0), ("process", 450.0, 600.0),
        ("idle", 600.0, 1000.0),
    ], key=lambda p: (p[1], -p[2]))
    tr = {"phases": phases, "window": (50.0, 850.0), "pairs": None}
    run = {"_spans": tr, "seconds": 51.0, "profile": (
        {"window.at": {"secs": 10.0, "calls": 0},
         "idle": {"secs": 1.0, "calls": 1}},
        {"window.at": {"secs": 140.0, "calls": 0},
         "idle": {"secs": 66.0, "calls": 9}})}
    cell = _cell(**{"spans:prefill_paired_tok_s": lambda run, cell: None})
    # dispatch 100..200 and 350..400, the gap 400..450, process 450..600
    assert READERS[BUSY](run, cell) == pytest.approx(100 * 350 / 800)
    out = capsys.readouterr().out
    # away 65 s of the 130 s between the snapshots: the old reader's
    # 1 - 65 / 51 clamps to nothing
    assert "130.00 s apart" in out and "away 65.00 s" in out
    assert "reads 0.0%" in out
    # a CPU rehearsal has no device window: the annotations' own extent
    tr["window"] = None
    assert READERS[BUSY](run, cell) == pytest.approx(100 * 350 / 1000)
    # and a program without annotations says nothing
    assert READERS[BUSY]({"_spans": None, "profile": ({}, {})}, cell) is None


# -- the files ---------------------------------------------------------------


def test_each_new_metric_file_agrees_with_its_entry_and_names_a_reader():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    named, entries = set(), {}
    for e in bench["per_layer"]:
        m = json.load(open(os.path.join(
            REPO, "perfbench", "metrics", e["name"] + ".json")))
        if not m["reader"].startswith(("regions:", "step_thread:")):
            continue
        assert m["reader"] in READERS, e["name"]
        named.add(m["reader"])
        entries[e["name"]] = e
        for key in ("unit", "better", "layer", "source", "moves",
                    "workloads"):
            assert e[key] == m[key], (e["name"], key)
        assert e["workloads"] == CELLS  # not solar-open2.reasoning
        assert e["moves"] == "tpot_p50_ms"
    assert named == set(NEW) | {BUSY} and len(NEW) == 12
    assert set(entries) == {
        "device.region_named_share", "model.prefill_device_share",
        "engine.step_thread_busy_share",
        *(f"model.decode_region_ms.{g}" for g in GROUPS),
        *(f"model.prefill_region_us_tok.{g}" for g in GROUPS),
    }
    assert GROUPS == REGISTRY.GROUPS
    assert entries["engine.step_thread_busy_share"]["source"] == "program_span"
    assert all(e["source"] == "device_trace" for n, e in entries.items()
               if n != "engine.step_thread_busy_share")
    # appended: the thirteen are the last of per_layer
    assert [e["name"] for e in bench["per_layer"][-13:]] == list(entries)


def test_the_new_modules_load_without_jax_or_the_program():
    code = (
        "import sys; sys.path.insert(0, 'perfbench')\n"
        "from lib import spec\n"
        "import importlib.util as u\n"
        "for f in ('perfbench/lib/regions.py', 'perfbench/readers/regions.py',"
        " 'perfbench/readers/step_thread.py',"
        " 'dynamo_tpu/models/regions.py'):\n"
        "    s = u.spec_from_file_location('m', f)\n"
        "    m = u.module_from_spec(s); s.loader.exec_module(m)\n"
        "from lib import regions\n"
        "assert regions.load_registry().GROUPS\n"
        "bad = [k for k in sys.modules if k == 'jax' or "
        "k.startswith(('jax.', 'dynamo_tpu'))]\n"
        "assert not bad, bad\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
