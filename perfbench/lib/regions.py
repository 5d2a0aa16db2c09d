"""Device time booked to the program's own regions: every operation of a
trace resolved to the ``jax.named_scope`` that made it, by kind of program.

Where the names come from (looked up in one whole trace by hand, PR 37):
the profiler stores, in plane ``/host:metadata``, the optimised HLO of
every program that ran while it traced, one event-metadata entry a program
under the very name the device plane's ``XLA Modules`` line gives its
executions (``jit_decode_steps_impl(<program id>)``), as a serialized
``HloProto`` in the stat ``Hlo Proto``. Each instruction there carries the
``op_name`` its ``jax.named_scope`` path gave it. So the join needs nothing
from the engine: an operation of line ``XLA Ops`` is looked up by its
instruction name in the table of the module executing at its start, found
by that module's own name and id, never by jit name alone (the bursts of
1, 4 and 8 steps share a jit name and number their instructions
differently). ``jax.profiler.ProfileData`` shows no metadata plane, so the
file's protobuf wire format is read here directly, the few fields needed.

The vocabulary is the program's: ``dynamo_tpu/models/regions.py`` of the
checkout, loaded by path (it imports nothing). On a checkout without it
(any commit before PR 37) ``load_registry`` returns None and every reader
returns None. Nothing of JAX or of the program is imported when this
module is loaded.

By hand: ``python3 perfbench/lib/regions.py <trace.xplane.pb> [--registry
<regions.py>] [--top N]`` prints the breakdown of any trace, a parent's
too.
"""

from __future__ import annotations

import bisect
import importlib.util
import os
import re
import sys

if __name__ == "__main__":  # run by hand: ``lib`` is this file's package
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from lib import trace  # noqa: E402

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
UNNAMED = "unnamed"  # in no table, or no region of the registry on its path
AMBIGUOUS = "ambiguous"  # variants of one jit name disagree on the region
PROGRAMS = {"decode": ["decode_steps"], "prefill": ["prefill_forward"]}
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REGISTRY = os.path.join(_ROOT, "dynamo_tpu", "models", "regions.py")
_PACKED = (36, 38)  # HloInstructionProto's repeated int64 fields
_ID = re.compile(r"\(\d+\)$")


def load_registry(path: str | None = None):
    """The program's registry module, or None where the checkout has
    none."""
    path = path or REGISTRY
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location("_program_regions", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the protobuf wire format, as far as a trace and an HLO module need it


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field, wire type, value) of the message in ``buf[start:end]``: an
    int for a varint or a fixed width, a (start, end) pair for bytes."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(buf, wire: int, v) -> list[int]:
    """A repeated integer field's values, packed or not."""
    if wire == 0:
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def _get(buf, span, *wanted: int) -> dict[int, list]:
    """{field: its values, in order} of the wanted fields of the message
    in ``buf[span]``."""
    out: dict[int, list] = {f: [] for f in wanted}
    for f, wire, v in _fields(buf, *span):
        if f in out:
            out[f] += _ints(buf, wire, v) if f in _PACKED and wire == 2 else [v]
    return out


def stored_programs(buf) -> dict[str, tuple[int, int]]:
    """{program name as the modules line prints it: span of its serialized
    HloProto} from a trace file's bytes (XSpace.planes = 1; XPlane.name =
    2, .event_metadata = 4, .stat_metadata = 5, both maps of key = 1,
    value = 2; XEventMetadata.name = 2, .stats = 5; XStatMetadata.name =
    2; XStat.metadata_id = 1, .bytes_value = 6)."""
    out: dict[str, tuple[int, int]] = {}
    for plane in _get(buf, (0, len(buf)), 1)[1]:
        p = _get(buf, plane, 2, 4, 5)
        if not p[2] or _text(buf, p[2][0]) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in p[5]:
            e = _get(buf, entry, 1, 2)
            for name in _get(buf, e[2][0], 2)[2] if e[2] else ():
                stat_names[e[1][0] if e[1] else 0] = _text(buf, name)
        for entry in p[4]:
            for md in _get(buf, entry, 2)[2]:
                m = _get(buf, md, 2, 5)
                for stat in m[5]:
                    st = _get(buf, stat, 1, 6)
                    sid = st[1][0] if st[1] else 0
                    if (m[2] and st[6]
                            and stat_names.get(sid, HLO_STAT) == HLO_STAT):
                        out[_text(buf, m[2][0])] = st[6][0]
    return out


def hlo_instructions(buf, span) -> tuple[str, dict[int, list[dict]], int]:
    """(module name, {computation id: instructions}, the entry
    computation's id) of a serialized HloProto (.hlo_module = 1) or
    HloModuleProto (.name = 1, .computations = 3, .entry_computation_id =
    6; HloComputationProto.instructions = 2, .id = 5;
    HloInstructionProto.name = 1, .opcode = 2, .metadata = 7 with
    OpMetadata.op_name = 2, .id = 35, .operand_ids = 36,
    .called_computation_ids = 38)."""
    mod = _get(buf, span, 1, 3, 6)
    if not mod[6] and mod[1]:  # an HloProto (.buffer_assignment = 3, no
        # entry id): step into its module
        mod = _get(buf, mod[1][0], 1, 3, 6)
    comps = {}
    for comp in mod[3]:
        c = _get(buf, comp, 2, 5)
        instrs = []
        for ins in c[2]:
            i = _get(buf, ins, 1, 2, 7, 35, 36, 38)
            op_name = _get(buf, i[7][0], 2)[2] if i[7] else ()
            instrs.append({
                "name": _text(buf, i[1][0]) if i[1] else "",
                "opcode": _text(buf, i[2][0]) if i[2] else "",
                "op_name": _text(buf, op_name[0]) if op_name else "",
                "id": i[35][0] if i[35] else None,
                "operands": i[36], "calls": i[38],
            })
        comps[c[5][0] if c[5] else None] = instrs
    return (_text(buf, mod[1][0]) if mod[1] else "", comps,
            mod[6][0] if mod[6] else -1)


def program_table(buf, span, registry) -> dict:
    """``{"module": name, "ops": {instruction: (region, leaf, mixed,
    inherited)}}`` for one program. Region: the innermost registry name on
    the instruction's ``op_name`` (``registry.resolve``), leaf its last
    component (the opcode where it has none). A fusion takes its own
    ``op_name``; where that names no region and its fused instructions
    name exactly one, that one; it is ``mixed`` where they (and it) name
    more than one. An instruction the compiler made itself (a layout
    ``copy``, the ``slice-done`` of a prefetched weight) carries no
    ``op_name`` at all: it is booked to the region that made what it
    moves, else to the region that takes it, through other such
    instructions, and marked ``inherited``. An instruction of the program
    outside every scope keeps None."""
    module, comps, _entry = hlo_instructions(buf, span)
    by_id = {i["id"]: i for instrs in comps.values() for i in instrs}
    users: dict[int, list] = {}
    for instrs in comps.values():
        for ins in instrs:
            for o in ins["operands"]:
                users.setdefault(o, []).append(ins)
    inside: dict[int, set] = {}

    def regions_inside(cid: int, seen=()) -> set:
        if cid in inside:
            return inside[cid]
        found = set()
        for ins in comps.get(cid, ()):
            r = registry.resolve(ins["op_name"])[0]
            if r is not None:
                found.add(r)
            for c in ins["calls"]:
                if c not in seen:
                    found |= regions_inside(c, (*seen, cid))
        inside[cid] = found
        return found

    own: dict[int, tuple] = {}
    for ins in by_id.values():
        region, leaf = registry.resolve(ins["op_name"])
        mixed = False
        if ins["opcode"] == "fusion":
            fused = set()
            for c in ins["calls"]:
                fused |= regions_inside(c)
            if region is None and len(fused) == 1:
                region = next(iter(fused))
            mixed = len(fused | ({region} if region else set())) > 1
        # the compiler's own instruction has no primitive to name: its
        # leaf is its name without the number (``slice-done``, ``copy``)
        own[ins["id"]] = (
            region, leaf or trace._NUM.sub("", ins["name"]) or ins["opcode"],
            mixed)

    def moves(ins) -> bool:
        # the compiler's own instruction: it comes from no line of the
        # program, so it carries no ``op_name`` at all
        return own[ins["id"]][0] is None and not ins["op_name"]

    def upstream(ins):
        for _ in range(32):  # through the movers before it
            if not ins["operands"]:
                return None
            ins = by_id.get(ins["operands"][0])
            if ins is None:
                return None
            if own[ins["id"]][0] is not None:
                return own[ins["id"]][0]
            if not moves(ins):
                return None
        return None

    def downstream(ins):
        front, seen = [ins], {ins["id"]}
        for _ in range(32):  # through the movers after it, breadth first
            nxt = []
            for i in front:
                for u in users.get(i["id"], ()):
                    if u["id"] in seen:
                        continue
                    seen.add(u["id"])
                    if own[u["id"]][0] is not None:
                        return own[u["id"]][0]
                    if moves(u):
                        nxt.append(u)
            if not nxt:
                return None
            front = nxt
        return None

    ops = {}
    for ins in by_id.values():
        region, leaf, mixed = own[ins["id"]]
        inherited = False
        if moves(ins):
            region = upstream(ins) or downstream(ins)
            inherited = region is not None
        ops[ins["name"]] = (region, leaf, mixed, inherited)
    return {"module": module, "ops": ops}


def tables_of(path: str, registry) -> dict[str, dict]:
    """{program name with its id: table} for every program the trace at
    ``path`` stores."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return {
        name: program_table(buf, span, registry)
        for name, span in stored_programs(buf).items()
    }


# -- the join ---------------------------------------------------------------


def _instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.partition(" = ")[0].strip()
    return head[1:] if head.startswith("%") else head


def _lookup(tables: dict, by_jit: dict, module: str, instr: str):
    """(region, leaf, mixed, inherited) or UNNAMED / AMBIGUOUS for an operation of
    ``module``: by the module's own name and id; where the trace stores no
    program under it, by the variants of its jit name that hold the
    instruction, if they agree."""
    table = tables.get(module)
    if table is not None:
        return table["ops"].get(instr, UNNAMED)
    found = {
        t["ops"][instr] for t in by_jit.get(_ID.sub("", module), ())
        if instr in t["ops"]
    }
    if not found:
        return UNNAMED
    if len({f[0] for f in found}) > 1:
        return AMBIGUOUS
    return sorted(found, key=str)[0]


def join(ops, modules, tables: dict, registry, programs: dict) -> dict:
    """Seconds and calls by (kind of program, region, leaf) over the
    operations ``ops`` (``trace.self_times`` rows) and the executed
    programs ``modules`` ((start, end, name) sorted by start). An
    operation belongs to the program executing at its start; a ``while``
    counts only the time its body's operations do not cover. ``groups``
    of a kind sum to its ``secs``: what is unnamed or ambiguous reports
    under ``rest``."""
    by_jit: dict[str, list] = {}
    for name, t in tables.items():
        by_jit.setdefault(_ID.sub("", name), []).append(t)
    starts = [m[0] for m in modules]
    kinds = [trace.classify(m[2], programs) for m in modules]
    # a trace holds a million operations and a few thousand distinct
    # (module, instruction) pairs: an operation adds its time to its pair
    # and to its program's group, the rest is done once a pair
    pairs: dict[tuple, list] = {}
    per_module: dict[float, dict] = {}
    for name, a, _b, own in ops:
        i = bisect.bisect_right(starts, a) - 1
        inside = i >= 0 and modules[i][0] <= a < modules[i][1]
        key = (modules[i][2], name) if inside else ("", name)
        pair = pairs.get(key)
        if pair is None:
            got = (_lookup(tables, by_jit, key[0], _instruction(name))
                   if inside else UNNAMED)
            named = not isinstance(got, str) and got[0] is not None
            pair = pairs[key] = [
                0.0, 0, kinds[i] if inside else "none", got if named else (
                    got if isinstance(got, str) else UNNAMED),
                registry.group_of(got[0]) if named else registry.REST]
        pair[0] += own
        pair[1] += 1
        if inside and pair[2] != "other":
            m = per_module.get(modules[i][0])
            if m is None:
                m = per_module[modules[i][0]] = dict.fromkeys(
                    registry.GROUPS, 0.0)
            m[pair[4]] += own * 1e-9
    rows: dict[tuple, list] = {}
    by_kind: dict[str, dict] = {}
    totals = {"named": 0.0, UNNAMED: 0.0, AMBIGUOUS: 0.0, "mixed": 0.0,
              "inherited": 0.0}
    unnamed_ops: dict[tuple, list] = {}

    def add(table, key, secs, calls):
        rec = table.setdefault(key, [0.0, 0])
        rec[0] += secs
        rec[1] += calls

    for (_module, name), (ns, calls, kind, got, group) in pairs.items():
        secs = ns * 1e-9
        if isinstance(got, str):
            region, leaf = got, trace.normalise(name)
            totals[region] += secs
            add(unnamed_ops, (kind, leaf), secs, calls)
        else:
            region, leaf, mixed, inherited = got
            totals["named"] += secs
            totals["mixed"] += secs * mixed
            totals["inherited"] += secs * inherited
        add(rows, (kind, region, leaf), secs, calls)
        k = by_kind.setdefault(kind, {
            "secs": 0.0, "groups": dict.fromkeys(registry.GROUPS, 0.0),
            "regions": {},
        })
        k["secs"] += secs
        k["groups"][group] += secs
        k["regions"][region] = k["regions"].get(region, 0.0) + secs
    return {
        "rows": rows, "by_kind": by_kind, "per_module": per_module,
        "named_s": totals["named"], "unnamed_s": totals[UNNAMED],
        "ambiguous_s": totals[AMBIGUOUS], "mixed_s": totals["mixed"],
        "inherited_s": totals["inherited"],
        "unnamed_ops": unnamed_ops,
    }


def reduce_plane(plane, tables: dict, registry, programs: dict,
                 window=None) -> dict | None:
    """One device plane joined to ``tables``, over the same window and the
    same self times as ``lib/trace.py: reduce_plane`` (first operation to
    last where ``window`` is None)."""
    lines = {ln.name: ln for ln in plane.lines}
    if trace.OPS_LINE not in lines:
        return None
    ops = [e for e in trace._events(lines[trace.OPS_LINE]) if e[2] > 0]
    if not ops:
        return None
    modules = sorted(
        (s, s + d, n) for n, s, d in (
            trace._events(lines[trace.MODULES_LINE])
            if trace.MODULES_LINE in lines else [])
    )
    if window is None:
        window = (min(s for _, s, _ in ops), max(s + d for _, s, d in ops))
    w0, w1 = window
    ops = [(n, max(s, w0), min(s + d, w1)) for n, s, d in ops]
    ops = trace.self_times([(n, a, b) for n, a, b in ops if b > a])
    busy = trace._union([(a, b) for _, a, b, _ in ops])
    out = join(ops, modules, tables, registry, programs)
    out["busy_s"] = sum(b - a for a, b in busy) * 1e-9
    out["window_s"] = (w1 - w0) * 1e-9
    out["window"] = window
    return out


def reduce_file(path: str, programs: dict, registry=None) -> dict | None:
    """The trace at ``path`` by region (its first TPU plane): None where
    the checkout has no registry, the trace stores no program, or it holds
    no device operation (a CPU rehearsal)."""
    registry = registry or load_registry()
    if registry is None:
        return None
    tables = tables_of(path, registry)
    if not tables:
        return None
    from jax.profiler import ProfileData

    for plane in trace.device_planes(ProfileData.from_file(path)):
        r = reduce_plane(plane, tables, registry, programs)
        if r is not None:
            r["programs"] = len(tables)
            return r
    return None


def describe(reduced: dict, top: int = 12) -> list[str]:
    """The ``regions:`` lines of a reduced trace, without a step count."""
    busy = reduced["busy_s"]
    out = [
        f"regions: busy {busy:.4f} s of {reduced['window_s']:.4f} s over "
        f"{reduced.get('programs', 0)} stored programs: named "
        f"{reduced['named_s']:.4f} s ({100 * reduced['named_s'] / busy:.2f}%"
        f"; of it in fusions of more than one region "
        f"{reduced['mixed_s']:.4f}, the compiler's own copies and prefetches "
        f"booked by what they move {reduced['inherited_s']:.4f}), unnamed "
        f"{reduced['unnamed_s']:.4f}, ambiguous {reduced['ambiguous_s']:.4f}"
    ]
    for kind, k in sorted(
            reduced["by_kind"].items(), key=lambda kv: -kv[1]["secs"]):
        groups = ", ".join(f"{g} {s:.4f}" for g, s in k["groups"].items())
        out.append(f"regions: {kind} {k['secs']:.4f} s: {groups}")
    rows = sorted(reduced["rows"].items(), key=lambda kv: -kv[1][0])[:top]
    for (kind, region, leaf), (secs, calls) in rows:
        out.append(
            f"regions:   {kind:8s} {region} / {leaf}: {secs:.4f} s, "
            f"{calls} calls")
    worst = sorted(
        reduced["unnamed_ops"].items(), key=lambda kv: -kv[1][0])[:6]
    if worst:
        out.append("regions: unnamed, largest: " + ", ".join(
            f"{kind} {leaf} {rec[0]:.4f} s x{rec[1]}"
            for (kind, leaf), rec in worst))
    return out


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--registry", default=None)
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--by-region", action="store_true",
                    help="also print every (kind, region) total")
    ap.add_argument("--dump-tables", default=None,
                    help="write the programs' tables to this JSON file")
    args = ap.parse_args(argv)
    registry = load_registry(args.registry)
    if registry is None:
        raise SystemExit("regions: no registry (dynamo_tpu/models/regions.py)")
    if args.dump_tables:
        with open(args.dump_tables, "w") as f:
            json.dump(tables_of(args.trace, registry), f)
    reduced = reduce_file(args.trace, PROGRAMS, registry)
    if reduced is None:
        raise SystemExit("regions: nothing to read in this trace")
    print("\n".join(describe(reduced, args.top)))
    if args.by_region:
        for kind, k in reduced["by_kind"].items():
            for region, secs in sorted(
                    k["regions"].items(), key=lambda kv: -kv[1]):
                print(f"regions: {kind} {region}: {secs:.4f} s "
                      f"({registry.group_of(region)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
