"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU's plane is
named ``/device:TPU:<n>``; its line ``XLA Ops`` has one event per executed
operation and its line ``XLA Modules`` one per executed program. Nothing in
the program's hot path has a ``jax.named_scope`` yet, so the reduction keys
on the names the compiler prints (``fused_decode_attention``,
``fusion.w_down.w_up``...), normalised by dropping the numbers the compiler
appends, and on the names of the jitted programs (``jit_decode_steps_impl``).

Busy is the union of the intervals in which an operation ran on the
device; idle is the rest of the traced window. Each idle gap is named by
the programs on either side of it, since no host span is on the profiler's
clock yet.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_NUM = re.compile(r"([._-]\d+)+$|\(\d+\)$")


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


def normalise(name: str) -> str:
    """The trace prints an operation as its whole HLO line:
    ``%fusion.w_down.12 = bf16[8]{0} fusion(...)`` -> ``fusion.w_down``. A
    fusion the compiler left unnamed is told apart by its largest operand:
    ``%fusion.7 = ... fusion(bf16[32,4096] %a, bf16[14336,4096] %b)`` ->
    ``fusion_14336x4096``."""
    head, _, rest = name.partition(" = ")
    base = head.strip().lstrip("%")
    prev = None
    while prev != base:
        prev = base
        base = _NUM.sub("", base)
    base = base or prev
    if base == "fusion" and rest:
        shapes = [m.group(1).split(",") for m in _SHAPE.finditer(rest)]
        if shapes:
            def size(dims):
                n = 1
                for d in dims:
                    n *= int(d)
                return n

            base = "fusion_" + "x".join(max(shapes, key=size))
    return base


def self_times(ops: list[tuple[str, float, float]]) -> list[tuple[str, float, float, float]]:
    """(name, start, end, self seconds-in-ns) of every operation: an
    operation that holds others (a ``while`` and its body) counts only the
    time none of them covers."""
    out: list[list] = []
    stack: list[int] = []
    for n, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(b, parent[2]) - a
        out.append([n, a, b, b - a])
        stack.append(len(out) - 1)
    return [(n, a, b, max(0.0, s)) for n, a, b, s in out]


def _events(line) -> list[tuple[str, float, float]]:
    return [
        (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events
    ]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def device_planes(profile) -> list:
    return [
        p for p in profile.planes
        if p.name.startswith("/device:TPU:") and "SparseCore" not in p.name
    ]


def classify(name: str, programs: dict) -> str:
    """The kind of a program by its name: the first kind of ``programs``
    (``{"decode": ["decode_steps"], ...}``) with a substring in it."""
    for kind, needles in programs.items():
        if any(n in name for n in needles):
            return kind
    return "other"


def reduce_plane(plane, programs: dict, window: tuple[float, float] | None):
    """One device's numbers. ``window`` = (start_ns, end_ns) on the plane's
    clock, or None for first event to last."""
    lines = {ln.name: ln for ln in plane.lines}
    if OPS_LINE not in lines:
        return None
    ops = [e for e in _events(lines[OPS_LINE]) if e[2] > 0]
    if not ops:
        return None
    modules = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
    modules = sorted(
        (s, s + d, classify(n, programs), n) for n, s, d in modules
    )
    if window is None:
        window = (min(s for _, s, _ in ops), max(s + d for _, s, d in ops))
    w0, w1 = window
    ops = [(n, max(s, w0), min(s + d, w1)) for n, s, d in ops]
    ops = self_times([(n, a, b) for n, a, b in ops if b > a])
    busy = _union([(a, b) for _, a, b, _ in ops])
    busy_ns = sum(b - a for a, b in busy)

    # device seconds and call counts by operation name
    by_op: dict[str, list[float]] = {}
    names: dict[str, str] = {}  # the HLO line is long: normalise it once
    for n, _, _, own in ops:
        short = names.get(n)
        if short is None:
            short = names[n] = normalise(n)
        rec = by_op.setdefault(short, [0.0, 0])
        rec[0] += own * 1e-9
        rec[1] += 1

    # device seconds, runs and operation counts by kind of program
    starts = [m[0] for m in modules]
    def kind_of(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and modules[i][0] <= t < modules[i][1]:
            return modules[i][2]
        return "none"

    by_kind: dict[str, dict] = {}
    for n, a, _, own in ops:
        k = by_kind.setdefault(
            kind_of(a), {"secs": 0.0, "ops": {}, "runs": 0}
        )
        k["secs"] += own * 1e-9
        rec = k["ops"].setdefault(names[n], [0.0, 0])
        rec[0] += own * 1e-9
        rec[1] += 1
    for a, b, kind, _ in modules:
        if b > w0 and a < w1:
            by_kind.setdefault(
                kind, {"secs": 0.0, "ops": {}, "runs": 0}
            )["runs"] += 1

    # idle gaps by the programs on either side
    gaps: dict[str, float] = {}
    edges = [(w0, w0)] + busy + [(w1, w1)]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        if start <= end:
            continue
        before, after = kind_of(end - 1), kind_of(start)
        mid = kind_of((end + start) / 2)
        if mid != "none" and before == mid == after:
            label = f"inside_a_{mid}_program"
        else:
            label = f"between_{before}_and_{after}_programs"
        gaps[label] = gaps.get(label, 0.0) + (start - end) * 1e-9
    return {
        "busy_s": busy_ns * 1e-9, "window_s": (w1 - w0) * 1e-9,
        "by_op": by_op, "by_kind": by_kind, "gaps": gaps,
    }


def reduce_file(path: str, programs: dict, window=None):
    """The trace at ``path`` reduced over its TPU planes: per-device
    results under ``devices`` and their mean busy seconds. None where the
    trace holds no device operation (a CPU rehearsal)."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    devices = []
    for plane in device_planes(profile):
        r = reduce_plane(plane, programs, window)
        if r is not None:
            devices.append(r)
    if not devices:
        return None
    first = devices[0]
    return {
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "window_s": first["window_s"],
        "by_op": first["by_op"], "by_kind": first["by_kind"],
        "gaps": first["gaps"],
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(
        ((n, rec[0]) for n, rec in reduced["by_op"].items()),
        key=lambda kv: -kv[1],
    )[:top]
    gaps = sorted(reduced["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[n, s] for n, s in gaps],
    }


def describe(path: str, limit: int = 4) -> str:
    """Planes, lines and first events of a trace, for reading one by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:limit]:
                stats = {k: v for k, v in list(e.stats)[:6]}
                out.append(
                    f"    {e.name!r} start {e.start_ns} dur {e.duration_ns} "
                    f"{stats}"
                )
    return "\n".join(out)
