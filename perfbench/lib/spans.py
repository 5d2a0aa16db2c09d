"""The engine's own spans, read from the profiler's trace and the flight
recorder: what the program says about itself, on the device's clock.

A profiled engine (``EngineConfig.profile``, which ``run.py`` sets for the
traced run) writes into the JAX profiler's trace, on the step thread's line
of plane ``/host:CPU``:

- ``engine.<phase>`` for every step-thread phase (``idle``, ``build_batch``,
  ``packed_prefill``, ``dispatch.d2h_wait`` ...), nested as the code nests;
- ``engine.launch`` around every device program it issues, with ``kind``
  (``prefill``, ``decode``, ...), a running ``seq`` and the host counts the
  program was built from (``tokens``/``rows``; ``steps``/``live``/``slots``);
- ``engine.clock`` once a loop cycle, carrying ``mono_ns``: the offset
  between the profiler's clock and ``time.monotonic`` is the median of
  (start - mono_ns) over these.

Its flight recorder keeps every finished request's timeline (``admit``,
``prefill_dispatch`` with the launch's ``seq``, ``first_token``,
``first_delta``) on ``time.monotonic``, the clock the load generator uses.

The device runs one stream in launch order. So the k-th traced launch of a
prefill or decode program is the k-th execution of such a program in the
trace, once the executions at the head that belong to launches made before
the trace began are dropped: those that began before the first traced
launch, and then as many more as it takes for every launch to meet a
program of its own kind that starts no earlier than the launch. Where no
such alignment exists nothing is paired and nothing is reported.

On a program without these spans (any commit before they existed) every
function here returns None. Nothing of JAX or of the program is imported
when this module is loaded.
"""

from __future__ import annotations

import bisect
from collections import namedtuple

from lib import stats, trace

HOST_PLANE = "/host:CPU"
PREFIX = "engine."
LAUNCH = "engine.launch"
CLOCK = "engine.clock"
PAIRED_KINDS = ("prefill", "decode")
# step-thread phases in which the host is not what the device waits for:
# parked for work, or blocked on a device->host copy
AWAY = ("idle",)
AWAY_SUFFIX = ".d2h_wait"
MAX_HEAD = 64  # executions of earlier launches a trace may open with

Launch = namedtuple("Launch", "kind seq start end counts")
Module = namedtuple("Module", "kind name start end")


def _int(v, default=0) -> int:
    try:
        return int(v)
    except (TypeError, ValueError):
        return default


def host_events(profile) -> dict | None:
    """The ``engine.*`` annotations of a trace: phases as (name, start,
    end), launches, and the clock samples as (start_ns, mono_ns). None
    where the trace holds none."""
    phases, launches, clock = [], [], []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if not name.startswith(PREFIX):
                    continue
                a = float(e.start_ns)
                b = a + float(e.duration_ns)
                if name == CLOCK:
                    st = dict(e.stats)
                    if "mono_ns" in st:
                        clock.append((a, float(_int(st["mono_ns"]))))
                elif name == LAUNCH:
                    st = dict(e.stats)
                    launches.append(Launch(
                        str(st.get("kind", "")), _int(st.get("seq"), -1), a, b,
                        {k: _int(v) for k, v in st.items()
                         if k not in ("kind", "seq")},
                    ))
                else:
                    phases.append((name[len(PREFIX):], a, b))
    if not phases and not launches:
        return None
    launches.sort(key=lambda ln: (ln.seq, ln.start))
    phases.sort(key=lambda p: (p[1], -p[2]))
    return {"phases": phases, "launches": launches, "clock": clock}


def fit_clock(clock: list[tuple[float, float]]) -> dict | None:
    """``offset_ns`` such that profiler time = monotonic time + offset:
    the median of the differences; ``residual_ns`` is the largest distance
    of a sample from it, ``iqr_ns`` the spread of the middle half."""
    if not clock:
        return None
    diffs = sorted(a - m for a, m in clock)
    offset = stats.percentile(diffs, 0.5)
    return {
        "offset_ns": offset,
        "residual_ns": max(abs(d - offset) for d in diffs),
        "iqr_ns": stats.percentile(diffs, 0.75) - stats.percentile(diffs, 0.25),
        "samples": len(diffs),
    }


def device_modules(plane, programs: dict) -> list[Module]:
    """The executed programs of one device plane, in execution order."""
    out = []
    for ln in plane.lines:
        if ln.name != trace.MODULES_LINE:
            continue
        for e in ln.events:
            a = float(e.start_ns)
            out.append(Module(
                trace.classify(e.name, programs), e.name, a,
                a + float(e.duration_ns),
            ))
    out.sort(key=lambda m: m.start)
    return out


def device_busy(plane) -> tuple[list[tuple[float, float]], tuple[float, float]] | None:
    """(busy intervals, window) of one device plane, as ``lib/trace.py``
    counts them for ``device.idle_share``: the union of the operations'
    intervals, first operation to last."""
    for ln in plane.lines:
        if ln.name != trace.OPS_LINE:
            continue
        ops = [(s, s + d) for _, s, d in trace._events(ln) if d > 0]
        if not ops:
            return None
        return trace._union(ops), (
            min(a for a, _ in ops), max(b for _, b in ops))
    return None


def pair(launches: list[Launch], modules: list[Module]) -> list[tuple[Launch, Module]] | None:
    """Each traced prefill or decode launch with the execution of its
    program (see the module's docstring). None where no alignment of the
    two sequences has every launch meet a program of its kind that starts
    no earlier than the launch; launches whose programs had not run when
    the trace stopped stay unpaired."""
    ls = [ln for ln in launches if ln.kind in PAIRED_KINDS]
    ms = [m for m in modules if m.kind in PAIRED_KINDS]
    if not ls or not ms:
        return None
    head = sum(1 for m in ms if m.start < ls[0].start)
    for h in range(head, min(len(ms), head + MAX_HEAD) + 1):
        n = min(len(ls), len(ms) - h)
        if n <= 0:
            break
        if all(
            ms[h + i].kind == ls[i].kind and ms[h + i].start >= ls[i].start
            for i in range(n)
        ):
            return [(ls[i], ms[h + i]) for i in range(n)]
    return None


def innermost(phases: list[tuple[str, float, float]]) -> list[tuple[float, float, str | None]]:
    """The step thread's time as disjoint (start, end, phase) pieces, each
    under the innermost phase open then (None between phases). ``phases``
    sorted by (start, -end), properly nested, as one thread writes them."""
    out: list[tuple[float, float, str | None]] = []
    stack: list[tuple[str, float, float]] = []
    t = None

    def emit(upto: float) -> None:
        nonlocal t
        if t is not None and upto > t:
            out.append((t, upto, stack[-1][0] if stack else None))
        t = upto if t is None else max(t, upto)

    for name, a, b in phases:
        while stack and stack[-1][2] <= a:
            emit(stack[-1][2])
            stack.pop()
        emit(a)
        stack.append((name, a, b))
    while stack:
        emit(stack[-1][2])
        stack.pop()
    return out


def away(phase: str | None) -> bool:
    return phase is not None and (phase in AWAY or phase.endswith(AWAY_SUFFIX))


def idle_by_phase(busy, window, phases) -> dict[str, float]:
    """Device-idle seconds inside ``window`` by the step thread's innermost
    phase during them; ``(none)`` between phases, ``(untraced)`` where the
    host trace holds no annotation at all (before its first, after its
    last)."""
    pieces = innermost(phases)
    starts = [p[0] for p in pieces]
    out: dict[str, float] = {}
    w0, w1 = window
    edges = [(w0, w0)] + [iv for iv in busy if iv[1] > w0 and iv[0] < w1] + [(w1, w1)]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        a, b = max(end, w0), min(start, w1)
        if b <= a:
            continue
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(pieces) and pieces[i][0] < b:
            pa, pb, name = pieces[i]
            lo, hi = max(a, pa), min(b, pb)
            if hi > lo:
                key = name or "(none)"
                out[key] = out.get(key, 0.0) + (hi - lo) * 1e-9
                covered += hi - lo
            i += 1
        if (b - a) - covered > 0:
            out["(untraced)"] = out.get("(untraced)", 0.0) + (
                (b - a) - covered) * 1e-9
    return out


def read_file(path: str, programs: dict) -> dict | None:
    """Everything above from the trace at ``path``: None where it holds no
    ``engine.*`` annotation. ``pairs``, ``busy`` and ``window`` are None on
    a trace without a device plane (a CPU rehearsal)."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    host = host_events(profile)
    if host is None:
        return None
    out = dict(host, clock_fit=fit_clock(host["clock"]), modules=[],
               pairs=None, busy=None, window=None)
    planes = trace.device_planes(profile)
    if planes:
        out["modules"] = device_modules(planes[0], programs)
        out["pairs"] = pair(host["launches"], out["modules"])
        bw = device_busy(planes[0])
        if bw is not None:
            out["busy"], out["window"] = bw
    return out


# -- the flight recorder's timelines ------------------------------------


def timelines(engine) -> list | None:
    """Every timeline the engine's flight recorder holds finished, or None
    where it does not say it kept them all (a program whose recorder is a
    ring, or one that rotated)."""
    flight = getattr(engine, "flight", None)
    if flight is None or not getattr(flight, "complete", False):
        return None
    return flight.finished()


def event_s(tl, name: str, *, last: bool = False):
    """The monotonic instant of the timeline's first event ``name`` (with
    ``last``, the last instant the event coalesced), or None."""
    for ev in tl.events:
        if ev["name"] == name:
            return tl.t0 + (ev["t_last"] if last else ev["t"])
    return None


def chain(tl) -> dict | None:
    """The instants of one request's way to its first token, on
    ``time.monotonic``: None unless it has them all."""
    out = {
        "enqueue": tl.t0, "admit": event_s(tl, "admit", last=True),
        "dispatch": event_s(tl, "prefill_dispatch"),
        "first_token": event_s(tl, "first_token"),
        "first_delta": event_s(tl, "first_delta"),
    }
    if any(v is None for v in out.values()):
        return None
    ev = next(e for e in tl.events if e["name"] == "prefill_dispatch")
    out["seq"] = ev.get("seq")
    out["prompt_tokens"] = tl.attrs.get("prompt_tokens")
    return out


def match_records(chains: list[dict], records: list[dict], t0: float) -> list[tuple[dict, dict]]:
    """Each chain with the client's record of the same request: the same
    prompt length, enqueued between the client's send and its first
    chunk."""
    by_len: dict = {}
    for r in records:
        if r.get("ok") and r.get("chunks") and r.get("sent") is not None:
            by_len.setdefault(r["prompt_tokens"], []).append(r)
    out = []
    for c in chains:
        at = c["enqueue"] - t0
        for r in by_len.get(c["prompt_tokens"], ()):
            if r["sent"] <= at <= r["chunks"][0]:
                out.append((c, r))
                break
    return out
