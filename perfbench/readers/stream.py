"""Readers of the delivery path: a burst's tokens on their way from the
device to the client, and the event loop's own lag.

A profiled engine (``EngineConfig.profile``) writes, beside its
``engine.*`` annotations (``lib/spans.py``):

- ``stream.post`` on the step thread, around the posts of one device
  program's tokens, with the program's launch ``seq`` (a decode burst's, so
  the pairing of ``lib/spans.py`` gives the instant the program ended on
  the device);
- ``stream.take`` on the event loop, once an item ``generate()`` takes off
  its queue: ``rid`` (the stream's running number), ``wait_us`` (take
  minus post);
- ``loop.stall`` on the event loop, when its heartbeat woke over 50 ms
  late: ``lag_us``;
- the flight recorder's ``delta`` event, coalesced: the LAST entry's
  ``t_last`` is the instant ``generate()`` handed over the stream's last
  tokens, and with ``generated`` gives the client's formula at the engine;
- the counters ``stream.items`` / ``stream.wait_us`` and
  ``event_loop.stalled_us`` in ``profile_snapshot()``, and the heartbeat's
  ring of wake-ups (``engine.loop_probe.lags``: ``time.monotonic_ns`` of
  the wake-up, its lag in us).

Every reader returns None, and raises nothing, on a program without them
(any commit before PR 51). The trace is read once a run for these events
and kept on the run; that reading prints the ``stream:`` lines: the gap
chain's medians and every stall with what the step thread and the device
did meanwhile. Nothing of JAX or of the program is imported when this
module is loaded.
"""

import functools
import time
from collections import namedtuple

from lib import spans, stats, trace

POST, TAKE, STALL = "stream.post", "stream.take", "loop.stall"
MATCHED_SHARE = 0.9  # of the client's streams, for the overhead to be read
# how long after the engine's first delta the client's first chunk may come
# and still be the same stream's (the frontend's share of time to first
# token is a fraction of a ms; a stalled loop may hold it)
FIRST_CHUNK_S = 1.0

_Post = namedtuple("Post", "seq start")
_Take = namedtuple("Take", "rid at wait_us")
_Stall = namedtuple("Stall", "at lag_us")


def _say(msg: str) -> None:
    print(msg, flush=True)


def _reader(fn):
    """None, and a line in the log, where ``fn`` meets something it did not
    expect: a reader raises nothing, on any program."""
    @functools.wraps(fn)
    def guarded(run, cell):
        try:
            return fn(run, cell)
        except Exception as e:  # noqa: BLE001
            _say(f"stream: {fn.__name__} found nothing it could read: {e!r}")
            return None
    return guarded


_int = spans._int  # an annotation's attribute as an int, or a default


def _delivery_events(profile) -> dict | None:
    """The ``stream.*`` and ``loop.*`` annotations of a trace, on whatever
    thread's line: posts and takes in time order, stalls. None where the
    trace holds none."""
    posts, takes, stalls = [], [], []
    for plane in profile.planes:
        if plane.name != spans.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name not in (POST, TAKE, STALL):
                    continue
                a = float(e.start_ns)
                st = dict(e.stats)
                if name == POST:
                    posts.append(_Post(_int(st.get("seq"), -1), a))
                elif name == TAKE:
                    takes.append(_Take(
                        _int(st.get("rid"), -1), a, _int(st.get("wait_us"))))
                else:
                    stalls.append(_Stall(a, _int(st.get("lag_us"))))
    if not posts and not takes and not stalls:
        return None
    posts.sort(key=lambda p: p.start)
    takes.sort(key=lambda t: t.at)
    stalls.sort(key=lambda s: s.at)
    return {"posts": posts, "takes": takes, "stalls": stalls}


def _spans(run, cell):
    """The run's ``engine.*`` spans as ``readers/spans.py`` reads them once
    a run and keeps them on it."""
    if "_spans" not in run:
        cell.readers["spans:prefill_paired_tok_s"](run, cell)
    return run.get("_spans")


def _delivery(run, cell):
    """The run's trace read for the delivery path's events, once: None
    without a trace or without such events."""
    if "_stream" not in run:
        run["_stream"] = None
        path = run.get("trace_dir") and trace.find_xplane(run["trace_dir"])
        if path:
            from jax.profiler import ProfileData

            t = time.monotonic()
            try:
                run["_stream"] = _delivery_events(ProfileData.from_file(path))
            except Exception as e:  # noqa: BLE001 - a reader never raises
                _say(f"stream: the trace could not be read: {e!r}")
            if run["_stream"] is not None:
                _say(f"stream: read {path} in {time.monotonic() - t:.1f} s")
        if run["_stream"] is not None:
            try:
                _log(run, cell, run["_stream"])
            except Exception as e:  # noqa: BLE001 - a log line costs no metric
                _say(f"stream: no summary: {e!r}")
    return run["_stream"]


# -- the flight recorder's side: a stream's time per output token --------


def _last_delta_s(tl):
    """The instant ``generate()`` handed over the stream's last tokens:
    the LAST ``delta`` entry's last instant (another event between two
    deltas opens a new entry), or None."""
    for ev in reversed(tl.events):
        if ev["name"] == "delta":
            return tl.t0 + ev["t_last"]
    return None


def _streams(run):
    """A row for every finished request whose timeline holds both ends of
    its stream: the client's formula, (last delta - first delta) /
    (generated - 1), at ``generate()``. ``windowed`` is the load
    generator's own rule (``lib/loadgen.py``): an open loop counts the
    requests due inside the window (here: enqueued inside it, a few ms
    later), a closed loop those with a chunk inside it (here: a delta).
    None where the recorder did not keep the window whole."""
    if "_stream_rows" not in run:
        run["_stream_rows"] = None
        tls = spans.timelines(run.get("engine"))
        if tls is not None:
            t0, t1 = run["t0"], run["t0"] + run["seconds"]
            closed = (run.get("plan") or {}).get("loop") == "closed"
            rows = []
            for tl in tls:
                first = spans.event_s(tl, "first_delta")
                last = _last_delta_s(tl)
                n = tl.attrs.get("generated") or 0
                if (
                    first is None or last is None or n < 2
                    or getattr(tl, "dropped_events", 0)
                ):
                    continue
                rows.append({
                    "enqueue": tl.t0, "first_delta": first, "last_delta": last,
                    "prompt_tokens": tl.attrs.get("prompt_tokens"),
                    "tpot": (last - first) / (n - 1),
                    "windowed": (first < t1 and last >= t0) if closed
                    else t0 <= tl.t0 < t1,
                })
            run["_stream_rows"] = rows
    return run["_stream_rows"]


def _matched(run):
    """(row, record, the client's time per output token) for the client's
    streams that find their row, and the share of them that do. The rule
    is ``lib/spans.py: match_records``' (the same prompt length, the
    engine's instant between the client's send and its first chunk) taken
    at the first delta, not at the enqueue: a closed loop's requests wait
    seconds in the queue, where another request of the same length
    enqueued meanwhile would match as well. The client's first chunk
    follows the engine's first delta by a fraction of a ms; a record takes
    the nearest row within ``FIRST_CHUNK_S`` and a row serves one record."""
    rows = _streams(run)
    if not rows:
        return [], 0.0
    t0 = run["t0"]
    by_len: dict = {}
    for row in rows:
        by_len.setdefault(row["prompt_tokens"], []).append(row)
    got, clients, taken = [], 0, set()
    for r in stats.windowed(run["records"]):
        tpot = stats.tpot_s(r)
        if tpot is None or r.get("sent") is None:
            continue
        clients += 1
        first = t0 + r["chunks"][0]
        near = [
            (first - row["first_delta"], i, row)
            for i, row in enumerate(by_len.get(r["prompt_tokens"], ()))
            if t0 + r["sent"] <= row["first_delta"] <= first
            and first - row["first_delta"] <= FIRST_CHUNK_S
            and id(row) not in taken
        ]
        if near:
            _, _, row = min(near)
            taken.add(id(row))
            got.append((row, r, tpot))
    return got, (len(got) / clients if clients else 0.0)


@_reader
def stream_tpot_p50_ms(run, cell):
    """Median of (last ``delta`` - ``first_delta``) / (generated - 1) over
    the window's requests, by the load generator's rule: the whole
    window. Listed on every cell that lists any of these metrics, so it
    is also what has a traced run's trace read and its ``stream:`` lines
    printed where the cell lists no metric of the trace's own."""
    _delivery(run, cell)
    rows = [r for r in _streams(run) or [] if r["windowed"]]
    if not rows:
        return None
    return stats.ms(stats.percentile([r["tpot"] for r in rows], 0.5))


@_reader
def tpot_overhead_p50_ms(run, cell):
    """Median, over the matched requests, of the client's own time per
    output token minus the engine's: what worker endpoint, transport,
    frontend and socket add to a gap. None under 90% matched."""
    got, share = _matched(run)
    if not got or share < MATCHED_SHARE:
        if got:
            _say(f"stream: {100 * share:.1f}% of the client's streams "
                 "matched a timeline: the overhead is not read")
        return None
    return stats.ms(stats.percentile(
        [client - row["tpot"] for row, _, client in got], 0.5))


# -- the trace's side ----------------------------------------------------


def _landings_ns(run, cell) -> list[float]:
    """A paired decode program's end on the device -> its ``stream.post``
    begins: the read of its tokens and phase 1 of ``_process_burst``."""
    tr, ev = _spans(run, cell), _delivery(run, cell)
    if not tr or not tr["pairs"] or not ev:
        return []
    ended = {ln.seq: m.end for ln, m in tr["pairs"] if ln.kind == "decode"}
    return [p.start - ended[p.seq] for p in ev["posts"] if p.seq in ended]


@_reader
def burst_landing_p50_ms(run, cell):
    got = _landings_ns(run, cell)
    if not got:
        return None
    return stats.percentile(got, 0.5) * 1e-6


@_reader
def post_to_stream_p95_ms(run, cell):
    """95th percentile of ``stream.take``'s ``wait_us``, the traced part."""
    ev = _delivery(run, cell)
    if not ev or not ev["takes"]:
        return None
    return stats.percentile([t.wait_us for t in ev["takes"]], 0.95) * 1e-3


def _counted(run, name):
    """What the counter ``name`` counted between the window's two
    snapshots."""
    before, after = run["profile"]
    return after[name]["calls"] - before.get(name, {}).get("calls", 0)


@_reader
def post_to_stream_mean_ms(run, cell):
    """``stream.wait_us`` over ``stream.items`` between the window's two
    snapshots: the mean wait from post to take of the WHOLE window, where
    the 95th percentile reads the traced part."""
    if "stream.items" not in run["profile"][1]:
        return None
    items = _counted(run, "stream.items")
    return _counted(run, "stream.wait_us") * 1e-3 / items if items else None


def _gaps_ns(takes) -> list[float]:
    by_rid: dict = {}
    for t in takes:
        by_rid.setdefault(t.rid, []).append(t.at)
    out = []
    for ats in by_rid.values():
        ats.sort()
        out.extend(b - a for a, b in zip(ats, ats[1:]))
    return out


@_reader
def stream_gap_p95_ms(run, cell):
    """Gaps between a stream's consecutive ``stream.take``, pooled, 95th
    percentile, the traced part: the gap the client's ``itl_p95_ms`` sees,
    as the engine hands it over."""
    ev = _delivery(run, cell)
    gaps = _gaps_ns(ev["takes"]) if ev else []
    if not gaps:
        return None
    return stats.percentile(gaps, 0.95) * 1e-6


# -- the event loop's heartbeat ------------------------------------------


def _snapshots(run):
    """(before, after, seconds between them) of the window's two
    ``profile_snapshot()`` calls, or None where they do not say when they
    were taken or hold no heartbeat."""
    before, after = run["profile"]
    if (
        "window.at" not in before or "window.at" not in after
        or "event_loop.stalled_us" not in after
    ):
        return None
    apart = after["window.at"]["secs"] - before["window.at"]["secs"]
    return (before, after, apart) if apart > 0 else None


def _wake_ups(run):
    """The heartbeat's (instant in ns of ``time.monotonic``, lag in us)
    between the window's two snapshots' own instants, from the probe's
    ring; None where there is no ring or it no longer reaches back to the
    window's opening."""
    snaps = _snapshots(run)
    lags = getattr(getattr(run.get("engine"), "loop_probe", None), "lags", None)
    if snaps is None or not lags:
        return None
    lo, hi = (snap["window.at"]["secs"] * 1e9 for snap in snaps[:2])
    rows = list(lags)
    if len(rows) == getattr(lags, "maxlen", None) and rows[0][0] > lo:
        _say("stream: the heartbeat's ring begins after the window opened")
        return None
    return [(at, lag) for at, lag in rows if lo <= at <= hi]


@_reader
def loop_lag_max_ms(run, cell):
    """The latest wake-up of the heartbeat between the window's two
    snapshots: the largest lag the probe's ring holds between their
    instants (a running maximum cannot be differenced)."""
    rows = _wake_ups(run)
    return max(lag for _, lag in rows) * 1e-3 if rows else None


@_reader
def loop_stalled_share(run, cell):
    """``event_loop.stalled_us`` between the window's two snapshots over
    the time that lay between them (``window.at``)."""
    snaps = _snapshots(run)
    if snaps is None:
        return None
    return 100.0 * _counted(run, "event_loop.stalled_us") * 1e-6 / snaps[2]


# -- the log lines -------------------------------------------------------


def _p50(values):
    return stats.percentile(values, 0.5)


def _fmt(v, scale=1.0) -> str:
    return "-" if v is None else f"{v * scale:.2f}"


def _stall_lines(tr, ev) -> list[str]:
    """Every ``loop.stall`` with the step thread's phases and the device's
    state while the loop stood still: ``lib/spans.py: idle_by_phase`` over
    the stall as its window, once with nothing busy (the phases' shares)
    and once with the device's busy intervals (what is left is idle)."""
    phases = tr["phases"] if tr else []
    busy = (tr["busy"] or []) if tr else []
    w0 = tr["window"][0] if tr and tr["window"] else None
    out = []
    for s in ev["stalls"]:
        a, b = s.at - s.lag_us * 1e3, s.at
        secs = max(b - a, 1.0) * 1e-9
        by = spans.idle_by_phase([], (a, b), phases)
        shares = ", ".join(
            f"{k} {100 * v / secs:.0f}%"
            for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:4])
        idle = sum(spans.idle_by_phase(busy, (a, b), phases).values())
        at = f"{(a - w0) * 1e-9:+.3f} s of the traced window" if w0 else "?"
        out.append(
            f"stream: loop.stall {s.lag_us * 1e-3:.1f} ms from {at}: step "
            f"thread {shares}; device busy {100 * (1 - idle / secs):.0f}%"
        )
    return out


def _log(run, cell, ev) -> None:
    tr = _spans(run, cell)
    _say(f"stream: {len(ev['posts'])} posts, {len(ev['takes'])} takes of "
         f"{len({t.rid for t in ev['takes']})} streams, "
         f"{len(ev['stalls'])} stalls in the trace")
    # the device's side of a gap: the decode programs, what ran between
    # two of them, and their start-to-start cycle
    decodes = sorted(
        ((m, ln) for ln, m in (tr["pairs"] if tr and tr["pairs"] else [])
         if ln.kind == "decode"), key=lambda p: p[0].start)
    prefills = sorted(
        (m for m in (tr["modules"] if tr else []) if m.kind == "prefill"),
        key=lambda m: m.start)
    cycles, between, steps = [], [], []
    for (m0, ln0), (m1, _) in zip(decodes, decodes[1:]):
        cycles.append(m1.start - m0.start)
        steps.append(ln0.counts.get("steps", 0))
        between.append(sum(
            p.end - p.start for p in prefills if m0.end <= p.start < m1.start))
    waits = [t.wait_us for t in ev["takes"]]
    got, share = _matched(run)
    t0 = run["t0"]
    _say(
        "stream: gap chain, p50 ms: decode program "
        f"{_fmt(_p50([m.end - m.start for m, _ in decodes]), 1e-6)} of "
        f"{_fmt(_p50(steps))} steps, prefill programs between two bursts "
        f"{_fmt(_p50(between), 1e-6)} (mean "
        f"{_fmt(sum(between) / len(between) if between else None, 1e-6)}), "
        f"cycle start to start {_fmt(_p50(cycles), 1e-6)} (mean "
        f"{_fmt(sum(cycles) / len(cycles) if cycles else None, 1e-6)}; all "
        f"cycles over all their steps "
        f"{_fmt(sum(cycles) / sum(steps) if sum(steps) else None, 1e-6)} a "
        "step) -> device end to "
        f"post {_fmt(_p50(_landings_ns(run, cell)), 1e-6)} -> post to take "
        f"{_fmt(_p50(waits), 1e-3)} -> take to the client's chunk "
        f"{_fmt(_p50([t0 + r['chunks'][-1] - row['last_delta'] for row, r, _ in got]), 1e3)}"
        f" (a stream's last; its first "
        f"{_fmt(_p50([t0 + r['chunks'][0] - row['first_delta'] for row, r, _ in got]), 1e3)})"
        f"; per token over {len(got)} matched streams "
        f"({100 * share:.1f}% of the client's): engine "
        f"{_fmt(_p50([row['tpot'] for row, _, _ in got]), 1e3)}, client "
        f"{_fmt(_p50([c for _, _, c in got]), 1e3)}"
    )
    records = stats.windowed(run["records"])
    _say(
        "stream: a stream's gaps, 95th percentile ms: between its takes in "
        f"the trace {_fmt(stats.percentile(_gaps_ns(ev['takes']), 0.95), 1e-6)}"
        ", between the client's chunks over the whole window "
        f"{_fmt(stats.percentile(stats.pooled(records, stats.gaps_s), 0.95), 1e3)}"
        "; the client's time per output token, p50 "
        f"{_fmt(_p50(stats.pooled(records, stats.tpot_s)), 1e3)}"
    )
    mean = post_to_stream_mean_ms(run, cell)
    if mean is not None:
        _say(f"stream: between the window's snapshots "
             f"{_counted(run, 'stream.items')} items took {mean:.2f} ms from "
             "post to take on average")
    rows = _wake_ups(run)
    if rows:
        late = [lag for _, lag in rows if lag > 50_000]
        lag, at = max((lag, at) for at, lag in rows)
        _say(
            f"stream: the heartbeat ticked {len(rows)} times between them, "
            f"late {sum(lag for _, lag in rows) / len(rows) * 1e-3:.2f} ms on "
            f"average, {len(late)} times over 50 ms for {sum(late) * 1e-3:.1f}"
            f" ms in all; the latest, {lag * 1e-3:.1f} ms, "
            f"{at * 1e-9 - t0:+.1f} s from the window's opening (it is "
            f"{run['seconds']:.0f} s)"
        )
    for line in _stall_lines(tr, ev):
        _say(line)
