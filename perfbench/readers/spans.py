"""Readers of the engine's own spans (``lib/spans.py``): the way of a request
to its first token, split at the instants the program records; decode
occupancy; prefill tokens over the device time of the very programs that
took them; device idle while the step thread was busy.

Every reader returns None, and raises nothing, on a program that writes no
``engine.*`` annotation, whose flight recorder does not say it kept the
whole window, or whose launches cannot be paired with executions: on such a
program (any commit before the spans existed) these metrics are absent from
the line. The trace is read once a run and kept on the run; that reading
prints two ``spans:`` lines, the chain's medians with its residual against
the client's time to first token, and device-idle seconds by step-thread
phase.
"""

import functools
import time

from lib import spans, stats, trace


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
            _say(f"spans: {fn.__name__} found nothing it could read: {e!r}")
            return None
    return guarded


def _traced(run, cell):
    """The run's trace read for spans, once: None without one."""
    if "_spans" not in run:
        run["_spans"] = None
        path = run.get("trace_dir") and trace.find_xplane(run["trace_dir"])
        if path:
            t = time.monotonic()
            try:
                run["_spans"] = spans.read_file(
                    path, cell.config["trace_names"]["programs"])
            except Exception as e:  # noqa: BLE001 - a reader never raises
                _say(f"spans: the trace could not be read for spans: {e!r}")
            if run["_spans"] is not None:
                _say(f"spans: read {path} in {time.monotonic() - t:.1f} s")
                try:
                    _log(run, cell, run["_spans"])
                except Exception as e:  # noqa: BLE001 - a log line costs no metric
                    _say(f"spans: no summary: {e!r}")
    return run["_spans"]


def _chains(run):
    """Chains of the requests enqueued inside the window, or None where
    the recorder did not keep the window whole."""
    if "_chains" not in run:
        run["_chains"] = None
        tls = spans.timelines(run.get("engine"))
        if tls is not None:
            t0, t1 = run["t0"], run["t0"] + run["seconds"]
            run["_chains"] = [
                c for c in map(spans.chain, tls)
                if c is not None and t0 <= c["enqueue"] < t1
            ]
    return run["_chains"]


def _to_mono(tr, ns: float) -> float:
    """A profiler instant in seconds of ``time.monotonic``."""
    return (ns - tr["clock_fit"]["offset_ns"]) * 1e-9


def _traced_requests(run, cell):
    """(chain, launch, program) for every request whose prefill launch is
    paired with an execution in the trace; None where nothing pairs."""
    tr, chains = _traced(run, cell), _chains(run)
    if not tr or not tr["pairs"] or not tr["clock_fit"] or chains is None:
        return None
    by_seq = {ln.seq: (ln, m) for ln, m in tr["pairs"] if ln.kind == "prefill"}
    out = [(c, *by_seq[c["seq"]]) for c in chains if c["seq"] in by_seq]
    return out or None


def _p50_ms(values):
    return stats.ms(stats.percentile(values, 0.5))


@_reader
def queue_wait_p50_ms(run, cell):
    """Enqueue -> ``admit``, the requests enqueued inside the window."""
    chains = _chains(run)
    if not chains:
        return None
    return _p50_ms([c["admit"] - c["enqueue"] for c in chains])


@_reader
def dispatch_to_first_token_p50_ms(run, cell):
    """``prefill_dispatch`` -> ``first_token``, the whole window."""
    chains = _chains(run)
    if not chains:
        return None
    return _p50_ms([c["first_token"] - c["dispatch"] for c in chains])


@_reader
def prefill_device_wait_p50_ms(run, cell):
    """``prefill_dispatch`` -> the request's prefill program starts on the
    device: the traced part, by the pairing and the clock fit."""
    reqs = _traced_requests(run, cell)
    if not reqs:
        return None
    tr = run["_spans"]
    return _p50_ms([_to_mono(tr, m.start) - c["dispatch"] for c, _, m in reqs])


@_reader
def first_token_landing_p50_ms(run, cell):
    """The request's prefill program ends on the device -> ``first_token``
    (the host has the value): the traced part."""
    reqs = _traced_requests(run, cell)
    if not reqs:
        return None
    tr = run["_spans"]
    return _p50_ms([c["first_token"] - _to_mono(tr, m.end) for c, _, m in reqs])


@_reader
def batch_occupancy(run, cell):
    """Live slots over slots offered, over the traced decode bursts,
    weighted by their steps."""
    tr = _traced(run, cell)
    if not tr:
        return None
    bursts = [ln.counts for ln in tr["launches"] if ln.kind == "decode"]
    offered = sum(c.get("slots", 0) * c.get("steps", 0) for c in bursts)
    if not offered:
        return None
    live = sum(c.get("live", 0) * c.get("steps", 0) for c in bursts)
    return 100.0 * live / offered


def _paired_prefills(tr):
    """Paired prefill launches whose programs ran wholly inside the traced
    window."""
    if not tr or not tr["pairs"] or not tr["window"]:
        return []
    w0, w1 = tr["window"]
    return [
        (ln, m) for ln, m in tr["pairs"]
        if ln.kind == "prefill" and m.start >= w0 and m.end <= w1
    ]


@_reader
def prefill_paired_tok_s(run, cell):
    """Real prompt tokens of the prefill launches whose programs ran inside
    the trace, over those programs' device time."""
    got = _paired_prefills(_traced(run, cell))
    secs = sum(m.end - m.start for _, m in got) * 1e-9
    tokens = sum(ln.counts.get("tokens", 0) for ln, _ in got)
    return tokens / secs if secs > 0 and tokens else None


def _idle(tr):
    if not tr or not tr["busy"] or not tr["phases"]:
        return None
    return spans.idle_by_phase(tr["busy"], tr["window"], tr["phases"])


@_reader
def idle_host_busy_share(run, cell):
    """Device idle while the step thread was in a phase other than ``idle``
    and ``*.d2h_wait`` (between phases counts as busy), over the traced
    window; the window and the busy time are ``device.idle_share``'s."""
    tr = _traced(run, cell)
    by = _idle(tr)
    if by is None:
        return None
    w0, w1 = tr["window"]
    busy_host = sum(
        s for name, s in by.items()
        if name != "(untraced)" and not spans.away(name)
    )
    return 100.0 * busy_host / ((w1 - w0) * 1e-9)


# -- the two log lines ---------------------------------------------------


def _log(run, cell, tr) -> None:
    fit = tr["clock_fit"] or {}
    kinds: dict = {}
    for ln in tr["launches"]:
        kinds[ln.kind] = kinds.get(ln.kind, 0) + 1
    mods: dict = {}
    for m in tr["modules"]:
        mods[m.kind] = mods.get(m.kind, 0) + 1
    _say(
        f"spans: {len(tr['phases'])} phase annotations, launches {kinds}, "
        f"executions {mods}, paired "
        f"{len(tr['pairs']) if tr['pairs'] else 0}; clock fit over "
        f"{fit.get('samples', 0)} samples, residual "
        f"{fit.get('residual_ns', 0.0) * 1e-3:.1f} us (middle half "
        f"{fit.get('iqr_ns', 0.0) * 1e-3:.1f} us)"
    )
    by = _idle(tr)
    if by is not None:
        w0, w1 = tr["window"]
        table = ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(by.items(), key=lambda kv: -kv[1])
        )
        _say(f"spans: device idle {sum(by.values()):.4f} s of "
             f"{(w1 - w0) * 1e-9:.3f} s by step-thread phase: {table}")
    _log_chain(run, cell, tr)


def _log_chain(run, cell, tr) -> None:
    """The chain's medians over the traced requests, against the client's
    time to first token of the same requests."""
    reqs = _traced_requests(run, cell)
    if not reqs:
        return
    program = {id(c): m for c, _, m in reqs}
    rows = [
        (c, r, program[id(c)]) for c, r in spans.match_records(
            [c for c, _, _ in reqs], stats.windowed(run["records"]),
            run["t0"])
    ]
    if not rows:
        return
    t0 = run["t0"]
    parts = {
        "queue_wait": [c["admit"] - c["enqueue"] for c, _, _ in rows],
        "admit_to_dispatch": [c["dispatch"] - c["admit"] for c, _, _ in rows],
        "device_wait": [_to_mono(tr, m.start) - c["dispatch"] for c, _, m in rows],
        "prefill_program": [(m.end - m.start) * 1e-9 for _, _, m in rows],
        "landing": [c["first_token"] - _to_mono(tr, m.end) for c, _, m in rows],
        "frontend": [t0 + r["chunks"][0] - c["first_delta"] for c, r, _ in rows],
    }
    beside = {
        "ingress": [c["enqueue"] - (t0 + r["due"]) for c, r, _ in rows],
        "token_to_delta": [c["first_delta"] - c["first_token"] for c, _, _ in rows],
    }
    ttft = _p50_ms([stats.ttft_s(r) for _, r, _ in rows])
    total = sum(_p50_ms(v) for v in parts.values())
    _say(
        f"spans: TTFT chain over {len(rows)} traced requests, p50 ms: "
        + ", ".join(f"{k} {_p50_ms(v):.2f}" for k, v in parts.items())
        + f"; sum {total:.2f} against the client's {ttft:.2f}: residual "
        f"{ttft - total:.2f} ms ({100.0 * (ttft - total) / ttft:.1f}%); "
        "beside the chain: "
        + ", ".join(f"{k} {_p50_ms(v):.2f}" for k, v in beside.items())
    )
