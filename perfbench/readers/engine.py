"""Readers of the program's counters: queue, pages, the step thread's
profile, compile events, and the engine's side of time to first token."""

from lib import stats


def _in_window(run):
    t0, t1 = run["t0"], run["t0"] + run["seconds"]
    return [row for row in run["samples"] if t0 <= row[0] <= t1]


def waiting_peak(run, cell):
    rows = _in_window(run)
    return max((r[1] for r in rows), default=None)


def pages_peak_share(run, cell):
    rows = _in_window(run)
    if not rows:
        return None
    return 100.0 * max(r[2] for r in rows) / run["engine"].config.num_pages


def host_share(run, cell):
    """The share of the window the step thread spent neither idle nor
    blocked on the device: ``profile_snapshot()`` phases ``idle`` and
    ``*.d2h_wait``, window end minus window start."""
    before, after = run["profile"]
    if "idle" not in after and "dispatch" not in after:
        return None  # the phase profiler was off

    def secs(name):
        return after.get(name, {}).get("secs", 0.0) - before.get(
            name, {}).get("secs", 0.0)

    away = secs("idle") + secs("dispatch.d2h_wait") + secs("readmit.d2h_wait")
    return 100.0 * max(0.0, 1.0 - away / run["seconds"])


def compiles_in_window(run, cell):
    return run["compiles_in_window"]


def ttft_overhead_p50_ms(run, cell):
    """Client first chunk minus the engine's first delta, per request,
    matched by the prompt's length: what the frontend, the
    transport and the client add to time to first token."""
    t0 = run["t0"]
    by_len: dict = {}
    for n, at in run["firsts"]:
        by_len.setdefault(n, []).append(at - t0)
    deltas = []
    for r in stats.windowed(run["records"]):
        if not r["ok"]:
            continue
        firsts = by_len.get(r["prompt_tokens"], [])
        # several requests may share a length: the engine's first delta is
        # the latest one not after the client's first chunk
        before = [a for a in firsts if a <= r["chunks"][0]]
        if before:
            deltas.append(r["chunks"][0] - max(before))
    return stats.ms(stats.percentile(deltas, 0.5))
