"""The step thread's busy share, read where it can be trusted: from the
``engine.<phase>`` annotations on the profiler's clock, inside the traced
window.

``engine:host_share`` sets the time the step thread was away (parked, or
blocked on the device) between two ``profile_snapshot()`` calls against the
window's nominal seconds; in a traced run ``run.py`` takes the second call
after the profiler has stopped, 15-130 s past the window's close, so the
time away outgrows the divisor: the share clamps to 0.0
(``solar-open2.reasoning``) or wanders with how long the profiler took to
stop (PR 37). Nor do the phase sums hold while the profiler stops: it
stalls the step thread inside whatever phase it is in. A snapshot of a
program since PR 37 says when it was taken (``window.at``), and this reader
prints how far apart the two were.

None, raising nothing, on a program without the annotations.
"""

from lib import spans


def _say(msg: str) -> None:
    print(msg, flush=True)


def _pieces(tr):
    """(window, the step thread's innermost pieces) of a run's spans: the
    device's traced window; the annotations' own extent on a trace without
    a device plane (a CPU rehearsal)."""
    if not tr or not tr["phases"]:
        return None, []
    pieces = spans.innermost(tr["phases"])
    window = tr["window"] or (pieces[0][0], pieces[-1][1])
    return window, pieces


def step_thread_busy_share(run, cell):
    """The step thread's time in phases other than ``idle`` and
    ``*.d2h_wait`` (between phases counts as busy; before its first
    annotation and after its last nothing is counted), over the traced
    window."""
    try:
        # that reader reads the spans once a run and keeps them on the run
        cell.readers["spans:prefill_paired_tok_s"](run, cell)
        window, pieces = _pieces(run.get("_spans"))
        if not pieces:
            return None
        w0, w1 = window
        busy = sum(
            min(b, w1) - max(a, w0) for a, b, phase in pieces
            if not spans.away(phase) and min(b, w1) > max(a, w0)
        )
        _log_snapshots(run)
        return 100.0 * busy / (w1 - w0) if w1 > w0 else None
    except Exception as e:  # noqa: BLE001 - a reader raises nothing
        _say(f"step_thread: found nothing it could read: {e!r}")
        return None


def _log_snapshots(run) -> None:
    """How far apart the window's two ``profile_snapshot()`` calls were
    taken, against the seconds ``engine:host_share`` divides by."""
    before, after = run["profile"]
    if "window.at" not in before or "window.at" not in after:
        return
    apart = after["window.at"]["secs"] - before["window.at"]["secs"]

    def secs(name):
        return after.get(name, {}).get("secs", 0.0) - before.get(
            name, {}).get("secs", 0.0)

    away = secs("idle") + secs("dispatch.d2h_wait") + secs("readmit.d2h_wait")
    _say(
        f"step_thread: the window's two profile snapshots were taken "
        f"{apart:.2f} s apart (the window is {run['seconds']:.0f} s) and the "
        f"step thread was away {away:.2f} s between them: engine.host_share "
        f"sets that against the window's seconds and reads "
        f"{100.0 * max(0.0, 1.0 - away / run['seconds']):.1f}%"
    )
