"""Readers of what the client saw: the end-to-end metrics and the load
generator's own. Each takes the run (records of the requests, the window)
and the cell, and returns a number or None."""

from lib import stats


def _pct(run, fn, q):
    v = stats.percentile(stats.pooled(stats.windowed(run["records"]), fn), q)
    return stats.ms(v)


def ttft_p50_ms(run, cell):
    return _pct(run, stats.ttft_s, 0.5)


def ttft_p95_ms(run, cell):
    return _pct(run, stats.ttft_s, 0.95)


def itl_p95_ms(run, cell):
    return _pct(run, stats.gaps_s, 0.95)


def tpot_p50_ms(run, cell):
    return _pct(run, stats.tpot_s, 0.5)


def late_p99_ms(run, cell):
    if run["plan"]["loop"] != "open":
        return None
    return _pct(run, stats.late_s, 0.99)


def out_tok_s(run, cell):
    """Completion tokens delivered inside the window over its seconds, on
    the cell's chips."""
    return stats.tokens_in_window(run["records"], run["seconds"]) / run["seconds"]


def setup_s(run, cell):
    """Process start to the opening of the window: build, weights,
    precompile, warm-up, lead-in."""
    return run["setup_s"]
