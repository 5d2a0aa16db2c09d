"""The arithmetic of the client-side metrics: one definition of a
percentile and of each latency, used by every metric reader.

A request record is what ``loadgen`` writes for one request: ``due``,
``sent`` and ``chunks`` (the arrival instant of every content chunk of the
stream) in seconds relative to the opening of the window,
``completion_tokens`` and ``prompt_tokens`` from the response's usage, and
``ok`` (HTTP 200, stream closed, exactly ``max_tokens`` tokens, finish
reason ``length``). No JAX here.
"""

from __future__ import annotations


def percentile(values, q: float):
    """The ``q`` quantile (0..1) by linear interpolation between the two
    nearest ranks; None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def windowed(records: list[dict]) -> list[dict]:
    return [r for r in records if r.get("windowed")]


def ttft_s(rec: dict):
    """From the instant the request was DUE to its first content chunk: a
    generator that runs late, or a server that stalls the requests behind
    it, both count."""
    if not rec.get("ok") or not rec.get("chunks"):
        return None
    return rec["chunks"][0] - rec["due"]


def late_s(rec: dict):
    """How late the generator sent the request."""
    if rec.get("sent") is None:
        return None
    return rec["sent"] - rec["due"]


def gaps_s(rec: dict) -> list[float]:
    """The gaps between successive content chunks of one stream: what a
    reader of the stream sees as a stall, however many tokens a chunk
    carries."""
    c = rec.get("chunks") or []
    if not rec.get("ok"):
        return []
    return [b - a for a, b in zip(c, c[1:])]


def tpot_s(rec: dict):
    """(last chunk - first chunk) / (completion tokens - 1), the tokens
    from the response's usage count: right when a chunk carries several."""
    c = rec.get("chunks") or []
    n = rec.get("completion_tokens") or 0
    if not rec.get("ok") or len(c) < 2 or n < 2:
        return None
    return (c[-1] - c[0]) / (n - 1)


def tokens_in_window(records: list[dict], seconds: float) -> float:
    """Completion tokens delivered inside ``[0, seconds)``. SSE chunks
    carry no token count, so a stream's tokens (its usage count) are
    credited evenly to its chunks; a stream wholly inside the window
    counts exactly. Streams that failed count for nothing."""
    total = 0.0
    for r in records:
        c = r.get("chunks") or []
        n = r.get("completion_tokens") or 0
        if not r.get("ok") or not c or not n:
            continue
        inside = sum(1 for t in c if 0.0 <= t < seconds)
        total += n * inside / len(c)
    return total


def ms(x):
    return None if x is None else 1e3 * x


def pooled(records: list[dict], fn) -> list[float]:
    out = []
    for r in records:
        v = fn(r)
        if v is None:
            continue
        if isinstance(v, list):
            out.extend(v)
        else:
            out.append(v)
    return out
