"""Readers of the profiler's trace and the device's memory: time by kind
of program, the decode kernel's calls, busy and idle. The bytes a step must
move come from ``lib/costs.py``; the peaks from its table."""

from lib import costs
from lib import stack as stk


def _kind(run, kind):
    tr = run.get("trace")
    if not tr:
        return None
    return tr["by_kind"].get(kind)


def _attention_calls(run, cell):
    k = _kind(run, "decode")
    if not k:
        return None, None
    secs = calls = 0
    for name in cell.config["trace_names"]["decode_attention_ops"]:
        for op, rec in k["ops"].items():
            if name in op:
                secs += rec[0]
                calls += rec[1]
    return (secs, calls) if calls else (None, None)


def _model_steps(run, cell):
    """Model steps inside the traced decode programs: the decode-attention
    kernel runs once a layer a step."""
    _, calls = _attention_calls(run, cell)
    if not calls:
        return None
    return calls / cell.config["num_hidden_layers"]


def decode_step_ms(run, cell):
    k, steps = _kind(run, "decode"), _model_steps(run, cell)
    if not k or not steps:
        return None
    return 1e3 * k["secs"] / steps


def _traced_rows(run):
    """The sampler's rows inside the traced part of the window."""
    if not run.get("traced"):
        return []
    a, b = run["t0"] + run["traced"][0], run["t0"] + run["traced"][1]
    return [r for r in run["samples"] if a <= r[0] <= b and r[3] > 0]


def _live(run, cell):
    """(mean live tokens, counted in whole pages; mean live slots) while a
    decode batch existed in the traced part."""
    rows = _traced_rows(run)
    if not rows:
        return None, None
    page = run["engine"].config.page_size
    return (
        page * sum(r[4] for r in rows) / len(rows),
        sum(r[3] for r in rows) / len(rows),
    )


def decode_hbm_share(run, cell):
    """Bytes a decode step must move over the peak bandwidth, over the
    step's device time."""
    step_ms = decode_step_ms(run, cell)
    tokens, batch = _live(run, cell)
    if not step_ms or tokens is None:
        return None
    peak = costs.peaks_for(run["device"]["kind"])["hbm_bytes_s"]
    least_s = costs.decode_step_bytes(cell.config, tokens, batch) / peak
    return 100.0 * least_s / (step_ms * 1e-3)


def fused_decode_attn_hbm_share(run, cell):
    """The decode-attention kernel alone: the bytes a call must move over
    the peak bandwidth, over its device time a call."""
    secs, calls = _attention_calls(run, cell)
    tokens, batch = _live(run, cell)
    if not calls or tokens is None:
        return None
    peak = costs.peaks_for(run["device"]["kind"])["hbm_bytes_s"]
    least_s = costs.decode_attention_bytes_per_call(
        cell.config, tokens, batch) / peak
    return 100.0 * least_s / (secs / calls)


def prefill_tok_s(run, cell):
    """Real prompt tokens over the device time of prefill programs: the
    tokens of the prefill dispatches the engine made during the traced
    part, by the benchmark's tap on them. A dispatch runs up to two bursts
    after it is made, so a program at either edge of the trace may be
    counted on one side only."""
    k = _kind(run, "prefill")
    if not k or not k["secs"] or not run.get("traced"):
        return None
    a, b = run["t0"] + run["traced"][0], run["t0"] + run["traced"][1]
    tokens = sum(sum(ns) for t, ns in run.get("prefills", ()) if a <= t < b)
    return tokens / k["secs"] if tokens else None


def idle_share(run, cell):
    tr = run.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def peak_mem_share(run, cell):
    """The peak when the window closed: set-up and serving, not the output
    check's reference."""
    limit = stk.memory_limit_bytes()
    if not limit or not run.get("memory_peak_bytes"):
        return None
    return 100.0 * run["memory_peak_bytes"] / limit
