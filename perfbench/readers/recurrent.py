"""Readers for a decoder with recurrent (KDA) layers beside paged GQA
layers (configurations whose reference is ``linear_moe``): each KDA kernel
against its own roofline and the whole decode step against its bytes
(``lib/costs_linear_moe.py``), and how many of the state rows held were
live. The kernels are found in the trace by the names the configuration's
``trace_names`` gives (``kda_decode_ops``, ``kda_prefill_ops``); the rows
held come from the program's ``recurrent_state.rows`` counter; the
sampler's live slots and tokens and the experts a step touched are read as
``readers/moe.py`` reads them (imported, not copied). On a program without
those names or counters every reader returns None."""

from lib import costs, costs_linear_moe
from readers import moe as _moe


def _ops(run, cell, kind, key):
    """(device seconds, calls) of the ops of ``kind`` programs whose names
    hold one of ``trace_names[key]``."""
    tr = run.get("trace")
    k = tr["by_kind"].get(kind) if tr else None
    names = cell.config.get("trace_names", {}).get(key)
    if not k or not names:
        return None, None
    secs = calls = 0
    for op, rec in k["ops"].items():
        if any(n in op for n in names):
            secs += rec[0]
            calls += rec[1]
    return (secs, calls) if calls else (None, None)


def _roof_s(run, n_bytes, flops):
    kind = run["device"]["kind"]
    return max(n_bytes / costs.peaks_for(kind)["hbm_bytes_s"],
               flops / costs_linear_moe.peak_flops_s(kind))


def kda_decode_roofline_share(run, cell):
    """A call of ``kda_step``: the time its live rows' state (read and
    written) and operands take at the peak bandwidth, or its operations at
    the peak rate if that is longer, over its device time a call."""
    secs, calls = _ops(run, cell, "decode", "kda_decode_ops")
    if not calls:
        return None
    _, batch = _moe._live(run)
    if not batch:
        return None
    least_s = _roof_s(
        run, costs_linear_moe.kda_step_bytes_per_call(cell.config, batch),
        costs_linear_moe.kda_step_flops_per_call(cell.config, batch))
    return 100.0 * least_s / (secs / calls)


def kda_prefill_roofline_share(run, cell):
    """A call of ``kda_chunk``: the blocks of 64 real tokens and the rows
    of the prefill dispatches made in the traced part (the benchmark's tap
    on them), a dispatch in the mean; their operands and states over the
    peak bandwidth, or their operations over the peak rate if longer, over
    the kernel's device time a call."""
    secs, calls = _ops(run, cell, "prefill", "kda_prefill_ops")
    if not calls or not run.get("traced"):
        return None
    a, b = run["t0"] + run["traced"][0], run["t0"] + run["traced"][1]
    sent = [ns for t, ns in run.get("prefills", ()) if a <= t < b]
    if not sent:
        return None
    block = costs_linear_moe.KDA_BLOCK
    blocks = sum(-(-n // block) for ns in sent for n in ns) / len(sent)
    rows = sum(sum(1 for n in ns if n) for ns in sent) / len(sent)
    least_s = _roof_s(
        run,
        costs_linear_moe.kda_chunk_bytes_per_call(cell.config, blocks, rows),
        costs_linear_moe.kda_chunk_flops_per_call(cell.config, blocks))
    return 100.0 * least_s / (secs / calls)


def linear_decode_hbm_share(run, cell):
    """The whole step: the weights the counters say it touched, every live
    row's state and convolution tail in and out, the GQA layers' live K
    and V, over the peak bandwidth, over the decode programs' device time
    a step (``kda_step`` runs once a KDA layer a step)."""
    k = _moe._decode(run)
    _, calls = _ops(run, cell, "decode", "kda_decode_ops")
    if not k or not calls or "profile" not in run:
        return None
    tokens, batch = _moe._live(run)
    if tokens is None:
        return None
    touched = _moe._experts_touched_per_step(run)
    if touched is None:
        return None
    steps = calls / costs_linear_moe._dims(cell.config)["n_kda"]
    least_s = costs_linear_moe.decode_step_bytes(
        cell.config, tokens, batch, touched
    ) / costs.peaks_for(run["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * least_s / (k["secs"] / steps)


def state_rows_peak_share(run, cell):
    """The most state rows live at any sample of the window (a live slot
    owns one) over the rows the engine holds."""
    after = run["profile"][1]
    held = after.get("recurrent_state.rows", {}).get("calls")
    t0, t1 = run["t0"], run["t0"] + run["seconds"]
    rows = [r[3] for r in run.get("samples", ()) if t0 <= r[0] <= t1]
    if not held or not rows:
        return None
    return 100.0 * max(rows) / held
