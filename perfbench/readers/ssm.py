"""Readers for a parallel-hybrid decoder (configurations whose reference
is ``parallel_ssm``: a Mamba-2 / SSD mixer over a state row AND attention
over pages in every layer): the decode kernel and the chunk form against
their own rooflines, the whole decode step against its bytes
(``lib/costs_parallel_ssm.py``), the SSM's share of a decode program's
device time by the program's own regions, and how many of the state rows
held were live. ``ssd_step`` is found in the trace by the name the
configuration's ``trace_names`` gives (``ssd_decode_ops``); the chunk form
and the SSM's regions through the join of the trace to the program's
registry (``readers/regions.py``, imported, not copied); live slots and
tokens as ``readers/moe.py`` reads them. On a program without those names,
regions or counters every reader returns None and raises nothing."""

from lib import costs, costs_parallel_ssm
from readers import moe as _moe
from readers import recurrent as _recurrent
from readers import regions as _regions

# the regions of the SSM in a decode program (dynamo_tpu/models/regions.py)
SSM_DECODE_REGIONS = ("ssm_proj", "ssm_conv", "ssm_gates", "ssd_step",
                      "state_rows")
# and of the chunk form, whichever half is a kernel
SSD_CHUNK_REGIONS = ("ssd_chunk", "ssd_chunk_operands")


def _roof_s(run, n_bytes, flops):
    kind = run["device"]["kind"]
    return max(n_bytes / costs.peaks_for(kind)["hbm_bytes_s"],
               flops / costs_parallel_ssm.peak_flops_s(kind))


def _kind_regions(run, cell, kind):
    """{region: device seconds} of the programs of ``kind``, and their
    seconds in all; (None, None) where the trace cannot be joined."""
    j = _regions._joined(run, cell)
    k = j["by_kind"].get(kind) if j else None
    if not k or not k["secs"]:
        return None, None
    return k["regions"], k["secs"]


@_regions._reader
def ssd_decode_roofline_share(run, cell):
    """A call of ``ssd_step``: the time its live rows' state (read and
    written), tails and operands take at the peak bandwidth, or its
    operations at the peak rate if that is longer, over its device time a
    call."""
    secs, calls = _recurrent._ops(run, cell, "decode", "ssd_decode_ops")
    if not calls:
        return None
    _, batch = _moe._live(run)
    if not batch:
        return None
    least_s = _roof_s(
        run, costs_parallel_ssm.ssd_step_bytes_per_call(cell.config, batch),
        costs_parallel_ssm.ssd_step_flops_per_call(cell.config, batch))
    return 100.0 * least_s / (secs / calls)


def _counted(run, name):
    """What the counter ``name`` of ``profile_snapshot()`` counted in the
    window; None on a program that keeps no such counter."""
    before, after = run.get("profile") or ({}, {})
    if name not in after:
        return None
    return after[name]["calls"] - before.get(name, {}).get("calls", 0)


def _tokens_rows(sent):
    return (sum(sum(ns) for ns in sent),
            sum(1 for ns in sent for n in ns if n))


@_regions._reader
def ssd_prefill_roofline_share(run, cell):
    """The chunk form, ALL of it: every operation of the prefill programs
    under ``ssd_chunk`` (and ``ssd_chunk_operands``, should a half of it
    become a kernel) against the bytes and operations of the prefill
    dispatches made in the traced part, a layer each. The benchmark's tap
    gives the traced part's real tokens and rows; the chunks the state was
    carried through and the rows that resumed one are the engine's own
    counts (``ssd.prefill_chunks``, ``ssd.rows_resumed``) between the
    run's two snapshots, at the traced part's share of the tokens and rows
    tapped between the same two instants (``window.at``: a traced run's
    second snapshot waits for the profiler to stop, past the window)."""
    if not run.get("traced"):
        return None
    regions, all_secs = _kind_regions(run, cell, "prefill")
    chunks_w = _counted(run, "ssd.prefill_chunks")
    resumed_w = _counted(run, "ssd.rows_resumed")
    if not regions or chunks_w is None or resumed_w is None:
        return None
    secs = sum(regions.get(r, 0.0) for r in SSD_CHUNK_REGIONS)
    a, b = run["t0"] + run["traced"][0], run["t0"] + run["traced"][1]
    t0, t1 = (snap["window.at"]["secs"] for snap in run["profile"])
    taps = run.get("prefills", ())
    tokens, rows = _tokens_rows([ns for t, ns in taps if a <= t < b])
    tokens_w, rows_w = _tokens_rows([ns for t, ns in taps if t0 <= t < t1])
    if not secs or not tokens:
        return None
    chunks = chunks_w * tokens / tokens_w
    resumed = resumed_w * rows / rows_w
    layers = cell.config["num_hidden_layers"]
    least_s = layers * _roof_s(
        run,
        costs_parallel_ssm.ssd_chunk_bytes_per_call(
            cell.config, tokens, rows, resumed),
        costs_parallel_ssm.ssd_chunk_flops_per_call(cell.config, chunks))
    _regions._say(
        f"ssm: the chunk form {secs:.4f} s of the prefill programs' "
        f"{all_secs:.4f} s ({100 * secs / all_secs:.1f}%) over {tokens} "
        f"tokens in {rows} rows tapped while traced; between the snapshots "
        f"({t1 - t0:.1f} s) {chunks_w} chunks over {tokens_w} tokens and "
        f"{resumed_w} of {rows_w} rows resumed")
    return 100.0 * least_s / secs


@_regions._reader
def ssm_decode_hbm_share(run, cell):
    """The whole step: the weights and the head, every live row's state
    and tail in and out in every layer, the live K and V of every layer,
    over the peak bandwidth, over ``model.decode_step_ms``' own time."""
    step_ms = cell.readers["device:decode_step_ms"](run, cell)
    _, calls = _recurrent._ops(run, cell, "decode", "ssd_decode_ops")
    tokens, batch = _moe._live(run)
    if not step_ms or not calls or tokens is None:
        return None
    least_s = costs_parallel_ssm.decode_step_bytes(
        cell.config, tokens, batch
    ) / costs.peaks_for(run["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * least_s / (step_ms * 1e-3)


@_regions._reader
def ssm_decode_share(run, cell):
    """Device time of the decode programs under the SSM's regions (its
    projections, convolution, gates, ``ssd_step`` and the state
    directory) over all their device time."""
    regions, secs = _kind_regions(run, cell, "decode")
    if not regions or not any(r in regions for r in SSM_DECODE_REGIONS):
        return None
    return 100.0 * sum(regions.get(r, 0.0) for r in SSM_DECODE_REGIONS) / secs


def ssm_state_rows_peak_share(run, cell):
    """The most state rows live at any sample of the window over the rows
    the engine holds."""
    return _recurrent.state_rows_peak_share(run, cell)
