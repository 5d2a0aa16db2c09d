"""Readers for a decoder-hybrid-decoder (configurations whose reference is
``sambay``: Mamba-1 scan layers beside window layers of differential
attention, ONE full-attention layer whose pages the cross layers above
read, GMU layers): the cross-decoder's and the scan layers' share of a
decode program's device time by the program's own regions
(``readers/regions.py``'s join, imported, not copied), the scan's decode
kernel against its roofline, each decode-attention kind against the bytes
it must read (``lib/costs_sambay.py``), and the rows the prefill programs
took through the upper half beside the tokens they took through the lower
(the program's ``prefill.*`` counters). The kernels are found in the trace
by the names the configuration's ``trace_names`` gives; live slots and
tokens as ``readers/moe.py`` reads them. On a program without those names,
regions or counters every reader returns None and raises nothing."""

from lib import costs, costs_sambay
from readers import gswa as _gswa
from readers import moe as _moe
from readers import recurrent as _recurrent
from readers import regions as _regions
from readers import ssm as _ssm

# the regions of a decode program (dynamo_tpu/models/regions.py) that are
# the cross-decoder's mixers: the GMU whole, a cross layer's kernel; the
# cross layers' share of ``attn_diff`` (every attention layer opens it)
CROSS_DECODE_REGIONS = ("gmu", "attn_cross")
# and a scan layer's mixer: projections, convolution, gates, the kernel,
# the state directory
SCAN_DECODE_REGIONS = ("ssm_proj", "ssm_conv", "ssm_gates", "scan",
                       "state_rows")

_kind_regions = _ssm._kind_regions
_grew = _gswa._grew


@_regions._reader
def sambay_cross_decode_share(run, cell):
    """Device time of the decode programs under ``gmu`` and ``attn_cross``
    and the cross layers' part of ``attn_diff`` (by their count among the
    attention layers) over all their device time."""
    regions, secs = _kind_regions(run, cell, "decode")
    if not regions or "attn_cross" not in regions:
        return None  # not a program of this family
    cross = costs_sambay.layers_of(cell.config, "cross")
    attn = cross + costs_sambay.layers_of(cell.config, "window") + (
        costs_sambay.layers_of(cell.config, "full"))
    part = sum(regions.get(r, 0.0) for r in CROSS_DECODE_REGIONS)
    part += regions.get("attn_diff", 0.0) * cross / attn
    return 100.0 * part / secs


@_regions._reader
def sambay_scan_decode_share(run, cell):
    """Device time of the decode programs under the scan layers' mixer
    regions over all their device time."""
    regions, secs = _kind_regions(run, cell, "decode")
    if not regions or "scan" not in regions:
        return None
    return 100.0 * sum(
        regions.get(r, 0.0) for r in SCAN_DECODE_REGIONS) / secs


@_regions._reader
def sambay_scan_decode_roofline_share(run, cell):
    """A call of the scan's decode kernel: the time its live rows' state
    (read and written), tails and operand rows take at the peak bandwidth,
    or its operations at the peak rate if that is longer, over its device
    time a call."""
    secs, calls = _recurrent._ops(run, cell, "decode", "scan_decode_ops")
    _, batch = _moe._live(run)
    if not calls or not batch:
        return None
    kind = run["device"]["kind"]
    least_s = max(
        costs_sambay.scan_step_bytes_per_call(cell.config, batch)
        / costs.peaks_for(kind)["hbm_bytes_s"],
        costs_sambay.scan_step_flops_per_call(cell.config, batch)
        / costs_sambay.peak_flops_s(kind))
    return 100.0 * least_s / (secs / calls)


def _attn_share(run, cell, key, ctx_tokens):
    secs, calls = _recurrent._ops(run, cell, "decode", key)
    if not calls or ctx_tokens is None:
        return None
    least_s = costs_sambay.attention_bytes_per_call(
        cell.config, ctx_tokens) / _moe._peak(run)
    return 100.0 * least_s / (secs / calls)


@_regions._reader
def sambay_shared_kv_decode_hbm_share(run, cell):
    """A call of the decode kernel over the ONE full pool (the full
    layer's own call and every cross layer's): the live rows' pages as
    laid out, read once a call, over the peak bandwidth, over its device
    time a call."""
    tokens, _ = _moe._live(run)
    return _attn_share(run, cell, "shared_attention_ops", tokens)


@_regions._reader
def sambay_window_decode_hbm_share(run, cell):
    """A call of the window layers' decode kernel: the live rows' tokens
    INSIDE the window (the sampled live tokens less the share the
    ``kv.window_*`` counters found past it) as laid out, read once, over
    the peak bandwidth, over its device time a call."""
    tokens, _ = _moe._live(run)
    dead = _gswa._dead_share(run)
    if tokens is None or dead is None:
        return None
    return _attn_share(
        run, cell, "window_attention_ops", tokens * (1.0 - dead))


@_regions._reader
def sambay_prefill_cross_rows_share(run, cell):
    """``prefill.cross_rows`` over ``prefill.rows`` between the window's
    two snapshots: rows the prefill programs took through the layers that
    write no cache, over the prompt tokens they took through the layers
    below. ~1 / the prompts' length; 100 would say that the upper half ran
    for every row."""
    cross, rows = _grew(run, "prefill.cross_rows"), _grew(run, "prefill.rows")
    return 100.0 * cross / rows if cross is not None and rows else None
