"""Readers for a decoder that keeps KDA layers over state rows AND latent
(MLA) layers over latent pages in one model (configurations whose
reference is ``linear_latent_moe``): each decode kernel and the chunkwise
prefill against their own rooflines, the whole decode step against its
bytes (``lib/costs_linear_latent_moe.py``), and the two mixers' share of a
decode program's device time by the program's own regions. ``kda_step``
and ``attn_latent`` are found in the trace by the names the
configuration's ``trace_names`` gives (``kda_decode_ops``,
``latent_decode_ops``); the chunk form and the mixers' regions through the
join of the trace to the program's registry (``readers/regions.py``,
imported, not copied); live slots and tokens and the experts a step
touched as ``readers/moe.py`` reads them, the roofline and the counters'
growth as ``readers/ssm.py`` does. On a program without those
names, regions or counters every reader returns None and raises nothing."""

from lib import costs, costs_linear_latent_moe
from readers import moe as _moe
from readers import recurrent as _recurrent
from readers import regions as _regions
from readers import ssm as _ssm

# the regions of the two mixers in a decode program
# (dynamo_tpu/models/regions.py): KDA's as Solar-Open2's programs name
# them, the latent layer's as JoyAI's do, what both open (attn_qkv around
# KDA's operands, attn_kv around a cache write, attn_out) and the state
# directory
MIXER_DECODE_REGIONS = (
    "attn_qkv", "kda_proj", "kda_conv", "kda_gates", "kda_step",
    "latent_q", "latent_kv", "latent_absorb", "latent_schedule",
    "attn_latent", "attn_kv", "attn_out", "state_rows",
)
# the chunkwise form: the kernel and whatever XLA does beside it
KDA_CHUNK_REGIONS = ("kda_chunk", "kda_chunk_operands")


# time at the roofline, a kind of program's time by region, a counter's
# growth over the window, the tapped tokens and rows: as ``readers/ssm.py``
# reads them (the peaks are one table: ``lib/costs_latent_moe.py``)
_roof_s, _kind_regions = _ssm._roof_s, _ssm._kind_regions
_counted, _tokens_rows = _ssm._counted, _ssm._tokens_rows


@_regions._reader
def linlat_kda_decode_roofline_share(run, cell):
    """A call of ``kda_step``: the time its live rows' state (read and
    written) and operands take at the peak bandwidth, or its operations at
    the peak rate if that is longer, over its device time a call."""
    secs, calls = _recurrent._ops(run, cell, "decode", "kda_decode_ops")
    if not calls:
        return None
    _, batch = _moe._live(run)
    if not batch:
        return None
    least_s = _roof_s(
        run,
        costs_linear_latent_moe.kda_step_bytes_per_call(cell.config, batch),
        costs_linear_latent_moe.kda_step_flops_per_call(cell.config, batch))
    return 100.0 * least_s / (secs / calls)


@_regions._reader
def linlat_kda_prefill_roofline_share(run, cell):
    """The chunkwise form, ALL of it: every operation of the prefill
    programs under ``kda_chunk`` and under ``kda_chunk_operands`` beside it
    against the bytes and operations of the prefill dispatches made in the
    traced part, a KDA layer each. The benchmark's tap gives the traced
    part's real tokens and rows; the blocks a state was carried through
    and the rows that resumed one are the engine's own counts
    (``kda.prefill_blocks``, ``kda.rows_resumed``) between the run's two
    snapshots, at the traced part's share of the tokens and rows tapped
    between the same two instants (``window.at``: a traced run's second
    snapshot waits for the profiler to stop, past the window)."""
    if not run.get("traced"):
        return None
    regions, all_secs = _kind_regions(run, cell, "prefill")
    blocks_w = _counted(run, "kda.prefill_blocks")
    resumed_w = _counted(run, "kda.rows_resumed")
    if not regions or blocks_w is None or resumed_w is None:
        return None
    secs = sum(regions.get(r, 0.0) for r in KDA_CHUNK_REGIONS)
    a, b = run["t0"] + run["traced"][0], run["t0"] + run["traced"][1]
    t0, t1 = (snap["window.at"]["secs"] for snap in run["profile"])
    taps = run.get("prefills", ())
    tokens, rows = _tokens_rows([ns for t, ns in taps if a <= t < b])
    tokens_w, rows_w = _tokens_rows([ns for t, ns in taps if t0 <= t < t1])
    if not secs or not tokens or not tokens_w or not rows_w:
        return None
    blocks = blocks_w * tokens / tokens_w
    resumed = resumed_w * rows / rows_w
    layers = costs_linear_latent_moe._dims(cell.config)["n_kda"]
    least_s = layers * _roof_s(
        run,
        costs_linear_latent_moe.kda_chunk_bytes_per_call(
            cell.config, tokens, rows, resumed),
        costs_linear_latent_moe.kda_chunk_flops_per_call(cell.config, blocks))
    _regions._say(
        f"linlat: the chunk form {secs:.4f} s of the prefill programs' "
        f"{all_secs:.4f} s ({100 * secs / all_secs:.1f}%) over {tokens} "
        f"tokens in {rows} rows tapped while traced; between the snapshots "
        f"({t1 - t0:.1f} s) {blocks_w} blocks over {tokens_w} tokens and "
        f"{resumed_w} of {rows_w} rows resumed")
    return 100.0 * least_s / secs


@_regions._reader
def linlat_latent_decode_roofline_share(run, cell):
    """A call of ``attn_latent`` on the latent kind's pool: the time its
    live latents (read once), new rows, queries and outputs take at the
    peak bandwidth, or its operations at the peak rate if that is longer,
    over its device time a call."""
    secs, calls = _recurrent._ops(run, cell, "decode", "latent_decode_ops")
    tokens, batch = _moe._live(run)
    if not calls or tokens is None:
        return None
    least_s = _roof_s(
        run,
        costs_linear_latent_moe.latent_decode_bytes_per_call(
            cell.config, tokens, batch),
        costs_linear_latent_moe.latent_decode_flops_per_call(
            cell.config, tokens, batch))
    return 100.0 * least_s / (secs / calls)


@_regions._reader
def linlat_decode_hbm_share(run, cell):
    """The whole step: the weights by kind of layer, the experts the
    counters say it touched and the head, every live row's state and tail
    in and out in every KDA layer, the live latents of the latent layer,
    over the peak bandwidth, over ``model.decode_step_ms``' own time."""
    step_ms = cell.readers["device:decode_step_ms"](run, cell)
    tokens, batch = _moe._live(run)
    if not step_ms or tokens is None or "profile" not in run:
        return None
    touched = _moe._experts_touched_per_step(run)
    if touched is None:
        return None
    least_s = costs_linear_latent_moe.decode_step_bytes(
        cell.config, tokens, batch, touched
    ) / costs.peaks_for(run["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * least_s / (step_ms * 1e-3)


@_regions._reader
def linlat_mixer_decode_share(run, cell):
    """Device time of the decode programs under KDA's and the latent
    layer's regions and the state directory over all their device time:
    whether the two mechanisms do most of a step's work."""
    regions, secs = _kind_regions(run, cell, "decode")
    if not regions or not (
            "kda_step" in regions and "attn_latent" in regions):
        return None
    return 100.0 * sum(
        regions.get(r, 0.0) for r in MIXER_DECODE_REGIONS) / secs
