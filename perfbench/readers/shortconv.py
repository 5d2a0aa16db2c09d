"""Readers for a decoder of gated short convolutions beside QK-normed GQA
layers over experts all held (configurations whose reference is
``shortconv_moe``): the grouped products against the bytes of the experts
a step touched, the conv layers' mixers against their weights and tails
(``lib/costs_shortconv_moe.py``), and the expert layers' and the mixers'
shares of a decode program's device time by the program's own regions
(``readers/regions.py``'s join, imported, not copied). A model step is
counted by the attention kernel, which runs once an attention layer a
step: the conv layers run no kernel of their own. Live slots and the
experts touched as ``readers/moe.py`` reads them. On a program without
those names, regions or counters every reader returns None and raises
nothing."""

from lib import costs_shortconv_moe
from readers import moe as _moe
from readers import recurrent as _recurrent
from readers import regions as _regions
from readers import ssm as _ssm

# the regions of a decode program (dynamo_tpu/models/regions.py): the
# short convolution's own, the mixers' in all, the MLPs'
CONV_REGIONS = ("conv_proj", "conv_mix")
MIXER_DECODE_REGIONS = CONV_REGIONS + (
    "attn_qkv", "attn_kv", "attn_out", "attn_full", "state_rows")
EXPERT_DECODE_REGIONS = (
    "mlp", "moe_route", "moe_experts", "moe_dispatch", "moe_grouped", "gmm",
    "moe_combine", "moe_count")

_kind_regions = _ssm._kind_regions


def _steps(run, cell):
    """Model steps in the traced decode programs: the attention kernel's
    calls over the attention layers kept."""
    _, calls = _recurrent._ops(run, cell, "decode", "full_attention_ops")
    layers = costs_shortconv_moe._dims(cell.config)["attn"].count(True)
    return calls / layers if calls and layers else None


@_regions._reader
def shortconv_decode_step_ms(run, cell):
    """Device time of the decode programs in the trace over the model
    steps in them, a step counted by the attention kernel alone."""
    k, steps = _moe._decode(run), _steps(run, cell)
    if not k or not steps:
        return None
    return 1e3 * k["secs"] / steps


@_regions._reader
def shortconv_experts_hbm_share(run, cell):
    """The grouped products (``gmm``, three a layer): the three matrices
    of every expert a step touched, by the ``moe.decode`` counters, over
    the peak bandwidth, over the products' device time a step."""
    secs, calls = _recurrent._ops(run, cell, "decode", "expert_ops")
    steps = _steps(run, cell)
    if not calls or not steps or "profile" not in run:
        return None
    touched = _moe._experts_touched_per_step(run)
    if touched is None:
        return None
    least_s = touched * costs_shortconv_moe.expert_bytes(cell.config) / (
        _moe._peak(run))
    return 100.0 * least_s / (secs / steps)


@_regions._reader
def shortconv_mix_decode_hbm_share(run, cell):
    """The conv layers' mixers in the decode programs (regions
    ``conv_proj`` and ``conv_mix``): their weights, taps and the live
    rows' tails in and out over the peak bandwidth, over the regions'
    device time a step."""
    regions, _ = _kind_regions(run, cell, "decode")
    steps = _steps(run, cell)
    _, batch = _moe._live(run)
    if not regions or not steps or not batch:
        return None
    secs = sum(regions.get(r, 0.0) for r in CONV_REGIONS)
    if not secs:
        return None
    least_s = costs_shortconv_moe.conv_mix_decode_bytes_per_step(
        cell.config, batch) / _moe._peak(run)
    return 100.0 * least_s / (secs / steps)


def _share(run, cell, names, must):
    regions, secs = _kind_regions(run, cell, "decode")
    if not regions or must not in regions:
        return None
    return 100.0 * sum(regions.get(r, 0.0) for r in names) / secs


@_regions._reader
def shortconv_expert_decode_share(run, cell):
    """Device time of the decode programs under ``mlp`` and beneath (the
    two dense MLPs, the router, dispatch, grouped products, combine and
    counters of the expert layers) over all their device time."""
    return _share(run, cell, EXPERT_DECODE_REGIONS, "conv_mix")


@_regions._reader
def shortconv_mixer_decode_share(run, cell):
    """Device time of the decode programs under the mixers' regions (the
    short convolution's, the attention layers' projections and kernel,
    the state directory) over all their device time."""
    return _share(run, cell, MIXER_DECODE_REGIONS, "conv_mix")
