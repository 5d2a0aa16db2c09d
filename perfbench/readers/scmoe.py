"""Readers for a decoder of shortcut-connected MoE double layers over
latent attention with identity experts (configurations whose reference is
``scmoe_latent``): the decode step, the shortcut's expert layer's and the
two attentions' shares of a decode program's device time by the program's
own regions (``readers/regions.py``'s join, imported, not copied), the
latent decode kernel at the published head count against its roofline and
the grouped products against the bytes of the experts a step touched
(``lib/costs_scmoe_latent.py``), and the share of the counted decode rows'
picks that were identity experts (the program's ``moe.decode.zero_picks``
/ ``moe.decode.ffn_picks`` counters). A model step is counted by the
latent decode kernel, which runs once a SUB-layer a step. Live slots and
tokens and the experts touched as ``readers/moe.py`` reads them. On a
program without those names, regions or counters every reader returns None
and raises nothing."""

from lib import costs, costs_scmoe_latent
from readers import moe as _moe
from readers import recurrent as _recurrent
from readers import regions as _regions
from readers import ssm as _ssm

# the regions of a decode program (dynamo_tpu/models/regions.py): the
# shortcut's expert layer (the dense FFNs are ``mlp`` alone) and the two
# attentions
SHORTCUT_REGIONS = (
    "moe_route", "moe_experts", "moe_dispatch", "moe_grouped", "gmm",
    "moe_combine", "moe_zero", "moe_count")
LATENT_REGIONS = (
    "latent_q", "latent_kv", "latent_absorb", "attn_kv", "attn_latent",
    "latent_schedule", "attn_out")

_kind_regions = _ssm._kind_regions


def _steps(run, cell):
    """Model steps in the traced decode programs: the latent decode
    kernel's calls over the cache layers (two a decoder layer)."""
    _, calls = _recurrent._ops(run, cell, "decode", "decode_attention_ops")
    layers = costs_scmoe_latent.cache_layers(cell.config)
    return calls / layers if calls and layers else None


@_regions._reader
def scmoe_decode_step_ms(run, cell):
    """Device time of the decode programs in the trace over the model
    steps in them."""
    k, steps = _moe._decode(run), _steps(run, cell)
    if not k or not steps:
        return None
    return 1e3 * k["secs"] / steps


def _share(run, cell, names):
    regions, secs = _kind_regions(run, cell, "decode")
    if not regions or not {"moe_route", "latent_q"} <= set(regions):
        return None  # not a program of this family
    return 100.0 * sum(regions.get(r, 0.0) for r in names) / secs


@_regions._reader
def scmoe_shortcut_decode_share(run, cell):
    """Device time of the decode programs in the shortcut's expert layer
    (router, dispatch, grouped products, combine, the identity term, the
    counters) over all their device time."""
    return _share(run, cell, SHORTCUT_REGIONS)


@_regions._reader
def scmoe_latent_decode_share(run, cell):
    """Device time of the decode programs in the two attentions (query and
    latent projections, absorption, the kernel and its schedule, the output
    projection) over all their device time."""
    return _share(run, cell, LATENT_REGIONS)


@_regions._reader
def scmoe_latent_decode_roofline_share(run, cell):
    """A call of the latent decode kernel at the published head count: the
    time its live pages as laid out, read once, take at the peak bandwidth,
    or its operations at the bf16 peak if that is longer, over its device
    time a call."""
    secs, calls = _recurrent._ops(run, cell, "decode", "decode_attention_ops")
    tokens, batch = _moe._live(run)
    if not calls or tokens is None:
        return None
    kind = run["device"]["kind"]
    least_s = max(
        costs_scmoe_latent.decode_attention_bytes_per_call(
            cell.config, tokens, batch) / costs.peaks_for(kind)["hbm_bytes_s"],
        costs_scmoe_latent.decode_attention_flops_per_call(
            cell.config, tokens, batch)
        / costs_scmoe_latent.peak_flops_s(kind),
    )
    return 100.0 * least_s / (secs / calls)


@_regions._reader
def scmoe_experts_hbm_share(run, cell):
    """The grouped products (``gmm``, three an expert layer): the three
    matrices of every expert a step touched, by the ``moe.decode``
    counters, over the peak bandwidth, over the products' device time a
    step."""
    secs, calls = _recurrent._ops(run, cell, "decode", "expert_ops")
    steps = _steps(run, cell)
    if not calls or not steps or "profile" not in run:
        return None
    touched = _moe._experts_touched_per_step(run)
    if touched is None:
        return None
    least_s = costs_scmoe_latent.grouped_products_bytes_per_step(
        cell.config, touched) / _moe._peak(run)
    return 100.0 * least_s / (secs / steps)


@_regions._reader
def zero_pick_share(run, cell):
    """Identity picks over all picks of the counted decode rows, over the
    window: the share of a decode row's expert choices that cost
    nothing."""
    before, after = run["profile"]
    names = ("moe.decode.zero_picks", "moe.decode.ffn_picks")
    if not all(n in after for n in names):
        return None
    zero, ffn = (
        after[n]["calls"] - before.get(n, {}).get("calls", 0) for n in names
    )
    return 100.0 * zero / (zero + ffn) if zero + ffn > 0 else None
