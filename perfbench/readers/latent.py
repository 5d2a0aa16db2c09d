"""Readers for a latent-attention decoder with held experts
(configurations whose reference is ``latent_moe``): the latent decode
kernel against its own roofline and the whole step against its bytes
(``lib/costs_latent_moe.py``), and how far the kernel's fetches follow the
live context (the program's ``decode_kv.*`` counters). The trace's decode
ops, the sampler's live tokens and the experts a step touched are read as
``readers/moe.py`` reads them (imported, not copied). On a program without
those names or counters every reader returns None."""

from lib import costs, costs_latent_moe
from readers import moe as _moe


def latent_decode_attn_roofline_share(run, cell):
    """A call of the latent decode kernel: the time its bytes take at the
    peak bandwidth, or its operations at the peak rate if that is longer,
    over its device time a call."""
    secs, calls = _moe._ops(run, cell, "decode_attention_ops")
    tokens, batch = _moe._live(run)
    if not calls or tokens is None:
        return None
    kind = run["device"]["kind"]
    least_s = max(
        costs_latent_moe.decode_attention_bytes_per_call(
            cell.config, tokens, batch) / costs.peaks_for(kind)["hbm_bytes_s"],
        costs_latent_moe.decode_attention_flops_per_call(
            cell.config, tokens, batch) / costs_latent_moe.peak_flops_s(kind),
    )
    return 100.0 * least_s / (secs / calls)


def latent_decode_hbm_share(run, cell):
    """The whole step: the weights the counters say it touched and the
    live latents, over the peak bandwidth, over the decode programs'
    device time a step (a kernel call a layer a step)."""
    k, steps = _moe._decode(run), _moe._steps(run, cell)
    tokens, batch = _moe._live(run)
    if not k or not steps or tokens is None or "profile" not in run:
        return None
    touched = _moe._experts_touched_per_step(run)
    if touched is None:
        return None
    least_s = costs_latent_moe.decode_step_bytes(
        cell.config, tokens, batch, touched
    ) / costs.peaks_for(run["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * least_s / (k["secs"] / steps)


def latent_pages_fetched_over_live(run, cell):
    """Pages the kernel's live chunks fetched over the pages that held
    context, over the window's dispatched bursts: what a chunk's size
    wastes (1 = nothing)."""
    before, after = run["profile"]
    names = ("decode_kv.pages_fetched", "decode_kv.pages_live")
    if not all(n in after for n in names):
        return None
    fetched, live = (
        after[n]["calls"] - before.get(n, {}).get("calls", 0) for n in names
    )
    return fetched / live if fetched and live else None
