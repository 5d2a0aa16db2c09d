"""Readers for a decoder of gated, QK-normed GQA window and full layers
between four norms a layer over held experts beside a shared one
(configurations whose reference is ``gated_swa_moe``): the attention's
share of a decode program's device time by the program's own regions
(``readers/regions.py``'s join, imported, not copied; the output norms are
the region ``norm_out``), each decode-attention kind and the grouped
products against the bytes they must read (``lib/costs_gated_swa_moe.py``),
the compiler's own prefetches among the grouped products' time, and the
share of the window layers' tokens that lie past their window (the
program's ``kv.window_*`` counters). The decode step's device time is
``device:decode_step_ms`` (``model.decode_step_ms``: a step counted by the
attention kernels, one call a layer a step, as here). Live slots and tokens
and the experts touched as ``readers/moe.py`` reads them. On a program
without those names, regions or counters every reader returns None and
raises nothing."""

from lib import costs_gated_swa_moe
from readers import moe as _moe
from readers import recurrent as _recurrent
from readers import regions as _regions
from readers import ssm as _ssm

# the regions of a decode program (dynamo_tpu/models/regions.py) that are
# the attention of a layer: projections, norms of q and k and the rotation
# (``attn_qkv``), page write and both kernels, the gate and the output
# projection (``attn_out``), and the layer's norms in and out
ATTN_DECODE_REGIONS = (
    "attn_qkv", "attn_kv", "attn_window", "attn_full", "attn_out", "norm",
    "norm_out")

_kind_regions = _ssm._kind_regions


def _steps(run, cell):
    """Model steps in the traced decode programs: a decode-attention
    kernel (either kind) runs once a layer a step."""
    _, calls = _recurrent._ops(run, cell, "decode", "decode_attention_ops")
    layers = len(costs_gated_swa_moe._dims(cell.config)["windowed"])
    return calls / layers if calls and layers else None


def _grew(run, name):
    """A program counter's growth between the window's two snapshots;
    None where the program has no such counter."""
    before, after = run.get("profile") or ({}, {})
    if name not in after:
        return None
    return after[name]["calls"] - before.get(name, {}).get("calls", 0)


def _dead_share(run):
    """Of the tokens the live rows held in the window layers over the
    window's decode steps, the share past their layer's window."""
    dead = _grew(run, "kv.window_dead_tokens")
    held = _grew(run, "kv.window_layer_tokens")
    return dead / held if dead is not None and held else None


@_regions._reader
def gswa_attn_decode_share(run, cell):
    """Device time of the decode programs under the attention's regions
    (projections with the q and k norms and the rotation, page write and
    both kernels, gate and output projection, the norms in and out) over
    all their device time."""
    regions, secs = _kind_regions(run, cell, "decode")
    if not regions or "norm_out" not in regions:
        return None  # not a program of this family
    return 100.0 * sum(regions.get(r, 0.0) for r in ATTN_DECODE_REGIONS) / secs


def _attn_share(run, cell, key, ctx_tokens, batch):
    secs, calls = _recurrent._ops(run, cell, "decode", key)
    if not calls or ctx_tokens is None or not batch:
        return None
    least_s = costs_gated_swa_moe.decode_attention_bytes_per_call(
        cell.config, ctx_tokens, batch) / _moe._peak(run)
    return 100.0 * least_s / (secs / calls)


@_regions._reader
def gswa_window_decode_hbm_share(run, cell):
    """A call of the window layers' decode kernel: the live rows' tokens
    INSIDE the window (the sampled live tokens less the share the
    ``kv.window_*`` counters found past it) as laid out, read once, over
    the peak bandwidth, over its device time a call."""
    tokens, batch = _moe._live(run)
    dead = _dead_share(run)
    if tokens is None or dead is None:
        return None
    return _attn_share(
        run, cell, "window_attention_ops", tokens * (1.0 - dead), batch)


@_regions._reader
def gswa_full_decode_hbm_share(run, cell):
    """A call of the full layers' decode kernel: the live rows' whole
    context as laid out, read once, over the peak bandwidth, over its
    device time a call."""
    tokens, batch = _moe._live(run)
    return _attn_share(run, cell, "full_attention_ops", tokens, batch)


# the grouped products of a decode program: the Mosaic calls (``gmm``) and
# what XLA does around them under ``moe_grouped``, the compiler's own
# prefetches of the experts' stacked weights booked to either
GROUPED_REGIONS = ("gmm", "moe_grouped")


@_regions._reader
def gswa_experts_hbm_share(run, cell):
    """The grouped products (three an expert layer): the three matrices of
    every held expert a step touched, by the ``moe.decode`` counters, over
    the peak bandwidth, over the device time a step of the regions ``gmm``
    and ``moe_grouped``. By REGION, not by the kernel's name, because the
    kernel does not move all of its bytes: in the decode program compiled
    for a v5e the compiler copies 11 of the 24 stacked matrices
    ``[16, 2048, 1024]`` WHOLE from HBM into VMEM ahead of their ``gmm``
    call, four ``slice-start`` / ``slice-done`` of four experts each
    (memory space ``S(1)``; the call's operand is the copy), and those
    calls then read VMEM: their time alone against HBM bytes read 99.8%
    and 113% of the peak (PERF.md section 6, PR 53). The waits for those
    copies are booked to the call's region and are in this share's time;
    what they move beyond the touched experts is not in its bytes."""
    regions, _ = _kind_regions(run, cell, "decode")
    steps = _steps(run, cell)
    if not regions or not steps or "profile" not in run:
        return None
    secs = sum(regions.get(r, 0.0) for r in GROUPED_REGIONS)
    touched = _moe._experts_touched_per_step(run)
    if not secs or touched is None:
        return None
    least_s = touched * costs_gated_swa_moe.expert_bytes(cell.config) / (
        _moe._peak(run))
    return 100.0 * least_s / (secs / steps)


@_regions._reader
def gswa_experts_prefetch_share(run, cell):
    """Of the decode programs' device time under ``gmm`` and
    ``moe_grouped``, the share spent in the compiler's own sliced
    prefetches of the stacked expert weights (``slice-start`` /
    ``slice-done``): whole stacks of 16 held experts copied for calls that
    touch ~7, the part of ``gswa_experts_hbm_share``'s time that no
    kernel of this repo spends."""
    j = _regions._joined(run, cell)
    if not j or "rows" not in j:
        return None
    secs = sliced = 0.0
    for (kind, region, leaf), (s, _calls) in j["rows"].items():
        if kind == "decode" and region in GROUPED_REGIONS:
            secs += s
            if leaf.startswith("slice-"):
                sliced += s
    return 100.0 * sliced / secs if secs else None


@_regions._reader
def window_dead_share(run, cell):
    """Tokens the live rows held in the window layers that lay more than
    the window behind their row's length, over all they held there, over
    the window's decode steps: what pages found by layer kind would give
    back of the window layers' pool."""
    dead = _dead_share(run)
    return None if dead is None else 100.0 * dead
