"""Fallback accounting for fused/quantized kernel downgrades.

ROADMAP #7 named the failure mode: fp8 + tp>1 silently takes the XLA
path (a QuantPool's scale leaves have no PartitionSpec to ride the tp
shard_map), and nothing in the metrics or logs says so — the only
symptom is a throughput number far under the bf16 one. Every
capability-gated downgrade in ops/ now calls :func:`note_fallback`:
the downgrade shows up in ``dynamo_fused_fallback_total{reason}`` and
the FIRST occurrence of each reason logs — a warning when it is a
surprise (quantized pool forced off the fused path), debug when the
config plainly asked for it
(``DYNAMO_PALLAS=0``, CPU backend).

Trace-time caveat: the dispatchers run under jit trace, so the counter
bumps once per compiled SPECIALIZATION that takes the fallback, not
once per step. A nonzero series means "this shape/config runs
degraded"; it is not a per-step rate. dynalint DL014 enforces that
every catalogued capability gate's downgrade branch reaches this
module (or logs outright).
"""

from __future__ import annotations

import logging
import threading

from dynamo_tpu.runtime.metrics import MetricsRegistry, register_registry

log = logging.getLogger("dynamo.ops.fallback")

REGISTRY = MetricsRegistry()
_FALLBACKS = REGISTRY.counter(
    "fused_fallback_total",
    "Fused/quantized kernel downgrades taken at dispatch, by reason",
    ["reason"],
)
# Not a downgrade, so not a reason of the counter above (the benchmark's
# output check refuses a run that shows ANY of its series): which of its
# two kernels a grouped product of an expert layer compiled to on the
# chip, chosen by the call's static shape (models/moe.py: _grouped_matmul)
_GROUPED = REGISTRY.counter(
    "grouped_product_total",
    "Expert-layer grouped products compiled on the chip, by path "
    "(resident: ops/pallas/grouped.py, rows and output in VMEM; "
    "streamed: megablox, rows that do not fit)",
    ["path"],
)
register_registry("ops.fallback", REGISTRY)

_seen: set[str] = set()
_seen_lock = threading.Lock()


def note_fallback(
    reason: str, *, detail: str = "", expected: bool = False
) -> None:
    """Count a fused→XLA / quantized→bf16 downgrade and log it once.

    ``reason`` is a low-cardinality label (see catalog.METRIC_NAMES:
    quant_tp_shardmap | no_pallas_backend). ``expected=True`` drops the
    one-shot log to debug for downgrades the configuration explicitly
    chose.
    """
    _FALLBACKS.labels(reason).inc()
    with _seen_lock:
        if reason in _seen:
            return
        _seen.add(reason)
    msg = f"fused kernel fallback: {reason}"
    if detail:
        msg += f" ({detail})"
    (log.debug if expected else log.warning)(msg)


def note_grouped_product(path: str, *, detail: str = "") -> None:
    """Count a compiled grouped product by its path (``resident`` |
    ``streamed``); trace-time, like ``note_fallback``: once a compiled
    specialisation, not once a step. A decode program that counts
    ``streamed`` reads its experts' weights as megablox tiles them."""
    _GROUPED.labels(path).inc()
    log.debug("grouped product: %s (%s)", path, detail)


def reset_seen() -> None:
    """Re-arm the one-shot logs (tests)."""
    with _seen_lock:
        _seen.clear()
