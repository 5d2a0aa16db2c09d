"""fp8 KV-cache quantization: pool container + the shared quant math.

Decode is HBM-bandwidth-bound (PERF.md §5: a dense step runs at 87% of
its bytes' time), so the next integer speedup is fewer bytes per step, not
better overlap (ROADMAP S11). KV pages quantize to ``float8_e4m3fn``
values with ONE bf16 scale per (page, kv_head) — per-head because K/V
row magnitudes differ by head, per-page because that is the DMA
granularity of every kernel in ops/pallas (a page moves as one
descriptor; its scales ride as a [KH] vector).

``QuantPool`` is a NamedTuple — automatically a JAX pytree — that rides
the existing ``k_pages``/``v_pages`` argument slots through every jit
boundary: ``donate_argnums`` donates BOTH leaves, the engine's opaque
pool plumbing (precompile, pipeline carry, SPMD snapshot) flows
unchanged, and ``kv_dtype="bf16"`` keeps plain arrays so the unquantized
path stays bit-identical to the pre-quantization goldens.

Scale discipline (the append-time invariant every writer shares):

- A page's scale only GROWS: appending a row computes
  ``new_scale = max(old_scale, amax(row) / FP8_MAX)`` per head, rounded
  to the bf16 the pool stores (quantize and dequantize must use the
  SAME rounded value or the codec biases).
- When the scale grows, the page's existing fp8 values are REQUANTIZED
  in the same pass by ``old_scale / new_scale`` — free on the decode hot
  path, where the staged RMW already holds the whole destination page in
  VMEM (ops/pallas/fused_decode.py), and a small gather/scatter on the
  XLA fallback paths.
- ``scale == 0`` means "empty page": dequant yields exact zeros,
  quant maps all-zero rows to zero without dividing.

The math helpers below are pure ``jnp`` so the SAME ops (same rounding
order) run inside the Pallas kernels, in the XLA fallback paths, and in
interpret mode on CPU — XLA CPU has no native e4m3 arithmetic, but the
codec only ever converts (astype), never computes, in fp8.

KVBM tier blocks pack values + scales into ONE uint8 payload
(``pack_pages``/``unpack_pages``): host/disk/remote pools store bytes
they cannot silently upcast, the disk tier's [2, ...] stacking and the
remote tier's single-dtype header keep working, and G2->G1 onboard
re-materializes fp8 directly (bitcast, never a bf16 round-trip).
"""

from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

FP8_DTYPE = jnp.float8_e4m3fn
FP8_MAX = 448.0  # float8_e4m3fn max finite (jnp.finfo(...).max)
SCALE_DTYPE = jnp.bfloat16
_TINY = 1e-30  # division guard; never the stored scale

KV_DTYPES = ("bf16", "fp8")


def resolve_kv_dtype(value: str | None = None) -> str:
    """Normalize an EngineConfig.kv_dtype / DYN_KV_DTYPE setting.

    Empty/None means "consult DYN_KV_DTYPE, default bf16" — an explicit
    config value wins over the environment. "bf16" = unquantized pool in
    the model dtype (bit-identical serving); "fp8" = e4m3 values with
    per-page per-head bf16 scales (the throughput mode).
    """
    v = (value or os.environ.get("DYN_KV_DTYPE") or "bf16").strip().lower()
    if v in ("bf16", "bfloat16", "native"):
        return "bf16"
    if v in ("fp8", "float8", "e4m3", "float8_e4m3fn"):
        return "fp8"
    raise ValueError(
        f"unknown kv_dtype {value!r} (DYN_KV_DTYPE): expected one of "
        f"{KV_DTYPES}"
    )


class QuantPool(NamedTuple):
    """One quantized KV pool: fp8 values + bf16 per-page(-per-head) scales.

    GQA K or V pool: ``vals [L, num_pages, KH, page, D]`` fp8,
    ``scale [L, num_pages, KH]``. MLA latent cache:
    ``vals [L, num_pages, page, D]``, ``scale [L, num_pages, page]``
    (per-ROW: the latent has no head axis to amortize over, and per-row
    scales cost the same bytes as per-head would for a GQA pool).
    A NamedTuple is already a pytree: donation, jit carries, and
    device_put with a matching QuantPool of shardings all work.
    """

    vals: jax.Array
    scale: jax.Array

    # shape/dtype delegate to the values so shape-reading call sites
    # (page_size = k_pages.shape[3], itemsize-based window sizing) keep
    # working on either pool form
    @property
    def shape(self):
        return self.vals.shape

    @property
    def dtype(self):
        return self.vals.dtype

    @property
    def ndim(self):
        return self.vals.ndim

    def layer(self, li: int) -> "QuantPool":
        """Per-layer slice (both leaves)."""
        return QuantPool(self.vals[li], self.scale[li])


def is_quant(pool) -> bool:
    return isinstance(pool, QuantPool)


def init_quant_pool(vals_shape: tuple[int, ...], scale_ndim: int) -> QuantPool:
    """Zero pool: fp8 zeros + zero scales (scale 0 == empty page)."""
    return QuantPool(
        jnp.zeros(vals_shape, FP8_DTYPE),
        jnp.zeros(vals_shape[:scale_ndim], SCALE_DTYPE),
    )


# ------------------------------------------------------------ codec math
# Shared by the Pallas kernels (traced jnp on loaded VMEM values) and the
# XLA fallback paths so both produce the same bits.


def append_scale(
    old_scale_f32: jax.Array, rows_f32: jax.Array, keepdims: bool = False
) -> jax.Array:
    """New per-head scale after appending ``rows`` (amax over the last
    axis, kept as a size-1 axis with ``keepdims``), rounded through the
    bf16 the pool stores and returned as f32. Monotone: never below the
    old scale."""
    amax = jnp.max(jnp.abs(rows_f32), axis=-1, keepdims=keepdims)
    ns = jnp.maximum(old_scale_f32, amax / FP8_MAX)
    return ns.astype(SCALE_DTYPE).astype(jnp.float32)


def rescale_factor(old_scale_f32: jax.Array, new_scale_f32: jax.Array):
    """old/new ratio that re-encodes existing fp8 values under a grown
    scale (0 for empty pages)."""
    return jnp.where(
        new_scale_f32 > 0,
        old_scale_f32 / jnp.maximum(new_scale_f32, _TINY),
        0.0,
    )


def quant_values(x_f32: jax.Array, scale_f32: jax.Array) -> jax.Array:
    """x / scale clipped into the finite e4m3 range (NOT yet cast —
    callers astype to the target ref/array dtype). e4m3fn overflows to
    NaN rather than saturating, so the clip is mandatory."""
    q = jnp.where(
        scale_f32 > 0, x_f32 / jnp.maximum(scale_f32, _TINY), 0.0
    )
    return jnp.clip(q, -FP8_MAX, FP8_MAX)


def dequant(vals: jax.Array, scale_f32: jax.Array) -> jax.Array:
    """fp8 values -> f32 under a (pre-broadcast) f32 scale."""
    return vals.astype(jnp.float32) * scale_f32


def table_col_scales(
    scale_l: jax.Array,  # one layer's scales [num_pages, KH]
    block_tables: jax.Array,  # [B, P] int32
    page_size: int,
    window_pages: int,
) -> jax.Array:
    """Per-sequence COLUMN scales for the decode kernels: f32
    ``[B, 1, n_chunks * window_pages * KH * page]`` in the kernels'
    flattened window order (page, head, token), the table zero-padded to
    whole window chunks (those page slots are beyond ``P`` and masked by
    the validity check). The dynamic page gather and the broadcast along
    the token axis happen here in XLA; the kernels take one chunk's
    ``[1, Nw]`` (v3 by a static slice, the fused kernel by its loop's
    chunk, a chunk a row) and scale scores / probabilities with it.
    Shared by both decode kernels so their dequant bits agree."""
    s = scale_l[block_tables].astype(jnp.float32)  # [B, P, KH]
    B, P, KH = s.shape
    pad = -P % window_pages
    if pad:
        s = jnp.pad(s, ((0, 0), (0, pad), (0, 0)))
    s = jnp.broadcast_to(s[..., None], (B, P + pad, KH, page_size))
    return s.reshape(B, 1, -1)


def quant_page_tiles(
    tiles: jax.Array,  # [n, KH, page, D] (or [n, page, D] for MLA) f32-able
    valid_tok,  # broadcastable bool mask over tiles (True = real token)
    head_axes: tuple[int, ...],  # axes reduced per scale entry
) -> tuple[jax.Array, jax.Array]:
    """Page-granular prefill quantization: zero the padded/garbage token
    rows FIRST (they would otherwise inflate the page amax and cost the
    real rows precision), then one scale per (page[, head]).

    Returns ``(vals fp8, scale bf16)`` shaped for a ``.at[safe_pg].set``
    pair. Zeroing the garbage rows is safe: they sit beyond num_tokens,
    masked from attention, and are overwritten (via requant RMW) as
    decode appends land there.
    """
    t = jnp.where(valid_tok, tiles.astype(jnp.float32), 0.0)
    s = (jnp.max(jnp.abs(t), axis=head_axes) / FP8_MAX).astype(
        SCALE_DTYPE
    )
    sf = s.astype(jnp.float32)
    expand = sf.reshape(sf.shape + (1,) * len(head_axes))
    return quant_values(t, expand).astype(FP8_DTYPE), s


def quant_append_rows(
    pool: QuantPool,
    rows: jax.Array,  # [N, KH, D] new KV rows (unquantized, f32-able)
    dst_page: jax.Array,  # [N] pool page ids (0 = trash)
    dst_off: jax.Array,  # [N] row offset within the page
    layer: int,
) -> QuantPool:
    """XLA-path quantized KV append (the write_new_kv analogue): gather
    the destination pages, grow their scales by the new rows' amax,
    requantize, splice the quantized rows, scatter back.

    Same math/rounding order as the fused kernel's staged-RMW writeback.
    Rows must target DISTINCT pages (trash-page duplicates excepted —
    garbage by contract); same-page groups (speculative verify) append
    position by position instead.
    """
    page_size = pool.vals.shape[-2]
    rows_f = rows.astype(jnp.float32)
    if rows.ndim == 2:
        # MLA latent: per-(page, ROW) scales — no head axis exists, the
        # row is the natural sub-unit, and row-owned scales mean an
        # append NEVER requantizes its neighbors (no double-quantization
        # and a plain scatter instead of a page RMW)
        ns = append_scale(jnp.zeros_like(rows_f[:, 0]), rows_f)  # [N]
        row_q = quant_values(rows_f, ns[:, None]).astype(FP8_DTYPE)
        return QuantPool(
            pool.vals.at[layer, dst_page, dst_off].set(row_q),
            pool.scale.at[layer, dst_page, dst_off].set(
                ns.astype(SCALE_DTYPE)
            ),
        )
    # GQA: [N, KH, page, D] pages, [N, KH] per-(page, head) scales —
    # the granularity the Pallas kernels DMA and dequantize at.
    # A scale's lifetime is ONE page occupancy: appends land row by row,
    # so an append at row 0 means this sequence just ACQUIRED the page —
    # the previous occupant's leftover scale must not ratchet into ours
    # (a large stale scale would push our rows into e4m3 subnormal/zero
    # territory). Reset to 0 = fresh-page semantics; the stale fp8 rows
    # rescale to 0 and are overwritten/masked anyway.
    old_s = pool.scale[layer, dst_page].astype(jnp.float32)  # [N, KH]
    old_s = jnp.where((dst_off == 0)[:, None], 0.0, old_s)
    ns = append_scale(old_s, rows_f)  # [N, KH]
    fac = rescale_factor(old_s, ns)
    page_f = pool.vals[layer, dst_page].astype(jnp.float32)
    page_f = page_f * fac[:, :, None, None]
    row_q = quant_values(rows_f, ns[:, :, None])  # [N, KH, D]
    hit = (
        jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size, 1), 2)
        == dst_off[:, None, None, None]
    )
    merged = jnp.clip(
        jnp.where(hit, row_q[:, :, None, :], page_f), -FP8_MAX, FP8_MAX
    )
    return QuantPool(
        pool.vals.at[layer, dst_page].set(merged.astype(FP8_DTYPE)),
        pool.scale.at[layer, dst_page].set(ns.astype(SCALE_DTYPE)),
    )


def gather_dequant_pages(
    pool_l: QuantPool,  # one layer: vals [NP, KH, page, D], scale [NP, KH]
    block_table: jax.Array,  # [P] int32
    layer=None,  # ``pool_l`` is a whole pool [L, NP, ...]: its layer
) -> jax.Array:
    """Quantized counterpart of ops.attention.gather_pages: materialize
    the listed pages as f32 ``[P*page, KH, D]`` (dequantized). With
    ``layer`` (an int or a traced scalar) they are gathered out of the
    pool in one indexing step, as there."""
    at = (block_table,) if layer is None else (layer, block_table)
    toks = pool_l.vals[at]  # [P, KH, page, D]
    s = pool_l.scale[at].astype(jnp.float32)  # [P, KH]
    toks = toks.astype(jnp.float32) * s[:, :, None, None]
    P, H, page, D = toks.shape
    return toks.transpose(0, 2, 1, 3).reshape(P * page, H, D)


def gather_dequant_rows(
    pool_l: QuantPool,  # one layer: vals [NP, page, D], scale [NP, page]
    block_table: jax.Array,  # [P]
) -> jax.Array:
    """MLA analogue: one sequence's latent rows as f32 [P*page, D]
    (per-row scales — see quant_append_rows)."""
    rows = pool_l.vals[block_table].astype(jnp.float32)  # [P, page, D]
    s = pool_l.scale[block_table].astype(jnp.float32)  # [P, page]
    rows = rows * s[:, :, None]
    P, page, D = rows.shape
    return rows.reshape(P * page, D)


# -------------------------------------------------------- KVBM block codec


def packed_bytes_per_page(pool: QuantPool) -> int:
    """Per-(layer, page) payload bytes of a packed tier block."""
    vals_n = 1
    for d in pool.vals.shape[2:]:
        vals_n *= d
    return vals_n * pool.vals.dtype.itemsize + packed_scale_bytes(pool)


def packed_scale_bytes(pool: QuantPool) -> int:
    """Per-(layer, page) SCALE-tail bytes of a packed tier block — the
    suffix of ``packed_bytes_per_page`` that validators decode to judge
    scale finiteness. Kept here so every reader of the packed layout
    shares one definition."""
    scale_n = 1
    for d in pool.scale.shape[2:]:
        scale_n *= d
    return scale_n * pool.scale.dtype.itemsize


def pack_pages(pool: QuantPool, page_ids: jax.Array) -> jax.Array:
    """Gather whole pages for tier offload/transfer as ONE uint8 array
    ``[L, n, X]`` = fp8 value bytes ++ bf16 scale bytes per (layer, page).
    A byte payload cannot be silently upcast by a tier, stacks for the
    disk pool, and round-trips the remote tier's single-dtype header.
    """
    L = pool.vals.shape[0]
    n = page_ids.shape[0]
    vals = pool.vals[:, page_ids]  # [L, n, ...] fp8
    scale = pool.scale[:, page_ids]  # [L, n(, KH)] bf16
    vb = jax.lax.bitcast_convert_type(vals, jnp.uint8).reshape(L, n, -1)
    sb = jax.lax.bitcast_convert_type(scale, jnp.uint8).reshape(L, n, -1)
    return jnp.concatenate([vb, sb], axis=-1)


def unpack_pages(
    packed: jax.Array,  # [L, n, X] uint8
    vals_tail: tuple[int, ...],  # pool.vals.shape[2:]
    scale_tail: tuple[int, ...],  # pool.scale.shape[2:]
) -> tuple[jax.Array, jax.Array]:
    """Inverse of pack_pages -> (vals fp8 [L, n, *vals_tail],
    scale bf16 [L, n, *scale_tail]). Pure bitcasts: onboard never takes
    a bf16 round-trip through dequantized values."""
    L, n, _X = packed.shape
    vn = 1
    for d in vals_tail:
        vn *= d
    vals = jax.lax.bitcast_convert_type(
        packed[:, :, :vn].reshape((L, n) + vals_tail), FP8_DTYPE
    )
    sdt = jnp.dtype(SCALE_DTYPE)
    scale = jax.lax.bitcast_convert_type(
        packed[:, :, vn:].reshape((L, n) + scale_tail + (sdt.itemsize,)),
        SCALE_DTYPE,
    )
    return vals, scale


def packed_block_ok(
    block: tuple, expect_nbytes: int, scale_tail_bytes: int
) -> bool:
    """Host-side sanity check for ONE tier block (k, v) before onboard:
    right payload length and FINITE scales — a corrupted scale would
    dequantize a whole page to NaN/inf and poison every later step, so a
    bad block is treated as a tier MISS (logged by the caller), mirroring
    the g4 corrupt-payload path."""
    import numpy as np

    try:
        import ml_dtypes

        sdt = np.dtype(ml_dtypes.bfloat16)
    except ImportError:  # pragma: no cover - ml_dtypes ships with jax
        return True
    for part in block:
        arr = np.asarray(part)
        if arr.dtype != np.uint8 or arr.ndim != 2:
            return False
        if arr.shape[-1] != expect_nbytes:
            return False
        scales = arr[:, expect_nbytes - scale_tail_bytes:]
        if not np.isfinite(
            scales.copy().view(sdt).astype(np.float32)
        ).all():
            return False
    return True
