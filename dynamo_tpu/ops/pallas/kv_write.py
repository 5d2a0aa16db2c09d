"""Pallas TPU kernel: token KV writes into the paged cache.

The per-step cache update — writing each sequence's new K/V row into its
(page, offset) slot — is an XLA scatter in the pure-JAX path. Measured on
v5e that scatter costs ~0.35 ms per layer (~11 ms of a 16-layer decode
step), dwarfing the actual bytes moved (128 KB). A direct row DMA is
impossible (Mosaic requires HBM slices aligned to the (8, 128) tile; a
single token row slices the sublane dim to 1), so this kernel does a
pipelined read-modify-write at page granularity instead: for each batch
row, DMA the whole destination page — in the page-major pool layout
([num_pages, KH, page, D]) a page is ONE contiguous [KH, page, D] block,
a single DMA descriptor — splice the new token row in VMEM, and DMA it
back, double-buffered across grid steps so the next page loads while the
current one is modified and stored.

Decode writes one row per sequence; sequences never share their tail page
(prefix-cache sharing covers sealed full pages only), so programs never
RMW the same page — except the trash page (dst_page == 0) used by
padded/inactive slots, whose content is garbage by contract
(models/llama.py TRASH_PAGE).

TPU-native replacement for the role of the reference's block-copy CUDA
kernel on the write path (lib/llm/src/kernels/block_copy.cu — layout-aware
scatter between KV pools).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kv_write_kernel(
    # scalar prefetch (SMEM)
    dst_page_ref,  # [N] int32
    dst_off_ref,  # [N] int32
    # inputs
    k_new_ref,  # [1, KH, D] VMEM block (this program's row)
    v_new_ref,  # [1, KH, D] VMEM block
    k_pages_in,  # [L, P, KH, page, D] ANY (aliased with k_out)
    v_pages_in,
    # outputs (ANY, aliased)
    k_out_ref,
    v_out_ref,
    # scratch
    k_buf,  # [2, KH, page, D] VMEM
    v_buf,
    in_sems,  # DMA sems [2, 2] (k/v x slot)
    out_sems,  # DMA sems [2, 2]
    *,
    layer: int,
):
    i = pl.program_id(0)
    n = pl.num_programs(0)
    slot = jax.lax.rem(i, 2)
    nxt = 1 - slot

    def in_copy(pages_ref, buf, ch, j, s):
        page = dst_page_ref[j]
        return pltpu.make_async_copy(
            pages_ref.at[layer, page], buf.at[s], in_sems.at[ch, s]
        )

    def out_copy(buf, out_ref, ch, j, s):
        page = dst_page_ref[j]
        return pltpu.make_async_copy(
            buf.at[s], out_ref.at[layer, page], out_sems.at[ch, s]
        )

    @pl.when(i == 0)
    def _():
        in_copy(k_pages_in, k_buf, 0, 0, 0).start()
        in_copy(v_pages_in, v_buf, 1, 0, 0).start()

    # prefetch the next program's page into the other slot — after its
    # previous out-DMA (program i-1, same slot) has drained
    @pl.when(i + 1 < n)
    def _():
        @pl.when(i >= 1)
        def _():
            out_copy(k_buf, k_out_ref, 0, i - 1, nxt).wait()
            out_copy(v_buf, v_out_ref, 1, i - 1, nxt).wait()

        in_copy(k_pages_in, k_buf, 0, i + 1, nxt).start()
        in_copy(v_pages_in, v_buf, 1, i + 1, nxt).start()

    in_copy(k_pages_in, k_buf, 0, i, slot).wait()
    in_copy(v_pages_in, v_buf, 1, i, slot).wait()

    # splice the new token row at dst_off
    off = dst_off_ref[i]
    page_size = k_buf.shape[2]
    row = (
        jax.lax.broadcasted_iota(jnp.int32, (1, page_size, 1), 1) == off
    )  # [1, page, 1]
    k_buf[slot] = jnp.where(row, k_new_ref[0][:, None, :], k_buf[slot])
    v_buf[slot] = jnp.where(row, v_new_ref[0][:, None, :], v_buf[slot])

    out_copy(k_buf, k_out_ref, 0, i, slot).start()
    out_copy(v_buf, v_out_ref, 1, i, slot).start()

    @pl.when(i == n - 1)
    def _():
        out_copy(k_buf, k_out_ref, 0, i, slot).wait()
        out_copy(v_buf, v_out_ref, 1, i, slot).wait()

        @pl.when(n >= 2)
        def _():
            out_copy(k_buf, k_out_ref, 0, i - 1, nxt).wait()
            out_copy(v_buf, v_out_ref, 1, i - 1, nxt).wait()


@functools.partial(
    jax.jit, static_argnames=("layer", "interpret"), donate_argnums=(0, 1)
)
def kv_write_pallas(
    k_pages: jax.Array,  # [L, P, KH, page, D]
    v_pages: jax.Array,
    k_new: jax.Array,  # [N, KH, D]
    v_new: jax.Array,
    dst_page: jax.Array,  # [N] int32 (0 = trash page)
    dst_off: jax.Array,  # [N] int32
    *,
    layer: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Write N new-token KV rows into layer ``layer``'s page slots.

    The page arrays are input/output-aliased so the update is in place
    (pair with donation at the jit boundary above).
    """
    N, KH, D = k_new.shape
    page_size = k_pages.shape[3]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N,),
        in_specs=[
            pl.BlockSpec(
                (1, KH, D), lambda i, *_: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, KH, D), lambda i, *_: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),  # k_pages
            pl.BlockSpec(memory_space=pl.ANY),  # v_pages
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, KH, page_size, D), k_pages.dtype),
            pltpu.VMEM((2, KH, page_size, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    k_out, v_out = pl.pallas_call(
        functools.partial(_kv_write_kernel, layer=layer),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operand numbering includes the 2 scalar-prefetch args:
        # 2=k_new 3=v_new 4=k_pages 5=v_pages -> outputs 0, 1
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(
        dst_page.astype(jnp.int32), dst_off.astype(jnp.int32),
        k_new.astype(k_pages.dtype), v_new.astype(v_pages.dtype),
        k_pages, v_pages,
    )
    return k_out, v_out


def write_new_kv(
    k_pages: jax.Array,  # [L, P, KH, page, D]
    v_pages: jax.Array,
    k_new: jax.Array,  # [N, KH, D]
    v_new: jax.Array,
    dst_page: jax.Array,  # [N]
    dst_off: jax.Array,  # [N]
    *,
    layer: int,
    mesh=None,
) -> tuple[jax.Array, jax.Array]:
    """Cache-write dispatch: DMA kernel on real TPU, XLA scatter elsewhere.

    With a mesh the kernel runs under shard_map over "tp" (KV heads
    sharded, row indices replicated) — mirroring the attention dispatch in
    ops/attention.py; off-TPU the XLA scatter is both correct and fast
    enough for tests. A pool wider than the model head dim
    (ops/attention.pool_head_dim zero-padding for lane alignment) gets
    the new rows zero-padded to the pool width — which is also what
    keeps this on the DMA-kernel path for e.g. D=64 models.

    QuantPool pools (ops/quant.py) take the quantized append: gather the
    destination pages, grow their per-head scales by the new rows,
    requantize + splice, scatter back (same codec math as the fused
    kernel's staged RMW). Rows must target distinct pages — same-page
    groups (speculative verify) append one position at a time.
    """
    from dynamo_tpu.ops.attention import lane_aligned, pad_heads, use_pallas
    from dynamo_tpu.ops.quant import is_quant, quant_append_rows

    if k_pages.shape[-1] != k_new.shape[-1]:
        k_new = pad_heads(k_new, k_pages.shape[-1])
        v_new = pad_heads(v_new, v_pages.shape[-1])

    if is_quant(k_pages):
        return (
            quant_append_rows(k_pages, k_new, dst_page, dst_off, layer),
            quant_append_rows(v_pages, v_new, dst_page, dst_off, layer),
        )

    if (
        lane_aligned(k_pages.shape[-1])
        and use_pallas()
        and jax.default_backend() == "tpu"
    ):
        kernel = functools.partial(kv_write_pallas, layer=layer)
        if mesh is not None and mesh.shape.get("tp", 1) > 1:
            from jax.sharding import PartitionSpec as P

            # dynalint: disable=DL013 -- array pools only: the
            # quantized append returned above (quant_append_rows)
            # before this shard_map
            kernel = jax.shard_map(
                kernel,
                mesh=mesh,
                in_specs=(
                    P(None, None, "tp", None, None),  # k_pages
                    P(None, None, "tp", None, None),
                    P(None, "tp", None),  # k_new: heads sharded
                    P(None, "tp", None),
                    P(None),  # dst_page replicated
                    P(None),
                ),
                out_specs=(
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                ),
                check_vma=False,
            )
        return kernel(k_pages, v_pages, k_new, v_new, dst_page, dst_off)
    from dynamo_tpu.ops.fallback import note_fallback

    if jax.default_backend() == "tpu":
        # off-TPU the XLA scatter is the intended path; on a real TPU
        # landing here means the DMA append kernel was available in
        # principle but gated off
        note_fallback(
            "lane_misaligned"
            if not lane_aligned(k_pages.shape[-1]) else "no_pallas_backend",
            detail="write_new_kv: XLA scatter append",
            expected=not use_pallas(),
        )
    return (
        k_pages.at[layer, dst_page, :, dst_off].set(
            k_new.astype(k_pages.dtype)
        ),
        v_pages.at[layer, dst_page, :, dst_off].set(
            v_new.astype(v_pages.dtype)
        ),
    )
