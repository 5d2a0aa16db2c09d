"""The decode kernels must COMPILE for the chip at Llama-3-8B widths.

Interpret-mode tests (test_fused_decode, test_pallas_attention,
test_kv_write) check the kernels' numbers on the CPU; they cannot see
what the TPU compiler refuses — a slice off the tiling, a block shape
Mosaic cannot lay out, scratch past the scoped-VMEM or DMA-semaphore
space. This file asks the installed compiler, for a DESCRIBED v5e device
(no chip attached, nothing runs): about two seconds a case.

The file name sorts first on purpose: the tier-1 clock cuts the run
short on a slow box, and these guard every later kernel change at no
chip time.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep compiler logs out of /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.ops.pallas.fused_decode import fused_decode_attention
from dynamo_tpu.ops.pallas.kv_write import kv_write_pallas
from dynamo_tpu.ops.pallas.paged_attention_v3 import paged_decode_attention_v3
from dynamo_tpu.ops.quant import FP8_DTYPE, SCALE_DTYPE, QuantPool

# Llama-3-8B attention widths (ModelSpec.llama3_8b): 32 Q / 8 KV x 128
H, KH, D = 32, 8, 128
B, L = 8, 2  # engine-default slot count; two layers so ``layer`` indexes


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device, persistent compile cache off around the
    module: a described-device executable is written to the cache but can
    never be read back without a chip (on-chip-measurement guide, 2.3)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 -- no libtpu, no description
        pytest.skip(f"TPU topology description unavailable: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _pool(dev, page, pages_per_seq, *, fp8=False, layers=True):
    num_pages = 1 + B * pages_per_seq // 4
    lead = (L,) if layers else ()
    shape = lead + (num_pages, KH, page, D)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    if fp8:
        return QuantPool(s(shape, FP8_DTYPE), s(shape[:-2], SCALE_DTYPE))
    return s(shape, jnp.bfloat16)


def _rows(dev, *shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


@pytest.mark.parametrize(
    "page,pages_per_seq,fp8,window,sinks",
    [
        pytest.param(16, 64, False, 0, False, id="bf16-page16x64"),
        # 2,048-token tables: the DMA-semaphore space bounds the window
        pytest.param(16, 128, False, 0, False, id="bf16-page16x128"),
        # four 4 MiB window slots were the whole scoped-VMEM limit
        pytest.param(32, 64, False, 0, False, id="bf16-page32x64"),
        # scale operands: blocks Mosaic can lay out, heads off the lanes
        pytest.param(16, 64, True, 0, False, id="fp8-page16x64"),
        pytest.param(16, 64, False, 128, True, id="sinks-window128"),
    ],
)
def test_fused_decode_compiles_for_v5e(
    v5e, page, pages_per_seq, fp8, window, sinks
):
    pool = _pool(v5e, page, pages_per_seq, fp8=fp8)
    i32 = jnp.int32
    kwargs = {"layer": 1, "window": window}
    if sinks:
        kwargs["sinks"] = _rows(v5e, H)
    fused_decode_attention.lower(
        _rows(v5e, B, H, D), pool, pool,
        _rows(v5e, B, KH, D), _rows(v5e, B, KH, D),
        _rows(v5e, B, pages_per_seq, dtype=i32),
        _rows(v5e, B, dtype=i32), _rows(v5e, B, dtype=i32),
        _rows(v5e, B, dtype=i32), **kwargs,
    ).compile()


@pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "fp8"])
def test_paged_attention_v3_compiles_for_v5e(v5e, fp8):
    pool = _pool(v5e, 16, 64, fp8=fp8, layers=False)
    kwargs = {}
    if fp8:
        kwargs = {"k_scale": pool.scale, "v_scale": pool.scale}
        pool = pool.vals
    paged_decode_attention_v3.lower(
        _rows(v5e, B, H, D), pool, pool,
        _rows(v5e, B, 64, dtype=jnp.int32), _rows(v5e, B, dtype=jnp.int32),
        **kwargs,
    ).compile()


def test_kv_write_compiles_for_v5e(v5e):
    pool = _pool(v5e, 16, 64)
    kv_write_pallas.lower(
        pool, pool, _rows(v5e, B, KH, D), _rows(v5e, B, KH, D),
        _rows(v5e, B, dtype=jnp.int32), _rows(v5e, B, dtype=jnp.int32),
        layer=1,
    ).compile()
