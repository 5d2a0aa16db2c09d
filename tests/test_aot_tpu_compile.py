"""The decode kernels must COMPILE for the chip at Llama-3-8B widths.

Interpret-mode tests (test_fused_decode, test_kv_write) check the
kernels' numbers on the CPU; they cannot see
what the TPU compiler refuses — a slice off the tiling, a block shape
Mosaic cannot lay out, scratch past the scoped-VMEM or DMA-semaphore
space. This file asks the installed compiler, for a DESCRIBED v5e device
(no chip attached, nothing runs): about two seconds a case.

The file name sorts first on purpose: the tier-1 clock cuts the run
short on a slow box, and these guard every later kernel change at no
chip time.
"""

import dataclasses
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep compiler logs out of /tmp

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.engine.config import LayerKind, ModelSpec
from dynamo_tpu.models import llama, mla
from dynamo_tpu.models.family import get_family
from dynamo_tpu.ops.pallas.fused_decode import fused_decode_attention
from dynamo_tpu.ops.pallas.kv_write import kv_write_pallas
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
        # the column scales ride the window's own live chunks
        pytest.param(16, 64, True, 128, False, id="fp8-window128"),
        # MiMo's 4,608-token table: 18 chunks, and a scale operand whose
        # 288 columns of pages are no multiple of the lanes
        pytest.param(16, 288, True, 0, False, id="fp8-page16x288"),
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


@pytest.mark.parametrize(
    "kv_heads,pages_per_seq,window,sinks,scope",
    [
        # MiMo-V2.5's two kinds of layer at 128 slots: K 192 laid out as
        # 256 lanes, V 128. A full layer's 4,608-token table is 18 chunks
        # of 16 pages, of which the kernel's loop runs a sequence's own;
        # a window layer is handed 9 pages, one chunk.
        pytest.param(4, 288, 0, False, "attn_full", id="mimo-full"),
        pytest.param(8, 9, 128, True, "attn_window", id="mimo-window"),
    ],
)
def test_fused_decode_compiles_for_v5e_with_k_wider_than_v(
    v5e, kv_heads, pages_per_seq, window, sinks, scope
):
    slots, heads, dk, dv = 128, 64, 256, 128

    def pool(d):
        return jax.ShapeDtypeStruct(
            (L, 257, kv_heads, 16, d), jnp.bfloat16, sharding=v5e)

    i32 = jnp.int32
    kwargs = {"layer": 1, "window": window, "scope": scope,
              "scale": 192 ** -0.5}
    if sinks:
        kwargs["sinks"] = _rows(v5e, heads)
    compiled = fused_decode_attention.lower(
        _rows(v5e, slots, heads, dk), pool(dk), pool(dv),
        _rows(v5e, slots, kv_heads, dk), _rows(v5e, slots, kv_heads, dv),
        _rows(v5e, slots, pages_per_seq, dtype=i32),
        _rows(v5e, slots, dtype=i32), _rows(v5e, slots, dtype=i32),
        _rows(v5e, slots, dtype=i32), **kwargs,
    ).compile()
    # the kernel is named after the scope: a trace tells the kinds apart
    assert f"%{scope}" in compiled.as_text()


@pytest.mark.parametrize("dk", [D, 2 * D], ids=["k=v", "k-wider"])
def test_kv_write_compiles_for_v5e(v5e, dk):
    num_pages = 1 + B * 64 // 4
    k_pool = jax.ShapeDtypeStruct(
        (L, num_pages, KH, 16, dk), jnp.bfloat16, sharding=v5e)
    kv_write_pallas.lower(
        k_pool, _pool(v5e, 16, 64), _rows(v5e, B, KH, dk),
        _rows(v5e, B, KH, D),
        _rows(v5e, B, dtype=jnp.int32), _rows(v5e, B, dtype=jnp.int32),
        layer=1,
    ).compile()


@pytest.mark.parametrize(
    "pages_per_seq,dtype",
    [
        # JoyAI-LLM-Flash's cell: 128 slots, 32 heads against rows of 576
        # values laid out as 640 lanes, 10,240-token tables in 40 chunks
        pytest.param(640, jnp.bfloat16, id="joyai-640"),
        # a table that its chunks do not divide, and a float32 pool
        pytest.param(100, jnp.bfloat16, id="bf16-100"),
        pytest.param(64, jnp.float32, id="f32-64"),
    ],
)
def test_latent_decode_compiles_for_v5e(v5e, pages_per_seq, dtype):
    from dynamo_tpu.ops.pallas.latent_decode import latent_decode_attention

    slots, heads, lanes, dc = 128, 32, 640, 512
    pool = jax.ShapeDtypeStruct((L, 1025, 16, lanes), dtype, sharding=v5e)
    i32 = jnp.int32
    compiled = latent_decode_attention.lower(
        _rows(v5e, slots, heads, lanes, dtype=dtype), pool,
        _rows(v5e, slots, lanes, dtype=dtype),
        _rows(v5e, slots, pages_per_seq, dtype=i32),
        _rows(v5e, slots, dtype=i32), _rows(v5e, slots, dtype=i32),
        _rows(v5e, slots, dtype=i32), layer=1, dc=dc, scope="attn_latent",
    ).compile()
    # the kernel is named after the scope: the trace's readers match it
    assert "%attn_latent" in compiled.as_text()


# heads, K nope, V, latent, a row's lanes, page, pages a table
_JOYAI = (32, 128, 128, 512, 640, 64, 160)


@pytest.mark.parametrize(
    "members,rows,dtype,widths",
    [
        # JoyAI-LLM-Flash's cell: the packed program's two members of
        # 1,024 rows, four tiles of 256 rows against blocks of 4 pages
        pytest.param(2, 1024, jnp.bfloat16, _JOYAI, id="joyai-2x1024"),
        # its single-prompt program, which is also a resumed chunk's
        pytest.param(1, 1024, jnp.bfloat16, _JOYAI, id="joyai-1024"),
        # a verify's few rows: one tile, padded to the lanes
        pytest.param(2, 9, jnp.bfloat16, _JOYAI, id="verify-9"),
        # rows that tiles of 256 do not divide, and float32
        pytest.param(1, 384, jnp.float32, _JOYAI, id="f32-384"),
        # heads of 64 + 32 and 64 (MiniCPM3's): padded to the lanes here
        pytest.param(2, 128, jnp.bfloat16, (40, 64, 64, 256, 384, 16, 64),
                     id="heads-of-64"),
    ],
)
def test_latent_prefill_compiles_for_v5e(v5e, members, rows, dtype, widths):
    from dynamo_tpu.ops.attention import (
        SCOPE_PREFILL_LATENT, latent_prefill_tiling,
    )
    from dynamo_tpu.ops.pallas.latent_prefill import latent_prefill_kernel

    heads, dn, dv, dc, lanes, page, pages = widths
    tq, bp = latent_prefill_tiling(rows, pages, page, kernel=True)
    i32 = jnp.int32
    compiled = latent_prefill_kernel.lower(
        _rows(v5e, members, rows, heads, dn, dtype=dtype),
        _rows(v5e, members, rows, heads, lanes - dc, dtype=dtype),
        jax.ShapeDtypeStruct((L, 1025, page, lanes), dtype, sharding=v5e),
        _rows(v5e, heads, dc, dn, dtype=dtype),
        _rows(v5e, heads, dc, dv, dtype=dtype),
        _rows(v5e, members, pages, dtype=i32), _rows(v5e, members, dtype=i32),
        _rows(v5e, members, dtype=i32),
        _rows(v5e, members, -(-rows // tq), dtype=i32),
        layer=_rows(v5e, dtype=i32), scale=0.07, tq=tq, bp=bp,
        scope=SCOPE_PREFILL_LATENT,
    ).compile()
    # named after its own scope, which no configuration's trace_names
    # holds: the decode kernel's roofline share reads ``attn_latent``
    text = compiled.as_text()
    assert "%prefill_latent" in text and "attn_latent" not in text


@pytest.mark.parametrize("heads", [64, 32], ids=["solar-64", "ling-32"])
def test_kda_step_compiles_for_v5e(v5e, heads):
    """Solar-Open2's and Ling's cells: 128 slots on 129 state rows, 64 / 32
    heads of 128 x 128 float32 and the convolution tails, both pools read
    through their blocks and aliased in place, a row found through the
    scalar-prefetched ``rows``; the step's projections ``[B, 3, H dk]`` and
    the taps in bf16 as the layer makes them, the convolution, the norms
    and the one transpose in the kernel."""
    from dynamo_tpu.ops.pallas.kda import kda_step

    slots, d, rows = 128, 128, 128
    f32 = jnp.float32
    compiled = jax.jit(
        lambda pool, conv, at, x, taps, a, b: kda_step(
            pool, conv, at, x, taps, a, b, layer=1, scope="kda_step"),
        donate_argnums=(0, 1),
    ).lower(
        _rows(v5e, 3, rows + 1, heads, d, d, dtype=f32),
        _rows(v5e, 3, rows + 1, 3, 3, heads * d),
        _rows(v5e, slots, dtype=jnp.int32),
        _rows(v5e, slots, 3, heads * d),
        _rows(v5e, 4, 3, heads * d),
        _rows(v5e, slots, heads, d, dtype=f32),
        _rows(v5e, slots, heads, dtype=f32),
    ).compile()
    # the kernel is named after the scope: the trace's readers match it
    assert "%kda_step" in compiled.as_text()


@pytest.mark.parametrize("rows,blocks", [(2, 16), (1, 16)],
                         ids=["pack-of-2", "single"])
def test_kda_chunk_compiles_for_v5e(v5e, rows, blocks):
    """The chunkwise form whole at the cell's prefill shapes: 1,024 tokens
    a row in 16 blocks of 64, 64 heads, the layer's operands as they are
    (``[N, T, H d]``), a block's half that does not depend on the state
    formed in the kernel (so no triangular solve beside it), float32
    products, the state from and to the pool's rows in place."""
    from dynamo_tpu.ops.pallas.kda import kda_chunk

    heads, c, d = 64, 64, 128
    f32 = jnp.float32
    lead = (rows, blocks * c)
    compiled = jax.jit(
        lambda *a: kda_chunk(
            *a, layer=2, block=c, sub=16, scope="kda_chunk"),
        donate_argnums=(5,),
    ).lower(
        _rows(v5e, *lead, heads * d, dtype=f32),
        _rows(v5e, *lead, heads * d, dtype=f32),
        _rows(v5e, *lead, heads * d, dtype=f32),
        _rows(v5e, *lead, heads * d, dtype=f32),
        _rows(v5e, *lead, heads, dtype=f32),
        _rows(v5e, 3, 129, heads, d, d, dtype=f32),
        _rows(v5e, rows, dtype=jnp.int32), _rows(v5e, rows, dtype=jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert "%kda_chunk" in text
    # the one custom call is the kernel: no solver beside it
    assert "triangular" not in text.lower()
    assert text.count("custom_call_target=") == 1
    assert 'custom_call_target="tpu_custom_call"' in text


def test_ssd_step_compiles_for_v5e(v5e):
    """Falcon-H1's cell: 128 slots on 129 state rows, 32 heads of 128 x
    256 float32 in 2 groups and the convolution tails of 5,120 channels,
    both pools aliased in place, a row found through the scalar-prefetched
    ``rows``."""
    from dynamo_tpu.ops.pallas.ssd import ssd_step

    slots, heads, p, n, groups, rows, ch = 128, 32, 128, 256, 2, 128, 5120
    f32 = jnp.float32
    compiled = jax.jit(
        lambda pool, conv, at, dx, decay, b, c, tail: ssd_step(
            pool, conv, at, dx, decay, b, c, tail, layer=1, scope="ssd_step"),
        donate_argnums=(0, 1),
    ).lower(
        _rows(v5e, 4, rows + 1, heads, p, n, dtype=f32),
        _rows(v5e, 4, rows + 1, 3, ch),
        _rows(v5e, slots, dtype=jnp.int32),
        _rows(v5e, slots, heads, p, dtype=f32),
        _rows(v5e, slots, heads, dtype=f32),
        _rows(v5e, slots, groups, n, dtype=f32),
        _rows(v5e, slots, groups, n, dtype=f32),
        _rows(v5e, slots, 3, ch),
    ).compile()
    # the kernel is named after the scope: the trace's readers match it
    assert "%ssd_step" in compiled.as_text()



def test_scan_step_compiles_for_v5e(v5e):
    """Phi-4-mini-flash's cell: 128 slots on 129 state rows of 16 x 5,120
    float32 (the channels on the lanes) and the convolution tails of
    5,120 channels, both pools aliased in place, a row found through the
    scalar-prefetched ``rows``."""
    from dynamo_tpu.ops.pallas.scan import scan_step

    slots, n, ch, rows = 128, 16, 5120, 128
    f32 = jnp.float32
    compiled = jax.jit(
        lambda pool, conv, at, dt, dx, a, b, c, tail: scan_step(
            pool, conv, at, dt, dx, a, b, c, tail, layer=1, scope="scan"),
        donate_argnums=(0, 1),
    ).lower(
        _rows(v5e, 4, rows + 1, n, ch, dtype=f32),
        _rows(v5e, 4, rows + 1, 3, ch),
        _rows(v5e, slots, dtype=jnp.int32),
        _rows(v5e, slots, ch, dtype=f32),
        _rows(v5e, slots, ch, dtype=f32),
        _rows(v5e, n, ch, dtype=f32),
        _rows(v5e, slots, n, dtype=f32),
        _rows(v5e, slots, n, dtype=f32),
        _rows(v5e, slots, 3, ch),
    ).compile()
    # the kernel is named after the scope: the trace's readers match it
    assert "%scan" in compiled.as_text()


def test_scan_chunk_compiles_for_v5e(v5e):
    """The cell's packed prefill: two rows of 1,024 tokens over 5,120
    channels, the state ``[16, 5120]`` float32 from and to a row of the
    129-row pool in place, the rows, the fresh flags and the lengths
    scalar-prefetched; ``x`` in the activations' dtype."""
    from dynamo_tpu.ops.pallas.scan import scan_chunk

    members, tokens, n, ch, rows = 2, 1024, 16, 5120, 128
    f32, i32 = jnp.float32, jnp.int32
    compiled = jax.jit(
        lambda x, dt, a, b, c, pool, at, fresh, lens: scan_chunk(
            x, dt, a, b, c, pool, at, fresh, lens, 1),
        donate_argnums=(5,),
    ).lower(
        _rows(v5e, members, tokens, ch),
        _rows(v5e, members, tokens, ch, dtype=f32),
        _rows(v5e, n, ch, dtype=f32),
        _rows(v5e, members, tokens, n, dtype=f32),
        _rows(v5e, members, tokens, n, dtype=f32),
        _rows(v5e, 4, rows + 1, n, ch, dtype=f32),
        _rows(v5e, members, dtype=i32),
        _rows(v5e, members, dtype=jnp.bool_),
        _rows(v5e, members, dtype=i32),
    ).compile()
    # the kernel is named after its own jit: the trace's readers match it
    assert "%scan_chunk" in compiled.as_text()
    # the pool is updated in place: no copy of its 42 MB a layer
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        4 * (rows + 1) * n * ch * 4)


# ------------------------------------------- whole programs, a row a cell


def _as_on_the_chip(monkeypatch):
    """The programs choose their kernels, and whether to interpret them,
    by the default backend: for a described device it is the CPU, so the
    choice is told what the chip would say."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _falcon_spec():
    """One layer of Falcon-H1: attention AND an SSD mixer off one norm."""
    return dataclasses.replace(
        ModelSpec.tiny_falcon_h1(), vocab_size=2048, hidden_size=5120,
        intermediate_size=21504, num_layers=1, num_heads=20, num_kv_heads=4,
        head_dim=128, dtype="bfloat16", layer_pattern=(0,),
        layer_kinds=(LayerKind(4, 1e11, mixer="ssd"),),
        ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_chunk=128)


def _ling_spec():
    """A KDA and the latent (MLA) expert layer of Ling-3.0-flash: 64 of 512
    experts held, one of the router's 8 groups; both layers clamped."""
    return dataclasses.replace(
        ModelSpec.tiny_ling3(), vocab_size=2048, hidden_size=2560,
        intermediate_size=6144, num_layers=2, num_heads=32, num_kv_heads=32,
        head_dim=128, dtype="bfloat16", layer_pattern=(1, 0),
        layer_kinds=(
            LayerKind(0, 6e6, mixer="latent", head_gate=True),
            LayerKind(0, 0.0, mixer="kda", gate_bound=-5.0, full_rank=True),
        ),
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rotary_dim=64, kda_heads=32, kda_head_dim=128,
        num_experts=512, held_experts=(64, 0), num_experts_per_token=8,
        moe_intermediate_size=768, n_group=8, topk_group=4,
        first_k_dense=0, expert_clamp=(4.0, 4.0), shared_clamp=(5.0, 7.0))


def _lfm2_spec():
    """A short-convolution and an attention expert layer of LFM2-24B-A2B:
    64 experts all held; heads of 64 packed two a 128-lane row; tails and
    no state pool."""
    return dataclasses.replace(
        ModelSpec.tiny_lfm2(), vocab_size=2048, hidden_size=2048,
        intermediate_size=11776, num_layers=2, num_heads=32, num_kv_heads=8,
        head_dim=64, dtype="bfloat16", layer_pattern=(1, 0),
        layer_kinds=(LayerKind(8, 1e6), LayerKind(0, 0.0, mixer="conv")),
        num_experts=64, num_experts_per_token=4, moe_intermediate_size=1536,
        first_k_dense=0)


def _longcat_spec():
    """ONE shortcut-connected double layer of LongCat-Flash-Chat: 16 of 512
    FFN experts held, 256 identity experts behind them (768 router outputs);
    64 heads over a latent of 512 + 64; a pool a sub-layer."""
    return dataclasses.replace(
        ModelSpec.tiny_longcat(), vocab_size=2048, hidden_size=6144,
        intermediate_size=12288, num_layers=1, num_heads=64, num_kv_heads=64,
        head_dim=96, dtype="bfloat16", kv_lora_rank=512, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=512, held_experts=(16, 0), zero_experts=256,
        num_experts_per_token=12, moe_intermediate_size=2048)


def _trinity_spec():
    """A window and a full (NoPE) expert layer of Trinity-Mini (afmoe), gated
    and QK-normed, four norms each (layer 0's dense MLP is the dense cells'
    own product); 16 of 128 experts held beside the shared one."""
    return dataclasses.replace(
        ModelSpec.tiny_trinity(), vocab_size=2048, hidden_size=2048,
        intermediate_size=6144, num_layers=2, num_heads=32, num_kv_heads=4,
        head_dim=128, dtype="bfloat16", layer_pattern=(0, 1), first_k_dense=0,
        layer_kinds=(LayerKind(4, 1e4, window=2048),
                     LayerKind(4, 1e4, rope=False)),
        embedding_multiplier=2048 ** 0.5, num_experts=128,
        held_experts=(16, 0), num_experts_per_token=8,
        moe_intermediate_size=1024)


def _phi4flash_spec():
    """One layer of each kind of Phi-4-mini-flash (the published layers
    15-19: window, scan (the memory), full, GMU, cross)."""
    from dynamo_tpu.models.loader import spec_from_hf_config

    return spec_from_hf_config(dict(
        model_type="phi4flash", hidden_size=2560, num_attention_heads=40,
        num_key_value_heads=20, intermediate_size=10240, vocab_size=2048,
        num_hidden_layers=5, layers_kept=[15, 16, 17, 18, 19],
        published_layers=32, sliding_window=512, layer_norm_eps=1e-5,
        mb_per_layer=2, tie_word_embeddings=True, torch_dtype="bfloat16",
    ), name="phi4flash-aot")


def _lfm2_cache(k, v):
    # 8 KV heads of 64 as 4 rows of 128 lanes: no zeros in the pool
    assert k.pools[0].shape == v.pools[0].shape == (1, 257, 4, 64, 128)
    assert k.pools[1] is None
    assert v.pools[1].shape == (1, 129, 2, 2048)


def _trinity_cache(k, v):
    assert k.pools[0].shape == v.pools[0].shape == (1, 513, 4, 64, 128)
    assert k.pools[1].shape == v.pools[1].shape == (1, 513, 4, 64, 128)


def _longcat_prefill(compiled, text, k, v):
    assert "attn_latent" not in text
    pools = sum(p.size * p.dtype.itemsize for p in k)
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


def _trinity_prefill(compiled, text, k, v):
    # far under what the whole-table charge of ``EngineConfig.
    # prefill_shapes`` would price a 4,096-row bucket at (8 GiB a row)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


def _ling_decode(compiled, text, k, v):
    # the kernels update the pools in place: what the program holds beside
    # its arguments is less than the state pool (258 MiB a KDA layer)
    state = k.pools[1]
    assert compiled.memory_analysis().temp_size_in_bytes < (
        state.size * state.dtype.itemsize // 2)


def _lfm2_decode(compiled, text, k, v):
    # the tails are updated in place: what the program holds beside its
    # arguments is far less than an expert layer's weights (1.2 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20


def _longcat_decode(compiled, text, k, v):
    assert text.count("tpu_custom_call") == 2 + 3  # two attentions, gmm x 3
    pools = sum(p.size * p.dtype.itemsize for p in k)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < pools


def _trinity_decode(compiled, text, k, v):
    """``attn_window`` over the 33 pages a 2,048-token window reaches, in
    chunks of 7; ``attn_full`` over the table's 160 in chunks of 8."""
    from dynamo_tpu.ops.pallas.fused_decode import chunk_pages

    page_bytes = 4 * 64 * (128 + 128) * 2
    assert chunk_pages(page_bytes, 33) == 7
    assert chunk_pages(page_bytes, 160) == 8
    # the stacked weights stay in HBM: the kernel fetches the experts a
    # step touched itself (``ops/pallas/grouped.py``). megablox's operand
    # the compiler copied WHOLE into VMEM (memory space S(1)) ahead of
    # the call, 4 of this program's 6 (PERF.md section 6, PRs 53 and 54)
    made = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\S+)", text, re.M))
    calls = re.findall(r"%gmm[.\d]* = \S+ custom-call\(([^)]*)\)", text)
    assert len(calls) == 6  # three products a layer
    for operands in calls:
        weights = operands.split(",")[-1].strip()
        assert "[16," in made[weights] and "S(1)" not in made[weights]
    pools = sum(p.size * p.dtype.itemsize for p in (*k.pools, *v.pools))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 256 * 2**20


# configuration -> the layers of its cell at the PUBLISHED widths (``spec``),
# the cell's engine (pages of 64, tables of 160: ``pages``, ``rows`` of state,
# ``slots``), the prefill programs it serves (``members`` of ``tokens`` tokens)
# and what each compiled text must hold: the kernels named after their scopes,
# which the trace's readers match. ``*_also`` is handed (compiled, text, k, v)
# for what is no name in the text. A ``model_config`` PR adds a row.
CELLS = {
    # the walk and the SSD chunk form (8 chunks of 128) in one layer
    "falcon": dict(
        spec=_falcon_spec, pages=257, rows=128, slots=128, tokens=1024,
        members=(2, 1), prefill_holds=("ssd_chunk",),
        decode_holds=("%ssd_step", "%attn_full")),
    # state rows AND latent pages under one block table, one program
    "ling": dict(
        spec=_ling_spec, pages=257, rows=128, slots=128, tokens=1024,
        members=(2, 1), prefill_holds=("%kda_chunk", "%prefill_latent"),
        decode_holds=("%kda_step", "%attn_latent"), decode_also=_ling_decode),
    # the rows' tails (plain XLA) AND the packed pool (4 heads of 128
    # lanes under 8 query heads each); grouped products over 64 groups
    "lfm2": dict(
        spec=_lfm2_spec, pages=257, rows=128, slots=128, tokens=1024,
        members=(2, 1), cache_is=_lfm2_cache,
        prefill_holds=("%gmm", "conv_mix"),
        decode_holds=("%attn_full", "%gmm", "conv_mix"),
        decode_also=_lfm2_decode),
    # ``attn_latent`` TWICE (a pool a sub-layer), the shortcut's expert
    # layer between them; both pools in place
    "longcat": dict(
        spec=_longcat_spec, pages=2049, rows=0, slots=128, tokens=1024,
        members=(2, 1), prefill_holds=("%prefill_latent",),
        prefill_also=_longcat_prefill,
        decode_holds=("%attn_latent", "moe_zero"),
        decode_also=_longcat_decode),
    # 4,096 tokens a row, a 2,048-token window (nine blocks a tile); the
    # fused kernel TWICE as two programs of it
    "trinity": dict(
        spec=_trinity_spec, pages=513, rows=0, slots=64, tokens=4096,
        members=(2,), cache_is=_trinity_cache,
        prefill_holds=("%gmm", "norm_out", "moe_shared"),
        prefill_also=_trinity_prefill,
        decode_holds=("%attn_window", "%attn_full", "%gmm", "norm_out"),
        decode_also=_trinity_decode),
    # the scan's chunk form is the kernel, a leaf of the region ``scan``;
    # ONE row a sequence through the GMU and the cross layer, which runs
    # the full kernel again with no write
    "phi4flash": dict(
        spec=_phi4flash_spec, pages=257, rows=128, slots=128, tokens=1024,
        members=(2, 1),
        prefill_holds=("/scan/", "/gmu/", "/attn_cross/", "/attn_diff/",
                       "%scan_chunk", "/scan/jit(scan_chunk)/"),
        decode_holds=("%scan", "%attn_window", "%attn_full", "%attn_cross")),
}
_DESCRIBED: dict = {}  # name -> the tree (one described device a session)


def described(v5e, name):
    """(cell, spec, weights, the cache's two sides) of ``name``, described
    on the device: made once a configuration, so a prefill pair and the
    decode burst lower the same tree. For a latent family the two sides
    are the pools and the experts' counters (``models/family.py``)."""
    if name not in _DESCRIBED:
        cell = CELLS[name]
        spec = cell["spec"]()
        fam = get_family(spec)
        kw = {"state_rows": cell["rows"]} if cell["rows"] else {}

        def on_device(make):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
                jax.eval_shape(make))

        params = on_device(
            lambda: fam.init_params(spec, jax.random.PRNGKey(0)))
        k, v = on_device(
            lambda: fam.init_cache(spec, cell["pages"], 64, **kw))
        cell.get("cache_is", lambda k, v: None)(k, v)
        _DESCRIBED[name] = (cell, spec, params, k, v)
    return _DESCRIBED[name]


def _holds(cell, phase, compiled, k, v):
    text = compiled.as_text()
    for name in cell[f"{phase}_holds"]:
        assert name in text, name
    cell.get(f"{phase}_also", lambda *a: None)(compiled, text, k, v)


@pytest.mark.parametrize("name,members", [
    pytest.param(name, n, id=f"{name}-{'pack-of-2' if n == 2 else 'single'}")
    for name, cell in CELLS.items() for n in cell["members"]])
def test_prefill_program_compiles_for_v5e(v5e, monkeypatch, name, members):
    """The prefill programs of the cell (the model module's own jits), its
    layers at the published widths: a pack of two rows and the single row
    (which is also a resumed chunk's); every leaf of the cache donated."""
    _as_on_the_chip(monkeypatch)
    cell, spec, params, k, v = described(v5e, name)
    i32 = jnp.int32
    lead = () if members == 1 else (members,)
    tokens, tables, starts, lens = (
        _rows(v5e, *lead, cell["tokens"], dtype=i32),
        _rows(v5e, *lead, 160, dtype=i32), _rows(v5e, *lead, dtype=i32),
        _rows(v5e, *lead, dtype=i32))
    m, pair, kw = (mla, (k,), {"counts": v}) if spec.is_mla else (
        llama, (k, v), {})
    program = m.prefill_forward if members == 1 else m.prefill_forward_batch
    lowered = program.lower(
        spec, params, tokens, tables, starts, *pair, lens, **kw)
    _holds(cell, "prefill", lowered.compile(), k, v)


@pytest.mark.parametrize("name", list(CELLS))
def test_decode_program_compiles_for_v5e(v5e, monkeypatch, name):
    """The decode burst of the cell, its layers at the published widths:
    every slot through each kind's kernel in one program, 8 steps, the
    sampler on the device."""
    _as_on_the_chip(monkeypatch)
    cell, spec, params, k, v = described(v5e, name)
    B_, i32, f32 = cell["slots"], jnp.int32, jnp.float32
    head = (spec, params, _rows(v5e, B_, dtype=i32),
            _rows(v5e, B_, 160, dtype=i32), _rows(v5e, B_, dtype=i32))
    tail = (_rows(v5e, B_, dtype=jnp.bool_),
            _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=i32),
            _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=jnp.uint32),
            _rows(v5e, B_, dtype=i32))
    m, pair, kw = (mla, (k,), {"counts": v}) if spec.is_mla else (
        llama, (k, v), {})
    lowered = m.decode_steps.lower(
        *head, *pair, *tail, n_steps=8, n_logprobs=0, **kw)
    _holds(cell, "decode", lowered.compile(), k, v)
