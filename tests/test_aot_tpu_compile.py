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

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep compiler logs out of /tmp

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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


def _falcon_layer(v5e, vocab=2048):
    """One layer of Falcon-H1 at the published widths, described: spec,
    weights and the cache of the cell's engine (128 slots, pages of 64)."""
    import dataclasses

    from dynamo_tpu.engine.config import LayerKind, ModelSpec
    from dynamo_tpu.models import llama

    spec = dataclasses.replace(
        ModelSpec.tiny_falcon_h1(), vocab_size=vocab, hidden_size=5120,
        intermediate_size=21504, num_layers=1, num_heads=20, num_kv_heads=4,
        head_dim=128, dtype="bfloat16", layer_pattern=(0,),
        layer_kinds=(LayerKind(4, 1e11, mixer="ssd"),),
        ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_chunk=128)

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    params = described(jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0))))
    k, v = described(jax.eval_shape(
        lambda: llama.init_cache(spec, 257, 64, state_rows=128)))
    return spec, params, k, v


def _as_on_the_chip(monkeypatch):
    """The programs choose their kernels, and whether to interpret them,
    by the default backend: for a described device it is the CPU, so the
    choice is told what the chip would say."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("rows", [2, 1], ids=["pack-of-2", "single"])
def test_falcon_prefill_program_compiles_for_v5e(v5e, monkeypatch, rows):
    """The prefill programs of the cell, a layer of them at the published
    widths: 1,024 tokens a row through the page write, the walk and the
    SSD chunk form (8 chunks of 128), state and tails from and to their
    rows; every leaf of the cache donated."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _falcon_layer(v5e)
    i32 = jnp.int32
    if rows == 1:
        lowered = jax.jit(
            llama.prefill_forward_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, 1024, dtype=i32),
                _rows(v5e, 160, dtype=i32), _rows(v5e, dtype=i32), k, v,
                _rows(v5e, dtype=i32))
    else:
        lowered = jax.jit(
            llama.prefill_forward_batch_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, rows, 1024, dtype=i32),
                _rows(v5e, rows, 160, dtype=i32), _rows(v5e, rows, dtype=i32),
                k, v, _rows(v5e, rows, dtype=i32))
    text = lowered.compile().as_text()
    assert "ssd_chunk" in text


def test_falcon_decode_program_compiles_for_v5e(v5e, monkeypatch):
    """The decode burst of the cell, a layer of it at the published
    widths: 128 slots through the fused attention kernel (``attn_full``)
    AND ``ssd_step`` in one layer, 8 steps, the sampler on the device."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _falcon_layer(v5e)
    B_, i32, f32 = 128, jnp.int32, jnp.float32
    text = jax.jit(
        llama.decode_steps_impl, static_argnums=(0,),
        static_argnames=("n_steps", "n_logprobs"), donate_argnums=(5, 6),
    ).lower(
        spec, params, _rows(v5e, B_, dtype=i32), _rows(v5e, B_, 160, dtype=i32),
        _rows(v5e, B_, dtype=i32), k, v, _rows(v5e, B_, dtype=jnp.bool_),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=i32),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=jnp.uint32),
        _rows(v5e, B_, dtype=i32), n_steps=8, n_logprobs=0,
    ).compile().as_text()
    assert "%ssd_step" in text and "%attn_full" in text


def _ling_layers(v5e, vocab=2048):
    """A KDA expert layer and the latent (MLA) expert layer of Ling-3.0-
    flash at the published widths, described: spec, weights and the cache
    of the cell's engine (128 slots, pages of 64; 64 of 512 experts held,
    one of the router's 8 groups; both layers clamped)."""
    import dataclasses

    from dynamo_tpu.engine.config import LayerKind, ModelSpec
    from dynamo_tpu.models import llama

    spec = dataclasses.replace(
        ModelSpec.tiny_ling3(), vocab_size=vocab, hidden_size=2560,
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

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    params = described(jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0))))
    k, v = described(jax.eval_shape(
        lambda: llama.init_cache(spec, 257, 64, state_rows=128)))
    return spec, params, k, v


@pytest.mark.parametrize("rows", [2, 1], ids=["pack-of-2", "single"])
def test_ling_prefill_program_compiles_for_v5e(v5e, monkeypatch, rows):
    """The prefill programs of the cell, a layer of each kind at the
    published widths: 1,024 tokens a row through ``kda_chunk`` at 32 heads
    from and to the state rows AND the latent page write and
    ``prefill_latent`` over the kind's pool, under one block table; the
    group-limited router over 512 experts; every leaf donated."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _ling_layers(v5e)
    i32 = jnp.int32
    if rows == 1:
        lowered = jax.jit(
            llama.prefill_forward_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, 1024, dtype=i32),
                _rows(v5e, 160, dtype=i32), _rows(v5e, dtype=i32), k, v,
                _rows(v5e, dtype=i32))
    else:
        lowered = jax.jit(
            llama.prefill_forward_batch_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, rows, 1024, dtype=i32),
                _rows(v5e, rows, 160, dtype=i32), _rows(v5e, rows, dtype=i32),
                k, v, _rows(v5e, rows, dtype=i32))
    text = lowered.compile().as_text()
    assert "%kda_chunk" in text and "%prefill_latent" in text


def test_ling_decode_program_compiles_for_v5e(v5e, monkeypatch):
    """The decode burst of the cell, a layer of each kind at the published
    widths: 128 slots through ``kda_step`` at 32 heads AND ``attn_latent``
    on the kind's pool (its schedule made once a step) in one program, 8
    steps, the sampler on the device."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _ling_layers(v5e)
    B_, i32, f32 = 128, jnp.int32, jnp.float32
    compiled = jax.jit(
        llama.decode_steps_impl, static_argnums=(0,),
        static_argnames=("n_steps", "n_logprobs"), donate_argnums=(5, 6),
    ).lower(
        spec, params, _rows(v5e, B_, dtype=i32), _rows(v5e, B_, 160, dtype=i32),
        _rows(v5e, B_, dtype=i32), k, v, _rows(v5e, B_, dtype=jnp.bool_),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=i32),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=jnp.uint32),
        _rows(v5e, B_, dtype=i32), n_steps=8, n_logprobs=0,
    ).compile()
    text = compiled.as_text()
    assert "%kda_step" in text and "%attn_latent" in text
    # the kernels update the pools in place: what the program holds beside
    # its arguments is less than the state pool (258 MiB a KDA layer)
    state = k.pools[1]
    assert compiled.memory_analysis().temp_size_in_bytes < (
        state.size * state.dtype.itemsize // 2)


def _lfm2_layers(v5e, vocab=2048):
    """A short-convolution expert layer and an attention expert layer of
    LFM2-24B-A2B at the published widths, described: spec, weights and the
    cache of the cell's engine (128 slots, pages of 64; all 64 experts
    held; heads of 64 packed two a 128-lane row of the pools; tails and no
    state pool)."""
    import dataclasses

    from dynamo_tpu.engine.config import LayerKind, ModelSpec
    from dynamo_tpu.models import llama

    spec = dataclasses.replace(
        ModelSpec.tiny_lfm2(), vocab_size=vocab, hidden_size=2048,
        intermediate_size=11776, num_layers=2, num_heads=32, num_kv_heads=8,
        head_dim=64, dtype="bfloat16", layer_pattern=(1, 0),
        layer_kinds=(LayerKind(8, 1e6), LayerKind(0, 0.0, mixer="conv")),
        num_experts=64, num_experts_per_token=4, moe_intermediate_size=1536,
        first_k_dense=0)

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    params = described(jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0))))
    k, v = described(jax.eval_shape(
        lambda: llama.init_cache(spec, 257, 64, state_rows=128)))
    return spec, params, k, v


@pytest.mark.parametrize("rows", [2, 1], ids=["pack-of-2", "single"])
def test_lfm2_prefill_program_compiles_for_v5e(v5e, monkeypatch, rows):
    """The prefill programs of the cell, a layer of each kind at the
    published widths: 1,024 tokens a row through the short convolution
    from and to the rows' tails AND the QK-normed attention's page write
    and walk over 64-wide heads packed two a row in 128-lane pools; the
    router over 64 experts all held, top-4; every leaf donated."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _lfm2_layers(v5e)
    # 8 KV heads of 64 as 4 rows of 128 lanes: no zeros in the pool
    assert k.pools[0].shape == v.pools[0].shape == (1, 257, 4, 64, 128)
    assert k.pools[1] is None
    assert v.pools[1].shape == (1, 129, 2, 2048)
    i32 = jnp.int32
    if rows == 1:
        lowered = jax.jit(
            llama.prefill_forward_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, 1024, dtype=i32),
                _rows(v5e, 160, dtype=i32), _rows(v5e, dtype=i32), k, v,
                _rows(v5e, dtype=i32))
    else:
        lowered = jax.jit(
            llama.prefill_forward_batch_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, rows, 1024, dtype=i32),
                _rows(v5e, rows, 160, dtype=i32), _rows(v5e, rows, dtype=i32),
                k, v, _rows(v5e, rows, dtype=i32))
    text = lowered.compile().as_text()
    assert "%gmm" in text and "conv_mix" in text


def test_lfm2_decode_program_compiles_for_v5e(v5e, monkeypatch):
    """The decode burst of the cell, a layer of each kind at the published
    widths: 128 slots through the short convolution's tails (plain XLA)
    AND the fused attention kernel on the packed pool (``attn_full``: 4
    heads of 128 lanes under 8 query heads each), the grouped products
    over 64 groups, 8 steps, the sampler on the device."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _lfm2_layers(v5e)
    B_, i32, f32 = 128, jnp.int32, jnp.float32
    compiled = jax.jit(
        llama.decode_steps_impl, static_argnums=(0,),
        static_argnames=("n_steps", "n_logprobs"), donate_argnums=(5, 6),
    ).lower(
        spec, params, _rows(v5e, B_, dtype=i32), _rows(v5e, B_, 160, dtype=i32),
        _rows(v5e, B_, dtype=i32), k, v, _rows(v5e, B_, dtype=jnp.bool_),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=i32),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=jnp.uint32),
        _rows(v5e, B_, dtype=i32), n_steps=8, n_logprobs=0,
    ).compile()
    text = compiled.as_text()
    assert "%attn_full" in text and "%gmm" in text and "conv_mix" in text
    # the tails are updated in place: what the program holds beside its
    # arguments is far less than an expert layer's weights (1.2 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20


def _longcat_layer(v5e, vocab=2048):
    """ONE shortcut-connected double layer of LongCat-Flash-Chat at the
    published widths, described: spec, weights and the cache of the cell's
    engine (128 slots, pages of 64; 16 of 512 FFN experts held, 256
    identity experts behind them in a router of 768 outputs; 64 heads
    over a latent of 512 + 64; a pool of latent pages a sub-layer)."""
    import dataclasses

    from dynamo_tpu.engine.config import ModelSpec
    from dynamo_tpu.models import mla

    spec = dataclasses.replace(
        ModelSpec.tiny_longcat(), vocab_size=vocab, hidden_size=6144,
        intermediate_size=12288, num_layers=1, num_heads=64, num_kv_heads=64,
        head_dim=96, dtype="bfloat16", kv_lora_rank=512, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=512, held_experts=(16, 0), zero_experts=256,
        num_experts_per_token=12, moe_intermediate_size=2048)

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    params = described(jax.eval_shape(
        lambda: mla.init_params(spec, jax.random.PRNGKey(0))))
    cache = described(jax.eval_shape(lambda: mla.init_cache(spec, 2049, 64)))
    counts = described(jax.eval_shape(lambda: mla.init_counts(spec)))
    return spec, params, cache, counts


@pytest.mark.parametrize("rows", [2, 1], ids=["pack-of-2", "single"])
def test_longcat_prefill_program_compiles_for_v5e(v5e, monkeypatch, rows):
    """The prefill programs of the cell, one double layer at the published
    widths: 1,024 tokens a row through both sub-layers' page writes and
    ``prefill_latent`` at 64 heads, the router over 768 outputs, the
    grouped products; both pools donated."""
    from dynamo_tpu.models import mla

    _as_on_the_chip(monkeypatch)
    spec, params, cache, counts = _longcat_layer(v5e)
    i32 = jnp.int32
    if rows == 1:
        lowered = mla.prefill_forward.lower(
            spec, params, _rows(v5e, 1024, dtype=i32),
            _rows(v5e, 160, dtype=i32), _rows(v5e, dtype=i32), cache,
            _rows(v5e, dtype=i32), counts=counts)
    else:
        lowered = mla.prefill_forward_batch.lower(
            spec, params, _rows(v5e, rows, 1024, dtype=i32),
            _rows(v5e, rows, 160, dtype=i32), _rows(v5e, rows, dtype=i32),
            cache, _rows(v5e, rows, dtype=i32), counts=counts)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "%prefill_latent" in text and "attn_latent" not in text
    pools = sum(p.size * p.dtype.itemsize for p in cache)
    assert compiled.memory_analysis().alias_size_in_bytes >= pools


def test_longcat_decode_program_compiles_for_v5e(v5e, monkeypatch):
    """The decode burst of the cell, one double layer at the published
    widths: 128 slots through ``attn_latent`` at 64 heads TWICE (a pool a
    sub-layer, the schedule made once a step), the shortcut's expert layer
    between them, 8 steps, the sampler on the device; the kernels update
    both pools in place."""
    from dynamo_tpu.models import mla

    _as_on_the_chip(monkeypatch)
    spec, params, cache, counts = _longcat_layer(v5e)
    B_, i32, f32 = 128, jnp.int32, jnp.float32
    compiled = mla.decode_steps.lower(
        spec, params, _rows(v5e, B_, dtype=i32), _rows(v5e, B_, 160, dtype=i32),
        _rows(v5e, B_, dtype=i32), cache, _rows(v5e, B_, dtype=jnp.bool_),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=i32),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=jnp.uint32),
        _rows(v5e, B_, dtype=i32), n_steps=8, n_logprobs=0, counts=counts,
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 + 3  # two attentions, gmm x 3
    assert "%attn_latent" in text and "moe_zero" in text
    pools = sum(p.size * p.dtype.itemsize for p in cache)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < pools


def _trinity_layers(v5e, vocab=2048):
    """Two layers of Trinity-Mini (afmoe) at the published widths,
    described: a window expert layer and a full (NoPE) expert layer,
    gated and QK-normed, four norms each (the dense MLP of layer 0 is the
    dense cells' own product at another width); spec,
    weights and the cache of the cell's engine (64 slots, pages of 64,
    tables of 160 pages; 16 of 128 experts held beside the shared one; a
    pool a kind)."""
    import dataclasses

    from dynamo_tpu.engine.config import LayerKind, ModelSpec
    from dynamo_tpu.models import llama

    spec = dataclasses.replace(
        ModelSpec.tiny_trinity(), vocab_size=vocab, hidden_size=2048,
        intermediate_size=6144, num_layers=2, num_heads=32, num_kv_heads=4,
        head_dim=128, dtype="bfloat16", layer_pattern=(0, 1), first_k_dense=0,
        layer_kinds=(LayerKind(4, 1e4, window=2048),
                     LayerKind(4, 1e4, rope=False)),
        embedding_multiplier=2048 ** 0.5, num_experts=128,
        held_experts=(16, 0), num_experts_per_token=8,
        moe_intermediate_size=1024)

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    params = described(jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0))))
    k, v = described(jax.eval_shape(lambda: llama.init_cache(spec, 513, 64)))
    return spec, params, k, v


@pytest.mark.parametrize("rows", [2], ids=["pack-of-2"])
def test_trinity_prefill_program_compiles_for_v5e(v5e, monkeypatch, rows):
    """The prefill programs of the cell, a layer of each sort at the
    published widths: 4,096 tokens a row through the page write and the
    walk at a 2,048-token window (nine blocks a tile) and over the whole
    row, 32 gated heads over 4 KV heads, the router over 128 experts of
    which 16 are held, the shared expert, the four norms; every leaf
    donated. What the program holds beside its arguments stays far under
    what the whole-table charge of ``EngineConfig.prefill_shapes`` would
    price a 4,096-row bucket at (8 GiB a row)."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _trinity_layers(v5e)
    assert k.pools[0].shape == v.pools[0].shape == (1, 513, 4, 64, 128)
    assert k.pools[1].shape == v.pools[1].shape == (1, 513, 4, 64, 128)
    i32 = jnp.int32
    if rows == 1:
        lowered = jax.jit(
            llama.prefill_forward_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, 4096, dtype=i32),
                _rows(v5e, 160, dtype=i32), _rows(v5e, dtype=i32), k, v,
                _rows(v5e, dtype=i32))
    else:
        lowered = jax.jit(
            llama.prefill_forward_batch_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, rows, 4096, dtype=i32),
                _rows(v5e, rows, 160, dtype=i32), _rows(v5e, rows, dtype=i32),
                k, v, _rows(v5e, rows, dtype=i32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "%gmm" in text and "norm_out" in text and "moe_shared" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


def test_trinity_decode_program_compiles_for_v5e(v5e, monkeypatch):
    """The decode burst of the cell, a layer of each sort at the published
    widths: 64 slots through the fused kernel TWICE as two programs of it
    (``attn_window`` over the 33 pages a 2,048-token window reaches, in
    chunks of 7; ``attn_full`` over the table's 160 in chunks of 8), 8
    queries a KV head, the gate behind it, the grouped products over 16
    groups, 8 steps, the sampler on the device; the pools updated in
    place."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.ops.pallas.fused_decode import chunk_pages

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _trinity_layers(v5e)
    page_bytes = 4 * 64 * (128 + 128) * 2
    assert chunk_pages(page_bytes, 33) == 7
    assert chunk_pages(page_bytes, 160) == 8
    B_, i32, f32 = 64, jnp.int32, jnp.float32
    compiled = jax.jit(
        llama.decode_steps_impl, static_argnums=(0,),
        static_argnames=("n_steps", "n_logprobs"), donate_argnums=(5, 6),
    ).lower(
        spec, params, _rows(v5e, B_, dtype=i32), _rows(v5e, B_, 160, dtype=i32),
        _rows(v5e, B_, dtype=i32), k, v, _rows(v5e, B_, dtype=jnp.bool_),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=i32),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=jnp.uint32),
        _rows(v5e, B_, dtype=i32), n_steps=8, n_logprobs=0,
    ).compile()
    text = compiled.as_text()
    assert "%attn_window" in text and "%attn_full" in text and "%gmm" in text
    assert "norm_out" in text
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


def _phi4flash_layers(v5e, vocab=2048):
    """One layer of each kind of Phi-4-mini-flash at the published widths
    (the published layers 15-19: window, scan (the memory), full, GMU,
    cross), described: spec, weights and the cache of the cell's engine
    (128 slots, pages of 64)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import spec_from_hf_config

    spec = spec_from_hf_config(dict(
        model_type="phi4flash", hidden_size=2560, num_attention_heads=40,
        num_key_value_heads=20, intermediate_size=10240, vocab_size=vocab,
        num_hidden_layers=5, layers_kept=[15, 16, 17, 18, 19],
        published_layers=32, sliding_window=512, layer_norm_eps=1e-5,
        mb_per_layer=2, tie_word_embeddings=True, torch_dtype="bfloat16",
    ), name="phi4flash-aot")

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            tree)

    params = described(jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0))))
    k, v = described(jax.eval_shape(
        lambda: llama.init_cache(spec, 257, 64, state_rows=128)))
    return spec, params, k, v


@pytest.mark.parametrize("rows", [2, 1], ids=["pack-of-2", "single"])
def test_phi4flash_prefill_program_compiles_for_v5e(v5e, monkeypatch, rows):
    """The prefill programs of the cell, a layer of each kind at the
    published widths: 1,024 tokens a row through the page write of a
    pair a row, the walk under a 512 window and over all keys, the scan's
    chunk form, and ONE row a sequence through the GMU and the cross
    layer; every leaf of the cache donated."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _phi4flash_layers(v5e)
    i32 = jnp.int32
    if rows == 1:
        lowered = jax.jit(
            llama.prefill_forward_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, 1024, dtype=i32),
                _rows(v5e, 160, dtype=i32), _rows(v5e, dtype=i32), k, v,
                _rows(v5e, dtype=i32))
    else:
        lowered = jax.jit(
            llama.prefill_forward_batch_impl, static_argnums=(0,),
            donate_argnums=(5, 6),
        ).lower(spec, params, _rows(v5e, rows, 1024, dtype=i32),
                _rows(v5e, rows, 160, dtype=i32), _rows(v5e, rows, dtype=i32),
                k, v, _rows(v5e, rows, dtype=i32))
    text = lowered.compile().as_text()
    for region in ("scan", "gmu", "attn_cross", "attn_diff"):
        assert f"/{region}/" in text, region
    # the scan's walk is the kernel, a leaf of the region ``scan``
    assert "%scan_chunk" in text and "/scan/jit(scan_chunk)/" in text


def test_phi4flash_decode_program_compiles_for_v5e(v5e, monkeypatch):
    """The decode burst of the cell, a layer of each kind at the
    published widths: 128 slots through ``scan``, the window and the full
    kernel over rows a pair wide, and the full kernel again with no write
    for the cross layer, 8 steps, the sampler on the device."""
    from dynamo_tpu.models import llama

    _as_on_the_chip(monkeypatch)
    spec, params, k, v = _phi4flash_layers(v5e)
    B_, i32, f32 = 128, jnp.int32, jnp.float32
    text = jax.jit(
        llama.decode_steps_impl, static_argnums=(0,),
        static_argnames=("n_steps", "n_logprobs"), donate_argnums=(5, 6),
    ).lower(
        spec, params, _rows(v5e, B_, dtype=i32), _rows(v5e, B_, 160, dtype=i32),
        _rows(v5e, B_, dtype=i32), k, v, _rows(v5e, B_, dtype=jnp.bool_),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=i32),
        _rows(v5e, B_, dtype=f32), _rows(v5e, B_, dtype=jnp.uint32),
        _rows(v5e, B_, dtype=i32), n_steps=8, n_logprobs=0,
    ).compile().as_text()
    for kernel in ("%scan", "%attn_window", "%attn_full", "%attn_cross"):
        assert kernel in text, kernel
