"""The latent family's prefill kernel (``ops/pallas/latent_prefill.py``)
interpreted on the CPU at toy widths: against its XLA twin
(``ops/attention.latent_prefill_walk``) and against the plain attention of
``models/mla.py: reference_forward``; its trip counts against
``prefill_blocks`` and the engine's ``prefill_kv`` counters; and the two
pools it does not serve (fp8, a tp mesh) on the twin, counted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import mla
from dynamo_tpu.ops import attention as attn_ops
from dynamo_tpu.ops import fallback
from dynamo_tpu.ops.pallas.latent_prefill import latent_prefill_kernel

H, DN, DR, DV, DC = 4, 16, 8, 12, 24
SPEC = ModelSpec(
    name="toy-latent", vocab_size=96, hidden_size=48, intermediate_size=64,
    num_layers=2, num_heads=H, num_kv_heads=H, head_dim=8, rope_theta=1e4,
    rms_eps=1e-6, dtype="float32", tie_embeddings=False, kv_lora_rank=DC,
    qk_nope_head_dim=DN, qk_rope_head_dim=DR, v_head_dim=DV,
)
SCALE = mla.softmax_scale(SPEC)
LAYER = 1


def _case(members, T, *, page=4, P=16, tq=8, bp=1, dtype=jnp.float32,
          lanes=DC + DR, heads=None):
    return dict(members=members, T=T, page=page, P=P, tq=tq, bp=bp,
                dtype=dtype, lanes=lanes, heads=heads)


CASES = {
    # (start_pos, real rows) a member; T padded rows a member
    "fresh-chunk": _case([(0, 16)], 16),
    "second-chunk": _case([(16, 16)], 16),
    "third-chunk-partly-filled": _case([(32, 7)], 16),
    "pack-unequal-and-padded": _case([(0, 16), (0, 9), (0, 0)], 16),
    "pack-of-resumed-chunks": _case([(16, 16), (8, 5)], 16, bp=2),
    "last-page-partly-filled": _case([(0, 13)], 16),
    "table-wider-than-the-context": _case([(0, 10)], 16, P=32, bp=4),
    "rows-not-a-multiple-of-the-tile": _case([(4, 12)], 12),
    "context-of-one-block": _case([(0, 16)], 16, bp=16),
    "table-its-blocks-do-not-divide": _case([(12, 8)], 8, P=5, bp=2),
    "verify-mid-page": _case([(5, 3), (22, 4)], 4, tq=4),
    "one-tile-of-all-rows": _case([(16, 16)], 16, tq=16, bp=2),
    "groups-of-two-heads": _case([(16, 16), (0, 6)], 16, heads=2),
    "lane-padded-pool": _case([(8, 16)], 16, lanes=DC + DR + 8),
    "bfloat16-pool": _case([(16, 16), (0, 11)], 16, dtype=jnp.bfloat16),
    "bfloat16-lane-padded-groups": _case(
        [(0, 16)], 16, dtype=jnp.bfloat16, lanes=DC + DR + 8, heads=1),
}


def _operands(case, seed=0):
    """A pool whose pages hold each member's own rows through its table
    (a shuffled one; everything else stays as drawn: finite junk that a
    correct kernel never lets through), its queries, and the weights."""
    members, T, page, P = (case[k] for k in ("members", "T", "page", "P"))
    N, dt = len(members), case["dtype"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pool = np.array(jax.random.normal(
        ks[0], (2, 1 + N * P, page, case["lanes"])).astype(dt))
    pool[..., DC + DR:] = 0  # writers pad a row with zeros
    tables = 1 + np.asarray(
        jax.random.permutation(ks[1], N * P)).reshape(N, P).astype(np.int32)
    ctx = []
    for n, (start, nt) in enumerate(members):
        rows = np.asarray(
            jax.random.normal(jax.random.fold_in(ks[2], n),
                              (start + nt, DC + DR)).astype(dt))
        for pos in range(start + nt):
            pool[LAYER, tables[n, pos // page], pos % page, :DC + DR] = rows[pos]
        ctx.append(rows)
    q_nope = (jax.random.normal(ks[3], (N, T, H, DN)) * 0.5).astype(dt)
    q_rope = (jax.random.normal(ks[4], (N, T, H, DR)) * 0.5).astype(dt)
    kw = jax.random.split(ks[5])
    w_uk = (jax.random.normal(kw[0], (H, DC, DN)) * DC ** -0.5).astype(dt)
    w_uv = (jax.random.normal(kw[1], (H, DC, DV)) * DC ** -0.5).astype(dt)
    start = jnp.asarray([m[0] for m in members], jnp.int32)
    kv_len = jnp.asarray([m[0] + m[1] for m in members], jnp.int32)
    return (q_nope, q_rope, jnp.asarray(pool), w_uk, w_uv,
            jnp.asarray(tables), start, kv_len), ctx


def _counts(case, start, kv_len):
    tiles = jnp.arange(-(-case["T"] // case["tq"]))[None, :]
    return attn_ops.prefill_blocks(
        start[:, None], (kv_len - start)[:, None], tiles, case["tq"], 0,
        case["page"], case["bp"])[1]


def _kernel(case, ops, **kw):
    q_nope, q_rope, pool, w_uk, w_uv, tables, start, kv_len = ops
    return latent_prefill_kernel(
        q_nope, attn_ops.pad_heads(q_rope, pool.shape[-1] - DC), pool, w_uk,
        w_uv, tables, start, kv_len, _counts(case, start, kv_len),
        layer=LAYER, scale=SCALE, tq=case["tq"], bp=case["bp"],
        heads=case["heads"], interpret=True, **kw)


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_against_the_twin_and_the_reference(name):
    case = CASES[name]
    ops, ctx = _operands(case)
    q_nope, q_rope, pool, w_uk, w_uv, tables, start, kv_len = ops
    got = np.asarray(_kernel(case, ops), np.float32)
    assert np.isfinite(got).all()
    twin = np.asarray(jax.vmap(
        lambda qn, qr, bt, sp, kvl: attn_ops.latent_prefill_walk(
            qn, qr, pool, LAYER, w_uk, w_uv, bt, sp, kvl, scale=SCALE)
    )(q_nope, q_rope, tables, start, kv_len), np.float32)
    bf16 = case["dtype"] == jnp.bfloat16
    # the twin computes in the same types; the reference in float32
    tol, ref_tol = (2e-2, 4e-2) if bf16 else (2e-5, 2e-5)
    lp = {"w_uk": w_uk, "w_uv": w_uv}
    for n, (sp, nt) in enumerate(case["members"]):
        np.testing.assert_allclose(got[n, :nt], twin[n, :nt], rtol=tol, atol=tol)
        if not nt:
            continue
        mask = (sp + np.arange(nt))[:, None] >= np.arange(sp + nt)[None, :]
        want = mla._dense_attention(
            SPEC, lp, q_nope[n, :nt], q_rope[n, :nt], jnp.asarray(ctx[n]),
            jnp.asarray(mask))
        np.testing.assert_allclose(
            got[n, :nt], np.asarray(want), rtol=ref_tol, atol=ref_tol)


def test_trip_counts_are_prefill_blocks_and_the_engine_counts_the_same(
        monkeypatch):
    """What each query tile of the kernel scored, read back from the
    kernel, is ``prefill_blocks`` under ``latent_prefill_tiling`` (the
    ``counts`` it was handed are not merely an upper bound), a tile stops
    at its own causal edge, and ``_count_prefill_kv`` adds exactly those:
    a member its own blocks, a padded member none."""
    case = _case([(16, 16), (0, 9), (0, 0), (40, 3)], 16, bp=2, heads=2)
    ops, _ = _operands(case)
    _, seen = _kernel(case, ops, visits=True)
    want = np.asarray(_counts(case, ops[-2], ops[-1]))
    assert np.asarray(seen).shape == (4, H // 2, 2)
    for g in range(H // 2):
        np.testing.assert_array_equal(np.asarray(seen)[:, g], want)
    # rows 16..23 see pages 0..5 (3 blocks of 2), rows 24..31 pages 0..7;
    # 9 rows from 0: one block, then two; a padded member: nothing; three
    # rows at 40 reach page 10 (6 blocks), the second tile has no real row
    assert want.tolist() == [[3, 4], [1, 2], [0, 0], [6, 0]]

    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    engine = InferenceEngine(SPEC, EngineConfig(
        page_size=4, num_pages=64, max_pages_per_seq=16, max_decode_slots=2,
        prefill_buckets=(16,), max_prefill_chunk_tokens=16))
    kernel = attn_ops.latent_kernel_serves(engine.k_pages, engine.mesh)
    assert kernel
    for rows, pages, starts, nts in (
        (16, 16, [16, 0, 0, 40], [16, 9, 0, 3]),
        (384, 160, [0, 256], [384, 100]),
        (1024, 160, [7168], [1024]),
    ):
        before = dict(engine.prefill_kv)
        engine._count_prefill_kv(rows, pages, starts, nts)
        tq, bp = attn_ops.latent_prefill_tiling(rows, pages, 4, kernel=True)
        tiles = np.arange(-(-rows // tq))[None, :]
        _, count = attn_ops.prefill_blocks(
            np.asarray(starts)[:, None], np.asarray(nts)[:, None], tiles, tq,
            0, 4, bp)
        grew = {k: engine.prefill_kv[k] - before[k] for k in before}
        assert grew == {
            "blocks_visited.latent": int(count.sum()),
            "blocks_table.latent": len(starts) * tiles.size * -(-pages // bp),
            "dispatches.latent": 1, "kernel_calls.latent": 1,
        }
    # the twin's tiling where the kernel does not serve: one tile of all
    # rows, a pack run to its longest member
    assert attn_ops.latent_prefill_tiling(1024, 160, 64, kernel=True) == (256, 4)
    assert attn_ops.latent_prefill_tiling(1024, 160, 64) == (1024, 4)
    assert attn_ops.latent_prefill_tiling(9, 160, 64, kernel=True) == (128, 4)
    monkeypatch.setenv("DYNAMO_PALLAS", "0")
    before = dict(engine.prefill_kv)
    engine._count_prefill_kv(16, 16, [16, 0], [16, 9])
    assert engine.prefill_kv["kernel_calls.latent"] == before["kernel_calls.latent"]
    assert engine.prefill_kv["dispatches.latent"] == before["dispatches.latent"] + 1
    assert engine.prefill_kv["blocks_visited.latent"] - before[
        "blocks_visited.latent"] == 2 * 1  # a 16-page table is one block


def _fallbacks(reason: str) -> float:
    """What ``dynamo_fused_fallback_total`` has counted under ``reason``."""
    return fallback._FALLBACKS.labels(reason)._value.get()


@pytest.mark.parametrize("what", ["fp8-pool", "tp-mesh"])
def test_pools_the_kernel_does_not_serve_take_the_twin_and_say_so(
        what, monkeypatch):
    """A ``QuantPool`` (with its exact ``new_rows`` overlay) and a tp mesh
    run the XLA walk with Pallas on, counted ``latent_prefill_fp8_xla`` /
    ``latent_prefill_tp_xla``, and agree with the kernel's answer."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    case = _case([(16, 16), (0, 9)], 16)
    ops, ctx = _operands(case)
    q_nope, q_rope, pool, w_uk, w_uv, tables, start, kv_len = ops
    want = np.asarray(_kernel(case, ops), np.float32)
    new_rows, mesh, reason, tol = None, None, "latent_prefill_tp_xla", 2e-5
    if what == "fp8-pool":
        from dynamo_tpu.ops.quant import QuantPool, quant_page_tiles

        flat = pool.reshape((-1,) + pool.shape[2:])
        vals, scale = quant_page_tiles(flat, True, (2,))
        pool = QuantPool(vals.reshape(pool.shape), scale.reshape(pool.shape[:3]))
        new_rows = jnp.stack([
            jnp.pad(jnp.asarray(c[sp:]), ((0, 16 - nt), (0, 0)))
            for c, (sp, nt) in zip(ctx, case["members"])])
        reason, tol = "latent_prefill_fp8_xla", 0.15
    else:
        from dynamo_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(tp=2, dp=1)
    before = _fallbacks(reason)
    attn_ops.latent_prefill_attention.clear_cache()
    got = attn_ops.latent_prefill_attention(
        q_nope, q_rope, pool, LAYER, w_uk, w_uv, tables, start, kv_len,
        scale=SCALE, new_rows=new_rows, mesh=mesh)
    attn_ops.latent_prefill_attention.clear_cache()
    assert _fallbacks(reason) == before + 1
    for n, (_, nt) in enumerate(case["members"]):
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[n, :nt], want[n, :nt], rtol=tol,
            atol=tol)
