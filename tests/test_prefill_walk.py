"""The prefill walk (ops/attention.paged_prefill_attention): blocks of a
sequence's pages under a running softmax, against ``causal_attention`` on
the gathered context, which stays as its oracle; the range function against
a brute-force mask; the lowered program's temporaries; the engine's
``prefill_kv`` counters."""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import LayerKind, ModelSpec
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops.quant import QuantPool, quant_page_tiles

PAGE = 4


@dataclasses.dataclass(frozen=True)
class Case:
    T: int  # rows of the call (padded)
    start: int  # position of the first row
    n: int  # real rows
    P: int = 24  # pages of the table
    H: int = 8
    KH: int = 2
    D: int = 16
    Dv: int = 16
    pool_pad: int = 0  # lanes the pools are wider than the model's heads
    packed: int = 1  # KV heads a row of the pools (pool_head_dim: 2 on
    # the chip where the heads are half a lane tile wide)
    window: int = 0
    sinks: bool = False
    fp8: bool = False
    tile: int = 8  # _TILE_ROWS for the case
    block: int = 16  # _BLOCK_TOKENS


CASES = {
    "full_first_chunk": Case(T=32, start=0, n=32),
    "full_second_chunk": Case(T=32, start=32, n=32),
    "kv_len_off_a_blocks_edge": Case(T=32, start=16, n=21),
    "padded_rows": Case(T=32, start=0, n=5),
    "window": Case(T=32, start=32, n=30, window=6),
    "window_wider_than_a_block": Case(T=32, start=32, n=32, window=40),
    "window_and_sinks": Case(T=32, start=16, n=32, window=6, sinks=True),
    "full_and_sinks": Case(T=32, start=8, n=17, sinks=True),
    "k192_v128_in_a_padded_pool": Case(
        T=16, start=8, n=13, H=8, KH=2, D=24, Dv=16, pool_pad=8, sinks=True,
        window=10,
    ),
    "gqa_4_to_1": Case(T=16, start=4, n=16, H=8, KH=2),
    "gqa_8_to_1": Case(T=16, start=4, n=16, H=8, KH=1),
    "gqa_16_to_1": Case(T=16, start=4, n=9, H=16, KH=1),
    "verify_sized": Case(T=5, start=37, n=4),
    "verify_sized_window": Case(T=5, start=37, n=5, window=6, sinks=True),
    "table_of_one_block": Case(T=8, start=4, n=7, P=4),
    "table_of_one_block_tiles": Case(T=16, start=0, n=12, P=4),
    "rows_not_a_tile_multiple": Case(T=12, start=4, n=11),
    "packed_pool_whole_prompt": Case(T=32, start=0, n=29, KH=4, packed=2),
    "packed_pool_second_chunk": Case(
        T=32, start=32, n=32, KH=4, packed=2, sinks=True),
    "packed_pool_window_four_a_row": Case(
        T=16, start=16, n=13, KH=4, packed=4, window=6),
    "fp8_pool": Case(T=16, start=16, n=13, fp8=True),
    "fp8_pool_verify": Case(T=5, start=18, n=4, fp8=True, window=6),
}


def _pools(c: Case, key, kv_len: int):
    """A sequence's K and V rows, and pools that hold them on shuffled
    pages ([1 layer, pages, KH, PAGE, width]; page 0 is the trash page)."""
    kk, kv_, kt = jax.random.split(key, 3)
    S = c.P * PAGE
    k = jax.random.normal(kk, (S, c.KH, c.D), jnp.float32)
    v = jax.random.normal(kv_, (S, c.KH, c.Dv), jnp.float32)
    live = jnp.arange(S)[:, None, None] < kv_len
    k, v = jnp.where(live, k, 7.0), jnp.where(live, v, 7.0)  # never read
    table = 1 + jax.random.permutation(kt, c.P).astype(jnp.int32)

    def pool(rows, width):
        # the writer under test: packed rows are the model's own, reshaped
        tiles = att.page_tiles(
            rows, PAGE, width * c.packed + c.pool_pad, c.KH // c.packed)
        assert tiles.shape[1:] == (
            c.KH // c.packed, PAGE, width * c.packed + c.pool_pad)
        if c.fp8:
            vals, s = quant_page_tiles(
                tiles, live.reshape(c.P, 1, PAGE, 1), (2, 3)
            )
            z = QuantPool(
                jnp.zeros((2, c.P + 1, *vals.shape[1:]), vals.dtype),
                jnp.zeros((2, c.P + 1, c.KH), s.dtype),
            )
            return QuantPool(
                z.vals.at[1, table].set(vals), z.scale.at[1, table].set(s)
            )
        z = jnp.zeros((2, c.P + 1, *tiles.shape[1:]), jnp.float32)
        return z.at[1, table].set(tiles)

    return k, v, pool(k, c.D), pool(v, c.Dv), table


def _walk_and_oracle(c: Case, seed: int, monkeypatch):
    monkeypatch.setattr(att, "_TILE_ROWS", c.tile)
    monkeypatch.setattr(att, "_BLOCK_TOKENS", c.block)
    key = jax.random.PRNGKey(seed)
    kq, kp, ks = jax.random.split(key, 3)
    kv_len = c.start + c.n
    q = jax.random.normal(kq, (c.T, c.H, c.D), jnp.float32)
    sinks = jax.random.normal(ks, (c.H,)) if c.sinks else None
    k, v, k_pool, v_pool, table = _pools(c, kp, kv_len)
    positions = c.start + jnp.arange(c.T)
    new_kv = None
    k_ctx = att.gather_ctx(k_pool, 1, table, c.D, c.KH)
    v_ctx = att.gather_ctx(v_pool, 1, table, c.Dv, c.KH)
    if c.packed > 1:
        # un-packed, the pool is the rows that went in, bit for bit; the
        # oracle attends to those
        np.testing.assert_array_equal(np.asarray(k_ctx), np.asarray(k))
        np.testing.assert_array_equal(np.asarray(v_ctx), np.asarray(v))
        k_ctx, v_ctx = k, v
    if c.fp8:
        # the call's own rows are exact over the quantised read-back
        new_kv = (k[c.start:c.start + c.T], v[c.start:c.start + c.T])
        k_ctx = k_ctx.at[positions].set(new_kv[0])
        v_ctx = v_ctx.at[positions].set(new_kv[1])
    want = att.causal_attention(
        q, k_ctx, v_ctx, positions, jnp.asarray(kv_len), window=c.window,
        sinks=sinks,
    )
    got = att.paged_prefill_attention(
        q, k_pool, v_pool, 1, table, jnp.asarray(c.start),
        jnp.asarray(kv_len), head_dim=c.D, v_dim=c.Dv, kv_heads=c.KH,
        window=c.window, sinks=sinks, new_kv=new_kv,
    )
    return got, want


@pytest.mark.parametrize("name", list(CASES))
def test_the_walk_is_causal_attention_on_the_gathered_context(
    name, monkeypatch
):
    c = CASES[name]
    got, want = _walk_and_oracle(c, 3, monkeypatch)
    assert got.shape == want.shape == (c.T, c.H, c.Dv)
    assert bool(jnp.isfinite(got).all())  # padded rows too
    np.testing.assert_allclose(
        np.asarray(got[: c.n]), np.asarray(want[: c.n]), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("packed", [1, 2], ids=["plain", "packed_pool"])
def test_a_pack_under_vmap_runs_each_member_to_its_own_length(
    monkeypatch, packed
):
    c = Case(T=16, start=0, n=0, window=0, sinks=True, packed=packed)
    monkeypatch.setattr(att, "_TILE_ROWS", c.tile)
    monkeypatch.setattr(att, "_BLOCK_TOKENS", c.block)
    starts = jnp.asarray([0, 16, 48, 0])
    ns = jnp.asarray([16, 3, 11, 0])  # a padded row of the pack too
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (4, c.T, c.H, c.D), jnp.float32)
    sinks = jax.random.normal(keys[1], (c.H,))
    # one pool for the pack, a table a member
    k, v, k_pool, v_pool, table = _pools(c, keys[2], c.P * PAGE)
    tables = jnp.stack([jnp.roll(table, r) for r in range(4)])

    def walk(q_i, bt, s, n):
        return att.paged_prefill_attention(
            q_i, k_pool, v_pool, 1, bt, s, s + n, head_dim=c.D, v_dim=c.Dv,
            kv_heads=c.KH, sinks=sinks,
        )

    def oracle(q_i, bt, s, n):
        return att.causal_attention(
            q_i, att.gather_ctx(k_pool, 1, bt, c.D, c.KH),
            att.gather_ctx(v_pool, 1, bt, c.Dv, c.KH), s + jnp.arange(c.T),
            s + n, sinks=sinks,
        )

    got = jax.jit(jax.vmap(walk))(q, tables, starts, ns)
    want = jax.vmap(oracle)(q, tables, starts, ns)
    assert bool(jnp.isfinite(got).all())
    for i, n in enumerate(np.asarray(ns)):
        np.testing.assert_allclose(
            np.asarray(got[i, :n]), np.asarray(want[i, :n]),
            rtol=2e-5, atol=2e-5,
        )


@pytest.mark.parametrize("window", [0, 3, 6, 40])
def test_the_range_function_against_a_brute_force_mask(window):
    """Every visited block holds a key some real row of the tile sees; no
    unvisited block holds one."""
    page, bp, tq, T = 4, 2, 8, 32
    n_tiles = T // tq
    for start in (0, 8, 24, 52):
        for n in (0, 1, 7, 8, 9, 20, 32):
            kv_len = start + n
            kv = np.arange(kv_len + 4 * page * bp)
            for tile in range(n_tiles):
                rows = start + tile * tq + np.arange(tq)
                rows = rows[rows < kv_len]
                seen = (kv[None, :] <= rows[:, None]) & (kv[None, :] < kv_len)
                if window:
                    seen &= kv[None, :] > rows[:, None] - window
                seen_pages = set((kv[seen.any(axis=0)] // page).tolist())
                first, count = att.prefill_blocks(
                    np.int32(start), np.int32(n), np.int32(tile), tq, window,
                    page, bp,
                )
                visited = [
                    set(range(first + j * bp, first + (j + 1) * bp))
                    for j in range(int(count))
                ]
                assert seen_pages <= set().union(*visited), (start, n, tile)
                for blk in visited:
                    assert blk & seen_pages, (start, n, tile, blk)


def test_tiling_follows_the_table_the_bucket_and_the_window():
    assert att.prefill_tiling(1024, 288, 16) == (
        att._TILE_ROWS, att._BLOCK_TOKENS // 16
    )
    # a table no wider than a block is one block; so is a window's reach
    assert att.prefill_tiling(5, 8, 16) == (5, 8)
    reach = (att._TILE_ROWS + 18) // 16 + 2
    assert att.prefill_tiling(1024, 288, 16, window=20) == (
        att._TILE_ROWS, reach
    )
    # and such a tile's walk is one step, wherever the call stands
    starts = np.arange(0, 3585, 16, dtype=np.int32)[:, None]
    tiles = np.arange(1024 // att._TILE_ROWS)[None, :]
    _, count = att.prefill_blocks(
        starts, np.int32(1024), tiles, att._TILE_ROWS, 20, 16, reach
    )
    assert count.max() == 1


@pytest.mark.parametrize("window", [0, 20], ids=["full", "window"])
def test_a_call_of_many_rows_walks_longer_blocks_to_the_same_attention(
        monkeypatch, window):
    """Past ``_LONG_CALL_ROWS`` rows a call's blocks are
    ``_LONG_BLOCK_TOKENS`` long (a 4,096-row bucket under a 2,048-token
    window: 16 pages of 64 where a 1,024-row bucket takes 4); what is
    computed is the same attention, a quarter of the loop steps."""
    assert att.prefill_tiling(4096, 160, 64, 2048) == (att._TILE_ROWS, 16)
    assert att.prefill_tiling(4096, 160, 64) == (att._TILE_ROWS, 16)
    assert att.prefill_tiling(1024, 160, 64, 2048) == (att._TILE_ROWS, 4)
    c = Case(T=32, start=32, n=30, window=window, block=8)
    short, want = _walk_and_oracle(c, 7, monkeypatch)
    steps = att.prefill_blocks(32, 30, np.arange(4), 8, window, PAGE, 2)[1]
    monkeypatch.setattr(att, "_LONG_CALL_ROWS", 16)
    monkeypatch.setattr(att, "_LONG_BLOCK_TOKENS", 32)
    assert att.prefill_tiling(32, c.P, PAGE, window)[1] == min(
        8, (8 + window - 2) // PAGE + 2 if window else 8)
    att.paged_prefill_attention.clear_cache()  # the tiling is read at trace
    long, _ = _walk_and_oracle(c, 7, monkeypatch)
    att.paged_prefill_attention.clear_cache()
    np.testing.assert_allclose(np.asarray(long), np.asarray(want), 2e-5, 2e-5)
    np.testing.assert_allclose(np.asarray(long), np.asarray(short), 2e-5, 2e-5)
    fewer = att.prefill_blocks(
        32, 30, np.arange(4), 8, window, PAGE,
        att.prefill_tiling(32, c.P, PAGE, window)[1])[1]
    assert fewer.sum() < steps.sum()


def _mimo_shaped_spec() -> ModelSpec:
    return ModelSpec(
        name="walk-mimo-shaped", vocab_size=128, hidden_size=64,
        num_layers=2, num_heads=8, num_kv_heads=2, head_dim=24,
        v_head_dim=16, intermediate_size=64, dtype="float32",
        layer_kinds=(
            LayerKind(num_kv_heads=2, rope_theta=1e6),
            LayerKind(num_kv_heads=4, rope_theta=1e4, window=128, sinks=True),
        ),
        layer_pattern=(0, 1),
    )


def test_no_float32_tensor_of_the_tables_width_in_the_prefill_program():
    """The lowered prefill of a MiMo-shaped spec (a full and a window
    layer, K wider than V, sinks; a 4,608-token table, a 1,024-row
    bucket) holds no float32 tensor whose last axis is the table's width:
    the scores are a block wide."""
    from dynamo_tpu.models import llama

    spec = _mimo_shaped_spec()
    params = jax.eval_shape(
        lambda: llama.init_params(spec, jax.random.PRNGKey(0))
    )
    k_pages, v_pages = jax.eval_shape(lambda: llama.init_cache(spec, 300, 16))
    T, pages = 1024, 288
    i32 = jnp.int32
    lowered = llama.prefill_forward.lower(
        spec, params, jax.ShapeDtypeStruct((T,), i32),
        jax.ShapeDtypeStruct((pages,), i32), jax.ShapeDtypeStruct((), i32),
        k_pages, v_pages, jax.ShapeDtypeStruct((), i32),
    )
    text = lowered.as_text()
    width = pages * 16
    wide = re.findall(rf"tensor<(?:\d+x)*{width}xf32>", text)
    assert not wide, wide[:3]
    # the walk's own scores are there, a block wide
    tq, bp = att.prefill_tiling(T, pages, 16)
    assert re.search(rf"tensor<(?:\d+x)*{tq}x{bp * 16}xf32>", text)


# -- the engine's counters -------------------------------------------------


def _engine(spec=None, **kw):
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import InferenceEngine

    base = dict(
        page_size=16, num_pages=320, max_pages_per_seq=288,
        max_decode_slots=2, prefill_buckets=(512, 1024),
        prefill_pack_size=2, max_prefill_chunk_tokens=1024,
        async_admissions=False,
    )
    base.update(kw)
    return InferenceEngine(spec or ModelSpec.tiny(), EngineConfig(**base))


def _prefill_kv(engine) -> dict:
    return {k.removeprefix("prefill_kv."): v["calls"]
            for k, v in engine.profile_snapshot().items()
            if k.startswith("prefill_kv.")}


async def test_a_300_token_prompt_in_a_4608_token_table_counts_300_tokens():
    from dynamo_tpu.runtime.context import Context

    engine = _engine()
    tq, bp = att.prefill_tiling(512, 288, 16)
    await engine.start()
    async for _ in engine.generate(
        {"token_ids": [3 + j % 50 for j in range(300)],
         "stop_conditions": {"max_tokens": 2, "ignore_eos": True},
         "sampling": {"temperature": 0.0}},
        Context("walk-300"),
    ):
        pass
    kv = _prefill_kv(engine)
    await engine.close()
    # a tile visits the blocks up to its last real row: 300 tokens' worth,
    # whatever the table's 4,608
    want = sum(
        -(-min(300, (i + 1) * tq) // (bp * 16))
        for i in range(512 // tq) if i * tq < 300
    )
    assert kv == {"blocks_visited.full": want,
                  "blocks_table.full": (512 // tq) * -(-288 // bp)}
    assert 4 * want < kv["blocks_table.full"]  # nowhere near the table
    engine.reset_profile_window()
    assert set(_prefill_kv(engine).values()) == {0}


def test_prefill_kv_by_layer_kind_and_a_pack_runs_to_its_longest_member():
    spec = dataclasses.replace(
        ModelSpec.tiny(), sliding_window=128,
        layer_types=("sliding_attention", "full_attention"),
    )
    engine = _engine(spec)
    assert engine._prefill_walks == {"full": 0, "window": 128}
    tq, bp = att.prefill_tiling(1024, 288, 16)
    _, wbp = att.prefill_tiling(1024, 288, 16, 128)
    tiles = 1024 // tq
    span = bp * 16

    # the second chunk of a prompt of 1,624: rows 1,024..1,623
    engine._count_prefill_kv(1024, 288, 1024, 600)
    full = sum(
        -(-min(1624, 1024 + (i + 1) * tq) // span)
        for i in range(tiles) if i * tq < 600
    )
    live_tiles = -(-600 // tq)
    assert _prefill_kv(engine) == {
        "blocks_visited.full": full,
        "blocks_table.full": tiles * -(-288 // bp),
        # a window layer's tile: the one block its window reaches from a
        # start on a tile's edge
        "blocks_visited.window": live_tiles,
        "blocks_table.window": tiles * -(-288 // wbp),
    }
    engine.reset_profile_window()
    # a pack of 512-row prompts, one of them padding: every member runs a
    # tile to the pack's longest
    engine._count_prefill_kv(512, 288, [0, 0, 0, 0], [512, 40, 300, 0])
    assert _prefill_kv(engine)["blocks_visited.full"] == 4 * sum(
        -(-min(512, (i + 1) * tq) // span) for i in range(512 // tq)
    )
    engine.reset_profile_window()
    # a verify of 9 rows a sequence, one of its two rows padding: one tile,
    # whose window reach (10 pages) is its one block; the tiling follows
    # the table the dispatch hands over, not the configuration's
    engine._count_prefill_kv(9, 96, [700, 0], [9, 0])
    assert _prefill_kv(engine) == {
        "blocks_visited.full": 2 * (708 // span + 1),
        "blocks_table.full": 2 * -(-96 // bp),
        "blocks_visited.window": 2,
        "blocks_table.window": 2 * -(-96 // 10),
    }


async def test_a_speculative_verify_is_counted_as_the_walk_it_runs():
    from dynamo_tpu.runtime.context import Context

    async def served(spec_mode):
        engine = _engine(spec_mode=spec_mode, decode_steps_per_dispatch=2)
        await engine.start()
        async for _ in engine.generate(
            {"token_ids": [3 + j % 12 for j in range(300)],
             "stop_conditions": {"max_tokens": 24, "ignore_eos": True},
             "sampling": {"temperature": 0.0}},
            Context(f"walk-{spec_mode}"),
        ):
            pass
        kv, verifies = _prefill_kv(engine), engine.spec_verifies
        await engine.close()
        return kv, verifies

    (off, none), (on, verifies) = await served("off"), await served("ngram")
    assert none == 0 and verifies > 0
    # each verify: one sequence, one tile of spec_k_max + 1 rows
    _, bp = att.prefill_tiling(9, 288, 16)
    assert on["blocks_table.full"] - off["blocks_table.full"] == (
        verifies * -(-288 // bp)
    )
    # rows at positions 300.. sit in the second block of 256 tokens
    assert on["blocks_visited.full"] - off["blocks_visited.full"] == (
        verifies * 2
    )
