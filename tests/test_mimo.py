"""MiMo-V2.5's layer at toy widths on the CPU: window and global layers
with their own KV heads and rope bases, K wider than V, sinks on the window
layers only, a value scale, rotary on the leading dims, a dense first layer
and held experts behind a router over all of them. Against the benchmark's
plain reference (``perfbench/references/hybrid_swa_moe.py``), which shares
no code with the program."""

import asyncio
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, LayerKind, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import llama
from dynamo_tpu.ops.attention import (
    window_table,
    decode_update_attention,
    paged_decode_attention,
)
from dynamo_tpu.ops.pallas.fused_decode import fused_decode_attention
from dynamo_tpu.runtime.context import Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reference reads the published keys; the program reads SPEC
CONFIG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 1,
    "swa_num_key_value_heads": 2, "head_dim": 12, "v_head_dim": 8,
    "partial_rotary_factor": 0.334, "rope_theta": 1e7, "swa_rope_theta": 1e4,
    "attention_value_scale": 0.707, "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "sliding_window": 8,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": None, "layernorm_epsilon": 1e-5,
    "vocab_size": 96, "num_hidden_layers": 4, "torch_dtype": "float32",
}
SPEC = ModelSpec(
    name="toy-mimo", vocab_size=96, hidden_size=32, intermediate_size=48,
    num_layers=4, num_heads=4, num_kv_heads=1, head_dim=12, v_head_dim=8,
    rotary_dim=4, value_scale=0.707, dtype="float32", tie_embeddings=False,
    num_experts=8, num_experts_per_token=2, moe_intermediate_size=16,
    moe_scoring="sigmoid", first_k_dense=1,
    layer_kinds=(LayerKind(1, 1e7), LayerKind(2, 1e4, window=8, sinks=True)),
    layer_pattern=(0, 1, 1, 0),
)
PAGE, PAGES_PER_SEQ = 4, 12
SEED = 5


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "hybrid_swa_moe",
        os.path.join(REPO, "perfbench/references/hybrid_swa_moe.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(ref):
    params = llama.init_params(SPEC, jax.random.PRNGKey(SEED))
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 96))
    want = np.asarray(ref.forward(
        CONFIG, SEED, toks, np.tile(np.arange(40), (2, 1))))
    return params, toks, want


def _cache():
    return llama.init_cache(SPEC, 1 + 3 * PAGES_PER_SEQ, PAGE)


def _table(row):
    return jnp.arange(PAGES_PER_SEQ, dtype=jnp.int32) + 1 + row * PAGES_PER_SEQ


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_prefill_then_decode_through_the_paged_cache(model):
    params, toks, want = model
    k, v = _cache()
    T = 30  # longer than the window of 8
    pad = jnp.zeros((32,), jnp.int32).at[:T].set(toks[0, :T])
    logits, k, v, zero = llama.prefill_forward(
        SPEC, params, pad, _table(0), jnp.asarray(0), k, v, jnp.asarray(T))
    _close(logits, want[0, T - 1])
    assert int(zero) == 0
    tables = jnp.zeros((2, PAGES_PER_SEQ), jnp.int32).at[0].set(_table(0))
    for j in range(6):  # crosses a page
        logits, k, v = llama.decode_forward(
            SPEC, params, jnp.asarray([toks[0, T + j], 0]), tables,
            jnp.asarray([T + j + 1, 1]), k, v, jnp.asarray([True, False]))
        _close(logits[0], want[0, T + j])
    # the counters: 3 expert layers, a prefill program and six steps
    counts = np.asarray(k.counts)
    assert counts[0].sum() == 0  # layer 0 is dense
    assert counts[1:, llama.COUNT_PREFILL, -1].tolist() == [1, 1, 1]
    assert counts[1:, llama.COUNT_DECODE, -1].tolist() == [6, 6, 6]
    assert counts[1:, llama.COUNT_PREFILL, -3].tolist() == [T * 2] * 3
    assert counts[1:, llama.COUNT_DECODE, -3].tolist() == [6 * 2] * 3
    # every expert is held: every assignment reached one
    assert (counts[1:, :, :-3].sum(-1) == counts[1:, :, -3]).all()
    # a decode step of one token touches its two experts, no more
    assert counts[1:, llama.COUNT_DECODE, -2].tolist() == [6 * 2] * 3


def test_a_prompt_longer_than_a_chunk_and_than_the_window(model):
    params, toks, want = model
    k, v = _cache()
    _, k, v, _ = llama.prefill_forward(
        SPEC, params, jnp.asarray(toks[0, :16]), _table(0), jnp.asarray(0),
        k, v, jnp.asarray(16))
    pad = jnp.zeros((16,), jnp.int32).at[:14].set(toks[0, 16:30])
    logits, k, v, _ = llama.prefill_forward(
        SPEC, params, pad, _table(0), jnp.asarray(16), k, v, jnp.asarray(14))
    _close(logits, want[0, 29])


def test_packed_prefill(model):
    params, toks, want = model
    k, v = _cache()
    lens = [30, 19]
    batch = jnp.zeros((2, 32), jnp.int32)
    for i, n in enumerate(lens):
        batch = batch.at[i, :n].set(toks[i, :n])
    logits, k, v, _ = llama.prefill_forward_batch(
        SPEC, params, batch, jnp.stack([_table(0), _table(1)]),
        jnp.zeros((2,), jnp.int32), k, v, jnp.asarray(lens))
    for i, n in enumerate(lens):
        _close(logits[i], want[i, n - 1])


def test_verify_scores_every_position(model):
    params, toks, want = model
    k, v = _cache()
    T, W = 21, 4  # the verify window starts mid-page
    pad = jnp.zeros((32,), jnp.int32).at[:T].set(toks[0, :T])
    _, k, v, _ = llama.prefill_forward(
        SPEC, params, pad, _table(0), jnp.asarray(0), k, v, jnp.asarray(T))
    targets, k, v, _ = llama.verify_forward(
        SPEC, params, jnp.asarray(toks[:1, T: T + W]), _table(0)[None],
        jnp.asarray([T]), k, v, jnp.asarray([W]))
    assert np.asarray(targets)[0].tolist() == want[
        0, T: T + W].argmax(-1).tolist()


async def test_the_engine_serves_it_chunked_and_counts(model):
    _, toks, want = model
    cfg = EngineConfig(
        page_size=PAGE, num_pages=64, max_pages_per_seq=PAGES_PER_SEQ,
        max_decode_slots=2, prefill_buckets=(8, 16), prefill_pack_size=2,
        max_prefill_chunk_tokens=16, decode_steps_per_dispatch=4,
        seed=SEED, guided_mode="off",
    )
    engine = InferenceEngine(SPEC, cfg)
    prompt = [int(t) for t in toks[0, :30]]  # two chunks, four windows
    req = {"token_ids": prompt, "stop_conditions": {"max_tokens": 1},
           "sampling_options": {"temperature": 0.0}}
    out = []
    async for item in engine.generate(req, Context()):
        out.extend(item.get("token_ids", []))
    assert out == [int(want[0, 29].argmax())]
    for _ in range(40):  # the counters come to the host on a duty cycle
        engine._publish_metrics()
    counters = engine.moe_counters()
    assert counters["layers"] == 3
    assert counters["prefill.assignments"] >= 3 * 30 * 2
    assert sum(counters[f"prefill.expert.{i}"] for i in range(8)) == (
        counters["prefill.assignments"])
    snap = engine.profile_snapshot()
    assert snap["moe.prefill.steps"]["calls"] == counters["prefill.steps"]
    assert 0 < counters["prefill.experts_touched"] <= (
        3 * 8 * counters["prefill.steps"])
    await engine.close()


async def _greedy(engine, prompt, n, out=None):
    out = [] if out is None else out
    req = {"token_ids": [int(t) for t in prompt],
           "stop_conditions": {"max_tokens": n, "ignore_eos": True},
           "sampling": {"temperature": 0.0}}
    async for item in engine.generate(req, Context()):
        assert item.get("finish_reason") != "error", item
        out.extend(item.get("token_ids", []))
    return out


async def test_a_chunked_prompt_behind_running_bursts(model):
    """Chunked under load: two streams decode in pipelined bursts while a
    30-token prompt prefills in chunks of 8, a window's length, through
    the window pool and the full pool. Every chunk after the first is
    launched behind the burst in flight (no flush lands it first); the
    first token is the reference's and all six are those the prompt gets
    alone and unchunked."""
    _, toks, want = model

    def build(**kw):
        return InferenceEngine(SPEC, EngineConfig(
            page_size=PAGE, num_pages=64, max_pages_per_seq=PAGES_PER_SEQ,
            max_decode_slots=3, decode_steps_per_dispatch=4, seed=SEED,
            guided_mode="off", **kw))

    alone = build(prefill_buckets=(32,), max_prefill_chunk_tokens=32)
    unchunked = await _greedy(alone, toks[0, :30], 6)
    assert unchunked[0] == int(want[0, 29].argmax())
    await alone.close()

    engine = build(prefill_buckets=(8,), max_prefill_chunk_tokens=8,
                   pipeline_decode=True)
    chunks, run_chunk = [], engine._run_partial_chunk

    def watched(waiting, sp, token_ids, start, end):
        chunks.append((start, len(engine._pipeline)))
        return run_chunk(waiting, sp, token_ids, start, end)

    engine._run_partial_chunk = watched
    a, b = [], []

    async def later():
        while min(len(a), len(b)) < 4:
            await asyncio.sleep(0.002)
        return await _greedy(engine, toks[0, :30], 6)

    outs = await asyncio.gather(
        _greedy(engine, toks[1, :5], 36, out=a),
        _greedy(engine, toks[1, 7:11], 36, out=b), later())
    assert outs[2] == unchunked and [len(o) for o in outs[:2]] == [36, 36]
    assert chunks == [(0, 1), (8, 1), (16, 1), (24, 1)]
    assert engine.chunked_prefill == {"chunks": 4, "chunks_behind_burst": 4}
    assert engine.allocator.active_pages == 0
    await engine.close()


def test_pages_move_by_kind(model):
    """extract / insert carry one block a kind and leave counters be."""
    params, toks, _ = model
    k, v = _cache()
    pad = jnp.zeros((32,), jnp.int32).at[:30].set(toks[0, :30])
    _, k, v, _ = llama.prefill_forward(
        SPEC, params, pad, _table(0), jnp.asarray(0), k, v, jnp.asarray(30))
    ids = jnp.asarray([1, 2, 3])
    kb, vb = llama.extract_kv_pages(k, v, ids)
    assert [b.shape for b in kb] == [(2, 3, 1, 4, 12), (2, 3, 2, 4, 12)]
    assert [b.shape for b in vb] == [(2, 3, 1, 4, 8), (2, 3, 2, 4, 8)]
    k2, v2 = _cache()
    k2, v2 = llama.insert_kv_pages(k2, v2, ids, kb, vb)
    for a, b in zip(k.pools + v.pools, k2.pools + v2.pools):
        np.testing.assert_array_equal(np.asarray(a[:, 1:4]),
                                      np.asarray(b[:, 1:4]))


def test_a_spec_from_json_hashes_and_says_no_to_a_short_pattern():
    kw = dict(
        num_layers=3, layer_kinds=[{"num_kv_heads": 2, "rope_theta": 1e4},
                                   {"num_kv_heads": 1, "rope_theta": 1e6,
                                    "window": 4}],
        layer_pattern=[0, 1, 0], held_experts=[2, 2], num_experts=8,
        layer_types=["full_attention"] * 3,
    )
    spec = ModelSpec(**kw)
    hash(spec)
    assert spec.kind(1).window == 4 and spec.kind(2).num_kv_heads == 2
    assert [spec.pool_slot(i) for i in range(3)] == [(0, 0), (1, 0), (0, 1)]
    assert spec.experts_here == (2, 2)
    assert ModelSpec(num_experts=8).experts_here == (8, 0)
    with pytest.raises(ValueError):
        ModelSpec(**dict(kw, layer_pattern=[0, 1]))
    with pytest.raises(ValueError):
        ModelSpec(**dict(kw, held_experts=[4, 6]))
    # the gpt-oss shorthand resolves to kinds too
    oss = ModelSpec.tiny_gpt_oss()
    assert (oss.kind(0).window, oss.kind(1).window) == (8, 0)
    assert oss.kind(1).sinks and not ModelSpec.tiny().has_attn_extras


# ----------------------------------------- the decode kernel, interpreted


def _decode_case(seed, *, B=3, H=4, KH=2, D=12, Dv=8, page=4, P=12, L=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n_pages = 1 + B * P
    return dict(
        q=jax.random.normal(ks[0], (B, H, D), jnp.float32),
        k_pages=jax.random.normal(ks[1], (L, n_pages, KH, page, D)),
        v_pages=jax.random.normal(ks[2], (L, n_pages, KH, page, Dv)),
        k_new=jax.random.normal(ks[3], (B, KH, D)),
        v_new=jax.random.normal(ks[4], (B, KH, Dv)),
        tables=1 + jnp.arange(B * P, dtype=jnp.int32).reshape(B, P),
        seq_lens=jnp.asarray([P * page, 9, 1], jnp.int32),
        sinks=jax.random.normal(ks[5], (H,), jnp.float32),
    )


@pytest.mark.parametrize("window,sinks,chunk_pages", [
    (0, False, None), (0, False, 4), (8, True, None), (8, True, 2),
    (0, True, 4), (8, False, 4),
])
def test_fused_decode_with_k_wider_than_v(window, sinks, chunk_pages):
    """Dk != Dv, with and without window and sinks, in one chunk and in
    several (a chunk a short sequence does not reach is skipped whole)."""
    c = _decode_case(3)
    page = c["k_pages"].shape[3]
    pos = c["seq_lens"] - 1
    dst_page = jnp.take_along_axis(
        c["tables"], (pos // page)[:, None], axis=1)[:, 0]
    dst_off = pos % page
    sink = c["sinks"] if sinks else None
    # the reference first (the kernel takes the pools): write the rows,
    # then plain gather attention
    k_ref = c["k_pages"].at[1, dst_page, :, dst_off].set(c["k_new"])
    v_ref = c["v_pages"].at[1, dst_page, :, dst_off].set(c["v_new"])
    want = paged_decode_attention(
        c["q"], k_ref[1], v_ref[1], c["tables"], c["seq_lens"],
        window=window, sinks=sink,
    )
    attn, kp, vp = fused_decode_attention(
        c["q"], c["k_pages"], c["v_pages"], c["k_new"], c["v_new"],
        c["tables"], c["seq_lens"], dst_page, dst_off, layer=1,
        window=window, sinks=sink, interpret=True,
        window_pages_override=chunk_pages, scope="attn_window",
    )
    assert attn.shape == (3, 4, 8)
    np.testing.assert_allclose(
        np.asarray(attn), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(v_ref))


@pytest.mark.parametrize("pallas", ["0", "1"])
def test_a_window_layer_is_handed_its_windows_pages(monkeypatch, pallas):
    """decode_update_attention trims a window layer's table to the pages
    its window reaches: same numbers as the whole table, either path."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    c = _decode_case(4)
    page, window = 4, 8
    tables, offset = jax.vmap(
        lambda bt, n: window_table(bt, n - 1, 1, window, page)
    )(c["tables"], c["seq_lens"])
    assert tables.shape == (3, 3)  # 8 tokens touch at most 3 pages of 4
    # 48 tokens -> its last two pages' worth
    assert (c["seq_lens"] - offset).tolist() == [8, 9, 1]
    pos = c["seq_lens"] - 1
    dst_page = jnp.take_along_axis(
        c["tables"], (pos // page)[:, None], axis=1)[:, 0]
    k_ref = c["k_pages"].at[0, dst_page, :, pos % page].set(c["k_new"])
    v_ref = c["v_pages"].at[0, dst_page, :, pos % page].set(c["v_new"])
    want = paged_decode_attention(
        c["q"], k_ref[0], v_ref[0], c["tables"], c["seq_lens"],
        window=window, sinks=c["sinks"],
    )
    attn, kp, vp = decode_update_attention(
        c["q"], c["k_pages"], c["v_pages"], c["k_new"], c["v_new"],
        c["tables"], c["seq_lens"], dst_page, pos % page, layer=0,
        window=window, sinks=c["sinks"],
    )
    np.testing.assert_allclose(
        np.asarray(attn), np.asarray(want), rtol=2e-5, atol=2e-5)
