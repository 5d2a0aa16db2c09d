"""JoyAI-LLM-Flash's layer at toy widths on the CPU: latent attention (a
query rank, a latent row shared by the heads, rope on interleaved pairs),
a dense first layer, then held experts behind a sigmoid router over all of
them beside a shared expert. The program (``models/mla.py``, the paged
latent cache, the decode kernel interpreted and the XLA walk) against the
benchmark's plain reference (``perfbench/references/latent_moe.py``), which
shares no code with it."""

import asyncio
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import mla
from dynamo_tpu.models.family import MlaFamily
from dynamo_tpu.ops import attention as attn_ops
from dynamo_tpu.ops.pallas.fused_decode import live_chunks
from dynamo_tpu.ops.pallas.latent_decode import (
    latent_chunk_pages, latent_decode_attention,
)
from dynamo_tpu.runtime.context import Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the reference reads the published keys; the program reads SPEC
CONFIG = {
    "hidden_size": 48, "num_attention_heads": 4, "q_lora_rank": 40,
    "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 12, "rope_theta": 32000000, "rope_interleave": True,
    "first_k_dense_replace": 1, "intermediate_size": 64,
    "moe_intermediate_size": 16, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6, "vocab_size": 96,
    "num_hidden_layers": 3, "torch_dtype": "float32",
    "experts": {"published": 16, "held": 4, "first": 4},
}


def _spec(**kw) -> ModelSpec:
    base = dict(
        name="toy-joyai", vocab_size=96, hidden_size=48, intermediate_size=64,
        num_layers=3, num_heads=4, num_kv_heads=4, head_dim=8,
        rope_theta=32e6, rms_eps=1e-6, dtype="float32", tie_embeddings=False,
        kv_lora_rank=24, q_lora_rank=40, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, rope_interleave=True,
        num_experts=16, held_experts=(4, 4), num_experts_per_token=4,
        moe_intermediate_size=16, moe_scoring="sigmoid", n_group=1,
        topk_group=1, routed_scaling_factor=2.5, n_shared_experts=1,
        first_k_dense=1, nextn_predict_layers=1,
    )
    base.update(kw)
    return ModelSpec(**base)


SPEC = _spec()
PAGE, PAGES_PER_SEQ, T = 4, 16, 40
SEED = 11


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "latent_moe", os.path.join(REPO, "perfbench/references/latent_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(ref):
    params = mla.init_params(SPEC, jax.random.PRNGKey(SEED))
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (3, T), 0, 96))
    want = np.asarray(ref.forward(
        CONFIG, SEED, toks, np.tile(np.arange(T), (3, 1))))
    return params, toks, want


def _cache():
    return (mla.init_cache(SPEC, 1 + 3 * PAGES_PER_SEQ, PAGE),
            mla.init_counts(SPEC))


def _table(row):
    return jnp.arange(PAGES_PER_SEQ, dtype=jnp.int32) + 1 + row * PAGES_PER_SEQ


def _close(got, want, tol=3e-4):
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


def _prefill(params, toks, row, start, n, cache, counts, bucket=16):
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = toks[row, start: start + n]
    return mla.prefill_forward(
        SPEC, params, jnp.asarray(padded), _table(row),
        jnp.asarray(start, jnp.int32), cache, jnp.asarray(n, jnp.int32),
        counts=counts,
    )


@pytest.mark.parametrize("chunks", [
    pytest.param([13], id="single"),
    pytest.param([16, 16, 7], id="three-chunks"),  # start_pos > 0 twice
])
def test_prefill_against_the_reference(model, chunks):
    params, toks, want = model
    cache, counts = _cache()
    start = 0
    for n in chunks:
        logits, cache, counts = _prefill(
            params, toks, 0, start, n, cache, counts)
        start += n
        _close(logits, want[0, start - 1])


def test_packed_prefill_against_the_reference(model):
    params, toks, want = model
    cache, counts = _cache()
    lens = np.asarray([16, 9, 0], np.int32)  # the third member is padding
    padded = np.zeros((3, 16), np.int32)
    for r, n in enumerate(lens):
        padded[r, :n] = toks[r, :n]
    bts = jnp.stack([_table(0), _table(1), jnp.zeros(PAGES_PER_SEQ, jnp.int32)])
    logits, cache, counts = mla.prefill_forward_batch(
        SPEC, params, jnp.asarray(padded), bts, jnp.zeros((3,), jnp.int32),
        cache, jnp.asarray(lens), counts=counts,
    )
    _close(logits[0], want[0, 15])
    _close(logits[1], want[1, 8])
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla-walk", "kernel"])
def test_decode_through_the_paged_latent_cache(model, monkeypatch, pallas):
    """Teacher-forced steps after prefills of 14, 3 and 0 tokens: across a
    page boundary (the kernel's chunk boundaries are
    ``test_latent_kernel_against_the_xla_walk``'s); a slot that starts
    from ONE token in the pool, and an empty slot that stays inactive."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    params, toks, want = model
    cache, counts = _cache()
    lens = [14, 1]
    for r, n in enumerate(lens):
        _, cache, counts = _prefill(params, toks, r, 0, n, cache, counts)
    bts = jnp.stack([_table(0), _table(1), jnp.zeros(PAGES_PER_SEQ, jnp.int32)])
    active = jnp.asarray([True, True, False])
    before = np.asarray(counts)
    for j in range(12):
        fed = jnp.asarray([toks[0, 14 + j], toks[1, 1 + j], 0], jnp.int32)
        seq = jnp.asarray([15 + j, 2 + j, 1], jnp.int32)
        logits, cache, counts = mla.decode_forward(
            SPEC, params, fed, bts, seq, cache, active, counts=counts)
        _close(logits[0], want[0, 14 + j])
        _close(logits[1], want[1, 1 + j])
    # the counters: 12 steps a layer, 2 counted rows x top-4 a step
    grew = np.asarray(counts) - before
    assert (grew[0] == 0).all()  # the dense layer keeps none
    assert (grew[1:, 1, -1] == 12).all() and (grew[1:, 0] == 0).all()
    assert (grew[1:, 1, -3] == 12 * 2 * 4).all()
    assert (grew[1:, 1, :4].sum(axis=1) <= 12 * 2 * 4).all()


@pytest.mark.parametrize("dtype,tol", [
    pytest.param(jnp.float32, 2e-5, id="float32"),
    pytest.param(jnp.bfloat16, 3e-2, id="bfloat16"),
])
@pytest.mark.parametrize("chunk", [2, 3, None], ids=["chunk2", "chunk3", "one"])
def test_latent_kernel_against_the_xla_walk(dtype, tol, chunk):
    """The kernel interpreted: an empty slot, one token in the pool, a page
    boundary, chunk boundaries (a table that a 3-page chunk does not
    divide), a full table; the new row lands, the trash page is not
    written, no other page changes."""
    B, H, dc, dr, page, P, L = 6, 4, 16, 8, 4, 8, 2
    D = dc + dr
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    pool = jax.random.normal(ks[0], (L, 1 + B * P, page, D)).astype(dtype)
    q = (jax.random.normal(ks[1], (B, H, D)) * 0.3).astype(dtype)
    new = jax.random.normal(ks[2], (B, D)).astype(dtype)
    bt = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    seq = np.asarray([1, 2, 5, 9, 23, 32], np.int32)
    dst_page = bt[np.arange(B), (seq - 1) // page]
    dst_page[0] = 0  # an inactive slot writes nowhere
    dst_off = (seq - 1) % page
    want = attn_ops.paged_latent_decode_attention(
        q, pool, 1, new, jnp.asarray(bt), jnp.asarray(seq), dc=dc)
    got, pool2 = latent_decode_attention(
        q, jnp.array(pool), new, jnp.asarray(bt), jnp.asarray(seq),
        jnp.asarray(dst_page), jnp.asarray(dst_off), layer=1, dc=dc,
        interpret=True, chunk_override=chunk,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol)
    expect = np.array(pool)
    for b in range(1, B):
        expect[1, dst_page[b], dst_off[b]] = np.asarray(new[b])
    np.testing.assert_array_equal(np.asarray(pool2), expect)


def test_interleaved_rope_is_a_permutation_of_the_same_weights(ref):
    """``rope_interleave`` draws the SAME published matrices and permutes
    their rope columns; with it off the program rotates half-split pairs of
    them, which the reference follows too (its ``interleave`` off)."""
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0, 96))
    at = np.arange(12)[None]
    for flag in (True, False):
        spec = _spec(rope_interleave=flag)
        params = mla.init_params(spec, jax.random.PRNGKey(SEED))
        got = mla.reference_forward(spec, params, jnp.asarray(toks[0]))
        want = ref.forward(dict(CONFIG, rope_interleave=flag), SEED, toks, at)
        _close(got, np.asarray(want)[0])
    a = mla.init_params(_spec(rope_interleave=True), jax.random.PRNGKey(SEED))
    b = mla.init_params(_spec(rope_interleave=False), jax.random.PRNGKey(SEED))
    wa, wb = (np.asarray(p["layers"][0]["w_kv_a"]) for p in (a, b))
    np.testing.assert_array_equal(wa[:, :24], wb[:, :24])
    np.testing.assert_array_equal(wa[:, 24:28], wb[:, 24::2])  # pairs' firsts
    assert not np.array_equal(wa, wb)


def test_the_shares_add_up(ref):
    """Four chips of four experts each, the shared expert counted once,
    make the uncut layer: the sum over the shares of what each adds to the
    residual, less the surplus copies of what every share computes alike
    (attention and the shared expert), is the layer with all 16 experts."""
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 10), 0, 96))
    cfg = dict(CONFIG, num_hidden_layers=2)
    w = ref.Weights(cfg, SEED)
    x = ref._embed_rows(w.embed(), toks, quant=None)
    x = ref._layer(w.m, 0, x, w.layer(0), None)  # the dense layer

    def layer1(held, first):
        c = dict(cfg, experts={"published": 16, "held": held, "first": first})
        wc = ref.Weights(c, SEED)
        lw = wc.layer(1)
        if held < 16:  # a share's experts are the uncut layer's own
            full = ref.Weights(
                dict(cfg, experts={"published": 16, "held": 16, "first": 0}),
                SEED).layer(1)
            for k in ("e_gate", "e_up", "e_down"):
                lw[k] = full[k][first: first + held]
        return np.asarray(ref._layer(wc.m, 1, x, lw, None)), wc, lw

    whole, wc, lw = layer1(16, 0)
    # what every share computes alike: attention, then the shared expert
    m = wc.m
    after_attn = ref._attention(
        x, {k: lw[k] for k in ref.ATTN}, heads=m["nh"], dc=m["dc"],
        dn=m["dn"], dr=m["dr"], dv=m["dv"], theta=m["theta"],
        interleave=m["interleave"], eps=m["eps"], quant=None)
    alike = np.asarray(ref._shared(
        after_attn, ref._rms(after_attn, m["eps"]),
        {k: lw[k] for k in ref.SHARED}, quant=None))
    shares = [layer1(4, first)[0] for first in (0, 4, 8, 12)]
    routed = sum(s - alike for s in shares)
    _close(alike + routed, whole, tol=1e-4)
    # and the program's share is the reference's share
    spec = _spec(num_layers=2)
    params = mla.init_params(spec, jax.random.PRNGKey(SEED))
    got = mla.reference_forward(spec, params, jnp.asarray(toks[0]))
    want = ref.forward(cfg, SEED, toks, np.arange(10)[None].repeat(2, 0))
    _close(got, np.asarray(want)[0])


def test_memory_does_not_follow_the_table():
    """No latent program holds a whole-table gather or a ``[.., max_ctx]``
    score: compiled for tables of 80 and 320 pages (both wider than the walk's
    block of 64) over one pool, decode
    and prefill hold the same temporaries within 10%."""
    params = jax.eval_shape(
        lambda: mla.init_params(SPEC, jax.random.PRNGKey(0)))

    def temp_bytes(pages_per_seq):
        cache = jax.ShapeDtypeStruct(
            (SPEC.num_layers, 1 + 4 * 320, PAGE, 32), jnp.float32)
        i32 = jnp.int32
        bts = jax.ShapeDtypeStruct((4, pages_per_seq), i32)
        dec = mla.decode_forward.lower(
            SPEC, params, jax.ShapeDtypeStruct((4,), i32), bts,
            jax.ShapeDtypeStruct((4,), i32), cache,
            jax.ShapeDtypeStruct((4,), bool),
        ).compile().memory_analysis().temp_size_in_bytes
        pre = mla.prefill_forward.lower(
            SPEC, params, jax.ShapeDtypeStruct((32,), i32),
            jax.ShapeDtypeStruct((pages_per_seq,), i32),
            jax.ShapeDtypeStruct((), i32), cache,
            jax.ShapeDtypeStruct((), i32),
        ).compile().memory_analysis().temp_size_in_bytes
        return dec, pre

    (d0, p0), (d1, p1) = temp_bytes(80), temp_bytes(320)
    assert abs(d1 - d0) <= 0.1 * d0, (d0, d1)
    assert abs(p1 - p0) <= 0.1 * p0, (p0, p1)


async def test_serves_through_the_engine_and_counts(monkeypatch):
    """The toy model through the REAL engine (scheduler, chunked prefill
    over the latent cache, the kernel interpreted in pipelined bursts): the
    greedy stream is the reference's own, and ``decode_kv``,
    ``prefill_kv.*.latent`` and ``moe_counters()`` read what hand
    arithmetic gives."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    engine = InferenceEngine(SPEC, EngineConfig(
        page_size=PAGE, num_pages=64, max_pages_per_seq=PAGES_PER_SEQ,
        max_decode_slots=2, prefill_buckets=(16,), max_prefill_chunk_tokens=16,
        decode_steps_per_dispatch=4, seed=SEED,
    ))
    assert isinstance(engine.fam, MlaFamily)
    prompt = [int(t) for t in np.arange(7, 7 + 21) % 96]  # two chunks
    out = []
    async for item in engine.generate(
        {"token_ids": prompt, "sampling": {"temperature": 0.0},
         "stop_conditions": {"max_tokens": 6, "ignore_eos": True}},
        Context(),
    ):
        assert item.get("finish_reason") != "error", item
        out.extend(item.get("token_ids") or [])
    assert len(out) == 6
    seq = list(prompt)
    for _ in range(6):
        padded = np.zeros((32,), np.int32)
        padded[: len(seq)] = seq
        lg = _jit_reference(SPEC, engine.params, jnp.asarray(padded))
        seq.append(int(np.argmax(np.asarray(lg[len(seq) - 1]))))
    assert out == seq[len(prompt):]

    # prefill: chunks of 16 and 5 rows; one tile of all 16 rows, blocks of
    # 16 pages (a 64-token table is one block): 1 block visited a chunk
    assert engine.prefill_kv["blocks_visited.latent"] == 2
    assert engine.prefill_kv["blocks_table.latent"] == 2
    # decode: 5 model steps served (the first token came from prefill) in
    # bursts of 4; every dispatched burst is counted over its 4 steps
    kv = engine.decode_kv
    chunk = latent_chunk_pages(engine.k_pages, PAGES_PER_SEQ)
    assert chunk == PAGES_PER_SEQ  # a 16-page table is one chunk
    assert kv["pages_fetched"] % chunk == 0 and kv["pages_fetched"] > 0
    assert kv["pages_table"] % (2 * PAGES_PER_SEQ * 4) == 0
    assert 0 < kv["pages_live"] <= kv["pages_fetched"] <= kv["pages_table"]
    first, count = live_chunks(np.asarray([22, 1]), PAGE, chunk)
    assert list(count) == [1, 0]  # an empty slot fetches nothing
    await engine.close()
    engine._metrics_publishes = 0
    for _ in range(34):  # two refreshes bring the device's counters over
        engine._publish_metrics()
    c = engine.moe_counters()
    assert c["layers"] == 2  # the dense first layer keeps none
    assert c["prefill.steps"] == 2 and c["prefill.assignments"] == 2 * 21 * 4
    assert c["decode.assignments"] >= 2 * 5 * 4
    assert sum(c[f"decode.expert.{i}"] for i in range(4)) <= c[
        "decode.assignments"]


async def _greedy(engine, prompt, n, out=None):
    out = [] if out is None else out
    async for item in engine.generate(
        {"token_ids": list(prompt), "sampling": {"temperature": 0.0},
         "stop_conditions": {"max_tokens": n, "ignore_eos": True}},
        Context(),
    ):
        assert item.get("finish_reason") != "error", item
        out.extend(item.get("token_ids") or [])
    return out


async def test_a_chunked_prompt_behind_running_bursts(monkeypatch):
    """Chunked under load: two streams decode in pipelined bursts through
    the kernel while a 37-token prompt prefills in chunks of 16. The
    chunks at ``start_pos`` 16 and 32 walk latents an earlier chunk wrote,
    each launched behind the burst in flight (no flush lands it first),
    and the prompt's tokens are those it gets alone and unchunked."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    prompt = [int(t) for t in np.arange(5, 5 + 37) * 7 % 96]

    def build(**kw):
        return InferenceEngine(SPEC, EngineConfig(
            page_size=PAGE, num_pages=64, max_pages_per_seq=PAGES_PER_SEQ,
            max_decode_slots=3, decode_steps_per_dispatch=4, seed=SEED, **kw))

    alone = build(prefill_buckets=(64,), max_prefill_chunk_tokens=64)
    want = await _greedy(alone, prompt, 6)
    assert alone.chunked_prefill["chunks"] == 0
    await alone.close()

    engine = build(prefill_buckets=(16,), max_prefill_chunk_tokens=16,
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
        return await _greedy(engine, prompt, 6)

    outs = await asyncio.gather(
        _greedy(engine, [3, 9, 27], 40, out=a),
        _greedy(engine, [8, 64, 32, 5], 40, out=b), later())
    assert outs[2] == want and [len(o) for o in outs[:2]] == [40, 40]
    assert chunks == [(0, 1), (16, 1), (32, 1)]
    assert engine.chunked_prefill == {"chunks": 3, "chunks_behind_burst": 3}
    assert engine.allocator.active_pages == 0
    await engine.close()


_jit_reference = jax.jit(mla.reference_forward, static_argnums=0)
