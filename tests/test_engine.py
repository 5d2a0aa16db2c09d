"""JAX engine tests (CPU mesh): paged-attention numerics vs the non-paged
reference, continuous batching, prefix cache, sampling, TP sharding."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.cache import OutOfPages, PageAllocator
from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.engine.sampling import sample_tokens
from dynamo_tpu.models import llama
from dynamo_tpu.ops.attention import paged_decode_attention
from dynamo_tpu.parallel.mesh import make_mesh
from dynamo_tpu.runtime.context import Context

pytestmark = pytest.mark.unit

SPEC = ModelSpec(
    vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
)


def small_config(**kw):
    defaults = dict(
        page_size=4, num_pages=64, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(8, 16, 32, 64),
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


# ------------------------------------------------------ numerics: vs reference


@pytest.mark.parametrize("spec", [
    SPEC, ModelSpec.tiny_moe(), ModelSpec.tiny_solar(),
    ModelSpec.tiny_falcon_h1(),
], ids=["dense", "moe", "recurrent", "parallel-ssm"])
def test_the_engines_weights_are_the_familys_draw(spec):
    """The engine draws random weights as one program; tensor for tensor
    they are what ``init_params`` gives op by op (the benchmark's plain
    references draw their own copy from the same recipe)."""
    engine = InferenceEngine(spec, small_config(seed=5))
    want = llama.init_params(spec, jax.random.PRNGKey(5))
    same = jax.tree.map(
        lambda a, b: a.dtype == b.dtype and bool(jnp.array_equal(a, b)),
        engine.params, want)
    assert all(jax.tree.leaves(same))


def test_prefill_matches_reference_forward():
    """Paged prefill logits == plain full-attention forward logits."""
    key = jax.random.PRNGKey(0)
    params = llama.init_params(SPEC, key)
    cfg = small_config()
    k_pages, v_pages = llama.init_cache(SPEC, cfg.num_pages + 1, cfg.page_size)

    tokens = np.array([5, 17, 3, 42, 8, 9, 23], np.int32)  # 7 tokens
    ref_logits = llama.reference_forward(SPEC, params, jnp.asarray(tokens))

    padded = np.zeros((16,), np.int32)
    padded[: len(tokens)] = tokens
    block_table = np.zeros((cfg.max_pages_per_seq,), np.int32)
    block_table[:2] = [1, 2]  # 7 tokens -> 2 pages of 4

    logits, k_pages, v_pages, _d = llama.prefill_forward(
        SPEC, params, jnp.asarray(padded), jnp.asarray(block_table),
        jnp.asarray(0, jnp.int32), k_pages, v_pages,
        jnp.asarray(len(tokens), jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits[-1]), rtol=2e-4, atol=2e-4
    )


def test_decode_matches_reference_forward():
    """Prefill N tokens then decode one: logits == reference at position N."""
    key = jax.random.PRNGKey(1)
    params = llama.init_params(SPEC, key)
    cfg = small_config()
    k_pages, v_pages = llama.init_cache(SPEC, cfg.num_pages + 1, cfg.page_size)

    tokens = np.array([5, 17, 3, 42, 8], np.int32)
    next_tok = 33
    full = np.concatenate([tokens, [next_tok]]).astype(np.int32)
    ref_logits = llama.reference_forward(SPEC, params, jnp.asarray(full))

    padded = np.zeros((8,), np.int32)
    padded[: len(tokens)] = tokens
    block_table = np.zeros((cfg.max_pages_per_seq,), np.int32)
    block_table[:2] = [1, 2]
    _, k_pages, v_pages, _d = llama.prefill_forward(
        SPEC, params, jnp.asarray(padded), jnp.asarray(block_table),
        jnp.asarray(0, jnp.int32), k_pages, v_pages,
        jnp.asarray(len(tokens), jnp.int32),
    )

    B = 4
    btabs = np.zeros((B, cfg.max_pages_per_seq), np.int32)
    btabs[0] = block_table
    toks = np.zeros((B,), np.int32)
    toks[0] = next_tok
    seq_lens = np.ones((B,), np.int32)
    seq_lens[0] = len(tokens) + 1
    active = np.zeros((B,), bool)
    active[0] = True

    logits, k_pages, v_pages = llama.decode_forward(
        SPEC, params, jnp.asarray(toks), jnp.asarray(btabs),
        jnp.asarray(seq_lens), k_pages, v_pages, jnp.asarray(active),
    )
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(ref_logits[-1]), rtol=2e-4, atol=2e-4
    )


def test_paged_decode_attention_ignores_other_pages():
    """A sequence's attention must only read its own pages."""
    kvh, d, ps = 2, 8, 4
    key = jax.random.PRNGKey(2)
    k_pages = jax.random.normal(key, (16, kvh, ps, d))
    v_pages = jax.random.normal(jax.random.fold_in(key, 1), (16, kvh, ps, d))
    q = jax.random.normal(jax.random.fold_in(key, 2), (1, 4, d))

    bt = np.zeros((1, 4), np.int32)
    bt[0, 0] = 3
    out1 = paged_decode_attention(q, k_pages, v_pages, jnp.asarray(bt), jnp.asarray([3]))
    # trash other pages; result must not change
    k2 = k_pages.at[5].set(999.0)
    v2 = v_pages.at[5].set(999.0)
    out2 = paged_decode_attention(q, k2, v2, jnp.asarray(bt), jnp.asarray([3]))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


# ------------------------------------------------------------------- sampling


def _sample(logits, temps, topk, topp, seeds, steps):
    return sample_tokens(
        logits, jnp.asarray(temps, jnp.float32), jnp.asarray(topk, jnp.int32),
        jnp.asarray(topp, jnp.float32), jnp.asarray(seeds, jnp.uint32),
        jnp.asarray(steps, jnp.int32),
    )


def test_sample_tokens_greedy_and_temperature():
    logits = jnp.asarray(
        [[0.0, 5.0, 1.0, 0.0], [0.0, 0.0, 0.0, 10.0]], jnp.float32
    )
    out = _sample(logits, [0.0, 0.0], [0, 0], [1.0, 1.0], [0, 0], [0, 0])
    assert list(np.asarray(out)) == [1, 3]

    # temperature sampling with top_k=1 is still deterministic argmax
    out = _sample(logits, [1.0, 1.0], [1, 1], [1.0, 1.0], [0, 0], [0, 0])
    assert list(np.asarray(out)) == [1, 3]

    # high temperature over uniform-ish logits: varying seed/step spreads
    logits2 = jnp.zeros((1, 4), jnp.float32)
    seen = set()
    for i in range(20):
        out = _sample(logits2, [5.0], [0], [1.0], [i], [i])
        seen.add(int(np.asarray(out)[0]))
    assert len(seen) > 1

    # same seed + same step -> identical draw (per-request reproducibility)
    a = _sample(logits2, [1.0], [0], [1.0], [42], [7])
    b = _sample(logits2, [1.0], [0], [1.0], [42], [7])
    assert int(np.asarray(a)[0]) == int(np.asarray(b)[0])


def test_sample_top_p_masks_tail():
    # one dominant token (p=0.9) -> top_p=0.5 keeps only it
    logits = jnp.log(jnp.asarray([[0.9, 0.04, 0.03, 0.03]], jnp.float32))
    for i in range(10):
        out = _sample(logits, [1.0], [0], [0.5], [i], [i])
        assert int(np.asarray(out)[0]) == 0


# ------------------------------------------------------------- page allocator


def test_page_allocator_prefix_cache_and_eviction():
    stored, evicted = [], []
    alloc = PageAllocator(
        8, 4,
        on_store=lambda sh, p: stored.append(sh),
        on_evict=lambda shs: evicted.extend(shs),
    )
    # 7 usable pages (page 0 reserved)
    pages = [alloc.alloc_page() for _ in range(3)]
    assert 0 not in pages
    alloc.seal_page(pages[0], 100, 0)
    alloc.seal_page(pages[1], 200, 100)
    assert stored == [100, 200]

    alloc.release(pages)
    # hashed pages cached, unhashed page freed
    assert alloc.evictable_pages == 2
    assert alloc.free_pages == 7 - 2

    assert alloc.match_prefix([100, 200, 300]) == [pages[0], pages[1]]
    taken = alloc.take_prefix([100, 200])
    assert taken == [pages[0], pages[1]]
    assert alloc.evictable_pages == 0

    # exhaust the pool; eviction must NOT touch referenced pages
    got = [alloc.alloc_page() for _ in range(5)]
    with pytest.raises(OutOfPages):
        alloc.alloc_page()
    alloc.release(taken)  # 100, 200 become evictable again
    p = alloc.alloc_page()  # evicts LRU (page of hash 100)
    assert 100 in evicted
    alloc.release(got + [p])


# ----------------------------------------------------------- engine end-to-end


def _tokens(items):
    return [t for x in items for t in x["token_ids"]]


async def test_engine_generates_stream(decode_schedule):
    eng = InferenceEngine(SPEC, small_config(**decode_schedule))
    req = {
        "token_ids": [5, 6, 7, 8, 9],
        "sampling": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": 6, "ignore_eos": True},
    }
    out = [x async for x in eng.generate(req, Context())]
    toks = _tokens(out)
    assert len(toks) == 6
    assert out[-1]["finish_reason"] == "length"
    assert all(0 <= t < SPEC.vocab_size for t in toks)
    # deterministic under greedy: same request -> same tokens
    out2 = [x async for x in eng.generate(req, Context())]
    assert _tokens(out2) == toks
    await eng.close()


async def test_engine_concurrent_requests_and_prefix_cache(decode_schedule):
    events = []

    class _Pub:
        def block_stored(self, sh, parent):
            events.append(("store", sh))

        def blocks_removed(self, shs):
            events.extend(("evict", sh) for sh in shs)

    eng = InferenceEngine(
        SPEC, small_config(**decode_schedule), event_publisher=_Pub()
    )
    prompt = list(range(10, 26))  # 16 tokens = 4 pages

    async def run(suffix):
        req = {
            "token_ids": prompt + suffix,
            "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
        }
        return [x async for x in eng.generate(req, Context())]

    results = await asyncio.gather(run([90]), run([91]), run([92]))
    assert all(len(_tokens(r)) == 4 for r in results)
    # prompt blocks sealed once -> stored events for the shared prefix exist
    assert any(e[0] == "store" for e in events)

    # a repeat of the same prompt should reuse cached pages
    before = eng.allocator.free_pages
    await run([93])
    # no page leak: free count returns after completion (cached pages are
    # evictable, not leaked)
    assert eng.allocator.active_pages == 0
    await eng.close()


async def test_engine_cancellation_frees_pages(decode_schedule):
    eng = InferenceEngine(SPEC, small_config(**decode_schedule))
    ctx = Context()
    req = {
        "token_ids": [1, 2, 3, 4, 5],
        "stop_conditions": {"max_tokens": 10_000, "ignore_eos": True},
    }
    got = []
    async for item in eng.generate(req, ctx):
        got.append(item)
        if len(got) == 3:
            ctx.stop_generating()
    await asyncio.sleep(0.2)
    assert eng.allocator.active_pages == 0
    assert all(s is None for s in eng._slots)
    await eng.close()


async def test_engine_rejects_oversized_and_empty():
    eng = InferenceEngine(SPEC, small_config())
    out = [x async for x in eng.generate({"token_ids": []}, Context())]
    assert out[0]["finish_reason"] == "error"
    big = {"token_ids": list(range(4 * 16 + 1))}  # > max_context (64)
    out = [x async for x in eng.generate(big, Context())]
    assert out[0]["finish_reason"] == "error"
    await eng.close()


# ------------------------------------------------------------------ tp mesh


def test_tp_sharded_prefill_matches_single_device():
    """TP=2 sharded execution must be numerically close to single-device."""
    mesh = make_mesh(tp=2)
    key = jax.random.PRNGKey(3)
    params = llama.init_params(SPEC, key)
    cfg = small_config()

    tokens = np.array([5, 17, 3, 42, 8, 9, 23], np.int32)
    ref = llama.reference_forward(SPEC, params, jnp.asarray(tokens))

    shardings = llama.param_shardings(SPEC, mesh)
    params_sharded = jax.tree.map(
        lambda p, s: jax.device_put(p, s), params, shardings
    )
    k_pages, v_pages = llama.init_cache(SPEC, cfg.num_pages + 1, cfg.page_size)
    ks, vs = llama.cache_shardings(mesh)
    k_pages = jax.device_put(k_pages, ks)
    v_pages = jax.device_put(v_pages, vs)

    padded = np.zeros((8,), np.int32)
    padded[: len(tokens)] = tokens
    block_table = np.zeros((cfg.max_pages_per_seq,), np.int32)
    block_table[:2] = [1, 2]
    logits, _, _, _d = llama.prefill_forward(
        SPEC, params_sharded, jnp.asarray(padded), jnp.asarray(block_table),
        jnp.asarray(0, jnp.int32), k_pages, v_pages,
        jnp.asarray(len(tokens), jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref[-1]), rtol=2e-3, atol=2e-3
    )


async def test_engine_on_tp_mesh_generates(decode_schedule):
    mesh = make_mesh(tp=2)
    eng = InferenceEngine(SPEC, small_config(**decode_schedule), mesh=mesh)
    req = {
        "token_ids": [3, 1, 4, 1, 5],
        "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
    }
    out = [x async for x in eng.generate(req, Context())]
    assert len(_tokens(out)) == 4
    assert out[-1]["finish_reason"] == "length"
    await eng.close()


def test_packed_prefill_failure_isolated_and_pages_released():
    """A raising prefill_batch must fail ONLY its group's requests,
    release their KV pages, and leave the engine able to admit new
    prompts (the error handler previously NameError'd on an undefined
    variable, failing every in-flight request and leaking the pages)."""
    from dynamo_tpu.engine.core import _Waiting

    eng = InferenceEngine(SPEC, small_config())
    free0 = eng.allocator.free_pages

    def make_preps():
        preps = []
        for i, n in enumerate((5, 6)):  # same bucket (8)
            w = _Waiting(
                request={
                    "token_ids": list(range(3, 3 + n)),
                    "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
                },
                context=Context(),
                out_q=asyncio.Queue(),
            )
            prep = eng._prefill(i, w)
            assert isinstance(prep, dict)  # deferred to the packed stage
            preps.append(prep)
        return preps

    preps = make_preps()
    real_fam = eng.fam

    class _Boom:
        def __getattr__(self, k):
            return getattr(real_fam, k)

        def prefill_batch(self, *a, **kw):
            raise RuntimeError("boom")

    eng.fam = _Boom()
    records = eng._run_packed_prefills(preps)
    assert records == []
    for prep in preps:
        item = prep["waiting"].out_q.get_nowait()
        assert item["finish_reason"] == "error"
        assert "boom" in item["error"]
        assert prep["sp"].pages == []
    assert eng.allocator.free_pages == free0  # nothing leaked

    # the engine recovers: the same admissions succeed afterwards
    eng.fam = real_fam
    records = eng._run_packed_prefills(make_preps())
    assert len(records) == 2


def test_packed_prefill_matches_singles():
    """prefill_forward_batch == N sequential prefill_forward calls:
    logits per prompt and every written page identical; padded rows
    (num_tokens=0) touch only the trash page."""
    key = jax.random.PRNGKey(9)
    params = llama.init_params(SPEC, key)
    cfg = small_config()
    page, mpps = cfg.page_size, cfg.max_pages_per_seq
    rng = np.random.default_rng(0)

    prompts = [list(rng.integers(3, SPEC.vocab_size, n)) for n in (7, 12, 9)]
    T = 16
    N = 4  # one padded row
    tokens = np.zeros((N, T), np.int32)
    bts = np.zeros((N, mpps), np.int32)
    starts = np.zeros((N,), np.int32)
    nts = np.zeros((N,), np.int32)
    next_page = 1
    for i, pr in enumerate(prompts):
        tokens[i, : len(pr)] = pr
        npg = (len(pr) + page - 1) // page
        bts[i, :npg] = np.arange(next_page, next_page + npg)
        next_page += npg
        nts[i] = len(pr)

    kb, vb = llama.init_cache(SPEC, cfg.num_pages + 1, page)
    lg_b, kb, vb, _d = llama.prefill_forward_batch(
        SPEC, params, jnp.asarray(tokens), jnp.asarray(bts),
        jnp.asarray(starts), kb, vb, jnp.asarray(nts),
    )

    ks, vs = llama.init_cache(SPEC, cfg.num_pages + 1, page)
    for i, pr in enumerate(prompts):
        lg_s, ks, vs, _d2 = llama.prefill_forward(
            SPEC, params, jnp.asarray(tokens[i]), jnp.asarray(bts[i]),
            jnp.asarray(0, jnp.int32), ks, vs, jnp.asarray(nts[i], jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(lg_b[i]), np.asarray(lg_s), rtol=2e-4, atol=2e-4
        )
    # every live page written identically (trash page 0 excluded)
    np.testing.assert_allclose(
        np.asarray(kb[:, 1:next_page]), np.asarray(ks[:, 1:next_page]),
        atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(vb[:, 1:next_page]), np.asarray(vs[:, 1:next_page]),
        atol=1e-5,
    )
