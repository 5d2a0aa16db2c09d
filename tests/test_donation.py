"""Donated-buffer audit: every hot-path jit must donate the KV pools it
updates (``donate_argnums`` discipline — without it each decode step
COPIES the multi-GB page arrays it rewrites; SNIPPETS.md [2]/[3]).

Two layers of enforcement:

- Behavioral: calling each hot jit with real arrays must invalidate
  exactly the expected inputs (jax marks donated buffers deleted at the
  API layer on every backend, so this holds on CPU tier-1 too).
- Inventory: every ``jax.jit`` object in the hot modules must appear in
  the audit table below — a NEW hot jit landing without a donation
  decision fails the test until it is classified (donating or
  explicitly read-only).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import sampling
from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.models import family, llama, mla
from dynamo_tpu.ops import quant
from dynamo_tpu.ops.pallas import fused_decode, kv_write

PJIT_TYPE = type(jax.jit(lambda x: x))

SPEC = ModelSpec(
    name="donate-audit", vocab_size=64, hidden_size=32,
    intermediate_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, dtype="float32", tie_embeddings=True,
)
MLA_SPEC = ModelSpec.tiny_deepseek()
B, PAGE, PPS = 2, 4, 3
NUM_PAGES = 1 + B * PPS


def _gqa_args():
    params = llama.init_params(SPEC, jax.random.PRNGKey(0))
    k, v = llama.init_cache(SPEC, NUM_PAGES, PAGE)
    bt = np.zeros((B, PPS), np.int32)
    for i in range(B):
        bt[i] = np.arange(1 + i * PPS, 1 + (i + 1) * PPS)
    return params, k, v, jnp.asarray(bt)


def _mla_args():
    params = mla.init_params(MLA_SPEC, jax.random.PRNGKey(0))
    cache = mla.init_cache(MLA_SPEC, NUM_PAGES, PAGE)
    bt = np.zeros((B, PPS), np.int32)
    for i in range(B):
        bt[i] = np.arange(1 + i * PPS, 1 + (i + 1) * PPS)
    return params, cache, jnp.asarray(bt)


def _deleted(arrs) -> list[bool]:
    # tree.leaves flattens QuantPool pools into (vals, scale) leaves, so
    # "donated" means EVERY leaf is — a donated value pool with a copied
    # scale buffer still fails
    return [a.is_deleted() for a in jax.tree.leaves(list(arrs))]


def _gqa_quant_args():
    params = llama.init_params(SPEC, jax.random.PRNGKey(0))
    k, v = llama.init_cache(SPEC, NUM_PAGES, PAGE, kv_dtype="fp8")
    bt = np.zeros((B, PPS), np.int32)
    for i in range(B):
        bt[i] = np.arange(1 + i * PPS, 1 + (i + 1) * PPS)
    return params, k, v, jnp.asarray(bt)


def test_gqa_prefill_donates_pools():
    params, k, v, bt = _gqa_args()
    tokens = jnp.zeros((8,), jnp.int32)
    logits, k2, v2, _ = llama.prefill_forward(
        SPEC, params, tokens, bt[0], jnp.asarray(0, jnp.int32), k, v,
        jnp.asarray(8, jnp.int32),
    )
    assert _deleted([k, v]) == [True, True]
    assert not tokens.is_deleted()
    assert not jax.tree.leaves(params)[0].is_deleted()


def test_gqa_packed_prefill_donates_pools():
    params, k, v, bt = _gqa_args()
    tokens = jnp.zeros((B, 8), jnp.int32)
    _logits, k2, v2, _ = llama.prefill_forward_batch(
        SPEC, params, tokens, bt, jnp.zeros((B,), jnp.int32), k, v,
        jnp.zeros((B,), jnp.int32),
    )
    assert _deleted([k, v]) == [True, True]


def test_gqa_verify_donates_pools():
    params, k, v, bt = _gqa_args()
    tokens = jnp.zeros((B, 3), jnp.int32)
    targets, k2, v2, _ = llama.verify_forward(
        SPEC, params, tokens, bt, jnp.zeros((B,), jnp.int32), k, v,
        jnp.zeros((B,), jnp.int32),
    )
    assert _deleted([k, v]) == [True, True]
    assert not tokens.is_deleted()


def test_mla_verify_donates_cache():
    params, cache, bt = _mla_args()
    tokens = jnp.zeros((B, 3), jnp.int32)
    _targets, cache2 = mla.verify_forward(
        MLA_SPEC, params, tokens, bt, jnp.zeros((B,), jnp.int32),
        cache, jnp.zeros((B,), jnp.int32),
    )
    assert cache.is_deleted()


def test_gqa_decode_steps_donates_pools():
    params, k, v, bt = _gqa_args()
    zB = jnp.zeros((B,), jnp.int32)
    out, k2, v2 = llama.decode_steps(
        SPEC, params, zB, bt, jnp.ones((B,), jnp.int32), k, v,
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.float32), zB,
        jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.uint32), zB,
        n_steps=2,
    )
    assert _deleted([k, v]) == [True, True]
    assert not bt.is_deleted()


def test_gqa_insert_donates_extract_does_not():
    _params, k, v, _bt = _gqa_args()
    ids = jnp.asarray([1, 2], jnp.int32)
    kb, vb = llama.extract_kv_pages(k, v, ids)
    assert _deleted([k, v]) == [False, False]  # extract is read-only
    k2, v2 = llama.insert_kv_pages(k, v, ids, kb, vb)
    assert _deleted([k, v]) == [True, True]


def test_state_rows_release_and_recurrent_programs_donate_every_leaf():
    """A model with recurrent layers: the state pool, the convolution
    tails and the directory ride the donated pair through the programs,
    and the engine's release of state rows updates the K side in place."""
    spec = ModelSpec.tiny_solar()
    params = llama.init_params(spec, jax.random.PRNGKey(0))
    k, v = llama.init_cache(spec, NUM_PAGES, PAGE, state_rows=2)
    bt = jnp.arange(1, 1 + PPS, dtype=jnp.int32)
    _, k2, v2, _ = llama.prefill_forward(
        spec, params, jnp.zeros((8,), jnp.int32), bt,
        jnp.asarray(0, jnp.int32), k, v, jnp.asarray(8, jnp.int32),
    )
    assert all(_deleted([k, v]))
    assert int(k2.rows.owner[0, 0]) == 1
    k3 = llama.release_state_rows(k2, jnp.asarray([1, -1], jnp.int32))
    assert all(_deleted([k2])) and not any(_deleted([v2]))
    assert int(k3.rows.owner[0, 0]) == 0


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernel"])
def test_pages_states_and_tails_of_one_kind_are_donated(monkeypatch, pallas):
    """A kind that keeps both (Falcon-H1): page pools, states, tails and
    the directory ride the donated pair through prefill, packed prefill
    and a decode burst; every leaf is given up and none is copied out."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    spec = ModelSpec.tiny_falcon_h1()
    params = llama.init_params(spec, jax.random.PRNGKey(0))
    k, v = llama.init_cache(spec, NUM_PAGES, PAGE, state_rows=2)
    leaves = len(jax.tree.leaves((k, v)))
    assert leaves == 4 + 1 + 3 + 1  # pages, state a side; counts; rows
    bt = jnp.arange(1, 1 + PPS, dtype=jnp.int32)
    i32 = jnp.int32
    pf = jax.jit(llama.prefill_forward_impl, static_argnums=(0,),
                 donate_argnums=(5, 6))
    _, k2, v2, _ = pf(
        spec, params, jnp.zeros((8,), i32), bt, jnp.asarray(0, i32), k, v,
        jnp.asarray(8, i32))
    assert all(_deleted([k, v]))
    pb = jax.jit(llama.prefill_forward_batch_impl, static_argnums=(0,),
                 donate_argnums=(5, 6))
    _, k3, v3, _ = pb(
        spec, params, jnp.zeros((2, 8), i32), jnp.stack([bt, bt * 0]),
        jnp.zeros((2,), i32), k2, v2, jnp.asarray([8, 0], i32))
    assert all(_deleted([k2, v2]))
    ds = jax.jit(llama.decode_steps_impl, static_argnums=(0,),
                 static_argnames=("n_steps", "n_logprobs"),
                 donate_argnums=(5, 6))
    z = jnp.zeros((2,), i32)
    _, k4, v4 = ds(
        spec, params, z, jnp.stack([bt, bt * 0]), jnp.asarray([9, 1], i32),
        k3, v3, jnp.asarray([True, False]), jnp.zeros((2,)), z,
        jnp.ones((2,)), jnp.zeros((2,), jnp.uint32), z, n_steps=2,
        n_logprobs=0)
    assert all(_deleted([k3, v3]))
    assert len(jax.tree.leaves((k4, v4))) == leaves
    assert int(k4.rows.owner[0, 0]) == 1


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernel"])
def test_state_tails_and_the_latent_pool_of_one_model_are_donated(
        monkeypatch, pallas):
    """KDA and latent kinds in one model (Ling-3.0): the states, the
    tails, the ONE latent pool (no V side), the counters and the
    directory ride the donated pair through prefill, packed prefill and a
    decode burst; every leaf is given up and none is copied out."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    spec = ModelSpec.tiny_ling3()
    params = llama.init_params(spec, jax.random.PRNGKey(0))
    k, v = llama.init_cache(spec, NUM_PAGES, PAGE, state_rows=2)
    leaves = len(jax.tree.leaves((k, v)))
    # latent pool, states, counts, rows (3) | tails, the V side's counts
    assert leaves == 1 + 1 + 1 + 3 + 1 + 1
    bt = jnp.arange(1, 1 + PPS, dtype=jnp.int32)
    i32 = jnp.int32
    pf = jax.jit(llama.prefill_forward_impl, static_argnums=(0,),
                 donate_argnums=(5, 6))
    _, k2, v2, _ = pf(
        spec, params, jnp.zeros((8,), i32), bt, jnp.asarray(0, i32), k, v,
        jnp.asarray(8, i32))
    assert all(_deleted([k, v]))
    pb = jax.jit(llama.prefill_forward_batch_impl, static_argnums=(0,),
                 donate_argnums=(5, 6))
    _, k3, v3, _ = pb(
        spec, params, jnp.zeros((2, 8), i32), jnp.stack([bt, bt * 0]),
        jnp.zeros((2,), i32), k2, v2, jnp.asarray([8, 0], i32))
    assert all(_deleted([k2, v2]))
    ds = jax.jit(llama.decode_steps_impl, static_argnums=(0,),
                 static_argnames=("n_steps", "n_logprobs"),
                 donate_argnums=(5, 6))
    z = jnp.zeros((2,), i32)
    _, k4, v4 = ds(
        spec, params, z, jnp.stack([bt, bt * 0]), jnp.asarray([9, 1], i32),
        k3, v3, jnp.asarray([True, False]), jnp.zeros((2,)), z,
        jnp.ones((2,)), jnp.zeros((2,), jnp.uint32), z, n_steps=2,
        n_logprobs=0)
    assert all(_deleted([k3, v3]))
    assert len(jax.tree.leaves((k4, v4))) == leaves
    assert int(k4.rows.owner[0, 0]) == 1
    assert v4.pools[0] is None and k4.pools[0].ndim == 4


def test_kv_write_kernel_donates_pools():
    _params, k, v, _bt = _gqa_args()
    kn = jnp.zeros((B, SPEC.num_kv_heads, SPEC.head_dim), jnp.float32)
    k2, v2 = kv_write.kv_write_pallas(
        k, v, kn, kn, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32), layer=0, interpret=True,
    )
    assert _deleted([k, v]) == [True, True]


def test_fused_decode_kernel_donates_pools():
    _params, k, v, bt = _gqa_args()
    q = jnp.zeros((B, SPEC.num_heads, SPEC.head_dim), jnp.float32)
    kn = jnp.zeros((B, SPEC.num_kv_heads, SPEC.head_dim), jnp.float32)
    _o, k2, v2 = fused_decode.fused_decode_attention(
        q, k, v, kn, kn, bt, jnp.ones((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        layer=0, interpret=True,
    )
    assert _deleted([k, v]) == [True, True]
    assert not q.is_deleted()


def test_mla_decode_and_prefill_donate_cache():
    params, cache, bt = _mla_args()
    tokens = jnp.zeros((8,), jnp.int32)
    _logits, cache2 = mla.prefill_forward(
        MLA_SPEC, params, tokens, bt[0], jnp.asarray(0, jnp.int32),
        cache, jnp.asarray(8, jnp.int32),
    )
    assert cache.is_deleted()
    zB = jnp.zeros((B,), jnp.int32)
    out = mla.decode_steps(
        MLA_SPEC, params, zB, bt, jnp.ones((B,), jnp.int32), cache2,
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.float32), zB,
        jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.uint32), zB,
        n_steps=1,
    )
    assert cache2.is_deleted()


def test_mla_latent_insert_donates_extract_does_not():
    _params, cache, _bt = _mla_args()
    ids = jnp.asarray([1, 2], jnp.int32)
    blocks = family._extract_latent(cache, ids)
    assert not cache.is_deleted()  # extract is read-only
    cache2 = family._insert_latent(cache, ids, np.asarray(blocks))
    assert cache.is_deleted()


def test_sampling_does_not_donate_logits():
    """sample_tokens must NOT donate: _complete_admissions reuses the
    stacked logits for the batched logprob pass after sampling."""
    logits = jnp.zeros((B, SPEC.vocab_size), jnp.float32)
    zB = jnp.zeros((B,), jnp.int32)
    sampling.sample_tokens(
        logits, jnp.zeros((B,), jnp.float32), zB,
        jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.uint32), zB,
    )
    assert not logits.is_deleted()


def test_masked_sampling_does_not_donate_logits_or_mask():
    """sample_tokens_masked (guided decoding) shares the sync-admission
    contract: the stacked logits feed the batched logprob pass after
    sampling, and the mask row for a slot is REUSED by the next burst
    when the sampled token did not advance the automaton's state (e.g.
    whitespace loops) — neither input may be invalidated."""
    logits = jnp.zeros((B, SPEC.vocab_size), jnp.float32)
    allowed = jnp.ones((B, SPEC.vocab_size), bool)
    zB = jnp.zeros((B,), jnp.int32)
    sampling.sample_tokens_masked(
        logits, allowed, jnp.zeros((B,), jnp.float32), zB,
        jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.uint32), zB,
    )
    assert not logits.is_deleted()
    assert not allowed.is_deleted()


# --------------------------------------------- quantized pools (fp8 KV)
# The same donation discipline over QuantPool pytrees: BOTH leaves (fp8
# values and bf16 scales) must be donated by every hot jit that updates
# the cache — a copied scale buffer is small but a copied value pool is
# the multi-GB bug the audit exists for (and the behavioral check below
# catches either, per _deleted).


def test_gqa_quant_prefill_and_verify_donate_both_leaves():
    params, k, v, bt = _gqa_quant_args()
    tokens = jnp.zeros((8,), jnp.int32)
    _logits, k2, v2, _ = llama.prefill_forward(
        SPEC, params, tokens, bt[0], jnp.asarray(0, jnp.int32), k, v,
        jnp.asarray(8, jnp.int32),
    )
    assert _deleted([k, v]) == [True] * 4  # vals + scale, k and v
    assert quant.is_quant(k2) and quant.is_quant(v2)
    tokens2 = jnp.zeros((B, 3), jnp.int32)
    _targets, k3, v3, _ = llama.verify_forward(
        SPEC, params, tokens2, bt, jnp.zeros((B,), jnp.int32), k2, v2,
        jnp.zeros((B,), jnp.int32),
    )
    assert _deleted([k2, v2]) == [True] * 4


def test_gqa_quant_decode_steps_donates_both_leaves():
    params, k, v, bt = _gqa_quant_args()
    zB = jnp.zeros((B,), jnp.int32)
    _out, k2, v2 = llama.decode_steps(
        SPEC, params, zB, bt, jnp.ones((B,), jnp.int32), k, v,
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.float32), zB,
        jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.uint32), zB,
        n_steps=2,
    )
    assert _deleted([k, v]) == [True] * 4
    assert not bt.is_deleted()


def test_quant_fused_decode_kernel_donates_value_pools():
    _params, k, v, bt = _gqa_quant_args()
    q = jnp.zeros((B, SPEC.num_heads, SPEC.head_dim), jnp.float32)
    kn = jnp.zeros((B, SPEC.num_kv_heads, SPEC.head_dim), jnp.float32)
    _o, k2, v2 = fused_decode.fused_decode_attention(
        q, k, v, kn, kn, bt, jnp.ones((B,), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        layer=0, interpret=True,
    )
    # donate_argnums=(1, 2) covers the whole QuantPool pytree: values
    # alias through the pallas_call, scales through the XLA scatter
    assert _deleted([k, v]) == [True] * 4
    assert not q.is_deleted()


def test_mla_quant_forwards_donate_cache_leaves():
    params, _c, bt = _mla_args()
    cache = mla.init_cache(MLA_SPEC, NUM_PAGES, PAGE, kv_dtype="fp8")
    tokens = jnp.zeros((8,), jnp.int32)
    _logits, cache2 = mla.prefill_forward(
        MLA_SPEC, params, tokens, bt[0], jnp.asarray(0, jnp.int32),
        cache, jnp.asarray(8, jnp.int32),
    )
    assert _deleted([cache]) == [True, True]
    tokens2 = jnp.zeros((B, 3), jnp.int32)
    _targets, cache3 = mla.verify_forward(
        MLA_SPEC, params, tokens2, bt, jnp.zeros((B,), jnp.int32),
        cache2, jnp.zeros((B,), jnp.int32),
    )
    assert _deleted([cache2]) == [True, True]
    zB = jnp.zeros((B,), jnp.int32)
    _out = mla.decode_steps(
        MLA_SPEC, params, zB, bt, jnp.ones((B,), jnp.int32), cache3,
        jnp.zeros((B,), bool), jnp.zeros((B,), jnp.float32), zB,
        jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.uint32), zB,
        n_steps=1,
    )
    assert _deleted([cache3]) == [True, True]


def test_quant_insert_donates_extract_does_not():
    _params, k, v, _bt = _gqa_quant_args()
    ids = jnp.asarray([1, 2], jnp.int32)
    kb, vb = llama.extract_kv_pages(k, v, ids)
    assert kb.dtype == jnp.uint8  # packed fp8+scale payload
    assert _deleted([k, v]) == [False] * 4  # extract is read-only
    k2, v2 = llama.insert_kv_pages(k, v, ids, kb, vb)
    assert _deleted([k, v]) == [True] * 4


def test_mla_quant_latent_insert_donates_extract_does_not():
    cache = mla.init_cache(MLA_SPEC, NUM_PAGES, PAGE, kv_dtype="fp8")
    ids = jnp.asarray([1, 2], jnp.int32)
    blocks = family._extract_latent(cache, ids)
    assert blocks.dtype == jnp.uint8
    assert _deleted([cache]) == [False, False]
    _cache2 = family._insert_latent(cache, ids, np.asarray(blocks))
    assert _deleted([cache]) == [True, True]


# --------------------------------------------------------------- inventory

# module -> {jit name: "donates" | "read-only"}. A jit object in one of
# these modules that is NOT listed fails the inventory test: new hot
# jits must make an explicit donation decision here (and get a
# behavioral test above when they donate).
AUDIT: dict = {
    llama: {
        "prefill_forward": "donates",
        "prefill_forward_batch": "donates",
        "prefill_forward_ring": "donates",
        "verify_forward": "donates",
        "decode_forward": "donates",
        "decode_steps": "donates",
        "extract_kv_pages": "read-only",
        "_draw": "read-only",  # a key and a scale in, a fresh matrix out
        "insert_kv_pages": "donates",
        "embed_forward": "read-only",
        # ops/attention's walk, imported: a jit of its own only so that a
        # kind's layers share one trace; it reads the pools inside the
        # prefill programs' jits, which are the ones that donate them
        "paged_prefill_attention": "read-only",
        # the engine's release of a recurrent model's state rows: the K
        # side's directory is updated in place
        "release_state_rows": "donates",
    },
    mla: {
        "prefill_forward": "donates",
        "prefill_forward_batch": "donates",
        "verify_forward": "donates",
        "decode_forward": "donates",
        "decode_steps": "donates",
        "embed_forward": "read-only",
        # jits inside the programs above (one trace for the layers of a
        # kind): their buffers are the outer program's, which donates
        "_attn_inputs_jit": "read-only",
        "_ffn_counting_jit": "read-only",
        "latent_prefill_attention": "read-only",
        "_draw": "read-only",
    },
    family: {
        "_extract_latent": "read-only",
        "_insert_latent_impl": "donates",
    },
    sampling: {
        "sample_tokens": "read-only",
        "sample_tokens_masked": "read-only",
        "token_logprobs": "read-only",
    },
    kv_write: {
        "kv_write_pallas": "donates",
    },
    fused_decode: {
        "fused_decode_attention": "donates",
    },
    # ops/quant.py holds codec MATH that traces into its callers' jits;
    # a jit object appearing there must take an explicit donation
    # decision here like everywhere else
    quant: {},
}


def test_every_hot_jit_is_audited():
    unaudited = []
    for mod, table in AUDIT.items():
        found = {
            name for name, obj in vars(mod).items()
            if isinstance(obj, PJIT_TYPE)
        }
        missing = found - set(table)
        if missing:
            unaudited.append((mod.__name__, sorted(missing)))
        stale = set(table) - found
        assert not stale, f"audit table lists absent jits in {mod.__name__}: {stale}"
    assert not unaudited, (
        "hot-path jits without a donation decision (add to AUDIT + a "
        f"behavioral test if they donate): {unaudited}"
    )
