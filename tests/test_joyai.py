"""JoyAI-LLM-Flash's layer at toy widths on the CPU: latent attention (a
query rank, a latent row shared by the heads, rope on interleaved pairs),
a dense first layer, then held experts behind a sigmoid router over all of
them beside a shared expert. The program (``models/mla.py``, the paged
latent cache, the decode kernel interpreted and the XLA walk) against the
benchmark's plain reference (``perfbench/references/latent_moe.py``), which
shares no code with it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_contract import (
    PROMPT, Family, _whole, behind_bursts, case, cases, run, two_slots,
)

from dynamo_tpu.engine.config import ModelSpec
from dynamo_tpu.models import mla
from dynamo_tpu.models.family import MlaFamily
from dynamo_tpu.ops import attention as attn_ops
from dynamo_tpu.ops.pallas.fused_decode import live_chunks
from dynamo_tpu.ops.pallas.latent_decode import (
    latent_chunk_pages, latent_decode_attention,
)

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
PAGE, PAGES_PER_SEQ = 4, 16
SEED = 11


def _two_slots(grew, steps):
    """The counters behind ``steps`` steps of two counted rows, top-4."""
    assert (grew[0] == 0).all()  # the dense layer keeps none
    assert (grew[1:, 1, -1] == steps).all() and (grew[1:, 0] == 0).all()
    assert (grew[1:, 1, -3] == steps * 2 * 4).all()
    assert (grew[1:, 1, :4].sum(axis=1) <= steps * 2 * 4).all()


def _served(engine, snap, served, outs):
    """``decode_kv``, ``prefill_kv.*.latent`` and ``moe_counters()`` read
    what hand arithmetic gives behind ONE prompt of 21 tokens."""
    assert isinstance(engine.fam, MlaFamily)
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
    c = engine.moe_counters()
    assert c["layers"] == 2  # the dense first layer keeps none
    assert c["prefill.steps"] == 2 and c["prefill.assignments"] == 2 * 21 * 4
    assert c["decode.assignments"] >= 2 * 5 * 4
    assert sum(c[f"decode.expert.{i}"] for i in range(4)) <= c[
        "decode.assignments"]


# the family's row of the contract (tests/family_contract.py): a latent
# family (the pair is the pool and the experts' counters); the pack's third
# member is padding; a 37-token prompt in chunks of 16 behind bursts
F = FAMILY = Family(
    spec=SPEC, config=CONFIG, reference="latent_moe", seed=SEED,
    prompts=(), chunked={"single": [(0, 13)],
                         "three-chunks": [(0, 16), (16, 16), (32, 7)]},
    packs=([(0, 0, 16), (1, 0, 9), (0, 0, 0)],), served=((PROMPT, 6),),
    bursts_paths=(), also={"two-slots": _two_slots, "serves": _served})


@pytest.mark.parametrize("case,kw", cases(
    F, case("two-slots-xla", two_slots, path="0"),
    case("two-slots-kernel", two_slots, path="1"),
    case("engine-chunks-behind-bursts", behind_bursts,
         chunk=16, n=37, busy=40)))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


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
        got = _whole(spec, params, jnp.asarray(toks[0]))
        want = ref.forward(dict(CONFIG, rope_interleave=flag), SEED, toks, at)
        F.close(got, np.asarray(want)[0])
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
    F.close(alike + routed, whole, tol=1e-4)
    # and the program's share is the reference's share
    spec = _spec(num_layers=2)
    params = mla.init_params(spec, jax.random.PRNGKey(SEED))
    got = _whole(spec, params, jnp.asarray(toks[0]))
    want = ref.forward(cfg, SEED, toks, np.arange(10)[None].repeat(2, 0))
    F.close(got, np.asarray(want)[0])


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
