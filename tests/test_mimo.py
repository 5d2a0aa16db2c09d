"""MiMo-V2.5's layer at toy widths on the CPU: window and global layers
with their own KV heads and rope bases, K wider than V, sinks on the window
layers only, a value scale, rotary on the leading dims, a dense first layer
and held experts behind a router over all of them. Against the benchmark's
plain reference (``perfbench/references/hybrid_swa_moe.py``), which shares
no code with the program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_contract import (
    Family, _cache, _model, _table, behind_bursts, case, cases, run, tokens,
)

from dynamo_tpu.engine.config import LayerKind, ModelSpec
from dynamo_tpu.models import llama
from dynamo_tpu.ops.attention import (
    window_table,
    decode_update_attention,
    paged_decode_attention,
)
from dynamo_tpu.ops.pallas.fused_decode import fused_decode_attention

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
PAGES_PER_SEQ = 12
SEED = 5


def _counted(k, v, n, steps):
    """The counters behind a prompt of ``n`` tokens and ``steps`` decode
    steps of one live slot: 3 expert layers, top-2."""
    counts = np.asarray(k.counts)
    assert counts[0].sum() == 0  # layer 0 is dense
    assert counts[1:, llama.COUNT_PREFILL, -1].tolist() == [1, 1, 1]
    assert counts[1:, llama.COUNT_DECODE, -1].tolist() == [steps] * 3
    assert counts[1:, llama.COUNT_PREFILL, -3].tolist() == [n * 2] * 3
    assert counts[1:, llama.COUNT_DECODE, -3].tolist() == [steps * 2] * 3
    # every expert is held: every assignment reached one
    assert (counts[1:, :, :-3].sum(-1) == counts[1:, :, -3]).all()
    # a decode step of one token touches its two experts, no more
    assert counts[1:, llama.COUNT_DECODE, -2].tolist() == [steps * 2] * 3


def _served(engine, snap, served, outs):
    """A prompt of two chunks and four windows: its first token is the
    plain reference's."""
    assert outs == [[int(_model(F)[2][0, 29].argmax())]]
    counters = engine.moe_counters()
    assert counters["layers"] == 3
    assert counters["prefill.assignments"] >= 3 * 30 * 2
    assert sum(counters[f"prefill.expert.{i}"] for i in range(8)) == (
        counters["prefill.assignments"])
    assert snap["moe.prefill.steps"]["calls"] == counters["prefill.steps"]
    assert 0 < counters["prefill.experts_touched"] <= (
        3 * 8 * counters["prefill.steps"])


# the family's row of the contract (tests/family_contract.py): a prompt
# and a second chunk longer than the window of 8 (the decode steps cross a
# page); a 30-token prompt in chunks of a window's length behind bursts
F = FAMILY = Family(
    spec=SPEC, config=CONFIG, reference="hybrid_swa_moe",
    seed=SEED, tol=2e-4, pages_per_seq=PAGES_PER_SEQ, seqs=2,
    prompts=((30, None),), chunked={"two-chunks": [(0, 16), (16, 14)]},
    packs=([(0, 0, 30), (1, 0, 19)],), bursts_paths=(), engine_path=None,
    served=((tokens(2, 40)[0, :30], 1),),
    engine=dict(prefill_buckets=(8, 16), prefill_pack_size=2,
                guided_mode="off"),
    also={"prefill-decode": _counted, "serves": _served})


@pytest.mark.parametrize("case,kw", cases(
    F, case("engine-chunks-behind-bursts", behind_bursts,
            chunk=8, n=30, busy=36)))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


def test_verify_scores_every_position(model):
    params, toks, want = model
    k, v = _cache(F)
    T, W = 21, 4  # the verify window starts mid-page
    pad = jnp.zeros((32,), jnp.int32).at[:T].set(toks[0, :T])
    _, k, v, _ = llama.prefill_forward(
        SPEC, params, pad, _table(F, 0), jnp.asarray(0), k, v, jnp.asarray(T))
    targets, k, v, _ = llama.verify_forward(
        SPEC, params, jnp.asarray(toks[:1, T: T + W]), _table(F, 0)[None],
        jnp.asarray([T]), k, v, jnp.asarray([W]))
    assert np.asarray(targets)[0].tolist() == want[
        0, T: T + W].argmax(-1).tolist()


def test_pages_move_by_kind(model):
    """extract / insert carry one block a kind and leave counters be."""
    params, toks, _ = model
    k, v = _cache(F)
    pad = jnp.zeros((32,), jnp.int32).at[:30].set(toks[0, :30])
    _, k, v, _ = llama.prefill_forward(
        SPEC, params, pad, _table(F, 0), jnp.asarray(0), k, v, jnp.asarray(30))
    ids = jnp.asarray([1, 2, 3])
    kb, vb = llama.extract_kv_pages(k, v, ids)
    assert [b.shape for b in kb] == [(2, 3, 1, 4, 12), (2, 3, 2, 4, 12)]
    assert [b.shape for b in vb] == [(2, 3, 1, 4, 8), (2, 3, 2, 4, 8)]
    k2, v2 = _cache(F)
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
