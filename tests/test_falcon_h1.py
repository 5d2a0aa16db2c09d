"""Falcon-H1 at toy widths on the CPU, against the benchmark's own plain
reference (``perfbench/references/parallel_ssm.py``, loaded by path: the
same module the chip is held to, not a copy): in every layer a Mamba-2
(SSD) mixer over a float32 state row AND GQA attention over pages, in
parallel off one norm, with the family's multipliers. Programs, the decode
kernel (interpreted) and its XLA twin, the chunk form, the state directory
shared with the pages' block table, and the engine around a sequence that
owns both.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_contract import Family, _cache, _whole, case, cases, gates, run

from dynamo_tpu.engine.config import EngineConfig, LayerKind, ModelSpec
from dynamo_tpu.models import llama
from dynamo_tpu.ops import attention as attn_ops

SPEC = ModelSpec.tiny_falcon_h1()
# the reference reads the published keys; the program reads SPEC
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 3, "intermediate_size": 96,
    "vocab_size": 96, "rms_norm_eps": 1e-5, "rope_theta": 1e11,
    "rope_scaling": None, "torch_dtype": "float32",
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_ssm": 32,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 16, "mamba_conv_bias": True, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "embedding_multiplier": 5.0, "lm_head_multiplier": 0.125,
    "key_multiplier": 0.3, "attention_in_multiplier": 0.9,
    "attention_out_multiplier": 0.6, "ssm_in_multiplier": 0.5,
    "ssm_out_multiplier": 0.7, "ssm_multipliers": [0.35, 0.5, 0.7, 0.8, 0.6],
    "mlp_multipliers": [0.7, 0.4],
}
PAGE, T, ROWS = 4, 40, 3
SEED = 11
TOL = 2e-5  # float32 on both sides; logits of magnitude ~0.5


def _served(engine, snap, served, outs):
    """Two prompts of 16 + 5 tokens: chunks of 16 tokens, 1 + 1 a prompt,
    the second resumed; five decode steps a prompt in bursts of 4."""
    assert engine.kda == {} and engine._kv_chunk_pages is not None
    assert engine.ssd["prefill_chunks"] == 4
    assert engine.ssd["rows_resumed"] == 2
    assert engine.ssd["decode_rows"] % 4 == 0 and engine.ssd["decode_rows"] >= 16
    assert engine.decode_kv["pages_live"] > 0  # the pages are counted too
    assert snap["recurrent_state.rows"]["calls"] == 2
    assert snap["ssd.prefill_chunks"]["calls"] == 4
    assert snap["ssd.rows_resumed"]["calls"] == 2
    assert "kda.decode_rows" not in snap


# the family's row of the contract (tests/family_contract.py): the state a
# packed row left is the single prefill's to 1e-6, as are a burst's pools
F = FAMILY = Family(
    spec=SPEC, config=CONFIG, reference="parallel_ssm",
    seed=SEED, tol=TOL, pool_tol=1e-6, pack_tol=1e-6, state_rows=ROWS,
    packs=([(0, 0, 13), (0, 0, 0)], [(1, 0, 16), (2, 0, 7)]),
    inactive_paths=("0", "1"), streams=(False, True),
    also={"serves": _served})


@pytest.mark.parametrize("case,kw", cases(F, case("engine-gates", gates)))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


def test_the_cache_keeps_pages_and_a_state_for_one_kind():
    """The one kind's entry holds its page pools and, beside them, the
    states and the convolution tails, a row a sequence and a trash row;
    the first leaf of the cache is still a page pool."""
    kd = SPEC.kind(0)
    assert kd.paged and kd.recurrent and SPEC.has_recurrent and SPEC.mixers == {"ssd"}
    k, v = _cache(F)
    assert isinstance(k.pools[0], llama.PagesAndState)
    assert k.pools[0].pages.shape == (3, 49, 2, PAGE, 16)
    assert k.pools[0].state.shape == (3, ROWS + 1, 4, 8, 16)
    assert k.pools[0].state.dtype == jnp.float32
    assert v.pools[0].state.shape == (3, ROWS + 1, 3, SPEC.ssm_conv_dim)
    assert SPEC.ssm_conv_dim == 32 + 2 * 2 * 16
    assert llama.page_size_of(k) == PAGE
    assert llama.kind_pages(SPEC, k, 0) is k.pools[0].pages
    # a softmax-only and a state-only kind keep the bare array
    sk, _ = llama.init_cache(ModelSpec.tiny_solar(), 9, PAGE, state_rows=2)
    assert sk.pools[0].ndim == 5 and sk.pools[1].shape[1] == 3


def _ssd_case(T_, seed=3, N=2, H=4, P=8, G=2, S=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (N, T_, H, P))
    # steps from 1e-3 to 1 against A in (1, 16): decays from a whisker
    # under 1 down to e^-16 a token
    dt = jnp.exp(jax.random.uniform(ks[1], (N, T_, H), minval=-7, maxval=0))
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    B = jax.random.normal(ks[3], (N, T_, G, S))
    C = jax.random.normal(ks[4], (N, T_, G, S))
    D = jax.random.uniform(ks[5], (H,), minval=0.5, maxval=1.5)
    h0 = jax.random.normal(ks[6], (N, H, P, S))
    return x, dt, A, B, C, D, h0


@pytest.mark.parametrize("T_", [16, 64, 150], ids=["one-chunk", "four", "ragged"])
def test_the_chunk_form_equals_the_token_recurrence(T_):
    """The chunkwise (SSD) form over chunks of 16 tokens, from a non-zero
    state, is the recurrence a token at a time: outputs and the state it
    leaves; a fresh row starts from zero whatever the pool held; a padded
    token (dt = 0) leaves the state as it was."""
    x, dt, A, B, C, D, h0 = _ssd_case(T_)
    N = x.shape[0]
    pool = jnp.concatenate([h0[:1] * 0 + 7.0, h0, h0[:1]])[None]
    y, out = attn_ops.ssd_chunk_prefill(
        x, dt, A, B, C, D, pool, jnp.arange(1, N + 1), jnp.zeros((N,), bool),
        layer=0, chunk=16)
    for n in range(N):
        want_y, want_h = attn_ops.ssd_recurrence(
            x[n], dt[n], A, B[n], C[n], D, h0[n])
        F.close(y[n], np.asarray(want_y), tol=2e-5)
        F.close(out[0, 1 + n], np.asarray(want_h), tol=2e-5)
    np.testing.assert_array_equal(np.asarray(out[0, 0]), np.asarray(pool[0, 0]))
    fresh_y, fresh = attn_ops.ssd_chunk_prefill(
        x[:1], dt[:1], A, B[:1], C[:1], D, pool, jnp.ones((1,), jnp.int32),
        jnp.ones((1,), bool), layer=0, chunk=16)
    want_y, want_h = attn_ops.ssd_recurrence(
        x[0], dt[0], A, B[0], C[0], D, h0[0] * 0)
    F.close(fresh_y[0], np.asarray(want_y), tol=2e-5)
    F.close(fresh[0, 1], np.asarray(want_h), tol=2e-5)
    # the tail of the tokens padded: the state after the real ones
    real = T_ - 5
    _, cut = attn_ops.ssd_chunk_prefill(
        x, jnp.where(jnp.arange(T_)[None, :, None] < real, dt, 0.0), A, B, C,
        D, pool, jnp.arange(1, N + 1), jnp.zeros((N,), bool), layer=0, chunk=16)
    _, want_h = attn_ops.ssd_recurrence(
        x[0, :real], dt[0, :real], A, B[0, :real], C[0, :real], D, h0[0])
    F.close(cut[0, 1], np.asarray(want_h), tol=2e-5)


def test_ssd_step_equals_its_xla_twin(monkeypatch):
    """The decode kernel, interpreted, against the XLA form that serves
    off the chip, over a pool with a trash row (three slots on it: first,
    between and last): outputs, states and tails of the live rows; the
    other rows and the other layer stay as they were to the bit; and one
    step is the recurrence's."""
    x, dt, A, B, C, D, _ = _ssd_case(4, seed=5)
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 6, 4, 8, 16))
    conv = jax.random.normal(jax.random.PRNGKey(8), (2, 6, 3, 96))
    tail = jax.random.normal(jax.random.PRNGKey(7), (5, 3, 96))
    rows = jnp.asarray([5, 3, 5, 0, 5], jnp.int32)  # row 5 is the trash row
    at = jnp.asarray([0, 0, 1, 1, 2])  # the operands a slot brings
    steps = {}
    for pallas in ("0", "1"):
        monkeypatch.setenv("DYNAMO_PALLAS", pallas)
        steps[pallas] = attn_ops.ssd_decode_step(
            pool, conv, rows, x[0, at], dt[0, at], A, B[0, at], C[0, at], D,
            tail, layer=1)
    live = np.asarray([1, 3])
    for a, b in zip(steps["0"], steps["1"]):
        assert a.shape == b.shape
    F.close(steps["0"][0][live], np.asarray(steps["1"][0][live]), tol=1e-5)
    for i in (1, 2):  # the pools: every row but the trash row
        F.close(steps["0"][i][:, :5], np.asarray(steps["1"][i][:, :5]), tol=1e-5)
    got_y, got_s, got_c = steps["1"]
    np.testing.assert_array_equal(np.asarray(got_s[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(
        np.asarray(got_s[1, [1, 2, 4]]), np.asarray(pool[1, [1, 2, 4]]))
    np.testing.assert_array_equal(
        np.asarray(got_c[1, [1, 2, 4]]), np.asarray(conv[1, [1, 2, 4]]))
    np.testing.assert_array_equal(np.asarray(got_c[1, 3]), np.asarray(tail[1]))
    np.testing.assert_array_equal(np.asarray(got_c[1, 0]), np.asarray(tail[3]))
    want_y, want_h = attn_ops.ssd_recurrence(
        x[0, :1], dt[0, :1], A, B[0, :1], C[0, :1], D, pool[1, 3])
    F.close(got_y[1], np.asarray(want_y[0]), tol=1e-5)
    F.close(got_s[1, 3], np.asarray(want_h), tol=1e-5)


MULTIPLIERS = [
    ("embedding_multiplier", None), ("lm_head_multiplier", None),
    ("key_multiplier", None), ("attention_in_multiplier", None),
    ("attention_out_multiplier", None), ("ssm_in_multiplier", None),
    ("ssm_out_multiplier", None),
    ("ssm_multipliers", 0), ("ssm_multipliers", 1), ("ssm_multipliers", 2),
    ("ssm_multipliers", 3), ("ssm_multipliers", 4),
    ("mlp_multipliers", 0), ("mlp_multipliers", 1),
]


@pytest.mark.parametrize(
    "name,at", MULTIPLIERS,
    ids=[n if a is None else f"{n}.{a}" for n, a in MULTIPLIERS])
def test_every_multiplier_moves_the_logits(ref, model, name, at):
    """Any single multiplier set to another value moves the logits by far
    more than the comparison's tolerance, in the program and in the
    reference alike (and by the same amount): none can be dropped
    unseen."""
    params, toks, want = model

    def other(value):
        if at is None:
            return value * 1.5
        return tuple(v * 1.5 if i == at else v for i, v in enumerate(value))

    spec = dataclasses.replace(SPEC, **{name: other(getattr(SPEC, name))})
    cfg = dict(CONFIG)
    cfg[name] = (other(tuple(CONFIG[name])) if at is not None
                 else other(CONFIG[name]))
    got = np.asarray(_whole(spec, params, jnp.asarray(toks[0])))
    moved = np.abs(got - want[0]).max()
    assert moved > 100 * TOL, (name, at, moved)
    theirs = np.asarray(ref.forward(
        cfg, SEED, toks[:1], np.arange(T)[None]))[0]
    F.close(got, theirs)


def test_a_checkpoint_in_the_published_layout_round_trips(tmp_path):
    """``save_params`` writes the published ``falcon_h1`` names (``mamba.
    in_proj``, the taps as ``[channels, 1, taps]`` with their bias,
    ``A_log``, ``D``, ``dt_bias``, the gated norm, ``feed_forward.*``,
    ``pre_ff_layernorm``, ``final_layernorm``) and a ``falcon_h1`` config
    with every multiplier; ``load_model_dir`` reads both back."""
    from dynamo_tpu.models import loader

    params = llama.init_params(SPEC, jax.random.PRNGKey(3))
    loader.save_params(SPEC, params, str(tmp_path))
    spec2, params2 = loader.load_model_dir(str(tmp_path), name=SPEC.name)
    # how random tables are drawn is no part of a checkpoint
    assert spec2 == dataclasses.replace(SPEC, vocab_draw_blocks=1)
    assert jax.tree.structure(params2) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    from safetensors import safe_open

    with safe_open(str(tmp_path / "model.safetensors"), "numpy") as f:
        names = set(f.keys())
        assert f.get_tensor(
            "model.layers.1.mamba.conv1d.weight").shape == (96, 1, 4)
        assert f.get_tensor("model.layers.0.mamba.in_proj.weight").shape == (
            32 + 96 + 4, 64)
    assert {"model.layers.0.mamba.A_log", "model.layers.2.mamba.D",
            "model.layers.1.mamba.dt_bias", "model.layers.0.mamba.norm.weight",
            "model.layers.0.mamba.conv1d.bias",
            "model.layers.2.mamba.out_proj.weight",
            "model.layers.0.self_attn.k_proj.weight",
            "model.layers.1.feed_forward.gate_proj.weight",
            "model.layers.0.pre_ff_layernorm.weight",
            "model.final_layernorm.weight", "lm_head.weight"} <= names
    assert "model.norm.weight" not in names


def test_the_tables_are_drawn_in_blocks():
    """The embedding and the head come in ``vocab_draw_blocks`` blocks,
    block ``b`` on its key folded with ``b``: a block alone is
    reproducible without the table."""
    key = jax.random.PRNGKey(5)
    blocks = SPEC.vocab_draw_blocks
    assert blocks == 8 and ModelSpec.tiny().vocab_draw_blocks == 1
    table = llama._draw_blocks(key, 0.02, (96, 64), 0, jnp.float32, blocks)
    head = llama._draw_blocks(key, 0.125, (64, 96), 1, jnp.float32, blocks)
    n = 96 // blocks
    for b in (0, 3, 7):
        part = jax.random.normal(jax.random.fold_in(key, b), (n, 64)) * 0.02
        F.close(table[b * n: (b + 1) * n], np.asarray(part), 1e-7)
        part = jax.random.normal(jax.random.fold_in(key, b), (64, n)) * 0.125
        F.close(head[:, b * n: (b + 1) * n], np.asarray(part), 1e-7)
    with pytest.raises(ValueError, match="does not cut"):
        llama._draw_blocks(key, 1.0, (100, 8), 0, jnp.float32, blocks)


def test_the_memory_guard_charges_the_chunk_form():
    """At the published widths the guard charges the SSD chunk form's
    operands beside the walk's tiles: 10,240-token tables leave a pack of
    2 at 1,024 in 3 GiB (575 MiB charged) and a single row in 512 MiB; a
    model without SSD layers is charged nothing for it."""
    spec = ModelSpec.tiny_falcon_h1(
        hidden_size=5120, num_heads=20, head_dim=128, ssm_heads=32,
        ssm_head_dim=128, ssm_state=256, ssm_chunk=128)
    cfg = EngineConfig(
        page_size=64, num_pages=3584, max_pages_per_seq=160,
        max_decode_slots=128, prefill_buckets=(1024,), prefill_pack_size=2,
        max_prefill_chunk_tokens=1024)
    assert cfg.prefill_shapes(spec, 3 * 2**30) == {1024: 2}
    assert cfg.prefill_shapes(spec, 2**29) == {1024: 1}
    solar = ModelSpec.tiny_solar()
    assert cfg.prefill_shapes(solar, 2**29) == cfg.prefill_shapes(
        dataclasses.replace(solar, ssm_chunk=16), 2**29)
    with pytest.raises(ValueError, match="paged kind first"):
        ModelSpec.tiny_solar(layer_kinds=(
            LayerKind(0, 0.0, mixer="kda"), LayerKind(2, 10000.0)))
