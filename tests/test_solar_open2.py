"""Solar-Open2 at toy widths on the CPU, against the benchmark's own plain
reference (``perfbench/references/linear_moe.py``, loaded by path: the same
module the chip is held to, not a copy): KDA layers over a recurrent state
beside the pages of a gated NoPE GQA layer, sigmoid-routed held experts and
a shared expert in every layer. Programs, kernels (interpreted), the state
directory, and the engine's gates around a sequence that owns a state row.
"""

import functools
import os

import jax
import jax.numpy as jnp
import kda_step_cases
import numpy as np
import pytest
from family_contract import Family, _whole, case, cases, gates, preempt, run

from dynamo_tpu.engine.config import EngineConfig, LayerKind, ModelSpec
from dynamo_tpu.models import llama
from dynamo_tpu.ops import attention as attn_ops

# the reference reads the published keys; the program reads SPEC
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 4, "layers_kept": [0, 1, 2, 3],
    "gqa_layers": [0, 4, 8], "use_rope": False, "use_gqa_gate": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "first_k_dense_replace": 0, "intermediate_size": 64,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "vocab_size": 96,
    "torch_dtype": "float32",
    "experts": {"published": 8, "held": 4, "first": 2},
}
SPEC = ModelSpec.tiny_solar(held_experts=(4, 2))
PAGE, ROWS = 4, 3
SEED = 11


def _served(engine, snap, served, outs):
    """Two prompts of 16 + 5 tokens: a block of 64 holds each chunk; five
    decode steps a prompt served in bursts of 4, each counted whole."""
    assert engine.kda["prefill_blocks"] == 4
    assert engine.kda["decode_rows"] % 4 == 0 and engine.kda["decode_rows"] >= 16
    assert snap["recurrent_state.rows"]["calls"] == 2
    assert snap["kda.prefill_blocks"]["calls"] == 4
    m = engine.moe_counters()
    assert m["layers"] == 4 and m["prefill.assignments"] == 4 * 2 * 21 * 2


# the family's row of the contract (tests/family_contract.py); its pack is
# three rows of different lengths and an empty one in ONE call
F = FAMILY = Family(
    spec=SPEC, config=CONFIG, reference="linear_moe",
    seed=SEED, state_rows=ROWS, chunked_paths=("1",), pack_path="1",
    packs=([(0, 0, 13), (0, 0, 0), (1, 0, 16), (2, 0, 7)],),
    inactive_paths=("1",), streams=(False, True),
    also={"serves": _served})


@pytest.mark.parametrize("case,kw", cases(
    F, case("engine-preempt", preempt), case("engine-gates", gates)))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


def _kda_case(T_, beta_scale=2.0, seed=3, N=2, H=4, D=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (N, T_, H, D))
    k = jax.random.normal(ks[1], (N, T_, H, D))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (N, T_, H, D))
    # decays from a whisker under 1 to e^-3.3 a token
    g = -jnp.exp(jax.random.uniform(ks[3], (N, T_, H, D), minval=-9, maxval=1.2))
    beta = beta_scale * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (N, T_, H)))
    s0 = jax.random.normal(ks[5], (N, H, D, D))
    return q, k, v, g, beta, s0


# the chunkwise form's cases: tokens a row, and what is odd about the pack
KDA_CASES = {
    "one-block": 64, "ragged": 150, "sub-block": 16, "long": 1024,
    "fresh-beside-resumed": 150, "trash-member": 150, "padded": 150,
    "parallel-keys": 128, "edge-decay": 128,
}
# what the chunkwise form itself loses against the recurrence where it is
# worst conditioned (the XLA twin reads the same): the inverse decay at the
# edge of its bound, T's inverse alternating +-2
KDA_LOOSE = {"edge-decay": 1e-4, "parallel-keys": 5e-5}


@functools.cache
def _kda_named(name):
    """Made once a worker: a pack of 2 for ``kda_chunk_prefill``: (q, k, v, g, beta, s0, fresh
    [2], trash [2], real [2]). ``fresh-beside-resumed``: member 0 starts
    from zero whatever its row holds; ``trash-member``: member 1 owns no
    row; ``padded``: tokens past ``real`` carry g = 0 and beta = 0;
    ``parallel-keys``: beta = 2 on keys within 2% of one direction (T's
    inverse alternates +-2: a doubling product would lose it);
    ``edge-decay``: -4.9 a token, the edge of the sub-block's e^-80."""
    T_ = KDA_CASES[name]
    q, k, v, g, beta, s0 = _kda_case(T_, seed=3 + len(name))
    fresh, trash, real = np.zeros(2, bool), np.zeros(2, bool), [T_, T_]
    if name == "fresh-beside-resumed":
        fresh[0] = True
    elif name == "trash-member":
        trash[1] = True
    elif name == "padded":
        real = [100, 37]
        live = jnp.arange(T_)[None, :] < jnp.asarray(real)[:, None]
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    elif name == "parallel-keys":
        k = k[:, :1] + 0.02 * k
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        beta = jnp.full_like(beta, 2.0)
    elif name == "edge-decay":
        g = jnp.full_like(g, -4.9)
    return q, k, v, g, beta, s0, fresh, trash, real


# a decode path (``DYNAMO_PALLAS``, read at TRACE time) a jit: the chunk
# form and the recurrence are traced once a shape a worker
_CHUNK = {path: jax.jit(
    lambda *a, **kw: attn_ops.kda_chunk_prefill(*a, **kw),
    static_argnames=("layer",)) for path in ("0", "1")}
_recurrence = jax.jit(attn_ops.kda_recurrence)


@functools.cache
def _kda_wanted(name):
    """The recurrence a token at a time over each member's real tokens of
    ``_kda_named(name)``: [(o, the state it leaves)]."""
    q, k, v, g, beta, s0, fresh, trash, real = _kda_named(name)
    return [_recurrence(
        q[n, :r], k[n, :r], v[n, :r], g[n, :r], beta[n, :r],
        s0[n] * (0.0 if fresh[n] or trash[n] else 1.0))
        for n, r in enumerate(real)]


def _chunk(q, k, v, g, beta, s0, fresh=None, trash=None):
    """``kda_chunk_prefill`` from and to rows 1.. of a pool whose row 0
    must come back as it was and whose last row is trash (a member of
    ``trash`` is sent there). Returns (o, the members' rows, the pool)."""
    N = q.shape[0]
    pool = jnp.concatenate([s0[:1] * 0 + 7.0, s0, s0[:1]])[None]
    rows = np.arange(1, N + 1)
    if trash is not None:
        rows = np.where(trash, N + 1, rows)
    o, new = _CHUNK[os.environ["DYNAMO_PALLAS"]](
        q, k, v, g, beta, pool, jnp.asarray(rows, jnp.int32),
        jnp.asarray(np.zeros(N, bool) if fresh is None else fresh), layer=0)
    np.testing.assert_array_equal(np.asarray(new[0, 0]), np.asarray(pool[0, 0]))
    return o, new[0, 1: N + 1], new


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernel"])
@pytest.mark.parametrize("case", list(KDA_CASES))
def test_kda_chunk_equals_the_token_recurrence(monkeypatch, pallas, case):
    """The chunkwise form over blocks of 64 tokens (one of 16 for a short
    row), from a non-zero state, with beta over 1 in the draw (negative
    eigenvalues) and decays down to e^-3.3 a token, is the recurrence a
    token at a time over each member's real tokens: outputs and the state
    it leaves. A member on the trash row leaves its own row as it was."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    q, k, v, g, beta, s0, fresh, trash, real = _kda_named(case)
    assert float(beta.max()) > 1.5
    o, s, _ = _chunk(q, k, v, g, beta, s0, fresh, trash)
    for n, (want_o, want_s) in enumerate(_kda_wanted(case)):
        r = real[n]
        if trash[n]:
            np.testing.assert_array_equal(np.asarray(s[n]), np.asarray(s0[n]))
            continue
        F.close(o[n, :r], np.asarray(want_o), tol=KDA_LOOSE.get(case, 2e-5))
        F.close(s[n], np.asarray(want_s), tol=KDA_LOOSE.get(case, 2e-5))


@pytest.mark.parametrize("case", ["both-kernels"] + list(KDA_CASES))
def test_kda_kernels_equal_their_xla_twins(monkeypatch, case):
    """Both kernels, interpreted, against the XLA forms that serve off the
    chip: the chunk form whole (its operands formed in the kernel against
    ``kda_chunk_operands`` and the scan), and (``both-kernels``) the decode
    step over a pool with a trash row (two slots on it), whose other rows
    stay as they were."""
    if case != "both-kernels":
        q, k, v, g, beta, s0, fresh, trash, real = _kda_named(case)
        outs = {}
        for pallas in ("0", "1"):
            monkeypatch.setenv("DYNAMO_PALLAS", pallas)
            outs[pallas] = _chunk(q, k, v, g, beta, s0, fresh, trash)
        tol = KDA_LOOSE.get(case, 1e-5)
        for n, r in enumerate(real):
            F.close(outs["1"][0][n, :r], np.asarray(outs["0"][0][n, :r]), tol=tol)
        # every row but the trash row
        F.close(outs["1"][2][:, :-1], np.asarray(outs["0"][2][:, :-1]), tol=tol)
        return
    q, k, v, g, beta, s0 = _kda_case(128, seed=5)
    outs = {}
    for pallas in ("0", "1"):
        monkeypatch.setenv("DYNAMO_PALLAS", pallas)
        outs[pallas] = _chunk(q, k, v, g, beta, s0)[:2]
        # a fresh row starts from zero whatever the pool held
        fresh = _CHUNK[pallas](
            q[:1], k[:1], v[:1], g[:1], beta[:1], s0[None, :2],
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool), layer=0)
        want = _recurrence(q[0], k[0], v[0], g[0], beta[0], s0[0] * 0)
        F.close(fresh[0][0], np.asarray(want[0]), tol=2e-5)
        F.close(fresh[1][0, 0], np.asarray(want[1]), tol=2e-5)
    for a, b in zip(outs["0"], outs["1"]):
        F.close(a, np.asarray(b), tol=1e-5)

    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 6, 4, 16, 16))
    conv = jax.random.normal(jax.random.PRNGKey(8), (2, 6, 3, 3, 64))
    x = jax.random.normal(jax.random.PRNGKey(7), (5, 3, 64))
    taps = jax.random.normal(jax.random.PRNGKey(6), (4, 3, 64)) * 0.5
    # row 5 is the trash row: a dead slot first, between and last
    rows = jnp.asarray([5, 3, 5, 0, 5], jnp.int32)
    at = jnp.asarray([0, 0, 1, 1, 2])  # the gates a slot brings
    steps = {}
    for pallas in ("0", "1"):
        monkeypatch.setenv("DYNAMO_PALLAS", pallas)
        steps[pallas] = attn_ops.kda_decode_step(
            pool, conv, rows, x, taps, g[0, at], beta[0, at], layer=1)
    live = np.asarray([1, 3])
    for x_, y in zip(steps["0"], steps["1"]):
        assert x_.shape == y.shape
    F.close(steps["0"][0][live], np.asarray(steps["1"][0][live]), tol=1e-5)
    for i in (1, 2):  # the pools: every row but the trash row
        F.close(steps["0"][i][:, :5], np.asarray(steps["1"][i][:, :5]), tol=1e-5)
    got_o, got_s, got_c = steps["1"]
    np.testing.assert_array_equal(np.asarray(got_s[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(
        np.asarray(got_s[1, [1, 2, 4]]), np.asarray(pool[1, [1, 2, 4]]))
    np.testing.assert_array_equal(
        np.asarray(got_c[1, [1, 2, 4]]), np.asarray(conv[1, [1, 2, 4]]))
    # a live slot's tail: its old rows but the first, then its new row
    for b in live:
        np.testing.assert_array_equal(
            np.asarray(got_c[1, rows[b]]),
            np.asarray(jnp.concatenate([conv[1, rows[b], 1:], x[b][None]])))
    # the step by hand: the taps over [tail; x], SiLU, the norms, the
    # recurrence a token at a time
    ext = jnp.concatenate([conv[1, 3], x[1][None]])  # [4, 3, 64]
    q1, k1, v1 = jax.nn.silu((taps * ext).sum(0)).reshape(3, 4, 16)
    q1 = q1 / jnp.sqrt((q1 * q1).sum(-1, keepdims=True) + 1e-6) / 4.0
    k1 = k1 / jnp.sqrt((k1 * k1).sum(-1, keepdims=True) + 1e-6)
    want_o, want_s = attn_ops.kda_recurrence(
        q1[None], k1[None], v1[None], g[0, :1], beta[0, :1], pool[1, 3])
    F.close(got_o[1], np.asarray(want_o[0]), tol=1e-5)
    F.close(got_s[1, 3], np.asarray(want_s), tol=1e-5)


@pytest.mark.parametrize("case", kda_step_cases.CASES)
def test_kda_step_from_the_projections_equals_its_xla_twin(monkeypatch, case):
    """``kda_step``'s new half (the tail's shift, the taps, SiLU, the
    norms, the columns: in the kernel) against its XLA twin through the
    layer's decode function, under THIS model's gates (low rank, softplus
    decay, beta doubled): the output, the state AND the tails' pool; a
    slot on the trash row among live ones; a tail that a ragged pack's
    prefill just wrote; 8 steps in a row; bf16 tails
    (``tests/kda_step_cases.py``)."""
    kda_step_cases.check(monkeypatch, SPEC, 1, case)


def test_the_state_directory_under_tables_anyone_may_build():
    """The directory as ``perfbench/lib/correct.py`` drives it: tables it
    builds itself (a row's pages back to back from page 1), no slot
    argument, no release. A prefill claims a row; a chunk and a decode
    step find it; an owner that comes back at position 0 keeps its row
    and starts from zero; with every row owned the least recently used is
    taken over; a row that should be there and is not is counted; a table
    on the trash page and an inactive slot own nothing."""
    rows = llama.init_cache(SPEC, 8, PAGE, state_rows=2)[0].rows
    i32 = lambda *x: jnp.asarray(x, jnp.int32)  # noqa: E731
    yes = jnp.asarray([True])

    idx, fresh, rows = llama._claim_state_rows(rows, i32(1), i32(0), yes)
    assert (int(idx[0]), bool(fresh[0])) == (0, True)
    idx, fresh, rows = llama._claim_state_rows(rows, i32(11), i32(0), yes)
    assert (int(idx[0]), bool(fresh[0])) == (1, True)
    # a later chunk of the first, then decode steps of both and a dead slot
    idx, fresh, rows = llama._claim_state_rows(rows, i32(1), i32(16), yes)
    assert (int(idx[0]), bool(fresh[0])) == (0, False)
    idx, rows = llama._find_state_rows(
        rows, i32(11, 1, 7, 0), jnp.asarray([True, True, False, True]))
    assert list(np.asarray(idx)) == [1, 0, 2, 2]  # 2 = the trash row
    assert int(rows.stats[0, llama.STAT_MISSING]) == 0
    # the same owner again from position 0: its own row, zeroed
    idx, fresh, rows = llama._claim_state_rows(rows, i32(11), i32(0), yes)
    assert (int(idx[0]), bool(fresh[0])) == (1, True)
    # touch owner 1 last, then a third owner takes over the older (11's)
    idx, rows = llama._find_state_rows(rows, i32(1), yes)
    idx, fresh, rows = llama._claim_state_rows(rows, i32(21), i32(0), yes)
    assert int(idx[0]) == 1 and list(np.asarray(rows.owner[0])) == [1, 21, 0]
    # 11 has lost its row: a chunk and a step of it run on the trash row
    idx, fresh, rows = llama._claim_state_rows(rows, i32(11), i32(16), yes)
    assert int(idx[0]) == 2
    idx, rows = llama._find_state_rows(rows, i32(11), yes)
    assert int(idx[0]) == 2
    assert int(rows.stats[0, llama.STAT_MISSING]) == 2
    assert int(rows.stats[0, llama.STAT_CLAIMS]) == 3
    # a pack: two new owners must not take one row; an empty member and a
    # table on the trash page claim nothing
    rows = llama.init_cache(SPEC, 8, PAGE, state_rows=2)[0].rows
    idx, fresh, rows = llama._claim_state_rows(
        rows, i32(5, 9, 13, 0), i32(0, 0, 0, 0),
        jnp.asarray([True, True, False, True]))
    assert list(np.asarray(idx)) == [0, 1, 2, 2]
    assert list(np.asarray(fresh)) == [True, True, False, False]
    # the engine frees rows by the pages it lets go of
    k = llama.init_cache(SPEC, 8, PAGE, state_rows=2)[0]._replace(rows=rows)
    k = llama.release_state_rows(k, i32(9, 4, -1, -1))
    assert list(np.asarray(k.rows.owner[0])) == [5, 0, 0]


def test_the_shares_add_up(ref):
    """Two chips of four experts each, the shared expert counted once,
    make the uncut expert layer: the sum over the shares of what each adds
    to the residual, less the surplus copy of what both compute alike (the
    mixer and the shared expert), is the layer with all 8 experts; and the
    program's share is the reference's share."""
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 10), 0, 96))
    cfg = dict(CONFIG, num_hidden_layers=2, layers_kept=[0, 1])
    x = ref._embed_rows(ref.Weights(cfg, SEED).embed(), toks, quant=None)

    def layer(i, held, first):
        c = dict(cfg, experts={"published": 8, "held": held, "first": first})
        wc = ref.Weights(c, SEED)
        lw = wc.layer(i)
        if held < 8:  # a share's experts are the uncut layer's own
            full = ref.Weights(
                dict(cfg, experts={"published": 8, "held": 8, "first": 0}),
                SEED).layer(i)
            for name in ("e_gate", "e_up", "e_down"):
                lw[name] = full[name][first: first + held]
        return np.asarray(ref._layer(wc.m, i, x, lw, None)), wc, lw

    for i in (0, 1):  # the GQA layer's experts, then a KDA layer's
        whole, wc, lw = layer(i, 8, 0)
        m = wc.m
        none = dict(m, held=0)  # the mixer and the shared expert alone
        alike = np.asarray(ref._layer(
            none, i, x, dict(lw, e_gate=lw["e_gate"][:0]), None))
        shares = [layer(i, 4, first)[0] for first in (0, 4)]
        F.close(alike + sum(s - alike for s in shares), whole, tol=1e-4)
    spec = ModelSpec.tiny_solar(
        held_experts=(4, 2), num_layers=2, layer_pattern=(0, 1))
    params = llama.init_params(spec, jax.random.PRNGKey(SEED))
    got = _whole(spec, params, jnp.asarray(toks[0]))
    want = ref.forward(cfg, SEED, toks, np.arange(10)[None].repeat(2, 0))
    F.close(got, np.asarray(want)[0])


def test_a_checkpoint_in_the_published_layout_round_trips(tmp_path):
    """``save_params`` writes the published names (the taps as ``[channels,
    1, taps]``, ``A_log``, ``dt_bias``, the low-rank pairs, the router's
    correction bias, the shared expert) and a ``solar_open2`` config;
    ``load_model_dir`` reads both back: the same spec, the same tree."""
    from dynamo_tpu.models import loader

    spec = ModelSpec.tiny_solar()
    params = llama.init_params(spec, jax.random.PRNGKey(3))
    loader.save_params(spec, params, str(tmp_path))
    spec2, params2 = loader.load_model_dir(str(tmp_path), name=spec.name)
    assert spec2 == spec
    assert jax.tree.structure(params2) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    from safetensors import safe_open

    with safe_open(str(tmp_path / "model.safetensors"), "numpy") as f:
        names = set(f.keys())
        assert f.get_tensor(
            "model.layers.1.self_attn.q_conv1d.weight").shape == (64, 1, 4)
    assert {"model.layers.0.self_attn.g_proj.weight",
            "model.layers.1.self_attn.A_log",
            "model.layers.2.self_attn.f_b_proj.weight",
            "model.layers.3.mlp.gate.e_score_correction_bias",
            "model.layers.0.mlp.shared_experts.up_proj.weight"} <= names
    assert "model.layers.1.self_attn.g_proj.weight" not in names


def test_the_memory_guard_offers_packs_beside_a_long_table():
    """A model with recurrent layers is charged what its programs hold (a
    tile of the walk, the chunkwise form's operands), not scores against
    the whole table: 10,240-token tables leave a pack of 2 at 1,024."""
    spec = ModelSpec.tiny_solar(
        hidden_size=4096, num_heads=64, head_dim=128, kda_heads=64,
        kda_head_dim=128)
    cfg = EngineConfig(
        page_size=64, num_pages=10240, max_pages_per_seq=160,
        max_decode_slots=256, prefill_buckets=(1024,), prefill_pack_size=2,
        max_prefill_chunk_tokens=1024)
    assert cfg.prefill_shapes(spec, 4 * 2**30) == {1024: 2}
    assert cfg.prefill_shapes(spec, 2**30) == {1024: 1}
    with pytest.raises(ValueError, match="paged kind first"):
        ModelSpec.tiny_solar(layer_kinds=(
            LayerKind(0, 0.0, mixer="kda"), LayerKind(2, 10000.0)))
