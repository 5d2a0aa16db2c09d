"""Solar-Open2 at toy widths on the CPU, against the benchmark's own plain
reference (``perfbench/references/linear_moe.py``, loaded by path: the same
module the chip is held to, not a copy): KDA layers over a recurrent state
beside the pages of a gated NoPE GQA layer, sigmoid-routed held experts and
a shared expert in every layer. Programs, kernels (interpreted), the state
directory, and the engine's gates around a sequence that owns a state row.
"""

import asyncio
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import kda_step_cases
import pytest

from dynamo_tpu.engine.config import EngineConfig, LayerKind, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import llama
from dynamo_tpu.models.family import GqaFamily, get_family
from dynamo_tpu.ops import attention as attn_ops
from dynamo_tpu.runtime.context import PRIORITY_HEADER, Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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
PAGE, PAGES_PER_SEQ, T, ROWS = 4, 16, 40, 3
SEED = 11


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "linear_moe", os.path.join(REPO, "perfbench/references/linear_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(ref):
    params = llama.init_params(SPEC, jax.random.PRNGKey(SEED))
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (3, T), 0, 96))
    want = np.asarray(ref.forward(
        CONFIG, SEED, toks, np.tile(np.arange(T), (3, 1))))
    return params, toks, want


def _cache(rows=ROWS):
    return llama.init_cache(
        SPEC, 1 + 3 * PAGES_PER_SEQ, PAGE, state_rows=rows)


def _table(row):
    return jnp.arange(PAGES_PER_SEQ, dtype=jnp.int32) + 1 + row * PAGES_PER_SEQ


def _close(got, want, tol=3e-4):
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


# fresh jits a test: the kernel/XLA choice is read at trace time
def _programs():
    return (jax.jit(llama.prefill_forward_impl, static_argnums=(0,)),
            jax.jit(llama.prefill_forward_batch_impl, static_argnums=(0,)),
            jax.jit(llama.decode_forward_impl, static_argnums=(0,)),
            jax.jit(llama.decode_steps_impl, static_argnums=(0,),
                    static_argnames=("n_steps", "n_logprobs")))


def _prefill(pf, params, toks, row, start, n, k, v, bucket=16):
    padded = np.zeros((bucket,), np.int32)
    padded[:n] = toks[row, start: start + n]
    logits, k, v, _ = pf(
        SPEC, params, jnp.asarray(padded), _table(row),
        jnp.asarray(start, jnp.int32), k, v, jnp.asarray(n, jnp.int32),
    )
    return logits, k, v


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernels"])
def test_prefill_then_decode_through_pages_and_state(model, monkeypatch, pallas):
    """A prompt through the prefill program, then teacher-forced decode
    steps through the pages of the GQA layer and the state rows of the
    KDA layers: every position's logits are the reference's whole forward
    pass. The other slots are empty or inactive."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    params, toks, want = model
    pf, _, df, _ = _programs()
    k, v = _cache()
    n = 21
    logits, k, v = _prefill(pf, params, toks, 1, 0, n, k, v, bucket=32)
    _close(logits, want[1, n - 1])
    bts = np.zeros((3, PAGES_PER_SEQ), np.int32)
    bts[2] = np.asarray(_table(1))
    active = np.array([False, False, True])
    for j in range(6):
        fed = np.zeros((3,), np.int32)
        seq = np.ones((3,), np.int32)
        fed[2], seq[2] = toks[1, n + j], n + j + 1
        lg, k, v = df(SPEC, params, jnp.asarray(fed), jnp.asarray(bts),
                      jnp.asarray(seq), k, v, jnp.asarray(active))
        _close(lg[2], want[1, n + j])
    stats = np.asarray(k.rows.stats[0])
    assert stats[llama.STAT_CLAIMS] == 1 and stats[llama.STAT_MISSING] == 0


@pytest.mark.parametrize("chunks", [
    [(0, 37)], [(0, 16), (16, 16), (32, 5)],
], ids=["one-shot", "three-chunks"])
def test_a_chunked_prompt_resumes_the_state(model, monkeypatch, chunks):
    """Chunks at ``start_pos`` > 0 resume the chunkwise kernel from the
    state and the convolution tail the chunk before left in the row: the
    last chunk's logits are the one-shot prefill's and the reference's."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    params, toks, want = model
    pf = _programs()[0]
    k, v = _cache()
    for start, n in chunks:
        logits, k, v = _prefill(
            pf, params, toks, 0, start, n, k, v,
            bucket=64 if n > 16 else 16)
    _close(logits, want[0, 36])
    assert int(k.rows.stats[0, llama.STAT_MISSING]) == 0


def test_packed_rows_equal_single_rows(model, monkeypatch):
    """Three rows of different lengths and an empty row in one packed
    call: each row's logits are the reference's, the empty row claims no
    state, and no two rows share one."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    params, toks, want = model
    pb = _programs()[1]
    k, v = _cache()
    lens = [13, 0, 16, 7]
    padded = np.zeros((4, 16), np.int32)
    bts = np.zeros((4, PAGES_PER_SEQ), np.int32)
    for i, (row, n) in enumerate(zip((0, 0, 1, 2), lens)):
        padded[i, :n] = toks[row, :n]
        if n:
            bts[i] = np.asarray(_table(row))
    logits, k, v, _ = pb(
        SPEC, params, jnp.asarray(padded), jnp.asarray(bts),
        jnp.zeros((4,), jnp.int32), k, v, jnp.asarray(lens, jnp.int32))
    for i, (row, n) in enumerate(zip((0, 0, 1, 2), lens)):
        if n:
            _close(logits[i], want[row, n - 1])
    owner = np.asarray(k.rows.owner[0])
    assert sorted(owner[:ROWS]) == [1, 1 + PAGES_PER_SEQ, 1 + 2 * PAGES_PER_SEQ]
    assert owner[ROWS] == 0  # the trash row is nobody's


def test_bursts_of_one_and_eight_agree(model, monkeypatch):
    """Eight greedy steps as one burst and as eight bursts of one: the
    same tokens, and the same state afterwards (the burst finds its rows
    once; the conv tail and the state carry between steps)."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    params, toks, _ = model
    pf, _, _, ds = _programs()
    B = 3
    bts = np.zeros((B, PAGES_PER_SEQ), np.int32)
    bts[0], bts[1] = np.asarray(_table(0)), np.asarray(_table(1))
    active = jnp.asarray([True, True, False])
    z = jnp.zeros((B,), jnp.int32)

    def run(bursts):
        k, v = _cache()
        for row, n in ((0, 9), (1, 14)):
            _, k, v = _prefill(pf, params, toks, row, 0, n, k, v)
        fed = np.array([toks[0, 9], toks[1, 14], 0], np.int32)
        seq = np.array([10, 15, 1], np.int32)
        out = []
        for n_steps in bursts:
            o, k, v = ds(
                SPEC, params, jnp.asarray(fed), jnp.asarray(bts),
                jnp.asarray(seq), k, v, active, jnp.zeros((B,)), z,
                jnp.ones((B,)), jnp.zeros((B,), jnp.uint32), z,
                n_steps=n_steps, n_logprobs=0)
            o = np.asarray(o)
            out.append(o[:2])
            fed[:2], seq[:2] = o[:2, -1], seq[:2] + n_steps
        return np.concatenate(out, axis=1), k

    one, k1 = run([1] * 8)
    eight, k8 = run([8])
    np.testing.assert_array_equal(one, eight)
    _close(k8.pools[1][:, :2], np.asarray(k1.pools[1][:, :2]), tol=1e-5)


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


def _kda_named(name):
    """A pack of 2 for ``kda_chunk_prefill``: (q, k, v, g, beta, s0, fresh
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


def _chunk(q, k, v, g, beta, s0, fresh=None, trash=None):
    """``kda_chunk_prefill`` from and to rows 1.. of a pool whose row 0
    must come back as it was and whose last row is trash (a member of
    ``trash`` is sent there). Returns (o, the members' rows, the pool)."""
    N = q.shape[0]
    pool = jnp.concatenate([s0[:1] * 0 + 7.0, s0, s0[:1]])[None]
    rows = np.arange(1, N + 1)
    if trash is not None:
        rows = np.where(trash, N + 1, rows)
    o, new = attn_ops.kda_chunk_prefill(
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
    for n in range(q.shape[0]):
        r = real[n]
        want_o, want_s = attn_ops.kda_recurrence(
            q[n, :r], k[n, :r], v[n, :r], g[n, :r], beta[n, :r],
            s0[n] * (0.0 if fresh[n] or trash[n] else 1.0))
        if trash[n]:
            np.testing.assert_array_equal(np.asarray(s[n]), np.asarray(s0[n]))
            continue
        _close(o[n, :r], np.asarray(want_o), tol=KDA_LOOSE.get(case, 2e-5))
        _close(s[n], np.asarray(want_s), tol=KDA_LOOSE.get(case, 2e-5))


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
            _close(outs["1"][0][n, :r], np.asarray(outs["0"][0][n, :r]), tol=tol)
        # every row but the trash row
        _close(outs["1"][2][:, :-1], np.asarray(outs["0"][2][:, :-1]), tol=tol)
        return
    q, k, v, g, beta, s0 = _kda_case(128, seed=5)
    outs = {}
    for pallas in ("0", "1"):
        monkeypatch.setenv("DYNAMO_PALLAS", pallas)
        outs[pallas] = _chunk(q, k, v, g, beta, s0)[:2]
        # a fresh row starts from zero whatever the pool held
        fresh = attn_ops.kda_chunk_prefill(
            q[:1], k[:1], v[:1], g[:1], beta[:1], s0[None, :2],
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool), layer=0)
        want = attn_ops.kda_recurrence(
            q[0], k[0], v[0], g[0], beta[0], s0[0] * 0)
        _close(fresh[0][0], np.asarray(want[0]), tol=2e-5)
        _close(fresh[1][0, 0], np.asarray(want[1]), tol=2e-5)
    for a, b in zip(outs["0"], outs["1"]):
        _close(a, np.asarray(b), tol=1e-5)

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
    _close(steps["0"][0][live], np.asarray(steps["1"][0][live]), tol=1e-5)
    for i in (1, 2):  # the pools: every row but the trash row
        _close(steps["0"][i][:, :5], np.asarray(steps["1"][i][:, :5]), tol=1e-5)
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
    _close(got_o[1], np.asarray(want_o[0]), tol=1e-5)
    _close(got_s[1, 3], np.asarray(want_s), tol=1e-5)


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
        _close(alike + sum(s - alike for s in shares), whole, tol=1e-4)
    spec = ModelSpec.tiny_solar(
        held_experts=(4, 2), num_layers=2, layer_pattern=(0, 1))
    params = llama.init_params(spec, jax.random.PRNGKey(SEED))
    got = llama.reference_forward(spec, params, jnp.asarray(toks[0]))
    want = ref.forward(cfg, SEED, toks, np.arange(10)[None].repeat(2, 0))
    _close(got, np.asarray(want)[0])


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


# ------------------------------------------------------------- the engine


def _engine(**kw):
    base = dict(
        page_size=PAGE, num_pages=64, max_pages_per_seq=PAGES_PER_SEQ,
        max_decode_slots=2, prefill_buckets=(16,), max_prefill_chunk_tokens=16,
        decode_steps_per_dispatch=4, seed=SEED,
    )
    base.update(kw)
    return InferenceEngine(SPEC, EngineConfig(**base))


async def _greedy(engine, prompt, n, out=None, ctx=None):
    out = [] if out is None else out
    async for item in engine.generate(
        {"token_ids": list(prompt), "sampling": {"temperature": 0.0},
         "stop_conditions": {"max_tokens": n, "ignore_eos": True}},
        ctx or Context(),
    ):
        assert item.get("finish_reason") != "error", item
        out.extend(item.get("token_ids") or [])
    return out


_jit_reference = jax.jit(llama.reference_forward, static_argnums=0)


def _greedy_reference(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((64,), np.int32)
        padded[: len(seq)] = seq
        lg = _jit_reference(SPEC, params, jnp.asarray(padded))
        seq.append(int(np.argmax(np.asarray(lg[len(seq) - 1]))))
    return seq[len(prompt):]


async def test_serves_through_the_engine_and_counts(monkeypatch):
    """The toy model through the REAL engine (scheduler, a prompt of two
    chunks, both kernels interpreted in bursts): the greedy stream is the
    whole forward pass's own; the same prompt a second time gives the same
    tokens and seals nothing (no page is reused under a prefix: it holds
    no state); the rows go back; the counters read what hand arithmetic
    gives."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    engine = _engine()
    fam = engine.fam
    assert isinstance(fam, GqaFamily) and fam.recurrent
    assert not fam.supports_prefix_reuse and not engine.allocator.prefix_cache
    prompt = [int(t) for t in np.arange(7, 7 + 21) % 96]  # two chunks
    want = _greedy_reference(engine.params, prompt, 6)
    assert await _greedy(engine, prompt, 6) == want
    assert await _greedy(engine, prompt, 6) == want
    assert engine.allocator._hash_page == {}
    assert engine.allocator.evictable_pages == 0
    assert engine.prefix_hit_tokens(prompt) == 0
    assert engine.allocator.active_pages == 0
    # two prompts of 16 + 5 tokens: a block of 64 holds each chunk; five
    # decode steps a prompt served in bursts of 4, each counted whole
    assert engine.kda["prefill_blocks"] == 4
    assert engine.kda["decode_rows"] % 4 == 0 and engine.kda["decode_rows"] >= 16
    await engine.close()
    engine._metrics_publishes = 0
    for _ in range(34):  # two refreshes bring the device's counters over
        engine._publish_metrics()
    c = engine.state_counters()
    assert c == {"rows": 2, "rows_live": 0, "claims": 2, "row_missing": 0}
    engine._flush_state_releases()
    assert list(np.asarray(engine.k_pages.rows.owner[0])) == [0, 0, 0]
    snap = engine.profile_snapshot()
    assert snap["recurrent_state.rows"]["calls"] == 2
    assert snap["kda.prefill_blocks"]["calls"] == 4
    m = engine.moe_counters()
    assert m["layers"] == 4 and m["prefill.assignments"] == 4 * 2 * 21 * 2


@pytest.mark.parametrize("pipeline", [False, True], ids=["plain", "pipelined"])
async def test_streams_share_the_engine(monkeypatch, pipeline):
    """Three prompts on two slots, one of them chunked behind running
    bursts: every stream is what it gets alone, pipelined or not, rows
    are claimed and freed as slots turn over, none goes missing."""
    monkeypatch.setenv("DYNAMO_PALLAS", "1")
    prompts = [[3, 9, 27], [8, 64, 32, 5],
               [int(t) for t in np.arange(5, 5 + 37) * 7 % 96]]
    engine = _engine(pipeline_decode=pipeline, async_admissions=True)
    want = [_greedy_reference(engine.params, p, n)
            for p, n in zip(prompts, (12, 9, 6))]
    outs = await asyncio.gather(*(
        _greedy(engine, p, n) for p, n in zip(prompts, (12, 9, 6))))
    assert outs == want
    assert engine.allocator.active_pages == 0
    await engine.close()
    assert int(engine.k_pages.rows.stats[0, llama.STAT_MISSING]) == 0
    assert int(engine.k_pages.rows.stats[0, llama.STAT_CLAIMS]) == 3


async def test_preempt_and_resume_by_recomputation(monkeypatch):
    """A batch stream preempted for an interactive one gives its row and
    pages back and resumes by prefilling its prompt and its output so far
    from an empty state: the tokens of an undisturbed run."""
    monkeypatch.setenv("DYNAMO_PALLAS", "0")
    prompt = [5, 11, 17, 23, 29]
    engine = _engine(max_decode_slots=1, prefill_buckets=(16, 32, 64),
                     max_prefill_chunk_tokens=64)
    want = _greedy_reference(engine.params, prompt, 24)
    got: list = []
    batch = asyncio.create_task(_greedy(
        engine, prompt, 24, out=got,
        ctx=Context(headers={PRIORITY_HEADER: "batch"})))
    while len(got) < 6:
        await asyncio.sleep(0.002)
    quick = await _greedy(engine, [2, 4, 6], 3)
    assert quick == _greedy_reference(engine.params, [2, 4, 6], 3)
    assert await batch == want
    assert sum(engine.preemptions.values()) >= 1
    assert engine.allocator.active_pages == 0
    await engine.close()
    assert int(engine.k_pages.rows.stats[0, llama.STAT_MISSING]) == 0


def _fallbacks(*reasons):
    from dynamo_tpu.ops import fallback

    return [fallback._FALLBACKS.labels(r)._value.get() for r in reasons]


async def test_every_gate_counts_its_reason():
    """What moves, reuses or rolls back pages alone is off for a model
    with recurrent layers, by the family's attributes; what is asked for
    anyway joins the fallback series under its own reason."""
    fam = get_family(SPEC)
    assert fam.recurrent
    for gate in ("ring_prefill", "spec_decode", "mesh", "prefix_reuse",
                 "page_transfer", "multimodal"):
        assert not getattr(fam, f"supports_{gate}"), gate
    assert fam.supports_packed_prefill
    plain = get_family(ModelSpec.tiny())
    assert plain.supports_prefix_reuse and plain.supports_page_transfer
    assert not plain.recurrent

    names = ("recurrent_no_page_offload", "recurrent_no_spec_decode",
             "recurrent_no_ring_prefill", "recurrent_no_page_transfer")
    before = _fallbacks(*names)
    from dynamo_tpu.kvbm import KvBlockManager, KvbmConfig

    engine = InferenceEngine(
        SPEC, EngineConfig(
            page_size=PAGE, num_pages=64, max_pages_per_seq=PAGES_PER_SEQ,
            max_decode_slots=2, prefill_buckets=(16,), spec_mode="ngram",
            sp=2, seed=SEED,
        ), kvbm=KvBlockManager(KvbmConfig(host_bytes=1 << 20)),
    )
    assert engine.kvbm is None and engine.offload is None
    assert not engine._spec_on
    assert [b - a for a, b in zip(before, _fallbacks(*names))] == [1, 1, 1, 0]
    # a decode-side disaggregated request: the pull is refused, counted,
    # and the stream is served by a local prefill of prompt + first token
    out = []
    async for item in engine.generate(
        {"token_ids": [4, 8, 15, 16], "sampling": {"temperature": 0.0},
         "stop_conditions": {"max_tokens": 4, "ignore_eos": True},
         "disagg": {"mode": "decode", "kv_transfer": {
             "first_token": 23, "address": "127.0.0.1:1", "handle": "x"}}},
        Context(),
    ):
        assert item.get("finish_reason") != "error", item
        out.extend(item.get("token_ids") or [])
    assert out == _greedy_reference(engine.params, [4, 8, 15, 16, 23], 3)
    assert _fallbacks(names[3])[0] - before[3] == 1
    await engine.close()
    # the programs with no recurrent form say so to a direct caller
    k, v = _cache()
    with pytest.raises(NotImplementedError, match="speculative verify"):
        llama.verify_forward_impl(
            SPEC, engine.params, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1, PAGES_PER_SEQ), jnp.int32), jnp.zeros((1,), jnp.int32),
            k, v, jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match="meshes"):
        from dynamo_tpu.parallel.mesh import make_mesh

        InferenceEngine(SPEC, EngineConfig(seed=SEED), mesh=make_mesh(tp=2, dp=1))


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
