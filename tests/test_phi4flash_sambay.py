"""Phi-4-mini-flash (SambaY) at toy widths on the CPU, against the
benchmark's own plain reference (``perfbench/references/sambay.py``, loaded
by path: the same module the chip is held to, not a copy): Mamba-1 scan
layers beside window layers of differential attention, ONE full layer
whose pages the cross layers read, GMU layers that read the last scan's
output, and a prefill that sends one row a sequence through the upper
half. Programs, the scan's decode kernel (interpreted) and its XLA twin,
the chunk form, the counters, the published names.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from family_contract import (
    CHUNKS, Family, _cache, _model, _prefill, _programs, _reference, _step,
    _table, _whole, cases, run, tokens,
)


from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.models import llama, loader
from dynamo_tpu.models.family import get_family
from dynamo_tpu.ops import attention as attn_ops

# the reference reads the published keys; the program reads the spec the
# loader makes of them. A toy cut of a 32-layer stack: S W | S F | G C G C
CONFIG = {
    "name": "toy-phi4flash", "model_type": "phi4flash", "hidden_size": 64,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "vocab_size": 96, "num_hidden_layers": 8,
    "layers_kept": [0, 1, 16, 17, 18, 19, 20, 21],
    "published_layers": 32, "sliding_window": 8, "layer_norm_eps": 1e-5,
    "mb_per_layer": 2, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "torch_dtype": "float32",
}
SPEC = dataclasses.replace(
    loader.spec_from_hf_config(CONFIG, name="toy-phi4flash"),
    vocab_draw_blocks=8)
PAGE, T, ROWS = 4, 40, 3
SEED = 11
TOL = 2e-5  # float32 on both sides; logits of magnitude ~0.5


def _served(engine, snap, served, outs):
    """Prompts of 30 and 7 tokens, over and under the window of 8: every
    token is the PLAIN reference's choice. By hand: the prompts' tokens
    through the lower layers, one row each through the upper; two cross
    layers, to one window layer, read the live rows at every step."""
    for (prompt, _), out in zip(served, outs):
        row = np.concatenate([prompt, out]).astype(np.int32)[None]
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))[None]
        want = np.asarray(_reference(F).forward(CONFIG, SEED, row, at))[0]
        assert out == [int(t) for t in want.argmax(-1)]
    assert snap["prefill.rows"]["calls"] == 37
    assert snap["prefill.cross_rows"]["calls"] == 2
    steps = snap["kv.shared_read_tokens"]["calls"]
    assert steps and steps % 2 == 0
    assert steps >= 2 * (sum(range(31, 39)) + sum(range(8, 10)))
    assert 2 * snap["kv.window_layer_tokens"]["calls"] == steps


def _decodes(k, v, members):
    """A decode step behind the chunks or the pack (``members``: table
    row, start, tokens) reads what they wrote: pages, state and tails."""
    params, toks, want = _model(F)
    at = {s: (row, start + n)
          for s, (row, start, n) in enumerate(members) if n}
    lg, k, v = _step(F, _programs(F)[2], params, toks, at, k, v)
    for s, (row, n) in at.items():
        F.close(lg[s], want[row, n])


# the family's row of the contract (tests/family_contract.py): prompts
# under and over the window of 8 on both paths; the absolute difference
# under TOL; a pack of three; the engine packs two and holds four slots
F = FAMILY = Family(
    spec=SPEC, config=CONFIG, reference="sambay",
    seed=SEED, tol=TOL, rtol=0.0, state_rows=ROWS,
    prompts=((5, "0"), (5, "1"), (30, "0"), (30, "1")),
    chunked={"three-chunks": CHUNKS["three-chunks"]},
    packs=([(0, 0, 30), (0, 0, 0), (2, 0, 21)],),
    bursts_paths=(None,), inactive_paths=("1",), engine_path=None,
    engine=dict(num_pages=96, max_decode_slots=4, prefill_buckets=(16, 32),
                max_prefill_chunk_tokens=32, prefill_pack_size=2),
    served=((tokens()[0, :30], 9), (tokens()[2, :7], 3)),
    also={"serves": _served, "packed": _decodes,
          "chunked": lambda k, v, chunks: _decodes(
              k, v, [(0, 0, sum(n for _, n in chunks))])})


@pytest.mark.parametrize("case,kw", cases(F))
def test_the_family_contract(case, kw, monkeypatch):
    run(case, F, monkeypatch, **kw)


def test_the_spec_and_the_cache_of_the_five_kinds():
    kinds = [SPEC.kind(li) for li in range(SPEC.num_layers)]
    assert [kd.mixer for kd in kinds] == [
        "scan", "softmax", "scan", "softmax", "gmu", "softmax", "gmu",
        "softmax"]
    assert [kd.window for kd in kinds if kd.mixer == "softmax"] == [8, 0, 0, 0]
    assert SPEC.carried_from == 4 and SPEC.memory_layer == 2
    assert SPEC.layer_ids == (0, 1, 16, 17, 18, 19, 20, 21)
    cross = kinds[5]
    assert cross.reads == (1, 0) and not cross.paged and cross.carried
    assert get_family(SPEC).recurrent
    k, v = _cache(F)
    # a pair of KV heads a row; the cross and the GMU kinds own nothing
    assert k.pools[0].shape == (1, 49, 2, PAGE, 16)
    assert k.pools[1].shape == v.pools[1].shape == (1, 49, 2, PAGE, 16)
    assert k.pools[2].shape == (2, ROWS + 1, 16, 128)  # [N, C] float32
    assert k.pools[2].dtype == jnp.float32
    assert v.pools[2].shape == (2, ROWS + 1, 3, 128)
    assert k.pools[3] is None and k.pools[4] is None
    assert v.pools[3] is None and v.pools[4] is None


def test_layers_that_write_no_cache_come_last():
    with pytest.raises(ValueError, match="come last"):
        dataclasses.replace(
            SPEC, layer_pattern=(2, 0, 2, 1, 3, 4, 2, 4))


def test_the_whole_sequence_pass_equals_the_reference(model):
    params, toks, want = model
    for r in range(3):
        F.close(_whole(SPEC, params, jnp.asarray(toks[r])),
               want[r], 4e-5)


def _all_rows_prefill(params, toks, n, k, v):
    """The prefill program with EVERY row through every layer: the same
    lower loop, then the upper layers over all rows against the pages the
    full layer left, the logits of the last row."""
    spec = SPEC
    padded = np.zeros((32,), np.int32)
    padded[:n] = toks[:n]

    def program(params, k, v):
        # the lower half as served: one row through a model cut at the
        # upper half leaves exactly its pages, state and tails
        low = dataclasses.replace(
            spec, num_layers=spec.carried_from,
            layer_pattern=spec.layer_pattern[:spec.carried_from],
            layer_ids=spec.layer_ids[:spec.carried_from])
        return llama.prefill_forward_impl(
            low, dict(params, layers=params["layers"][:spec.carried_from]),
            jnp.asarray(padded), _table(F, 0), jnp.asarray(0), k, v,
            jnp.asarray(n))

    _, k, v, _ = program(params, k, v)
    whole = _whole(spec, params, jnp.asarray(toks[:n]))
    return whole[n - 1], k, v


def test_the_last_row_prefill_equals_the_all_rows_pass(model):
    params, toks, want = model
    n = 30
    logits, k, v = _prefill(
        F, _programs(F)[0], params, toks, 0, 0, n, *_cache(F), bucket=32)
    all_rows, k2, v2 = _all_rows_prefill(params, toks[0], n, *_cache(F))
    F.close(logits, all_rows)
    F.close(logits, want[0, n - 1])
    # and leaves the same pages, state and tails (to the rounding of two
    # compilations): the upper half wrote none
    for a, b in zip(jax.tree.leaves((k.pools, v.pools)),
                    jax.tree.leaves((k2.pools, v2.pools))):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=TOL)


def test_cross_layers_read_the_full_layers_pages_and_write_none(model):
    params, toks, want = model
    n = 20
    pf, _, df, _ = _programs(F)
    _, k, v = _prefill(F, pf, params, toks, 0, 0, n, *_cache(F), bucket=32)
    before = jax.tree.map(np.asarray, (k.pools[1], v.pools[1]))
    # a decode program of the upper half alone over a live slot leaves the
    # shared pool as it found it, the trash page apart
    logits, k, v = _step(F, df, params, toks, {0: (0, n)}, k, v)
    F.close(logits[0], want[0, n])
    page = int(np.asarray(_table(F, 0))[n // PAGE])
    for was, now in zip(before, (k.pools[1], v.pools[1])):
        now = np.asarray(now)
        # the step's one new row, written by the full layer itself
        changed = np.argwhere((was != now).any(axis=(0, 2, 3, 4)))[:, 0]
        assert set(changed) <= {0, page}
    # spoil the full layer's pages: the cross layers see it
    spoiled = k._replace(pools=(k.pools[0], k.pools[1] * 0.5, *k.pools[2:]))
    bad, _, _ = _step(F, df, params, toks, {0: (0, n + 1)}, spoiled, v)
    assert float(np.abs(np.asarray(bad[0]) - want[0, n + 1]).max()) > 1e-3


def test_the_memory_is_layer_16s_scan_and_no_other(model, monkeypatch):
    params, toks, want = model
    assert SPEC.layer_id(SPEC.memory_layer) == 16
    # the memory taken from the first scan in place of the last
    monkeypatch.setattr(ModelSpec, "memory_layer", property(lambda spec: 0))
    swapped = dataclasses.replace(SPEC, name="toy-phi4flash-swapped")
    got = llama.reference_forward(swapped, params, jnp.asarray(toks[0]))
    assert float(np.abs(np.asarray(got) - want[0]).max()) > 1e-3


@pytest.mark.parametrize("drop", ["lambda", "factor", "norm"])
def test_each_term_of_the_difference_is_in_the_comparison(
        model, monkeypatch, drop):
    params, toks, want = model
    if drop == "lambda":
        params = dict(params, layers=[
            dict(lp, lambda_q1=lp["lambda_q1"] * 0) if "lambda_q1" in lp
            else lp for lp in params["layers"]])
    elif drop == "factor":
        monkeypatch.setattr(llama, "lambda_init", lambda layer_id: 0.8)
    else:
        params = dict(params, layers=[
            dict(lp, subln=jnp.ones_like(lp["subln"])) if "subln" in lp
            else lp for lp in params["layers"]])
    with jax.disable_jit():
        got = llama.reference_forward(SPEC, params, jnp.asarray(toks[0, :12]))
    assert float(np.abs(np.asarray(got) - want[0, :12]).max()) > 1e-4


def _scan_recurrence(x, dt, A, B, C, D, s0):
    """The recurrence a token at a time: what the chunk form and the
    decode step must equal. x, dt: [T, C]; A: [N, C]; B, C: [T,
    N]; D: [C]; s0: [N, C]. Returns (y [T, C] float32, s)."""
    f32 = jnp.float32

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        s = jnp.exp(dt_t * A) * s + (dt_t * x_t) * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0) + D * x_t

    s, y = jax.lax.scan(step, s0.astype(f32), (
        x.astype(f32), dt.astype(f32), B.astype(f32), C.astype(f32)))
    return y, s


def _scan_case(T_, seed=3, R=2, C=128, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (R, T_, C), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (R, T_, C)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (N, C), minval=0.0, maxval=2.8))
    B = jax.random.normal(ks[3], (R, T_, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (R, T_, N), jnp.float32)
    D = jax.random.uniform(ks[5], (C,), minval=0.5, maxval=1.5)
    return x, dt, A, B, Cm, D


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernel"])
@pytest.mark.parametrize("T_", [16, 64, 150], ids=["short", "one-chunk", "ragged"])
def test_the_chunk_form_equals_the_token_recurrence(monkeypatch, T_, pallas):
    """The XLA twin and the ``scan_chunk`` kernel (interpreted), each
    against the recurrence a token at a time."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    x, dt, A, B, C, D = _scan_case(T_)
    R, _, Cn = x.shape
    N = A.shape[0]
    s0 = jax.random.normal(jax.random.PRNGKey(9), (R, N, Cn), jnp.float32)
    pool = jnp.zeros((2, 4, N, Cn), jnp.float32).at[1, 1:3].set(s0)
    rows = jnp.asarray([1, 2], jnp.int32)
    # member 0 resumes its row, member 1 starts fresh; a padded tail
    real = jnp.arange(T_) < T_ - 3
    dt = dt.at[1].set(jnp.where(real[:, None], dt[1], 0.0))
    y, pool2 = attn_ops.scan_chunk_prefill(
        x, dt, A, B, C, D, pool, rows, jnp.asarray([False, True]), layer=1,
        num_tokens=jnp.asarray([T_, T_ - 3], jnp.int32))
    for r, start in ((0, s0[0]), (1, jnp.zeros_like(s0[0]))):
        want_y, want_s = _scan_recurrence(
            x[r], dt[r], A, B[r], C[r], D, start)
        np.testing.assert_allclose(y[r], want_y, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(pool2[1, 1 + r], want_s, rtol=2e-5,
                                   atol=2e-5)
    np.testing.assert_array_equal(pool2[0], pool[0])
    np.testing.assert_array_equal(pool2[1, 0], pool[1, 0])  # nobody's row


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernel"])
def test_a_pack_with_an_empty_member_on_the_trash_row(monkeypatch, pallas):
    """A pack of two whose second member has no tokens and owns no row:
    the first member's walk is the recurrence, the rows of the pool that
    belong to somebody are as they were."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    T_ = 32
    x, dt, A, B, C, D = _scan_case(T_)
    N, Cn = A.shape
    pool = jax.random.normal(
        jax.random.PRNGKey(9), (2, 4, N, Cn), jnp.float32)
    dt = dt.at[1].set(0.0)
    y, pool2 = attn_ops.scan_chunk_prefill(
        x, dt, A, B, C, D, pool, jnp.asarray([2, 3], jnp.int32),
        jnp.asarray([False, True]), layer=0,
        num_tokens=jnp.asarray([T_, 0], jnp.int32))
    want_y, want_s = _scan_recurrence(
        x[0], dt[0], A, B[0], C[0], D, pool[0, 2])
    np.testing.assert_allclose(y[0], want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(pool2[0, 2], want_s, rtol=2e-5, atol=2e-5)
    assert bool(jnp.isfinite(y[1]).all())
    np.testing.assert_array_equal(pool2[0, :2], pool[0, :2])
    np.testing.assert_array_equal(pool2[1], pool[1])


@pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernel"])
def test_the_blocks_past_a_rows_tokens_leave_its_state(monkeypatch, pallas):
    """A row of 1,024 places with 40 real tokens: the state is what the
    40 tokens make of it (the kernel walks one block of its sixteen and
    skips the rest), every ``y`` is finite."""
    monkeypatch.setenv("DYNAMO_PALLAS", pallas)
    T_, n = 1024, 40
    x, dt, A, B, C, D = _scan_case(T_, R=1)
    N, Cn = A.shape
    pool = jax.random.normal(
        jax.random.PRNGKey(9), (1, 3, N, Cn), jnp.float32)
    dt = jnp.where((jnp.arange(T_) < n)[None, :, None], dt, 0.0)
    y, pool2 = attn_ops.scan_chunk_prefill(
        x, dt, A, B, C, D, pool, jnp.asarray([1], jnp.int32),
        jnp.asarray([False]), layer=0, num_tokens=jnp.asarray([n], jnp.int32))
    want_y, want_s = _scan_recurrence(
        x[0, :n], dt[0, :n], A, B[0, :n], C[0, :n], D, pool[0, 1])
    np.testing.assert_allclose(y[0, :n], want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(pool2[0, 1], want_s, rtol=2e-5, atol=2e-5)
    assert bool(jnp.isfinite(y).all())
    np.testing.assert_array_equal(pool2[0, 0], pool[0, 0])


def test_scan_step_equals_its_xla_twin_and_the_recurrence(monkeypatch):
    x, dt, A, B, C, D = _scan_case(1, R=5)
    x, dt, B, C = x[:, 0], dt[:, 0], B[:, 0], C[:, 0]
    N, Cn = A.shape
    pool = jax.random.normal(
        jax.random.PRNGKey(4), (2, 5, N, Cn), jnp.float32)
    conv = jnp.zeros((2, 5, 3, Cn), jnp.float32)
    tail = jax.random.normal(jax.random.PRNGKey(5), (5, 3, Cn), jnp.float32)
    # slots: rows 2, 0, the trash row (idle), 3, the trash row
    rows = jnp.asarray([2, 0, 4, 3, 4], jnp.int32)
    outs = {}
    for pallas in ("0", "1"):
        monkeypatch.setenv("DYNAMO_PALLAS", pallas)
        outs[pallas] = attn_ops.scan_decode_step(
            pool, conv, rows, x, dt, A, B, C, D, tail, layer=1)
    (y0, p0, c0), (y1, p1, c1) = outs["0"], outs["1"]
    live = np.asarray([0, 1, 3])
    np.testing.assert_allclose(y1[live], y0[live], rtol=1e-6, atol=1e-6)
    for r in (0, 2, 3):  # the live rows; the trash row holds anything
        np.testing.assert_allclose(p1[1, r], p0[1, r], rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(c1[1, r], c0[1, r])
    np.testing.assert_array_equal(p1[0], pool[0])  # the other layer
    np.testing.assert_array_equal(p1[1, 1], pool[1, 1])  # nobody's row
    for s in live:
        want_y, want_s = _scan_recurrence(
            x[s][None], dt[s][None], A, B[s][None], C[s][None], D,
            pool[1, rows[s]])
        np.testing.assert_allclose(y1[s], want_y[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(p1[1, rows[s]], want_s, rtol=1e-5,
                                   atol=1e-5)


def test_the_config_round_trips_by_the_published_names(tmp_path, model):
    params = model[0]
    cfg = loader.hf_config_from_spec(SPEC)
    for key in ("mb_per_layer", "sliding_window", "layer_norm_eps",
                "tie_word_embeddings", "num_key_value_heads", "layers_kept",
                "published_layers"):
        assert cfg[key] == CONFIG[key], key
    assert cfg["model_type"] == "phi4flash"
    again = loader.spec_from_hf_config(cfg, name=SPEC.name)
    assert dataclasses.replace(again, vocab_draw_blocks=8) == SPEC
    loader.save_params(SPEC, params, str(tmp_path))
    from safetensors import safe_open

    with safe_open(str(tmp_path / "model.safetensors"), "numpy") as f:
        names = set(f.keys())
        wqkv = f.get_tensor("model.layers.1.attn.Wqkv.weight")
        a_log = f.get_tensor("model.layers.0.attn.A_log")
    for name in (
        "model.embed_tokens.weight", "model.final_layernorm.weight",
        "model.final_layernorm.bias", "model.layers.0.attn.in_proj.weight",
        "model.layers.0.attn.conv1d.weight", "model.layers.0.attn.conv1d.bias",
        "model.layers.0.attn.x_proj.weight", "model.layers.0.attn.dt_proj.bias",
        "model.layers.0.attn.D", "model.layers.0.attn.out_proj.weight",
        "model.layers.1.attn.Wqkv.bias", "model.layers.1.attn.out_proj.weight",
        "model.layers.1.attn.inner_cross_attn.lambda_q1",
        "model.layers.1.attn.inner_cross_attn.subln.weight",
        "model.layers.4.attn.in_proj.weight", "model.layers.5.attn.Wqkv.weight",
        "model.layers.0.mlp.gate_up_proj.weight",
        "model.layers.0.mlp.down_proj.weight",
        "model.layers.0.input_layernorm.bias",
        "model.layers.0.post_attention_layernorm.weight",
    ):
        assert name in names, name
    assert "lm_head.weight" not in names
    assert wqkv.shape == (64 + 2 * 32, 64)  # [q | k | v] rows, published
    assert a_log.shape == (128, 16)  # [channels, states], published
    spec2, loaded = loader.load_model_dir(str(tmp_path), name=SPEC.name)
    assert dataclasses.replace(spec2, vocab_draw_blocks=8) == SPEC
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_lower_precision_than_stated_fails_a_tolerance(model, ref):
    _, toks, want = model
    low = np.asarray(ref.forward(
        CONFIG, SEED, toks[:1], np.arange(T)[None], quant="fp8"))
    assert float(np.abs(low - want[:1]).max()) > 100 * TOL


def test_the_memory_guard_charges_the_chunk_form():
    cfg = EngineConfig(
        page_size=64, num_pages=512, max_pages_per_seq=160,
        max_decode_slots=8, prefill_buckets=(1024,), prefill_pack_size=2,
        max_prefill_chunk_tokens=1024)
    wide = dataclasses.replace(
        SPEC, hidden_size=2560, scan_inner=5120, num_heads=40, head_dim=64)
    # two rows' pairs of a chunk and their copies are ~0.5 GB: a pack of two
    # fits 1 GB and not 0.4
    assert cfg.prefill_shapes(wide, 2 ** 30)[1024] == 2
    assert cfg.prefill_shapes(wide, int(0.4 * 2 ** 30))[1024] == 1
