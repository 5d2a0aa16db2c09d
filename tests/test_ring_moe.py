"""Ring attention (sequence parallelism) + MoE/EP: numerics and engine e2e.

Runs on the virtual 8-device CPU mesh. Ring attention must match plain
causal attention bit-for-bit in f32 up to accumulation-order tolerance;
the MoE model must serve through the full engine, and both must compose
with tp sharding in the multi-chip jit path.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import EngineConfig, ModelSpec
from dynamo_tpu.engine.core import InferenceEngine
from dynamo_tpu.models import llama, moe
from dynamo_tpu.ops.attention import causal_attention
from dynamo_tpu.parallel.mesh import make_mesh
from dynamo_tpu.parallel.ring import ring_attention
from dynamo_tpu.runtime.context import Context

pytestmark = pytest.mark.unit

MOE_SPEC = ModelSpec.tiny_moe()


def small_config(**kw):
    defaults = dict(
        page_size=4, num_pages=64, max_pages_per_seq=16,
        max_decode_slots=4, prefill_buckets=(8, 16, 32, 64),
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


async def run(engine, token_ids, max_tokens=6):
    out = []
    req = {
        "token_ids": list(token_ids),
        "sampling": {"temperature": 0.0},
        "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True},
        "eos_token_ids": [2],
    }
    async for item in engine.generate(req, Context()):
        out.extend(item.get("token_ids") or [])
        assert item.get("finish_reason") != "error", item
    return out


# -------------------------------------------------------------- ring numerics


@pytest.mark.parametrize("sp", [2, 4])
def test_ring_attention_matches_causal(sp):
    T, H, KH, D = 32, 4, 2, 16
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (T, H, D), jnp.float32)
    k = jax.random.normal(kk, (T, KH, D), jnp.float32)
    v = jax.random.normal(kv, (T, KH, D), jnp.float32)

    want = causal_attention(q, k, v, jnp.arange(T), jnp.asarray(T))
    mesh = make_mesh(sp=sp)
    got = ring_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_ring_attention_composes_with_tp():
    T, H, KH, D = 16, 4, 2, 8
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (T, H, D), jnp.float32)
    k = jax.random.normal(kk, (T, KH, D), jnp.float32)
    v = jax.random.normal(kv, (T, KH, D), jnp.float32)
    want = causal_attention(q, k, v, jnp.arange(T), jnp.asarray(T))
    mesh = make_mesh(sp=2, tp=2)
    got = ring_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_ring_prefill_matches_reference_forward():
    spec = ModelSpec(
        vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
    )
    key = jax.random.PRNGKey(0)
    params = llama.init_params(spec, key)
    mesh = make_mesh(sp=4)
    page_size, pages = 4, 16
    k_pages, v_pages = llama.init_cache(spec, pages + 1, page_size)

    tokens = np.arange(13) % 97  # 13 real tokens, padded to 16
    ref = llama.reference_forward(spec, params, jnp.asarray(tokens, jnp.int32))

    padded = np.zeros((16,), np.int32)
    padded[:13] = tokens
    bt = np.zeros((8,), np.int32)
    bt[:4] = [1, 2, 3, 4]
    logits, k_pages, v_pages, _ = llama.prefill_forward_ring(
        spec, params, jnp.asarray(padded), jnp.asarray(bt),
        k_pages, v_pages, jnp.asarray(13, jnp.int32), mesh=mesh,
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref[-1]), atol=2e-4
    )
    # KV written by the ring path must equal the plain paged path's for
    # every REAL token row. (Partial-tail-page rows beyond num_tokens hold
    # padded-position garbage — masked by attention, overwritten as decode
    # appends — and the two paths' garbage legitimately differs from layer
    # 2 on: padded activations see different attention masks.)
    k2, v2 = llama.init_cache(spec, pages + 1, page_size)
    _, k2, v2, _d = llama.prefill_forward(
        spec, params, jnp.asarray(padded), jnp.asarray(np.pad(bt, (0, 0))),
        jnp.asarray(0, jnp.int32), k2, v2, jnp.asarray(13, jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(k_pages[:, 1:4]), np.asarray(k2[:, 1:4]), atol=1e-5
    )
    np.testing.assert_allclose(  # partial page: only its one valid row
        np.asarray(k_pages[:, 4, :, :1]), np.asarray(k2[:, 4, :, :1]),
        atol=1e-5,
    )


# ------------------------------------------------------------------ MoE layer


def test_moe_mlp_matches_per_token_loop():
    """Sorted, grouped dispatch == explicit per-token top-k loop."""
    spec = MOE_SPEC
    key = jax.random.PRNGKey(3)
    lp = moe.init_moe_layer(spec, key)
    x = jax.random.normal(jax.random.PRNGKey(4), (5, spec.hidden_size), jnp.float32)

    got = np.asarray(moe.moe_mlp(spec, lp, x))

    probs = np.asarray(jax.nn.softmax(x.astype(jnp.float32) @ lp["router"], axis=-1))
    want = np.zeros_like(got)
    for t in range(x.shape[0]):
        idx = np.argsort(-probs[t])[: spec.num_experts_per_token]
        w = probs[t][idx]
        w = w / w.sum()
        for j, e in enumerate(idx):
            xe = np.asarray(x[t])
            h = np.asarray(jax.nn.silu(xe @ lp["w_gate"][e])) * (xe @ lp["w_up"][e])
            want[t] += w[j] * (h @ lp["w_down"][e])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_moe_params_and_shardings_align():
    mesh = make_mesh(ep=2, tp=2)
    params = llama.init_params(MOE_SPEC, jax.random.PRNGKey(0))
    shardings = llama.param_shardings(MOE_SPEC, mesh)
    # tree structures must match so device_put can zip them
    jax.tree.map(lambda p, s: None, params, shardings)
    p = jax.tree.map(lambda p, s: jax.device_put(p, s), params, shardings)
    assert p["layers"][0]["moe"]["w_gate"].sharding.spec == \
        shardings["layers"][0]["moe"]["w_gate"].spec


# ------------------------------------------------------------- engine e2e


async def test_engine_serves_moe_model():
    engine = InferenceEngine(MOE_SPEC, small_config())
    prompt = list(range(40, 52))
    want = await run(engine, prompt)
    assert len(want) == 6
    got = await run(engine, prompt)  # warm prefix path
    assert got == want
    await engine.close()


async def test_engine_serves_moe_with_ep_mesh():
    cfg = small_config(ep=2, tp=2)
    mesh = make_mesh(ep=2, tp=2)
    engine = InferenceEngine(MOE_SPEC, cfg, mesh=mesh)
    got = await run(engine, list(range(30, 40)))
    assert len(got) == 6
    await engine.close()


async def test_engine_ring_prefill_path():
    """sp>1 engine takes the ring path for cold prompts and matches sp=1."""
    spec = ModelSpec(
        vocab_size=97, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, dtype="float32",
    )
    plain = InferenceEngine(spec, small_config())
    prompt = list(range(20, 20 + 14))
    want = await run(plain, prompt)
    await plain.close()

    mesh = make_mesh(sp=2)
    ring = InferenceEngine(spec, small_config(sp=2), mesh=mesh)
    got = await run(ring, prompt)
    assert got == want
    await ring.close()


def _loop_moe(spec, lp, x, first=0):
    """Per-token loop over the chosen experts that ``lp`` holds."""
    topi, topv = (np.asarray(a) for a in moe.route(spec, lp, x))
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for e, w in zip(topi[t] - first, topv[t]):
            if 0 <= e < lp["w_gate"].shape[0]:
                xe = np.asarray(x[t])
                h = np.asarray(jax.nn.silu(xe @ lp["w_gate"][e])) * (
                    xe @ lp["w_up"][e])
                want[t] += w * (h @ lp["w_down"][e])
    return want


@pytest.mark.parametrize("T", [1, 64, 257])
def test_moe_is_dropless_under_a_router_that_sends_all_to_one_expert(T):
    """Every token's every assignment is computed, whatever the skew: the
    old capacity dispatch dropped all but C of these."""
    spec = MOE_SPEC
    lp = dict(moe.init_moe_layer(spec, jax.random.PRNGKey(3)))
    router = np.zeros((spec.hidden_size, spec.num_experts), np.float32)
    router[:, 0] = 5.0
    lp["router"] = jnp.asarray(router)
    x = jax.random.uniform(
        jax.random.PRNGKey(5), (T, spec.hidden_size), jnp.float32, 0.1, 1.0
    )
    topi, _ = moe.route(spec, lp, x)
    assert bool(jnp.all(topi[:, 0] == 0))  # every token's first choice
    got, counts = moe.moe_mlp(spec, lp, x, counted=jnp.ones((T,), bool))
    np.testing.assert_allclose(
        np.asarray(got), _loop_moe(spec, lp, x), rtol=2e-4, atol=2e-4
    )
    assert int(counts[0]) == T  # expert 0 took a row of every token
    assert int(counts[-2]) == T * spec.num_experts_per_token
    assert int(counts[:-2].sum()) == int(counts[-2])  # all held: none lost
    assert 1 <= int(counts[-1]) <= spec.num_experts  # experts touched


def test_moe_shares_add_up_to_the_whole_layer():
    """model-configs 4: each of the ep shares, told which experts it
    holds, computes its experts' part, and the parts sum to the uncut
    layer."""
    import dataclasses

    spec = MOE_SPEC
    lp = moe.init_moe_layer(spec, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (33, spec.hidden_size),
                          jnp.float32)
    whole = np.asarray(moe.moe_mlp(spec, lp, x))
    parts = np.zeros_like(whole)
    for first in range(0, spec.num_experts, 2):
        share = dataclasses.replace(spec, held_experts=(2, first))
        lps = {
            k: (v[first: first + 2] if k in moe._EXPERT_SPECS else v)
            for k, v in lp.items()
        }
        part = np.asarray(moe.moe_mlp(share, lps, x))
        np.testing.assert_allclose(
            part, _loop_moe(share, lps, x, first), rtol=2e-4, atol=2e-4
        )
        parts += part
    np.testing.assert_allclose(parts, whole, rtol=2e-4, atol=2e-4)


def test_moe_counters_leave_out_rows_that_are_not_counted():
    spec = MOE_SPEC
    lp = moe.init_moe_layer(spec, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (10, spec.hidden_size),
                          jnp.float32)
    counted = jnp.arange(10) < 6
    _, counts = moe.moe_mlp(spec, lp, x, counted=counted)
    topi = np.asarray(moe.route(spec, lp, x)[0])[:6]
    want = np.bincount(topi.reshape(-1), minlength=spec.num_experts)
    assert np.asarray(counts[:-2]).tolist() == want.tolist()
    assert int(counts[-2]) == 6 * spec.num_experts_per_token
    assert int(counts[-1]) == int((want > 0).sum())


def test_moe_under_an_ep_mesh_is_the_same_layer():
    """ep x tp shards of the one layer, a psum across them."""
    spec = MOE_SPEC
    mesh = make_mesh(ep=2, tp=2)
    lp = moe.init_moe_layer(spec, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (12, spec.hidden_size),
                          jnp.float32)
    want = np.asarray(moe.moe_mlp(spec, lp, x))
    sharded = jax.tree.map(
        jax.device_put, lp, moe.moe_layer_shardings(mesh, spec)
    )
    got = jax.jit(lambda lp_, x_: moe.moe_mlp(spec, lp_, x_, mesh=mesh))(
        sharded, x
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


# ------------------------------------------- the combine: a gather, not a scatter


def _scatter_add_experts(spec, lp, x, topi, topv, first):
    """``moe._held_experts`` as it summed before the un-permute: every
    sorted row weighted in float32 and scatter-added into its token. Kept
    here as the yardstick of the gather form."""
    T, k = topi.shape
    n = lp["w_gate"].shape[0]
    slot = moe._slots(topi, first, n)
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.bincount(slot, length=n + 1)[:n].astype(jnp.int32)
    rows = x[order // k]
    g = jax.lax.ragged_dot(rows, lp["w_gate"], sizes)
    u = jax.lax.ragged_dot(rows, lp["w_up"], sizes)
    h = jax.nn.silu(g) * u
    out = jax.lax.ragged_dot(h.astype(x.dtype), lp["w_down"], sizes)
    w = jnp.where(slot < n, topv.reshape(T * k), 0.0)[order]
    out = jnp.where(w[:, None] != 0, out.astype(jnp.float32) * w[:, None], 0.0)
    return jnp.zeros((T, x.shape[1]), jnp.float32).at[order // k].add(out)


_HELD, _FIRST, _EXPERTS = 4, 4, 16  # experts 4..7 of 16 are held


@functools.cache
def _combine_layer(k):
    """(spec, lp, route, the scatter-add, ``_held_experts``) of a share of
    4 of 16 experts under top-``k``: the layer initialised once a module,
    the three under one ``jax.jit`` each, so a shape ``[T, k]`` is traced
    and compiled once a worker and not dispatched op by op a case."""
    spec = dataclasses.replace(
        MOE_SPEC, num_experts=_EXPERTS, num_experts_per_token=k,
        held_experts=(_HELD, _FIRST))
    return (
        spec, moe.init_moe_layer(spec, jax.random.PRNGKey(3)),
        jax.jit(lambda lp, x: moe.route(spec, lp, x)),
        jax.jit(lambda lp, x, topi, topv: _scatter_add_experts(
            spec, lp, x, topi, topv, _FIRST)),
        jax.jit(lambda lp, x, topi, topv: moe._held_experts(
            spec, lp, x, topi, topv, _FIRST)))


def _combine_case(case, T, k):
    """(x, topi, topv) of a case."""
    spec, lp, route, _, _ = _combine_layer(k)
    x = jax.random.normal(
        jax.random.PRNGKey(T * 8 + k), (T, spec.hidden_size), jnp.float32)
    topi, topv = route(lp, x)
    if case == "one_held_expert":  # every assignment of every token
        topi = jnp.full((T, k), _FIRST + 1, jnp.int32)
    elif case == "none_held":
        topi = (topi % _FIRST).astype(jnp.int32)  # experts 0..3: elsewhere
    return x, topi, topv


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("T", [1, 5, 128, 1024, 2048])
@pytest.mark.parametrize("case", [
    "routed", "one_held_expert", "none_held", "undefined_rows", "ep_mesh"])
def test_moe_combine_by_gather_is_the_scatter_add(case, T, k, monkeypatch):
    """The un-permute (each token gathers its k rows and adds them in the
    order of its top-k) gives what the float32 scatter-add over every
    assignment gave, to float32 rounding: over a chunk's and a pack's
    rows, with all assignments on one held expert, with none held
    (exactly 0), with NaN and inf in the rows no group reached, and under
    the "ep" mesh."""
    spec, lp, _, scatter_add, held_experts = _combine_layer(k)
    x, topi, topv = _combine_case(case, T, k)
    want = np.asarray(scatter_add(lp, x, topi, topv))
    tol = dict(rtol=1e-6, atol=1e-6 * max(1.0, float(np.abs(want).max())))
    if case == "ep_mesh":
        # the whole layer through the shard_map: two shards of two experts
        # each, their shares met in a psum (one more float32 addition)
        mesh = make_mesh(ep=2)
        sharded = jax.tree.map(
            jax.device_put, lp, moe.moe_layer_shardings(mesh, spec))
        got = jax.jit(lambda lp_, x_: moe.moe_mlp(spec, lp_, x_, mesh=mesh))(
            sharded, x)
        np.testing.assert_allclose(np.asarray(got), want, **tol)
        return
    if case == "undefined_rows":
        real = moe._grouped_matmul

        def poisoned(a, w, sizes):
            out = real(a, w, sizes)
            past = jnp.arange(a.shape[0])[:, None] >= jnp.sum(sizes)
            junk = jnp.where(
                jnp.arange(a.shape[0])[:, None] % 2 == 0, jnp.nan, jnp.inf)
            return jnp.where(past, junk, out)

        monkeypatch.setattr(moe, "_grouped_matmul", poisoned)
        # traced under the patch: a program of its own
        held_experts = jax.jit(lambda *a: moe._held_experts(spec, *a, _FIRST))
    got = np.asarray(held_experts(lp, x, topi, topv))
    assert got.dtype == np.float32 and got.shape == x.shape
    assert np.isfinite(got).all()
    if case == "none_held":
        assert not got.any() and not want.any()
    elif case == "one_held_expert":
        assert np.abs(got).max() > 0
    np.testing.assert_allclose(got, want, **tol)


# ------------------- the index work: compares and reductions, the same integers


def _route_by_top_k(spec, lp, x):
    """``moe.route`` as it was written with ``jax.lax.top_k``, a one-hot
    of the kept groups and ``take_along_axis``: the yardstick of the
    rounds. -> (topi, topv, the chosen values)."""
    T = x.shape[0]
    E, k = spec.num_experts, spec.num_experts_per_token
    router_logits = x.astype(jnp.float32) @ lp["router"]
    if "router_bias" in lp:
        router_logits = router_logits + lp["router_bias"]
    if spec.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(router_logits)
        choice = scores + lp["score_bias"]
        if spec.n_group > 1:
            gsz = E // spec.n_group
            grouped = choice.reshape(T, spec.n_group, gsz)
            group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)
            _gv, gidx = jax.lax.top_k(group_scores, spec.topk_group)
            gmask = jax.nn.one_hot(
                gidx, spec.n_group, dtype=jnp.float32).sum(axis=1)
            choice = jnp.where(
                jnp.repeat(gmask, gsz, axis=-1) > 0, choice, 0.0)
        cv, topi = jax.lax.top_k(choice, k)
        topv = jnp.take_along_axis(scores, topi, axis=1)
        if spec.norm_topk_prob:
            topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
        topv = topv * spec.routed_scaling_factor
    else:
        probs = jax.nn.softmax(router_logits, axis=-1)
        cv, topi = jax.lax.top_k(probs, k)
        topv = cv / jnp.maximum(cv.sum(axis=-1, keepdims=True), 1e-9)
    return topi.astype(jnp.int32), topv, cv


_ROUTERS = {
    "sigmoid_256_top8": dict(
        num_experts=256, num_experts_per_token=8, moe_scoring="sigmoid",
        routed_scaling_factor=2.5),
    "sigmoid_512_4_of_8_groups": dict(
        num_experts=512, num_experts_per_token=8, moe_scoring="sigmoid",
        n_group=8, topk_group=4, routed_scaling_factor=2.5),
    "softmax_bias_32_top4": dict(
        num_experts=32, num_experts_per_token=4, moe_bias=True),
}


def _router_case(name, scores, T=96):
    """(spec, lp, x) of a router; ``scores`` "ties": router columns drawn
    from a pool of five, every bias but three at -2 (under any sigmoid),
    rows of x repeated and rows of zeros, so that choices
    tie exactly within a row and a grouped row is left with fewer than k
    positive choices in the groups it keeps."""
    import dataclasses

    spec = dataclasses.replace(MOE_SPEC, **_ROUTERS[name])
    E, d = spec.num_experts, spec.hidden_size
    keys = jax.random.split(jax.random.PRNGKey(E), 4)
    lp = {"router": 0.5 * jax.random.normal(keys[0], (d, E), jnp.float32)}
    x = jax.random.normal(keys[1], (T, d), jnp.float32)
    bias = 0.1 * jax.random.normal(keys[2], (E,), jnp.float32)
    if scores == "ties":
        pool = jax.random.normal(keys[0], (d, 5), jnp.float32)
        lp["router"] = pool[:, jax.random.randint(keys[3], (E,), 0, 5)]
        bias = jnp.full((E,), -2.0).at[
            jnp.asarray([E // 8 + 6, E // 8 + 7, E // 2 + 3])].set(0.25)
        x = x.at[T // 2:].set(x[: T - T // 2]).at[::7].set(0.0)
    lp["score_bias" if spec.moe_scoring == "sigmoid" else "router_bias"] = bias
    return spec, lp, x


@pytest.mark.parametrize("scores", ["random", "ties"])
@pytest.mark.parametrize("name", list(_ROUTERS))
def test_route_by_rounds_is_top_k_bit_for_bit(name, scores):
    """The picks by rounds of a first-occurrence max and the weights by a
    select are ``lax.top_k``'s indices in its order and
    ``take_along_axis``'s floats, bit for bit: ungrouped sigmoid, group-
    limited sigmoid and softmax with a bias; on drawn scores and on
    scores that tie (lower index first), masked zeros picked included."""
    spec, lp, x = _router_case(name, scores)
    want_i, want_v, cv = (np.asarray(a) for a in _route_by_top_k(spec, lp, x))
    if scores == "ties":
        assert (cv[:, 1:] == cv[:, :-1]).any()  # picks that tie in a row
        if spec.n_group > 1:
            # a row whose kept groups hold fewer than k positive choices:
            # the zeros of the groups left out are picked
            assert ((cv == 0).sum(axis=1) > 0).any()
            assert ((cv > 0).sum(axis=1) < spec.num_experts_per_token).any()
    for fn in (moe.route, jax.jit(moe.route, static_argnums=0)):
        got_i, got_v = (np.asarray(a) for a in fn(spec, lp, x))
        assert got_i.dtype == np.int32 and got_v.dtype == np.float32
        assert (got_i == want_i).all()
        assert (got_v.view(np.uint32) == want_v.view(np.uint32)).all()


@pytest.mark.parametrize("T", [1, 128, 1024])
@pytest.mark.parametrize("case", [
    "random", "all_to_one_expert", "none_held", "counted_rows_left_out"])
def test_sizes_and_counters_are_bincount(case, T, monkeypatch):
    """The groups' sizes and the counters, written as compares and sums,
    are ``np.bincount`` of the held assignments, integer for integer."""
    import dataclasses

    E, k, n, first = 64, 8, 16, 16  # experts 16..31 of 64 are held
    topi = np.random.default_rng(T).integers(0, E, (T, k))
    if case == "all_to_one_expert":
        topi[:] = first + 3
    elif case == "none_held":
        topi = np.where((topi >= first) & (topi < first + n), 0, topi)
    counted = np.ones((T,), bool)
    if case == "counted_rows_left_out":
        counted = np.arange(T) % 3 != 1

    def held_bincount(rows):
        local = rows.reshape(-1) - first
        return np.bincount(local[(local >= 0) & (local < n)], minlength=n)

    topi_ = jnp.asarray(topi, jnp.int32)
    slot = moe._slots(topi_, first, n)
    for fn in (moe._sizes, jax.jit(moe._sizes, static_argnums=1)):
        sizes = np.asarray(fn(slot, n))
        assert sizes.dtype == np.int32
        assert sizes.tolist() == held_bincount(topi).tolist()

    # the counters beside the layer's output, the router's picks replaced
    spec = dataclasses.replace(
        MOE_SPEC, num_experts=E, num_experts_per_token=k,
        held_experts=(n, first))
    lp = moe.init_moe_layer(spec, jax.random.PRNGKey(3))
    x = jax.random.normal(
        jax.random.PRNGKey(4), (T, spec.hidden_size), jnp.float32)
    real = moe.route
    monkeypatch.setattr(
        moe, "route", lambda *a: (topi_, real(*a)[1]))
    _, counts = moe.moe_mlp(spec, lp, x, counted=jnp.asarray(counted))
    counts, want = np.asarray(counts), held_bincount(topi[counted])
    assert counts.dtype == np.int32
    assert counts[:n].tolist() == want.tolist()
    assert int(counts[n]) == int(counted.sum()) * k  # assignments in all
    assert int(counts[n + 1]) == int((want > 0).sum())  # experts touched


def test_moe_under_an_ep_mesh_keeps_sizes_and_counters():
    """Under the "ep" mesh each shard sizes its own groups; the layer and
    its counters are the single-shard layer's."""
    spec = MOE_SPEC
    mesh = make_mesh(ep=2)
    lp = moe.init_moe_layer(spec, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (40, spec.hidden_size),
                          jnp.float32)
    counted = jnp.arange(40) % 4 != 0
    want, want_counts = moe.moe_mlp(spec, lp, x, counted=counted)
    sharded = jax.tree.map(
        jax.device_put, lp, moe.moe_layer_shardings(mesh, spec))
    got, counts = jax.jit(
        lambda lp_, x_: moe.moe_mlp(spec, lp_, x_, mesh=mesh, counted=counted)
    )(sharded, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    assert np.asarray(counts).tolist() == np.asarray(want_counts).tolist()
