"""The decode step of a KDA layer from its projections, both ways: the
``kda_step`` kernel (interpreted) against its XLA twin through
``models/llama._kda_decode``, for the gate kind of whichever model's spec
is handed in (``tests/test_solar_open2.py``: low rank, softplus decay,
beta doubled; ``tests/test_ling3_flash.py``: full rank, the bounded
decay). 32 heads, so two head blocks a slot. What is compared: the layer's
output, the state pool AND the tails' pool.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from family_contract import _close

from dynamo_tpu.models import llama
from dynamo_tpu.ops import attention as attn_ops

CASES = ("every-slot-live", "trash-among-live", "after-a-ragged-prefill",
         "eight-steps", "bf16")
HEADS, ROWS, LAYERS, LJ = 32, 5, 2, 1


# a decode path (``DYNAMO_PALLAS``, read at TRACE time) a jit: the step's
# programs are traced once a shape a worker, not dispatched op by op
_DECODE = {path: jax.jit(
    lambda *a: llama._kda_decode(*a), static_argnums=(0, 1, 6))
    for path in ("0", "1")}


@functools.cache
def _layer(spec, ki, dtype):
    """(spec at 32 KDA heads, the kind, one of its layers' weights), made
    once a model a dtype."""
    spec = dataclasses.replace(spec, kda_heads=HEADS)
    params = llama.init_params(spec, jax.random.PRNGKey(17))
    lp = params["layers"][spec.layer_pattern.index(ki)]
    lp = jax.tree.map(
        lambda w: w.astype(dtype) if w.dtype == jnp.float32 and w.ndim > 1
        else w, lp)
    return spec, spec.layer_kinds[ki], lp


@functools.cache
def _pools(spec, dtype, seed=5):
    H, D = spec.kda_heads, spec.kda_head_dim
    ks, kc = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ks, (LAYERS, ROWS + 1, H, D, D)),
            jax.random.normal(
                kc, (LAYERS, ROWS + 1, spec.kda_conv - 1, 3, H * D)
            ).astype(dtype))


def check(monkeypatch, spec, ki, case):
    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    spec, kd, lp = _layer(spec, ki, dtype)
    d = spec.hidden_size
    s_pool, c_pool = _pools(spec, dtype)
    # the trash row (ROWS) first, between live slots and last
    idx = jnp.asarray({"trash-among-live": [ROWS, 3, ROWS, 0, ROWS]}.get(
        case, [2, 0, 4]), jnp.int32)
    B, steps = idx.shape[0], 8 if case == "eight-steps" else 1
    hs = jax.random.normal(jax.random.PRNGKey(23), (steps, B, d)).astype(dtype)

    if case == "after-a-ragged-prefill":
        # rows 2 and 0 as a pack's prefill leaves them: 21 and 7 real
        # tokens of a 32-token bucket, neither a multiple of a block
        monkeypatch.setenv("DYNAMO_PALLAS", "1")
        _, s_pool, c_pool = llama._kda_prefill(
            spec, kd, lp, jax.random.normal(jax.random.PRNGKey(29), (2, 32, d)),
            s_pool, c_pool, LJ, idx[:2], jnp.asarray([True, False]),
            jnp.asarray([21, 7], jnp.int32))

    def run(pallas):
        monkeypatch.setenv("DYNAMO_PALLAS", pallas)
        s, c, outs = s_pool, c_pool, []
        for h in hs:
            o, s, c = _DECODE[pallas](spec, kd, lp, h, s, c, LJ, idx)
            outs.append(o)
        return jnp.stack(outs), s, c

    (o_x, s_x, c_x), (o_k, s_k, c_k) = run("0"), run("1")
    live = np.asarray(idx) != ROWS
    _close(o_k[:, live], o_x[:, live], tol=2e-2 if case == "bf16" else 1e-5)
    # every row but the trash row; the tails are copies: to the bit
    _close(s_k[:, :ROWS], s_x[:, :ROWS], 1e-5)
    np.testing.assert_array_equal(
        np.asarray(c_k[:, :ROWS], np.float32), np.asarray(c_x[:, :ROWS], np.float32))
    # the other layer, and the rows no live slot owns, are as they were
    idle = sorted(set(range(ROWS)) - set(np.asarray(idx)[live].tolist()))
    for new, old in ((s_k, s_pool), (c_k, c_pool)):
        np.testing.assert_array_equal(
            np.asarray(new[0], np.float32), np.asarray(old[0], np.float32))
        np.testing.assert_array_equal(
            np.asarray(new[LJ, idle], np.float32),
            np.asarray(old[LJ, idle], np.float32))
    # a live slot's tail: the old rows but the first `steps`, then the
    # projections of the tokens fed, q | k | v apart
    x = jnp.stack([hs @ lp["wq"], hs @ lp["wk"], hs @ lp["wv"]], axis=2)
    for b in np.flatnonzero(live):
        want = jnp.concatenate([c_pool[LJ, idx[b]], x[:, b].astype(dtype)])
        np.testing.assert_array_equal(
            np.asarray(c_k[LJ, idx[b]], np.float32),
            np.asarray(want[steps:], np.float32))
    if case != "every-slot-live":
        return
    # and the step is the chunkwise path's arithmetic on one token: its
    # operands (``_kda_inputs``) through the recurrence a token at a time
    tail = c_pool[LJ, idx].reshape(B, spec.kda_conv - 1, -1)
    q, k, v, g, beta, _ = llama._kda_inputs(spec, kd, lp, hs[0][:, None], tail)
    for b in range(B):
        want_o, want_s = attn_ops.kda_recurrence(
            q[b], k[b], v[b], g[b], beta[b], s_pool[LJ, idx[b]])
        _close(s_k[LJ, idx[b]], want_s, 1e-5)
        _close(o_k[0, b], llama._kda_out(spec, kd, lp, want_o[0], hs[0, b]),
               1e-5)
