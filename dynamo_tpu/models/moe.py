"""Mixture-of-experts FFN: dropless, and told which experts it holds.

Covers the reference's MoE model families (gpt-oss-120b EP configs,
deepseek-r1 wide-EP — engine_configs/deepseek_r1/wide_ep/wide_ep_agg.yaml
``moe_expert_parallel_size``, recipes/deepseek-r1/sglang-wideep). Every
token's top-k assignments are computed, none is dropped: the router scores
all ``num_experts``, the assignments that land on the experts HELD here
are sorted by expert, and one grouped matrix product a projection
(``_grouped_matmul``) runs over the sorted rows. The products' rows go
back to their tokens by the inverse of that sort: each token gathers its
k rows and adds them, weighted, in float32 and in the order of its top-k
(``_combine``; no floating-point scatter). Assignments to experts held
elsewhere have weight 0 and add nothing here; their holders add them.

Which experts are held is stated from outside, two ways through one code
path (``_held_experts``): ``ModelSpec.held_experts`` for a process that is
one share of a larger deployment, and an "ep" mesh axis, under which the
same function runs in a ``shard_map`` over the expert (and "tp") shards
and the shares meet in a ``psum``.

The layer's index arithmetic is compares, selects and reductions over
dense ``[T, E]`` / ``[n, T*k]`` arrays (``_top_k``, ``_two_best``,
``_sizes``), never ``jax.lax.top_k``, ``jnp.take_along_axis`` or
``jnp.bincount``: on a v5e those lower to a sort, a gather along the
minor axis and an integer scatter-add, the three things the chip runs
worst. Beside the program, us a layer call of 128 / 1,024 tokens (my
chip runs, PR 42): ``route`` over 256 experts, top-8, 17.9 / 125 with
``top_k`` + ``take_along_axis`` and 9.9 / 19.0 as it stands; over 512
experts in 8 groups of which 4 are kept 122 / 227 against 12.5 / 36.6;
the sizes of 16 groups out of 1,024 / 8,192 / 16,384 assignments 10.4 /
73.8 / 146 by ``bincount`` and 1.3 / 1.7 / 2.4 by a compare and a sum.
The integers and the floats are the parent's, bit for bit, on the chip
as on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import ModelSpec
# the regions of the expert layer (jax.named_scope: metadata only)
from dynamo_tpu.models.regions import (
    SCOPE_EXPERTS,
    SCOPE_GMM,
    SCOPE_MOE_COMBINE,
    SCOPE_MOE_COUNT,
    SCOPE_MOE_DISPATCH,
    SCOPE_MOE_GROUPED,
    SCOPE_MOE_ZERO,
    SCOPE_ROUTE,
)

Params = dict


def init_moe_layer(spec: ModelSpec, key: jax.Array) -> Params:
    """Router over all experts (and the identity experts behind them,
    which have no weights) + stacked weights of the held ones."""
    dtype = jnp.dtype(spec.dtype)
    d, e, f = spec.hidden_size, spec.router_outputs, spec.moe_intermediate_size
    held = spec.experts_here[0]
    k1, k2, k3, k4 = jax.random.split(key, 4)

    from dynamo_tpu.models.llama import _draw

    def dense(k, shape, scale=None):
        if scale is None:
            scale = 1.0 / jnp.sqrt(shape[-2])
        return _draw(k, scale, shape=shape, dtype=dtype)

    # a softmax router over hundreds of outputs is drawn so that a
    # unit-RMS input gives logits of standard deviation 1.5 at EVERY
    # width (0.019 at d = 6,144): at 0.02 a toy width's probabilities are
    # all but equal and the correction bias alone would pick
    r_scale = 1.5 / d ** 0.5 if spec.moe_scoring == "softmax_bias" else 0.02
    out = {
        "router": dense(k1, (d, e), scale=r_scale).astype(jnp.float32),
        "w_gate": dense(k2, (held, d, f)),
        "w_up": dense(k3, (held, d, f)),
        "w_down": dense(k4, (held, f, d)),
    }
    if spec.moe_bias:  # gpt-oss: router + expert biases
        out["router_bias"] = jnp.zeros((e,), jnp.float32)
        out["b_gate"] = jnp.zeros((held, f), dtype)
        out["b_up"] = jnp.zeros((held, f), dtype)
        out["b_down"] = jnp.zeros((held, d), dtype)
    if spec.moe_scoring == "sigmoid":
        # DeepSeek-V3 aux-free load balancing: learned per-expert
        # correction bias shifts SELECTION only, never the weights. Drawn
        # non-zero: at zero the mechanism would drop out of every
        # comparison made on random weights.
        out["score_bias"] = _draw(
            jax.random.fold_in(key, 1), 0.1, shape=(e,), dtype=jnp.float32
        )
    elif spec.moe_scoring == "softmax_bias":
        # the same mechanism over probabilities, whose mean is 1 / e: a
        # bias of that size moves picks near the k-th without choosing
        # them alone
        out["score_bias"] = _draw(
            jax.random.fold_in(key, 1), 1.0 / e, shape=(e,),
            dtype=jnp.float32,
        )
    return out


def moe_layer_shardings(mesh: Mesh, spec: ModelSpec | None = None) -> Params:
    """Experts sharded over "ep", expert-FFN columns over "tp"."""

    def ns(*axes):
        return NamedSharding(mesh, P(*axes))

    out = {"router": ns(), **{k: ns(*v) for k, v in _EXPERT_SPECS.items()
                              if k.startswith("w_")}}
    if spec is not None and spec.moe_bias:
        out.update(
            router_bias=ns(),
            **{k: ns(*v) for k, v in _EXPERT_SPECS.items()
               if k.startswith("b_")},
        )
    if spec is not None and spec.moe_scoring in ("sigmoid", "softmax_bias"):
        out["score_bias"] = ns()
    return out


# how each per-expert leaf shards: the one table behind the parameter
# shardings and the shard_map's in_specs
_EXPERT_SPECS = {
    "w_gate": ("ep", None, "tp"), "w_up": ("ep", None, "tp"),
    "w_down": ("ep", "tp", None),
    "b_gate": ("ep", "tp"), "b_up": ("ep", "tp"), "b_down": ("ep", None),
}


def _top_k(x: jax.Array, k: int, of: jax.Array | None = None):
    """``jax.lax.top_k(x, k)`` over the last axis as k rounds of a max and
    the index of its first occurrence, the winner masked out after each:
    the same indices in the same order, ties included (lower index
    first), for finite ``x``. -> (values [..., k], indices [..., k]
    int32); with ``of`` the values are ``of``'s at the indices (one
    element and zeros summed, so ``of``'s own bits) and not ``x``'s. The
    rounds are unrolled: as a ``fori_loop`` they serve as fast in a
    shorter program, but the profiler takes 18 s longer to stop on a
    traced JoyAI run, which then passes 360 s (my chip runs, PR 42)."""
    vals, idx = [], []
    for _ in range(k):
        m, i, hit = _first_max(x)
        if of is not None:
            m = jnp.sum(jnp.where(hit, of, 0.0), axis=-1, keepdims=True)
        vals.append(m)
        idx.append(i)
        x = jnp.where(hit, -jnp.inf, x)
    # the barrier keeps the values an array of their own: a sum over the
    # concatenate (the weights' normaliser) the chip's compiler turns into
    # adds in an order of its own, 3-4 ulp off ``top_k``'s (PR 42)
    return jax.lax.optimization_barrier(
        (jnp.concatenate(vals, axis=-1), jnp.concatenate(idx, axis=-1)))


def _first_max(x: jax.Array):
    """(the max over the last axis, the index of its first occurrence,
    both with that axis kept, and where that one element is)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    m = jnp.max(x, axis=-1, keepdims=True)
    i = jnp.min(jnp.where(x == m, iota, x.shape[-1]), axis=-1, keepdims=True)
    return m, i, iota == i


def _two_best(x: jax.Array) -> jax.Array:
    """``jax.lax.top_k(x, 2)[0].sum(-1)``: the max over the last axis plus
    the max of the rest (a tie counts twice)."""
    best, _i, hit = _first_max(x)
    return best[..., 0] + jnp.max(jnp.where(hit, -jnp.inf, x), axis=-1)


@jax.named_scope(SCOPE_ROUTE)
def route(spec: ModelSpec, lp: Params, x: jax.Array):
    """x: [T, d] -> (expert ids [T, k] int32, weights [T, k] f32), over
    ALL ``num_experts`` (and, behind them, ``zero_experts`` identity
    experts: ids >= ``num_experts``). Router arithmetic in float32."""
    T = x.shape[0]
    E, k = spec.num_experts, spec.num_experts_per_token
    router_logits = x.astype(jnp.float32) @ lp["router"]
    if "router_bias" in lp:
        router_logits = router_logits + lp["router_bias"]
    if spec.moe_scoring == "sigmoid":
        # DeepSeek-V3 noaux_tc routing (HF DeepseekV3TopkRouter): sigmoid
        # scores; the learned correction bias + group-limited top-k pick
        # the experts, but the combine WEIGHTS come from the unbiased
        # scores, renormalized and scaled by routed_scaling_factor
        scores = jax.nn.sigmoid(router_logits)  # [T, E]
        choice = scores + lp["score_bias"]
        if spec.n_group > 1:
            G, gsz = spec.n_group, E // spec.n_group
            group_scores = _two_best(choice.reshape(T, G, gsz))  # [T, G]
            _gv, gidx = _top_k(group_scores, spec.topk_group)  # [T, kept]
            kept = jnp.any(
                gidx[:, :, None] == jnp.arange(G, dtype=jnp.int32), axis=1)
            choice = jnp.where(jnp.repeat(kept, gsz, axis=-1), choice, 0.0)
        topv, topi = _top_k(choice, k, of=scores)  # [T, k]
        if spec.norm_topk_prob:
            topv = topv / (
                topv.sum(axis=-1, keepdims=True) + spec.moe_norm_eps)
        topv = topv * spec.routed_scaling_factor
    elif spec.moe_scoring == "softmax_bias":
        # LongCat-Flash (HF LongcatFlashTopkRouter): probabilities over
        # every output, the identity experts' among them; the correction
        # bias picks, the weights are the unbiased probabilities times
        # routed_scaling_factor, renormalised only where the config says
        probs = jax.nn.softmax(router_logits, axis=-1)
        topv, topi = _top_k(probs + lp["score_bias"], k, of=probs)
        if spec.norm_topk_prob:
            topv = topv / (
                topv.sum(axis=-1, keepdims=True) + spec.moe_norm_eps)
        topv = topv * spec.routed_scaling_factor
    else:
        # softmax-all + top-k renormalize == softmax over the top-k
        # logits (HF gpt-oss GptOssTopKRouter): same selection/weights
        probs = jax.nn.softmax(router_logits, axis=-1)  # [T, E]
        topv, topi = _top_k(probs, k)  # [T, k]
        topv = topv / jnp.maximum(topv.sum(axis=-1, keepdims=True), 1e-9)
    return topi, topv


@jax.named_scope(SCOPE_EXPERTS)
def _held_experts(
    spec: ModelSpec, lp: Params, x: jax.Array, topi: jax.Array,
    topv: jax.Array, first, *, down_bias=True, clamp: float = 0.0,
) -> jax.Array:
    """The share of the layer's output that the experts in ``lp`` make:
    experts ``first .. first + n`` of the model, n = ``lp["w_gate"]``'s
    leading axis. x: [T, d] -> [T, d] float32. ``down_bias``: whether
    this shard is the one that adds the down projection's bias.
    ``clamp``: the layer's plain-SiLU bound on an expert's two halves
    (``ModelSpec.expert_clamp``; a static of the program), 0 = none."""
    T, k = topi.shape
    n = lp["w_gate"].shape[0]
    # the layer's three steps, each a region of its own beneath
    # moe_experts: sort the assignments by expert and gather their rows
    # (keeping where each assignment went), the grouped products, then
    # un-permute: each token gathers its k rows back and sums them,
    # weighted, in the order of its top-k
    with jax.named_scope(SCOPE_MOE_DISPATCH):
        slot = _slots(topi, first, n)  # absent: n, sorted behind every group
        order = jnp.argsort(slot, stable=True)
        # inv[a]: where assignment a (token a // k, choice a % k) sorted
        # to. A second sort of 8,192 integers is 7 us on a v5e where the
        # scatter of an iota is 39 (16,384: 9 and 77; my chip run, PR 38)
        inv = jnp.argsort(order)
        sizes = _sizes(slot, n)
        eid = jnp.minimum(slot[order], n - 1)
        rows = x[order // k]  # [T*k, d]: the held assignments lead, by expert
    # rows past sum(sizes) belong to no group; what the grouped products
    # leave there is weighted by zero below
    with jax.named_scope(SCOPE_MOE_GROUPED):
        g = _grouped_matmul(rows, lp["w_gate"], sizes)
        u = _grouped_matmul(rows, lp["w_up"], sizes)
        if "b_gate" in lp:
            g = g + lp["b_gate"][eid]
            u = u + lp["b_up"][eid]
        if spec.swiglu_limit:
            # gpt-oss clamped swiglu (HF GptOssExperts.forward): gate capped
            # above, linear clamped both ways, swish slope alpha, (up + 1)
            g = jnp.minimum(g, spec.swiglu_limit)
            u = jnp.clip(u, -spec.swiglu_limit, spec.swiglu_limit)
            h = g * jax.nn.sigmoid(spec.swiglu_alpha * g) * (u + 1.0)
        elif clamp:
            h = jax.nn.silu(jnp.minimum(g, clamp)) * jnp.clip(u, -clamp, clamp)
        else:
            h = jax.nn.silu(g) * u
        out = _grouped_matmul(h.astype(x.dtype), lp["w_down"], sizes)
        if "b_down" in lp:
            out = out + jnp.where(down_bias, lp["b_down"][eid], 0)
    with jax.named_scope(SCOPE_MOE_COMBINE):
        return _combine(out, inv.reshape(T, k),
                        jnp.where(slot.reshape(T, k) < n, topv, 0.0))


def _combine(out: jax.Array, inv: jax.Array, w: jax.Array) -> jax.Array:
    """y[t] = sum over j of w[t, j] * out[inv[t, j]], float32 [T, d]: the
    un-permute of the sorted rows, as one gather in token order. ``out``
    [T*k, d] in sorted order; ``inv`` [T, k] the sorted position of each
    of a token's k assignments (a permutation of 0 .. T*k); ``w`` [T, k]
    float32, 0 where the expert is held elsewhere. A weight of 0 adds
    exactly 0 whatever its row holds (a row no group reached is
    undefined). The rows are gathered choice-major ([k, T, d]), so a
    choice is a contiguous slab and the k terms are added slab by slab in
    the order of the top-k: the result does not depend on how the sort
    laid the rows out."""
    T, k = inv.shape
    rows = out.at[inv.T.reshape(T * k)].get(
        mode="promise_in_bounds", unique_indices=True).reshape(k, T, -1)
    wt = w.T
    # the mask made once, [k, T]: compared slab by slab the chip's
    # compiler makes k small fusions of the compares beside the sum
    keep = wt != 0
    y = None
    for j in range(k):
        term = jnp.where(keep[j][:, None],
                         rows[j].astype(jnp.float32) * wt[j][:, None], 0.0)
        y = term if y is None else y + term
    return y


# megablox's tiles, for the calls whose rows stream: rows a tile of the
# grouped product; its tiles of the contracted and the output dims. On a
# v5e at MiMo's expert (4096 x 2048, 16 held) one
# product over 64 to 4,096 live rows takes 0.43 ms at (128, 1024, 2048),
# 76% of what reading the 268 MB of weights takes; ``jax.lax.ragged_dot``
# 0.94 ms (my chip runs, PR 28). (128, 2048, 2048) does not fit VMEM.
_GMM_TILES = (128, 1024, 2048)


def _grouped_matmul(a: jax.Array, w: jax.Array, sizes: jax.Array):
    """rows [m, k] sorted by group x w [g, k, n] -> [m, n]: row r of group
    i times ``w[i]``; ``sizes`` [g] rows a group, in order. Rows past
    ``sum(sizes)`` come back undefined. On the chip a Mosaic call under
    the name ``gmm``, chosen by the call's static shape alone: where the
    rows and the output fit VMEM beside two weight tiles (a decode step's
    call) the repo's own kernel, which reads each touched expert once
    (``ops/pallas/grouped.py``); else (rows that stream: prefill) the
    grouped matmul that ships with JAX (megablox), which visits only the
    row tiles a group reaches. ``jax.lax.ragged_dot`` elsewhere."""
    from dynamo_tpu.ops.attention import use_pallas
    from dynamo_tpu.ops.fallback import note_fallback, note_grouped_product

    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu and use_pallas()):
        if on_tpu:
            # off the chip ragged_dot IS the path (the kernel interpreted
            # would take minutes); on it, this is DYNAMO_PALLAS=0
            note_fallback("no_pallas_backend", expected=True,
                          detail="moe grouped product: jax.lax.ragged_dot")
        return jax.lax.ragged_dot(a, w, sizes)
    from dynamo_tpu.ops.pallas import grouped

    m = a.shape[0]
    shape = f"{a.shape} x {w.shape}"
    if grouped.tile_n(m, *w.shape[1:], a.dtype.itemsize) is not None:
        note_grouped_product("resident", detail=shape)
        return grouped.grouped_matmul(a, w, sizes, scope=SCOPE_GMM)
    note_grouped_product("streamed", detail=shape)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    tm, tk, tn = _GMM_TILES
    a = jnp.pad(a, ((0, -m % tm), (0, 0)))
    out = gmm(
        a, w, sizes, preferred_element_type=a.dtype,
        tiling=(tm, min(tk, w.shape[1]), min(tn, w.shape[2])),
    )
    return out[:m]


def moe_mlp(
    spec: ModelSpec, lp: Params, x: jax.Array, *, mesh: Mesh | None = None,
    counted: jax.Array | None = None, clamp: float = 0.0,
):
    """x: [T, d] -> [T, d] through the top-k routed experts held here
    (``clamp``: see ``_held_experts``).

    ``counted`` ([T] bool) asks for the layer's counters beside the
    output: (y, counts [n_held + 2] int32) = assignments of the counted
    rows to each held expert, then their assignments in all (k a row),
    then how many held experts got at least one (whose weights this call
    had to read). A model with identity experts (``spec.zero_experts``)
    adds each token's own input times the sum of its identity picks'
    weights, once, outside the shares (``moe_zero``), and counts two more
    behind the held experts' sizes ([n_held + 4]): the counted rows' picks
    that were identity experts, and those that were FFN experts, held
    here or not (the two sum to k a counted row).
    Under a mesh with an "ep" or "tp" axis the expert weights are shards
    (moe_layer_shardings) and each shard's share is summed across them.
    """
    T = x.shape[0]
    topi, topv = route(spec, lp, x)
    n_held, first = spec.experts_here
    axes = tuple(
        a for a in ("ep", "tp")
        if mesh is not None and mesh.shape.get(a, 1) > 1
    )
    if not axes:
        y = _held_experts(spec, lp, x, topi, topv, first, clamp=clamp)
    else:
        ep = mesh.shape.get("ep", 1)
        experts = {k: v for k, v in lp.items() if k in _EXPERT_SPECS}

        def shard(x_, topi_, topv_, experts_):
            at = first
            if ep > 1:
                at = first + jax.lax.axis_index("ep") * (n_held // ep)
            # under a "tp" split of the expert width every shard holds
            # the whole down bias: the first adds it
            first_tp = "tp" not in axes or jax.lax.axis_index("tp") == 0
            return jax.lax.psum(_held_experts(
                spec, experts_, x_, topi_, topv_, at, down_bias=first_tp,
                clamp=clamp,
            ), axes)

        y = jax.shard_map(
            shard, mesh=mesh,
            in_specs=(P(), P(), P(), {
                k: P(*(a if a in axes else None for a in _EXPERT_SPECS[k]))
                for k in experts
            }),
            out_specs=P(), check_vma=False,
        )(x, topi, topv, experts)
    if spec.zero_experts:
        # held by no chip: every chip adds its own tokens' term, as it
        # does a shared expert's, so it counts once when shares are summed
        with jax.named_scope(SCOPE_MOE_ZERO):
            zero = topi >= spec.num_experts
            y = y + x.astype(jnp.float32) * jnp.sum(
                jnp.where(zero, topv, 0.0), axis=-1, keepdims=True)
    y = y.astype(x.dtype)
    if counted is None:
        return y
    # a row that is not counted routes to no expert for the count
    with jax.named_scope(SCOPE_MOE_COUNT):
        sizes = _sizes(
            _slots(jnp.where(counted[:, None], topi, -1), first, n_held),
            n_held)
        total = (jnp.sum(counted) * topi.shape[1]).astype(jnp.int32)
        touched = jnp.sum(sizes > 0).astype(jnp.int32)
        picks = []
        if spec.zero_experts:
            zeros = jnp.sum(zero & counted[:, None]).astype(jnp.int32)
            picks = [zeros[None], (total - zeros)[None]]
        return y, jnp.concatenate(
            [sizes, *picks, total[None], touched[None]])


def _slots(topi: jax.Array, first, n: int) -> jax.Array:
    """Each assignment's expert as an index into experts first .. first +
    n, flat; ``n`` for an expert that is not among them."""
    local = topi.reshape(-1) - first
    return jnp.where((local >= 0) & (local < n), local, n)


def _sizes(slot: jax.Array, n: int) -> jax.Array:
    """How many of ``slot`` ([m], values 0 .. n) are each of 0 .. n - 1,
    int32 [n]: ``bincount`` as a compare against every bin and a sum, the
    assignments along the lanes."""
    bins = jnp.arange(n, dtype=slot.dtype)[:, None]
    return jnp.sum(slot[None, :] == bins, axis=1, dtype=jnp.int32)
