"""On-device token sampling.

Sampling runs on the accelerator so only the sampled ids [B] cross to host
each step (pulling [B, vocab] logits would burn PCIe/host time every
iteration). Per-slot parameters travel as arrays; temperature 0 selects
greedy via a where, keeping one jitted function for the whole batch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# the sampler's region of a program (decode bursts inline it): op_name
# metadata only, as models/llama.py's scopes
from dynamo_tpu.models.regions import SCOPE_SAMPLER

NEG_INF = -1e30


@partial(jax.jit, static_argnames=("n_top",))
@jax.named_scope(SCOPE_SAMPLER)
def token_logprobs(
    logits: jax.Array,  # [B, V] f32
    sampled: jax.Array,  # [B] int32
    n_top: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Log-probabilities for sampled tokens (+ top-n alternatives).

    Returns (sampled_logprob [B], top_ids [B, n], top_logprobs [B, n]);
    n = max(n_top, 1) to keep shapes static (callers slice). Role of the
    reference's logprob surface (lib/llm/src/perf/logprobs.rs + OpenAI
    logprobs fields) computed on device from the step's logits.
    """
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    logprobs = logits - lse  # [B, V]
    picked = jnp.take_along_axis(logprobs, sampled[:, None], axis=1)[:, 0]
    n = max(n_top, 1)
    top_vals, top_ids = jax.lax.top_k(logprobs, n)
    return picked, top_ids.astype(jnp.int32), top_vals


@jax.named_scope(SCOPE_SAMPLER)
def _sample_tokens_impl(
    logits: jax.Array,  # [B, V] f32
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32 (0 = off)
    top_p: jax.Array,  # [B] f32 (1.0 = off)
    seeds: jax.Array,  # [B] uint32: per-request sampling seed
    steps: jax.Array,  # [B] int32: tokens generated so far (fold-in)
) -> jax.Array:
    """Returns sampled token ids [B].

    Randomness is per-request: key_i = fold_in(PRNGKey(seed_i), step_i), so a
    request with an explicit seed reproduces its stream regardless of what
    else shares the batch.

    Each stage (top-k mask, top-p mask, categorical draw) is gated by a
    runtime ``lax.cond`` on whether ANY row needs it: the masks cost two
    full-vocab bitonic sorts per row (~5 ms/step at B=64, V=32k on v5e —
    more than half a decode step), so an all-greedy batch must pay only
    the argmax.
    """
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)

    # top-k mask (k == 0 -> disabled)
    def apply_topk_all(lg):
        def one(row, k):
            kth = jnp.sort(row)[-jnp.maximum(k, 1)]
            mask = row >= kth
            return jnp.where((k > 0) & ~mask, NEG_INF, row)

        return jax.vmap(one)(lg, top_k)

    logits_k = jax.lax.cond(
        jnp.any(top_k > 0), apply_topk_all, lambda lg: lg, logits
    )

    # top-p (nucleus) mask
    def apply_topp_all(lg):
        def one(row, p):
            sorted_lg = jnp.sort(row)[::-1]
            probs = jax.nn.softmax(sorted_lg)
            cum = jnp.cumsum(probs)
            # keep tokens whose cumulative prob (exclusive) < p
            cutoff_count = jnp.sum(cum - probs < p)
            kth = sorted_lg[jnp.maximum(cutoff_count - 1, 0)]
            return jnp.where((p < 1.0) & (row < kth), NEG_INF, row)

        return jax.vmap(one)(lg, top_p)

    logits_kp = jax.lax.cond(
        jnp.any(top_p < 1.0), apply_topp_all, lambda lg: lg, logits_k
    )

    def draw(lg):
        temp = jnp.maximum(temperature, 1e-6)[:, None]
        keys = jax.vmap(
            lambda s, st: jax.random.fold_in(jax.random.PRNGKey(s), st)
        )(seeds, steps)
        return jax.vmap(
            lambda k, row: jax.random.categorical(k, row)
        )(keys, lg / temp)

    sampled = jax.lax.cond(
        jnp.any(temperature > 0.0), draw, lambda lg: greedy, logits_kp
    )
    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


sample_tokens = jax.jit(_sample_tokens_impl, donate_argnums=())


@partial(jax.jit, donate_argnums=())
def sample_tokens_masked(
    logits: jax.Array,  # [B, V] f32
    allowed: jax.Array,  # [B, V] bool: per-slot grammar-allowed tokens
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32
    top_p: jax.Array,  # [B] f32
    seeds: jax.Array,  # [B] uint32
    steps: jax.Array,  # [B] int32
) -> jax.Array:
    """sample_tokens under a guided-decoding constraint mask.

    Disallowed tokens drop to NEG_INF BEFORE the greedy argmax and the
    temperature/top-k/top-p pipeline, so both greedy and sampled draws
    can only land on grammar-legal tokens (guided/runtime.py guarantees
    each constrained row keeps at least one True). Free slots ride the
    same batch with all-True rows — the where() is identity for them —
    and an ALL-free batch never calls this jit at all (the engine passes
    no mask), so unguided serving pays nothing.
    """
    return _sample_tokens_impl(
        jnp.where(allowed, logits, NEG_INF),
        temperature, top_k, top_p, seeds, steps,
    )
