"""Model-family dispatch: one engine, multiple attention architectures.

The engine's hot loop (engine/core.py) is family-agnostic: it drives a
small adapter surface — params/cache init, prefill, fused decode, page
extract/insert — and the adapter maps it onto the family's functional
core. Two families today:

- ``GqaFamily``: llama/mistral/mixtral/qwen/gpt-oss/MiMo/Solar-Open2/
  Falcon-H1/Ling-3.0 (models/llama.py) — paged K and V pools, GQA
  attention, and among its layer kinds KDA and SSD layers over a
  recurrent state a sequence (``llama.KindPools``: the state pool and its
  directory ride the same pair) and latent (MLA) layers over one pool of
  latent rows a layer (``LayerKind.latent``: models/mla.py's layer under
  the kinds' block table), the full feature matrix (packed
  prefill, ring prefill, meshes, logprobs, embeddings). ``k_pages`` and
  ``v_pages`` are each ONE pytree: an array ``[L, pages, kv_heads, page,
  D]``, a ``QuantPool`` of such (fp8), or, for a model whose layer kinds
  differ in KV heads, ``llama.KindPools``: a pool a kind over one
  page-id space, K ``head_dim`` wide and V ``v_dim``. Every leaf leads
  with a layer axis.
- ``MlaFamily``: a model whose EVERY layer is latent attention,
  DeepSeek-V2/V3/R1 and JoyAI-LLM-Flash (models/mla.py's own loops; to
  fold into a one-kind model, ROADMAP.md D15): ONE latent cache, which
  rides the ``k_pages`` slot;
  the ``v_pages`` slot carries the expert layers' counters (it was an
  inert ``[1]`` placeholder), so page bookkeeping, KVBM tier blocks and
  transfer metadata flow unchanged. Supports meshes (tp over heads, ep
  over experts, replicated latent cache), packed prefill, logprobs, and
  embeddings; ring prefill (long MLA prompts chunk instead) and
  multimodal stay gated off.

Ref: the reference delegates this dispatch to its engines (vLLM model
registry); here it is explicit and small.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import ModelSpec

__all__ = ["get_family", "GqaFamily", "MlaFamily", "Lowered",
           "lowered_calls"]


class Lowered(Exception):
    """Raised in place of running one of a family's jitted programs inside
    ``lowered_calls``; ``lowered`` is the program lowered for the call's
    arguments (``jax.stages.Lowered``)."""

    def __init__(self, lowered):
        super().__init__("a program was lowered in place of running")
        self.lowered = lowered


class _Lowering:
    """A family's module of programs whose jitted functions lower and
    raise ``Lowered`` where the real ones would run."""

    def __init__(self, real):
        self._real = real

    def __getattr__(self, name):
        fn = getattr(self._real, name)
        if not (callable(fn) and hasattr(fn, "lower")):
            return fn

        def lower(*args, **kw):
            raise Lowered(fn.lower(*args, **kw))

        return lower


@contextlib.contextmanager
def lowered_calls(fam):
    """Inside, the first program ``fam`` dispatches (``fam.prefill``,
    ``fam.decode_steps``, ...) is traced and lowered for its arguments and
    raised as ``Lowered`` instead of run: nothing is donated and nothing
    executes. What ``InferenceEngine.precompile`` compiles ahead from, with
    the very call its warm-up dispatch makes, so that the dispatch finds
    the program in ``jit``'s own cache."""
    attr = "mla" if isinstance(fam, MlaFamily) else "m"
    real = getattr(fam, attr)
    setattr(fam, attr, _Lowering(real))
    try:
        yield
    finally:
        setattr(fam, attr, real)


class GqaFamily:
    """llama-family adapter: thin passthrough to models/llama.py."""

    supports_packed_prefill = True
    supports_ring_prefill = True
    supports_mesh = True
    supports_logprobs = True
    supports_embeddings = True
    supports_multimodal = True  # prefill embedding injection (EPD)
    supports_spec_decode = True  # prompt-lookup verify (engine/spec.py)
    # what the pages of a sequence are good for beyond serving it: reuse
    # under another sequence's prefix, offload to the KVBM tiers, transfer
    # to another engine (disaggregation, migration pulls, SPMD rejoin). A
    # model with recurrent layers keeps state that no page holds, so all
    # three are off for it (``recurrent``), each counted where it is
    # asked for (engine/core.py: _note_recurrent_gate)
    supports_prefix_reuse = True
    supports_page_transfer = True
    recurrent = False

    def __init__(self, spec: Any | None = None):
        from dynamo_tpu.models import llama

        self.m = llama
        # ring attention has no sink/sliding-window support: gpt-oss-like
        # specs fall back to chunked prefill for long prompts
        if spec is not None and spec.has_attn_extras:
            self.supports_ring_prefill = False
        if spec is not None and spec.has_recurrent:
            self.recurrent = True
            self.supports_ring_prefill = False  # no state across shards
            self.supports_spec_decode = False  # no roll-back of a state
            self.supports_mesh = False  # the state has no sharding yet
            self.supports_multimodal = False
            self.supports_prefix_reuse = False
            self.supports_page_transfer = False
        if spec is not None and spec.has_latent:
            # a latent kind beside the others: one pool of latent rows in
            # the kinds' page-id space (llama.KindPools), its layer from
            # models/mla.py. No V side, so the page movers, the verify's
            # token-granular write, the ring and a mesh have no form yet
            self.supports_ring_prefill = False
            self.supports_spec_decode = False
            self.supports_mesh = False
            self.supports_multimodal = False
            self.supports_page_transfer = False

    def init_params(self, spec, key):
        return self.m.init_params(spec, key)

    def param_shardings(self, spec, mesh):
        return self.m.param_shardings(spec, mesh)

    def cache_shardings(self, mesh, kv_dtype="bf16", spec=None):
        return self.m.cache_shardings(mesh, kv_dtype, spec)

    def init_cache(self, spec, num_pages, page_size, kv_dtype="bf16",
                   state_rows=0, tp=1):
        return self.m.init_cache(
            spec, num_pages, page_size, kv_dtype=kv_dtype,
            state_rows=state_rows, tp=tp,
        )

    def state_stats(self, k, v):
        """The state directory's device-side counters ``[clock, claims,
        rows missing]`` (llama.StateRows; a recurrent model's alone)."""
        return k.rows.stats[0]

    def release_state_rows(self, k, v, pages):
        """The cache with the state rows freed whose owner is among
        ``pages`` (see llama.release_state_rows)."""
        return self.m.release_state_rows(k, pages), v

    def prefill(self, spec, params, tokens, bt, start, k, v, n, mesh=None,
                mm_embeds=None, mm_pos=None):
        return self.m.prefill_forward(
            spec, params, tokens, bt, start, k, v, n, mesh=mesh,
            mm_embeds=mm_embeds, mm_pos=mm_pos,
        )

    def prefill_batch(self, spec, params, tokens, bts, starts, k, v, ns,
                      mesh=None):
        return self.m.prefill_forward_batch(
            spec, params, tokens, bts, starts, k, v, ns, mesh=mesh
        )

    def prefill_ring(self, spec, params, tokens, bt, k, v, n, mesh):
        return self.m.prefill_forward_ring(
            spec, params, tokens, bt, k, v, n, mesh=mesh
        )

    def verify(self, spec, params, tokens, bts, starts, k, v, ns,
               mesh=None, allowed=None):
        return self.m.verify_forward(
            spec, params, tokens, bts, starts, k, v, ns, mesh=mesh,
            allowed=allowed,
        )

    def decode_steps(self, spec, params, tokens, bts, lens, k, v, active,
                     temps, topk, topp, seeds, steps, *, n_steps, n_logprobs,
                     mesh=None, allowed=None):
        return self.m.decode_steps(
            spec, params, tokens, bts, lens, k, v, active, temps, topk,
            topp, seeds, steps, n_steps=n_steps, n_logprobs=n_logprobs,
            mesh=mesh, allowed=allowed,
        )

    def moe_counts(self, k, v):
        """The expert layers' device-side counters in this family's
        cache, or None: a model with layer kinds keeps them on the K
        side (``llama.KindPools.counts``)."""
        return getattr(k, "counts", None)

    def extract_pages(self, k, v, page_ids):
        return self.m.extract_kv_pages(k, v, page_ids)

    def insert_pages(self, k, v, page_ids, kb, vb):
        return self.m.insert_kv_pages(k, v, page_ids, kb, vb)

    def embed_forward(self, spec, params, tokens, num_tokens):
        return self.m.embed_forward(spec, params, tokens, num_tokens)


class MlaFamily:
    """Latent-attention (MLA) adapter over models/mla.py. The engine's
    ``(k_pages, v_pages)`` pair carries the family's two device-side
    states: ``k_pages`` the ONE latent cache ``[L, pages, page, D]`` (an
    array, or a ``QuantPool`` for fp8; for a model of shortcut-connected
    double layers a tuple of two such pools, one a sub-layer:
    ``mla.sub_pools``) and ``v_pages`` the expert layers'
    counters ``[L, 2, n_held + 3]`` int32 (``[L, 2, 0]`` without
    experts), which every program adds to as it runs. Both lead with a
    layer axis, as every leaf of the pair does; page bookkeeping, KVBM
    tier blocks and transfer metadata see the latent cache alone
    (``extract_pages`` ships an inert v block).

    Under a mesh per-head work shards over "tp", experts over "ep"
    (mla.param_shardings), and the latent cache replicates: it has no
    head axis, so every rank decodes against a local copy with no gather
    collective (ref topology: recipes/deepseek-r1/sglang-wideep/
    tep16p-dep16d-disagg.yaml:63, --ep-size 16)."""

    supports_packed_prefill = True
    supports_ring_prefill = False  # long MLA prompts take the chunked path
    supports_mesh = True
    supports_logprobs = True
    supports_embeddings = True
    supports_multimodal = False
    supports_spec_decode = True  # prompt-lookup verify (engine/spec.py)
    supports_prefix_reuse = True
    supports_page_transfer = True
    recurrent = False

    def __init__(self):
        from dynamo_tpu.models import mla

        self.mla = mla
        # what a caller reaches for the family's one-step decode program
        # in the pair's signature (``fam.m.decode_forward``)
        self.m = self

    def init_params(self, spec, key):
        return self.mla.init_params(spec, key)

    def param_shardings(self, spec, mesh):
        return self.mla.param_shardings(spec, mesh)

    def cache_shardings(self, mesh, kv_dtype="bf16", spec=None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        # the counters are a single replicated leaf either way
        sub = spec.sub_layers if spec is not None else 1
        return (self.mla.cache_shardings(mesh, kv_dtype, sub),
                NamedSharding(mesh, P()))

    def init_cache(self, spec, num_pages, page_size, kv_dtype="bf16", tp=1):
        # ``tp`` shards heads, and a latent row has none
        cache = self.mla.init_cache(
            spec, num_pages, page_size, kv_dtype=kv_dtype
        )
        return cache, self.mla.init_counts(spec)

    def prefill(self, spec, params, tokens, bt, start, k, v, n, mesh=None):
        logits, cache, counts = self.mla.prefill_forward(
            spec, params, tokens, bt, start, k, n, mesh=mesh, counts=v
        )
        # engine contract: four values, the last a constant zero
        # (llama._no_drops)
        return logits, cache, counts, jnp.zeros((), jnp.int32)

    def prefill_batch(self, spec, params, tokens, bts, starts, k, v, ns,
                      mesh=None):
        logits, cache, counts = self.mla.prefill_forward_batch(
            spec, params, tokens, bts, starts, k, ns, mesh=mesh, counts=v
        )
        return logits, cache, counts, jnp.zeros((), jnp.int32)

    def verify(self, spec, params, tokens, bts, starts, k, v, ns,
               mesh=None, allowed=None):
        targets, cache, counts = self.mla.verify_forward(
            spec, params, tokens, bts, starts, k, ns, mesh=mesh,
            allowed=allowed, counts=v,
        )
        return targets, cache, counts, jnp.zeros((), jnp.int32)

    def decode_forward(self, spec, params, tokens, bts, lens, k, v, active,
                       mesh=None):
        return self.mla.decode_forward(
            spec, params, tokens, bts, lens, k, active, mesh=mesh, counts=v
        )

    def decode_steps(self, spec, params, tokens, bts, lens, k, v, active,
                     temps, topk, topp, seeds, steps, *, n_steps, n_logprobs,
                     mesh=None, allowed=None):
        # (out[, logprobs, top ids, top values], cache, counts)
        return self.mla.decode_steps(
            spec, params, tokens, bts, lens, k, active, temps, topk, topp,
            seeds, steps, n_steps=n_steps, n_logprobs=n_logprobs, mesh=mesh,
            allowed=allowed, counts=v,
        )

    def moe_counts(self, k, v):
        return v

    def extract_pages(self, k, v, page_ids):
        # latent blocks [L, n, page, D] (a double layer's two pools one
        # behind the other on the layer axis); the v slot stays inert
        # (kept in kvbm/transfer payloads so block plumbing is
        # shape-agnostic)
        pools = self.mla.sub_pools(k)
        blocks = [_extract_latent(pool, page_ids) for pool in pools]
        n = page_ids.shape[0]
        return (blocks[0] if len(pools) == 1 else jnp.concatenate(blocks),
                jnp.zeros((1, n), jnp.int8))

    def insert_pages(self, k, v, page_ids, kb, vb):
        pools = self.mla.sub_pools(k)
        if len(pools) == 1:
            return _insert_latent(k, page_ids, kb), v
        L = len(kb) // len(pools)
        return tuple(
            _insert_latent(pool, page_ids, kb[j * L: (j + 1) * L])
            for j, pool in enumerate(pools)
        ), v

    def embed_forward(self, spec, params, tokens, num_tokens):
        return self.mla.embed_forward(spec, params, tokens, num_tokens)


@jax.jit
def _extract_latent(cache, page_ids):
    from dynamo_tpu.ops.quant import is_quant, pack_pages

    if is_quant(cache):
        # fp8 cache: values + scales leave as ONE packed uint8 payload
        # per (layer, page) — KVBM tiers/transfer carry exactly those
        # bytes (see llama._extract_kv_pages_impl)
        return pack_pages(cache, page_ids)
    return cache[:, page_ids]


# donated: the latent cache updates in place (disagg resume / KVBM
# onboard install whole pages into the live pool — a copy here doubles
# the cache's HBM footprint for the duration of the insert)
@partial(jax.jit, donate_argnums=(0,))
def _insert_latent_impl(cache, page_ids, blocks):
    from dynamo_tpu.ops.quant import QuantPool, is_quant, unpack_pages

    if is_quant(cache):
        vals, scale = unpack_pages(
            blocks, cache.vals.shape[2:], cache.scale.shape[2:]
        )
        return QuantPool(
            cache.vals.at[:, page_ids].set(vals),
            cache.scale.at[:, page_ids].set(scale),
        )
    return cache.at[:, page_ids].set(blocks)


def _insert_latent(cache, page_ids, blocks):
    return _insert_latent_impl(cache, page_ids, jnp.asarray(blocks))


def get_family(spec: ModelSpec) -> Any:
    return MlaFamily() if spec.is_mla else GqaFamily(spec)
