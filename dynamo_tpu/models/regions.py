"""The regions of the device programs: the one list of names the program
may open with ``jax.named_scope``, each with the group it reports under.

A scope is metadata: it becomes a component of the ``op_name`` of every
operation traced under it (``jit(decode_steps_impl)/.../attn_qkv/
dot_general``) and changes no program. The models, the kernels' callers,
the sampler and the engine's feed glue import their ``SCOPE_*`` strings
from here; a reader of a profiler trace imports the same table
(``perfbench/lib/regions.py`` loads this file by path), so the vocabulary
of a breakdown is the program's own. Nothing is imported here: the file
loads without JAX.

A Mosaic call is named in a trace after the innermost scope (or jit)
around it, and the benchmark's ``kernels.*`` metrics find it by that name
(a configuration's ``trace_names``): no scope below may be opened directly
around a kernel that already has one of ``KERNEL_SCOPES``.
"""

from __future__ import annotations

import re

# the groups, as PERF.md section 3's rows
ATTN_PROJ = "attn_proj"  # what turns a layer's input into queries, keys,
# values (or latents, or the KDA mixer's operands), and its output back
ATTN_CTX = "attn_ctx"  # cache write + attention over the cached context,
# or the recurrent state's update
FFN = "ffn"
HEAD = "head"
REST = "rest"
GROUPS = (ATTN_PROJ, ATTN_CTX, FFN, HEAD, REST)

# -- attn_proj
SCOPE_QKV = "attn_qkv"  # q/k/v projections + rope (GQA); the KDA mixer's
# operands, whose parts are named beneath it
SCOPE_OUT = "attn_out"  # output projection (and its gate)
SCOPE_LATENT_Q = "latent_q"  # MLA: query down- and up-projection + rope
SCOPE_LATENT_KV = "latent_kv"  # MLA: the new latent row (down-projection,
# norm, roped shared key)
SCOPE_LATENT_ABSORB = "latent_absorb"  # MLA decode: W_uk into the queries,
# W_uv out of the latent output
SCOPE_KDA_PROJ = "kda_proj"  # KDA: the q | k | v projections
SCOPE_KDA_CONV = "kda_conv"  # KDA: tails, the causal convolutions, norms
SCOPE_KDA_GATES = "kda_gates"  # KDA: decay and beta
SCOPE_SSM_PROJ = "ssm_proj"  # SSD: the input and output projections, mup
SCOPE_SSM_CONV = "ssm_conv"  # SSD: tails, the causal convolution on x|B|C
SCOPE_SSM_GATES = "ssm_gates"  # SSD: dt, the decays, the gated group norm
SCOPE_CONV_PROJ = "conv_proj"  # short convolution: input and output
# projections
SCOPE_CONV_MIX = "conv_mix"  # short convolution: tail read, B * x, the
# taps, C *, tail write
SCOPE_GMU = "gmu"  # a Gated Memory Unit: both projections and the gate
# by the memory layer's output
# -- attn_ctx
SCOPE_KV = "attn_kv"  # KV write + attention over the paged context
SCOPE_ATTN_WINDOW = "attn_window"
SCOPE_ATTN_FULL = "attn_full"
SCOPE_FUSED_DECODE = "fused_decode_attention"  # the kernel's own name,
# where a model of one kind of layer opens no scope around it
# the latent kernels keep these names whichever loops call them: a model
# whose every layer is latent (models/mla.py's own) and a latent KIND
# beside KDA layers in models/llama.py's, where one decode program holds
# ``kda_step`` and ``attn_latent`` both
SCOPE_ATTN_LATENT = "attn_latent"
SCOPE_PREFILL_LATENT = "prefill_latent"
SCOPE_LATENT_SCHEDULE = "latent_schedule"  # the decode kernel's live
# chunks, once a step
SCOPE_KDA_STEP = "kda_step"
SCOPE_KDA_CHUNK = "kda_chunk"
SCOPE_KDA_CHUNK_OPERANDS = "kda_chunk_operands"  # what XLA does of the
# chunkwise form beside ``kda_chunk``: the pad of the token axis where the
# kernel forms a block's operands itself; off the chip the batched half
# (decays, triangular solves, re-layouts)
SCOPE_SSD_STEP = "ssd_step"  # SSD decode: the state rows' update
SCOPE_SSD_CHUNK = "ssd_chunk"  # SSD prefill: the chunk form, all of it
SCOPE_STATE_ROWS = "state_rows"  # the recurrent state's directory
SCOPE_SCAN = "scan"  # Mamba-1's selective scan: the decode step's kernel
# (and its XLA twin), the prefill's walk (``scan_chunk`` below, and its
# XLA twin, the chunk form). Its projections, convolution and gates
# report under the SSD mixer's names
SCOPE_SCAN_CHUNK = "scan_chunk"  # the prefill walk's kernel, named by its
# own jit (ops/pallas/scan.py: scan_chunk), called inside ``scan``: a LEAF
# of that region (``LEAVES``), so the region's seconds compare across the
# XLA form and the kernel
SCOPE_ATTN_CROSS = "attn_cross"  # a layer that reads another layer's
# pages and writes none (SambaY's cross-decoder): its decode kernel
SCOPE_ATTN_DIFF = "attn_diff"  # differential attention: the two maps'
# difference under lambda and the norm a pair
# -- ffn
SCOPE_MLP = "mlp"
SCOPE_ROUTE = "moe_route"
SCOPE_EXPERTS = "moe_experts"
SCOPE_MOE_DISPATCH = "moe_dispatch"  # sort the assignments by expert
# (and invert the sort for the combine), gather their rows
SCOPE_MOE_GROUPED = "moe_grouped"  # the three grouped products + swiglu
SCOPE_GMM = "gmm"  # the Mosaic grouped product: ops/pallas/grouped.py where
# a call's rows fit VMEM, else megablox's own jit of that name
SCOPE_MOE_COMBINE = "moe_combine"  # un-permute: a token gathers its k rows
# back and adds them, weighted, in float32
SCOPE_MOE_SHARED = "moe_shared"  # the shared expert
SCOPE_MOE_ZERO = "moe_zero"  # the identity experts: a token's own input
# times the sum of its identity picks' weights
SCOPE_MOE_COUNT = "moe_count"  # the layer's device-side counters
# -- head
SCOPE_HEAD = "head"  # final norm + vocabulary projection
SCOPE_SAMPLER = "sampler"
# -- rest
SCOPE_NORM = "norm"  # a layer's two input norms
SCOPE_NORM_OUT = "norm_out"  # the norms of what a layer's mixer and its
# FFN put out, before the residual add (``ModelSpec.sandwich_norm``)
SCOPE_RESIDUAL = "residual"
SCOPE_EMBED = "embed"
SCOPE_INDEX = "page_index"  # positions, page ids and masks a program
# derives from its tables
SCOPE_BURST = "burst_glue"  # a decode burst's carry between its steps
SCOPE_FEED = "feed"  # the engine's feed glue between programs

REGIONS: dict[str, str] = {
    SCOPE_QKV: ATTN_PROJ, SCOPE_OUT: ATTN_PROJ, SCOPE_LATENT_Q: ATTN_PROJ,
    SCOPE_LATENT_KV: ATTN_PROJ, SCOPE_LATENT_ABSORB: ATTN_PROJ,
    SCOPE_KDA_PROJ: ATTN_PROJ, SCOPE_KDA_CONV: ATTN_PROJ,
    SCOPE_KDA_GATES: ATTN_PROJ, SCOPE_SSM_PROJ: ATTN_PROJ,
    SCOPE_SSM_CONV: ATTN_PROJ, SCOPE_SSM_GATES: ATTN_PROJ,
    SCOPE_CONV_PROJ: ATTN_PROJ, SCOPE_CONV_MIX: ATTN_PROJ,
    SCOPE_GMU: ATTN_PROJ,
    SCOPE_KV: ATTN_CTX, SCOPE_ATTN_WINDOW: ATTN_CTX,
    SCOPE_ATTN_FULL: ATTN_CTX, SCOPE_FUSED_DECODE: ATTN_CTX,
    SCOPE_ATTN_LATENT: ATTN_CTX, SCOPE_PREFILL_LATENT: ATTN_CTX,
    SCOPE_LATENT_SCHEDULE: ATTN_CTX, SCOPE_KDA_STEP: ATTN_CTX,
    SCOPE_KDA_CHUNK: ATTN_CTX, SCOPE_KDA_CHUNK_OPERANDS: ATTN_CTX,
    SCOPE_SSD_STEP: ATTN_CTX, SCOPE_SSD_CHUNK: ATTN_CTX,
    SCOPE_STATE_ROWS: ATTN_CTX, SCOPE_SCAN: ATTN_CTX,
    SCOPE_ATTN_CROSS: ATTN_CTX, SCOPE_ATTN_DIFF: ATTN_CTX,
    SCOPE_MLP: FFN, SCOPE_ROUTE: FFN, SCOPE_EXPERTS: FFN,
    SCOPE_MOE_DISPATCH: FFN, SCOPE_MOE_GROUPED: FFN, SCOPE_GMM: FFN,
    SCOPE_MOE_COMBINE: FFN, SCOPE_MOE_SHARED: FFN, SCOPE_MOE_COUNT: FFN,
    SCOPE_MOE_ZERO: FFN,
    SCOPE_HEAD: HEAD, SCOPE_SAMPLER: HEAD,
    SCOPE_NORM: REST, SCOPE_NORM_OUT: REST, SCOPE_RESIDUAL: REST,
    SCOPE_EMBED: REST,
    SCOPE_INDEX: REST, SCOPE_BURST: REST, SCOPE_FEED: REST,
}

# kernel names that are no region of their own: each is opened inside the
# region it names here and reports as a leaf of it (``resolve`` passes
# over a name that ``REGIONS`` does not hold)
LEAVES: dict[str, str] = {SCOPE_SCAN_CHUNK: SCOPE_SCAN}

# the names a trace gives the Mosaic calls, which the benchmark's
# ``kernels.*`` metrics read by: each stays the innermost name around its
# kernel
KERNEL_SCOPES = (
    SCOPE_FUSED_DECODE, SCOPE_ATTN_WINDOW, SCOPE_ATTN_FULL,
    SCOPE_ATTN_LATENT, SCOPE_PREFILL_LATENT, SCOPE_GMM, SCOPE_KDA_STEP,
    SCOPE_KDA_CHUNK, SCOPE_SSD_STEP, SCOPE_SSD_CHUNK, SCOPE_SCAN,
    SCOPE_ATTN_CROSS, SCOPE_SCAN_CHUNK,
)

_WRAPPED = re.compile(r"\(([^()]*)\)")


def _bare(component: str) -> str:
    """A component of an ``op_name`` without the transforms around it:
    ``jit(gmm)`` -> ``gmm``, ``vmap(jit(attn_full))`` -> ``attn_full``."""
    m = _WRAPPED.search(component)
    return m.group(1) if m else component


def resolve(op_name: str) -> tuple[str | None, str]:
    """(region, leaf) of an operation from its ``op_name``: the innermost
    component that is a name of ``REGIONS`` (None where the path holds
    none), and the path's last component, the primitive, or the name of
    ``LEAVES`` it stands under inside the region."""
    parts = [p for p in op_name.split("/") if p]
    if not parts:
        return None, ""
    leaf = parts[-1]
    for part in reversed(parts):
        name = _bare(part)
        if name in REGIONS:
            return name, leaf
        if name in LEAVES:
            leaf = name
    return None, parts[-1]


def group_of(region: str | None) -> str:
    """The group a region reports under; ``rest`` for none."""
    return REGIONS.get(region, REST)
