"""Model + engine configuration.

ModelSpec describes a llama-family transformer (all the models the reference
recipes target are in-family or MoE variants handled in models/moe.py);
EngineConfig describes the serving engine's memory and batching envelope.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass(frozen=True)
class LayerKind:
    """One kind of attention layer: what differs between the layers of a
    model that interleaves kinds (window and global layers with their own
    KV head counts and rope bases). Everything a layer shares with the
    others stays on ``ModelSpec``."""

    num_kv_heads: int
    rope_theta: float
    window: int = 0  # sliding window in tokens; 0 = full attention
    sinks: bool = False  # one learned logit a query head in the softmax
    # what mixes tokens: "softmax" attention over the sequence's pages;
    # "kda" (Kimi Delta Attention, arXiv:2510.26692): a gated delta rule
    # over a fixed-size state a sequence, which owns no page in this layer
    # (``ModelSpec.kda_*`` give its sizes; the three fields above are
    # unread for it); or "ssd" (Mamba-2, arXiv:2405.21060): a scalar-decay
    # state-space mixer over a state row (``ModelSpec.ssm_*``) IN PARALLEL
    # with softmax attention over pages off the same norm (Falcon-H1), so
    # the kind keeps both: what a kind keeps is ``paged`` and ``recurrent``;
    # or "latent" (MLA, models/mla.py): softmax attention over ONE pool of
    # latent rows a layer, ``kv_lora_rank + qk_rope_head_dim`` wide and
    # shared by the heads (``ModelSpec``'s MLA sizes and rope base;
    # ``num_kv_heads`` and ``window`` are unread for it), and no V pool;
    # or "conv" (LFM2's gated short convolution): ``C * conv(B * x)`` off
    # one input projection, whose whole state is the ``conv_taps - 1``
    # last ``B * x`` a sequence: a row of tails and no state matrix;
    # or "scan" (Mamba-1, arXiv:2312.00752): a selective scan ALONE in its
    # layer over a state ``[scan_state, scan_inner]`` a row, a decay a
    # channel a state (``ModelSpec.scan_*``), beside the tail of its
    # 4-tap convolution; or "gmu" (SambaY's Gated Memory Unit,
    # arXiv:2507.06607): ``W_2 (silu(W_1 h) * m)`` with ``m`` the output
    # of the model's memory layer for the same token
    # (``ModelSpec.memory_layer``): it keeps neither pages nor a row
    mixer: str = "softmax"
    # a KDA kind's forms. ``gate_bound`` < 0: the decay a channel is
    # bounded, ``gate_bound * sigmoid(exp(a_log) * (f + dt_bias))`` in
    # (gate_bound, 0); 0: ``-exp(a_log) * softplus(f + dt_bias)``.
    # ``full_rank``: the decay's and the output gate's projections are one
    # matrix each ``d -> H D``, not a pair through rank ``kda_head_dim``
    gate_bound: float = 0.0
    full_rank: bool = False
    # the attention output is gated by HEAD before the output projection:
    # ``wo (a_h * sigmoid(x @ w_gate_head)_h)`` (``ModelSpec.attn_gate``
    # is the gate by element)
    head_gate: bool = False
    # softmax layers of this kind rotate q and k at the position. False:
    # the kind carries no position (NoPE) and ``rope_theta`` is unread,
    # beside kinds of the same model that do rotate
    # (``ModelSpec.use_rope`` is the switch for every kind at once)
    rope: bool = True
    # differential attention (arXiv:2410.05258): the heads pair up (even,
    # odd), a pair's output is ``rms(a1 - lambda a2)`` over a V twice as
    # wide, and the kind's pool holds a PAIR a row: ``num_kv_heads / 2``
    # heads of ``2 head_dim`` (K ``[k1 | k2]``, V ``[v_2j | v_2j+1]``)
    differential: bool = False
    # a softmax kind that owns no pool: its layers have queries and an
    # output projection alone and read the pages of layer ``reads[1]``
    # (among its kind's layers) of kind ``reads[0]`` (SambaY's
    # cross-decoder). Empty: the kind reads and writes its own
    reads: tuple[int, ...] = ()

    def __post_init__(self) -> None:
        object.__setattr__(self, "reads", tuple(self.reads))

    @property
    def recurrent(self) -> bool:
        """The kind keeps a row a sequence beside the pages (found
        through ``llama.StateRows``): convolution tails, and a state
        matrix where it has one (``state``)."""
        return self.mixer in ("kda", "ssd", "conv", "scan")

    @property
    def state(self) -> bool:
        """The kind's row holds a float32 state matrix beside its tails."""
        return self.mixer in ("kda", "ssd", "scan")

    @property
    def latent(self) -> bool:
        """The kind's pages are latent rows: one pool, no V side."""
        return self.mixer == "latent"

    @property
    def paged(self) -> bool:
        """The kind keeps pages: it has softmax attention (KV heads, or
        the one latent row the heads share) over pages of its own."""
        return (self.num_kv_heads > 0 or self.latent) and not self.reads

    @property
    def carried(self) -> bool:
        """A layer of the kind writes nothing a later token reads: it
        mixes what earlier layers left for the SAME token (a GMU) or
        another layer's pages (``reads``), so a prefill runs it for a
        sequence's last row alone."""
        return self.mixer == "gmu" or bool(self.reads)


@dataclass(frozen=True)
class ModelSpec:
    name: str = "tiny-test"
    vocab_size: int = 272  # mock-tokenizer-compatible default
    hidden_size: int = 128
    intermediate_size: int = 256
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    # MoE (0 experts = dense)
    num_experts: int = 0
    num_experts_per_token: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0  # always-on dense experts (DeepSeek)
    first_k_dense: int = 0  # leading layers with plain dense MLP
    # routing flavor: "softmax" (mixtral/qwen/gpt-oss), "sigmoid"
    # (DeepSeek-V3 noaux_tc: sigmoid scores + learned correction bias +
    # group-limited top-k + routed scaling) or "softmax_bias" (LongCat-
    # Flash: softmax over ALL the router's outputs, the correction bias
    # picks, the weights are the probabilities x routed scaling,
    # renormalised only under ``norm_topk_prob``)
    moe_scoring: str = "softmax"
    n_group: int = 0  # expert groups for group-limited routing (0 = off)
    topk_group: int = 0  # groups each token may route into
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # identity ("zero-computation") experts behind the ``num_experts`` FFN
    # experts: the router has ``num_experts + zero_experts`` outputs, and a
    # pick of id >= ``num_experts`` adds ``w * u`` at the token's own chip.
    # No chip holds them and they have no weights
    zero_experts: int = 0
    # shortcut-connected MoE (LongCat-Flash): a decoder layer is TWO
    # sub-layers (a latent attention and a dense FFN of
    # ``intermediate_size`` each) and one expert layer whose input is the
    # first FFN's and whose output is added after the second FFN. The
    # cache keeps a pool a sub-layer (models/mla.py)
    shortcut_moe: bool = False
    # MLA (DeepSeek-family latent attention; 0 = plain GQA attention)
    kv_lora_rank: int = 0  # latent dim d_c (the per-token KV cache row)
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0  # decoupled-RoPE key dim, shared across heads
    v_head_dim: int = 0
    q_lora_rank: int = 0  # query low-rank compression (0 = full q_proj)
    # LongCat-Flash's two scalars: the queries times sqrt(hidden /
    # q_lora_rank) behind ``wq_b``, the normed latent (not the roped key)
    # times sqrt(hidden / kv_lora_rank) before ``w_kv_b``
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # gpt-oss attention extras (ref recipes/gpt-oss-120b; HF GptOssConfig)
    sliding_window: int = 0  # 0 = full attention everywhere
    layer_types: tuple[str, ...] = ()  # per-layer "sliding_attention" /
    # "full_attention"; empty + sliding_window>0 = every layer windowed
    attn_sinks: bool = False  # learned per-head sink logits in softmax
    attn_bias: bool = False  # q/k/v/o projection biases
    moe_bias: bool = False  # router + expert (gate_up/down) biases
    swiglu_limit: float = 0.0  # clamped swiglu bound (gpt-oss 7.0); 0 = off
    swiglu_alpha: float = 0.0  # swish slope inside clamp (gpt-oss 1.702)
    # a plain-SiLU clamp A LAYER (0 = none; empty = no layer has one):
    # ``silu(min(gate, L)) * clip(up, -L, L)``, no alpha and no + 1, the
    # routed experts' and the shared expert's apart
    expert_clamp: tuple[float, ...] = ()
    shared_clamp: tuple[float, ...] = ()
    # YaRN rope scaling (gpt-oss, DeepSeek-R1; HF _compute_yarn_parameters)
    rope_scaling_factor: float = 0.0  # 0 = no scaling
    rope_orig_max_pos: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.0  # 0 = unset
    rope_mscale_all_dim: float = 0.0
    rope_truncate: bool = True  # floor/ceil the correction range bounds
    # checkpoint stores rope dims pair-interleaved (DeepSeek MLA weights);
    # the loader de-interleaves q_rope/k_rope projection columns to our
    # half-split convention — exact, since both sides of every rope-dim
    # dot product get the same permutation
    rope_interleave: bool = False
    # layers by kind (GQA path). ``layer_kinds`` lists the kinds and
    # ``layer_pattern`` gives each layer's index into it; the KV cache
    # then holds one pool a kind (models/llama.py: init_cache), since the
    # kinds may differ in KV heads. Empty = one kind, described by the
    # flat fields above (``sliding_window``/``layer_types``/``attn_sinks``
    # remain the gpt-oss shorthand for two kinds that share a pool).
    layer_kinds: tuple[LayerKind, ...] = ()
    layer_pattern: tuple[int, ...] = ()
    # GQA heads whose K and V differ in width: ``head_dim`` is q and k,
    # ``v_head_dim`` is v and the attention output (0 = head_dim)
    rotary_dim: int = 0  # leading dims of q/k that rotate (0 = all)
    value_scale: float = 1.0  # v is multiplied by this before attention
    # expert parallelism told from outside: this process holds
    # ``held_experts = (count, first)`` of ``num_experts``; the router
    # keeps all its outputs and assignments to absent experts add nothing
    # here (their chips add them). Empty = all experts held.
    held_experts: tuple[int, ...] = ()
    # multi-token-prediction layers the published checkpoint carries
    # behind its ``num_layers`` decoder layers (``num_nextn_predict_
    # layers``). The next-token forward pass has no use for them: the
    # loader drops their tensors as expected, not as strays
    nextn_predict_layers: int = 0
    # softmax layers without a positional term (NoPE): q and k are not
    # rotated and ``rope_theta`` is unread
    use_rope: bool = True
    # softmax layers gate their attention output by element before the
    # output projection: ``wo (a * sigmoid(x @ w_gate_attn))``
    attn_gate: bool = False
    # KDA layers (``LayerKind.mixer == "kda"``): heads of ``kda_head_dim``
    # keys and values (no KV grouping), a causal depthwise convolution of
    # ``kda_conv`` taps on q, k and v, decay and output gate through a
    # rank of ``kda_head_dim``, and beta in (0, 2) where
    # ``kda_neg_eigval`` (else (0, 1))
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_neg_eigval: bool = False
    # SSD layers (``LayerKind.mixer == "ssd"``, Mamba-2): ``ssm_heads``
    # heads of ``ssm_head_dim`` channels over a state ``[head_dim,
    # ssm_state]`` a head, B and C shared by the heads of one of
    # ``ssm_groups`` groups, a causal depthwise convolution of ``ssm_conv``
    # taps (with bias) on x | B | C, the chunkwise form at ``ssm_chunk``
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # gated short-convolution layers (``LayerKind.mixer == "conv"``): a
    # causal depthwise convolution of ``conv_taps`` taps, no bias, on
    # ``B * x`` over ``hidden_size`` channels
    conv_taps: int = 3
    # selective-scan layers (``LayerKind.mixer == "scan"``, Mamba-1):
    # ``scan_inner`` channels over ``scan_state`` states a channel, the
    # time step through a rank of ``scan_dt_rank``, a causal depthwise
    # convolution of ``scan_conv`` taps (with bias); GMU layers are
    # ``scan_inner`` wide too
    scan_inner: int = 0
    scan_state: int = 16
    scan_dt_rank: int = 0
    scan_conv: int = 4
    # the norms' form: "rms" (a gain), or "layer" (LayerNorm: mean and
    # variance, a gain and a bias; the layers' two and the final one)
    norm: str = "rms"
    # the PUBLISHED index of each layer where a cut keeps some of them
    # (``layers_kept``) and a layer's constants follow it (differential
    # attention's ``lambda_init``); empty: a layer's own index
    layer_ids: tuple[int, ...] = ()
    # softmax layers norm q and k a head before the rotation (RMSNorm,
    # one gain ``[head_dim]`` each, shared by the heads)
    qk_norm: bool = False
    # what the sigmoid router adds to the chosen scores' sum before it
    # divides by it (``norm_topk_prob``); a family's published constant
    moe_norm_eps: float = 1e-20
    # sandwich norms (afmoe): a layer norms what its mixer and its FFN put
    # OUT, each with a gain of its own (``post_attn_norm``,
    # ``post_mlp_norm``), before the residual add: ``x + norm(attn(norm(
    # x)))``, then ``x + norm(ffn(norm(x)))``
    sandwich_norm: bool = False
    # the Falcon-H1 family's fixed scalar multipliers (muP); 1 = absent.
    # ``ssm_multipliers`` scale the z | x | B | C | dt segments of the SSM
    # input projection's output, ``mlp_multipliers`` the gate projection
    # and the down projection's output
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = ()
    mlp_multipliers: tuple[float, ...] = ()
    # blocks along the vocabulary that ``init_params`` DRAWS the embedding
    # and the head in, block b on its key folded with b (random weights
    # only; a checkpoint's tables load as they are). 1 = whole, on the key
    # itself. A table whose float32 normals would not fit beside the model
    # sets more: 261,120 x 5,120 is 5.35 GB whole, a third of a chip (the
    # largest drawn whole, 131,072 x 5,120, is 2.68 GB)
    vocab_draw_blocks: int = 1

    def __post_init__(self) -> None:
        # a spec read from JSON brings lists and dicts; the spec is a
        # static argument of every program, so it must hash
        def fix(name, value):
            object.__setattr__(self, name, value)

        fix("layer_types", tuple(self.layer_types))
        fix("layer_pattern", tuple(self.layer_pattern))
        fix("layer_ids", tuple(self.layer_ids))
        fix("held_experts", tuple(self.held_experts))
        fix("expert_clamp", tuple(float(c) for c in self.expert_clamp))
        fix("shared_clamp", tuple(float(c) for c in self.shared_clamp))
        fix("ssm_multipliers", tuple(float(m) for m in self.ssm_multipliers))
        fix("mlp_multipliers", tuple(float(m) for m in self.mlp_multipliers))
        fix("layer_kinds", tuple(
            k if isinstance(k, LayerKind) else LayerKind(**k)
            for k in self.layer_kinds
        ))
        if self.layer_kinds and len(self.layer_pattern) != self.num_layers:
            raise ValueError(
                f"layer_pattern names {len(self.layer_pattern)} layers, "
                f"the model has {self.num_layers}"
            )
        if self.layer_kinds and not self.layer_kinds[0].paged:
            # the cache's first leaf is a page pool (llama.page_size_of)
            raise ValueError("layer_kinds must list a paged kind first")
        if self.carried_from < self.num_layers and not all(
            self.kind(li).carried
            for li in range(self.carried_from, self.num_layers)
        ):
            raise ValueError(
                "layers that read a memory or another layer's pages (gmu, "
                "reads) come last: a prefill runs them for one row"
            )
        if self.zero_experts and self.moe_scoring != "softmax_bias":
            raise ValueError(
                "zero_experts are routed by moe_scoring 'softmax_bias' alone"
            )
        if self.shortcut_moe and not (
            self.is_mla and self.num_experts
            and not (self.first_k_dense or self.n_shared_experts)
        ):
            raise ValueError(
                "shortcut_moe: every layer is two latent attentions, two "
                "dense FFNs and an expert layer (no leading dense layer, "
                "no shared expert)"
            )
        if self.held_experts:
            n, first = self.held_experts
            if not 0 < n <= self.num_experts - first:
                raise ValueError(
                    f"held_experts {self.held_experts} outside "
                    f"{self.num_experts} experts"
                )

    def kind(self, li: int) -> LayerKind:
        """What kind of layer ``li`` is: THE place the ``sliding_window``
        / ``layer_types`` / ``attn_sinks`` shorthand (two kinds that share
        a pool) resolves into a ``LayerKind``."""
        if self.layer_kinds:
            return self.layer_kinds[self.layer_pattern[li]]
        window = self.sliding_window
        if window and self.layer_types:
            window = (
                window if self.layer_types[li] == "sliding_attention" else 0
            )
        return LayerKind(
            self.num_kv_heads, self.rope_theta, window, self.attn_sinks
        )

    @property
    def kinds(self) -> tuple[LayerKind, ...]:
        """Every kind of layer the model has, listed or resolved from the
        shorthand."""
        return self.layer_kinds or tuple(dict.fromkeys(
            self.kind(li) for li in range(self.num_layers)
        ))

    @property
    def has_recurrent(self) -> bool:
        """Some layer keeps a recurrent state a sequence beside the pages."""
        return any(k.recurrent for k in self.layer_kinds)

    @property
    def has_latent(self) -> bool:
        """Some kind of layer keeps latent pages beside the other kinds."""
        return any(k.latent for k in self.layer_kinds)

    @property
    def carried_from(self) -> int:
        """The first layer of the model's upper half, whose layers write
        no cache (``LayerKind.carried``: SambaY's cross-decoder), or
        ``num_layers`` where it has none. A prefill program takes every
        row through the layers below and one row a sequence from here."""
        return next(
            (li for li in range(self.num_layers) if self.kind(li).carried),
            self.num_layers,
        ) if self.layer_kinds else self.num_layers

    @property
    def memory_layer(self) -> int:
        """The scan layer whose output before its gate every GMU layer
        reads: the last selective scan below ``carried_from`` (SambaY: the
        self-decoder's last scan), or -1 where the model has none."""
        return max(
            (li for li in range(self.carried_from)
             if self.kind(li).mixer == "scan"), default=-1)

    def layer_id(self, li: int) -> int:
        """Layer ``li``'s published index (``layer_ids``)."""
        return self.layer_ids[li] if self.layer_ids else li

    def clamps(self, li: int) -> tuple[float, float]:
        """Layer ``li``'s plain-SiLU clamps (routed experts, shared
        expert); 0 = none."""
        return (self.expert_clamp[li] if self.expert_clamp else 0.0,
                self.shared_clamp[li] if self.shared_clamp else 0.0)

    @property
    def mixers(self) -> frozenset[str]:
        """The ``LayerKind.mixer`` of every listed kind."""
        return frozenset(k.mixer for k in self.layer_kinds)

    def pool_slot(self, li: int) -> tuple[int, int]:
        """(kind, index among that kind's layers) of layer ``li``: where
        its pages live when the cache holds a pool a kind."""
        ki = self.layer_pattern[li]
        return ki, self.layer_pattern[:li].count(ki)

    def is_moe_layer(self, li: int) -> bool:
        return bool(self.num_experts) and li >= self.first_k_dense

    @property
    def v_dim(self) -> int:
        """Width of a value head (GQA path)."""
        return self.v_head_dim or self.head_dim

    @property
    def experts_here(self) -> tuple[int, int]:
        """(count, first) of the experts this process holds."""
        return self.held_experts or (self.num_experts, 0)

    @property
    def router_outputs(self) -> int:
        """The router's width: the FFN experts, then the identity ones."""
        return self.num_experts + self.zero_experts

    @property
    def sub_layers(self) -> int:
        """Attentions (and cache layers) a decoder layer."""
        return 2 if self.shortcut_moe else 1

    @property
    def has_attn_extras(self) -> bool:
        return any(k.window or k.sinks for k in self.kinds)

    @classmethod
    def llama3_8b(cls) -> "ModelSpec":
        return cls(
            name="llama-3-8b", vocab_size=128256, hidden_size=4096,
            intermediate_size=14336, num_layers=32, num_heads=32,
            num_kv_heads=8, head_dim=128, tie_embeddings=False,
        )

    @classmethod
    def llama3_70b(cls) -> "ModelSpec":
        return cls(
            name="llama-3-70b", vocab_size=128256, hidden_size=8192,
            intermediate_size=28672, num_layers=80, num_heads=64,
            num_kv_heads=8, head_dim=128, tie_embeddings=False,
        )

    @classmethod
    def tiny(cls, vocab_size: int = 272) -> "ModelSpec":
        return cls(vocab_size=vocab_size)

    @classmethod
    def dryrun(cls) -> "ModelSpec":
        """Tiny spec with kv_heads=8 so tp up to 8 divides the KV head axis
        (__graft_entry__, profile_engine's CPU smoke)."""
        return cls(
            name="dryrun", vocab_size=512, hidden_size=256,
            intermediate_size=512, num_layers=2, num_heads=8,
            num_kv_heads=8, head_dim=32, tie_embeddings=True,
        )

    @classmethod
    def tiny_moe(cls) -> "ModelSpec":
        return cls(
            name="tiny-moe", vocab_size=272, hidden_size=64,
            intermediate_size=128, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=16, dtype="float32",
            num_experts=4, num_experts_per_token=2, moe_intermediate_size=64,
        )

    @classmethod
    def mixtral_8x7b(cls) -> "ModelSpec":
        return cls(
            name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
            intermediate_size=14336, num_layers=32, num_heads=32,
            num_kv_heads=8, head_dim=128, tie_embeddings=False,
            num_experts=8, num_experts_per_token=2,
            moe_intermediate_size=14336,
        )

    @classmethod
    def gpt_oss_120b(cls) -> "ModelSpec":
        """Wide-EP config (ref: engine_configs gpt-oss-120b recipes), with
        the full attention feature set: alternating sliding-window/full
        layers, attention sinks, projection + expert biases, clamped
        swiglu, YaRN rope (HF GptOssConfig values)."""
        return cls(
            name="gpt-oss-120b", vocab_size=201088, hidden_size=2880,
            intermediate_size=2880, num_layers=36, num_heads=64,
            num_kv_heads=8, head_dim=64, tie_embeddings=False,
            rope_theta=150000.0,
            num_experts=128, num_experts_per_token=4,
            moe_intermediate_size=2880,
            sliding_window=128,
            layer_types=tuple(
                "sliding_attention" if i % 2 == 0 else "full_attention"
                for i in range(36)
            ),
            attn_sinks=True, attn_bias=True, moe_bias=True,
            swiglu_limit=7.0, swiglu_alpha=1.702,
            rope_scaling_factor=32.0, rope_orig_max_pos=4096,
            rope_truncate=False,
        )

    @classmethod
    def tiny_gpt_oss(cls) -> "ModelSpec":
        """Toy gpt-oss architecture at test scale: every flagship
        attention extra on (sinks, alternating sliding windows, biases,
        clamped swiglu, YaRN)."""
        return cls(
            name="tiny-gpt-oss", vocab_size=96, hidden_size=32,
            intermediate_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=8, dtype="float32",
            tie_embeddings=False, rope_theta=150000.0,
            num_experts=4, num_experts_per_token=2,
            moe_intermediate_size=32,
            sliding_window=8,
            layer_types=("sliding_attention", "full_attention"),
            attn_sinks=True, attn_bias=True, moe_bias=True,
            swiglu_limit=7.0, swiglu_alpha=1.702,
            rope_scaling_factor=32.0, rope_orig_max_pos=4096,
            rope_truncate=False,
        )

    @classmethod
    def deepseek_r1(cls) -> "ModelSpec":
        """DeepSeek-R1/V3 (ref recipes/deepseek-r1/): MLA + wide MoE with
        one shared expert and 3 leading dense layers."""
        return cls(
            name="deepseek-r1", vocab_size=129280, hidden_size=7168,
            intermediate_size=18432, num_layers=61, num_heads=128,
            num_kv_heads=128, head_dim=128, tie_embeddings=False,
            rope_theta=10000.0,
            rope_scaling_factor=40.0, rope_orig_max_pos=4096,
            rope_mscale=1.0, rope_mscale_all_dim=1.0,
            rope_interleave=True,
            num_experts=256, num_experts_per_token=8,
            moe_scoring="sigmoid", n_group=8, topk_group=4,
            routed_scaling_factor=2.5,
            moe_intermediate_size=2048, n_shared_experts=1,
            first_k_dense=3,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, q_lora_rank=1536,
        )

    @classmethod
    def tiny_deepseek(cls) -> "ModelSpec":
        """Toy MLA+MoE spec: the deepseek-r1 architecture at test scale."""
        return cls(
            name="tiny-deepseek", vocab_size=96, hidden_size=32,
            intermediate_size=64, num_layers=3, num_heads=4,
            num_kv_heads=4, head_dim=16, dtype="float32",
            tie_embeddings=False,
            num_experts=4, num_experts_per_token=2,
            moe_scoring="sigmoid", n_group=2, topk_group=1,
            routed_scaling_factor=2.5,
            moe_intermediate_size=32, n_shared_experts=1, first_k_dense=1,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, q_lora_rank=24,
        )

    @classmethod
    def tiny_solar(cls, **kw) -> "ModelSpec":
        """Toy Solar-Open2 architecture: one gated NoPE GQA layer to three
        KDA layers, sigmoid-routed experts beside a shared expert in every
        layer."""
        base = dict(
            name="tiny-solar", vocab_size=96, hidden_size=64,
            intermediate_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=16, dtype="float32",
            rope_theta=10000.0, tie_embeddings=False, use_rope=False,
            attn_gate=True,
            layer_kinds=(
                LayerKind(2, 10000.0),
                LayerKind(0, 0.0, mixer="kda"),
            ),
            layer_pattern=(0, 1, 1, 1),
            kda_heads=4, kda_head_dim=16, kda_neg_eigval=True,
            num_experts=8, num_experts_per_token=2,
            moe_intermediate_size=32, moe_scoring="sigmoid",
            n_shared_experts=1,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_falcon_h1(cls, **kw) -> "ModelSpec":
        """Toy Falcon-H1 architecture: every layer a Mamba-2 (SSD) mixer
        and GQA attention in parallel off one norm, a dense MLP, the
        family's multipliers all away from 1."""
        base = dict(
            name="tiny-falcon-h1", vocab_size=96, hidden_size=64,
            intermediate_size=96, num_layers=3, num_heads=4,
            num_kv_heads=2, head_dim=16, dtype="float32",
            rope_theta=1e11, tie_embeddings=False,
            layer_kinds=(LayerKind(2, 1e11, mixer="ssd"),),
            layer_pattern=(0, 0, 0),
            ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
            ssm_conv=4, ssm_chunk=16,
            embedding_multiplier=5.0, lm_head_multiplier=0.125,
            key_multiplier=0.3, attention_in_multiplier=0.9,
            attention_out_multiplier=0.6, ssm_in_multiplier=0.5,
            ssm_out_multiplier=0.7,
            ssm_multipliers=(0.35, 0.5, 0.7, 0.8, 0.6),
            mlp_multipliers=(0.7, 0.4), vocab_draw_blocks=8,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_ling3(cls, **kw) -> "ModelSpec":
        """Toy Ling-3.0 architecture: KDA layers (full-rank bounded decay)
        to one latent (MLA) layer gated by head, a leading dense layer,
        group-limited sigmoid routing beside a shared expert, a clamp a
        layer."""
        base = dict(
            name="tiny-ling3", vocab_size=96, hidden_size=64,
            intermediate_size=96, num_layers=3, num_heads=4,
            num_kv_heads=4, head_dim=16, dtype="float32", rms_eps=1e-6,
            rope_theta=6e6, tie_embeddings=False,
            layer_kinds=(
                LayerKind(0, 6e6, mixer="latent", head_gate=True),
                LayerKind(0, 0.0, mixer="kda", gate_bound=-5.0,
                          full_rank=True),
            ),
            layer_pattern=(1, 1, 0),
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rotary_dim=8,
            kda_heads=4, kda_head_dim=16,
            num_experts=16, num_experts_per_token=4,
            moe_intermediate_size=32, moe_scoring="sigmoid",
            n_group=4, topk_group=2, routed_scaling_factor=2.5,
            n_shared_experts=1, first_k_dense=1,
            expert_clamp=(0.0, 0.5, 0.75), shared_clamp=(0.0, 0.6, 0.4),
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_lfm2(cls, **kw) -> "ModelSpec":
        """Toy LFM2-MoE architecture: gated short-convolution layers to
        one QK-normed GQA layer, a leading dense layer, sigmoid routing
        with a selection-only bias over experts all held, the family's
        epsilon in the weights' sum."""
        base = dict(
            name="tiny-lfm2", vocab_size=96, hidden_size=64,
            intermediate_size=96, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=16, dtype="float32", rms_eps=1e-5,
            rope_theta=1e6, tie_embeddings=True, qk_norm=True,
            layer_kinds=(
                LayerKind(2, 1e6), LayerKind(0, 0.0, mixer="conv"),
            ),
            layer_pattern=(1, 0, 1, 1), conv_taps=3,
            num_experts=8, num_experts_per_token=4,
            moe_intermediate_size=32, moe_scoring="sigmoid",
            moe_norm_eps=1e-6, first_k_dense=1,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_trinity(cls, **kw) -> "ModelSpec":
        """Toy Trinity (afmoe) architecture: gated, QK-normed GQA whose
        window layers rotate and whose full layer carries no position, a
        pool a kind, sandwich norms, the embedding's muP
        factor, a leading dense layer, sigmoid routing with a
        selection-only bias beside a shared expert."""
        base = dict(
            name="tiny-trinity", vocab_size=96, hidden_size=64,
            intermediate_size=96, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=16, dtype="float32", rms_eps=1e-5,
            rope_theta=10000.0, tie_embeddings=False, qk_norm=True,
            attn_gate=True, sandwich_norm=True, embedding_multiplier=8.0,
            layer_kinds=(
                LayerKind(2, 10000.0, window=8),
                LayerKind(2, 10000.0, rope=False),
            ),
            layer_pattern=(0, 0, 1, 0),
            num_experts=8, num_experts_per_token=2,
            moe_intermediate_size=32, moe_scoring="sigmoid",
            routed_scaling_factor=2.826, n_shared_experts=1,
            first_k_dense=1,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_phi4flash(cls, **kw) -> "ModelSpec":
        """Toy Phi-4-mini-flash (SambaY) architecture: Mamba-1 scan layers
        beside window layers of differential attention, one full layer
        whose pages the cross layers above read, GMU layers that read the
        last scan's output, LayerNorm with bias, biased projections, no
        position, a tied head; the published layer indices of a cut."""
        scan, gmu = (LayerKind(0, 0.0, mixer=m) for m in ("scan", "gmu"))
        base = dict(
            name="tiny-phi4flash", vocab_size=96, hidden_size=64,
            intermediate_size=96, num_layers=8, num_heads=8,
            num_kv_heads=4, head_dim=16, dtype="float32", rms_eps=1e-5,
            tie_embeddings=True, use_rope=False, attn_bias=True,
            norm="layer",
            layer_kinds=(
                LayerKind(4, 0.0, window=8, differential=True),
                LayerKind(4, 0.0, differential=True),
                scan, gmu,
                LayerKind(4, 0.0, differential=True, reads=(1, 0)),
            ),
            layer_pattern=(2, 0, 2, 1, 3, 4, 3, 4),
            layer_ids=(0, 1, 16, 17, 18, 19, 20, 21),
            scan_inner=128, scan_state=16, scan_dt_rank=4, scan_conv=4,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_longcat(cls, **kw) -> "ModelSpec":
        """Toy LongCat-Flash architecture: shortcut-connected double
        layers (two latent attentions, two dense FFNs, one expert layer
        on the shortcut), identity experts behind the FFN experts, the
        softmax router with a correction bias and un-normalised weights,
        both MLA scalars."""
        base = dict(
            name="tiny-longcat", vocab_size=96, hidden_size=64,
            intermediate_size=96, num_layers=2, num_heads=4,
            num_kv_heads=4, head_dim=16, dtype="float32", rms_eps=1e-5,
            rope_theta=1e7, tie_embeddings=False, rope_interleave=True,
            shortcut_moe=True, num_experts=8, zero_experts=4,
            num_experts_per_token=3, moe_intermediate_size=32,
            moe_scoring="softmax_bias", routed_scaling_factor=6.0,
            norm_topk_prob=False,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, q_lora_rank=16,
            mla_scale_q_lora=True, mla_scale_kv_lora=True,
        )
        base.update(kw)
        return cls(**base)

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the SSD convolution runs over: x | B | C."""
        return (self.ssm_heads * self.ssm_head_dim
                + 2 * self.ssm_groups * self.ssm_state)

    @property
    def is_mla(self) -> bool:
        """EVERY layer is latent attention (models/mla.py's own programs).
        A model that lists its kinds keeps latent layers as a kind
        (``LayerKind.latent``) beside the others in models/llama.py's."""
        return self.kv_lora_rank > 0 and not self.layer_kinds

    @classmethod
    def preset(cls, name: str) -> "ModelSpec":
        presets = {
            "tiny-test": cls.tiny,
            "tiny-moe": cls.tiny_moe,
            "tiny-deepseek": cls.tiny_deepseek,
            "tiny-gpt-oss": cls.tiny_gpt_oss,
            "tiny-solar": cls.tiny_solar,
            "tiny-falcon-h1": cls.tiny_falcon_h1,
            "tiny-ling3": cls.tiny_ling3,
            "tiny-longcat": cls.tiny_longcat,
            "tiny-trinity": cls.tiny_trinity,
            "tiny-phi4flash": cls.tiny_phi4flash,
            "llama-3-8b": cls.llama3_8b,
            "llama-3-70b": cls.llama3_70b,
            "mixtral-8x7b": cls.mixtral_8x7b,
            "gpt-oss-120b": cls.gpt_oss_120b,
            "deepseek-r1": cls.deepseek_r1,
        }
        if name in presets:
            return presets[name]()
        raise KeyError(f"unknown model preset {name!r}")


@dataclass
class EngineConfig:
    # paged KV cache
    page_size: int = 16  # tokens per page (= router block_size granularity)
    num_pages: int = 2048  # HBM page budget (per shard)
    max_pages_per_seq: int = 64  # max context = page_size * this
    # KV-cache storage dtype: "bf16" = unquantized pool in the model
    # dtype (bit-identical serving), "fp8" = e4m3 values + per-page/head
    # bf16 scales (ops/quant.py — halves decode HBM reads and the KVBM
    # tier footprint; outputs drift within the tolerance goldens,
    # tests/test_quant_goldens.py). "" = consult DYN_KV_DTYPE, default
    # bf16; an explicit value here wins over the environment.
    kv_dtype: str = ""
    # batching. None = auto-size from the page budget: enough slots that
    # decode batch, not slot count, is the limiter, while every slot can
    # still hold a full-length context out of the pool
    max_decode_slots: int | None = 8
    prefill_buckets: tuple[int, ...] = (64, 128, 256, 512, 1024, 2048, 4096)
    # per-step prefill admission token budget (ref: vLLM
    # max_num_batched_tokens): waiting prompts are admitted (each a bucketed
    # prefill dispatch) until the budget is spent, so a queue of short
    # prompts lands in one step instead of one per step
    max_prefill_tokens_per_step: int = 2048
    # packed prefill width: same-bucket admissions batch into ONE dispatch
    # of exactly this many prompt rows (padded; larger groups chunk) —
    # one compiled shape per bucket, N prompts per host round-trip
    prefill_pack_size: int = 8
    # decode model steps fused per device dispatch (vLLM multi-step
    # scheduling analogue): amortizes host dispatch + token sync; tokens
    # stream in bursts of this size, EOS overshoot is discarded host-side
    decode_steps_per_dispatch: int = 1
    # pipelined decode bursts: burst k+1 is dispatched, its fed tokens
    # chained on device from burst k's samples, BEFORE the host reads
    # burst k. So one burst is in flight when the read returns (it has
    # just started) and two just after a dispatch: the step thread's own
    # work hides behind the running burst, and a prefill launched in that
    # cycle waits for one burst, not more. Stops are detected one burst late
    # (overshoot discarded). Cancels, admin ops and an open chunked
    # prefill flush the pipeline; admissions interleave WITHOUT flushing.
    pipeline_decode: bool = False
    # admission first tokens sampled on device and materialized a step
    # later (never blocks the step thread on the d2h RTT); off = the
    # synchronous sample-and-emit path
    async_admissions: bool = True
    # the SHORT decode burst: the length a burst takes while a shorter
    # one would let somebody in sooner (engine/core.py _short_burst).
    # That is while the queue is empty beside a free slot (arrivals pace
    # the engine: the next prompt's prefill waits for the burst in
    # flight, and a first token that no hold lands (_land_ready_waves)
    # rides home on its slot's first burst, so both waits follow the
    # burst's length), and while prompts are
    # waiting under half occupancy (n_active*2 < slots: the ramp-up).
    # Otherwise full bursts: a backlog beside a batch at least half full,
    # or no free slot. One more compiled decode program where it differs
    # from decode_steps_per_dispatch and 1. 0 = never shorten.
    decode_steps_admit_pending: int = 4
    # chunked prefill (ref: vLLM max_num_batched_tokens pass-through):
    # prompts whose uncached tail exceeds this run as a sequence of
    # chunk-sized prefill steps interleaved with decode, so one long
    # admission cannot stall every decoding stream for a whole forward
    max_prefill_chunk_tokens: int = 512
    # parallelism (mesh axes sizes; 1 = off)
    tp: int = 1
    dp: int = 1
    sp: int = 1  # sequence/context parallel (ring-attention prefill)
    ep: int = 1  # expert parallel (MoE)
    # admission queue bound: a request arriving with this many already
    # waiting is refused with ServiceUnavailable (-> migration re-drives
    # on another worker, or HTTP 503 + Retry-After when none can take it)
    # instead of queueing unboundedly behind a saturated engine — unless
    # a LOWER-priority waiting entry can be shed in its place
    # (engine/tenancy.py shed policy: lowest priority class, most-over-
    # quota tenant, newest entry). The 503's Retry-After derives from
    # live queue depth x recent step time, not a constant. 0 = off.
    max_waiting: int = 0
    # per-tenant fairness + quotas (engine/tenancy.py): quota spec
    # string ("tenantA:weight=4,rate=1000,burst=2000;*:rate=200") or an
    # already-parsed {tenant: TenantQuota} dict. "" = consult
    # DYN_TENANT_QUOTAS, default unmetered equal-weight tenants (the
    # weighted-fair queue still applies; buckets are wide open).
    tenants: str | dict = ""
    # priority preemption: when an interactive request cannot admit
    # (no free slot, or the prompt cannot get pages), pause a BATCH
    # stream — over-quota tenants preferred, newest admission first;
    # an in-quota batch stream is still fair game when it is the only
    # thing standing between an interactive user and a slot (class
    # priority outranks quota standing). The victim's KV seals +
    # offloads through the KVBM host tier, its slot/pages free, and it
    # re-enqueues for a transparent resume (bit-identical greedy
    # continuation). False = interactive waits like everyone else.
    preemption: bool = True
    # speculative decoding (ROADMAP #6; engine/spec.py): "ngram" turns on
    # the prompt-lookup drafter + batched verify for greedy, logprob-free
    # slots — each verify dispatch lands 1..spec_k_max+1 tokens instead
    # of joining the one-token-per-step decode bursts. Bit-identical
    # output at temperature 0 (accept-longest-prefix against the
    # target's own argmax); per-slot acceptance EWMA decays k to 0 on
    # incompressible streams, transparently returning the slot to the
    # burst path. Forced off under SPMD (verify is not in the follower
    # replay protocol).
    spec_mode: str = "off"  # "off" | "ngram"
    spec_k_max: int = 8  # max draft tokens per verify (verify width k+1)
    spec_ngram_min: int = 1  # shortest suffix n-gram the drafter matches
    spec_ngram_max: int = 4  # longest (tried first: stronger predictor)
    # emitted tokens between k=1 reprobes while a slot is parked at k=0
    # (0 = never reprobe: once decayed, the request stays non-spec)
    spec_reprobe_tokens: int = 64
    # guided decoding (guided/): "auto" serves grammar-constrained
    # requests whenever the worker has a token vocabulary (single-host
    # only — masks are not in the SPMD replay protocol); "off" rejects
    # them with a typed error. DYN_GUIDED_MODE / --guided set this on
    # workers.
    guided_mode: str = "auto"  # "auto" | "off"
    # sampling
    seed: int = 0
    # step-thread phase profiler, the one switch: per-phase wall seconds +
    # call counts via profile_snapshot(), incl. the dispatch.* attribution;
    # every phase, device launch and loop cycle as an engine.* annotation
    # in a jax.profiler trace; the flight recorder keeps every finished
    # timeline (docs/OBSERVABILITY.md)
    profile: bool = False

    def __post_init__(self) -> None:
        if self.max_decode_slots is None:
            self.max_decode_slots = max(
                8, min(64, self.num_pages // max(1, self.max_pages_per_seq))
            )
        from dynamo_tpu.ops.quant import resolve_kv_dtype

        self.kv_dtype = resolve_kv_dtype(self.kv_dtype)
        if isinstance(self.tenants, str):
            import os

            from dynamo_tpu.engine.tenancy import parse_tenant_quotas

            spec = self.tenants or os.environ.get("DYN_TENANT_QUOTAS", "")
            self.tenants = parse_tenant_quotas(spec)

    @property
    def max_context(self) -> int:
        return self.page_size * self.max_pages_per_seq

    def prefill_shapes(
        self, spec: ModelSpec, free_bytes: int | None, tp: int = 1
    ) -> dict[int, int]:
        """``{bucket: pack width}`` the engine offers: every prefill shape
        it will compile and dispatch. Derived from ``max_context`` (a
        bucket past the first one that holds a whole context is never
        reached) and from what fits ``free_bytes`` of device memory
        beside the weights and the cache (None = the backend reports no
        limit, as on the CPU: everything configured is offered).

        The guard charges a pack float32 scores ``[rows, H/tp, T,
        max_context]`` x 1.5 a layer plus 96 KiB per prompt token: what
        the programs held while prefill attention scored the whole table
        (PR 21's ``memory_analysis()``). Since the walk over pages
        (ops/attention.paged_prefill_attention) they hold scores ``[rows,
        H/tp, 128, 256]`` whatever the table, 186 MiB in all for a pack
        of 8 x 512 at Llama-3-8B widths at 4,096-token and 32,768-token
        tables alike, where this charges 3.4 and 26 GiB (PERF.md section
        6, PR 31). So the charge is an UPPER BOUND now, too high by the
        table's width over a block's: past 4k-token tables it halves
        packs, and past 8k refuses buckets, that would fit. Bringing it
        down widens what an engine compiles at start (one
        ``prefill_pack_size`` serves every bucket, so the top bucket gets
        its pack back, a program more), which is why it waits on a pack
        width a bucket in the configuration: ROADMAP.md S3 (b). A pack
        width that does not fit halves; a bucket that does not fit even
        one row is not offered, nor is any above it. A bucket of more than
        1,024 rows of a model that lists its kinds is charged the walk's
        true tiles: see ``_WHOLE_TABLE_ROWS`` below — longer prompts then
        go through chunks of the largest bucket that is
        (``max_prefill_chunk_tokens`` is capped by it). A guard with
        margin, not a tuner. The latent family (``spec.is_mla``) is
        charged what ITS XLA walk holds (``need_latent``: it never had a
        whole-table form to stay compatible with; an upper bound where
        its kernel serves); so is a model
        with recurrent layers (``need_recurrent``): its softmax layers the
        walk's true tiles, its KDA layers the chunkwise form's float32
        operands, its SSD layers the chunk form's (decay matrices a head
        a chunk, the carried states), and where latent layers are a kind
        beside them, ``need_latent`` on top. The state rows themselves are
        part of the pools, so ``free_bytes`` already lacks them."""
        top = self.bucket_for(min(
            self.max_context, self.max_prefill_chunk_tokens,
            self.prefill_buckets[-1],
        ))
        heads = max(1, spec.num_heads // max(1, tp))
        # the widest bucket the whole-table charge below still prices for
        # a model that lists its layer kinds. Every such configuration it
        # shapes (MiMo's {512: 2, 1024: 1} is its doing) stops at 1,024
        # rows; past that it charges ONE row of a 4,096-row bucket under
        # a 10,240-token table 8 GiB of scores that no program has held
        # since the walk, and refuses the bucket on a chip whose programs
        # hold 26 MiB of them. So such a model's wider bucket is charged
        # the walk's true tiles (``need_recurrent``, whose state terms
        # are 0 without such layers). A model of one kind keeps the old
        # charge at every bucket (tests/test_chunked_prefill.py pins its
        # sets), until ROADMAP.md S5 (b) replaces all of it
        _WHOLE_TABLE_ROWS = 1024

        def need(rows: int, bucket: int) -> int:
            if spec.is_mla:
                return need_latent(rows, bucket)
            if (spec.has_recurrent or spec.has_latent
                    or (spec.layer_kinds and bucket > _WHOLE_TABLE_ROWS)):
                # a model with both kinds is charged both: the layers of
                # one program run in turn, so the sum is an upper bound
                # (the rest of the program counted once)
                need = need_recurrent(rows, bucket)
                if spec.has_latent:
                    need += need_latent(rows, bucket) - 96 * 1024 * rows * bucket
                return need
            scores = 4 * rows * heads * bucket * self.max_context
            return scores * 3 // 2 + 96 * 1024 * rows * bucket

        def need_latent(rows: int, bucket: int) -> int:
            # what the latent XLA walk holds (ops/attention.
            # latent_prefill_walk), whatever the table: float32
            # scores of all a call's rows against ONE block (scores,
            # probabilities and their rounded copy: x 3), the running
            # accumulator in and out of the loop, and the same 96 KiB a
            # prompt token for the rest of the program. An UPPER BOUND
            # too since PR 36: where the Mosaic kernel serves
            # (ops/pallas/latent_prefill.py) the scores never leave VMEM
            # and a program holds the padded queries and the output, a
            # tenth of this. The charge stays the walk's so that the set
            # of compiled prefill programs does (ROADMAP.md S3 (b)).
            from dynamo_tpu.ops.attention import latent_prefill_tiling

            _, bp = latent_prefill_tiling(
                bucket, self.max_pages_per_seq, self.page_size
            )
            scores = 4 * rows * heads * bucket * bp * self.page_size
            acc = 4 * rows * heads * bucket * spec.v_head_dim
            return scores * 3 + acc * 2 + 96 * 1024 * rows * bucket

        def need_recurrent(rows: int, bucket: int) -> int:
            # the softmax layers' walk holds float32 scores of a tile of
            # queries against one block of pages, three copies, and its
            # accumulator (ops/attention.paged_prefill_attention); a KDA
            # layer's chunkwise form a score of float32 arrays [rows,
            # bucket, heads x head_dim] (ops/attention.kda_chunk_prefill:
            # its operands by block and what they are made from, where
            # XLA forms them; an upper bound where the kernel does, kept
            # so that the shapes are the same either way); 96 KiB a
            # prompt token for the rest
            from dynamo_tpu.ops.attention import prefill_tiling

            tq, bp = prefill_tiling(
                bucket, self.max_pages_per_seq, self.page_size, 0
            )
            scores = 4 * rows * heads * tq * bp * self.page_size
            kda = 4 * 20 * rows * bucket * spec.kda_heads * spec.kda_head_dim
            # an SSD layer's chunk form (ops/attention.ssd_chunk_prefill):
            # per head the chunk's decay matrix and masked C B^T [chunk,
            # chunk] float32 (two copies and a rounded one), x, y and dt x
            # [head_dim] a token in float32, B and C a group, and the
            # carried states [chunks, heads, head_dim, state]
            Hs, Q = spec.ssm_heads, spec.ssm_chunk
            ssd = 4 * rows * bucket * (
                3 * Hs * Q + 4 * Hs * spec.ssm_head_dim
                + 4 * spec.ssm_groups * spec.ssm_state
            ) + 4 * 2 * rows * -(-bucket // max(1, Q)) * (
                Hs * spec.ssm_head_dim * spec.ssm_state)
            # a selective-scan layer's chunk form (ops/attention.
            # scan_chunk_prefill): a chunk's (decay, input) pairs [chunk,
            # state, channels] float32, the associative scan's copies of
            # them and the states, and a handful of [channels] a token
            from dynamo_tpu.ops.attention import SCAN_CHUNK

            scan = 4 * 6 * rows * spec.scan_inner * (
                min(SCAN_CHUNK, bucket) * spec.scan_state + bucket
            ) if "scan" in spec.mixers else 0
            return scores * 3 + kda + ssd + scan + 96 * 1024 * rows * bucket

        shapes: dict[int, int] = {}
        for bucket in self.prefill_buckets:
            if bucket > top:
                break
            width = max(1, self.prefill_pack_size)
            if free_bytes is not None:
                while width > 1 and need(width, bucket) > free_bytes:
                    width //= 2
                if need(width, bucket) > free_bytes:
                    break
            shapes[bucket] = width
        if not shapes:
            raise ValueError(
                f"no prefill bucket of {self.prefill_buckets} fits "
                f"{free_bytes} free device bytes at max_context "
                f"{self.max_context}: lower max_pages_per_seq or num_pages"
            )
        return shapes

    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if length <= b:
                return b
        raise ValueError(
            f"prompt length {length} exceeds largest prefill bucket "
            f"{self.prefill_buckets[-1]}"
        )
