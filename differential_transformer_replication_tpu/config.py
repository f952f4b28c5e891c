"""Configuration dataclasses.

Replaces the reference's single ``TrainingConfig`` (train.py:57-93) with an
explicit model/train split and a real ``model`` switch instead of the
reference's comment-toggled model selection (train.py:205-230).

Reference landmines deliberately fixed here (SURVEY.md section 5.6):
  - ``n_terms`` is a real typed field (train.py:79 lacks an annotation, so
    it silently becomes a class attribute and is dropped from ``vars()``),
  - ``batch_size`` is not carried as a dead field (train.py:67 declares it
    but only ``micro_batch_size`` is ever used),
  - no global-config access from helper functions (train.py:36).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

MODEL_KINDS = ("control", "diff", "ndiff", "jamba", "kimi_linear", "afmoe",
               "deepseek_v2", "nemotron_h", "lfm2")

# Fields only the ``jamba`` family reads. Another family given one of them
# at a value other than its default is refused by name: a field that is
# silently ignored lets a configuration file describe a model the program
# does not run.
JAMBA_FIELDS = (
    "ffn_hidden", "kv_heads", "norm_eps", "tie_embeddings",
    "attn_layer_period", "attn_layer_offset", "mamba_d_state",
    "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "ssm_state_dtype",
    "ssm_impl",
)
# Fields only the ``kimi_linear`` family reads (``ffn_hidden`` and
# ``norm_eps`` it shares with ``jamba``), refused the same way.
KIMI_LINEAR_FIELDS = (
    "ffn_hidden", "norm_eps", "kda_layers", "full_attn_layers",
    "kda_head_dim", "kda_conv", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts",
    "experts_per_token", "moe_hidden", "first_dense_layers",
    "routed_scaling", "held_experts",
)
# Fields only the ``afmoe`` family reads (the MLP's width, the K/V heads and
# the norm's eps it shares with ``jamba``, the experts' fields with
# ``kimi_linear``), refused the same way.
AFMOE_FIELDS = (
    "ffn_hidden", "kv_heads", "norm_eps", "head_dim", "layer_types",
    "sliding_window", "sliding_ring", "rope_theta", "num_experts",
    "experts_per_token", "moe_hidden", "first_dense_layers",
    "routed_scaling", "held_experts",
)
# Fields only the ``deepseek_v2`` family reads (the MLA sizes and the
# experts' fields it shares with ``kimi_linear``, ``rope_theta`` with
# ``afmoe``), refused the same way.
DEEPSEEK_V2_FIELDS = (
    "ffn_hidden", "norm_eps", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "rope_scaling", "num_experts", "experts_per_token", "moe_hidden",
    "first_dense_layers", "routed_scaling", "held_experts", "n_group",
    "topk_group", "n_shared_experts",
)
# Fields only the ``nemotron_h`` family reads (the K/V heads, the norm's
# eps, the convolution's taps and the state's dtype it shares with
# ``jamba``, the experts' fields with ``kimi_linear``), refused the same
# way. The last two are the published multi-token-prediction module's,
# which this family refuses by name (:meth:`_check_nemotron_h_fields`).
NEMOTRON_H_FIELDS = (
    "kv_heads", "norm_eps", "mamba_d_conv", "ssm_state_dtype",
    "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
    "n_groups", "ssm_state_size", "chunk_size", "moe_latent_size",
    "moe_shared_hidden", "mlp_act", "num_experts", "experts_per_token",
    "moe_hidden", "routed_scaling", "held_experts",
    "num_nextn_predict_layers", "mtp_hybrid_override_pattern",
)
# Fields only the ``lfm2`` family reads (the MLP's width, the K/V heads, the
# norm's eps and the tied head it shares with ``jamba``, ``head_dim``,
# ``layer_types`` and ``rope_theta`` with ``afmoe``, the experts' fields with
# ``kimi_linear``), refused the same way.
LFM2_FIELDS = (
    "ffn_hidden", "kv_heads", "norm_eps", "tie_embeddings", "head_dim",
    "layer_types", "rope_theta", "conv_taps", "router_eps", "num_experts",
    "experts_per_token", "moe_hidden", "first_dense_layers",
    "routed_scaling", "held_experts",
)
FAMILY_FIELDS = {"jamba": JAMBA_FIELDS, "kimi_linear": KIMI_LINEAR_FIELDS,
                 "afmoe": AFMOE_FIELDS, "deepseek_v2": DEEPSEEK_V2_FIELDS,
                 "nemotron_h": NEMOTRON_H_FIELDS, "lfm2": LFM2_FIELDS}
# ``hybrid_override_pattern`` as the published config.json spells it, a
# letter a layer, and the mixer kind ``ModelConfig.layer_kinds`` gives each:
# a Mamba-2 mixer, grouped-query attention without positions (read in
# blocks as afmoe's full layers are), or no mixer at all (the layer is its
# expert feed-forward part alone)
NEMOTRON_H_LAYERS = {"M": "mamba2", "*": "full", "E": "none"}
# the keys of a YaRN ``rope_scaling`` block, as the published config.json
# spells them (``type`` beside them says "yarn")
YARN_KEYS = ("factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
             "original_max_position_embeddings")
# ``layer_types`` as the published config.json spells them, and the mixer
# kind ``ModelConfig.layer_kinds`` gives each
AFMOE_LAYER_TYPES = {"sliding_attention": "window", "full_attention": "full"}
# the ``lfm2`` family's ``layer_types``: a gated short convolution whose
# whole cache is its window, or grouped-query attention over every earlier
# position (rotary, unlike afmoe's full layers: models/lfm2.py says so, the
# kind does not)
LFM2_LAYER_TYPES = {"conv": "shortconv", "full_attention": "full"}


@dataclass(frozen=True)
class ModelConfig:
    """Hyperparameters shared by the model families.

    Mirrors the constructor surface of the reference models
    (control.py:114, diff_transformer.py:129, Ndiff_transformer.py:183);
    the ``jamba`` family (models/jamba.py: Mamba-1 mixers beside a few
    grouped-query attention layers) adds the block of fields at the end.
    """

    model: str = "control"  # one of MODEL_KINDS (train.py:205-230 switch)
    vocab_size: int = 12000  # train.py:41 (BPE vocab)
    n_embd: int = 768  # train.py:60
    n_head: int = 4  # train.py:61; the *diff* head count
    n_layer: int = 8  # train.py:62
    block_size: int = 512  # train.py:63
    dropout: float = 0.0  # train.py:64
    n_terms: int = 4  # Ndiff_transformer.py:183 default (train.py's 0 is a bug)
    # TPU execution policy (no reference analog; reference used CUDA AMP fp16,
    # train.py:251-279 — on TPU we use bf16 compute without loss scaling).
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # Attention backend: "xla" (merged-head einsum under jit) or "pallas"
    # (fused differential flash attention kernel).
    attention_impl: str = "xla"
    # FFN/norm backend — the non-attention hot path. "xla": the reference
    # composition (ops/swiglu.py + ops/norms.py as separate XLA ops).
    # "pallas": the fused kernels — residual-add + LayerNorm in one pass
    # at every block boundary (ops/fused_norm_residual.py, GroupLayerNorm
    # included) and the SwiGLU chain (gate/xform matmuls -> SiLU ->
    # product, optionally with the pre-LN fused in front) as one Pallas
    # kernel with a fused backward (ops/fused_ffn.py). Selected exactly
    # like attention_impl, for all three model families and the decode
    # path; interpret-mode on CPU.
    ffn_impl: str = "xla"
    # Decode-side (serving / generate_cached) attention backend for the
    # single-query step over the ring KV cache: "xla" keeps the plain
    # einsum+softmax composition (models/decode.py), "pallas" routes the
    # batched L=1 step through the fused online-softmax kernel
    # (ops/decode_attention.py: per-stream softmaxes + lambda combine in
    # one pass; score maps never reach HBM). Selected exactly like
    # attention_impl/ffn_impl; interpret-mode on CPU. Prefill chunks
    # always run the XLA chunk path (compute-bound, not the decode
    # bottleneck).
    decode_attention_impl: str = "xla"
    # KV-cache storage dtype for the ring/slot-pool caches
    # (models/decode.py init_cache): "auto" stores compute_dtype (the
    # pre-quantization behavior), "bf16" forces bfloat16 storage, "int8"
    # stores symmetric per-head-scale int8 K/V (ops/decode_attention.py
    # quantize_kv) — about half the bf16 HBM bytes per slot, so ~2x
    # concurrent slot capacity at equal HBM, with dequantization fused
    # into the Pallas kernel's tile loads (the XLA path dequantizes the
    # cache row before attending). bf16/auto decode is bit-identical
    # between impls at the greedy level; int8 is tolerance-gated
    # (tests/test_decode_attention.py).
    kv_cache_dtype: str = "auto"
    # Sequence-parallel strategy when the mesh's sequence axis is > 1:
    # "ring" (K/V rotation with O(Tl) chunk memory, parallel/ring.py) or
    # "ulysses" (all-to-all head/sequence re-sharding so the unmodified
    # full-T flash kernel runs per head slice, parallel/ulysses.py).
    sequence_impl: str = "ring"
    # Rematerialize each transformer block on the backward pass
    # (jax.checkpoint): trades ~1/3 more FLOPs for O(n_layer) less
    # activation memory — the standard TPU lever for bigger micro-batches
    # or longer contexts (no reference analog; it keeps all activations).
    # It is also how a run trades back the two (M, 4E) pre-activations
    # the fused FFN's forward saves for its backward (ops/fused_ffn.py):
    # under remat they live for one block, not for the whole backward.
    remat: bool = False
    # What jax.checkpoint may SAVE per block when remat is on — the
    # per-layer-group recompute policy (models/common.py REMAT_POLICIES):
    #   "none"       jax.checkpoint's default: save only block inputs,
    #                recompute everything (max memory savings),
    #   "dots"       save matmul outputs (checkpoint_policies.dots_
    #                saveable): skips recomputing the MXU-bound work,
    #                recomputes only the cheap elementwise/norm chain —
    #                (the fused FFN's two pre-activations come out of
    #                a kernel, not a dot, so they are replayed with it;
    #                sweep with tools/ffn_sweep.py --remat-policies),
    #   "dots_no_batch"  dots_with_no_batch_dims_saveable (Flax's
    #                default "save the small stuff" policy),
    #   "nothing"    nothing_saveable, explicit,
    #   "everything" everything_saveable (remat becomes a no-op marker).
    remat_policy: str = "none"
    # Fused chunked linear+cross-entropy (ops/losses.py): when set, the
    # training loss never materializes the (B, T, V) logits — it scans
    # position-chunks of this size through the lm head with a
    # recompute-backward. The long-context companion to the flash kernels
    # (the full logits tensor, not attention, is the memory wall once
    # flash is on). forward() then returns (None, loss) when targets are
    # given. None = dense loss (the reference's shape, control.py:153-159).
    loss_chunk: Optional[int] = None
    # -- the ``jamba`` family's fields (JAMBA_FIELDS; models/jamba.py) ------
    # Hidden width of the gated MLP; 0 = the reference families' 4 * n_embd.
    ffn_hidden: int = 0
    # K/V heads shared by n_head // kv_heads query heads each (1 =
    # multi-query); 0 = n_head, one K/V head a query head.
    kv_heads: int = 0
    # eps of the family's RMSNorm (a scale only; the reference families
    # keep LayerNorm at 1e-5); 0 = 1e-6. The family also fixes the
    # position scheme: none of any kind (no RoPE, no table).
    norm_eps: float = 0.0
    # The head reuses the token table (logits = x E^T), no lm_head leaf.
    tie_embeddings: bool = False
    # Layer i (0-based) mixes tokens by attention iff
    # i % attn_layer_period == attn_layer_offset, else by a Mamba block
    # (transformers' JambaConfig.layers_block_type).
    attn_layer_period: int = 8
    attn_layer_offset: int = 4
    # Mamba-1 sizes: d_inner = mamba_expand * n_embd channels, each with a
    # state of mamba_d_state values and a causal convolution of
    # mamba_d_conv taps; mamba_dt_rank 0 = ceil(n_embd / 16).
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # Storage dtype of a sequence's recurrent state in the decode cache
    # (models/decode.py:init_cache). The recurrence itself always runs in
    # float32, whatever compute_dtype. Only float32 is taken: a narrower
    # state rounds an accumulator that lives for thousands of steps, and
    # none has been tested or measured.
    ssm_state_dtype: str = "float32"
    # Selective-scan backend, selected like attention_impl/ffn_impl: "xla"
    # (a lax.scan over time; differentiable) or "pallas" (ops/ssm.py:
    # ssm_scan_fwd keeps the state on the chip over a chunk, and
    # ssm_state_update advances the active slots of the decode pool in
    # place; forward only).
    ssm_impl: str = "xla"
    # -- the ``kimi_linear`` family's fields (KIMI_LINEAR_FIELDS;
    # models/kimi_linear.py) ------------------------------------------------
    # The published layer lists, numbered from 1: a layer mixes tokens by
    # KDA (a gated delta rule with a recurrent state a head) or by MLA
    # (attention over a latent cache, no position information). Together
    # they name every layer once.
    kda_layers: Tuple[int, ...] = ()
    full_attn_layers: Tuple[int, ...] = ()
    # KDA: n_head heads, keys and values kda_head_dim wide, a causal
    # depthwise convolution of kda_conv taps on q, k and v.
    kda_head_dim: int = 128
    kda_conv: int = 4
    # MLA: the cache holds kv_lora_rank + qk_rope_head_dim values a
    # position; a head's key is qk_nope_head_dim values widened from the
    # latent beside the qk_rope_head_dim shared ones (not rotated).
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Experts: layers past the first ``first_dense_layers`` replace the
    # dense MLP (ffn_hidden wide) by ``num_experts`` routed SwiGLU experts
    # of ``moe_hidden``, ``experts_per_token`` a token by a sigmoid router
    # with a correction bias, weights renormalised and scaled by
    # ``routed_scaling``, plus one shared expert. ``held_experts`` is the
    # half-open range of experts whose weights this program holds (an
    # expert-parallel share; (0, 0) = all): the router ranks all
    # ``num_experts``, only the held ones' terms are added.
    num_experts: int = 0
    experts_per_token: int = 8
    moe_hidden: int = 1024
    first_dense_layers: int = 1
    routed_scaling: float = 1.0
    held_experts: Tuple[int, int] = (0, 0)
    # -- the ``afmoe`` family's fields (AFMOE_FIELDS; models/afmoe.py) ------
    # The published ``layer_types`` list, a name a layer: a
    # ``"sliding_attention"`` layer rotates q and k at their absolute
    # position (``rope_theta``, dimension i paired with i + d/2) and sees
    # the last ``sliding_window`` positions, itself among them; a
    # ``"full_attention"`` layer carries no position and sees every
    # earlier one. ``head_dim`` is a head's width (0 = n_embd // n_head;
    # the published heads are wider than that). A sequence's cache holds
    # rings of two lengths: a full layer's is ``block_size`` long (it
    # cannot roll, so it bounds the sequence), a sliding layer's
    # ``sliding_ring`` (0 = min(block_size, 2 * sliding_window)); what it
    # holds past the window is the longest chunk of tokens that may be
    # written at once at a rolled position (:meth:`ring_slack`).
    head_dim: int = 0
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 0
    sliding_ring: int = 0
    rope_theta: float = 10000.0
    # -- the ``deepseek_v2`` family's fields (DEEPSEEK_V2_FIELDS;
    # models/deepseek_v2.py) ------------------------------------------------
    # MLA in every layer, as ``kimi_linear``'s layer sizes it
    # (kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim), with
    # a LOW-RANK query (x W_qa, RMSNorm, W_qb; ``q_lora_rank`` wide) and a
    # rotary part: the qk_rope_head_dim dimensions of every query head and
    # of the shared key part turn at the token's position (``rope_theta``,
    # dimension 2i with 2i + 1), the key part before it enters the latent
    # ring. ``rope_scaling`` is the published YaRN block whole (a dict in
    # a configuration file, kept as a sorted tuple of pairs: the config is
    # a jit key), read through :meth:`yarn`; empty = plain frequencies.
    q_lora_rank: int = 0
    rope_scaling: Tuple[Tuple[str, Any], ...] = ()
    # Experts: a softmax router over ``num_experts`` in ``n_group`` groups
    # of equal size (a group is a device of the expert-parallel stage): a
    # token keeps the ``topk_group`` groups whose best expert scores
    # highest and the ``experts_per_token`` best experts inside them,
    # weighted by ``routed_scaling`` times their probability (not
    # renormalised); ``n_shared_experts`` shared experts are ONE MLP of
    # n_shared_experts * moe_hidden, added unscaled.
    n_group: int = 1
    topk_group: int = 1
    n_shared_experts: int = 1
    # -- the ``nemotron_h`` family's fields (NEMOTRON_H_FIELDS;
    # models/nemotron_h.py) -------------------------------------------------
    # The published ``hybrid_override_pattern``, a letter a layer: ``M`` a
    # Mamba-2 (SSD) mixer, ``*`` grouped-query attention without positions,
    # ``E`` an expert feed-forward part. A layer is ONE of the three, under
    # one norm: a mixer layer has no feed-forward part and an ``E`` layer
    # no mixer and no cache.
    hybrid_override_pattern: str = ""
    # Mamba-2: ``mamba_num_heads`` heads of ``mamba_head_dim`` channels,
    # each head with ONE decay and a state of (head_dim, ssm_state_size)
    # float32; ``B`` and ``C`` are shared by the heads of a group
    # (``n_groups`` of them); the convolution (``mamba_d_conv`` taps) runs
    # over x, B and C together; the gated RMSNorm norms a group's
    # channels; a prompt is scanned in chunks of ``chunk_size`` tokens as
    # matrix products (ops/ssd.py).
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    n_groups: int = 1
    ssm_state_size: int = 128
    chunk_size: int = 128
    # Experts in a latent: one projection a layer enters ``moe_latent_size``
    # (0 = the experts read the hidden state), the routed experts are
    # ``moe_latent_size -> moe_hidden -> moe_latent_size``, one projection
    # leaves it; the shared expert, ``moe_shared_hidden`` wide, reads the
    # hidden state. ``mlp_act`` names the experts' activation as the
    # published config does: ``"silu"`` is the gated ``silu(gate) * up`` of
    # the other families (their only value), ``"relu2"`` the UNGATED
    # ``relu(up) ** 2`` of this one (its only value: the experts' leaves
    # hold ``up``, not ``gate_up``, and ``ops/moe.py`` follows the leaves).
    moe_latent_size: int = 0
    moe_shared_hidden: int = 0
    mlp_act: str = "silu"
    # The published multi-token-prediction module (a draft head beside the
    # language model). Left out: any value but the default is refused.
    num_nextn_predict_layers: int = 0
    mtp_hybrid_override_pattern: str = ""
    # -- the ``lfm2`` family's fields (LFM2_FIELDS; models/lfm2.py) ----------
    # ``layer_types`` names a layer ``"conv"`` (a gated short convolution of
    # ``conv_taps`` taps a channel, the published ``conv_L_cache``, over
    # n_embd channels: a slot keeps its last ``conv_taps - 1`` gated inputs
    # and nothing else) or ``"full_attention"`` (grouped-query attention,
    # q and k normed a head and THEN rotated at ``rope_theta``, every
    # earlier position visible). The experts' router divides the chosen
    # scores by their sum plus ``router_eps`` (0 for the other families,
    # whose published routers add nothing).
    conv_taps: int = 3
    router_eps: float = 0.0

    def __post_init__(self):
        if self.model not in MODEL_KINDS:
            raise ValueError(f"model must be one of {MODEL_KINDS}, got {self.model!r}")
        for name in ("kda_layers", "full_attn_layers", "held_experts",
                     "layer_types"):
            # a configuration file gives lists; the config is a jit key
            object.__setattr__(self, name, tuple(getattr(self, name)))
        object.__setattr__(self, "rope_scaling", tuple(sorted(
            dict(self.rope_scaling).items())))
        self._check_family_fields()
        self._check_jamba_fields()
        self._check_kimi_linear_fields()
        self._check_afmoe_fields()
        self._check_deepseek_v2_fields()
        self._check_nemotron_h_fields()
        self._check_lfm2_fields()
        self._check_expert_fields()
        if self.attention_impl not in ("xla", "pallas"):
            raise ValueError(
                "attention_impl must be 'xla' or 'pallas', got "
                f"{self.attention_impl!r}"
            )
        if self.ffn_impl not in ("xla", "pallas"):
            raise ValueError(
                f"ffn_impl must be 'xla' or 'pallas', got {self.ffn_impl!r}"
            )
        if self.decode_attention_impl not in ("xla", "pallas"):
            raise ValueError(
                "decode_attention_impl must be 'xla' or 'pallas', got "
                f"{self.decode_attention_impl!r}"
            )
        if self.kv_cache_dtype not in ("auto", "bf16", "int8"):
            raise ValueError(
                "kv_cache_dtype must be one of auto|bf16|int8, got "
                f"{self.kv_cache_dtype!r}"
            )
        if self.remat_policy not in (
            "none", "dots", "dots_no_batch", "nothing", "everything"
        ):
            raise ValueError(
                "remat_policy must be one of none|dots|dots_no_batch|"
                f"nothing|everything, got {self.remat_policy!r}"
            )
        if self.sequence_impl not in ("ring", "ulysses"):
            raise ValueError(
                "sequence_impl must be 'ring' or 'ulysses', got "
                f"{self.sequence_impl!r}"
            )
        if self.loss_chunk is not None and self.loss_chunk < 1:
            raise ValueError(f"loss_chunk must be positive, got {self.loss_chunk}")
        if self.model == "ndiff" and self.n_terms < 1:
            raise ValueError(
                "n_terms must be >= 1 (the reference's n_terms=0 config, "
                "train.py:79, would crash at Ndiff_transformer.py:119)"
            )

    def _check_family_fields(self):
        mine = FAMILY_FIELDS.get(self.model, ())
        for f in dataclasses.fields(self):
            owners = [fam for fam, names in FAMILY_FIELDS.items()
                      if f.name in names]
            if (owners and f.name not in mine
                    and getattr(self, f.name) != f.default):
                raise ValueError(
                    f"{f.name} is a field of the {' and '.join(owners)} "
                    f"famil{'ies' if len(owners) > 1 else 'y'}; model "
                    f"{self.model!r} does not read it"
                )

    def _check_kimi_linear_fields(self):
        if self.model != "kimi_linear":
            return
        for name in ("attention_impl", "ffn_impl", "decode_attention_impl"):
            if getattr(self, name) != "xla":
                raise ValueError(
                    f"the kimi_linear family runs {name}='xla' only, got "
                    f"{getattr(self, name)!r}: the kernels that 'pallas' "
                    "selects (ops/flash.py, ops/fused_ffn.py, "
                    "ops/decode_attention.py) know neither a latent cache "
                    "nor experts. The option chooses nothing for this "
                    "family: its MLA layers read the latent ring through "
                    "their own kernels whatever it says (ops/mla.py, "
                    "mla_latent_decode_fwd and mla_chunk_widened_fwd)"
                )
        if self.dropout:
            raise ValueError("the kimi_linear family has no dropout")
        layers = sorted(self.kda_layers + self.full_attn_layers)
        if layers != list(range(1, self.n_layer + 1)):
            raise ValueError(
                "kda_layers and full_attn_layers (numbered from 1) must "
                f"name each of the {self.n_layer} layers once, got "
                f"{self.kda_layers} and {self.full_attn_layers}"
            )
        for name in ("kda_head_dim", "kv_lora_rank", "qk_nope_head_dim",
                     "qk_rope_head_dim", "v_head_dim"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got {getattr(self, name)}")
        if self.kda_conv < 2:
            raise ValueError("kda_conv must be >= 2 (a carried window)")

    def _check_expert_fields(self):
        """The fields of a family whose later layers hold routed experts
        (kimi_linear, afmoe, deepseek_v2, lfm2) or whose ``E`` layers do
        (nemotron_h)."""
        if self.model not in ("kimi_linear", "afmoe", "deepseek_v2",
                              "nemotron_h", "lfm2"):
            return
        for name in ("moe_hidden", "experts_per_token"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got {getattr(self, name)}")
        if not 0 <= self.first_dense_layers <= self.n_layer:
            raise ValueError(
                f"first_dense_layers ({self.first_dense_layers}) must lie in "
                f"[0, n_layer = {self.n_layer}]"
            )
        if ("E" in self.hybrid_override_pattern
                if self.model == "nemotron_h"
                else self.first_dense_layers < self.n_layer):
            lo, hi = self.held_expert_range
            if self.num_experts < self.experts_per_token:
                raise ValueError(
                    f"num_experts ({self.num_experts}) must be at least "
                    f"experts_per_token ({self.experts_per_token})"
                )
            if not 0 <= lo < hi <= self.num_experts:
                raise ValueError(
                    f"held_experts {self.held_experts} must be a non-empty "
                    f"range within [0, num_experts = {self.num_experts}]"
                )

    def _check_afmoe_fields(self):
        if self.model != "afmoe":
            return
        for name in ("attention_impl", "ffn_impl", "decode_attention_impl"):
            if getattr(self, name) != "xla":
                raise ValueError(
                    f"the afmoe family takes {name}='xla' only, got "
                    f"{getattr(self, name)!r}: the kernels that 'pallas' "
                    "selects (ops/flash.py, ops/fused_ffn.py, "
                    "ops/decode_attention.py) know neither a window, grouped "
                    "K/V heads, a gated output nor experts. The option "
                    "chooses nothing for this family: its decode step reads "
                    "the rings through its own kernel whatever it says "
                    "(ops/ring_attention.py, ring_gqa_decode_fwd), its "
                    "prefill attention and projections are XLA's"
                )
        if self.dropout:
            raise ValueError("the afmoe family has no dropout")
        if len(self.layer_types) != self.n_layer or any(
                t not in AFMOE_LAYER_TYPES for t in self.layer_types):
            raise ValueError(
                f"layer_types must name each of the {self.n_layer} layers "
                f"as one of {sorted(AFMOE_LAYER_TYPES)}, got "
                f"{self.layer_types}"
            )
        if self.n_head % self.n_kv_head:
            raise ValueError(
                f"n_head ({self.n_head}) must divide by kv_heads "
                f"({self.n_kv_head})"
            )
        if self.head_size % 2:
            raise ValueError(
                f"head_dim ({self.head_size}) must be even: the sliding "
                "layers rotate dimension i with i + head_dim / 2"
            )
        if "window" in self.layer_kinds():
            W, R = self.sliding_window, self.ring_len("window")
            if not 1 <= W <= R <= self.block_size:
                raise ValueError(
                    f"sliding_window ({W}) <= sliding_ring ({R}) <= "
                    f"block_size ({self.block_size}) must hold, all >= 1: a "
                    "sliding layer's ring holds its window, and a sequence "
                    "ends where the full layers' ring does"
                )

    def _check_deepseek_v2_fields(self):
        if self.model != "deepseek_v2":
            return
        for name in ("attention_impl", "ffn_impl", "decode_attention_impl"):
            if getattr(self, name) != "xla":
                raise ValueError(
                    f"the deepseek_v2 family takes {name}='xla' only, got "
                    f"{getattr(self, name)!r}: the kernels that 'pallas' "
                    "selects (ops/flash.py, ops/fused_ffn.py, "
                    "ops/decode_attention.py) know neither a latent cache "
                    "nor experts. The option chooses nothing for this "
                    "family: its decode step reads the latent ring through "
                    "its own kernel whatever it says (ops/mla.py, "
                    "mla_latent_decode_fwd)"
                )
        if self.dropout:
            raise ValueError("the deepseek_v2 family has no dropout")
        for name in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                     "qk_rope_head_dim", "v_head_dim", "n_shared_experts"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got {getattr(self, name)}")
        if self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim ({self.qk_rope_head_dim}) must be even: "
                "the rotation turns dimension 2i with 2i + 1")
        if self.rope_scaling:
            block = dict(self.rope_scaling)
            kind = block.pop("type", "yarn")
            if kind != "yarn" or sorted(block) != sorted(YARN_KEYS):
                raise ValueError(
                    "rope_scaling must be a YaRN block (type 'yarn' with "
                    f"{', '.join(YARN_KEYS)}), got {dict(self.rope_scaling)}")
        if self.first_dense_layers < self.n_layer:
            size, (lo, hi) = self.expert_group_size, self.held_expert_range
            if (self.n_group < 1 or self.num_experts % self.n_group
                    or not 1 <= self.topk_group <= self.n_group
                    or self.experts_per_token > self.topk_group * size):
                raise ValueError(
                    f"num_experts ({self.num_experts}) must divide into "
                    f"n_group ({self.n_group}) groups, of which a token keeps "
                    f"topk_group ({self.topk_group}) that hold its "
                    f"experts_per_token ({self.experts_per_token})")
            if lo % size or hi % size:
                raise ValueError(
                    f"held_experts {self.held_experts} must be whole routing "
                    f"groups of {size} experts: a group is what one device "
                    "of the expert-parallel stage holds")

    def _check_nemotron_h_fields(self):
        if self.model != "nemotron_h":
            return
        for name in ("attention_impl", "ffn_impl", "decode_attention_impl"):
            if getattr(self, name) != "xla":
                raise ValueError(
                    f"the nemotron_h family takes {name}='xla' only, got "
                    f"{getattr(self, name)!r}: the kernels that 'pallas' "
                    "selects (ops/flash.py, ops/fused_ffn.py, "
                    "ops/decode_attention.py) know neither grouped K/V "
                    "heads, a layer without a mixer nor experts. The "
                    "option chooses nothing for this family: its decode "
                    "step reads the attention layers' rings through "
                    "ring_gqa_decode_fwd (ops/ring_attention.py) and "
                    "advances the Mamba-2 states through "
                    "ssm_ssd_state_update (ops/ssd.py) whatever it says"
                )
        if self.num_nextn_predict_layers or self.mtp_hybrid_override_pattern:
            raise ValueError(
                "the nemotron_h family leaves the multi-token-prediction "
                "module out (num_nextn_predict_layers, "
                "mtp_hybrid_override_pattern): it is a draft head beside "
                "the language model whose published config gives its layer "
                "pattern and not how it joins the next token's embedding "
                "to the hidden state, and serving it is speculative "
                "decoding over a recurrent state, which needs a snapshot "
                "of that state that the engine does not take"
            )
        if self.ssm_state_dtype != "float32":
            raise ValueError(
                "ssm_state_dtype must be 'float32' (no narrower recurrent "
                f"state is tested or measured), got {self.ssm_state_dtype!r}"
            )
        if self.dropout:
            raise ValueError("the nemotron_h family has no dropout")
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.n_layer or any(
                c not in NEMOTRON_H_LAYERS for c in pattern):
            raise ValueError(
                f"hybrid_override_pattern must name each of the "
                f"{self.n_layer} layers as one of "
                f"{sorted(NEMOTRON_H_LAYERS)} (a Mamba-2 mixer, attention, "
                f"an expert feed-forward part), got {pattern!r}"
            )
        if self.n_embd % self.n_head or self.n_head % self.n_kv_head:
            raise ValueError(
                f"n_embd ({self.n_embd}) must divide by n_head "
                f"({self.n_head}) and n_head by kv_heads ({self.n_kv_head})"
            )
        for name in ("mamba_num_heads", "mamba_head_dim", "n_groups",
                     "ssm_state_size", "chunk_size"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got {getattr(self, name)}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"mamba_num_heads ({self.mamba_num_heads}) must divide into "
                f"n_groups ({self.n_groups}) groups of heads that share B "
                "and C")
        if self.mamba_d_conv < 2:
            raise ValueError("mamba_d_conv must be >= 2 (a carried window)")
        if "E" in pattern and self.mlp_act != "relu2":
            raise ValueError(
                "the nemotron_h family's experts are the published ungated "
                f"relu2 (mlp_act='relu2'), got {self.mlp_act!r}: the gated "
                "silu form is the other families'")
        if "E" in pattern and (self.moe_latent_size < 0
                               or self.moe_shared_hidden < 1):
            raise ValueError(
                "an expert layer needs moe_shared_hidden >= 1 (the shared "
                "expert's width) and moe_latent_size >= 0, got "
                f"{self.moe_shared_hidden} and {self.moe_latent_size}")

    def _check_lfm2_fields(self):
        if self.model != "lfm2":
            return
        for name in ("attention_impl", "ffn_impl", "decode_attention_impl"):
            if getattr(self, name) != "xla":
                raise ValueError(
                    f"the lfm2 family takes {name}='xla' only, got "
                    f"{getattr(self, name)!r}: the kernels that 'pallas' "
                    "selects (ops/flash.py, ops/fused_ffn.py, "
                    "ops/decode_attention.py) know neither grouped K/V "
                    "heads, normed heads, a convolution's window nor "
                    "experts. The option chooses nothing for this family: "
                    "its decode step reads the attention layers' rings "
                    "through ring_gqa_decode_fwd (ops/ring_attention.py) "
                    "whatever it says, and its convolution is three "
                    "elementwise taps"
                )
        if self.dropout:
            raise ValueError("the lfm2 family has no dropout")
        if len(self.layer_types) != self.n_layer or any(
                t not in LFM2_LAYER_TYPES for t in self.layer_types):
            raise ValueError(
                f"layer_types must name each of the {self.n_layer} layers "
                f"as one of {sorted(LFM2_LAYER_TYPES)}, got "
                f"{self.layer_types}"
            )
        if self.n_head % self.n_kv_head:
            raise ValueError(
                f"n_head ({self.n_head}) must divide by kv_heads "
                f"({self.n_kv_head})"
            )
        if self.head_size % 2:
            raise ValueError(
                f"head_dim ({self.head_size}) must be even: the attention "
                "layers rotate dimension i with i + head_dim / 2"
            )
        if self.conv_taps < 2:
            raise ValueError("conv_taps must be >= 2 (a carried window)")
        if self.router_eps < 0:
            raise ValueError(
                f"router_eps must be >= 0, got {self.router_eps}")

    def _check_jamba_fields(self):
        if self.model != "jamba":
            return
        for name in ("attention_impl", "ffn_impl", "decode_attention_impl"):
            if getattr(self, name) != "xla":
                raise ValueError(
                    f"the jamba family runs {name}='xla' only, got "
                    f"{getattr(self, name)!r}: the Pallas attention and FFN "
                    "kernels take as many K/V heads as query heads, "
                    "LayerNorm and biased projections"
                )
        if self.ssm_impl not in ("xla", "pallas"):
            raise ValueError(
                f"ssm_impl must be 'xla' or 'pallas', got {self.ssm_impl!r}"
            )
        if self.ssm_state_dtype != "float32":
            raise ValueError(
                "ssm_state_dtype must be 'float32' (no narrower recurrent "
                f"state is tested or measured), got {self.ssm_state_dtype!r}"
            )
        if self.dropout:
            raise ValueError("the jamba family has no dropout")
        if self.n_embd % self.n_head or self.n_head % self.n_kv_head:
            raise ValueError(
                f"n_embd ({self.n_embd}) must divide by n_head "
                f"({self.n_head}) and n_head by kv_heads ({self.n_kv_head})"
            )
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                f"attn_layer_offset ({self.attn_layer_offset}) must lie in "
                f"[0, attn_layer_period = {self.attn_layer_period})"
            )
        for name in ("mamba_d_state", "mamba_d_conv", "mamba_expand"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got {getattr(self, name)}")
        if self.mamba_d_conv < 2:
            raise ValueError("mamba_d_conv must be >= 2 (a carried window)")

    @property
    def ffn_width(self) -> int:
        """Hidden width of the block's gated MLP."""
        return self.ffn_hidden or 4 * self.n_embd

    @property
    def n_kv_head(self) -> int:
        return self.kv_heads or self.n_head

    @property
    def resolved_norm_eps(self) -> float:
        """eps of the jamba, kimi_linear, afmoe, deepseek_v2, nemotron_h and
        lfm2 families' RMSNorm."""
        return self.norm_eps or 1e-6

    @property
    def d_inner(self) -> int:
        """Channels of a Mamba mixer."""
        return self.mamba_expand * self.n_embd

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.n_embd // 16)

    @property
    def ssd_inner(self) -> int:
        """Channels of a Mamba-2 mixer: heads times a head's width."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def ssd_conv_channels(self) -> int:
        """What a Mamba-2 mixer's convolution runs over: x beside every
        group's B and C."""
        return self.ssd_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def cannot_roll(self) -> bool:
        """Whether a sequence cannot run past ``block_size``, because SOME
        layer's ring cannot roll: diff's learned position table cannot, and
        jamba's and kimi_linear's attention layers and afmoe's full layers
        carry no position at all, so a rolled ring would turn them into
        sliding-window layers without a word. afmoe's sliding layers roll
        inside that bound, in rings of their own length
        (:meth:`ring_len`). deepseek_v2's layers do carry positions, but
        they see EVERY earlier one: a rolled ring of latents would make
        them sliding-window layers, which the model is not; lfm2's
        attention layers likewise (:attr:`full_layers_rotate`)."""
        return self.model in ("diff", "jamba", "kimi_linear", "afmoe",
                              "deepseek_v2", "nemotron_h", "lfm2")

    @property
    def full_layers_rotate(self) -> bool:
        """Whether the ``"full"`` layers carry positions (lfm2's rotate q
        and k; afmoe's and nemotron_h's carry none). Either way they see
        every earlier position and their ring cannot roll."""
        return self.model == "lfm2"

    def ring_len(self, kind: str) -> int:
        """Positions the ring of a layer of mixer ``kind`` holds a slot
        (:meth:`layer_kinds`): ``block_size``, but for a sliding layer."""
        if kind != "window":
            return self.block_size
        return self.sliding_ring or min(self.block_size,
                                        2 * self.sliding_window)

    def ring_window(self, kind: str) -> int:
        """Positions a query of a layer of ``kind`` sees, itself among
        them: the whole ring, but for a sliding layer."""
        return self.sliding_window if kind == "window" else self.block_size

    @property
    def ring_slack(self) -> int:
        """The longest chunk that may be written at once into a sliding
        layer's ring at a rolled position: what the ring holds past the
        window, so that the chunk's writes evict only positions none of
        its rows may see (``last - ring < row - window`` for every row).
        ``block_size`` for a model without sliding layers."""
        if "window" not in self.layer_kinds():
            return self.block_size
        return max(1, self.ring_len("window") - self.sliding_window)

    @property
    def held_expert_range(self) -> Tuple[int, int]:
        """``[lo, hi)``: the experts whose weights this program holds."""
        lo, hi = self.held_experts
        return (lo, hi) if hi else (0, self.num_experts)

    @property
    def expert_group_size(self) -> int:
        """Experts a routing group (``num_experts / n_group``)."""
        return self.num_experts // max(1, self.n_group)

    @property
    def mla_rotary(self) -> bool:
        """Whether an MLA layer turns its queries' and its shared key
        part's ``qk_rope_head_dim`` dimensions at the token's position
        (deepseek_v2); kimi_linear's MLA carries no position."""
        return self.model == "deepseek_v2"

    @property
    def yarn(self) -> Optional[dict]:
        """The YaRN block of ``rope_scaling`` as a dict, or None."""
        return dict(self.rope_scaling) or None

    def mlp_kinds(self) -> Tuple[str, ...]:
        """``"dense"`` or ``"moe"`` for every layer, 0-based: a model with
        experts holds them in the layers past the first
        ``first_dense_layers``. A ``nemotron_h`` layer has a feed-forward
        part only where its pattern says ``E``: ``"moe"`` there and
        ``"none"`` in a mixer layer."""
        if self.model == "nemotron_h":
            return tuple("moe" if c == "E" else "none"
                         for c in self.hybrid_override_pattern)
        if not self.num_experts:
            return ("dense",) * self.n_layer
        return tuple("dense" if i < self.first_dense_layers else "moe"
                     for i in range(self.n_layer))

    def layer_kinds(self) -> Tuple[str, ...]:
        """The mixer of every layer, 0-based: ``"attention"``, ``"mamba"``
        (jamba), ``"kda"`` or ``"latent"`` (kimi_linear), ``"window"`` or
        ``"full"`` (afmoe), ``"latent"`` (deepseek_v2); ``"mamba2"``,
        ``"full"`` or ``"none"`` (nemotron_h: a layer that is an expert
        feed-forward part alone has no mixer and keeps no cache);
        ``"shortconv"`` or ``"full"`` (lfm2). The
        reference families attend in every layer. ``"latent"`` is MLA over
        a ring of latents, read in blocks as far as it is live
        (ops/mla.py) whichever family keeps it: whether its key part is
        rotated is ``mla_rotary``'s to say, not the kind's."""
        if self.model == "nemotron_h":
            return tuple(NEMOTRON_H_LAYERS[c]
                         for c in self.hybrid_override_pattern)
        if self.model == "deepseek_v2":
            return ("latent",) * self.n_layer
        if self.model == "afmoe":
            return tuple(AFMOE_LAYER_TYPES[t] for t in self.layer_types)
        if self.model == "lfm2":
            return tuple(LFM2_LAYER_TYPES[t] for t in self.layer_types)
        if self.model == "kimi_linear":
            return tuple("kda" if i in self.kda_layers else "latent"
                         for i in range(1, self.n_layer + 1))
        if self.model != "jamba":
            return ("attention",) * self.n_layer
        return tuple(
            "attention"
            if i % self.attn_layer_period == self.attn_layer_offset
            else "mamba"
            for i in range(self.n_layer)
        )

    @property
    def head_size(self) -> int:
        """Per-head query/key width.

        control.py:96 uses n_embd // n_head; the differential variants halve
        it because each head carries a doubled value
        (diff_transformer.py:111, Ndiff_transformer.py:164).
        """
        if self.head_dim:
            return self.head_dim
        if self.model in ("control", "jamba", "kimi_linear", "afmoe",
                          "deepseek_v2", "nemotron_h", "lfm2"):
            return self.n_embd // self.n_head
        return self.n_embd // (self.n_head * 2)

    @property
    def value_size(self) -> int:
        """Per-head value width: doubled for differential variants
        (diff_transformer.py:30, Ndiff_transformer.py:59)."""
        if self.model in ("control", "jamba", "kimi_linear", "afmoe",
                          "deepseek_v2", "nemotron_h", "lfm2"):
            return self.head_size
        return self.head_size * 2

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ServingConfig:
    """Continuous-batching inference engine knobs (serving/engine.py).

    The engine holds a fixed pool of ``num_slots`` KV-cache slots (one
    per in-flight sequence) and runs one iteration per step: admit queued
    requests into free slots, advance prefill by at most
    ``prefill_budget`` prompt tokens (in power-of-two chunks no larger
    than ``prefill_chunk``), then decode every active slot as one batched
    length-1 chunk. All shapes are static — slot count, chunk ladder and
    RoPE table length are fixed at engine build — so admissions and
    retirements never recompile (Orca-style iteration-level scheduling
    over a vLLM-style slot pool; no reference analog).
    """

    # Fixed decode batch = KV slot pool size. Memory scales linearly:
    # each slot owns a full (n_layer, S, block_size) K/V ring.
    num_slots: int = 8
    # Largest single prefill chunk (tokens). Prompts are split into
    # descending power-of-two chunks <= this, so at most
    # log2(prefill_chunk)+1 prefill shapes ever compile.
    prefill_chunk: int = 128
    # Max prompt tokens prefilled per engine iteration, across all
    # admissions (FCFS). Bounds how long a burst of long prompts can
    # stall decoding sequences — Orca's iteration-level fairness knob.
    prefill_budget: int = 256
    # RoPE table length = hard cap on prompt + generated tokens for the
    # RoPE families (control/ndiff), which may roll past block_size on
    # the ring cache. 0 = block_size (in-window only). The diff family's
    # learned absolute position table cannot roll (models/decode.py), so
    # it is always capped at block_size regardless of this value.
    max_seq_len: int = 0
    # Default stop token; a request's SamplingParams.eos_token_id
    # overrides. None = length-only termination (the reference has no
    # EOS concept in generation, control.py:163-171).
    eos_token_id: Optional[int] = None
    # Admission bound: submissions past this many WAITING requests (not
    # yet holding a slot) are rejected immediately with QueueFullError
    # (HTTP 503 from /generate) instead of growing the wait queue — and
    # the caller's latency — without limit. 0 = unbounded (the
    # pre-bound behavior).
    max_queue_len: int = 0
    # Default server-side deadline (seconds from submission) applied to
    # requests that do not carry their own. Expired requests are shed at
    # admission and retired mid-decode (KV slot reclaimed) with a typed
    # DeadlineExceededError instead of decoding for a caller that has
    # already given up. 0 = no default deadline.
    default_deadline_s: float = 0.0
    # Graceful-drain budget: drain() stops admission (HTTP 503 +
    # Retry-After), then waits this long for in-flight requests to
    # finish before force-failing the stragglers and shutting down.
    drain_timeout_s: float = 30.0
    # Engine supervision (serving/server.py:EngineRunner): a crashed
    # engine step fails its in-flight requests with EngineCrashError,
    # rebuilds the slot pool from params, and resumes — up to this many
    # restarts per runner lifetime, each preceded by an exponential
    # backoff (restart_backoff_s * 2^n, capped at
    # restart_backoff_max_s). Budget exhausted = the runner fails hard.
    max_restarts: int = 3
    restart_backoff_s: float = 0.5
    restart_backoff_max_s: float = 30.0
    # Watchdog: a decode iteration exceeding this wall-time budget marks
    # the engine "degraded" on /health (it cannot be interrupted — the
    # device call is synchronous — but operators/load-balancers can
    # route around it). 0 = watchdog off.
    step_time_budget_s: float = 0.0
    # Continuous on-device profiling (obs/device_profile.py): every
    # this-many engine iterations, wrap ONE iteration in a
    # jax.profiler capture, parse it off-loop, and publish the
    # per-kernel step decomposition as device_* gauges on /metrics,
    # {"record":"device_profile"} JSONL rows, and a stitchable
    # device-lane Chrome trace — all under <profile_dir>. Uncaptured
    # iterations pay one integer compare; the decode compile count
    # stays 1 (capture wraps an already-compiled step). 0 = off.
    profile_every: int = 0
    profile_dir: str = "device_profiles"
    # Serving-side overrides of the corresponding ModelConfig knobs,
    # applied by ServingEngine at build: a checkpoint trained with the
    # defaults can still serve with the fused decode kernel / quantized
    # KV without editing its saved model config. "" = inherit the
    # ModelConfig value.
    decode_attention_impl: str = ""
    kv_cache_dtype: str = ""
    # Paged KV cache (serving/pages.py). 0 = the legacy contiguous
    # per-slot rings. > 0 = the slot pool stores KV in fixed pages of
    # this many tokens (must divide block_size), mapped through
    # per-slot page tables that ride the ONE jitted decode step as
    # runtime arrays — zero recompiles as pages churn. Admission then
    # keys on FREE PAGES, not slots: short requests reserve only the
    # pages they can ever write, so capacity stops scaling with
    # worst-case context.
    kv_page_size: int = 0
    # Total physical pages in the pool (one reserved trash page is
    # added on top). 0 = auto: num_slots * (block_size / kv_page_size)
    # + prefix_cache_pages — the contiguous-equivalent footprint.
    # Sizing BELOW auto is the capacity lever: 2x num_slots over the
    # same pages serves 2x concurrent short-context requests at equal
    # HBM (admission sheds to the queue when pages run out).
    kv_pool_pages: int = 0
    # Radix-tree shared-prefix reuse (serving/pages.py): retired
    # prompts donate their KV pages to a refcounted radix tree;
    # requests sharing a cached prefix skip its prefill (near-zero
    # TTFT) and fork copy-on-write at partial-page boundaries.
    # Unreferenced prefixes are LRU-evicted under page pressure.
    # Only meaningful with kv_page_size > 0.
    prefix_cache: bool = True
    # Extra pool pages added on top of the auto sizing as cached-
    # prefix headroom, so a fully-loaded slot pool still keeps hot
    # system prompts resident instead of thrashing them.
    prefix_cache_pages: int = 0
    # Speculative decoding (serving/spec.py). "" = off. "ngram" = the
    # drafter-free prompt-lookup fallback (a host-side suffix map over
    # each request's prompt + emitted tokens proposes continuations);
    # "model" = a small drafter checkpoint (spec_drafter_ckpt —
    # typically the control family beside a diff/ndiff target; any
    # family sharing the tokenizer works) run on its own slot-pool KV
    # cache. Either way the target verifies k drafted tokens in ONE
    # fused multi-row pool step (models/decode.py:forward_decode_spec)
    # with a fused accept/reject: greedy requests accept on argmax
    # match (bit-identical to non-spec greedy), sampled requests run
    # the Leviathan et al. 2023 acceptance-ratio test under the
    # existing fold_in per-request key chains.
    spec_mode: str = ""
    # Draft tokens proposed per slot per iteration (the k in the fused
    # k+1-row verify). k is baked into a fixed ladder {0, spec_draft_len}
    # of compiled step shapes; PER-REQUEST draft lengths (admission
    # caps, SamplingParams.draft_len, window clamps) ride as runtime
    # arrays, so mixed spec/non-spec traffic never recompiles.
    spec_draft_len: int = 4
    # Drafter checkpoint dir for spec_mode == "model", loaded beside
    # the target's params via load_params_for_inference (manifest
    # verification and int8 weight quantization apply to it too).
    spec_drafter_ckpt: str = ""
    # Verify-step formulation (models/decode.py:forward_decode_spec).
    # "exact" (default): a static unroll of k+1 engine-native L=1
    # sub-steps in one jitted program — every matmul keeps the plain
    # decode step's shapes, so greedy spec output is bit-identical to
    # non-spec decoding at ANY model size. "batched": all rows in one
    # pass through the fused multi-query decode-attention kernel (each
    # slot's KV ring/pages streamed ONCE for all k+1 rows — the
    # bandwidth-optimal TPU formulation); large-contraction XLA
    # matmuls may reassociate reductions vs the 1-row step, so greedy
    # ties can resolve differently at scale (bit-identical at the
    # pinned test sizes; sampled distribution unchanged).
    spec_verify: str = "exact"
    # Structured decoding (serving/constrain.py). Cap on the top-N
    # alternatives a request may ask to echo per token
    # (SamplingParams.logprobs) — N is baked into the jitted sampler's
    # output packing, so the cap is the compile-time K and per-request
    # values <= K ride as runtime truncation.
    max_logprobs: int = 5
    # Compiled-constraint cache capacity (distinct FSMs held,
    # refcounted like radix prefixes; refcount-0 entries LRU-evict
    # past this bound). Entries are host numpy tables — bytes show on
    # /metrics as serving_constraint_cache_bytes.
    constraint_cache_entries: int = 32
    # Host-RAM KV page tier (serving/host_tier.py). 0 = off. > 0 =
    # evicted full radix pages DEMOTE into pinned host buffers up to
    # this many bytes (own LRU) instead of vanishing, and admissions
    # matching a demoted prefix PROMOTE it back with a host->device
    # copy — never a recompute. Also enables mid-decode preemption:
    # a lower class's pages stash here and resume bit-exact. int8
    # pages (~0.53x bf16 bytes) make a few GB hold ~50x the HBM pool.
    # Only meaningful with kv_page_size > 0.
    host_tier_bytes: int = 0
    # Anti-starvation aging for priority scheduling: a queued request's
    # effective rank improves by one class per this many seconds
    # waited, so saturating high-priority traffic cannot starve the
    # batch class forever. 0 = no aging (strict class order).
    priority_aging_s: float = 10.0
    # Per-class concurrent-slot bounds, "class:N,class:N" (classes from
    # serving/request.py:PRIORITY_CLASSES). A class at its bound stops
    # admitting until one of its slots retires — e.g. "batch:2" keeps
    # bulk traffic from occupying the whole pool. "" = no bounds.
    priority_max_slots: str = ""
    # Model-quality telemetry (obs/quality.py). When on, the jitted
    # sample/verify steps append a fixed-shape per-slot quality vector
    # (sampled-distribution entropy, top-1 logit margin, repetition
    # flag — models/decode.py:quality_vector) to their packed outputs:
    # runtime arrays only, so the decode compile count stays 1 and
    # telemetry-OFF output stays bit-identical to the pre-quality
    # layout. The engine folds the signals into serving_token_entropy/
    # serving_logit_margin histograms, per-request
    # RequestOutput.quality stats, per-layer serving_lambda_mean
    # gauges (ops/lambdas.py path), and the serving_quality_drift
    # gauge vs the reference fingerprint below.
    quality_telemetry: bool = False
    # Path to a reference quality fingerprint JSON (recorded from a
    # known-good window via ``--quality-record``): live entropy/margin
    # sketches are compared against it with a PSI-style drift score
    # exposed as serving_quality_drift. "" = no reference (drift 0).
    quality_fingerprint: str = ""

    def __post_init__(self):
        if self.decode_attention_impl not in ("", "xla", "pallas"):
            raise ValueError(
                "decode_attention_impl must be ''|'xla'|'pallas', got "
                f"{self.decode_attention_impl!r}"
            )
        if self.kv_cache_dtype not in ("", "auto", "bf16", "int8"):
            raise ValueError(
                "kv_cache_dtype must be ''|auto|bf16|int8, got "
                f"{self.kv_cache_dtype!r}"
            )
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {self.num_slots}")
        if self.max_queue_len < 0:
            raise ValueError(
                f"max_queue_len must be >= 0, got {self.max_queue_len}"
            )
        for name in ("default_deadline_s", "drain_timeout_s",
                     "restart_backoff_s", "restart_backoff_max_s",
                     "step_time_budget_s"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"{name} must be >= 0, got {getattr(self, name)}"
                )
        if self.max_restarts < 0:
            raise ValueError(
                f"max_restarts must be >= 0, got {self.max_restarts}"
            )
        if self.profile_every < 0:
            raise ValueError(
                f"profile_every must be >= 0, got {self.profile_every}"
            )
        if self.prefill_chunk < 1 or (
            self.prefill_chunk & (self.prefill_chunk - 1)
        ):
            raise ValueError(
                f"prefill_chunk must be a positive power of two, got "
                f"{self.prefill_chunk}"
            )
        if self.prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1, got {self.prefill_budget}"
            )
        if self.max_seq_len < 0:
            raise ValueError(f"max_seq_len must be >= 0, got {self.max_seq_len}")
        for name in ("kv_page_size", "kv_pool_pages", "prefix_cache_pages"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"{name} must be >= 0, got {getattr(self, name)}"
                )
        if self.spec_mode not in ("", "ngram", "model"):
            raise ValueError(
                "spec_mode must be ''|'ngram'|'model', got "
                f"{self.spec_mode!r}"
            )
        if self.spec_mode and self.spec_draft_len < 1:
            raise ValueError(
                f"spec_draft_len must be >= 1 with spec_mode set, got "
                f"{self.spec_draft_len}"
            )
        if self.spec_verify not in ("exact", "batched"):
            raise ValueError(
                "spec_verify must be 'exact'|'batched', got "
                f"{self.spec_verify!r}"
            )
        if self.max_logprobs < 1:
            raise ValueError(
                f"max_logprobs must be >= 1, got {self.max_logprobs}"
            )
        if self.constraint_cache_entries < 1:
            raise ValueError(
                "constraint_cache_entries must be >= 1, got "
                f"{self.constraint_cache_entries}"
            )
        if self.host_tier_bytes < 0:
            raise ValueError(
                f"host_tier_bytes must be >= 0, got {self.host_tier_bytes}"
            )
        if self.priority_aging_s < 0:
            raise ValueError(
                f"priority_aging_s must be >= 0, got "
                f"{self.priority_aging_s}"
            )
        self.priority_slot_bounds()  # validate the spec string eagerly

    def paged(self) -> bool:
        """Whether the engine runs the paged KV-cache subsystem."""
        return self.kv_page_size > 0

    def tiered(self) -> bool:
        """Whether the engine runs the host-RAM page tier (and with it
        mid-decode preemption)."""
        return self.paged() and self.host_tier_bytes > 0

    def priority_slot_bounds(self) -> dict:
        """Parsed ``priority_max_slots``: {class: max concurrent slots}.
        Raises on unknown classes or malformed entries."""
        bounds: dict = {}
        if not self.priority_max_slots:
            return bounds
        valid = ("high", "normal", "batch")
        for part in self.priority_max_slots.split(","):
            part = part.strip()
            if not part:
                continue
            cls, sep, n = part.partition(":")
            cls = cls.strip()
            if not sep or cls not in valid:
                raise ValueError(
                    "priority_max_slots entries must be 'class:N' with "
                    f"class in {valid}, got {part!r}"
                )
            try:
                bound = int(n)
            except ValueError:
                raise ValueError(
                    f"priority_max_slots bound must be an int, got {n!r}"
                )
            if bound < 1:
                raise ValueError(
                    f"priority_max_slots bound must be >= 1, got {bound}"
                )
            bounds[cls] = bound
        return bounds

    def spec_enabled(self) -> bool:
        """Whether the engine runs the speculative-decoding subsystem
        (serving/spec.py)."""
        return bool(self.spec_mode)

    def resolved_pool_pages(self, model: "ModelConfig") -> int:
        """Total physical pages (EXCLUDING the reserved trash page) for
        this model: explicit ``kv_pool_pages`` or the contiguous-
        equivalent auto sizing, plus the prefix-cache headroom."""
        if not self.paged():
            return 0
        if model.block_size % self.kv_page_size:
            raise ValueError(
                f"kv_page_size ({self.kv_page_size}) must divide "
                f"block_size ({model.block_size})"
            )
        per_slot = model.block_size // self.kv_page_size
        base = self.kv_pool_pages or self.num_slots * per_slot
        return base + self.prefix_cache_pages

    def resolved_max_seq_len(self, model: "ModelConfig") -> int:
        """Hard cap on prompt + generated length for this model family."""
        if model.cannot_roll:
            return model.block_size
        return max(self.max_seq_len, model.block_size)

    def replace(self, **kw) -> "ServingConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class RouterConfig:
    """Multi-replica router knobs (serving/router.py).

    The router is the fleet-level robustness layer over N single-engine
    replicas: it probes each replica's ``/ready`` + ``/metrics``, spreads
    ``/generate`` traffic with a power-of-two-choices picker over passive
    load scores, fails retriable replies over to a DIFFERENT replica
    under a total per-request deadline, and (optionally) hedges requests
    stuck past a p99-derived latency budget. All knobs are host-side —
    nothing here touches device code or compile caches.
    """

    # -- active health probing ----------------------------------------
    # Seconds between probes of a replica whose last probe succeeded.
    probe_interval_s: float = 0.5
    # Per-probe HTTP timeout (GET /ready, GET /metrics).
    probe_timeout_s: float = 2.0
    # A FAILING replica is probed with exponential backoff: first retry
    # after probe_backoff_s, doubling up to probe_backoff_max_s — a dead
    # host is not hammered at the healthy cadence.
    probe_backoff_s: float = 0.5
    probe_backoff_max_s: float = 10.0
    # Consecutive probe/request transport failures before the replica is
    # EJECTED (never picked, probed on the backoff schedule).
    eject_after: int = 3
    # Slow re-admission: an ejected replica must pass this many
    # consecutive probes before it takes traffic again (a flapping host
    # does not oscillate in and out of rotation on one lucky probe).
    readmit_after: int = 2

    # -- failover / retry ----------------------------------------------
    # Max failover ATTEMPTS per request (first attempt included).
    # Attempts prefer distinct replicas, but when nothing un-tried is
    # eligible a recovered already-tried replica may be re-tried — so
    # on a small fleet this bounds attempts, not distinct replicas.
    max_attempts: int = 3
    # Total per-request wall-clock budget at the router (seconds),
    # bounding first attempt + backoffs + failovers; a client deadline_s
    # tightens it further. 0 = unbounded.
    default_deadline_s: float = 120.0
    # Jittered-backoff envelope between failover attempts
    # (serving/retry.py:backoff_delay semantics).
    retry_base_s: float = 0.05
    retry_cap_s: float = 1.0
    # Honored Retry-After values are capped here — a replica asking for
    # a 30 s drain-budget wait must not stall a request that another
    # replica could serve right now (and a buggy/hostile header must
    # never park the router for minutes).
    retry_after_cap_s: float = 2.0

    # -- hedging -------------------------------------------------------
    # Fire a second (hedged) attempt on a different replica when the
    # first has been in flight longer than hedge_factor * observed-p99
    # latency (floored at hedge_min_s). First reply wins. 0 = off.
    hedge_factor: float = 0.0
    hedge_min_s: float = 0.25

    # -- load scoring (power-of-two-choices inputs) --------------------
    # score = queue_weight * queue_depth/slots
    #       + slot_weight  * slot_occupancy/slots
    #       + kv_weight    * kv_utilization
    #       + inflight/slots   (router-side, always on: the passive
    #         metrics are probe-stale; in-flight counts are not)
    queue_weight: float = 1.0
    slot_weight: float = 1.0
    kv_weight: float = 0.5

    # -- admission shedding / affinity ---------------------------------
    # Before shedding (or failing a mid-failover request), wait up to
    # this long for SOME replica to become eligible — it bridges the
    # sub-second windows where a rolling restart has one replica
    # draining and the other not yet re-admitted. Bounded additionally
    # by the request's deadline. 0 = shed immediately.
    wait_for_replica_s: float = 2.0
    # Retry-After sent when the router itself sheds (zero eligible
    # replicas, or every eligible replica already tried and failed).
    shed_retry_after_s: float = 1.0
    # Sticky session routing: requests carrying a "session_id" stick to
    # one replica (prefix-cache locality groundwork, ROADMAP item 1)
    # and fail over — with re-pinning — when it dies.
    affinity: bool = True
    # The affinity map is LRU-capped at this many sessions — a router
    # fronting months of unique session_ids must not grow without
    # bound. Evicting a quiet session only costs it its pin.
    affinity_max_sessions: int = 10_000

    # -- fleet metrics staleness ---------------------------------------
    # /fleet/metrics re-serves each replica's LAST probed /metrics body.
    # Bodies older than this are EXCLUDED from the aggregation (a
    # blackholed replica's hour-old counters must not be silently judged
    # as current); every replica's age is stamped as a
    # fleet_scrape_age_seconds gauge so downstream judges
    # (tools/slo_report.py --max-scrape-age, the autoscaler) can apply
    # their own bound. 0 = legacy unbounded behavior.
    metrics_max_age_s: float = 10.0

    # -- live migration / resume-by-replay (serving/migrate.py) --------
    # Total wall-clock budget for migrating ONE slot (destination probe
    # + export + checksummed transfer + import ACK). A migration that
    # cannot land within it falls back to replay — the request is never
    # harmed either way. 0 disables migration: drain degrades to the
    # replay/plain-retry rungs only.
    migrate_budget_s: float = 10.0
    # A migrated continuation can be migrated AGAIN while the router is
    # following it (one-at-a-time rolling restarts drain the destination
    # next); /migrate/await then answers another forwarding pointer.
    # The router follows the chain up to this many hops before falling
    # back to the replay rung — a bound, not a retry count, so a
    # pathological ping-pong can never loop forever.
    migrate_max_hops: int = 4
    # Per-request cap on journaled emitted tokens (ReplayJournal). A
    # runaway generation stops growing its entry; replay then degrades
    # gracefully to a longer — still bit-exact — re-decode of the tail.
    replay_journal_max_tokens: int = 4096
    # Finished-entry LRU size: journal ids of completed requests are
    # remembered this long so late duplicate replies resolve without
    # re-registering, bounded against months of unique requests.
    replay_journal_max_finished: int = 1024

    # -- predictive admission (serving/admission.py) -------------------
    # When on, the router's shed paths (no_replica, exhausted failover,
    # proactive admission sheds) compute an HONEST Retry-After from
    # fleet-wide capacity — backlog at-or-above the request's priority
    # class divided by the MEASURED fleet service rate — instead of the
    # static shed_retry_after_s. Falls back to the static value until
    # enough traffic has been observed to measure a rate.
    admission_predictive: bool = True
    # EWMA halflife for the measured fleet service rate (req/s).
    admission_rate_halflife_s: float = 10.0
    # Cap on the computed Retry-After (a deep backlog must answer "come
    # back in 30 s", not "come back in an hour" — clients treat large
    # values as outages).
    admission_max_retry_after_s: float = 30.0
    # Proactive shedding: reject a request whose PREDICTED wait
    # (backlog ahead of its class / service rate) exceeds this bound
    # scaled by its class multiplier (high 2x, normal 1x, batch 0.5x —
    # batch sheds first, high last). 0 = never shed proactively; the
    # honest Retry-After still applies to organic sheds.
    admission_wait_bound_s: float = 0.0

    def __post_init__(self):
        for name in ("probe_interval_s", "probe_timeout_s",
                     "probe_backoff_s", "probe_backoff_max_s",
                     "default_deadline_s", "retry_base_s", "retry_cap_s",
                     "retry_after_cap_s", "hedge_factor", "hedge_min_s",
                     "queue_weight", "slot_weight", "kv_weight",
                     "wait_for_replica_s", "shed_retry_after_s",
                     "metrics_max_age_s", "migrate_budget_s",
                     "admission_rate_halflife_s",
                     "admission_max_retry_after_s",
                     "admission_wait_bound_s"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"{name} must be >= 0, got {getattr(self, name)}"
                )
        if self.eject_after < 1:
            raise ValueError(
                f"eject_after must be >= 1, got {self.eject_after}"
            )
        if self.readmit_after < 1:
            raise ValueError(
                f"readmit_after must be >= 1, got {self.readmit_after}"
            )
        if self.max_attempts < 1:
            raise ValueError(
                f"max_attempts must be >= 1, got {self.max_attempts}"
            )
        if self.affinity_max_sessions < 1:
            raise ValueError(
                f"affinity_max_sessions must be >= 1, got "
                f"{self.affinity_max_sessions}"
            )
        if self.migrate_max_hops < 1:
            raise ValueError(
                f"migrate_max_hops must be >= 1, got "
                f"{self.migrate_max_hops}"
            )
        for name in ("replay_journal_max_tokens",
                     "replay_journal_max_finished"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"{name} must be >= 0, got {getattr(self, name)}"
                )

    def replace(self, **kw) -> "RouterConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class AutoscalerConfig:
    """Fleet control-plane knobs (tools/autoscaler.py).

    The autoscaler closes the loop over surfaces that already exist:
    it polls the router's ``/fleet/metrics``, judges windowed SLO burn
    (obs/slo.py semantics) plus queue/KV utilization, and actuates
    replica count through tools/fleet.py's chaos-proven drain/relaunch
    machinery. Hysteresis (sustain counts), per-direction cooldowns and
    hard min/max bounds make the state machine immune to a flapping
    signal by construction — tests/test_autoscaler.py drives it with
    synthetic burn traces and the ``scale_flap`` fault.
    """

    # Seconds between /fleet/metrics polls (one control tick each).
    poll_interval_s: float = 1.0
    # Hard replica-count bounds. The autoscaler never drains the fleet
    # below min_replicas (even at zero load) and never grows it past
    # max_replicas (even at infinite burn).
    min_replicas: int = 1
    max_replicas: int = 4
    # Scale-up trigger: windowed burn rate above this (1.0 = the SLO
    # error budget is being spent exactly as provisioned) OR
    # utilization above util_high, sustained for scale_up_sustain
    # consecutive ticks.
    scale_up_burn: float = 1.0
    # Scale-down trigger: burn below this AND utilization below
    # util_low, sustained for scale_down_sustain consecutive ticks.
    # The asymmetry (down needs a longer streak) is deliberate: adding
    # capacity late sheds traffic, removing it late only costs money.
    scale_down_burn: float = 0.5
    scale_up_sustain: int = 3
    scale_down_sustain: int = 6
    # Per-direction cooldowns: after any scale action, no further
    # action in that direction until this much time has passed (the
    # fleet must re-equilibrate before the signal is trusted again).
    cooldown_up_s: float = 5.0
    cooldown_down_s: float = 15.0
    # Utilization score thresholds: the score is the max of fleet
    # queue-pressure (queued / total slots), mean KV utilization and
    # mean host-tier utilization over FRESH replicas.
    util_high: float = 0.85
    util_low: float = 0.30
    # Metrics bodies older than this (per-replica scrape_age_seconds)
    # are treated as MISSING, not current — a blackholed replica must
    # not feed the control loop hour-old numbers.
    stale_after_s: float = 5.0
    # SLO objective bounds used for the windowed burn computation
    # (same semantics as tools/slo_report.py --ttft/--itl/--target).
    ttft_threshold_s: float = 1.0
    itl_threshold_s: float = 0.25
    slo_target: float = 0.99

    # -- canaried rollout ----------------------------------------------
    # Traffic fraction the router splits to a designated canary
    # replica while its window runs.
    canary_fraction: float = 0.25
    # Canary observation window (seconds) before the judge rules.
    canary_window_s: float = 15.0
    # Judge: the canary must hold windowed burn at or under this...
    canary_max_burn: float = 1.0
    # ...and its TTFT p95 must not exceed the control replicas' pooled
    # p95 by more than this fraction (0.5 = +50%).
    canary_max_regress: float = 0.5
    # A verdict needs at least this many canary-served requests in the
    # window; fewer is "inconclusive" and the controller ROLLS BACK
    # (never promote on no evidence).
    canary_min_requests: int = 8
    # Quality axis (obs/quality.py): a canary whose
    # serving_quality_drift (PSI vs the fleet's reference fingerprint)
    # exceeds this rolls back even when latency is flat — the knee of
    # the conventional PSI reading ("> 0.25 = shifted"). 0 = quality
    # drift never gates (e.g. a fleet without quality telemetry).
    canary_max_drift: float = 0.25
    # ...and a canary whose constraint-validity rate falls more than
    # this far below the control replicas' rate rolls back too (a
    # checkpoint that stops satisfying its FSMs is broken regardless
    # of its latency). 0 = validity delta never gates.
    canary_max_validity_delta: float = 0.05

    def __post_init__(self):
        for name in ("poll_interval_s", "scale_up_burn",
                     "scale_down_burn", "cooldown_up_s",
                     "cooldown_down_s", "util_high", "util_low",
                     "stale_after_s", "ttft_threshold_s",
                     "itl_threshold_s", "canary_window_s",
                     "canary_max_burn", "canary_max_regress",
                     "canary_max_drift", "canary_max_validity_delta"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"{name} must be >= 0, got {getattr(self, name)}"
                )
        if self.min_replicas < 1:
            raise ValueError(
                f"min_replicas must be >= 1, got {self.min_replicas}"
            )
        if self.max_replicas < self.min_replicas:
            raise ValueError(
                f"max_replicas ({self.max_replicas}) must be >= "
                f"min_replicas ({self.min_replicas})"
            )
        if self.scale_up_sustain < 1 or self.scale_down_sustain < 1:
            raise ValueError("sustain counts must be >= 1")
        if not 0.0 < self.slo_target < 1.0:
            raise ValueError(
                f"slo_target must be in (0, 1), got {self.slo_target}"
            )
        if not 0.0 < self.canary_fraction < 1.0:
            raise ValueError(
                f"canary_fraction must be in (0, 1), got "
                f"{self.canary_fraction}"
            )
        if self.canary_min_requests < 1:
            raise ValueError(
                f"canary_min_requests must be >= 1, got "
                f"{self.canary_min_requests}"
            )

    def replace(self, **kw) -> "AutoscalerConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. The reference has no working distributed path
    (NCCL/DDP imported but never initialized, train.py:7-10,88); this is the
    TPU-native replacement: axes map onto ICI.
    """

    pipeline: int = 1  # pipeline parallel (GPipe stages, parallel/pipeline.py)
    data: int = 1  # data parallel (batch sharding + gradient psum)
    fsdp: int = 1  # parameter/optimizer sharding over the data axis group
    tensor: int = 1  # tensor parallel (head / ffn-hidden sharding)
    sequence: int = 1  # context parallel (ring attention over sequence)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        # pipeline is the LAST (fastest-varying, stride-1) axis so
        # consecutive stages are adjacent in jax.devices() enumeration
        # order — the best default for the ppermute activation handoff
        # (true physical torus adjacency would need
        # jax.experimental.mesh_utils.create_device_mesh on big slices)
        return ("data", "fsdp", "tensor", "sequence", "pipeline")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.data, self.fsdp, self.tensor, self.sequence, self.pipeline)

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclass(frozen=True)
class TrainConfig:
    """Training recipe, mirroring train.py:57-93 field for field."""

    model: ModelConfig = field(default_factory=ModelConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)

    # Optimization (train.py:67-78)
    grad_acc_steps: int = 1  # train.py:68
    micro_batch_size: int = 32  # train.py:69 (per optimizer step, pre-DP-split)
    max_iters: int = 40_000  # train.py:70
    eval_interval: int = 500  # train.py:71
    eval_iters: int = 200  # train.py:72
    learning_rate: float = 3.2e-4  # train.py:73
    min_lr: float = 6e-5  # train.py:74
    weight_decay: float = 0.1  # train.py:75
    beta1: float = 0.9  # train.py:76
    beta2: float = 0.95  # train.py:77
    warmup_iters: int = 1000  # train.py:78
    grad_clip: float = 1.0  # train.py:275

    # Reference quirk preserved as a flag: train.py:223-230 doubles the head
    # count when training the control model ("Double the heads since each
    # head is smaller") so control roughly param-matches diff.
    control_head_multiplier: int = 2

    # Data (train.py:82, 155, 41-46)
    dataset: str = "tinystories"  # "tinystories" | "synthetic" | path to a .txt
    # "epoch": exact epoch-permutation shuffle matching the reference's
    # DataLoader semantics (train.py:184-191), served by the native O(1)-
    # memory Feistel bijection (data/native.py). "replacement": uniform
    # with-replacement draws (statistically equivalent for stride-1
    # windows, no permutation machinery).
    sampler: str = "epoch"
    num_train_samples: int = 1_000_000
    vocab_size: int = 12000
    min_frequency: int = 2
    val_fraction: float = 0.1  # train.py:178 (90/10 split)
    tokenizer_dir: str = "tokenizer"

    # Profiling: capture a jax.profiler trace of a few steady-state steps
    # into this directory (TensorBoard/Perfetto viewable); None = off.
    profile_dir: Optional[str] = None
    # Continuous on-device profiling (obs/device_profile.py): every
    # this-many iterations, wrap ONE train step in a jax.profiler
    # capture, parse it off-loop, and publish the per-kernel step
    # decomposition + derived MFU as device_* gauges (the --metrics-port
    # sidecar), {"record":"device_profile"} rows in metrics.jsonl, and a
    # device-lane Chrome trace stitchable under the host timeline
    # (tools/trace_stitch.py). Mutually exclusive in practice with a
    # profile_dir window (the jax profiler is global; an overlapping
    # capture is counted as a failure, never fatal). 0 = off.
    profile_every: int = 0
    # Rotating spool for the sampled captures; "auto" derives
    # `<checkpoint_path stem>.profiles` so concurrent runs in one
    # directory never share a spool.
    profile_spool_dir: str = "auto"

    # Observability (obs/; no reference analog).
    # Prometheus sidecar: serve the trainer's metrics registry at
    # http://0.0.0.0:<port>/metrics from a daemon thread (obs/http.py)
    # so a scraper can watch a live run. 0 = off. Multi-process runs
    # bind it on process 0 only.
    metrics_port: int = 0
    # Host-side span trace (obs/spans.py): write Chrome-trace-event JSON
    # of the train loop (data_wait / dispatch / block spans per step;
    # open in Perfetto) to this path. Complements profile_dir, which
    # captures the DEVICE-side XLA timeline. None = off.
    trace_path: Optional[str] = None

    # Logging (train.py:90-93)
    log_interval: int = 10
    wandb_project: str = "diff-transformer"
    wandb_run_name: Optional[str] = None
    use_wandb: bool = False  # wandb sink is optional; stdout+jsonl always on
    metrics_path: Optional[str] = "metrics.jsonl"

    # Checkpointing (train.py:307-317 saved; resume is new capability)
    checkpoint_path: str = "best_model.ckpt"
    # Preemption safety: a resumable last-state checkpoint written on ANY
    # trainer exit (SIGTERM, Ctrl-C, crash, completion). "auto" derives
    # `<checkpoint_path stem>.last<ext>` so concurrent runs in one
    # directory never clobber each other's rescue checkpoint; None
    # disables; any other string is used verbatim.
    last_checkpoint_path: Optional[str] = "auto"
    resume_from: Optional[str] = None
    # Minimum seconds between best-checkpoint DISK writes. 0 = the
    # reference's write-on-every-improvement (train.py:307-317). With a
    # positive throttle the best state is still snapshotted ON DEVICE at
    # every improvement and any pending snapshot is flushed at exit
    # (after the rescue save), so the final best checkpoint is identical
    # on every exit path EXCEPT a multi-process crash: there the flush
    # (a collective) must be skipped like the rescue save, and a
    # deferred improvement is lost — best.ckpt then holds the last
    # WRITTEN best, not the last observed one. Useful where
    # device->host transfer is slow (it was 5-7 MB/s on the 2026-07
    # installation, ~3 min per recipe-scale write; not measured on
    # today's machine).
    checkpoint_min_interval_s: float = 0.0

    # Durable rotating step checkpoints (train/ckpt_writer.py). Every
    # ckpt_interval iterations the trainer snapshots the full train
    # state into `<ckpt_dir>/step-NNNNNNNN/`, certified by a per-file
    # SHA-256 manifest written last (its presence = the save completed;
    # loads re-verify digests, so corruption is never silently
    # resumed). 0 = off (best/last checkpoints still written and still
    # manifest-certified).
    ckpt_interval: int = 0
    # Root of the step-checkpoint tree. "auto" derives
    # `<checkpoint_path stem>.steps` so concurrent runs in one
    # directory never share a rotation tree.
    ckpt_dir: str = "auto"
    # Write step checkpoints from a background writer thread: the train
    # loop blocks only for the device->host snapshot; serialization,
    # file I/O, certification and retention GC run off-loop. If a save
    # is still in flight at the next interval the loop blocks until it
    # drains (back-pressure; the blocked time is the ckpt_blocked
    # histogram in obs/). False = write inline (the loop stalls for the
    # full save).
    ckpt_async: bool = True
    # Retention: keep the newest N verified step checkpoints...
    ckpt_keep_last: int = 3
    # ...plus every checkpoint whose step is a multiple of this,
    # forever (0 = none) — the cheap long-horizon audit trail.
    ckpt_keep_every: int = 0

    # Fault tolerance (train/anomaly.py; no reference analog). The
    # anomaly guard computes a per-step ``bad`` flag (non-finite
    # loss/grad-norm, or grad-norm above spike_factor x a running EMA of
    # good-step norms) INSIDE the jitted step and skips the optimizer
    # update under lax.cond — zero recompiles, zero extra collectives.
    # The trainer keeps a periodic on-device good-state snapshot, rolls
    # back to it after rollback_after consecutive bad steps, and aborts
    # with TrainingDivergedError after max_rollbacks rollbacks (the
    # finite-check rescue save then refuses to overwrite the good
    # checkpoint). Unsupported (auto-disabled) on the pipeline path.
    anomaly_guard: bool = True
    # spike when grad_norm > spike_factor * EMA(good grad norms); the
    # non-finite check is always on regardless
    anomaly_spike_factor: float = 4.0
    anomaly_ema_beta: float = 0.99
    # good steps before spike detection arms (the EMA must see real
    # norms first; early training legitimately swings)
    anomaly_warmup_steps: int = 50
    # consecutive bad steps before the trainer rolls back to the
    # snapshot (skipping already protected the state; a persistent
    # streak means the state itself is suspect)
    anomaly_rollback_after: int = 20
    # rollbacks before the run aborts cleanly
    anomaly_max_rollbacks: int = 3
    # iterations between good-state snapshots (one extra train state in
    # HBM — same footprint note as checkpoint_min_interval_s)
    anomaly_snapshot_interval: int = 200
    # iterations between host polls of the guard's bad_streak scalar.
    # Each poll blocks on the step's result, costing the async-dispatch
    # overlap for that iteration (~launch latency); 1 = react
    # immediately, the default amortizes it to noise. Skipping itself
    # happens every step on-device regardless of this cadence.
    anomaly_check_interval: int = 10

    # Overlap-scheduled data-parallel gradient sync (parallel/dp_step.py).
    # On a PURE data-parallel mesh (data > 1, every other axis 1) the
    # step runs under shard_map with the gradient all-reduce issued PER
    # LAYER-GROUP BUCKET from inside the backward pass (a custom-vjp
    # identity on each bucket's params), so the collective for layer k's
    # gradients overlaps the backward compute of layers < k instead of
    # running fully exposed after it. Numerically the same mean-gradient
    # (modulo float reduction order); single jit, donated state, zero
    # recompiles — pinned in tests/test_fused_ffn.py. Ineligible meshes
    # (fsdp/tensor/sequence/pipeline > 1) fall back to the GSPMD path
    # regardless of this flag.
    dp_overlap: bool = True
    # Consecutive transformer blocks per gradient-sync bucket. 1 = one
    # all-reduce per layer (max overlap, most collectives); n_layer =
    # one bucket (no overlap — the GSPMD schedule, minus fusion).
    # Embeddings and the ln_f/lm_head tail always form their own
    # buckets.
    dp_bucket_layers: int = 2

    # Distributed-training resilience (train/watchdog.py,
    # parallel/heartbeat.py). step_deadline_s is the trainer analogue
    # of ServingConfig.step_time_budget_s: armed around each jitted-
    # step dispatch/block (eval and checkpoint writes run disarmed); a
    # hung iteration dumps hang_report.json (all-thread stacks, last
    # device_profile row, compile counter) and exits with the distinct
    # hang code the supervisor restarts under its own budget. Both are
    # pure host-side threads: compile count is unaffected (pinned in
    # tests/test_watchdog.py). 0 = off.
    step_deadline_s: float = 0.0
    # hang_report.json destination; "auto" derives
    # `<checkpoint_path stem>.hang_report.json`.
    hang_report_path: str = "auto"
    # Multi-host liveness mesh: a shared-filesystem directory (every
    # host must see it — the checkpoint mount qualifies) where each
    # process publishes a heartbeat file every heartbeat_interval_s
    # seconds off-loop. A peer silent past heartbeat_timeout_s trips
    # the local watchdog immediately (coordinated abort) instead of
    # waiting out a wedged collective. None = off.
    heartbeat_dir: Optional[str] = None
    heartbeat_interval_s: float = 1.0
    heartbeat_timeout_s: float = 10.0
    # Elastic resume: a checkpoint may be resumed onto a DIFFERENT
    # mesh shape / global batch (checkpoints are stored host-canonical,
    # so same param shapes reshard freely; the epoch sampler fast-
    # forwards from the checkpoint's recorded consumed-window count so
    # the permutation stays exact across batch-size changes). When
    # exactness is impossible — the consumed count lands mid-way
    # through a new-size accumulation boundary, or a legacy checkpoint
    # predates the recorded count while the batch math changed — the
    # resume raises a typed ElasticResumeError unless this escape
    # hatch accepts the (bounded) inexactness.
    allow_inexact_resume: bool = False

    # Fault injection spec (utils/faults.py), merged with the DTX_FAULTS
    # env var. Testing/chaos only; None = inert.
    faults: Optional[str] = None

    def resolved_last_checkpoint_path(self) -> Optional[str]:
        if self.last_checkpoint_path != "auto":
            return self.last_checkpoint_path
        import os

        root, ext = os.path.splitext(self.checkpoint_path)
        return f"{root}.last{ext or '.ckpt'}"

    def resolved_ckpt_dir(self) -> str:
        """Root of the rotating step-checkpoint tree
        (train/ckpt_writer.py); "auto" keys it off checkpoint_path like
        the rescue checkpoint, so runs never share a rotation tree."""
        if self.ckpt_dir != "auto":
            return self.ckpt_dir
        import os

        root, _ = os.path.splitext(self.checkpoint_path)
        return f"{root}.steps"

    def resolved_hang_report_path(self) -> str:
        """Watchdog hang-report destination (train/watchdog.py);
        "auto" keys it off checkpoint_path like the rotation tree, so
        concurrent runs in one directory never clobber each other's
        post-mortem."""
        if self.hang_report_path != "auto":
            return self.hang_report_path
        import os

        root, _ = os.path.splitext(self.checkpoint_path)
        return f"{root}.hang_report.json"

    def resolved_profile_spool(self) -> str:
        """Spool dir for sampled device-profile captures
        (obs/device_profile.py); "auto" keys it off checkpoint_path
        like the rotation tree."""
        if self.profile_spool_dir != "auto":
            return self.profile_spool_dir
        import os

        root, _ = os.path.splitext(self.checkpoint_path)
        return f"{root}.profiles"

    seed: int = 1337  # train.py:329-330

    def resolved_model(self) -> ModelConfig:
        """Apply trainer-level switches to the model config: the
        control-head-doubling quirk (train.py:226) and the single source of
        truth for vocab_size (the trainer's, which the tokenizer produces —
        train.py:160)."""
        m = self.model
        if m.vocab_size != self.vocab_size:
            m = m.replace(vocab_size=self.vocab_size)
        if m.model == "control" and self.control_head_multiplier != 1:
            m = m.replace(n_head=m.n_head * self.control_head_multiplier)
        return m

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        d: dict[str, Any] = dataclasses.asdict(self)
        return d
