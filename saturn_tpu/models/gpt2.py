"""GPT-2 family in flax.linen, built TPU-first.

Parity target: the reference's hand-rolled GPT-J/GPT-2 zoo
(``examples/wikitext103/models/GPTJ.py:25-526``). The reference flattened the
model into an ``nn.Sequential`` so GPipe/OffloadModel could partition layers
(``GPTJ.py:502-526``). The TPU-native analog of that structural property is a
**scanned layer stack**: all transformer blocks are one ``nn.scan`` with a
leading layer axis on every block param. That single axis is what makes every
parallelism technique a *sharding annotation*:

- pipeline: shard the layer axis over a ``stage`` mesh axis,
- FSDP: shard the widest weight axis over ``data``,
- tensor parallel: shard qkv/mlp matrices over ``model``,
- offload: host-offload the stacked params wholesale.

Design choices for the MXU: bf16 activations/compute, fp32 params and softmax
accumulation; weights kept as large fused matmuls (single qkv projection,
fused MLP) so XLA tiles them onto the systolic array.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from saturn_tpu.core.modelspec import ModelSpec


#: layer kinds that are one mixer and no second half (``MixerBlock``)
MIXER_KINDS = ("mamba2", "attention_only", "latent_moe")


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # padded to a multiple of 128 for MXU tiling
    seq_len: int = 512       # reference trains at context 512 (GPTJ.py:507)
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: Optional[int] = None  # default 4*d_model
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False  # rematerialize blocks (activation checkpointing)
    # GPT-J structure (reference ``GPTJ.py:44-79`` rotary helpers,
    # ``GPTJ.py:392-424`` block): rotary position embeddings on the first
    # ``rotary_dim`` dims of q/k (no learned positions), and the attention +
    # MLP branches applied in parallel off one LayerNorm.
    rotary: bool = False
    rotary_dim: Optional[int] = None  # default: full head_dim
    parallel_residual: bool = False
    # Mixture-of-experts: replace the dense MLP with a Switch-routed expert
    # MLP (ops/moe.py). Aux load-balance loss is sown and surfaced via
    # ``ModelSpec.apply_with_aux_fn``.
    moe: bool = False
    n_experts: int = 8
    capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    # Sequence-parallel mode: name of the mesh axis the sequence is sharded
    # over. When set, the model must run inside shard_map — attention becomes
    # ring attention (ops/ring.py) or Ulysses all-to-all attention
    # (ops/ulysses.py) per ``seq_mode``, and positions are offset by the
    # shard index. None = dense single-program attention.
    seq_axis: Optional[str] = None
    seq_axis_size: int = 1
    seq_mode: str = "ring"  # "ring" | "ulysses"
    # Double-buffer the ring's k/v neighbor hop: ship block s+1 while block
    # s is still being folded (ops/ring.py overlap schedule; bit-identical
    # output, only the hop's program order moves). Ring mode only.
    seq_overlap: bool = False
    # Single-program attention implementation: "dense" (XLA einsums), "flash"
    # (fused Pallas kernel, ops/flash.py), or "auto" (flash wherever the
    # kernel can lower — on one v5e chip at GPT-J widths 309.5 ms a batch
    # against 343.8 ms for dense, PERF.md section 5, and dense is refused
    # for memory first at long seq). Ignored when seq_axis is set
    # (sequence-parallel attention has its own kernels).
    attention: str = "auto"
    # The fused head's backward (ops/ce.py), where a caller states it:
    # "stash" keeps the forward's bf16 logits for it, "recompute" derives
    # each score block again. None leaves it to the op (the stash is kept
    # under ``ce.STASH_BYTES_MAX``). The trial runner states "stash" for a
    # larger one where the compiled program has room for it.
    ce_mode: Optional[str] = None
    # False = bidirectional (encoder / BERT-class) attention. Sequence-
    # parallel attention paths assume causal, so seq techniques are only
    # feasible for causal configs.
    causal: bool = True
    # Llama-class structure knobs (beyond the reference's GPT-2/GPT-J zoo):
    # RMSNorm instead of LayerNorm, SwiGLU instead of GELU, and
    # grouped-query attention (n_kv_heads < n_heads). The flash kernel
    # takes grouped k/v natively (ops/flash.py — the (B, H, T, D) k/v
    # expansion never materializes); dense/ring/ulysses see k/v repeated
    # to n_heads activation-side. n_kv_heads=None keeps the fused 3D qkv
    # projection and exact param-shape compatibility with every earlier
    # preset.
    norm: str = "layernorm"          # "layernorm" | "rmsnorm"
    mlp_act: str = "gelu"            # "gelu" | "swiglu"
    n_kv_heads: Optional[int] = None
    # lax.scan unroll factor for the layer stack. The round-3 profiler trace
    # showed the scan's dynamic-update-slice activation stashing dragging
    # the MLP matmul fusions to ~0.4-0.5 efficiency; unrolling lets XLA
    # address the stash statically. 1 = plain scan (smallest compile);
    # measure on the chip before changing the default (a traced run of a
    # cell, perf/README.md).
    scan_unroll: int = 1
    # Looped-LM structure knobs (Ouro-class: one stack of layers run several
    # times on shared weights). Each at its default leaves every earlier
    # preset's program unchanged op for op.
    #   sandwich_norm: a norm on each branch's *output* as well
    #     (x += post(attn(ln_1 x)); x += post(mlp(ln_2 x))).
    #   rope_theta: the rotary base.
    #   use_bias: False drops the bias of every projection.
    #   tie_head: False gives the output head its own (V, D) ``lm_head``.
    #   n_passes: how many times a token passes the whole stack; ``ln_f``
    #     follows every pass and its output feeds the next one.
    sandwich_norm: bool = False
    rope_theta: float = 10000.0
    use_bias: bool = True
    tie_head: bool = True
    n_passes: int = 1
    # Hybrid-stack structure knobs (Olmo-Hybrid-class: gated-delta-rule
    # linear-attention layers between full-attention layers). Each at its
    # default leaves every earlier preset's program unchanged op for op.
    #   layer_types: the kinds of one *period* of the stack
    #     ("linear_attention" | "full_attention"); the stack is
    #     ``n_layers / len(layer_types)`` periods, one ``nn.scan`` over a
    #     period block that applies its layers in order. None = one kind.
    #   held_heads: how many of the ``n_heads`` published heads this program
    #     holds (a tensor-parallel rank's share of every mixer: q/k/v/gate
    #     columns and ``attn_out`` rows of the held heads only). Head widths
    #     stay ``d_model / n_heads``. None = all.
    #   pre_norm: False drops the norm *before* each branch (with
    #     ``sandwich_norm`` the block is x += N(mixer(x)); x += N(mlp(x))).
    #   qk_norm: an RMSNorm on the full layers' q and k, over all held heads'
    #     lanes together, before the heads are split.
    #   learned_positions: False with ``rotary=False`` gives no position
    #     signal at all (the recurrent layers carry the order).
    #   lin_*: a linear layer's key / value head widths, the taps of its
    #     depthwise causal convolution, whether beta reaches 2 (a negative
    #     eigenvalue of I - beta k k^T), and the chunk of ``ops/gdn.py``.
    layer_types: Optional[Tuple[str, ...]] = None
    held_heads: Optional[int] = None
    pre_norm: bool = True
    qk_norm: bool = False
    learned_positions: bool = True
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    lin_conv: int = 4
    lin_neg_eigval: bool = True
    lin_chunk: int = 64
    # Routed-expert / mixed-window structure knobs (Laguna-class: a leading
    # dense layer, then periods of sliding-window and full-attention layers
    # whose feed-forward is a shared expert beside top-k routed experts).
    # Each at its default leaves every earlier preset's program unchanged op
    # for op.
    #   head_width: a head's lanes where ``d_model / n_heads`` is not it (q
    #     is then wider than the stream: ``heads x head_width`` lanes).
    #   kind_heads: q heads of a layer kind where the kinds differ,
    #     (("full_attention", 48), ("sliding_attention", 64)); the k/v heads
    #     are ``n_kv_heads`` for every kind.
    #   window: a "sliding_attention" layer's reach, the token itself counted
    #     (query i reads keys i - window + 1 .. i).
    #   window_rope_theta: a sliding layer rotates all its lanes at this
    #     base; a full layer keeps ``rotary_dim`` / ``rope_theta`` and, with
    #     ``yarn`` = (factor, original positions, beta_fast, beta_slow,
    #     attention factor), YaRN's interpolated frequencies.
    #   attn_gate: a sigmoid gate a head on the attention output, from the
    #     block's normed input (``attn_gate``, d_model -> heads).
    #   lead_layers: layers before the scanned periods, outside the scan
    #     (param key ``lead``): full attention and the dense MLP ``d_ff``.
    #     ``n_layers`` counts them.
    #   routed_experts: experts the router scores (0 = no routed layer);
    #     ``held_experts`` of them are computed here (a chip's share, the
    #     first ones: the router keeps all its outputs and its ``top_k`` a
    #     token, the layer computes the part of the result the held experts
    #     give, nothing stands in for the rest); ``expert_ff`` /
    #     ``shared_ff`` the experts' and the shared expert's SwiGLU widths;
    #     ``routed_scale`` multiplies the normalised weights. The row buffer
    #     and its tile are ``ops/moe.py``'s (``BUFFER``, ``ROW_TILE``).
    head_width: Optional[int] = None
    kind_heads: Optional[Tuple[Tuple[str, int], ...]] = None
    window: Optional[int] = None
    window_rope_theta: float = 10000.0
    yarn: Optional[Tuple[float, int, float, float, float]] = None
    attn_gate: bool = False
    lead_layers: int = 0
    routed_experts: int = 0
    held_experts: Optional[int] = None
    top_k: int = 8
    expert_ff: int = 512
    shared_ff: int = 0
    routed_scale: float = 1.0
    # State-space / latent-expert structure knobs (Nemotron-H-class: every
    # layer one mixer and no second half, x += Mixer(N(x)), the mixer a
    # Mamba-2 layer, a softmax-attention layer or a routed-expert layer).
    # Each at its default leaves every earlier preset's program unchanged op
    # for op.
    #   layer_types gains the three kinds of such a stack: "mamba2",
    #     "attention_only" (causal attention over ``n_kv_heads`` grouped k/v
    #     heads, ``attn_out``, nothing else) and "latent_moe" (the routed
    #     layer alone).
    #   norm_eps: the RMSNorms' epsilon where it is not flax's 1e-6.
    #   ssm_heads / ssm_groups: a Mamba-2 layer's published heads (of
    #     ``ssm_head_dim`` lanes; 0 = no such layer) and the groups that share
    #     B and C (``ssm_state`` wide); ``ssm_conv`` the taps of its depthwise
    #     causal convolution (with a bias), ``ssm_chunk`` the chunk of
    #     ``ops/ssd.py``. With ``held_heads`` the layer holds the same share
    #     of its heads as the attention layers of theirs
    #     (``ssm_heads x held_heads / n_heads``), whole groups only: the
    #     gated norm is over a group's lanes, so a share on group boundaries
    #     computes exactly its heads' part of the uncut layer.
    #   held_heads with ``n_kv_heads``: the held q heads read the k/v heads
    #     of their groups (8 of 32 q heads over 2 k/v heads: one k/v head).
    #   latent_dim: the width the routed experts read and write (0 = the
    #     stream's): ``latent_down`` before them, ``latent_up`` after; the
    #     router and the shared expert read the stream.
    #   expert_act: "swiglu" or "relu2" (``relu(x W1)^2 W2``, no gate), of the
    #     routed experts and the shared one.
    #   router_bias: a selection bias (``router_bias``, one scalar an expert)
    #     added to the scores for the choice only.
    norm_eps: Optional[float] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    latent_dim: int = 0
    expert_act: str = "swiglu"
    router_bias: bool = False
    # Delta-rule / latent-attention structure knobs (Ling-class: periods of
    # Kimi-delta-attention layers and one latent-attention layer, each before
    # a dense or routed feed-forward). Each at its default leaves every
    # earlier preset's program unchanged op for op.
    #   layer_types gains "kda" (the delta rule with a decay a key channel,
    #     ``ops/kda.py``, behind three short convolutions: heads of
    #     ``head_dim`` keys and values, ``lin_conv`` taps, the gate
    #     ``kda_gate_floor x sigmoid(exp(A_log) (h W_a + dt_bias))``) and "mla"
    #     (latent attention: a ``kv_latent``-wide normed projection of the
    #     stream gives every head its ``qk_nope_dim`` content key lanes and
    #     ``v_head_dim`` value lanes; beside it ``qk_rope_dim`` rotary lanes
    #     of one key shared by the heads; q of ``qk_nope_dim + qk_rope_dim``
    #     lanes a head; with ``head_qk_norm`` an RMSNorm over each head's q and
    #     k lanes, one gain shared by the heads, before the rotation at
    #     ``rope_theta``). Both take ``attn_gate`` and ``held_heads``.
    #   lead_kind: the mixer of the leading dense layers ("full_attention"
    #     or "kda").
    #   route_groups / route_groups_kept: the routed layer's group limit
    #     (``ops/moe.py::limited_choice``; 0 = none); routed_buffer: its row
    #     buffer as a multiple of the mean held pairs (None: ``ops/moe.py::
    #     BUFFER``).
    #   swiglu_limit: the largest published clamp of an expert among the
    #     layers held (a cell's configuration passes it); only 0 (none) is
    #     built.
    kv_latent: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    head_qk_norm: bool = False
    kda_gate_floor: float = -5.0
    lead_kind: str = "full_attention"
    route_groups: int = 0
    route_groups_kept: int = 0
    routed_buffer: Optional[float] = None
    swiglu_limit: float = 0.0
    # Route-before-mixer structure knobs (SmallThinker-class: every layer
    # routed, no dense layer and no shared expert, a rotary-less full layer
    # to three rotated sliding ones). Each at its default leaves every
    # earlier preset's program unchanged op for op.
    #   route_from: the rows a routed layer's router reads. "ff_input": the
    #     experts' own, the normed stream after the mixer. "block_input": the
    #     block's input, ahead of ``ln_1`` and un-normed; the route
    #     (``ops/moe.py::route``) is made before the mixer and handed across
    #     it to the experts (``experts_under``), which read ``ln_2`` of the
    #     stream after the mixer.
    #   router_score: "sigmoid" (normalised over the chosen) or "softmax"
    #     (over the chosen logits).
    #   expert_act gains "reglu": ``relu(u W_g) * (u W_u)``.
    #   rotary_kinds: the softmax layer kinds that rotate q and k where not
    #     every one does (None: all of them, with ``rotary``); a kind left
    #     out has no position signal but its causal mask.
    route_from: str = "ff_input"
    router_score: str = "sigmoid"
    rotary_kinds: Optional[Tuple[str, ...]] = None
    # Short-convolution structure knobs (LFM2-class: three doubly gated
    # short-convolution layers to one grouped-query attention layer, leading
    # dense layers whose mixer is the convolution, routed SwiGLU experts
    # after). Each at its default leaves every earlier preset's program
    # unchanged op for op.
    #   layer_types gains "conv": ``[B | C | u] = y W_in; out = (C * conv(B *
    #     u)) W_out``, the convolution depthwise and causal over
    #     ``conv_taps`` tokens, no activation, no heads
    #     (``Block._short_conv_mixer``); ``lead_kind`` takes it too.
    #   head_qk_norm on a softmax layer: an RMSNorm over each head's q and k
    #     lanes (one gain of ``head_dim`` shared by the heads), under grouped
    #     k/v too, before the rotation.
    #   route_eps: added to the sum a token's chosen sigmoid scores are
    #     normalised by (``ops/moe.py::RoutedPlan.eps``).
    conv_taps: int = 3
    route_eps: float = 0.0
    name: str = "gpt2-small"

    def __post_init__(self) -> None:
        if self.seq_mode not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_mode must be 'ring' or 'ulysses', got {self.seq_mode!r}"
            )
        if self.attention not in ("auto", "dense", "flash"):
            raise ValueError(
                f"attention must be 'auto', 'dense' or 'flash', "
                f"got {self.attention!r}"
            )
        from saturn_tpu.ops.ce import stash_of

        stash_of(self.ce_mode)   # a ValueError for a mode the op has not
        if self.rotary:
            rd = self.rotary_dim if self.rotary_dim is not None else self.head_dim
            if rd % 2 != 0 or rd > self.head_dim:
                raise ValueError(
                    f"rotary_dim must be even and <= head_dim "
                    f"({self.head_dim}), got {rd}"
                )
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', "
                             f"got {self.norm!r}")
        if self.mlp_act not in ("gelu", "swiglu"):
            raise ValueError(f"mlp_act must be 'gelu' or 'swiglu', "
                             f"got {self.mlp_act!r}")
        if self.sandwich_norm and self.parallel_residual:
            raise ValueError("sandwich_norm needs the sequential residual "
                             "(each branch's output is normed before its add)")
        if self.n_passes < 1:
            raise ValueError(f"n_passes must be >= 1, got {self.n_passes}")
        if self.n_passes > 1 and self.moe:
            raise ValueError("a looped stack (n_passes > 1) with moe=True is "
                             "not supported: the sown aux loss has one slot "
                             "per layer, not per layer application")
        if self.n_kv_heads is not None and (
            self.n_kv_heads < 1 or self.n_heads % self.n_kv_heads != 0
        ):
            raise ValueError(
                f"n_kv_heads must divide n_heads ({self.n_heads}), "
                f"got {self.n_kv_heads}"
            )
        if not self.pre_norm and not self.sandwich_norm:
            raise ValueError("pre_norm=False needs sandwich_norm=True "
                             "(a block with no norm at all is not offered)")
        if self.held_heads is not None and not 1 <= self.held_heads <= self.n_heads:
            raise ValueError(
                f"held_heads must be 1..n_heads ({self.n_heads}), got {self.held_heads}")
        if self.held_heads is not None and self.n_kv_heads is not None:
            per = self.n_heads // self.n_kv_heads      # q heads a k/v head
            if self.held_heads % per and per % self.held_heads:
                raise ValueError(
                    f"held_heads ({self.held_heads}) over grouped k/v must be whole "
                    f"groups of {per} q heads, or a whole part of one group")
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            kinds = set(self.layer_types)
            if not kinds or kinds - {"linear_attention", "full_attention",
                                     "sliding_attention", "kda", "mla", "conv",
                                     *MIXER_KINDS}:
                raise ValueError(
                    f"layer_types holds 'linear_attention' / 'full_attention' "
                    f"/ 'sliding_attention' / 'kda' / 'mla' / 'conv' / "
                    f"{' / '.join(map(repr, MIXER_KINDS))}, "
                    f"got {self.layer_types!r}")
            if (self.n_layers - self.lead_layers) % len(self.layer_types) != 0:
                raise ValueError(
                    f"n_layers ({self.n_layers}) less the {self.lead_layers} "
                    f"leading must be whole periods of "
                    f"{len(self.layer_types)} layers")
            if "sliding_attention" in kinds and (
                not self.window or not self.causal or self.seq_axis is not None
            ):
                raise ValueError(
                    "a sliding-attention layer needs window >= 1 and is causal "
                    "and single-program")
            if "linear_attention" in kinds and (
                not self.causal or self.seq_axis is not None or self.moe
            ):
                raise ValueError(
                    "a linear-attention layer is causal, dense-MLP and "
                    "single-program (its state crosses the whole sequence)")
            if kinds & {"kda", "mla"} and (
                not self.causal or self.seq_axis is not None or self.moe
                or self.n_kv_heads is not None or self.kind_heads is not None
                or self.rotary or kinds & set(MIXER_KINDS)
                or ("mla" in kinds and (self.kv_latent < 1 or self.qk_rope_dim % 2))
            ):
                raise ValueError(
                    "a kda / mla layer is causal and single-program, one k/v "
                    "head a q head, with its own rotary (rotary=False) on an "
                    "even qk_rope_dim beside kv_latent >= 1 lanes of latent")
            if "conv" in kinds | {self.lead_kind} and (
                not self.causal or self.seq_axis is not None or self.moe
                or self.held_heads is not None or self.conv_taps < 1
            ):
                raise ValueError(
                    "a short-convolution layer is causal and single-program (it "
                    "reads conv_taps - 1 >= 0 tokens back across a shard's edge) "
                    "and holds all its channels (a share of them is not built)")
            if kinds & set(MIXER_KINDS) and (
                not self.causal or self.seq_axis is not None or self.moe
                or self.lead_layers or self.kind_heads is not None
            ):
                raise ValueError(
                    "a stack of mixer-alone layers is causal and single-program "
                    "(a state-space layer's state crosses the whole sequence), "
                    "with no leading layer and one q-head count")
            if "mamba2" in kinds:
                per = self.ssm_heads // max(self.ssm_groups, 1)
                if (self.ssm_heads < 1 or self.ssm_heads % self.ssm_groups
                        or self.ssm_heads * self.heads_held % self.n_heads
                        or self.ssm_heads_held % per):
                    raise ValueError(
                        f"a mamba2 layer needs ssm_heads ({self.ssm_heads}) in whole "
                        f"groups ({self.ssm_groups}) and a held share on group "
                        f"boundaries, got {self.heads_held} of {self.n_heads} heads")
            if "latent_moe" in kinds and not self.routed_experts:
                raise ValueError("a latent_moe layer needs routed_experts")

        if self.kind_heads is not None:
            object.__setattr__(self, "kind_heads", tuple(
                (str(k), int(h)) for k, h in self.kind_heads))
            if self.held_heads is not None or self.n_kv_heads is None or any(
                    h % self.n_kv_heads for _, h in self.kind_heads):
                raise ValueError(
                    "kind_heads needs whole heads over n_kv_heads k/v heads "
                    f"that divide each count, got {self.kind_heads!r}")
        if self.lead_layers and self.layer_types is None:
            raise ValueError("lead_layers precede a stack of layer_types")
        if self.lead_kind not in ("full_attention", "kda", "conv"):
            raise ValueError(f"lead_kind must be 'full_attention', 'kda' or "
                             f"'conv', got {self.lead_kind!r}")
        if self.head_qk_norm and self.qk_norm:
            raise ValueError("qk_norm (over all held heads' lanes together) and "
                             "head_qk_norm (over each head's) are one or the other")
        if self.swiglu_limit:
            raise ValueError(
                f"swiglu_limit {self.swiglu_limit}: an expert's clamp is not built "
                "(the published configuration names the limit and not the "
                "clamp's form; the layers held have 0: ROADMAP.md, Reach)")
        if self.yarn is not None:
            object.__setattr__(self, "yarn", tuple(self.yarn))
        if self.rotary_kinds is not None:
            object.__setattr__(self, "rotary_kinds", tuple(self.rotary_kinds))
            if not self.rotary or set(self.rotary_kinds) - {
                    "full_attention", "sliding_attention"}:
                raise ValueError(
                    "rotary_kinds names the softmax kinds ('full_attention', "
                    "'sliding_attention') that rotate under rotary=True, got "
                    f"{self.rotary_kinds!r}")
        if self.routed_experts:
            held = self.experts_held
            if self.expert_act not in ("swiglu", "reglu", "relu2"):
                raise ValueError(f"expert_act must be 'swiglu', 'reglu' or "
                                 f"'relu2', got {self.expert_act!r}")
            if self.expert_act == "reglu" and self.shared_ff:
                raise ValueError("a ReGLU shared expert is not built (the one "
                                 "ReGLU stack has no shared expert)")
            if self.router_score not in ("sigmoid", "softmax"):
                raise ValueError(f"router_score must be 'sigmoid' or 'softmax', "
                                 f"got {self.router_score!r}")
            if self.route_from not in ("ff_input", "block_input") or (
                    self.route_from == "block_input" and (
                        self.latent_dim or self.parallel_residual
                        or set(self.layer_types or ()) & set(MIXER_KINDS))):
                raise ValueError(
                    "route_from is 'ff_input' or, in a sequential block of a "
                    "mixer and a routed feed-forward with no latent, "
                    f"'block_input': got {self.route_from!r}")
            if (self.layer_types is None or self.moe or self.seq_axis is not None
                    or (self.expert_act == "swiglu" and self.mlp_act != "swiglu")
                    or not 1 <= self.top_k <= self.routed_experts
                    or held < 1 or self.routed_experts % held):
                raise ValueError(
                    "a routed layer is SwiGLU or relu2, in a stack of layer_types, "
                    "single-program, with held_experts a whole share of "
                    f"routed_experts: got top_k {self.top_k}, "
                    f"{self.held_experts} of {self.routed_experts} experts")

    @property
    def head_dim(self) -> int:
        return self.head_width or self.d_model // self.n_heads

    @property
    def experts_held(self) -> int:
        return self.routed_experts if self.held_experts is None else self.held_experts

    def heads_of(self, kind: str) -> int:
        """q heads of a layer of ``kind`` (held, where a share is held)."""
        return dict(self.kind_heads or ()).get(kind, self.heads_held)

    @property
    def ff_dim(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def heads_held(self) -> int:
        return self.n_heads if self.held_heads is None else self.held_heads

    @property
    def kv_heads_held(self) -> Optional[int]:
        """k/v heads the held q heads read (None: one a q head)."""
        if self.n_kv_heads is None or self.held_heads is None:
            return self.n_kv_heads
        return max(1, self.n_kv_heads * self.held_heads // self.n_heads)

    @property
    def ssm_heads_held(self) -> int:
        return self.ssm_heads * self.heads_held // self.n_heads

    @property
    def ssm_groups_held(self) -> int:
        return max(1, self.ssm_groups * self.heads_held // self.n_heads)

    @property
    def n_periods(self) -> int:
        """Trip count of the layer scan: periods of ``layer_types``, or
        layers where the stack has one kind."""
        return (self.n_layers - self.lead_layers) // len(self.layer_types or (None,))

    @property
    def stack_kinds(self) -> Optional[Dict[str, int]]:
        """Layers of each kind in one period; None for a one-kind stack."""
        if self.layer_types is None:
            return None
        return {k: self.layer_types.count(k) for k in dict.fromkeys(self.layer_types)}

    @property
    def stack_lead(self) -> Optional[Dict[str, int]]:
        """Layers before the scanned periods, by kind (``lead_kind``'s mixer
        with the dense MLP); None where the stack has none."""
        return {self.lead_kind + "_dense": self.lead_layers} if self.lead_layers else None

    def example_inputs(self, batch_size: int = 1):
        return jnp.zeros((batch_size, self.seq_len), dtype=jnp.int32)


# Size presets matching the public GPT-2 family plus a GPT-J-class config
# (reference example workload is GPT-J-6B, ``GPTJ.py:504-507``) and a tiny
# config for CPU-mesh tests.
PRESETS: Dict[str, Dict[str, Any]] = {
    "test-tiny": dict(d_model=64, n_layers=2, n_heads=4, vocab_size=256, seq_len=64),
    "gpt2-small": dict(d_model=768, n_layers=12, n_heads=12),
    "gpt2-medium": dict(d_model=1024, n_layers=24, n_heads=16),
    "gpt2-large": dict(d_model=1280, n_layers=36, n_heads=20),
    "gpt2-xl": dict(d_model=1600, n_layers=48, n_heads=25),
    # GPT-J-6B: rotary on the first 64 head dims + parallel attn/MLP residual
    # (reference ``GPTJ.py:82-268,392-424``; config ``GPTJ.py:504-507``).
    "gptj-6b": dict(
        d_model=4096, n_layers=28, n_heads=16, d_ff=16384,
        rotary=True, rotary_dim=64, parallel_residual=True,
    ),
    # GPT-J-class ~1.3B config (GPT-neo-1.3B-shaped): the single-chip
    # billion-parameter capability row — too big for plain residency with
    # Adam on a 16 GiB chip, the case the offload executor exists for
    # (reference ``Spilled.py:23-28``).
    "gptj-1b3": dict(
        d_model=2048, n_layers=24, n_heads=16, d_ff=8192,
        rotary=True, rotary_dim=64, parallel_residual=True,
    ),
    "gptj-test-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, vocab_size=256, seq_len=64,
        rotary=True, rotary_dim=8, parallel_residual=True,
    ),
    # Llama-class family (beyond the reference zoo): RMSNorm + SwiGLU +
    # full-head rotary + grouped-query attention. Shapes follow the public
    # TinyLlama-1.1B and Llama-3-8B configs; vocab stays this framework's
    # 50304 (tied embedding head, native tokenizer world).
    "llama-1b": dict(
        d_model=2048, n_layers=22, n_heads=32, n_kv_heads=4, d_ff=5632,
        rotary=True, norm="rmsnorm", mlp_act="swiglu",
    ),
    "llama-8b": dict(
        d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
        rotary=True, norm="rmsnorm", mlp_act="swiglu",
    ),
    "llama-test-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab_size=256, seq_len=64, rotary=True, norm="rmsnorm",
        mlp_act="swiglu",
    ),
    # Ouro (ByteDance/Ouro-2.6B): a looped LM -- 48 layers run 4 times on
    # shared weights, ``ln_f`` after every pass. Sandwich RMSNorm, SwiGLU,
    # rotary (base 1e6) on the whole 128-wide head, no bias anywhere, an
    # untied head. The published exit gate (Linear(d, 1) per pass) is not
    # built: at the published early_exit_threshold 1 no pass is skipped,
    # and the next-token loss gives it no gradient.
    "ouro-2.6b": dict(
        d_model=2048, n_layers=48, n_heads=16, d_ff=5632, vocab_size=49152,
        rotary=True, rope_theta=1e6, norm="rmsnorm", mlp_act="swiglu",
        sandwich_norm=True, use_bias=False, tie_head=False, n_passes=4,
    ),
    "ouro-test-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, d_ff=176, vocab_size=256,
        seq_len=64, rotary=True, rope_theta=1e6, norm="rmsnorm",
        mlp_act="swiglu", sandwich_norm=True, use_bias=False,
        tie_head=False, n_passes=4,
    ),
    # Olmo-Hybrid (allenai/Olmo-Hybrid-7B): 8 periods of three gated-delta-
    # rule layers (30 heads of 96 / 192, a 4-tap convolution in front, beta
    # up to 2) and one full-attention layer (30 heads of 128, q/k RMSNorm,
    # no rotary: ``rope_theta`` is null in the source). OLMo's reordered
    # norm (x += N(f(x))), SwiGLU, no bias, an untied head.
    "olmo-hybrid-7b": dict(
        d_model=3840, n_layers=32, n_heads=30, d_ff=11008, vocab_size=100352,
        norm="rmsnorm", mlp_act="swiglu", pre_norm=False, sandwich_norm=True,
        use_bias=False, tie_head=False, learned_positions=False, qk_norm=True,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        lin_key_dim=96, lin_value_dim=192, lin_conv=4, lin_neg_eigval=True,
    ),
    "olmo-hybrid-test-tiny": dict(
        d_model=64, n_layers=8, n_heads=4, d_ff=176, vocab_size=256,
        seq_len=64, norm="rmsnorm", mlp_act="swiglu", pre_norm=False,
        sandwich_norm=True, use_bias=False, tie_head=False,
        learned_positions=False, qk_norm=True,
        layer_types=("linear_attention",) * 3 + ("full_attention",),
        lin_key_dim=12, lin_value_dim=24, lin_conv=4, lin_neg_eigval=True,
        lin_chunk=16,
    ),
    # Laguna (poolside/Laguna-XS.2, 33.4B-A3B): a leading dense layer (full
    # attention, SwiGLU 8192), then periods of three sliding-window layers
    # (64 q heads, window 512, plain rotary on all 128 lanes) and one
    # full-attention layer (48 q heads, YaRN rotary on the first 64 lanes),
    # all over 8 k/v heads of 128 with a sigmoid gate a head; every layer of
    # a period feeds a shared expert beside 256 routed ones (top-8, sigmoid
    # scores normalised and scaled 2.5), each a SwiGLU of 512. RMSNorm
    # before each branch, no bias, an untied head. The published order
    # starts each period at its full layer (layers 0, 4, 8, ..): with layer 0
    # taken out as the leading dense layer, the scanned period is layers
    # 1-4: sliding, sliding, sliding, full. A stack is 1 + 4 p layers: 37
    # here, the published 40 less the three sliding layers that follow the
    # last full one (a trailing part of a period is not built: ROADMAP.md,
    # Reach).
    "laguna-xs2": dict(
        d_model=2048, n_layers=37, n_heads=48, n_kv_heads=8, head_width=128,
        d_ff=8192, vocab_size=100352, rotary=True, rotary_dim=64,
        rope_theta=500000.0, yarn=(64.0, 4096, 64.0, 1.0, 1.4158883083359672),
        norm="rmsnorm", mlp_act="swiglu",
        use_bias=False, tie_head=False, attn_gate=True, lead_layers=1,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        kind_heads=(("full_attention", 48), ("sliding_attention", 64)),
        window=512, routed_experts=256, top_k=8, expert_ff=512, shared_ff=512,
        routed_scale=2.5,
    ),
    "laguna-test-tiny": dict(
        d_model=64, n_layers=5, n_heads=6, n_kv_heads=2, head_width=16,
        d_ff=128, vocab_size=256, seq_len=64, rotary=True, rotary_dim=8,
        rope_theta=500000.0, yarn=(64.0, 16, 8.0, 1.0, 1.4158883083359672),
        norm="rmsnorm", mlp_act="swiglu",
        use_bias=False, tie_head=False, attn_gate=True, lead_layers=1,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        kind_heads=(("full_attention", 6), ("sliding_attention", 8)),
        window=24, routed_experts=16, held_experts=4, top_k=4, expert_ff=32,
        shared_ff=32, routed_scale=2.5,
    ),
    # Nemotron-3-Super (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
    # ``nemotron_h``): 88 layers, each one mixer alone by the letters of
    # ``hybrid_override_pattern``: M a Mamba-2 layer (128 heads of 64 in 8
    # groups, state 128, a 4-tap convolution with bias), E a LatentMoE layer
    # (512 routed relu2 experts of 2688 in a 1024-wide latent, top-22 under a
    # selection bias, scaled 5, beside a shared relu2 expert of 5376 on the
    # stream), * attention (32 q heads over 2 k/v heads of 128, no position
    # signal). RMSNorm (eps 1e-5) before each mixer, no bias but the
    # convolution's, an untied head. The published order is not periodic (its
    # first two attention layers follow 7 and 8 others); the preset is its
    # first whole period, layers 26..36: E M E M E M E M E M *. Multi-token
    # prediction is not built (ROADMAP.md, Reach).
    "nemotron3-super": dict(
        d_model=4096, n_layers=11, n_heads=32, n_kv_heads=2, head_width=128,
        vocab_size=131072, norm="rmsnorm", norm_eps=1e-5, use_bias=False,
        tie_head=False, learned_positions=False,
        layer_types=("latent_moe", "mamba2") * 5 + ("attention_only",),
        ssm_heads=128, ssm_head_dim=64, ssm_groups=8, ssm_state=128,
        ssm_conv=4, ssm_chunk=128, routed_experts=512, top_k=22,
        expert_ff=2688, shared_ff=5376, routed_scale=5.0, latent_dim=1024,
        expert_act="relu2", router_bias=True,
    ),
    "nemotron-test-tiny": dict(
        d_model=64, n_layers=11, n_heads=8, n_kv_heads=2, head_width=16,
        vocab_size=256, seq_len=64, norm="rmsnorm", norm_eps=1e-5,
        use_bias=False, tie_head=False, learned_positions=False,
        layer_types=("latent_moe", "mamba2") * 5 + ("attention_only",),
        ssm_heads=16, ssm_head_dim=8, ssm_groups=8, ssm_state=16,
        ssm_conv=4, ssm_chunk=16, routed_experts=12, held_experts=4, top_k=3,
        expert_ff=48, shared_ff=96, routed_scale=5.0, latent_dim=32,
        expert_act="relu2", router_bias=True,
    ),
    # Ling-3.0-flash (inclusionAI/Ling-3.0-flash-VL, the language model):
    # two leading dense layers (KDA + SwiGLU 6144), then periods of six
    # layers in the published order from layer 2 on: KDA, KDA, KDA, MLA, KDA,
    # KDA (the MLA layers are the published 5, 11, ..: every sixth), each
    # before a shared expert beside 512 routed ones (top-8 among the experts
    # of the 4 best of 8 groups, sigmoid scores under a selection bias,
    # normalised and scaled 2.5), each a SwiGLU of 768. KDA: 32 heads of 128
    # keys and values, three 4-tap convolutions, a gate a key channel bounded
    # at -5, a gate a head on the output. MLA: 32 heads, q and k of 128 + 64
    # rotary lanes (base 6e6), v of 128, from a 512-wide latent, an RMSNorm on
    # each head's q and k. RMSNorm before each branch, no bias, an untied
    # head, no position table. A stack is 2 + 6 p layers: 38 here, the
    # published 42 less the four layers that follow the last whole period (a
    # trailing part of a period is not built: ROADMAP.md, Reach). Neither
    # the vision tower nor multi-token prediction is built.
    "ling3-flash": dict(
        d_model=2560, n_layers=38, n_heads=32, head_width=128, d_ff=6144,
        vocab_size=157184, norm="rmsnorm", mlp_act="swiglu", use_bias=False,
        tie_head=False, learned_positions=False, attn_gate=True,
        lead_layers=2, lead_kind="kda",
        layer_types=("kda",) * 3 + ("mla",) + ("kda",) * 2,
        kv_latent=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        head_qk_norm=True, rope_theta=6e6, lin_conv=4, kda_gate_floor=-5.0,
        routed_experts=512, top_k=8, expert_ff=768, shared_ff=768,
        routed_scale=2.5, router_bias=True, route_groups=8,
        route_groups_kept=4,
    ),
    "ling-test-tiny": dict(
        d_model=64, n_layers=7, n_heads=4, head_width=16, d_ff=128,
        vocab_size=256, seq_len=128, norm="rmsnorm", mlp_act="swiglu",
        use_bias=False, tie_head=False, learned_positions=False,
        attn_gate=True, lead_layers=1, lead_kind="kda",
        layer_types=("kda",) * 3 + ("mla",) + ("kda",) * 2,
        kv_latent=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        head_qk_norm=True, rope_theta=6e6, lin_conv=4, kda_gate_floor=-5.0,
        routed_experts=16, held_experts=4, top_k=4,
        expert_ff=32, shared_ff=32, routed_scale=2.5, router_bias=True,
        route_groups=4, route_groups_kept=2,
    ),
    # SmallThinker (PowerInfer/SmallThinker-21BA3B-Instruct): 52 layers, every
    # one the same but for its attention's kind: layer l with l % 4 == 0 full
    # attention with q and k *not* rotated (NoPE), the other three of a period
    # a 4096-key sliding window rotated over all 128 lanes at base 1.5e6; 28 q
    # heads over 4 k/v heads of 128 (q 3584 wide). Every feed-forward is 64
    # routed ReGLU experts of 768 (top-6, a softmax over the chosen logits),
    # no shared expert and no dense layer; **the router reads the block's
    # input, before the first norm and the attention**
    # (``route_from="block_input"``). RMSNorm (eps 1e-6) before each branch,
    # no bias, an untied head. 52 layers are 13 whole periods in the
    # published order: full, sliding, sliding, sliding.
    "smallthinker-21b": dict(
        d_model=2560, n_layers=52, n_heads=28, n_kv_heads=4, head_width=128,
        vocab_size=151936, rotary=True, rope_theta=1.5e6,
        window_rope_theta=1.5e6, rotary_kinds=("sliding_attention",),
        norm="rmsnorm", use_bias=False, tie_head=False,
        layer_types=("full_attention",) + ("sliding_attention",) * 3,
        window=4096, routed_experts=64, top_k=6, expert_ff=768,
        expert_act="reglu", router_score="softmax", route_from="block_input",
    ),
    "smallthinker-test-tiny": dict(
        d_model=64, n_layers=4, n_heads=14, n_kv_heads=2, head_width=16,
        vocab_size=256, seq_len=64, rotary=True, rope_theta=1.5e6,
        window_rope_theta=1.5e6, rotary_kinds=("sliding_attention",),
        norm="rmsnorm", use_bias=False, tie_head=False,
        layer_types=("full_attention",) + ("sliding_attention",) * 3,
        window=32, routed_experts=16, held_experts=4, top_k=4, expert_ff=32,
        expert_act="reglu", router_score="softmax", route_from="block_input",
    ),
    # LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B, ``lfm2_moe``): 24 layers, 18 doubly
    # gated short convolutions (3 taps, no bias, no activation) and 6
    # grouped-query attention layers (32 q heads over 8 k/v heads of 64, an
    # RMSNorm a head on q and k before a rotation of all 64 lanes at base
    # 1e6). Layers 0 and 1 are dense (a conv mixer before a SwiGLU of 7168),
    # every later feed-forward 32 routed SwiGLU experts of 1792: top-4 of
    # sigmoid scores under a selection bias, the weights normalised over the
    # chosen (+ 1e-6), scaled 1, no shared expert. RMSNorm (eps 1e-5) before
    # each branch, no bias, the head tied to the embedding. The published
    # order after the two leading layers is four periods of full, conv, conv,
    # conv (layers 2..17) and then full, conv, conv, full, conv, conv (18..23):
    # a tail of two periods of *three*. A stack here is 2 + 4 p layers: 18, the
    # published 24 less that tail (periods of another length after the last
    # whole one are not built: ROADMAP.md, Reach; ``build_lfm2`` refuses a
    # depth that reaches them).
    "lfm2-8b-a1b": dict(
        d_model=2048, n_layers=18, n_heads=32, n_kv_heads=8, d_ff=7168,
        vocab_size=65536, rotary=True, rope_theta=1e6, norm="rmsnorm",
        norm_eps=1e-5, mlp_act="swiglu", use_bias=False, head_qk_norm=True,
        lead_layers=2, lead_kind="conv",
        layer_types=("full_attention",) + ("conv",) * 3, conv_taps=3,
        routed_experts=32, top_k=4, expert_ff=1792, routed_scale=1.0,
        router_bias=True, route_eps=1e-6,
    ),
    "lfm2-test-tiny": dict(
        d_model=64, n_layers=5, n_heads=8, n_kv_heads=2, d_ff=128,
        vocab_size=256, seq_len=64, rotary=True, rope_theta=1e6,
        norm="rmsnorm", norm_eps=1e-5, mlp_act="swiglu", use_bias=False,
        head_qk_norm=True, lead_layers=1, lead_kind="conv",
        layer_types=("full_attention",) + ("conv",) * 3, conv_taps=3,
        routed_experts=16, held_experts=4, top_k=4, expert_ff=32,
        routed_scale=1.0, router_bias=True, route_eps=1e-6,
    ),
    # Switch-style MoE family (extension beyond the reference; SURVEY.md §2.3
    # lists EP as absent there).
    "moe-test-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, vocab_size=256, seq_len=64,
        moe=True, n_experts=4, d_ff=128,
    ),
    "gpt2-small-moe8": dict(d_model=768, n_layers=12, n_heads=12, moe=True,
                            n_experts=8),
}


def rotary_sin_cos(positions: jax.Array, rotary_dim: int,
                   theta: float = 10000.0):
    """(sin, cos) tables, each (T, rotary_dim//2), fp32.

    Reference computed fixed sinusoids and rotated every-other dim
    (``GPTJ.py:44-79``); we use the equivalent half-split rotation, which XLA
    fuses into the surrounding matmuls without the interleaving gathers.
    """
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    )
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.sin(angles), jnp.cos(angles)


def yarn_inv_freq(rotary_dim: int, theta: float, factor: float,
                  original_positions: int, beta_fast: float, beta_slow: float):
    """YaRN's frequencies, (rotary_dim // 2,) float32: each plain frequency
    ``theta^(-2j / rotary_dim)`` interpolated towards itself / ``factor`` by a
    linear ramp over the dimensions between the one that turns ``beta_fast``
    times in ``original_positions`` positions (and faster: kept) and the one
    that turns ``beta_slow`` times (and slower: divided by ``factor``)."""
    plain = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                             / rotary_dim))

    def turns_at(turns):   # the (real-valued) dimension that turns so often
        return rotary_dim * math.log(original_positions / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rotary_tables(cfg: "GPT2Config", kind: str, positions: jax.Array):
    """(sin, cos, rotary_dim) of a softmax layer of ``kind``: a sliding layer
    rotates all its lanes at ``window_rope_theta``; any other the first
    ``rotary_dim`` at ``rope_theta``, with YaRN's frequencies and its
    attention factor on sin and cos where ``cfg.yarn`` says."""
    if kind == "sliding_attention":
        rd = cfg.head_dim
        return (*rotary_sin_cos(positions, rd, cfg.window_rope_theta), rd)
    rd = cfg.rotary_dim or cfg.head_dim
    if cfg.yarn is None:
        return (*rotary_sin_cos(positions, rd, cfg.rope_theta), rd)
    factor, original, beta_fast, beta_slow, attention_factor = cfg.yarn
    angles = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(
        rd, cfg.rope_theta, factor, int(original), beta_fast, beta_slow)[None, :]
    return (jnp.sin(angles) * attention_factor,
            jnp.cos(angles) * attention_factor, rd)


def apply_rotary(t: jax.Array, sin: jax.Array, cos: jax.Array, rotary_dim: int):
    """Rotate the first ``rotary_dim`` dims of ``t`` (..., T, D) by position."""
    sin, cos = sin.astype(t.dtype), cos.astype(t.dtype)
    t_rot, t_pass = t[..., :rotary_dim], t[..., rotary_dim:]
    half = rotary_dim // 2
    t1, t2 = t_rot[..., :half], t_rot[..., half:]
    rotated = jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)
    return jnp.concatenate([rotated, t_pass], axis=-1)


def config_for(name: str, **overrides) -> GPT2Config:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; options: {list(PRESETS)}")
    kw = dict(PRESETS[name])
    kw.update(overrides)
    return GPT2Config(name=name, **kw)


def resolve_attention(cfg: GPT2Config) -> GPT2Config:
    """Resolve attention='auto' to a concrete implementation for the current
    backend: flash wherever the Pallas kernel can lower (measured ≥ dense at
    every seq on the chip), dense otherwise (CPU tests, indivisible seq)."""
    if cfg.attention != "auto":
        return cfg
    from saturn_tpu.ops.flash import flash_supported

    return replace(cfg, attention="flash" if flash_supported(cfg) else "dense")


def _tap_init(taps: int):
    """A depthwise Conv1d's own initialiser: +-1/sqrt(fan_in)."""
    def init(key, shape, dtype):
        bound = 1.0 / math.sqrt(taps)
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _decay_init(key, shape, dtype):
    """Mamba-2's ``A_log``: decay rates uniform in [1, 16)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _step_init(key, shape, dtype):
    """Mamba-2's ``dt_bias``: step sizes log-uniform in [1e-3, 1e-1], through
    the inverse softplus."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype,
                                      math.log(1e-3), math.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def _gate_bias_init(key, shape, dtype):
    """A KDA layer's ``dt_bias``: the gate's logit about -3.9 (a decay of
    exp(-0.1) a token), spread so that a channel in a hundred forgets
    faster than exp(-2)."""
    return -3.9 + 1.1 * jax.random.normal(key, shape, dtype)


def _unit(t):
    """``t`` over its l2 norm along the last axis (a head's lanes)."""
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _norm_cls(cfg: GPT2Config):
    """The ONE place the cfg.norm choice maps to a flax module class —
    Block norms, the model's ln_f, and the pipeline head must stay in
    sync."""
    cls = nn.RMSNorm if cfg.norm == "rmsnorm" else nn.LayerNorm
    if cfg.norm_eps is not None:
        return functools.partial(cls, epsilon=cfg.norm_eps)
    return cls


class Block(nn.Module):
    """Pre-LN transformer block, scan-compatible signature.

    ``sandwich_norm=True`` (sequential wiring only) norms each branch's
    output too, before its residual add (``ln_1_post``, ``ln_2_post``).

    Two residual wirings (parity with ``GPTJ.py:392-424``): sequential GPT-2
    (ln_1 → attn, ln_2 → mlp) or, with ``parallel_residual=True``, GPT-J's
    parallel form (one ln, attn and mlp added together). ``rotary=True``
    rotates the first ``rotary_dim`` q/k dims by position.

    ``kind`` chooses the mixer: softmax attention, the gated delta rule of
    ``ops/gdn.py`` behind a short convolution (``_linear_mixer``), Kimi delta
    attention, latent attention, or the doubly gated short convolution alone
    (``_short_conv_mixer``). With
    ``held_heads`` the mixer computes the held heads' part of its output:
    the projections' columns and ``attn_out``'s rows of those heads, and
    nothing that stands in for the heads held elsewhere."""

    cfg: GPT2Config
    kind: str = "full_attention"
    #: "routed": the feed-forward is a shared expert beside the held routed
    #: experts (``_routed_mlp``); "dense": the MLP of ``d_ff``
    ff: str = "dense"

    @nn.compact
    def __call__(self, x, _unused):
        cfg = self.cfg
        dt, pdt = cfg.dtype, cfg.param_dtype
        B, T, D = x.shape

        def make_norm(name):
            return _norm_cls(cfg)(dtype=dt, param_dtype=pdt, name=name)

        def dense(features, name):
            return nn.Dense(features, dtype=dt, param_dtype=pdt,
                            use_bias=cfg.use_bias, name=name)

        # a router that reads the block's input makes its route here, ahead of
        # the first norm; the experts take it up after the mixer
        made = self._route(x) if (
            self.ff == "routed" and cfg.route_from == "block_input") else None
        h = make_norm("ln_1")(x) if cfg.pre_norm else x
        if self.kind == "linear_attention":
            attn = self._linear_mixer(h, dense)
        elif self.kind == "kda":
            attn = self._kda_mixer(h, dense)
        elif self.kind == "mla":
            attn = self._mla_mixer(h, dense, make_norm)
        elif self.kind == "conv":
            attn = self._short_conv_mixer(h, dense)
        else:
            attn = self._softmax_mixer(h, dense, make_norm)
        attn = dense(D, "attn_out")(attn)
        if cfg.sandwich_norm:
            attn = make_norm("ln_1_post")(attn)

        # ---- mlp (dense or Switch-routed experts) ----
        def mlp(inp):
            if cfg.moe:
                return self._moe_mlp(inp)
            if self.ff == "routed":
                return self._routed_mlp(inp, dense, made)
            if cfg.mlp_act == "swiglu":
                # Separate gate/up projections (NOT one fused 2F Dense): the
                # TP column rule shards each kernel's output dim, so
                # gate_i/up_i stay on the same model shard and silu(gate)*up
                # is local — a fused contiguous split would put all gate
                # columns on shard 0 and force a full-activation reshard
                # per layer.
                gate = dense(cfg.ff_dim, "mlp_gate")(inp)
                up = dense(cfg.ff_dim, "mlp_in")(inp)
                m = nn.silu(gate) * up
            else:
                m = dense(cfg.ff_dim, "mlp_in")(inp)
                m = nn.gelu(m, approximate=True)
            return dense(D, "mlp_out")(m)

        if cfg.parallel_residual:
            # GPT-J wiring: attn and MLP both read ln_1(x), one residual add
            # (reference ``GPTJ.py:392-424``).
            x = x + attn + mlp(h)
        else:
            x = x + attn
            m = mlp(make_norm("ln_2")(x) if cfg.pre_norm else x)
            if cfg.sandwich_norm:
                m = make_norm("ln_2_post")(m)
            x = x + m
        return x, None

    def _softmax_mixer(self, h, dense, make_norm):
        """(B, T, D) -> the held heads' attention output (B, T, A), before
        ``attn_out``; A = held heads x head_dim (D where all are held)."""
        cfg = self.cfg
        dt = cfg.dtype
        B, T, D = h.shape
        n_q = cfg.heads_of(self.kind)
        A = n_q * cfg.head_dim
        window = cfg.window if self.kind == "sliding_attention" else None
        if cfg.n_kv_heads is None:
            qkv = dense(3 * A, "qkv")(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            kv_heads = n_q
        else:
            # Grouped-query attention: k/v carry n_kv_heads; one fused
            # projection sized A + 2 * kv_dim (A = the q heads' lanes: D
            # wherever heads x head_dim is the stream's width).
            kv_heads = cfg.kv_heads_held
            kv_dim = kv_heads * cfg.head_dim
            qkv = dense(A + 2 * kv_dim, "qkv")(h)
            q = qkv[..., :A]
            k = qkv[..., A:A + kv_dim]
            v = qkv[..., A + kv_dim:]
        if cfg.qk_norm:
            # over all held heads' lanes together (with a share of the heads
            # the statistic is the held share's: ROADMAP.md, Reach)
            q, k = make_norm("q_norm")(q), make_norm("k_norm")(k)

        def heads(t, n):
            return t.reshape(B, T, n, cfg.head_dim).transpose(0, 2, 1, 3)

        q = heads(q, n_q)
        k, v = heads(k, kv_heads), heads(v, kv_heads)
        if cfg.head_qk_norm:
            # over a head's lanes, one gain shared by the heads (the q heads'
            # and the fewer k/v heads' alike), before the rotation
            q = self._head_norm(q, "q_norm", cfg.head_dim)
            k = self._head_norm(k, "k_norm", cfg.head_dim)
        if cfg.rotary_kinds is not None:
            from saturn_tpu.ops import plans

            kinds = [kind for kind in dict.fromkeys(cfg.layer_types or (self.kind,))
                     if kind in ("full_attention", "sliding_attention")]
            plans.record("rotary", {
                "rotated": [kind for kind in kinds if kind in cfg.rotary_kinds],
                "unrotated": [kind for kind in kinds if kind not in cfg.rotary_kinds]})
        if cfg.rotary and (cfg.rotary_kinds is None or self.kind in cfg.rotary_kinds):
            if cfg.seq_axis is not None:
                # Global positions for a sequence-sharded chunk.
                offset = jax.lax.axis_index(cfg.seq_axis) * T
            else:
                offset = 0
            sin, cos, rd = rotary_tables(cfg, self.kind, jnp.arange(T) + offset)
            q = apply_rotary(q, sin, cos, rd)
            k = apply_rotary(k, sin, cos, rd)
        if kv_heads != n_q and not (
            cfg.seq_axis is None and self._attention_impl() == "flash"
        ):
            # GQA on the non-flash paths: repeat k/v head groups up to
            # n_heads so dense/ring/ulysses see matched head counts. The
            # params stay at kv_heads — the repeat is activation-only. The
            # flash kernel handles grouped k/v natively (ops/flash.py), so
            # the expanded activations never exist there.
            rep = n_q // kv_heads
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        if cfg.seq_axis is not None:
            if cfg.seq_mode == "ulysses":
                from saturn_tpu.ops.ulysses import ulysses_attention

                attn = ulysses_attention(
                    q, k, v, axis_name=cfg.seq_axis, axis_size=cfg.seq_axis_size
                )
            else:
                from saturn_tpu.ops.ring import ring_attention

                attn = ring_attention(
                    q, k, v, axis_name=cfg.seq_axis,
                    axis_size=cfg.seq_axis_size, overlap=cfg.seq_overlap,
                )
        elif self._attention_impl() == "flash":
            from saturn_tpu.ops.flash import flash_attention

            attn = flash_attention(q, k, v, causal=cfg.causal, window=window)
        else:
            # fp32 softmax accumulation for stability; matmuls stay bf16-in.
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
            scores = scores / math.sqrt(cfg.head_dim)
            if cfg.causal:
                mask = jnp.tril(jnp.ones((T, T), dtype=bool))
                if window is not None:   # keys i - window + 1 .. i
                    mask = mask & ~jnp.tril(jnp.ones((T, T), dtype=bool), -window)
                scores = jnp.where(mask[None, None], scores, jnp.float32(-1e30))
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            attn = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        if cfg.attn_gate:
            # one scalar a head from the block's normed input, float32
            gate = jax.nn.sigmoid(dense(n_q, "attn_gate")(h).astype(jnp.float32))
            attn = (attn.astype(jnp.float32)
                    * gate.transpose(0, 2, 1)[..., None]).astype(dt)
        return attn.transpose(0, 2, 1, 3).reshape(B, T, A)

    def _linear_mixer(self, h, dense):
        """(B, T, D) -> the held heads' gated-delta-rule output (B, T,
        held x lin_value_dim), before ``attn_out``:

            q~, k~, v~ = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))
            q = q~ / |q~| / sqrt(dk);  k = k~ / |k~|        (per head)
            beta = (2 if lin_neg_eigval else 1) sigmoid(h Wb)
            g = -exp(A_log) softplus(h Wa + dt_bias)        (log decay)
            o = gated_delta_rule(q, k, v, g, beta)
            out = RMSNorm_head(o) * silu(h Wgate)

        The convolution is depthwise and causal (tap ``j`` of ``lin_conv``
        multiplies the token ``lin_conv - 1 - j`` back). Gates, norms and the
        rule's state are float32; the projections and the rule's products
        take ``cfg.dtype`` operands. The rule runs as the Pallas kernel where
        the attention implementation is "flash", as the plain chunked scan
        where it is "dense": one grid point chooses both mixers."""
        from saturn_tpu.ops.gdn import gated_delta_rule

        cfg = self.cfg
        dt, pdt = cfg.dtype, cfg.param_dtype
        B, T, _ = h.shape
        H, dk, dv = cfg.heads_held, cfg.lin_key_dim, cfg.lin_value_dim
        f32 = jnp.float32

        q = self._conv_heads(h, dense, "q", dk)
        k = self._conv_heads(h, dense, "k", dk)
        v = self._conv_heads(h, dense, "v", dv)
        gate = dense(H * dv, "lin_gate")(h)
        a = dense(H, "lin_a")(h).astype(f32)
        b = dense(H, "lin_b")(h).astype(f32)
        # Mamba-2's inits (``_decay_init``, ``_step_init``)
        a_log = self.param("A_log", _decay_init, (H,), pdt)
        dt_bias = self.param("dt_bias", _step_init, (H,), pdt)
        beta = (2.0 if cfg.lin_neg_eigval else 1.0) * jax.nn.sigmoid(b)
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(a + dt_bias.astype(f32))
        o = gated_delta_rule(
            (_unit(q) / math.sqrt(dk)).astype(dt), _unit(k).astype(dt), v.astype(dt),
            g.transpose(0, 2, 1), beta.transpose(0, 2, 1),
            impl="kernel" if self._attention_impl() == "flash" else "xla",
            chunk=cfg.lin_chunk,
        )
        o = nn.RMSNorm(dtype=f32, param_dtype=pdt, name="o_norm")(o)   # float32 as it comes
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H * dv)
        return (o * nn.silu(gate.astype(f32))).astype(dt)

    def _conv_heads(self, h, dense, which, width):
        """``silu(conv(h W))`` by heads, (B, H, T, width) float32: the
        projection ``lin_<which>`` to the held heads' lanes, then the depthwise
        causal convolution ``conv_<which>`` of ``lin_conv`` taps a lane."""
        cfg = self.cfg
        B, T, _ = h.shape
        H, taps, f32 = cfg.heads_held, cfg.lin_conv, jnp.float32
        t = dense(H * width, "lin_" + which)(h)
        w = self.param("conv_" + which, _tap_init(taps), (taps, t.shape[-1]),
                       cfg.param_dtype).astype(f32)
        padded = jnp.pad(t.astype(f32), ((0, 0), (taps - 1, 0), (0, 0)))
        out = nn.silu(sum(w[j] * padded[:, j:j + T] for j in range(taps)))
        return out.reshape(B, T, H, width).transpose(0, 2, 1, 3)

    def _head_norm(self, t, name, lanes):
        """An RMSNorm over the last axis of ``t`` (a head's ``lanes``) under
        one gain ``name`` of ``lanes`` entries shared by the heads, float32."""
        cfg, f32 = self.cfg, jnp.float32
        eps = 1e-6 if cfg.norm_eps is None else cfg.norm_eps
        gain = self.param(name, nn.initializers.ones, (lanes,), cfg.param_dtype)
        t = t.astype(f32)
        t = t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)
        return (t * gain.astype(f32)).astype(cfg.dtype)

    def _short_conv_mixer(self, h, dense):
        """(B, T, D) -> the doubly gated short convolution's output (B, T, D),
        before ``attn_out`` (its ``W_out``):

            B = h W_b;  C = h W_c;  u = h W_x       (``W_in`` = [W_b | W_c | W_x])
            s = B * u
            c_t = sum_j w_j * s_{t - (taps - 1) + j}    depthwise, causal
            out = C * c

        ``s`` is zero before the sequence; the last tap multiplies the token
        itself; no activation, no heads, no bias. The three blocks of the
        input projection are three kernels (``conv_b`` / ``conv_c`` /
        ``conv_x``): the tensor-parallel column rule shards each one's
        channels, so a channel's B, C and u lie on one shard and the gates and
        the depthwise convolution are local (a fused contiguous split would
        put all of B on the first shards: the comment on ``mlp_gate``). Gates
        and taps' products float32, the projections in ``cfg.dtype``. Plain
        XLA ops: no kernel of it."""
        from saturn_tpu.ops import plans

        cfg = self.cfg
        f32 = jnp.float32
        _, T, D = h.shape
        taps = cfg.conv_taps
        plans.record("conv", {
            "impl": "xla", "taps": taps, "channels": D,
            "layers_a_period": (cfg.layer_types or ()).count("conv"),
            "layers_in_the_lead": cfg.lead_layers if cfg.lead_kind == "conv" else 0})
        gate_in, gate_out, u = (dense(D, "conv_" + which)(h).astype(f32)
                                for which in "bcx")
        w = self.param("conv_w", _tap_init(taps), (taps, D), cfg.param_dtype).astype(f32)
        padded = jnp.pad(gate_in * u, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(w[j] * padded[:, j:j + T] for j in range(taps))
        return (gate_out * conv).astype(cfg.dtype)

    def _head_gate(self, attn, h, dense, n_heads):
        """``attn`` (B, H, T, lanes) times a sigmoid gate a head from the
        block's normed input (``attn_gate``, d_model -> heads), float32."""
        gate = jax.nn.sigmoid(dense(n_heads, "attn_gate")(h).astype(jnp.float32))
        return (attn.astype(jnp.float32)
                * gate.transpose(0, 2, 1)[..., None]).astype(self.cfg.dtype)

    def _kda_mixer(self, h, dense):
        """(B, T, D) -> the held heads' Kimi-delta-attention output (B, T,
        held x head_dim), before ``attn_out``:

            q~, k~, v~ = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))
            q = q~ / |q~| / sqrt(dk);  k = k~ / |k~|        (per head)
            beta = sigmoid(h Wb)                            (a scalar a head)
            g = floor sigmoid(exp(A_log) (h Wa + dt_bias))  (log decay a channel)
            o = kda(q, k, v, g, beta)                       (``ops/kda.py``)
            out = RMSNorm_head(o) * sigmoid(h Wgate)        (a gate a head)

        ``A_log`` is a scalar a head, ``dt_bias`` one a channel; ``floor`` =
        ``kda_gate_floor`` (-5) bounds the gate, which is what the chunked
        form's sub-blocks lean on. The convolution is ``_linear_mixer``'s.
        Gates, norms and the rule's state are float32. The rule is the plain
        chunked scan at every grid point (no kernel of it yet: ROADMAP.md,
        M5)."""
        from saturn_tpu.ops.kda import kda

        cfg = self.cfg
        dt, pdt, f32 = cfg.dtype, cfg.param_dtype, jnp.float32
        B, T, _ = h.shape
        H, dk = cfg.heads_held, cfg.head_dim
        q, k, v = (self._conv_heads(h, dense, which, dk) for which in "qkv")
        a = dense(H * dk, "lin_a")(h).astype(f32)
        b = dense(H, "lin_b")(h).astype(f32)
        a_log = self.param("A_log", nn.initializers.zeros, (H,), pdt)
        dt_bias = self.param("dt_bias", _gate_bias_init, (H * dk,), pdt)
        rate = jnp.repeat(jnp.exp(a_log.astype(f32)), dk)                 # (H dk,)
        g = cfg.kda_gate_floor * jax.nn.sigmoid(rate * (a + dt_bias.astype(f32)))
        o = kda((_unit(q) / math.sqrt(dk)).astype(dt), _unit(k).astype(dt), v.astype(dt),
                g.reshape(B, T, H, dk).transpose(0, 2, 1, 3),
                jax.nn.sigmoid(b).transpose(0, 2, 1))
        # one gain of head_dim lanes, shared by the heads; float32 as it comes
        o = nn.RMSNorm(dtype=f32, param_dtype=pdt, name="o_norm",
                       **({} if cfg.norm_eps is None else {"epsilon": cfg.norm_eps}))(o)
        if cfg.attn_gate:
            o = self._head_gate(o, h, dense, H)
        return o.astype(dt).transpose(0, 2, 1, 3).reshape(B, T, H * dk)

    def _mla_mixer(self, h, dense, make_norm):
        """(B, T, D) -> the held heads' latent-attention output (B, T, held x
        v_head_dim), before ``attn_out``:

            [qc_n | qr_n] = h Wq                  (a head: nope | rope lanes)
            [c | kr] = h Wkva;  c = N(c)          (kv_latent | rope lanes)
            [kc_n | v_n] = c Wkvb                 (a head: nope | v_head_dim)
            q_n = [qc_n | qr_n];  k_n = [kc_n | kr]   (kr shared by the heads)
            q_n, k_n = N_h(q_n), N_h(k_n)         (``head_qk_norm``: over a
                                                   head's lanes, one gain)
            rotary on the rope lanes of q_n and k_n at ``rope_theta``
            o_n = softmax(q_n . k_n / sqrt(nope + rope), causal) v_n

        and a sigmoid gate a head (``attn_gate``). The rotary lanes are the
        last ``qk_rope_dim`` of a head, in split-half order. Flash where the
        attention implementation is "flash" (``saturn_mla_*``: the scores'
        lanes and the values' differ), the plain einsums where it is "dense".
        The shared rotary key is broadcast over the heads here: the heads'
        norm makes each head's copy its own."""
        cfg = self.cfg
        dt, pdt, f32 = cfg.dtype, cfg.param_dtype, jnp.float32
        B, T, _ = h.shape
        H, L = cfg.heads_held, cfg.kv_latent
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        q = dense(H * (dn + dr), "mla_q")(h).reshape(B, T, H, dn + dr)
        kva = dense(L + dr, "mla_kv_a")(h)
        c = make_norm("kv_norm")(kva[..., :L])
        kvb = dense(H * (dn + dv), "mla_kv_b")(c).reshape(B, T, H, dn + dv)
        k = jnp.concatenate(
            [kvb[..., :dn], jnp.broadcast_to(kva[:, :, None, L:], (B, T, H, dr))], axis=-1)
        v = kvb[..., dn:]
        if cfg.head_qk_norm:
            q = self._head_norm(q, "q_norm", dn + dr)
            k = self._head_norm(k, "k_norm", dn + dr)
        sin, cos = rotary_sin_cos(jnp.arange(T), dr, cfg.rope_theta)

        def turned(t):      # (B, T, H, dn + dr) -> (B, H, T, dn + dr)
            t = t.transpose(0, 2, 1, 3)
            return jnp.concatenate(
                [t[..., :dn], apply_rotary(t[..., dn:], sin, cos, dr)], axis=-1)

        q, k, v = turned(q), turned(k), v.transpose(0, 2, 1, 3)
        if self._attention_impl() == "flash":
            from saturn_tpu.ops.flash import flash_attention

            attn = flash_attention(q, k, v, causal=True)
        else:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(f32)
            scores = scores / math.sqrt(dn + dr)
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            scores = jnp.where(mask[None, None], scores, jnp.float32(-1e30))
            attn = jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(scores, axis=-1).astype(dt), v)
        if cfg.attn_gate:
            attn = self._head_gate(attn, h, dense, H)
        return attn.transpose(0, 2, 1, 3).reshape(B, T, H * dv)

    def _attention_impl(self) -> str:
        """'auto' resolution for configs built without ``build_gpt2`` — one
        rule, shared with the factory path (:func:`resolve_attention`)."""
        return resolve_attention(self.cfg).attention

    def _routed_plan(self, tokens: int):
        from saturn_tpu.ops.moe import routed_plan

        cfg = self.cfg
        return routed_plan(
            tokens, cfg.routed_experts, cfg.experts_held, cfg.top_k,
            impl="kernel" if self._attention_impl() == "flash" else "xla",
            act=cfg.expert_act, latent=cfg.latent_dim, bias=cfg.router_bias,
            buffer=cfg.routed_buffer, groups=cfg.route_groups,
            groups_kept=cfg.route_groups_kept, score=cfg.router_score,
            route_from=cfg.route_from, eps=cfg.route_eps)

    def _router(self, D: int):
        """(the router's matrix, its selection bias or None)."""
        cfg = self.cfg
        router = self.param("router", nn.initializers.normal(0.02),
                            (D, cfg.routed_experts), cfg.param_dtype)
        bias = self.param("router_bias", nn.initializers.zeros,
                          (cfg.routed_experts,), cfg.param_dtype) \
            if cfg.router_bias else None
        return router, bias

    def _route(self, x):
        """The route (``ops/moe.py::route``) of the block's input ``x``
        (B, T, D), un-normed: the first half of the routed layer, made ahead
        of the mixer (``route_from="block_input"``)."""
        from saturn_tpu.ops import plans
        from saturn_tpu.ops.moe import route

        cfg = self.cfg
        B, T, D = x.shape
        plan = self._routed_plan(B * T)
        plans.record("moe", plan)
        router, bias = self._router(D)
        return route(x.reshape(B * T, D).astype(cfg.dtype), router, plan=plan,
                     scale=cfg.routed_scale, bias=bias)

    def _routed_mlp(self, inp, dense, made=None):
        """A shared expert (``shared_ff``; 0: none) beside the held share of
        ``routed_experts`` routed ones (``ops/moe.py::routed_experts``):
        every expert a SwiGLU, ReGLU or relu2 of ``expert_ff`` (``shared_ff``).
        The router scores all the experts in
        float32 and keeps its ``top_k`` a token; the tables hold
        ``held_experts`` experts (a leading expert axis: dim 1 under the
        layer scan) in ``param_dtype`` and are rounded to ``dtype`` inside
        the op. The kernels run where the attention implementation is
        "flash", the plain twin where it is "dense". The layer's counters go
        to the ``moe_stats`` collection. ``made``: the route where the block
        made it ahead of its mixer (``_route``); the experts here run under
        it (``ops/moe.py::experts_under``)."""
        from saturn_tpu.ops.moe import experts_under, routed_experts

        cfg = self.cfg
        B, T, D = inp.shape
        held, F = cfg.experts_held, cfg.expert_ff
        L = cfg.latent_dim or D             # the width the experts read and write
        gated = cfg.expert_act != "relu2"
        pdt = cfg.param_dtype
        init = nn.initializers.normal(0.02)
        if made is None:
            router, bias = self._router(D)
        w_gate = self.param("we_gate", init, (held, L, F), pdt) if gated else None
        w_up = self.param("we_up", init, (held, L, F), pdt)
        w_down = self.param("we_down", init, (held, F, L), pdt)
        plan = self._routed_plan(B * T)
        latent = dense(L, "latent_down")(inp).reshape(B * T, L) \
            if cfg.latent_dim else None
        if made is None:
            y, stats = routed_experts(
                inp.reshape(B * T, D), router, w_gate, w_up, w_down, plan=plan,
                scale=cfg.routed_scale, dtype=cfg.dtype, bias=bias, latent=latent)
        else:
            y, stats = experts_under(
                made, inp.reshape(B * T, D).astype(cfg.dtype), w_gate, w_up,
                w_down, plan=plan, dtype=cfg.dtype)
        for name, value in stats.items():
            self.sow("moe_stats", name, value)
        y = y.reshape(B, T, L)
        if cfg.latent_dim:
            y = dense(D, "latent_up")(y)
        if cfg.shared_ff:
            m = dense(cfg.shared_ff, "shared_in")(inp)
            if gated:
                m = nn.silu(dense(cfg.shared_ff, "shared_gate")(inp)) * m
            else:
                m = jnp.square(nn.relu(m.astype(jnp.float32))).astype(cfg.dtype)
            y = y + dense(D, "shared_out")(m)
        return y

    def _ssm_mixer(self, h, dense):
        """(B, T, D) -> a Mamba-2 layer's output (B, T, D), the held heads'
        part (H heads of P lanes in G groups of N-wide B and C):

            [z | xBC | dt] = h W_in            (H P | H P + 2 G N | H)
            xBC = silu(conv(xBC) + b);  x, B, C = split(xBC)
            Delta = softplus(dt + dt_bias);  A = -exp(A_log)
            o = ssd(x, Delta, A, B, C, D)      (``ops/ssd.py``)
            o = N_group(o * silu(z));  out = o W_out

        The convolution is depthwise and causal (tap ``j`` of ``ssm_conv``
        multiplies the token ``ssm_conv - 1 - j`` back). ``N_group`` is an
        RMSNorm over each group's lanes separately with one gain a lane.
        Gates, norm, decay and the recurrence's state are float32; the
        projections and the recurrence's products take ``cfg.dtype`` operands.
        The recurrence runs as the Pallas kernel where the attention
        implementation is "flash", as the plain chunked scan where it is
        "dense"."""
        from saturn_tpu.ops.ssd import ssd

        cfg = self.cfg
        dt, pdt, f32 = cfg.dtype, cfg.param_dtype, jnp.float32
        B, T, D = h.shape
        H, G = cfg.ssm_heads_held, cfg.ssm_groups_held
        P, N, taps = cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
        inner, bc = H * P, G * N

        zxbcdt = dense(2 * inner + 2 * bc + H, "in_proj")(h)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:2 * inner + 2 * bc]
        step = zxbcdt[..., 2 * inner + 2 * bc:].astype(f32)
        w = self.param("conv_w", _tap_init(taps), (taps, inner + 2 * bc), pdt).astype(f32)
        b = self.param("conv_b", nn.initializers.zeros, (inner + 2 * bc,), pdt)
        padded = jnp.pad(xbc.astype(f32), ((0, 0), (taps - 1, 0), (0, 0)))
        xbc = nn.silu(sum(w[j] * padded[:, j:j + T] for j in range(taps))
                      + b.astype(f32)).astype(dt)
        a_log = self.param("A_log", _decay_init, (H,), pdt)
        dt_bias = self.param("dt_bias", _step_init, (H,), pdt)
        skip = self.param("D", nn.initializers.ones, (H,), pdt)
        o = ssd(
            xbc[..., :inner].reshape(B, T, H, P),
            jax.nn.softplus(step + dt_bias.astype(f32)),
            -jnp.exp(a_log.astype(f32)),
            xbc[..., inner:inner + bc].reshape(B, T, G, N),
            xbc[..., inner + bc:].reshape(B, T, G, N),
            skip.astype(f32),
            impl="kernel" if self._attention_impl() == "flash" else "xla",
            chunk=cfg.ssm_chunk, published=(cfg.ssm_heads, cfg.ssm_groups),
        )
        o = o.reshape(B, T, inner) * nn.silu(z.astype(f32))
        grouped = o.reshape(B, T, G, inner // G)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
            + (1e-6 if cfg.norm_eps is None else cfg.norm_eps))
        gain = self.param("o_norm", nn.initializers.ones, (inner,), pdt)
        o = grouped.reshape(B, T, inner) * gain.astype(f32)
        return dense(D, "out_proj")(o.astype(dt))

    def _moe_mlp(self, inp):
        """Expert MLP with explicit (E, ...) weight tables — the leading
        expert axis is what the EP executor shards over the ``expert`` mesh
        axis (dim 1 once the layer scan adds its leading axis)."""
        from saturn_tpu.ops.moe import switch_moe

        cfg = self.cfg
        D, E, F = cfg.d_model, cfg.n_experts, cfg.ff_dim
        pdt = cfg.param_dtype
        init = nn.initializers.normal(0.02)
        router_w = self.param("router", init, (D, E), pdt)
        we_in = self.param("we_in", init, (E, D, F), pdt)
        be_in = self.param("be_in", nn.initializers.zeros, (E, F), pdt)
        we_out = self.param("we_out", init, (E, F, D), pdt)
        be_out = self.param("be_out", nn.initializers.zeros, (E, D), pdt)
        y, aux = switch_moe(
            inp,
            router_w.astype(cfg.dtype),
            we_in.astype(cfg.dtype),
            be_in.astype(cfg.dtype),
            we_out.astype(cfg.dtype),
            be_out.astype(cfg.dtype),
            capacity_factor=cfg.capacity_factor,
        )
        self.sow("aux_loss", "moe_load_balance", aux)
        return y


class MixerBlock(Block):
    """A layer that is one mixer and no second half: ``x += Mixer(ln_1(x))``,
    the mixer by ``kind`` (``MIXER_KINDS``): a Mamba-2 layer
    (``_ssm_mixer``), causal softmax attention with ``attn_out``
    (``_softmax_mixer``) or the routed-expert layer (``_routed_mlp``)."""

    @nn.compact
    def __call__(self, x, _unused):
        cfg = self.cfg

        def dense(features, name):
            return nn.Dense(features, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                            use_bias=cfg.use_bias, name=name)

        def make_norm(name):
            return _norm_cls(cfg)(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                                  name=name)

        h = make_norm("ln_1")(x)
        if self.kind == "mamba2":
            out = self._ssm_mixer(h, dense)
        elif self.kind == "latent_moe":
            out = self._routed_mlp(h, dense)
        else:
            out = dense(x.shape[-1], "attn_out")(
                self._softmax_mixer(h, dense, make_norm))
        return x + out, None


def _remat(block_cls, prevent_cse: bool = False, routed: bool = False):
    """``prevent_cse=False`` is for a block that is a scan's whole body: the
    loop boundary already keeps the backward's recomputation apart from the
    forward. Several rematerialised blocks in one body need the barriers.
    A ``routed`` block keeps its row buffer's integer tables (a megabyte;
    ``ops/moe.py::routed_layout``): its backward does not sort again."""
    policy = jax.checkpoint_policies.nothing_saveable
    if routed:
        from saturn_tpu.ops.moe import LAYOUT_NAME

        policy = jax.checkpoint_policies.save_only_these_names(LAYOUT_NAME)
    return nn.remat(block_cls, prevent_cse=prevent_cse, policy=policy)


class PeriodBlock(nn.Module):
    """One period of a stack of several block kinds (``cfg.layer_types``):
    its layers in order, each a :class:`Block` of its kind under the name
    ``l<i>``; the unit the layer scan repeats, with ``Block``'s signature.
    Under remat each layer is rematerialised on its own, so the backward
    holds one layer's activations at a time, as in a one-kind stack."""

    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, _unused):
        ff = "routed" if self.cfg.routed_experts else "dense"
        for i, kind in enumerate(self.cfg.layer_types):
            alone = kind in MIXER_KINDS
            block_cls = MixerBlock if alone else Block
            if self.cfg.remat:
                block_cls = _remat(
                    block_cls, prevent_cse=True,
                    routed=kind == "latent_moe" if alone else ff == "routed")
            x, _ = block_cls(self.cfg, kind=kind, ff=ff, name=f"l{i}")(x, None)
        return x, None


class LeadBlocks(nn.Module):
    """The ``cfg.lead_layers`` layers before the scanned periods, each a
    :class:`Block` of ``cfg.lead_kind``'s mixer with the dense MLP under the name
    ``l<i>``; rematerialised one by one under remat, like a period's."""

    cfg: GPT2Config

    @nn.compact
    def __call__(self, x):
        block_cls = _remat(Block, prevent_cse=True) if self.cfg.remat else Block
        for i in range(self.cfg.lead_layers):
            x, _ = block_cls(self.cfg, kind=self.cfg.lead_kind, name=f"l{i}")(x, None)
        return x


class GPT2(nn.Module):
    """Decoder-only LM with a scanned block stack under param key 'blocks'."""

    cfg: GPT2Config

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.cfg
        B, T = tokens.shape
        wte = self.param(
            "wte",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.d_model),
            cfg.param_dtype,
        )
        if cfg.rotary or not cfg.learned_positions:
            # GPT-J: positions enter through rotary q/k rotation in each
            # block; there is no learned position table (``GPTJ.py:271-338``).
            # (A hybrid stack may have neither: its recurrent layers order
            # the tokens.)
            x = wte[tokens].astype(cfg.dtype)
        else:
            wpe = self.param(
                "wpe",
                nn.initializers.normal(0.01),
                (cfg.seq_len, cfg.d_model),
                cfg.param_dtype,
            )
            if cfg.seq_axis is not None:
                # Local chunk of a sequence-sharded batch: positions offset by
                # the shard index (T here is the per-shard chunk length).
                offset = jax.lax.axis_index(cfg.seq_axis) * T
                pos = jax.lax.dynamic_slice_in_dim(wpe, offset, T, axis=0)
            else:
                pos = wpe[:T]
            x = wte[tokens].astype(cfg.dtype) + pos.astype(cfg.dtype)

        if cfg.lead_layers:
            # the layers before the periods: outside the scan, counted once
            x = LeadBlocks(cfg, name="lead")(x)
        if cfg.layer_types is not None:
            block_cls = PeriodBlock     # remat inside, layer by layer
        else:
            block_cls = _remat(Block) if cfg.remat else Block
        stack = nn.scan(
            block_cls,
            variable_axes={"params": 0, "aux_loss": 0, "moe_stats": 0},
            split_rngs={"params": True},
            length=cfg.n_periods,
            metadata_params={nn.PARTITION_NAME: "layers"},
            unroll=cfg.scan_unroll,
        )
        def final_norm(**kw):
            return _norm_cls(cfg)(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                                  name="ln_f", **kw)

        if cfg.n_passes == 1:
            x, _ = stack(cfg, name="blocks")(x, None)
            x = final_norm()(x)
        else:
            # Looped stack: the same scanned layers (parameters broadcast to
            # every pass), ``n_passes`` times, ``ln_f`` after every pass: one
            # outer scan over the layer scan. The backward sums each layer's
            # gradient over its uses; with remat the stash holds one block
            # input per layer *application*. (The same module applied
            # ``n_passes`` times in a Python loop is the same mathematics;
            # compiled for a v5e at the published widths, depth 8, K = 8, it
            # took 14.8 s against 9.5 s and planned 20.3 GiB against 17.3:
            # PR 28.)
            def one_pass(mdl, h, _):
                h, _ = stack(cfg, name="blocks", parent=mdl)(h, None)
                return final_norm(parent=mdl)(h), None

            x, _ = nn.scan(
                one_pass, variable_broadcast="params",
                split_rngs={"params": False}, length=cfg.n_passes,
            )(self, x, None)

        head = wte if cfg.tie_head else self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.d_model), cfg.param_dtype,
        )
        if return_hidden:
            # final hidden states for the fused head+loss path (ops/ce.py);
            # the caller owns the head matmul
            return x
        # Tied output head by default (reference ties via lm_head over
        # flattened weights, GPTJ.py:340-390); fp32 logits for a stable loss.
        logits = jnp.einsum("btd,vd->btv", x, head.astype(cfg.dtype))
        return logits.astype(jnp.float32)


def build_gpt2(
    name: str = "gpt2-small", pretrained: Any = None, **overrides
) -> ModelSpec:
    """Model factory suitable for ``Task(get_model=...)``.

    Returns a ModelSpec whose params tree is
    ``{'wte', 'blocks': {...leading layer axis...}, 'ln_f'}`` plus ``'wpe'``
    for non-rotary configs (rotary presets have no learned position table).

    ``pretrained``: a local torch/npz state-dict path or an already-loaded
    mapping in HF GPT-2/GPT-J naming — ``init_fn`` then returns the mapped
    weights instead of a random init, which makes every technique a
    *fine-tuning* executor (the reference's canonical workflow,
    ``examples/wikitext103/models/GPTJ.py:502-526``). Shape-validated
    against the preset up front; forwarded by ``Task.get_model`` kwargs like
    any other override.
    """
    cfg = resolve_attention(config_for(name, **overrides))
    module = GPT2(cfg)
    head_key = "wte" if cfg.tie_head else "lm_head"
    has_wpe = cfg.learned_positions and not cfg.rotary
    if pretrained is not None and (
        cfg.n_passes > 1 or cfg.sandwich_norm or not cfg.tie_head
        or cfg.layer_types is not None or cfg.held_heads is not None
        or cfg.routed_experts
    ):
        raise NotImplementedError(
            "pretrained ingest knows the GPT-2 / GPT-J state-dict names only"
        )

    if pretrained is None:
        def init_fn(rng):
            return module.init(rng, cfg.example_inputs())["params"]
    else:
        from saturn_tpu.models import ingest

        if isinstance(pretrained, str):
            # memoized: search builds one spec per candidate config and must
            # not re-read a multi-GB checkpoint each time
            mapped, unused = ingest.cached_params_from_path(pretrained, cfg)
        else:
            mapped, unused = ingest.params_from_state_dict(
                dict(pretrained), cfg
            )
        if unused:
            import logging

            logging.getLogger("saturn_tpu").info(
                "pretrained ingest: %d unused tensors (%s...)",
                len(unused), ", ".join(unused[:4]))
        ingest.validate_against(
            mapped, jax.eval_shape(
                lambda: module.init(jax.random.PRNGKey(0),
                                    cfg.example_inputs())["params"]
            )
        )

        def init_fn(rng):
            del rng  # deterministic: weights come from the state dict
            return jax.tree_util.tree_map(
                lambda x: jnp.asarray(x, dtype=cfg.param_dtype), mapped
            )

    def apply_fn(params, tokens):
        return module.apply({"params": params}, tokens)

    # Pipeline decomposition: embed / one-block / head as pure functions so
    # the pipeline executor can stage any model exposing these (the analog of
    # the reference's requirement that models be nn.Sequential-flattenable,
    # ``GPTJ.py:502-526``).
    def pipeline_embed(other_params, tokens):
        T = tokens.shape[-1]
        x = other_params["wte"][tokens].astype(cfg.dtype)
        if has_wpe:
            x = x + other_params["wpe"][:T].astype(cfg.dtype)
        if cfg.lead_layers:
            # everything before the scanned stack: a technique that rebuilds
            # the model from these pieces runs the leading layers here
            x = LeadBlocks(cfg).apply({"params": other_params["lead"]}, x)
        return x

    # the unit of the scanned stack: a layer, or a period of several kinds
    unit = PeriodBlock(cfg) if cfg.layer_types is not None else Block(cfg)

    def pipeline_block(layer_params, x):
        y, _ = unit.apply({"params": layer_params}, x, None)
        return y

    def final_norm(other_params, x):
        ln = _norm_cls(cfg)(dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        return ln.apply({"params": other_params["ln_f"]}, x)

    def pipeline_head(other_params, x):
        xn = final_norm(other_params, x)
        logits = jnp.einsum("btd,vd->btv", xn,
                            other_params[head_key].astype(cfg.dtype))
        return logits.astype(jnp.float32)

    def hidden_fn(params, tokens):
        return module.apply({"params": params}, tokens, return_hidden=True)

    def routed_stats(collected):
        """The routed layers' counters of one step, over layers: the pairs
        computed a layer, the fullest held expert's rows and the mean
        (``ops/moe.py::routed_experts``), and whether any layer needed the
        exact second path."""
        per = {name: jnp.concatenate([jnp.reshape(v, (-1,)) for v in jax.tree.leaves(
            {k: c[name] for k, c in sorted(collected.items())})]).astype(jnp.float32)
            for name in ("pairs_held", "rows_max", "second_path")}
        return {"moe_pairs_held": jnp.mean(per["pairs_held"]),
                "moe_rows_max": jnp.max(per["rows_max"]),
                "moe_rows_mean": jnp.mean(per["pairs_held"]) / cfg.experts_held,
                "moe_second_path": jnp.max(per["second_path"])}

    fused_loss_fn = fused_loss_parts_fn = fused_loss_stats_fn = routing_fn = None
    if cfg.routed_experts:
        def routing_fn(params, tokens):
            """The experts every routed layer's router chose, (layers, B * T,
            top_k) int32 in the stack's order: for a comparison of routing."""
            _, mut = module.apply({"params": params}, tokens, return_hidden=True,
                                  mutable=["moe_stats"])
            blocks = mut["moe_stats"]["blocks"]
            return jnp.concatenate(
                [jnp.stack([blocks[k]["chosen"][0][p] for k in sorted(blocks)])
                 for p in range(cfg.n_periods)])

    if cfg.causal and not cfg.moe and cfg.seq_axis is None:
        # Fused head+loss (ops/ce.py): hidden states + the head weights go
        # straight into the Pallas CE kernel — no (B,T,V) logits tensor.
        # Identical objective to pretraining_loss∘apply_fn (next-token CE,
        # mean over B*(T-1) real targets); the op itself falls back to a
        # dense computation off-TPU, so this is always safe to call.
        def _fused(params, tokens, reduction):
            return _fused_of(hidden_fn(params, tokens), params, tokens, reduction)

        def _fused_of(x, params, tokens, reduction):
            from saturn_tpu.ops.ce import fused_linear_cross_entropy, stash_of

            labels = jnp.pad(
                tokens[:, 1:].astype(jnp.int32), ((0, 0), (0, 1)),
                constant_values=-1,
            )
            return fused_linear_cross_entropy(
                x, params[head_key], labels, reduction=reduction,
                stash=stash_of(cfg.ce_mode),
            )

        def fused_loss_fn(params, tokens):
            return _fused(params, tokens, "mean")

        def fused_loss_parts_fn(params, tokens):
            # (loss_sum, valid_count) for sharded callers (the dp shard_map
            # wrapper psums both parts before dividing)
            return _fused(params, tokens, "sum_count")

        if cfg.routed_experts:
            def fused_loss_stats_fn(params, tokens):
                # the same loss with the routed layers' counters of the step
                # beside it (an auxiliary output: no gradient, no sync)
                x, mut = module.apply({"params": params}, tokens,
                                      return_hidden=True, mutable=["moe_stats"])
                stats = routed_stats(mut["moe_stats"]["blocks"])
                return _fused_of(x, params, tokens, "mean"), jax.lax.stop_gradient(stats)

    apply_with_aux_fn = None
    if cfg.moe:

        def apply_with_aux_fn(params, tokens):
            logits, mut = module.apply(
                {"params": params}, tokens, mutable=["aux_loss"]
            )
            aux_leaves = jax.tree.leaves(mut.get("aux_loss", {}))
            aux = sum((jnp.sum(a) for a in aux_leaves), jnp.float32(0.0))
            return logits, aux * cfg.moe_aux_weight

    hints = {
        "block_param_key": "blocks",  # where the scanned layer stack lives
        "n_layers": cfg.n_layers,
        # layers of each kind in one scanned unit, where the stack has
        # several kinds (``ModelSpec.stack_kinds``); the scan then runs
        # ``n_layers / sum(kinds)`` periods and ``pipeline["block"]`` is one
        # period
        "stack_kinds": cfg.stack_kinds,
        # layers before the scanned periods (``ModelSpec.stack_lead``); they
        # run inside ``pipeline["embed"]``, with their weights under "lead"
        "stack_lead": cfg.stack_lead,
        "moe": {"n_experts": cfg.n_experts} if cfg.moe else None,
        # a routed layer that computes a held share of its experts
        "routed": {"experts": cfg.routed_experts, "held": cfg.experts_held,
                   "top_k": cfg.top_k, "routing_fn": routing_fn}
        if cfg.routed_experts else None,
        "embed_param_keys": ("wte", "wpe") if has_wpe else ("wte",),
        # factory accepts seq_axis/seq_axis_size; the sharded attention +
        # boundary-label loss assume causal next-token training. A linear
        # layer's state crosses the whole sequence, a convolution reads
        # tokens back across a shard's edge: not sequence-parallel.
        # Nor is a sliding layer's mask or a routed layer (the configuration
        # refuses them a sequence axis).
        "seq_parallel": cfg.causal and not cfg.routed_experts and not (
            {"linear_attention", "mamba2", "kda", "sliding_attention", "conv"}
            & set(cfg.layer_types or ())),
        "pipeline": {
            "embed": pipeline_embed,
            "block": pipeline_block,
            "head": pipeline_head,
            "act_shape": lambda batch, seqlen: (batch, seqlen, cfg.d_model),
            "act_dtype": cfg.dtype,
            # A looped stack: embed, then ``passes`` times the whole block
            # stack with ``between`` (here ``ln_f``) applied between one
            # pass and the next, then head (which norms the last pass). A
            # technique that rebuilds the model from these pieces either
            # honours both or refuses the model (``ModelSpec.stack_passes``).
            "passes": cfg.n_passes,
            "between": final_norm if cfg.n_passes > 1 else None,
        },
    }
    return ModelSpec(
        init_fn=init_fn,
        apply_fn=apply_fn,
        config=cfg,
        hints=hints,
        apply_with_aux_fn=apply_with_aux_fn,
        fused_loss_fn=fused_loss_fn,
        fused_loss_parts_fn=fused_loss_parts_fn,
        fused_loss_objective="causal-lm" if fused_loss_fn else None,
        hidden_fn=hidden_fn,
        fused_loss_stats_fn=fused_loss_stats_fn,
    )


def build_gptj(name: str = "gptj-6b", **overrides) -> ModelSpec:
    """GPT-J factory (rotary + parallel residual; reference ``GPTJ.py:271-390``)."""
    return build_gpt2(name, **overrides)


def build_llama(name: str = "llama-1b", **overrides) -> ModelSpec:
    """Llama-class factory (RMSNorm + SwiGLU + GQA + rotary) — a family the
    reference zoo never had; every technique works on it because the stack
    is the same scanned-block ModelSpec contract."""
    return build_gpt2(name, **overrides)


def build_olmo_hybrid(name: str = "olmo-hybrid-7b", **overrides) -> ModelSpec:
    """Olmo-Hybrid factory: periods of three gated-delta-rule layers
    (``ops/gdn.py`` behind a 4-tap convolution) and one full-attention layer
    with q/k norms, no position signal, post-norm blocks (x += N(f(x))),
    SwiGLU, no bias, untied ``lm_head``. ``held_heads`` makes the program one
    tensor-parallel rank's share of every mixer. Same ``ModelSpec`` contract
    as :func:`build_gpt2`; the scanned unit, and so ``hints["pipeline"]``'s
    ``block``, is one period."""
    return build_gpt2(name, **overrides)


def build_laguna(name: str = "laguna-xs2", **overrides) -> ModelSpec:
    """Laguna factory: a leading dense layer outside the scan (param key
    ``lead``), then periods of three sliding-window layers and one
    full-attention layer at their own q-head counts over shared k/v heads,
    per-kind rotary (YaRN on the full layers), a gate a head, and a shared
    expert beside top-k routed experts (``ops/moe.py::routed_experts``) of
    which ``held_experts`` are computed here. Same ``ModelSpec`` contract as
    :func:`build_gpt2`: the scanned unit, and ``hints["pipeline"]``'s
    ``block``, is one period; its ``embed`` runs the leading layer."""
    return build_gpt2(name, **overrides)


def build_nemotron_h(name: str = "nemotron3-super", **overrides) -> ModelSpec:
    """Nemotron-H factory: periods of layers that are each one mixer alone
    (``MixerBlock``): Mamba-2 state-space layers (``ops/ssd.py`` behind a
    4-tap convolution, a gated norm a group), routed-expert layers whose
    relu2 experts read a latent projection of the stream, chosen top-k under
    a selection bias beside a shared expert
    (``ops/moe.py::routed_experts``), and causal attention over grouped k/v
    heads with no position signal. ``held_heads`` / ``held_experts`` make the
    program one chip's share of every mixer. Same ``ModelSpec`` contract as
    :func:`build_gpt2`; the scanned unit, and ``hints["pipeline"]``'s
    ``block``, is one period."""
    return build_gpt2(name, **overrides)


def build_ling(name: str = "ling3-flash", **overrides) -> ModelSpec:
    """Ling factory: leading dense layers outside the scan (param key
    ``lead``: KDA + SwiGLU), then periods of five Kimi-delta-attention layers
    (``ops/kda.py`` behind three 4-tap convolutions, a decay a key channel)
    and one latent-attention layer (MLA: ``ops/flash.py`` at 192 score lanes
    and 128 value lanes), every mixer under a gate a head, each before a
    shared expert beside top-k routed experts chosen under a group limit and
    a selection bias (``ops/moe.py::routed_experts``). ``held_heads`` /
    ``held_experts`` make the program one chip's share of every mixer and
    routed layer. A non-zero ``swiglu_limit`` (the published last layers'
    clamp) is refused. Same ``ModelSpec`` contract as :func:`build_gpt2`: the
    scanned unit, and ``hints["pipeline"]``'s ``block``, is one period; its
    ``embed`` runs the leading layers."""
    return build_gpt2(name, **overrides)


def build_smallthinker(name: str = "smallthinker-21b", **overrides) -> ModelSpec:
    """SmallThinker factory: periods of one rotary-less full-attention layer
    and three rotated sliding-window layers over grouped k/v heads, every
    feed-forward the held share of top-k routed ReGLU experts under a softmax
    over the chosen logits, with no shared expert and no dense layer; a
    layer's router reads the block's un-normed input, so its route
    (``ops/moe.py::route``) is made ahead of the mixer and the experts run
    under it after (``experts_under``). Same ``ModelSpec`` contract as
    :func:`build_gpt2`: the scanned unit, and ``hints["pipeline"]``'s
    ``block``, is one period (a route never leaves its block)."""
    return build_gpt2(name, **overrides)


#: the published layers a ``lfm2-8b-a1b`` stack can hold: the two leading
#: dense layers and the four whole periods of four that follow them
LFM2_WHOLE_PERIODS = 18


def build_lfm2(name: str = "lfm2-8b-a1b", **overrides) -> ModelSpec:
    """LFM2 factory: leading dense layers outside the scan (param key
    ``lead``: a doubly gated short convolution before a SwiGLU), then periods
    of one grouped-query attention layer (an RMSNorm a head on q and k before
    the rotation) and three short-convolution layers
    (``Block._short_conv_mixer``: plain XLA ops), each before the held share
    of top-k routed SwiGLU experts chosen by sigmoid scores under a selection
    bias (``ops/moe.py::routed_experts``), no shared expert, the head tied to
    the embedding. A depth past the published model's last whole period of
    four is refused: its last six layers are two periods of three. Same
    ``ModelSpec`` contract as :func:`build_gpt2`: the scanned unit, and
    ``hints["pipeline"]``'s ``block``, is one period; its ``embed`` runs the
    leading layers."""
    depth = overrides.get("n_layers", PRESETS[name]["n_layers"]) if name in PRESETS else 0
    if name == "lfm2-8b-a1b" and depth > LFM2_WHOLE_PERIODS:
        raise ValueError(
            f"n_layers {depth}: the published stack's layers 18..23 are full, "
            "conv, conv, full, conv, conv, two periods of three after four of "
            f"four; a stack here is whole periods of one length, {LFM2_WHOLE_PERIODS} "
            "layers at the most")
    return build_gpt2(name, **overrides)


def build_ouro(name: str = "ouro-2.6b", **overrides) -> ModelSpec:
    """Ouro factory: a looped LM (``n_passes`` runs of one scanned stack on
    shared weights, ``ln_f`` after each), sandwich RMSNorm, SwiGLU, rotary
    base 1e6 on the whole head, no bias, untied ``lm_head``. Same
    ``ModelSpec`` contract as :func:`build_gpt2`; ``hints["pipeline"]``
    carries the pass count and the between-passes function."""
    return build_gpt2(name, **overrides)

