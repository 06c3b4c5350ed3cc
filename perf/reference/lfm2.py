"""The plain reference for LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B, ``lfm2_moe``):
doubly gated short-convolution layers and grouped-query attention layers, each
before a dense or a routed feed-forward, in straightforward ``jax.numpy``,
float32, matmul precision ``highest``.

A Python loop over the layers; the convolution as ``taps`` shifted products;
attention as a masked dense softmax by blocks of query rows
(``laguna.py::_attention``); every held expert over every token under the mask
of the tokens that chose it (no sort, no grouped product, no row buffer); the
loss a log-softmax over the materialised logits of the tied head; AdamW
written out (``perf/reference/gpt.py``'s, optax's defaults). No kernels, no
fused head, no flax, nothing of ``saturn_tpu``. Same module contract as
``gpt.py``: ``arch_from_config``, ``seed_key``, ``program_params``,
``logits_of``, ``train``.

The model (``config.json`` gives sizes, ``layer_types`` and the router's
switches; what it does not say is marked + and listed under ``assumed`` in the
configuration file: the public modelling code, ``transformers``' ``lfm2_moe``,
as remembered). With ``N(x) = x / sqrt(mean(x^2) + eps) * g`` and no bias
anywhere, a block is + ``h = x + Mixer(N1(x)); out = h + FF(N2(h))``; after the
last a final ``N`` and + the head tied to the embedding.

Conv mixer (y = N1(x), ``conv_L_cache`` 3 taps):

    [B | C | u] = y W_in                  2048 -> 3 x 2048, in that order +
    s = B * u
    c_t = sum_j w_j * s_{t - 2 + j}       depthwise, causal, s zero before the
                                          sequence, tap 2 the token itself, no
                                          activation +, no bias
    out = (C * c) W_out

Attention mixer (32 q heads over 8 k/v heads of 64): ``q = y Wq, k = y Wk, v =
y Wv``; + q and k each through an RMSNorm over a head's 64 lanes (one gain of
64 shared by the heads) **before the rotation**; + all 64 lanes rotated at
theta 1e6, half-split pairs (j, j + 32); causal softmax over ``q . k /
sqrt(64)``; ``Wo``.

Feed-forward: the dense SwiGLU ``(silu(u Wg) * (u Wu)) Wd`` (7168) in the
leading layers (l < ``num_dense_layers``), the routed layer after (u = N2(h)):

    s = sigmoid(u Wr)  (float32, all 32);  I = the 4 largest of s + b
                                           (+ b enters the choice only)
    w_e = scale s_e / (sum_{e' in I} s_e' + 1e-6) +
    FF = sum_{e in I, e held} w_e E_e(u)       (SwiGLU 1792 each, no shared one)

**The held share.** ``Arch.held`` experts from ``Arch.first_expert`` on have
tables here (8 of the published 32 in the benchmark's configuration); the
router scores all 32 and keeps its 4 a token; a chosen expert that is not held
adds nothing (it lives on another chip). ``routed_part`` exposes the piece
``tests/test_lfm2.py`` adds the four shares up with.

**How it fits a 16 GB chip at the published widths** (508 M parameters:
weights and two moments 6.1 GB in float32): as ``ling.py``, ``train`` takes the
gradient by halves of a layer, attention goes by blocks of query rows and the
held experts one after another, and no program holds the whole model.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from perf.reference.gpt import _nest, flat, seed_key
from perf.reference.laguna import (_attention, _orthonormal_frame, _plain_mm,
                                   _rms_norm, _say_host_memory, _swiglu, _update,
                                   _xent)
# a stack of leading layers and periods, walked by halves of a layer, is
# Ling's: the same tree (``lead/l<i>``, ``blocks/l<i>`` with an axis of
# periods) read off ``Arch.lead`` / ``period`` / ``n_periods``; ``_halves``
# splits a layer's leaves at Ling's list of feed-forward leaves, which holds
# every one of this model's
from perf.reference.ling import (_halves, _layer_weights, _layers_of, _restack,
                                 _unstack)
from perf.reference.smallthinker import _rotary   # half-split, all lanes, ``rope_theta``

__all__ = ["Arch", "arch_from_config", "seed_key", "seeded_params",
           "program_layout", "program_params", "forward", "loss_fn", "train",
           "logits_of", "routed_part", "routing_of", "conv_mixer",
           "attention_mixer", "held_rows", "FAULTS"]

CONV, FULL = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"
#: the faults the layers can plant for ``perf/tests`` and ``tests``
FAULTS = ("taps_reversed", "conv_silu", "no_b_gate", "no_c_gate",
          "norm_all_lanes", "norm_after_rotation", "bias_on_weights",
          "weights_not_normalised", "period_rotated", "dense_at_expert_width")


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file (under
    the names ``perf/lib/flops_laguna.attn_call`` / ``gmm_call`` and
    ``perf/lib/flops_lfm2.py`` read)."""

    vocab_size: int                  # rows of the (tied) embedding held
    d_model: int
    kinds: Tuple[str, ...]           # the mixer of every layer held
    ffs: Tuple[str, ...]             # "dense" | "sparse", every layer held
    n_heads: int                     # q heads of an attention layer
    n_kv_heads: int
    head_dim: int
    taps: int                        # conv_L_cache
    rope_theta: float
    d_ff: int                        # a leading layer's SwiGLU width
    experts: int                     # the router's outputs
    held: int                        # experts whose tables are here
    first_expert: int
    top_k: int
    d_expert: int
    routed_scale: float
    route_eps: float
    norm_eps: float
    family: str = "lfm2"

    @property
    def n_layers(self) -> int:
        return len(self.kinds)

    @property
    def heads(self) -> Tuple[int, ...]:
        """q heads layer by layer (0 where the mixer has none)."""
        return tuple(self.n_heads if k == FULL else 0 for k in self.kinds)

    @property
    def lead(self) -> int:
        """Layers before the periods: the dense ones at the front."""
        return next((i for i, f in enumerate(self.ffs) if f != DENSE), len(self.ffs))

    @property
    def period(self) -> int:
        rest = self.kinds[self.lead:]
        return next((p for p in range(1, len(rest) + 1)
                     if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p)), 0)

    @property
    def n_periods(self) -> int:
        return (self.n_layers - self.lead) // self.period if self.period else 0


def arch_from_config(cfg: Dict[str, Any], seq_len: int) -> Arch:
    """``cfg`` is a file of ``perf/configs``; the model has no position table,
    so ``seq_len`` sizes nothing. The reduced keys hold what is held here
    (``layer_types``: the held layers' mixers in order; ``num_dense_layers``
    of them lead), ``published`` what the source has."""
    del seq_len
    run = cfg["run"]
    published = cfg.get("published", {})
    kinds = tuple(cfg["layer_types"])
    dense = int(cfg["num_dense_layers"])
    if len(kinds) != int(cfg["num_hidden_layers"]) or set(kinds) - {CONV, FULL}:
        raise ValueError(f"layer_types {kinds} are not the "
                         f"{cfg['num_hidden_layers']} conv / full_attention layers held")
    if cfg.get("conv_bias") or not cfg.get("norm_topk_prob") or not cfg.get("use_expert_bias"):
        raise ValueError("the reference knows a bias-free convolution and a router "
                         "whose chosen scores are normalised under a selection bias")
    return Arch(
        vocab_size=int(run["vocab_size"]),
        d_model=int(cfg["hidden_size"]),
        kinds=kinds,
        ffs=tuple(DENSE if l < dense else SPARSE for l in range(len(kinds))),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]),
        taps=int(cfg["conv_L_cache"]),
        rope_theta=float(cfg["rope_theta"]),
        d_ff=int(cfg["intermediate_size"]),
        experts=int(published.get("num_experts", cfg["num_experts"])),
        held=int(cfg["num_experts"]),
        first_expert=0,
        top_k=int(cfg["num_experts_per_tok"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        route_eps=float(run.get("overrides", {}).get("route_eps", 1e-6)),
        norm_eps=float(cfg["norm_eps"]),
    )


# ------------------------------------------------------------------ weights
#: The seeded values that are not plain normal draws (the benchmark's to
#: choose, listed under ``assumed``). **Routing is discrete**
#: (``perf/reference/laguna.py::AFFINITY`` has the arithmetic), so a token's
#: routing follows its identity, by Ling's scheme for a sigmoid router behind
#: a norm: the routers' columns, all routed layers' side by side (4 x 32 of
#: the stream's 2048 lanes), are one orthonormal signed-Hadamard frame at
#: length ``ROUTER_COLUMN``; every token id is given, layer by layer,
#: ``top_k`` of the experts by a draw from the weight seed, and its embedding
#: row is a unit-RMS normal row plus ``AFFINITY`` times the sum of those
#: experts' unit columns. A row's RMS is sqrt(1 + 256 x 16 / 2048) = 1.73; the
#: router reads ``N2(h)``, so a chosen logit stands at 0.25 x 16 / 1.73 = 2.3
#: (sigmoid 0.91) over a background of deviation 0.25 / 1.73 = 0.14 (sigmoid
#: 0.5 +- 0.04). ``BIAS``: the selection biases are normal of deviation 0.02,
#: not zero, so that the choice (by s + b) and the weights (from s alone)
#: differ and a bias that leaks into the weights moves them by a part in
#: fifty.
#: ``OUT``: the matrices that write to the stream (``attn_out``, ``mlp_out``,
#: ``we_down``) are normal with deviation 0.02 / sqrt(2 x 5) (a residual
#: branch's output over the root of twice the depth, GPT-2's rule, at the five
#: layers that write to this stream), so that what the layers add (rows of RMS
#: 0.1-0.25 a layer beside the embedding's 1.73) does not dilute the lean the
#: routers read (``ling.py::OUT`` has the first readings without such a rule)
#: and is still large enough for the layers' order to show in a gradient.
#: ``HEAD_GAIN``: **the head is tied to the embedding**, whose rows are
#: unit-RMS for the stream's sake (an untied head's are 0.02): under a final
#: norm of unit gain the logits would have deviation sqrt(2048) x 1.7 = 78 and
#: a token's own logit 2048 x 3 / 1.73 = 3500. The final norm's gain is
#: ``HEAD_GAIN`` x (1 + 0.02 z): the logits' deviation about 0.2, a token's own
#: logit about 9 of a log-sum near log(16384) = 9.7: neither flat nor
#: saturated (a saturated softmax's loss carries bf16's whole rounding of its
#: largest logit, 0.3 %, against a limit of 0.2 %).
AFFINITY = 16.0
ROUTER_COLUMN = 0.25
BIAS = 0.02
OUT = 0.02 / math.sqrt(2.0 * 5.0)
HEAD_GAIN = 0.0025


def _matrix(z):
    return 0.02 * z


def _gain(z):
    return 1.0 + 0.02 * z


def _out(z):
    return OUT * z


def _mixer_shapes(a: Arch, at: str, lead: Tuple[int, ...], kind: str):
    D, hd = a.d_model, a.head_dim
    out = {at + "ln_1/scale": (lead + (D,), _gain),
           at + "ln_2/scale": (lead + (D,), _gain)}
    if kind == CONV:
        out.update({at + "conv_in/kernel": (lead + (D, 3 * D), _matrix),
                    at + "conv_w": (lead + (a.taps, D), None),
                    at + "attn_out/kernel": (lead + (D, D), _out)})
    else:
        out.update({at + "q/kernel": (lead + (D, a.n_heads * hd), _matrix),
                    at + "k/kernel": (lead + (D, a.n_kv_heads * hd), _matrix),
                    at + "v/kernel": (lead + (D, a.n_kv_heads * hd), _matrix),
                    at + "q_norm": (lead + (hd,), _gain),
                    at + "k_norm": (lead + (hd,), _gain),
                    at + "attn_out/kernel": (lead + (a.n_heads * hd, D), _out)})
    return out


def _shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], Optional[Callable]]]:
    """leaf path -> (shape, value of a standard normal draw; None: a uniform
    draw, a convolution's taps). Paths are the program's (``lead/l<i>/...``;
    ``blocks/l<i>/...`` with a leading axis of periods), except that a conv
    layer's input projection is one leaf and an attention layer's q, k, v
    three."""
    P, D = a.n_periods, a.d_model
    out: Dict[str, Tuple[Tuple[int, ...], Optional[Callable]]] = {
        "wte": ((a.vocab_size, D), lambda z: z),
        "ln_f/scale": ((D,), lambda z: HEAD_GAIN * _gain(z)),
    }
    for i in range(a.lead):
        at = f"lead/l{i}/"
        out.update(_mixer_shapes(a, at, (), a.kinds[i]))
        out.update({at + "mlp_gate/kernel": ((D, a.d_ff), _matrix),
                    at + "mlp_in/kernel": ((D, a.d_ff), _matrix),
                    at + "mlp_out/kernel": ((a.d_ff, D), _out)})
    for i in range(a.period):
        at, F = f"blocks/l{i}/", a.d_expert
        out.update(_mixer_shapes(a, at, (P,), a.kinds[a.lead + i]))
        out.update({at + "router": ((P, D, a.experts), _matrix),
                    at + "router_bias": ((P, a.experts), lambda z: BIAS * z),
                    at + "we_gate": ((P, a.held, D, F), _matrix),
                    at + "we_up": ((P, a.held, D, F), _matrix),
                    at + "we_down": ((P, a.held, F, D), _out)})
    return out


def seeded_params(a: Arch, key) -> Dict[str, Any]:
    """Float32 weights from ``key`` (``seed_key(seed)``), every leaf random
    (the norms' gains and the taps too); the routers' columns an orthonormal
    frame, the embedding's rows leaning towards their experts' columns (the
    note above). Traceable, and free of matrix products: what is seeded must
    not depend on the precision a program is traced at."""
    out = {}
    for i, (path, (shape, value)) in enumerate(sorted(_shapes(a).items())):
        k = jax.random.fold_in(key, i)
        if value is None:       # a convolution's taps: +-1 / sqrt(taps)
            out[path] = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) / math.sqrt(a.taps)
        else:
            out[path] = value(jax.random.normal(k, shape, jnp.float32))
    routers = [(i, p) for i in range(a.period) for p in range(a.n_periods)]
    if routers:
        lanes = 1 << (a.d_model.bit_length() - 1)
        frame = jnp.pad(_orthonormal_frame(lanes, len(routers) * a.experts,
                                           jax.random.fold_in(key, 999)),
                        ((0, a.d_model - lanes), (0, 0)))                  # (D, n E)
        lean = jnp.zeros_like(out["wte"])
        for n, (i, p) in enumerate(routers):
            unit = frame[:, n * a.experts:(n + 1) * a.experts]             # (D, E)
            out[f"blocks/l{i}/router"] = out[f"blocks/l{i}/router"].at[p].set(
                ROUTER_COLUMN * unit)
            draw = jax.random.uniform(
                jax.random.fold_in(key, 1000 + p * a.period + i),
                (a.vocab_size, a.experts))
            _, own = jax.lax.top_k(draw, a.top_k)                          # (V, k)
            for slot in range(a.top_k):   # sums of rows, in a fixed order: no product
                lean = lean + unit.T[own[:, slot]]
        out["wte"] = out["wte"] + AFFINITY * lean
    return _nest(out)


def program_layout(a: Arch, tree: Dict[str, Any], xp=jnp) -> Dict[str, Any]:
    """A tree of the parameters' structure (weights, gradients, Adam moments)
    in the layout ``saturn_tpu/models/gpt2.py`` trains: a conv layer's input
    projection as its three blocks (``conv_b`` / ``conv_c`` / ``conv_x``, a
    kernel each: the tensor-parallel rule shards each one's channels), an
    attention layer's q, k, v side by side in one ``qkv`` kernel (both sides
    rotate half-split pairs: no lane moves). Layout only."""
    out = dict(tree)
    for where, name, kind in _layers_of(a):
        out[where] = dict(out[where])
        layer = dict(out[where][name])
        if kind == CONV:
            w = layer.pop("conv_in")["kernel"]
            D = w.shape[-2]
            for n, which in enumerate("bcx"):
                layer["conv_" + which] = {"kernel": w[..., n * D:(n + 1) * D]}
        else:
            q, k, v = (layer.pop(n)["kernel"] for n in ("q", "k", "v"))
            layer["qkv"] = {"kernel": xp.concatenate([q, k, v], axis=-1)}
        out[where][name] = layer
    return out


def program_params(a: Arch, key) -> Dict[str, Any]:
    """The seeded weights as the program is handed them. Traceable."""
    return program_layout(a, seeded_params(a, key))


# ------------------------------------------------------------------ forward
def conv_mixer(a: Arch, mm: Callable, p, y, fault: Optional[str] = None):
    """The doubly gated short convolution of normed rows ``y`` (B, T, D)."""
    T, D = y.shape[1], a.d_model
    bcu = mm(y, p["conv_in"]["kernel"])
    gate_in, gate_out, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    s = u if fault == "no_b_gate" else gate_in * u
    w = p["conv_w"][::-1] if fault == "taps_reversed" else p["conv_w"]
    padded = jnp.pad(s, ((0, 0), (a.taps - 1, 0), (0, 0)))
    c = sum(w[j] * padded[:, j:j + T] for j in range(a.taps))     # tap taps-1: the token
    if fault == "conv_silu":
        c = jax.nn.silu(c)
    return mm(c if fault == "no_c_gate" else gate_out * c, p["attn_out"]["kernel"])


def attention_mixer(a: Arch, mm: Callable, p, y, fault: Optional[str] = None):
    """Grouped-query attention of normed rows ``y``: an RMSNorm a head on q
    and k (one gain of ``head_dim`` shared by the heads), then the rotation."""
    B, T, _ = y.shape
    H, kv, hd, eps = a.n_heads, a.n_kv_heads, a.head_dim, a.norm_eps
    q = mm(y, p["q"]["kernel"])
    k = mm(y, p["k"]["kernel"])
    v = mm(y, p["v"]["kernel"]).reshape(B, T, kv, hd)
    if fault == "norm_all_lanes":    # one statistic over all the heads' lanes
        q = _rms_norm(q, jnp.tile(p["q_norm"], H), eps).reshape(B, T, H, hd)
        k = _rms_norm(k, jnp.tile(p["k_norm"], kv), eps).reshape(B, T, kv, hd)
        q, k = _rotary(a, q), _rotary(a, k)
    elif fault == "norm_after_rotation":
        q = _rms_norm(_rotary(a, q.reshape(B, T, H, hd)), p["q_norm"], eps)
        k = _rms_norm(_rotary(a, k.reshape(B, T, kv, hd)), p["k_norm"], eps)
    else:
        q = _rotary(a, _rms_norm(q.reshape(B, T, H, hd), p["q_norm"], eps))
        k = _rotary(a, _rms_norm(k.reshape(B, T, kv, hd), p["k_norm"], eps))
    o = _attention(q, k, v, None)
    return mm(o.reshape(B, T, H * hd), p["attn_out"]["kernel"])


def routing_of(a: Arch, p, u, fault: Optional[str] = None):
    """(the experts chosen (.., k), their weights (.., k)) of the normed rows
    ``u``: sigmoid scores over all the experts in float32 (never through the
    control's lower-precision product); the choice by scores + selection
    bias; the weights from the scores alone, over their sum + ``route_eps``,
    times the scaling factor."""
    scores = jax.nn.sigmoid(u @ p["router"])
    biased = scores + p["router_bias"]
    _, chosen = jax.lax.top_k(biased, a.top_k)
    top = jnp.take_along_axis(biased if fault == "bias_on_weights" else scores,
                              chosen, axis=-1)
    if fault == "weights_not_normalised":
        return chosen, a.routed_scale * top
    return chosen, a.routed_scale * top / (jnp.sum(top, axis=-1, keepdims=True) + a.route_eps)


def routed_part(a: Arch, mm: Callable, p, u, first_expert: Optional[int] = None,
                fault: Optional[str] = None):
    """The held experts' part of the routed layer's output for normed rows
    ``u`` (B, T, D): each held expert over every token, times the weight of
    the tokens that chose it (0 for the rest); the experts one after another
    (a ``lax.scan`` over the tables' expert axis, each rematerialised in the
    backward). ``first_expert`` overrides the architecture's share (a test
    adds all the shares up)."""
    first = a.first_expert if first_expert is None else first_expert
    chosen, weights = routing_of(a, p, u, fault)
    mine = chosen[..., None] == first + jnp.arange(a.held)            # (B, T, k, held)
    masks = jnp.moveaxis(jnp.sum(jnp.where(mine, weights[..., None], 0.0), axis=-2), -1, 0)

    @jax.checkpoint
    def expert(u, gate, up, down, m):
        return _swiglu(mm, u, gate, up, down) * m[..., None]

    def one_more(out, xs):
        return out + expert(u, *xs), None

    out, _ = jax.lax.scan(one_more, jnp.zeros_like(u),
                          (p["we_gate"], p["we_up"], p["we_down"], masks))
    return out


def _mixer_half(a: Arch, mm: Callable, kind: str, p, x, fault: Optional[str] = None):
    """``h = x + Mixer(N1(x))``."""
    y = _rms_norm(x, p["ln_1"]["scale"], a.norm_eps)
    return x + (conv_mixer if kind == CONV else attention_mixer)(a, mm, p, y, fault)


def _ff_half(a: Arch, mm: Callable, ff: str, p, h, fault: Optional[str] = None,
             routing: Optional[list] = None):
    """``out = h + FF(N2(h))``. ``routing``, a list, gains a routed layer's
    chosen experts."""
    u = _rms_norm(h, p["ln_2"]["scale"], a.norm_eps)
    if ff == DENSE:
        gate, up, down = (p[n]["kernel"] for n in ("mlp_gate", "mlp_in", "mlp_out"))
        if fault == "dense_at_expert_width":
            gate, up, down = gate[:, :a.d_expert], up[:, :a.d_expert], down[:a.d_expert]
        return h + _swiglu(mm, u, gate, up, down)
    if routing is not None:
        routing.append(routing_of(a, p, u)[0])
    return h + routed_part(a, mm, p, u, fault=fault)


def _layer(a: Arch, mm: Callable, kind: str, ff: str, p, x,
           fault: Optional[str] = None, routing: Optional[list] = None):
    """A block: its mixer's half, then its feed-forward's."""
    return _ff_half(a, mm, ff, p, _mixer_half(a, mm, kind, p, x, fault), fault, routing)


def _head(a: Arch, mm: Callable, top, x):
    """``top``: the leaves outside the stack (``ln_f``, ``wte``: the head is
    the embedding)."""
    return mm(_rms_norm(x, top["ln_f"]["scale"], a.norm_eps), top["wte"].T)


def _sig(a: Arch, n: int):
    return a.kinds[n], a.ffs[n]


def _order(a: Arch, fault: Optional[str] = None):
    """The layers in the order they run (``forward``, ``train`` and
    ``logits_of`` all walk it): ``period_rotated`` runs each period one place
    on (conv, conv, conv, full), a planted fault."""
    order = list(range(a.n_layers))
    if fault == "period_rotated" and a.period:
        for start in range(a.lead, a.n_layers, a.period):
            order[start:start + a.period] = order[start + 1:start + a.period] + [start]
    return order


def forward(a: Arch, params, tokens, mm: Optional[Callable] = None,
            fault: Optional[str] = None, routing: Optional[list] = None):
    """(B, T) int tokens -> (B, T, V) float32 logits. ``mm(x, w)`` is the
    matrix product of activations (..., K) and weights (K, N); the control of
    ``perf/lib/refcheck.py`` passes a lower-precision one and changes nothing
    else. ``routing``, a list, gains every routed layer's chosen experts."""
    mm = mm or _plain_mm
    x = params["wte"][tokens]
    for n in _order(a, fault=fault):
        layer = functools.partial(_layer, a, mm, *_sig(a, n), fault=fault, routing=routing)
        if routing is None:      # (a list cannot cross a checkpoint)
            layer = jax.checkpoint(layer)
        x = layer(_layer_weights(a, params, n), x)
    return _head(a, mm, params, x)


def loss_fn(a: Arch, params, tokens, mm: Optional[Callable] = None,
            fault: Optional[str] = None):
    """Next-token cross entropy, mean over the B x (T-1) targets."""
    return _xent(forward(a, params, tokens, mm, fault), tokens)


# ----------------------------------------------------------------- training
@functools.lru_cache(maxsize=None)
def _jitted(a: Arch, mm: Optional[Callable]) -> Dict[str, Callable]:
    """The jitted pieces of ``train`` and ``logits_of``, made once for an
    architecture and a matmul: one forward and one backward program for each
    kind of **half a layer** (the conv mixer, the attention mixer, the dense
    feed-forward, the routed one; ``ling.py``'s way: a whole layer's program
    would hold the conv mixer twice), none for the whole model. A
    feed-forward's forward also returns the experts its router chose (an
    empty array where it has none). The head's programs take the embedding:
    its gradient there and the lookup's are summed by ``_step``."""
    mul = mm or _plain_mm

    def mixer(kind, p, x):
        return _mixer_half(a, mul, kind, p, x)

    def mixer_back(kind, p, x, dh):
        _, vjp = jax.vjp(functools.partial(_mixer_half, a, mul, kind), p, x)
        return vjp(dh)                                  # (dp, dx)

    def ff(which, p, h):
        routing: list = []
        out = _ff_half(a, mul, which, p, h, routing=routing)
        return out, routing[0] if routing else jnp.zeros((0,), jnp.int32)

    def ff_back(which, p, h, dy):
        _, vjp = jax.vjp(functools.partial(_ff_half, a, mul, which), p, h)
        return vjp(dy)                                  # (dp, dh)

    def head_back(top, x, tokens):
        loss, (dtop, dx) = jax.value_and_grad(
            lambda t, h: _xent(_head(a, mul, t, h), tokens), argnums=(0, 1))(top, x)
        return loss, dtop, dx

    out = {"params": jax.jit(lambda k: seeded_params(a, k)),
           "layout": jax.jit(functools.partial(program_layout, a)),
           "embed": jax.jit(lambda wte, tokens: wte[tokens]),
           "embed_back": jax.jit(lambda dwte, tokens, dx: dwte.at[tokens].add(dx)),
           "head": jax.jit(functools.partial(_head, a, mul)),
           "head_back": jax.jit(head_back)}
    for kind in set(a.kinds):
        out["mixer", kind] = jax.jit(functools.partial(mixer, kind))
        out["mixer_back", kind] = jax.jit(functools.partial(mixer_back, kind))
    for which in set(a.ffs):
        out["ff", which] = jax.jit(functools.partial(ff, which))
        out["ff_back", which] = jax.jit(functools.partial(ff_back, which))
    return out


def _layer_forward(a: Arch, fns, n: int, p, x):
    """Layer ``n`` by its two programs -> (its mixer's output, its output,
    the experts its router chose)."""
    kind, which = _sig(a, n)
    mine, theirs = _halves(p)
    h = fns["mixer", kind](mine, x)
    out, chosen = fns["ff", which](theirs, h)
    return h, out, chosen


def _step(a: Arch, fns, update, state, tokens, routing: Optional[list] = None):
    """One AdamW step, the gradient by halves of a layer (``ling.py``'s).
    ``state``: ``{"p", "m", "v"}``, each ``{"top", "layers"}``, and ``"t"``.
    The tied embedding's gradient is the head's part plus the lookup's.
    ``routing``, a list, gains every routed layer's chosen experts."""
    p, m, v, t = state["p"], state["m"], state["v"], state["t"]

    def put(where, key, grads):
        new = update(p[where][key], grads, m[where][key], v[where][key], t)
        for tree, leaf in zip((p, m, v), new):
            tree[where][key] = leaf

    x = fns["embed"](p["top"]["wte"], tokens)
    inputs, order = [], _order(a)
    for n in order:
        h, out, chosen = _layer_forward(a, fns, n, p["layers"][n], x)
        inputs.append((x, h))
        x = out
        if routing is not None and chosen.size:
            routing.append(chosen)
    head = {k: p["top"][k] for k in ("ln_f", "wte")}
    loss, dhead, dx = fns["head_back"](head, x, tokens)
    put("top", "ln_f", dhead["ln_f"])
    dwte = dhead["wte"]
    del dhead, x
    for n in reversed(order):
        kind, which = _sig(a, n)
        mine, theirs = _halves(p["layers"][n])
        x, h = inputs.pop()
        dff, dh = fns["ff_back", which](theirs, h, dx)
        dmixer, dx = fns["mixer_back", kind](mine, x, dh)
        put("layers", n, {**dmixer, **dff})
        del dff, dmixer, dh, x, h
    put("top", "wte", fns["embed_back"](dwte, tokens, dx))
    state["t"] = t + 1
    return loss


def train(a: Arch, seed: int, batches, lr: float,
          mm: Optional[Callable] = None, keep_state: bool = False,
          routing: Optional[list] = None):
    """``len(batches)`` AdamW steps from the seeded weights. Returns (the loss
    before each step, as floats; the final state). The state is None unless
    ``keep_state``; then it is host arrays by leaf path, in the program's
    layout: ``{"m": first moments, "params": weights, "moved": ||weights -
    seeded weights|| per leaf}``: what a checkpoint of the program is held
    against. ``routing``, a list, gains a row a step: what ``held_rows`` reads
    of every routed layer's choice on that step's tokens, before the step's
    update."""
    import numpy as np

    fns, update = _jitted(a, mm), _update(float(lr))
    with jax.default_matmul_precision("highest"):
        key = seed_key(seed)
        state = {"p": _unstack(a, fns["params"](key)), "t": jnp.zeros((), jnp.int32)}
        for moment in ("m", "v"):
            state[moment] = jax.tree_util.tree_map(jnp.zeros_like, state["p"])
        losses = []
        for tokens in batches:
            chosen = None if routing is None else []
            losses.append(_step(a, fns, update, state, jnp.asarray(tokens), chosen))
            if routing is not None:
                routing.append(held_rows(a, chosen))
        out = [float(x) for x in losses]
        kept = None
        if keep_state:
            del state["v"]  # the second moments are not compared: free them first
            kept = {}
            for name, tree in (("m", "m"), ("params", "p")):   # one tree on the host at a time
                host = jax.tree_util.tree_map(np.asarray, state.pop(tree))
                kept[name] = flat(program_layout(a, _restack(a, host, np), xp=np))
                del host
            seeded = flat(jax.tree_util.tree_map(
                np.asarray, fns["layout"](fns["params"](key))))
            kept["moved"] = {
                k: float(np.sqrt(np.sum(np.square(w - seeded[k], dtype=np.float64))))
                for k, w in kept["params"].items()}
    del state
    _say_host_memory(f"{len(out)} training steps" + (" and the state's copy" if kept else ""))
    return out, kept


def held_rows(a: Arch, mine) -> Dict[str, Any]:
    """Of the experts every routed layer chose on one batch (``mine``, a
    layer each, in the order run): each held expert's rows a layer
    (``counts``), the batch's tokens, and the pairs a layer that an even
    routing would hold -- what a row buffer must take."""
    import numpy as np

    mine = np.stack([np.asarray(m).reshape(-1, a.top_k) for m in mine])
    held = (mine >= a.first_expert) & (mine < a.first_expert + a.held)
    return {"counts": [[int(c) for c in np.bincount(layer[h] - a.first_expert,
                                                    minlength=a.held)]
                       for layer, h in zip(mine, held)],
            "tokens": int(mine.shape[1]),
            "mean": mine.shape[1] * a.top_k * a.held / a.experts}


def _held_pairs(a: Arch, mine) -> None:
    """Print ``held_rows`` of the check's tokens."""
    got = held_rows(a, mine)
    pairs = [sum(layer) for layer in got["counts"]]
    print("perf: routing: the reference holds "
          f"{sum(pairs) / len(pairs) / got['tokens']:.3f} pairs a token; "
          "held pairs by layer " + ", ".join(str(x) for x in pairs)
          + f" (mean {got['mean']:.0f}), the fullest held expert's rows "
          f"{max(max(layer) for layer in got['counts'])}", flush=True)


def logits_of(a: Arch, seed: int, tokens, mm: Optional[Callable] = None):
    """Float32 logits of the seeded weights on ``tokens``. The reference's own
    call (no ``mm``) also prints the held experts' pairs a layer."""
    if mm is None:
        # what the search's compiles left in the allocator goes back first
        _say_host_memory("the program's search and window")
    fns = _jitted(a, mm)
    with jax.default_matmul_precision("highest"):
        params = _unstack(a, fns["params"](seed_key(seed)))
        x = fns["embed"](params["top"]["wte"], jnp.asarray(tokens))
        routing = []
        for n in _order(a):
            _, x, chosen = _layer_forward(a, fns, n, params["layers"][n], x)
            if chosen.size:
                routing.append(chosen)
        logits = fns["head"]({k: params["top"][k] for k in ("ln_f", "wte")}, x)
        del params, x
    if mm is None:      # the program at its own precision, outside "highest"
        _held_pairs(a, routing)
        _say_host_memory("the logits")
    return logits
