"""The plain reference for Olmo-Hybrid (allenai/Olmo-Hybrid-7B): gated-delta-
rule linear-attention layers between full-attention layers, in straightforward
``jax.numpy``, float32, matmul precision ``highest``.

Python loops over the layers; the delta rule **token by token** (a
``lax.scan`` over the tokens, nothing of ``saturn_tpu/ops/gdn.py``, no chunked
form, no WY transform); attention as two einsums and a softmax; the loss as a
log-softmax over the materialised logits; AdamW written out
(``perf/reference/gpt.py``'s, optax's defaults). No kernels, no fused head, no
flax. Same module contract as ``gpt.py``: ``arch_from_config``, ``seed_key``,
``program_params``, ``logits_of``, ``train``.

The published model (``config.json`` gives sizes and ``layer_types``; what it
does not say is listed under ``assumed`` in the configuration file). With
``N(x) = x / sqrt(mean(x^2) + eps) * g`` and no bias anywhere, every layer is

    h   = x + N1(mixer(x))
    out = h + N2((silu(h Wg) * (h Wu)) Wd)

(OLMo's reordered norm: on each branch's output, none before it). The mixer
of a ``full_attention`` layer, over the held heads of 128 lanes:

    q, k, v = x Wq, x Wk, x Wv;  q = Nq(q), k = Nk(k)   (over all held lanes)
    o = softmax_causal(q k^T / sqrt(128)) v;  mixer = o Wo

with no rotary and no position table (``rope_parameters.rope_theta`` is null
in the source). The mixer of a ``linear_attention`` layer, per held head with
keys of 96 and values of 192 lanes, ``conv`` a depthwise causal convolution
of 4 taps (tap j multiplies the token 3 - j back):

    q~, k~, v~ = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
    q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(96);   k = k~ / sqrt(|k~|^2 + 1e-6)
    beta_t = 2 sigmoid(x_t Wb)                  (linear_allow_neg_eigval)
    alpha_t = exp(-exp(A_log) softplus(x_t Wa + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,  S_0 = 0
    o_t = S_t q_t                               (S: 192 x 96 a head)
    mixer = [No(o_t) * silu(x_t Wgate)] Wo      (No: RMSNorm over a head's 192)

then the final norm and an untied head: ``logits = Nf(x_L) W_head^T``.

**The held share.** ``Arch.n_heads`` is the number of heads *held* (15 of the
published 30 in the benchmark's configuration): every mixer computes the held
heads' part of its output, ``Wo`` over the held heads' rows only, and ``Nq`` /
``Nk`` take their statistic over the held lanes. ``mixer_parts`` exposes the
pieces ``tests/test_olmo_hybrid.py`` adds two halves up with.

**How it fits a 16 GB chip at the published widths** (766 M parameters:
weights and two moments are 9.2 GB in float32, a whole gradient 3.1 GB more).
Each changes when a value is computed, never which:
  1. ``train`` takes the gradient **layer by layer**: a forward pass that
     keeps each layer's input, then from the loss backwards one layer at a
     time -- the layer's forward recomputed under ``jax.vjp``, its gradient
     put through AdamW at once and dropped. The chip never holds more than
     one layer's gradient (0.67 GB). The moments stay on the chip.
     ``perf/tests/test_reference_olmo_hybrid.py`` holds this to ``jax.grad``
     of ``loss_fn`` over the whole model.
  2. attention by blocks of ``ATTN_Q_BLOCK`` query rows, each under its own
     ``jax.checkpoint`` (a row's softmax is over its whole causal row).
  3. the token scan of the delta rule runs in pieces of ``SCAN_PIECE`` tokens,
     each under ``jax.checkpoint``: the backward keeps the state at the
     pieces' starts, not after every token (8192 states of 15 x 192 x 96
     float32 are 9 GB).
The loss is not blocked: 1 x 8192 x 12544 float32 logits are 0.4 GB.

Layout, the one departure: the package fuses a full layer's q, k, v into one
``qkv`` kernel; ``program_layout`` concatenates the reference's three.

What the shared readers see: ``n_heads`` (held) and ``head_dim`` for
``flops.flash_call``, ``d_model`` and ``vocab_size`` for ``ce_call``. The
required operations of this model are ``perf/lib/flops_hybrid.py``'s; the GPT
count behind ``d_ff`` / ``n_layers`` (``metrics/mfu.py``) would count four
attention layers of width 3840 where one of 1920 runs, and is not used for it.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from perf.reference.gpt import _nest, adamw_step, flat, seed_key

__all__ = ["Arch", "arch_from_config", "seed_key", "seeded_params",
           "program_layout", "program_params", "forward", "loss_fn", "train",
           "logits_of", "mixer_parts"]

ATTN_Q_BLOCK = 1024   # block 2
SCAN_PIECE = 64       # block 3
L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""

    vocab_size: int                 # rows of the embedding and the head held
    d_model: int
    kinds: Tuple[str, ...]          # the kind of every layer held, in order
    period: int                     # layers in one period of ``layer_types``
    n_heads: int                    # heads held (full and linear layers alike)
    head_dim: int                   # a full layer's head
    key_dim: int                    # a linear layer's key head
    value_dim: int                  # a linear layer's value head
    conv_taps: int
    neg_eigval: bool
    d_inner: int                    # SwiGLU's inner width
    norm_eps: float
    family: str = "olmo_hybrid"

    @property
    def n_layers(self) -> int:
        return len(self.kinds)

    @property
    def n_periods(self) -> int:
        return len(self.kinds) // self.period

    @property
    def d_ff(self) -> int:
        """The two-matrix MLP width with SwiGLU's three matrices' multiplies
        (what a GPT count would be handed; see the module docstring)."""
        return 3 * self.d_inner // 2


def arch_from_config(cfg: Dict[str, Any], seq_len: int) -> Arch:
    """``cfg`` is a file of ``perf/configs``; the model has no position table,
    so ``seq_len`` sizes nothing. The head counts are the held ones."""
    del seq_len
    heads = int(cfg["num_attention_heads"])
    same = ("num_key_value_heads", "linear_num_key_heads", "linear_num_value_heads")
    if any(int(cfg[k]) != heads for k in same):
        raise ValueError("the reference holds one share of the heads in every mixer")
    published = int(cfg.get("published", {}).get("num_attention_heads", heads))
    types = list(cfg["layer_types"])
    period = next(p for p in range(1, len(types) + 1)
                  if len(types) % p == 0 and types == types[:p] * (len(types) // p))
    held = int(cfg["num_hidden_layers"])
    if held % period:
        raise ValueError(f"{held} layers are not whole periods of {period}")
    return Arch(
        vocab_size=int(cfg["run"]["vocab_size"]),
        d_model=int(cfg["hidden_size"]),
        kinds=tuple(types[:held]),
        period=period,
        n_heads=heads,
        head_dim=int(cfg["hidden_size"]) // published,
        key_dim=int(cfg["linear_key_head_dim"]),
        value_dim=int(cfg["linear_value_head_dim"]),
        conv_taps=int(cfg["linear_conv_kernel_dim"]),
        neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        d_inner=int(cfg["intermediate_size"]),
        norm_eps=float(cfg["rms_norm_eps"]),
    )


# ------------------------------------------------------------------ weights
#: Where the seeded gains of a block's two output norms (N1, N2) are centred,
#: as ``perf/reference/ouro.py``'s ``POST_NORM_GAIN`` and for its reason: every
#: branch is normed to its gain and added to a stream of unit RMS (the
#: embedding's rows), so at gain 1 a branch is as large as the stream it joins
#: and a rounding difference is carried on at full weight -- and here the next
#: mixer reads that stream with no norm before it. An eighth, not Ouro's
#: quarter: read on the chip (my chip run, PR 33) the leaves outside the one
#: that stood out read 0.6 times at 0.125 what they read at 0.25. Listed
#: under ``assumed`` in the configuration file; the published gains are not
#: in ``config.json``.
POST_NORM_GAIN = 0.125
#: ``A_log`` and ``dt_bias`` a head: rates exp(A_log) around 4 (1 to 16 at two
#: standard deviations, Mamba-2's range) and steps softplus(dt_bias) around
#: 0.05 (0.007 to 0.37): a head forgets in 1 to 30 tokens. At steps around 0.01
#: (Mamba-2's, a memory of hundreds of tokens) the rule recalls what it
#: stored under a key it meets again, so its write ``beta (v - S k)`` is a
#: small difference of large terms wherever the synthetic tokens repeat, and
#: one layer's ``lin_b`` gradient read 0.017-0.032 on one token seed where
#: three others read 0.011-0.020 (my chip run, PR 33); at 0.05 no leaf stands
#: out and the seeds read alike (0.0173, 0.0197 at an output gain of 0.25).
A_LOG = (math.log(4.0), 0.7)
DT_BIAS = (math.log(math.expm1(0.05)), 1.0)


def _matrix(z):
    return 0.02 * z


#: The two gate projections are seeded smaller than the other matrices.
#: ``beta = 2 sigmoid(W_b x)`` with ``W_b`` at 0.02 reaches 1.8 and more on
#: one token in twenty (``W_b x`` has a standard deviation of 1.24 at d 3840),
#: where ``I - beta k k^T`` is all but a reflection (eigenvalue -1 along
#: ``k``): what a rounding changed in the state is then not damped, and the
#: gradients of everything that steers the state (q, k, the two gates) carry
#: bf16's roundings of the projections far. Read on the chip at the
#: published widths, 1 x 8192 tokens x 8 steps (my chip run, PR 33): at 0.02
#: the sound program's ``grad_rel_rms`` is 0.063 / 0.124 / 0.064 over three
#: runs (worst leaves ``lin_b``, ``lin_a``, ``lin_k`` of one layer; limit
#: 0.03), and 0.105 with every product of the rule itself in float32, so it
#: is not the rule's precision; halving the output norms' gain leaves 0.055;
#: with ``W_b`` at 0.005 it is 0.017. At the values below ``beta`` stays within
#: 0.7 .. 1.3 at two standard deviations -- above 1, the negative eigenvalue,
#: on every other token -- and the decay's step varies by a third either way.
#: Listed under ``assumed``; the published values are not in ``config.json``.
GATE_B_STD = 0.005
GATE_A_STD = 0.005


def _gain(centre):
    return lambda z: centre * (1.0 + 0.02 * z)


def _around(centre_scale):
    centre, scale = centre_scale
    return lambda z: centre + scale * z


def _shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], Callable]]:
    """leaf path -> (shape, value of a standard normal draw). Paths are the
    program's (``blocks/l<i>/...`` with a leading axis of periods), except that
    a full layer's q, k and v are three leaves here. The embedding's rows have
    unit RMS; the convolution's four taps 0.5 each, so that a channel leaves
    it as large as it came."""
    P, D, F, H = a.n_periods, a.d_model, a.d_inner, a.n_heads
    out: Dict[str, Tuple[Tuple[int, ...], Callable]] = {
        "wte": ((a.vocab_size, D), lambda z: z),
        "lm_head": ((a.vocab_size, D), _matrix),
        "ln_f/scale": ((D,), _gain(1.0)),
    }
    for i, kind in enumerate(a.kinds[:a.period]):
        at = f"blocks/l{i}/"
        if kind == LINEAR:
            K, V = H * a.key_dim, H * a.value_dim
            out.update({
                at + "lin_q/kernel": ((P, D, K), _matrix),
                at + "lin_k/kernel": ((P, D, K), _matrix),
                at + "lin_v/kernel": ((P, D, V), _matrix),
                at + "lin_gate/kernel": ((P, D, V), _matrix),
                at + "lin_a/kernel": ((P, D, H), lambda z: GATE_A_STD * z),
                at + "lin_b/kernel": ((P, D, H), lambda z: GATE_B_STD * z),
                at + "conv_q": ((P, a.conv_taps, K), lambda z: 0.5 * z),
                at + "conv_k": ((P, a.conv_taps, K), lambda z: 0.5 * z),
                at + "conv_v": ((P, a.conv_taps, V), lambda z: 0.5 * z),
                at + "A_log": ((P, H), _around(A_LOG)),
                at + "dt_bias": ((P, H), _around(DT_BIAS)),
                at + "o_norm/scale": ((P, a.value_dim), _gain(1.0)),
                at + "attn_out/kernel": ((P, V, D), _matrix),
            })
        else:
            A = H * a.head_dim
            out.update({
                at + "q/kernel": ((P, D, A), _matrix),
                at + "k/kernel": ((P, D, A), _matrix),
                at + "v/kernel": ((P, D, A), _matrix),
                at + "q_norm/scale": ((P, A), _gain(1.0)),
                at + "k_norm/scale": ((P, A), _gain(1.0)),
                at + "attn_out/kernel": ((P, A, D), _matrix),
            })
        out.update({
            at + "ln_1_post/scale": ((P, D), _gain(POST_NORM_GAIN)),
            at + "ln_2_post/scale": ((P, D), _gain(POST_NORM_GAIN)),
            at + "mlp_gate/kernel": ((P, D, F), _matrix),
            at + "mlp_in/kernel": ((P, D, F), _matrix),       # SwiGLU's "up"
            at + "mlp_out/kernel": ((P, F, D), _matrix),
        })
    return out


def seeded_params(a: Arch, key) -> Dict[str, Any]:
    """Float32 weights from ``key`` (``seed_key(seed)``), every leaf random
    (the norms' gains too, so that a gain put in the wrong place shows).
    Traceable. The period-stacked leaves are a layout only."""
    out = {}
    for i, (path, (shape, value)) in enumerate(sorted(_shapes(a).items())):
        out[path] = value(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
    return _nest(out)


def program_layout(a: Arch, tree: Dict[str, Any], xp=jnp) -> Dict[str, Any]:
    """A tree of the parameters' structure (weights, gradients, Adam moments)
    in the layout ``saturn_tpu/models/gpt2.py`` trains: a full layer's q, k, v
    side by side in one ``qkv`` kernel. ``xp`` is ``jnp`` or ``numpy``."""
    blocks = dict(tree["blocks"])
    for name, layer in tree["blocks"].items():
        if "q" in layer:
            layer = dict(layer)
            q, k, v = (layer.pop(n)["kernel"] for n in ("q", "k", "v"))
            layer["qkv"] = {"kernel": xp.concatenate([q, k, v], axis=-1)}
            blocks[name] = layer
    return dict(tree, blocks=blocks)


def program_params(a: Arch, key) -> Dict[str, Any]:
    """The seeded weights as the program is handed them. Traceable."""
    return program_layout(a, seeded_params(a, key))


def _layer_weights(a: Arch, blocks, n: int):
    """Layer ``n``'s own weights out of the period-stacked tree."""
    period, i = divmod(n, a.period)
    return jax.tree_util.tree_map(lambda x: x[period], blocks[f"l{i}"])


# ------------------------------------------------------------------ forward
def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def _attention(q, k, v):
    """Causal softmax attention on (B, T, H, hd), by blocks of query rows."""
    T, hd = q.shape[1], q.shape[-1]

    @jax.checkpoint
    def rows(q_rows, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / math.sqrt(hd)
        seen = (first + jnp.arange(q_rows.shape[1]))[:, None] >= jnp.arange(T)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    return jnp.concatenate(
        [rows(q[:, i:i + ATTN_Q_BLOCK], i) for i in range(0, T, ATTN_Q_BLOCK)], axis=1)


def _causal_conv(x, taps):
    """Depthwise, causal: y_t = sum_j taps[j] x_{t - (K - 1) + j} on (B, T, C)."""
    K, T = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = jnp.zeros_like(x)
    for j in range(K):
        y = y + taps[j] * padded[:, j:j + T]
    return y


def _delta_rule(q, k, v, alpha, beta):
    """The recurrence, one token at a time. ``q`` / ``k`` (B, T, H, dk), ``v``
    (B, T, H, dv), ``alpha`` / ``beta`` (B, T, H) -> ``o`` (B, T, H, dv)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]

    def token(S, x):                                   # S: (B, H, dv, dk)
        q_t, k_t, v_t, a_t, b_t = x
        S = a_t[..., None, None] * S                                # decay
        Sk = jnp.einsum("bhvk,bhk->bhv", S, k_t)
        S = S - b_t[..., None, None] * Sk[..., :, None] * k_t[..., None, :]   # erase
        S = S + b_t[..., None, None] * v_t[..., :, None] * k_t[..., None, :]  # write
        return S, jnp.einsum("bhvk,bhk->bhv", S, q_t)

    @jax.checkpoint                                    # block 3
    def piece(S, xs):
        return jax.lax.scan(token, S, xs)

    n = SCAN_PIECE if T % SCAN_PIECE == 0 else T
    pieces = [jnp.moveaxis(x, 1, 0).reshape(T // n, n, *x.shape[:1], *x.shape[2:])
              for x in (q, k, v, alpha, beta)]
    _, o = jax.lax.scan(piece, jnp.zeros((B, H, dv, dk), jnp.float32), pieces)
    return jnp.moveaxis(o.reshape(T, B, H, dv), 0, 1)


def mixer_parts(a: Arch, mm: Callable, kind: str, p, x, qk_rms=None):
    """The held heads' mixer output (B, T, D), before N1. ``qk_rms`` hands a
    full layer the root mean squares of q and k over *all* the published
    heads' lanes, each (B, T, 1), where the caller holds a share and wants the
    uncut layer's statistic; None takes them over the held lanes, as the
    program does."""
    B, T, D = x.shape
    H, eps = a.n_heads, a.norm_eps
    if kind == FULL:
        q, k, v = (mm(x, p[n]["kernel"]) for n in ("q", "k", "v"))
        if qk_rms is None:
            q = _rms_norm(q, p["q_norm"]["scale"], eps)
            k = _rms_norm(k, p["k_norm"]["scale"], eps)
        else:
            q = q / qk_rms[0] * p["q_norm"]["scale"]
            k = k / qk_rms[1] * p["k_norm"]["scale"]
        q, k, v = (t.reshape(B, T, H, a.head_dim) for t in (q, k, v))
        o = _attention(q, k, v).reshape(B, T, H * a.head_dim)
        return mm(o, p["attn_out"]["kernel"])
    dk, dv = a.key_dim, a.value_dim

    def conv_silu(name, width):
        y = _causal_conv(mm(x, p[f"lin_{name}"]["kernel"]), p[f"conv_{name}"])
        return jax.nn.silu(y).reshape(B, T, H, width)

    def unit(t):
        return t / jnp.sqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + L2_EPS)

    q, k, v = conv_silu("q", dk), conv_silu("k", dk), conv_silu("v", dv)
    beta = (2.0 if a.neg_eigval else 1.0) * jax.nn.sigmoid(mm(x, p["lin_b"]["kernel"]))
    step = jax.nn.softplus(mm(x, p["lin_a"]["kernel"]) + p["dt_bias"])
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * step)
    o = _delta_rule(unit(q) / math.sqrt(dk), unit(k), v, alpha, beta)
    o = _rms_norm(o, p["o_norm"]["scale"], eps).reshape(B, T, H * dv)
    return mm(o * jax.nn.silu(mm(x, p["lin_gate"]["kernel"])), p["attn_out"]["kernel"])


def _layer(a: Arch, mm: Callable, kind: str, p, x):
    eps = a.norm_eps
    h = x + _rms_norm(mixer_parts(a, mm, kind, p, x), p["ln_1_post"]["scale"], eps)
    f = mm(jax.nn.silu(mm(h, p["mlp_gate"]["kernel"])) * mm(h, p["mlp_in"]["kernel"]),
           p["mlp_out"]["kernel"])
    return h + _rms_norm(f, p["ln_2_post"]["scale"], eps)


def _head(a: Arch, mm: Callable, top, x):
    """``top``: the leaves outside the stack (``ln_f``, ``lm_head``)."""
    return mm(_rms_norm(x, top["ln_f"]["scale"], a.norm_eps), top["lm_head"].T)


def _xent(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def _plain_mm(x, w):
    return x @ w


def forward(a: Arch, params, tokens, mm: Optional[Callable] = None):
    """(B, T) int tokens -> (B, T, V) float32 logits. ``mm(x, w)`` is the
    matrix product of activations (..., K) and weights (K, N); the control of
    ``perf/lib/refcheck.py`` passes a lower-precision one and changes nothing
    else."""
    mm = mm or _plain_mm
    x = params["wte"][tokens]
    for n, kind in enumerate(a.kinds):
        x = jax.checkpoint(functools.partial(_layer, a, mm, kind))(
            _layer_weights(a, params["blocks"], n), x)
    return _head(a, mm, params, x)


def loss_fn(a: Arch, params, tokens, mm: Optional[Callable] = None):
    """Next-token cross entropy, mean over the B x (T-1) targets."""
    return _xent(forward(a, params, tokens, mm), tokens)


# ----------------------------------------------------------------- training
@functools.lru_cache(maxsize=None)
def _jitted(a: Arch, lr: float, mm: Optional[Callable]) -> Dict[str, Callable]:
    """The jitted pieces of ``train`` and ``logits_of``, made once for an
    architecture, a learning rate and a matmul."""
    mul = mm or _plain_mm

    def layer_back(kind, p, x, dy):
        _, vjp = jax.vjp(functools.partial(_layer, a, mul, kind), p, x)
        return vjp(dy)                                  # (dp, dx)

    def head_back(top, x, tokens):
        loss, (dtop, dx) = jax.value_and_grad(
            lambda t, h: _xent(_head(a, mul, t, h), tokens), argnums=(0, 1))(top, x)
        return loss, dtop, dx

    def update(p, g, m, v, t):
        new_p, opt = adamw_step(p, g, {"m": m, "v": v, "t": t}, lr)
        return new_p, opt["m"], opt["v"]

    out = {"params": jax.jit(lambda k: seeded_params(a, k)),
           "seeded": jax.jit(lambda k: program_params(a, k)),
           "embed": jax.jit(lambda wte, tokens: wte[tokens]),
           "embed_back": jax.jit(lambda wte, tokens, dx: jnp.zeros_like(wte).at[tokens].add(dx)),
           "head_back": jax.jit(head_back),
           "update": jax.jit(update, donate_argnums=(0, 1, 2, 3)),
           "logits": jax.jit(lambda k, t: forward(a, seeded_params(a, k), t, mm))}
    for kind in set(a.kinds):
        out["layer", kind] = jax.jit(functools.partial(_layer, a, mul, kind))
        out["layer_back", kind] = jax.jit(functools.partial(layer_back, kind))
    return out


def _unstack(a: Arch, params) -> Dict[str, Any]:
    """{"top": the leaves outside the stack, "layers": [each layer's own
    weights]}: what ``train`` updates piece by piece."""
    return {"top": {k: v for k, v in params.items() if k != "blocks"},
            "layers": [_layer_weights(a, params["blocks"], n) for n in range(a.n_layers)]}


def _restack(a: Arch, pieces, xp) -> Dict[str, Any]:
    blocks = {}
    for i in range(a.period):
        mine = [flat(pieces["layers"][p * a.period + i]) for p in range(a.n_periods)]
        blocks[f"l{i}"] = _nest({k: xp.stack([m[k] for m in mine]) for k in mine[0]})
    return dict(pieces["top"], blocks=blocks)


def _step(a: Arch, fns, state, tokens):
    """One AdamW step, the gradient layer by layer (module docstring, 1).
    ``state``: ``{"p", "m", "v"}``, each ``{"top", "layers"}``, and ``"t"``."""
    p, m, v, t = state["p"], state["m"], state["v"], state["t"]

    def put(where, key, grads):
        new = fns["update"](p[where][key], grads, m[where][key], v[where][key], t)
        for tree, leaf in zip((p, m, v), new):
            tree[where][key] = leaf

    x = fns["embed"](p["top"]["wte"], tokens)
    inputs = []
    for n, kind in enumerate(a.kinds):
        inputs.append(x)
        x = fns["layer", kind](p["layers"][n], x)
    head = {k: p["top"][k] for k in ("ln_f", "lm_head")}
    loss, dhead, dx = fns["head_back"](head, x, tokens)
    for k, g in dhead.items():
        put("top", k, g)
    del dhead, x
    for n in reversed(range(a.n_layers)):
        dp, dx = fns["layer_back", a.kinds[n]](p["layers"][n], inputs.pop(), dx)
        put("layers", n, dp)
        del dp
    put("top", "wte", fns["embed_back"](p["top"]["wte"], tokens, dx))
    state["t"] = t + 1
    return loss


def train(a: Arch, seed: int, batches, lr: float,
          mm: Optional[Callable] = None, keep_state: bool = False):
    """``len(batches)`` AdamW steps from the seeded weights. Returns (the loss
    before each step, as floats; the final state). The state is None unless
    ``keep_state``; then it is host arrays by leaf path, in the program's
    layout: ``{"m": first moments, "params": weights, "moved": ||weights -
    seeded weights|| per leaf}``: what a checkpoint of the program is held
    against."""
    import numpy as np

    fns = _jitted(a, float(lr), mm)
    with jax.default_matmul_precision("highest"):
        key = seed_key(seed)
        state = {"p": _unstack(a, fns["params"](key)), "t": jnp.zeros((), jnp.int32)}
        for moment in ("m", "v"):
            state[moment] = jax.tree_util.tree_map(jnp.zeros_like, state["p"])
        losses = [_step(a, fns, state, jnp.asarray(tokens)) for tokens in batches]
        out = [float(x) for x in losses]
        kept = None
        if keep_state:
            del state["v"]  # the second moments are not compared: free them first
            kept = {name: flat(program_layout(a, _restack(
                a, jax.tree_util.tree_map(np.asarray, state.pop(tree)), np), xp=np))
                for name, tree in (("m", "m"), ("params", "p"))}
            seeded = flat(jax.tree_util.tree_map(np.asarray, fns["seeded"](key)))
            kept["moved"] = {
                k: float(np.sqrt(np.sum(np.square(w - seeded[k], dtype=np.float64))))
                for k, w in kept["params"].items()}
    del state
    return out, kept


def logits_of(a: Arch, seed: int, tokens, mm: Optional[Callable] = None):
    """Float32 logits of the seeded weights on ``tokens``."""
    with jax.default_matmul_precision("highest"):
        return _jitted(a, 0.0, mm)["logits"](seed_key(seed), jnp.asarray(tokens))
