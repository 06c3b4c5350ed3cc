"""The plain reference for Ouro (ByteDance/Ouro-2.6B): a looped language model
in straightforward ``jax.numpy``, float32, matmul precision ``highest``.

A loop over the passes and, inside it, over the layers (note 3 below);
attention as two einsums and a softmax; the loss as a log-softmax over the
materialised logits; AdamW written out (``perf/reference/gpt.py``'s, optax's
defaults). No kernels, no fused head, no flax. Same module contract as
``gpt.py``: ``arch_from_config``, ``seed_key``, ``program_params``,
``logits_of``, ``train``.

The published model (``config.json`` gives the sizes; the block structure is
that of the published modelling code, listed under ``assumed`` in the
configuration file). With ``N(x) = x / sqrt(mean(x^2) + eps) * g``, rotary
``R`` on the whole head (half-split pairs, ``rotate_half``; base
``rope_theta``) and no bias anywhere, layer ``l`` is a sandwich-norm block

    a = N1(h);  q, k, v = a Wq, a Wk, a Wv
    o = softmax_causal(R(q) R(k)^T / sqrt(head_dim)) v;   h'  = h  + N2(o Wo)
    m = N3(h'); f = (silu(m Wg) * (m Wu)) Wd;             h'' = h' + N4(f)

and the model runs the same ``layers_held`` layers ``ut_steps`` times, the
final norm after every pass and its output fed to the next:

    x_0 = E[tokens];  x_t = Nf(L_n(... L_1(x_{t-1}) ...)), t = 1..ut_steps
    logits = x_last W_head            (the head is not tied to E)

The loss is the one the published forward computes from labels: next-token
cross-entropy on the logits of the last pass. The exit gate (a
``Linear(d, 1)`` on each ``x_t``) is not built: at the published
``early_exit_threshold`` 1 no pass is skipped, and this loss gives its 2049
parameters no gradient. The pre-training's exit-distribution objective is not
in the config and is not guessed.

Blocks of computation, so that the float32 reference fits a 16 GB chip at the
published widths (each changes when a value is computed, never which):
  1. ``jax.checkpoint`` around the plain layer function: the backward keeps
     one layer input per layer application and recomputes the rest;
  2. attention by blocks of ``ATTN_Q_BLOCK`` query rows, each block under its
     own ``jax.checkpoint`` (a block's T-wide score rows are recomputed, not
     kept). A row's softmax is over its whole causal row either way.
And one so that a run stays inside its time:
  3. the loop over the layers of one pass is a ``lax.scan`` of the plain
     layer function over the layer-stacked leaves, layer 1 first; the loop
     over the passes is Python's. Both unrolled (96 layer bodies, forward and
     backward, in float32 at six bf16 passes a product) the training step
     took 270 s to compile on the chip into a 336 MB executable, over the
     192 MiB the chip machine's compile cache keeps, so every run paid it
     (my chip run, PR 28); ``perf/tests/test_reference_ouro.py`` holds the
     scanned form to a forward written out layer by layer in numpy.
The loss is not blocked: 1 x 4096 x 49152 float32 logits are 0.8 GB.

Layout, the one departure: the package fuses q, k, v into one ``qkv`` kernel;
``program_layout`` concatenates the reference's three. The package rotates
split halves, as the published ``rotate_half`` does, so no lane moves.

What the shared readers see. They call ``flops.required_flops_per_token(
a.d_model, a.n_layers, a.d_ff, a.vocab_size, seq)``, a GPT count:
6 x (L (4 d^2 + 2 d ff) + d V) + 12 L S d. ``Arch`` keeps the true fields
(``layers_held``, ``ut_steps``, ``d_inner``) and exposes
  ``n_layers = layers_held x ut_steps``  layer applications a token passes:
      compute follows applications, not parameters;
  ``d_ff = 3 x d_inner / 2``             SwiGLU multiplies a token by three
      d x d_inner matrices (gate, up, down) where the GPT block has two
      d x ff: 2 d ff = 3 d d_inner.
The formula then gives 6 x (4N (4 d^2 + 3 d d_inner) + d V) + 12 (4N) S d:
every layer application's matrices and attention, the head once, the
embedding's gather nothing (``perf/tests/test_reference_ouro.py`` holds it
to the count written out by hand).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from perf.reference.gpt import _nest, adamw_init, adamw_step, flat, seed_key

__all__ = ["Arch", "arch_from_config", "seed_key", "seeded_params",
           "program_layout", "program_params", "forward", "loss_fn", "train",
           "logits_of"]

ATTN_Q_BLOCK = 1024  # block 2


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""

    vocab_size: int
    d_model: int
    layers_held: int       # layers with parameters of their own
    ut_steps: int          # passes over them
    n_heads: int
    d_inner: int           # SwiGLU's inner width (``intermediate_size``)
    rope_theta: float
    norm_eps: float
    family: str = "ouro"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_layers(self) -> int:
        """Layer applications a token passes (see the module docstring)."""
        return self.layers_held * self.ut_steps

    @property
    def d_ff(self) -> int:
        """The two-matrix MLP width with SwiGLU's three matrices' multiplies."""
        assert (3 * self.d_inner) % 2 == 0
        return 3 * self.d_inner // 2


def arch_from_config(cfg: Dict[str, Any], seq_len: int) -> Arch:
    """``cfg`` is a file of ``perf/configs``; the model has no position table,
    so ``seq_len`` sizes nothing."""
    del seq_len
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if int(cfg["num_key_value_heads"]) != heads or int(cfg["head_dim"]) * heads != d:
        raise ValueError("the reference has one k/v head per q head of d / heads lanes")
    return Arch(
        vocab_size=int(cfg["run"]["vocab_size"]),
        d_model=d,
        layers_held=int(cfg["num_hidden_layers"]),
        ut_steps=int(cfg["total_ut_steps"]),
        n_heads=heads,
        d_inner=int(cfg["intermediate_size"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
    )


# ------------------------------------------------------------------ weights
_NORMS = ("ln_1", "ln_1_post", "ln_2", "ln_2_post")   # N1, N2, N3, N4


#: Where the seeded gains of a block's two *output* norms (N2, N4) are
#: centred. At gain 1 and random weights every branch is as large as the
#: residual stream it joins (``Nf`` hands every pass a unit-RMS stream), and a
#: rounding difference then grows through the 4 x N layer applications. Read
#: on the chip at the published widths, depth 6, 1 x 4096 tokens, 8 steps
#: (my chip run, PR 28, four token seeds): at gain 1 the program, sound, reads
#: ``grad_rel_rms`` 0.0337-0.0385 and ``update_rel_rms`` 0.0609-0.0640, over
#: the committed limits 0.03 / 0.06 in every seed, and this reference with
#: only its matmuls in bf16, the configuration's own precision, reads
#: 0.0303-0.0308 / 0.0549-0.0563 against itself in float32: the limits are
#: reached before any program is involved. At a quarter the branches are to
#: the stream what they are in the GPT cells: the program reads 0.0130 /
#: 0.0292 at most over 22 seeds and the fp8 control 0.112 / 0.157 at least.
#: Training does not stay at gain 1 either; the published gains are not in
#: ``config.json``, so this is listed under ``assumed`` (PERF.md, section 4).
POST_NORM_GAIN = 0.25


def _shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], float, float]]:
    """leaf path -> (shape, scale, centre): the std of a matrix (centre 0),
    or of the relative noise around a norm's gain (centre: the gain). Paths
    are the program's, except that q, k and v are three leaves here. The
    embedding's rows have unit RMS, the scale ``Nf`` hands every later pass."""
    L, D, F = a.layers_held, a.d_model, a.d_inner
    out = {
        "wte": ((a.vocab_size, D), 1.0, 0.0),
        "lm_head": ((a.vocab_size, D), 0.02, 0.0),
        "ln_f/scale": ((D,), 0.02, 1.0),
        "blocks/q/kernel": ((L, D, D), 0.02, 0.0),
        "blocks/k/kernel": ((L, D, D), 0.02, 0.0),
        "blocks/v/kernel": ((L, D, D), 0.02, 0.0),
        "blocks/attn_out/kernel": ((L, D, D), 0.02, 0.0),
        "blocks/mlp_gate/kernel": ((L, D, F), 0.02, 0.0),
        "blocks/mlp_in/kernel": ((L, D, F), 0.02, 0.0),       # SwiGLU's "up"
        "blocks/mlp_out/kernel": ((L, F, D), 0.02, 0.0),
    }
    for n in _NORMS:
        out[f"blocks/{n}/scale"] = (
            (L, D), 0.02, POST_NORM_GAIN if n.endswith("_post") else 1.0)
    return out


def seeded_params(a: Arch, key) -> Dict[str, Any]:
    """Float32 weights from ``key`` (``seed_key(seed)``), every leaf random
    (the norms' gains too, so that a gain put in the wrong place shows).
    Traceable. Layer-stacked leaves are a layout only."""
    out = {}
    for i, (path, (shape, scale, centre)) in enumerate(sorted(_shapes(a).items())):
        x = scale * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[path] = centre * (1.0 + x) if centre else x
    return _nest(out)


def program_layout(a: Arch, tree: Dict[str, Any], xp=jnp) -> Dict[str, Any]:
    """A tree of the parameters' structure (weights, gradients, Adam moments)
    in the layout ``saturn_tpu/models/gpt2.py`` trains: q, k, v side by side
    in one ``qkv`` kernel. ``xp`` is ``jnp`` (traceable) or ``numpy``."""
    blocks = dict(tree["blocks"])
    q, k, v = (blocks.pop(n)["kernel"] for n in ("q", "k", "v"))
    blocks["qkv"] = {"kernel": xp.concatenate([q, k, v], axis=-1)}
    return dict(tree, blocks=blocks)


def program_params(a: Arch, key) -> Dict[str, Any]:
    """The seeded weights as the program is handed them. Traceable."""
    return program_layout(a, seeded_params(a, key))


# ------------------------------------------------------------------ forward
def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rotary(t, theta):
    """The published ``apply_rotary_pos_emb`` on (B, T, H, hd): every lane,
    pairs (j, j + hd/2) rotated by position x theta^(-2j/hd)."""
    T, hd = t.shape[1], t.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return t * jnp.cos(ang) + _rotate_half(t) * jnp.sin(ang)


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


def _layer(a: Arch, mm: Callable, p, h):
    B, T, D = h.shape
    H, hd, eps = a.n_heads, a.head_dim, a.norm_eps
    x = _rms_norm(h, p["ln_1"]["scale"], eps)
    q, k, v = (mm(x, p[n]["kernel"]).reshape(B, T, H, hd) for n in ("q", "k", "v"))
    o = _attention(_rotary(q, a.rope_theta), _rotary(k, a.rope_theta), v)
    o = mm(o.reshape(B, T, D), p["attn_out"]["kernel"])
    h = h + _rms_norm(o, p["ln_1_post"]["scale"], eps)
    m = _rms_norm(h, p["ln_2"]["scale"], eps)
    f = mm(jax.nn.silu(mm(m, p["mlp_gate"]["kernel"])) * mm(m, p["mlp_in"]["kernel"]),
           p["mlp_out"]["kernel"])
    return h + _rms_norm(f, p["ln_2_post"]["scale"], eps)


def forward(a: Arch, params, tokens, mm: Optional[Callable] = None):
    """(B, T) int tokens -> (B, T, V) float32 logits of the last pass.
    ``mm(x, w)`` is the matrix product of activations (..., K) and weights
    (K, N); the control of ``perf/lib/refcheck.py`` passes a lower-precision
    one and changes nothing else."""
    mm = mm or (lambda x, w: x @ w)
    layer = jax.checkpoint(functools.partial(_layer, a, mm))      # block 1

    def next_layer(x, weights):                                   # block 3
        return layer(weights, x), None

    x = params["wte"][tokens]
    for _ in range(a.ut_steps):
        x, _ = jax.lax.scan(next_layer, x, params["blocks"])
        x = _rms_norm(x, params["ln_f"]["scale"], a.norm_eps)
    return mm(x, params["lm_head"].T)


def loss_fn(a: Arch, params, tokens, mm: Optional[Callable] = None):
    """Next-token cross entropy, mean over the B x (T-1) targets."""
    logp = jax.nn.log_softmax(forward(a, params, tokens, mm)[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


# ----------------------------------------------------------------- training
@functools.lru_cache(maxsize=None)
def _jitted(a: Arch, lr: float, mm: Optional[Callable]) -> Dict[str, Callable]:
    """The jitted pieces of ``train`` and ``logits_of``, made once for an
    architecture, a learning rate and a matmul."""

    def step(params, opt, tokens):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(a, p, tokens, mm))(params)
        params, opt = adamw_step(params, grads, opt, lr)
        return params, opt, loss

    def moved(params, key):
        # per leaf of the program's layout: a checkpoint is held against it
        return jax.tree_util.tree_map(
            lambda p, p0: jnp.sqrt(jnp.sum(jnp.square(p - p0))),
            program_layout(a, params), program_params(a, key))

    return {"params": jax.jit(lambda k: seeded_params(a, k)),
            "opt": jax.jit(adamw_init),
            "step": jax.jit(step, donate_argnums=(0, 1)),
            "moved": jax.jit(moved),
            "logits": jax.jit(lambda k, t: forward(a, seeded_params(a, k), t, mm))}


def train(a: Arch, seed: int, batches, lr: float,
          mm: Optional[Callable] = None, keep_state: bool = False):
    """``len(batches)`` AdamW steps from the seeded weights: one jitted step,
    called in a Python loop. Returns (the loss before each step, as floats;
    the final state). The state is None unless ``keep_state``; then it is
    host arrays by leaf path, in the program's layout: ``{"m": first
    moments, "params": weights, "moved": ||weights - seeded weights|| per
    leaf}``: what a checkpoint of the program is held against."""
    import numpy as np

    fns = _jitted(a, float(lr), mm)
    with jax.default_matmul_precision("highest"):
        key = seed_key(seed)
        params = fns["params"](key)
        opt = fns["opt"](params)
        losses = []
        for tokens in batches:
            params, opt, loss = fns["step"](params, opt, jnp.asarray(tokens))
            losses.append(loss)
        out = [float(x) for x in losses]
        state = None
        if keep_state:
            m = opt["m"]
            del opt  # the second moments are not compared: free them first
            state = {"moved": {k: float(v) for k, v in
                               flat(fns["moved"](params, key)).items()}}
            for name, tree in (("m", m), ("params", params)):
                host = jax.tree_util.tree_map(np.asarray, tree)
                state[name] = flat(program_layout(a, host, xp=np))
            del m
    del params
    return out, state


def logits_of(a: Arch, seed: int, tokens, mm: Optional[Callable] = None):
    """Float32 logits of the seeded weights on ``tokens``."""
    with jax.default_matmul_precision("highest"):
        return _jitted(a, 0.0, mm)["logits"](seed_key(seed), jnp.asarray(tokens))
