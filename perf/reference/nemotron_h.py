"""The plain reference for Nemotron-H (nvidia/NVIDIA-Nemotron-3-Super-120B-
A12B-BF16, ``model_type`` ``nemotron_h``): a stack whose every layer is one
mixer and no second half -- a Mamba-2 state-space layer, a LatentMoE layer or
a softmax-attention layer -- in straightforward ``jax.numpy``, float32, matmul
precision ``highest``.

A Python loop over the layers; the state-space recurrence **token by token**
(a ``lax.scan`` over the tokens in checkpointed pieces: no chunked form, no
``C B^T``); every held expert over every token under the mask of the tokens
that chose it (a ``lax.scan`` over the experts: no sort, no grouped product,
no row buffer); attention by blocks of query rows; the loss as a log-softmax
over the materialised logits; AdamW written out (``perf/reference/gpt.py``'s).
No kernels, no fused head, no flax, nothing of ``saturn_tpu/ops``. Same
module contract as ``gpt.py``: ``arch_from_config``, ``seed_key``,
``program_params``, ``logits_of``, ``train``.

The model (``config.json`` gives the sizes and ``hybrid_override_pattern``;
what it does not say is marked + and listed under ``assumed`` in the
configuration file: the ``nemotron_h`` family's published modelling code, as
remembered). With ``N(x) = x / sqrt(mean(x^2) + eps) * g`` and no bias but the
convolution's, every layer is + ``x <- x + Mixer(N(x))``, the mixer by the
layer's letter; after the last a final ``N`` and an untied head.

``M``, Mamba-2 (H heads of P lanes in G groups, state N, y = N(x)):

    [z | xBC | dt] = y W_in                     (H P | H P + 2 G N | H)
    xBC = silu(conv4(xBC) + b)                  causal, depthwise, 4 taps
    x (H x P), B (G x N), C (G x N) = split(xBC)
    Delta_t = softplus(dt_t + dt_bias) +;  A = -exp(A_log) +   (a scalar a head)
    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T;  o_t = S_t C_t + D x_t
                                                (head h reads group h // (H / G))
    o = N_G(o * silu(z)) +                      RMSNorm over each group's lanes
    Mixer = o W_out

``*``, attention (H q heads over KV k/v heads of 128): causal softmax at scale
1 / sqrt(128), q head n reading k/v head n // (H / KV); no rotary and no other
position signal +; ``Mixer = o W_o``.

``E``, LatentMoE:

    s = sigmoid(y W_r)  (float32, all 512 experts)
    I = the 22 largest of s + b   (b: the selection bias, in the choice only)
    w_e = 5 s_e / sum_{e' in I} s_e'
    u = y W_down (4096 -> 1024);  E_e(u) = W2_e relu(W1_e u)^2
    Mixer = (sum_{e in I, e held} w_e E_e(u)) W_up + W2_s relu(W1_s y)^2

**The held share.** ``Arch.held`` experts from ``Arch.first_expert`` on have
tables here (8 of 512 in the benchmark's configuration); a chosen expert that
is not held adds nothing. The heads of the Mamba-2 and attention layers are a
share too (32 of 128 in 2 of 8 groups; 8 of 32 q heads over 1 of 2 k/v
heads): the weights' columns of the held heads, nothing for the rest
(``tests/test_nemotron_h.py`` adds the shares up to the uncut layers).

**How it fits a 16 GB chip at the published widths** (774 M parameters): as
``laguna.py``: ``train`` takes the gradient layer by layer, attention goes by
blocks of query rows, the held experts one after another and the recurrence
in pieces of ``SCAN_PIECE`` tokens, each rematerialised in the backward.
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
                                   _rms_norm, _say_host_memory, _update, _xent)

__all__ = ["Arch", "arch_from_config", "seed_key", "seeded_params",
           "program_layout", "program_params", "forward", "loss_fn", "train",
           "logits_of", "routed_part", "routing_of", "mamba_mixer",
           "attention_mixer", "latent_moe_mixer", "recurrence"]

SCAN_PIECE = 64
MAMBA, ATTENTION, MOE = "mamba2", "attention_only", "latent_moe"
LETTERS = {"M": MAMBA, "*": ATTENTION, "E": MOE}


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file. Head,
    group and expert counts are the *held* ones."""

    vocab_size: int                  # rows of the embedding and the head held
    d_model: int
    kinds: Tuple[str, ...]           # the mixer of every layer held
    n_heads: int                     # q heads held
    n_kv_heads: int                  # k/v heads held
    head_dim: int
    ssm_heads: int                   # Mamba-2 heads held
    ssm_groups: int                  # and their groups
    ssm_head_dim: int
    ssm_state: int
    conv_taps: int
    chunk: int                       # the published chunk_size (nothing here chunks)
    experts: int                     # the router's outputs
    held: int                        # experts whose tables are here
    first_expert: int
    top_k: int
    d_latent: int
    d_expert: int
    d_shared: int
    routed_scale: float
    norm_eps: float
    dt_range: Tuple[float, float, float] = (0.001, 0.1, 1e-4)   # min, max, floor
    preset: str = ""                 # the program's preset and overrides, for
    overrides: Tuple[Tuple[str, Any], ...] = ()   # the routing comparison
    builder: str = ""
    family: str = "nemotron_h"

    @property
    def n_layers(self) -> int:
        return len(self.kinds)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_bc(self) -> int:
        return self.ssm_groups * self.ssm_state


def arch_from_config(cfg: Dict[str, Any], seq_len: int) -> Arch:
    """``cfg`` is a file of ``perf/configs``; the model has no position table,
    so ``seq_len`` sizes nothing. ``hybrid_override_pattern`` keeps its
    published letters and ``run.layers`` says which of them run; the reduced
    keys hold what is held here, ``published`` what the source has."""
    del seq_len
    run = cfg["run"]
    first, last = run["layers"]
    letters = cfg["hybrid_override_pattern"][int(first):int(last)]
    if len(letters) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"run.layers {run['layers']} are not the "
                         f"{cfg['num_hidden_layers']} layers the file holds")
    if cfg.get("mlp_hidden_act") != "relu2" or int(cfg.get("n_group", 1)) != 1:
        raise ValueError("the reference knows relu2 experts and no group limit")
    return Arch(
        vocab_size=int(run["vocab_size"]),
        d_model=int(cfg["hidden_size"]),
        kinds=tuple(LETTERS[c] for c in letters),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        ssm_heads=int(cfg["mamba_num_heads"]),
        ssm_groups=int(cfg["n_groups"]),
        ssm_head_dim=int(cfg["mamba_head_dim"]),
        ssm_state=int(cfg["ssm_state_size"]),
        conv_taps=int(cfg["conv_kernel"]),
        chunk=int(cfg["chunk_size"]),
        experts=int(cfg.get("published", {}).get("n_routed_experts",
                                                 cfg["n_routed_experts"])),
        held=int(cfg["n_routed_experts"]),
        first_expert=0,
        top_k=int(cfg["num_experts_per_tok"]),
        d_latent=int(cfg["moe_latent_size"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        d_shared=int(cfg["moe_shared_expert_intermediate_size"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        norm_eps=float(cfg["layer_norm_epsilon"]),
        dt_range=(float(cfg["time_step_min"]), float(cfg["time_step_max"]),
                  float(cfg["time_step_floor"])),
        preset=str(run.get("preset", "")),
        overrides=tuple(sorted(run.get("overrides", {}).items())),
        builder=str(run.get("builder", "")),
    )


# ------------------------------------------------------------------ weights
#: The seeded values that are not plain normal draws (the benchmark's to
#: choose, listed under ``assumed``). **Routing is discrete and finer than
#: Laguna's** (the 22nd and 23rd of 512 scores), so Laguna's two structures
#: are kept (``perf/reference/laguna.py::AFFINITY``): the routers' columns,
#: all layers' together (5 x 512 in 4096 lanes), are one orthonormal frame of
#: length ``ROUTER_COLUMN``, and every token id is given, layer by layer,
#: ``top_k`` of the experts by a draw from the weight seed, its embedding row
#: a unit-RMS normal row plus ``AFFINITY`` times the sum of those experts'
#: unit router columns. With 5 x 22 = 110 columns in a row its RMS is
#: sqrt(1 + 16^2 x 110 / 4096) = 2.81, so after the norm a chosen logit
#: stands at 0.25 x 16 / 2.81 = 1.42 (sigmoid 0.81, not saturated) over a
#: background of deviation 0.09. ``BIAS``: the selection bias is seeded at
#: this deviation, far inside the gap, so that the path is run and the
#: choice follows the tokens' identity.
#: ``OUT``: the matrices that write to the stream (``out_proj``, ``attn_out``,
#: ``latent_up``, ``shared_out``) are normal with deviation 0.02 / sqrt(88),
#: the published initialiser's own rule (``rescale_prenorm_residual``: a
#: residual branch's output projection is divided by the root of the
#: published layer count, 88). Why it matters here: at 0.02 a relu2 shared
#: expert of 5376 writes rows of RMS 2.9 and a Mamba-2 layer 0.9 beside an
#: embedding row's 2.8, so the lean was diluted layer by layer and the
#: program routed 0.00 / 0.06 / 0.30 / 0.47 / 0.71 % of the pairs of the five
#: routed layers to another expert than the reference, with
#: ``grad_rel_rms`` 0.0318 at ``l2/we_up`` (my chip run, PR 42, the first form).
AFFINITY = 16.0
ROUTER_COLUMN = 0.25
BIAS = 0.02
OUT = 0.02 / math.sqrt(88.0)


def _matrix(z):
    return 0.02 * z


def _gain(z):
    return 1.0 + 0.02 * z


def _out(z):
    return OUT * z


def _shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], Callable]]:
    """leaf path -> (shape, value of a standard normal draw). Paths are the
    program's (``blocks/l<i>/...`` with a leading axis of one period), except
    that an attention layer's q, k and v are three leaves here. ``A_log`` and
    ``dt_bias`` are overwritten by ``seeded_params`` (uniform draws)."""
    D, lead = a.d_model, (1,)
    out: Dict[str, Tuple[Tuple[int, ...], Callable]] = {
        "wte": ((a.vocab_size, D), lambda z: z),
        "lm_head": ((a.vocab_size, D), _matrix),
        "ln_f/scale": ((D,), _gain),
    }
    for i, kind in enumerate(a.kinds):
        at = f"blocks/l{i}/"
        out[at + "ln_1/scale"] = (lead + (D,), _gain)
        if kind == MAMBA:
            lanes = a.d_inner + 2 * a.d_bc
            out.update({
                at + "in_proj/kernel": (lead + (D, a.d_inner + lanes + a.ssm_heads), _matrix),
                at + "conv_w": (lead + (a.conv_taps, lanes), lambda z: 0.5 * z),
                at + "conv_b": (lead + (lanes,), lambda z: 0.1 * z),
                at + "A_log": (lead + (a.ssm_heads,), lambda z: z),
                at + "dt_bias": (lead + (a.ssm_heads,), lambda z: z),
                at + "D": (lead + (a.ssm_heads,), _gain),
                at + "o_norm": (lead + (a.d_inner,), _gain),
                at + "out_proj/kernel": (lead + (a.d_inner, D), _out),
            })
        elif kind == ATTENTION:
            q, kv = a.n_heads * a.head_dim, a.n_kv_heads * a.head_dim
            out.update({
                at + "q/kernel": (lead + (D, q), _matrix),
                at + "k/kernel": (lead + (D, kv), _matrix),
                at + "v/kernel": (lead + (D, kv), _matrix),
                at + "attn_out/kernel": (lead + (q, D), _out),
            })
        else:
            L, F, S = a.d_latent, a.d_expert, a.d_shared
            out.update({
                at + "router": (lead + (D, a.experts), _matrix),
                at + "router_bias": (lead + (a.experts,), lambda z: BIAS * z),
                at + "latent_down/kernel": (lead + (D, L), _matrix),
                at + "latent_up/kernel": (lead + (L, D), _out),
                at + "we_up": (lead + (a.held, L, F), _matrix),
                at + "we_down": (lead + (a.held, F, L), _matrix),
                at + "shared_in/kernel": (lead + (D, S), _matrix),
                at + "shared_out/kernel": (lead + (S, D), _out),
            })
    return out


def seeded_params(a: Arch, key) -> Dict[str, Any]:
    """Float32 weights from ``key`` (``seed_key(seed)``), every leaf random;
    ``dt_bias`` and ``A_log`` by the published keys (a step log-uniform in
    ``time_step_min .. time_step_max``, floored, through the inverse
    softplus; ``A`` uniform in 1 .. 16 +); the routers' columns an
    orthonormal frame, the embedding's rows leaning towards their experts'
    columns (``AFFINITY``). Traceable, and free of matrix products: what is
    seeded must not depend on the precision a program is traced at."""
    out = {}
    for i, (path, (shape, value)) in enumerate(sorted(_shapes(a).items())):
        k = jax.random.fold_in(key, i)
        if path.endswith("/A_log"):
            out[path] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif path.endswith("/dt_bias"):
            lo, hi, floor = a.dt_range
            step = jnp.maximum(floor, jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(lo), math.log(hi))))
            out[path] = step + jnp.log(-jnp.expm1(-step))
        else:
            out[path] = value(jax.random.normal(k, shape, jnp.float32))
    routed = [i for i, kind in enumerate(a.kinds) if kind == MOE]
    if routed:
        frame = _orthonormal_frame(a.d_model, len(routed) * a.experts,
                                   jax.random.fold_in(key, 999))        # (D, n E)
        own = []
        for n, i in enumerate(routed):
            unit = frame[:, n * a.experts:(n + 1) * a.experts]           # (D, E)
            out[f"blocks/l{i}/router"] = (ROUTER_COLUMN * unit)[None]
            draw = jax.random.uniform(jax.random.fold_in(key, 1000 + i),
                                      (a.vocab_size, a.experts))
            own.append(n * a.experts + jax.lax.top_k(draw, a.top_k)[1].T)  # (k, V) columns

        def one_more(lean, columns):   # sums of rows, in a fixed order: no product,
            return lean + frame.T[columns], None    # and one gathered copy at a time

        lean, _ = jax.lax.scan(one_more, jnp.zeros_like(out["wte"]), jnp.concatenate(own))
        out["wte"] = out["wte"] + AFFINITY * lean
    return _nest(out)


def program_layout(a: Arch, tree: Dict[str, Any], xp=jnp) -> Dict[str, Any]:
    """A tree of the parameters' structure (weights, gradients, Adam moments)
    in the layout ``saturn_tpu/models/gpt2.py`` trains: an attention layer's
    q, k, v side by side in one ``qkv`` kernel (no rotary: no lane order)."""
    out = dict(tree, blocks=dict(tree["blocks"]))
    for i, kind in enumerate(a.kinds):
        if kind != ATTENTION:
            continue
        layer = dict(out["blocks"][f"l{i}"])
        q, k, v = (layer.pop(n)["kernel"] for n in ("q", "k", "v"))
        layer["qkv"] = {"kernel": xp.concatenate([q, k, v], axis=-1)}
        out["blocks"][f"l{i}"] = layer
    return out


def program_params(a: Arch, key) -> Dict[str, Any]:
    """The seeded weights as the program is handed them. Traceable."""
    return program_layout(a, seeded_params(a, key))


def _layer_weights(a: Arch, params, n: int):
    """Layer ``n``'s own weights out of the tree (the period's axis taken)."""
    return jax.tree_util.tree_map(lambda x: x[0], params["blocks"][f"l{n}"])


# ------------------------------------------------------------------ forward
def _causal_conv(x, taps, bias):
    """Depthwise, causal: lane by lane, ``out_t = sum_j taps[j] x_{t - (K-1-j)}
    + bias`` (tokens before the first are zeros); reach K."""
    K, T = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + T] for j in range(K)) + bias


def recurrence(x, delta, A, b, c, reset_every: Optional[int] = None):
    """``S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T; o_t = S_t C_t``,
    token by token: ``x`` (B, T, H, P), ``delta`` (B, T, H), ``A`` (H,), ``b``
    / ``c`` (B, T, G, N), head h reading group h // (H / G) -> (B, T, H, P).
    The scan runs in pieces of ``SCAN_PIECE`` tokens, each rematerialised in
    the backward. ``reset_every`` plants a fault (the state zeroed at every
    such position: a chunk boundary)."""
    B, T, H, P = x.shape
    per = H // b.shape[2]
    b, c = jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2)     # (B, T, H, N)

    def token(S, xs):                                   # S: (B, H, P, N)
        x_t, d_t, b_t, c_t, pos = xs
        if reset_every:
            S = jnp.where(pos % reset_every == 0, 0.0, S)
        S = jnp.exp(d_t * A)[..., None, None] * S \
            + (d_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return S, jnp.sum(S * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def piece(S, xs):
        return jax.lax.scan(token, S, xs)

    n = SCAN_PIECE if T % SCAN_PIECE == 0 else T
    seq = lambda t: jnp.moveaxis(t, 1, 0).reshape(T // n, n, *t.shape[:1], *t.shape[2:])
    pos = jnp.arange(T, dtype=jnp.int32).reshape(T // n, n)
    _, o = jax.lax.scan(piece, jnp.zeros((B, H, P, b.shape[-1]), jnp.float32),
                        (seq(x), seq(delta), seq(b), seq(c), pos))
    return jnp.moveaxis(o.reshape(T, B, H, P), 0, 1)


def mamba_mixer(a: Arch, mm: Callable, p, y, fault: Optional[str] = None):
    """``fault``: "state_reset", "no_skip" (``D x`` left out), "norm_all_lanes"
    (the gated norm over all held lanes at once)."""
    B, T, _ = y.shape
    H, G, P, N = a.ssm_heads, a.ssm_groups, a.ssm_head_dim, a.ssm_state
    inner, bc = a.d_inner, a.d_bc
    zxbcdt = mm(y, p["in_proj"]["kernel"])
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc],
                  zxbcdt[..., 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :inner].reshape(B, T, H, P)
    b = xbc[..., inner:inner + bc].reshape(B, T, G, N)
    c = xbc[..., inner + bc:].reshape(B, T, G, N)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    o = recurrence(x, delta, -jnp.exp(p["A_log"]), b, c,
                   reset_every=a.chunk if fault == "state_reset" else None)
    if fault != "no_skip":
        o = o + p["D"][:, None] * x
    o = o.reshape(B, T, inner) * jax.nn.silu(z)
    groups = 1 if fault == "norm_all_lanes" else G
    o = o.reshape(B, T, groups, inner // groups)
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + a.norm_eps)
    return mm(o.reshape(B, T, inner) * p["o_norm"], p["out_proj"]["kernel"])


def attention_mixer(a: Arch, mm: Callable, p, y):
    B, T, _ = y.shape
    q = mm(y, p["q"]["kernel"]).reshape(B, T, a.n_heads, a.head_dim)
    k = mm(y, p["k"]["kernel"]).reshape(B, T, a.n_kv_heads, a.head_dim)
    v = mm(y, p["v"]["kernel"]).reshape(B, T, a.n_kv_heads, a.head_dim)
    o = _attention(q, k, v, None)
    return mm(o.reshape(B, T, a.n_heads * a.head_dim), p["attn_out"]["kernel"])


def _relu2(t):
    return jnp.square(jax.nn.relu(t))


def routing_of(a: Arch, p, y, bias_on_weights: bool = False):
    """(the experts chosen (.., k), their weights (.., k)) of the normed rows
    ``y``: sigmoid scores over all the experts in float32 (never through the
    control's lower-precision product), the ``top_k`` largest of scores +
    selection bias, the weights from the scores alone, normalised, times the
    scaling factor. ``bias_on_weights`` plants a fault."""
    scores = jax.nn.sigmoid(y @ p["router"])
    biased = scores + p["router_bias"]
    _, chosen = jax.lax.top_k(biased, a.top_k)
    top = jnp.take_along_axis(biased if bias_on_weights else scores, chosen, axis=-1)
    return chosen, a.routed_scale * top / jnp.sum(top, axis=-1, keepdims=True)


def routed_part(a: Arch, mm: Callable, p, u, y, first_expert: Optional[int] = None,
                fault: Optional[str] = None):
    """The held experts' part of the routed sum, in the latent width: each
    held expert over every token's latent row ``u`` (B, T, L), times the
    weight of the tokens that chose it (0 for the rest); the experts one after
    another, each rematerialised in the backward. ``first_expert`` overrides
    the architecture's share (a test adds all the shares up)."""
    first = a.first_expert if first_expert is None else first_expert
    chosen, weights = routing_of(a, p, y, bias_on_weights=fault == "bias_on_weights")
    mine = chosen[..., None] == first + jnp.arange(a.held)            # (B, T, k, held)
    masks = jnp.moveaxis(jnp.sum(jnp.where(mine, weights[..., None], 0.0), axis=-2), -1, 0)
    if fault == "drop_pair":
        hit = jnp.argmax(masks.reshape(-1) > 0)
        masks = masks.reshape(-1).at[hit].set(0.0).reshape(masks.shape)

    @jax.checkpoint
    def expert(u, up, down, m):
        return mm(_relu2(mm(u, up)), down) * m[..., None]

    def one_more(out, xs):
        return out + expert(u, *xs), None

    out, _ = jax.lax.scan(one_more, jnp.zeros_like(u),
                          (p["we_up"], p["we_down"], masks))
    return out


def latent_moe_mixer(a: Arch, mm: Callable, p, y, fault: Optional[str] = None,
                     first_expert: Optional[int] = None, shared: bool = True):
    u = mm(y, p["latent_down"]["kernel"])
    out = mm(routed_part(a, mm, p, u, y, first_expert, fault), p["latent_up"]["kernel"])
    if shared and fault != "no_shared":
        out = out + mm(_relu2(mm(y, p["shared_in"]["kernel"])), p["shared_out"]["kernel"])
    return out


def _layer(a: Arch, mm: Callable, kind: str, p, x, fault: Optional[str] = None,
           routing: Optional[list] = None):
    """``fault`` plants one for ``perf/tests``: "drop_pair", "state_reset",
    "no_skip", "norm_all_lanes", "bias_on_weights", "no_shared". ``routing``,
    a list, gains a routed layer's chosen experts."""
    y = _rms_norm(x, p["ln_1"]["scale"], a.norm_eps)
    if kind == MAMBA:
        return x + mamba_mixer(a, mm, p, y, fault)
    if kind == ATTENTION:
        return x + attention_mixer(a, mm, p, y)
    if routing is not None:
        routing.append(routing_of(a, p, y)[0])
    return x + latent_moe_mixer(a, mm, p, y, fault)


def _head(a: Arch, mm: Callable, top, x):
    """``top``: the leaves outside the stack (``ln_f``, ``lm_head``)."""
    return mm(_rms_norm(x, top["ln_f"]["scale"], a.norm_eps), top["lm_head"].T)


def forward(a: Arch, params, tokens, mm: Optional[Callable] = None,
            fault: Optional[str] = None, routing: Optional[list] = None):
    """(B, T) int tokens -> (B, T, V) float32 logits. ``mm(x, w)`` is the
    matrix product of activations (..., K) and weights (K, N); the control of
    ``perf/lib/refcheck.py`` passes a lower-precision one and changes nothing
    else. ``routing``, a list, gains every routed layer's chosen experts."""
    mm = mm or _plain_mm
    x = params["wte"][tokens]
    for n, kind in enumerate(a.kinds):
        layer = functools.partial(_layer, a, mm, kind, fault=fault, routing=routing)
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
    layer kind, none for the whole model. A layer's forward also returns the
    experts its router chose (an empty array where it has none)."""
    mul = mm or _plain_mm

    def layer(kind, p, x):
        routing: list = []
        out = _layer(a, mul, kind, p, x, routing=routing)
        return out, routing[0] if routing else jnp.zeros((0,), jnp.int32)

    def layer_back(kind, p, x, dy):
        _, vjp = jax.vjp(functools.partial(_layer, a, mul, kind), p, x)
        return vjp(dy)                                  # (dp, dx)

    def head_back(top, x, tokens):
        loss, (dtop, dx) = jax.value_and_grad(
            lambda t, h: _xent(_head(a, mul, t, h), tokens), argnums=(0, 1))(top, x)
        return loss, dtop, dx

    out = {"params": jax.jit(lambda k: seeded_params(a, k)),
           "layout": jax.jit(functools.partial(program_layout, a)),
           "embed": jax.jit(lambda wte, tokens: wte[tokens]),
           "embed_back": jax.jit(lambda wte, tokens, dx: jnp.zeros_like(wte).at[tokens].add(dx)),
           "head": jax.jit(functools.partial(_head, a, mul)),
           "head_back": jax.jit(head_back)}
    for kind in set(a.kinds):
        out["layer", kind] = jax.jit(functools.partial(layer, kind))
        out["layer_back", kind] = jax.jit(functools.partial(layer_back, kind))
    return out


def _unstack(a: Arch, params) -> Dict[str, Any]:
    """{"top": the leaves outside the layers, "layers": [each layer's own
    weights]}: what ``train`` updates piece by piece."""
    return {"top": {k: v for k, v in params.items() if k != "blocks"},
            "layers": [_layer_weights(a, params, n) for n in range(a.n_layers)]}


def _restack(a: Arch, pieces, xp) -> Dict[str, Any]:
    blocks = {f"l{n}": _nest({k: v[None] for k, v in flat(layer).items()})
              for n, layer in enumerate(pieces["layers"])}
    return dict(pieces["top"], blocks=blocks)


def _step(a: Arch, fns, update, state, tokens):
    """One AdamW step, the gradient layer by layer (``laguna.py``'s).
    ``state``: ``{"p", "m", "v"}``, each ``{"top", "layers"}``, and ``"t"``."""
    p, m, v, t = state["p"], state["m"], state["v"], state["t"]

    def put(where, key, grads):
        new = update(p[where][key], grads, m[where][key], v[where][key], t)
        for tree, leaf in zip((p, m, v), new):
            tree[where][key] = leaf

    x = fns["embed"](p["top"]["wte"], tokens)
    inputs = []
    for n, kind in enumerate(a.kinds):
        inputs.append(x)
        x, _ = fns["layer", kind](p["layers"][n], x)
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

    fns, update = _jitted(a, mm), _update(float(lr))
    with jax.default_matmul_precision("highest"):
        key = seed_key(seed)
        state = {"p": _unstack(a, fns["params"](key)), "t": jnp.zeros((), jnp.int32)}
        for moment in ("m", "v"):
            state[moment] = jax.tree_util.tree_map(jnp.zeros_like, state["p"])
        losses = [_step(a, fns, update, state, jnp.asarray(tokens)) for tokens in batches]
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


def _routing_disagreement(a: Arch, seed: int, tokens, mine) -> None:
    """Print the share of (token, slot) pairs that the program routes to
    another expert than this reference does, layer by layer, on ``tokens``
    (``laguna.py``'s): the program's own model (the configuration's builder,
    preset and overrides; kernels where the backend has them) from the same
    seeded weights, its routers' choices through
    ``hints["routed"]["routing_fn"]``."""
    import importlib

    import numpy as np

    if not a.builder:
        return
    module, _, attr = a.builder.partition(":")
    spec = getattr(importlib.import_module(module), attr)(
        a.preset, seq_len=int(np.shape(tokens)[-1]), **dict(a.overrides))
    fn = (spec.hints.get("routed") or {}).get("routing_fn")
    if fn is None:
        return
    fns = _jitted(a, None)          # (the weights: two small programs that are there)
    theirs = np.asarray(jax.jit(fn)(
        fns["layout"](fns["params"](seed_key(seed))), jnp.asarray(tokens)))
    mine = np.asarray(mine).reshape(theirs.shape[0], -1, theirs.shape[-1])
    per_layer = []
    for ref_l, sys_l in zip(mine, theirs):
        same = (ref_l[:, :, None] == sys_l[:, None, :]).any(-1)       # (T, k)
        per_layer.append(1.0 - float(same.mean()))
    held = (mine >= a.first_expert) & (mine < a.first_expert + a.held)
    print("perf: routing: share of (token, slot) pairs the program routes to "
          "another expert than the reference, by routed layer: "
          + ", ".join(f"{x:.6f}" for x in per_layer)
          + f"; all layers {float(np.mean(per_layer)):.6f}; the reference holds "
          f"{held.mean() * a.top_k:.3f} pairs a token", flush=True)


def logits_of(a: Arch, seed: int, tokens, mm: Optional[Callable] = None):
    """Float32 logits of the seeded weights on ``tokens``. The reference's own
    call (no ``mm``) also prints how the program's routing of ``tokens``
    differs from the reference's (``_routing_disagreement``)."""
    if mm is None:
        # what the search's compiles left in the allocator goes back first
        _say_host_memory("the program's search and window")
    fns = _jitted(a, mm)
    with jax.default_matmul_precision("highest"):
        params = _unstack(a, fns["params"](seed_key(seed)))
        x = fns["embed"](params["top"]["wte"], jnp.asarray(tokens))
        routing = []
        for n, kind in enumerate(a.kinds):
            x, chosen = fns["layer", kind](params["layers"][n], x)
            if chosen.size:
                routing.append(chosen)
        logits = fns["head"]({k: params["top"][k] for k in ("ln_f", "lm_head")}, x)
        del params, x
    if mm is None:      # the program at its own precision, outside "highest"
        _routing_disagreement(a, seed, tokens, routing)
        _say_host_memory("the logits")
    return logits
