"""The plain reference for Ling-3.0-flash (inclusionAI/Ling-3.0-flash-VL, the
language model): Kimi-delta-attention layers and latent-attention layers, each
before a dense or a routed feed-forward, in straightforward ``jax.numpy``,
float32, matmul precision ``highest``.

A Python loop over the layers; the delta rule **token by token** (a
``lax.scan`` over the tokens in checkpointed pieces: no chunks, no
sub-blocks, no triangular solve); latent attention as a masked dense softmax
by blocks of query rows; every held expert over every token under the mask of
the tokens that chose it (no sort, no grouped product, no row buffer); the
loss as a log-softmax over the materialised logits; AdamW written out
(``perf/reference/gpt.py``'s). No kernels, no fused head, no flax, nothing of
``saturn_tpu``. Same module contract as ``gpt.py``: ``arch_from_config``,
``seed_key``, ``program_params``, ``logits_of``, ``train``.

The model (``config.json`` gives sizes and switches; what it does not say is
marked + and listed under ``assumed`` in the configuration file: the
mechanisms' own papers, Kimi Linear arXiv:2510.26692, DeepSeek-V2
arXiv:2405.04434, DeepSeek-V3 arXiv:2412.19437, and the ``bailing_moe``
family's convention as remembered). With ``N(x) = x / sqrt(mean(x^2) + eps) *
g`` and no bias anywhere, a block is + ``h = x + Mixer(N(x)); out = h +
FF(N(h))``; after the last a final ``N`` and an untied head. Published layer
``l`` has the MLA mixer where ``(l + 1) % layer_group_size == 0`` and KDA
elsewhere, SwiGLU ``intermediate_size`` where ``l < first_k_dense_replace``
and the routed layer after.

KDA (H heads of d = ``head_dim`` keys and values, y = N(x)):

    q = l2(silu(conv4(y Wq))) d^-1/2 +;  k = l2(silu(conv4(y Wk)));
    v = silu(conv4(y Wv))                 causal, depthwise, 4 taps, no bias
    beta_t = sigmoid(y w_beta)            (a scalar a head)
    g_t = kda_lower_bound sigmoid(exp(A_log) (y Wa + dt_bias)) +   (a channel)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t;   o <- N_head(o) sigmoid(y Wg);   out = o Wo

MLA (H heads; nope 128 | rope 64 lanes of q and k, 128 of v; latent 512):

    [qc | qr] = y Wq;  [c | kr] = y Wkva;  c <- N(c);  [kc | v] = c Wkvb
    q = N_h([qc | qr]) +;  k = N_h([kc | kr]) +     (kr shared by the heads;
                                 N_h over a head's 192 lanes, one gain)
    rotary (interleaved pairs +, base rope_theta) on the 64 rope lanes
    o = softmax(q k^T / sqrt(192), causal) v;  o <- o sigmoid(y Wg);  out = o Wo

Routed layer (y = N(h)):

    s = sigmoid(y Wr)  (float32, all 512);  t = s + b   (b in the choice only)
    groups of 64 consecutive experts, a group's score the sum of its two
    largest t; the 4 best groups stay +; I = the 8 largest t among them
    w_e = 2.5 s_e / sum_{e' in I} s_e'
    FF = sum_{e in I, e held} w_e E_e(y) + E_shared(y)     (SwiGLU 768 each)

**The held share**: ``Arch.held`` experts from ``Arch.first_expert`` on have
tables here; ``Arch.n_heads`` heads of each mixer (whole heads: the per-head
matrices' columns of the held heads; ``Wkva`` and its norm whole).

**How it fits a 16 GB chip at the published widths**: as ``laguna.py``:
``train`` takes the gradient layer by layer, attention goes by blocks of
query rows, the held experts one after another and the recurrence in pieces
of ``SCAN_PIECE`` tokens, each rematerialised in the backward.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from perf.reference.gpt import _nest, flat, seed_key
from perf.reference.laguna import (_orthonormal_frame, _plain_mm, _rms_norm,
                                   _say_host_memory, _swiglu, _update, _xent)

__all__ = ["Arch", "arch_from_config", "seed_key", "seeded_params",
           "program_layout", "program_params", "forward", "loss_fn", "train",
           "logits_of", "routed_part", "routing_of", "kda_mixer", "mla_mixer",
           "kda_recurrence", "routed_ff"]

SCAN_PIECE = 64
ATTN_Q_BLOCK = 128
KDA, MLA = "kda", "mla"
DENSE, SPARSE = "dense", "sparse"


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file. Head
    and expert counts are the *held* ones."""

    vocab_size: int                  # rows of the embedding and the head held
    d_model: int
    kinds: Tuple[str, ...]           # the mixer of every layer held
    ffs: Tuple[str, ...]             # "dense" | "sparse", every layer held
    n_heads: int                     # heads held, of either mixer
    head_dim: int                    # a KDA head's keys and values
    conv_taps: int
    gate_floor: float                # kda_lower_bound
    kv_latent: int
    qk_nope: int
    qk_rope: int
    v_head: int
    rope_theta: float
    d_dense: int                     # a leading layer's SwiGLU width
    experts: int                     # the router's outputs
    held: int                        # experts whose tables are here
    first_expert: int
    top_k: int
    groups: int
    groups_kept: int
    d_expert: int
    d_shared: int
    routed_scale: float
    norm_eps: float
    family: str = "ling"

    @property
    def n_layers(self) -> int:
        return len(self.kinds)

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
    so ``seq_len`` sizes nothing. ``run.layers`` names the published layers
    held, [first, last); the mixer and the feed-forward of each follow from
    the published ``layer_group_size`` and ``first_k_dense_replace``. The
    reduced keys hold what is held here, ``published`` what the source has."""
    del seq_len
    run = cfg["run"]
    published = cfg.get("published", {})
    first, last = (int(x) for x in run["layers"])
    if last - first != int(cfg["num_hidden_layers"]):
        raise ValueError(f"run.layers {run['layers']} are not the "
                         f"{cfg['num_hidden_layers']} layers the file holds")
    period = int(cfg["layer_group_size"])
    dense_until = int(published.get("first_k_dense_replace", cfg["first_k_dense_replace"]))
    layers = range(first, last)
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg[key][l] for l in layers):
            raise ValueError(f"{key} is not 0 in the layers held: the clamp's form "
                             "is not published and not built")
    if cfg.get("score_function") != "sigmoid" or cfg.get("q_lora_rank") is not None:
        raise ValueError("the reference knows sigmoid scores and a full-rank q")
    return Arch(
        vocab_size=int(run["vocab_size"]),
        d_model=int(cfg["hidden_size"]),
        kinds=tuple(MLA if (l + 1) % period == 0 else KDA for l in layers),
        ffs=tuple(DENSE if l < dense_until else SPARSE for l in layers),
        n_heads=int(cfg["num_attention_heads"]),
        head_dim=int(cfg["head_dim"]),
        conv_taps=int(cfg["short_conv_kernel_size"]),
        gate_floor=float(cfg["kda_lower_bound"]),
        kv_latent=int(cfg["kv_lora_rank"]),
        qk_nope=int(cfg["qk_nope_head_dim"]),
        qk_rope=int(cfg["qk_rope_head_dim"]),
        v_head=int(cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        d_dense=int(cfg["intermediate_size"]),
        experts=int(published.get("num_experts", cfg["num_experts"])),
        held=int(cfg["num_experts"]),
        first_expert=0,
        top_k=int(cfg["num_experts_per_tok"]),
        groups=int(cfg["n_group"]),
        groups_kept=int(cfg["topk_group"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        d_shared=int(cfg["moe_shared_expert_intermediate_size"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        norm_eps=float(cfg["rms_norm_eps"]),
    )


# ------------------------------------------------------------------ weights
#: The seeded values that are not plain normal draws (the benchmark's to
#: choose, listed under ``assumed``). **Routing is discrete**
#: (``perf/reference/laguna.py::AFFINITY`` has the arithmetic), so a token's
#: routing follows its identity: every token id leans towards ``top_k``
#: columns of one orthonormal frame (signed Hadamard columns of the largest
#: power of two of lanes the stream has: 2048 of 2560), its embedding row a
#: unit-RMS normal row plus ``AFFINITY`` times their sum. Six routers of 512
#: columns do not fit 2560 lanes side by side, so **the routers share the one
#: frame of 512 columns**, each under its own permutation of the experts that
#: keeps the groups whole (a permutation of the 8 groups and one of the 64
#: places in a group): a token leans to the same 8 columns in every layer, and
#: they are other experts in each. A row's RMS is sqrt(1 + 256 x 8 / 2560) =
#: 1.34, a chosen logit 0.25 x 16 / 1.34 = 2.98 (sigmoid 0.95) over a
#: background of deviation 0.19.
#: **The draw is made in groups**, so that the limit binds: an id's 8 columns
#: lie in 3 or 4 of the 8 groups (``groups_kept`` or one fewer), and one id in ``FIVE`` in 5, as 2 + 2 + 2 +
#: 1 + 1, with a ninth column, the stand-in, in the fourth group at
#: ``STAND_IN`` of the lean: without the limit such a token takes its 8 strong
#: columns; under it the fifth group (score 0.95 + a background 0.6) loses to
#: the fourth (0.95 + 0.90), its expert is not to be had, and the stand-in
#: (0.90 against a background of 0.6) is the eighth, whatever the rounding.
#: ``OUT``: the matrices that write to the stream (``attn_out``, ``mlp_out``,
#: ``shared_out``, ``we_down``) are normal with deviation 0.02 / sqrt(2 x 42)
#: (+ a residual branch's output divided by the root of twice the published
#: depth, GPT-2's rule). At 0.02 a KDA layer writes rows of RMS 0.6 (a
#: unit-RMS normed state under a gate of a half, 4096 lanes) and a SwiGLU of
#: 6144 0.5 beside an embedding row's 1.34, the lean is diluted layer by
#: layer, and the first chip readings (PR 45) had 0 / 0 / 0 / 1 / 7 / 1 of a
#: layer's 65536 pairs routed differently from the third routed layer on:
#: with 1029 held pairs in a layer one flipped pair is 3 % of a router's
#: gradient, and ``grad_rel_rms`` read 0.019-0.027 at a router (limit 0.03).
#: ``GATE``: ``dt_bias`` is normal about -3.9 with deviation 1.1 and ``A_log``
#: uniform in +-0.22, so that with ``Wa``'s unit-deviation logit the gate's
#: ``g`` has its median near -0.1 and one channel in a hundred under -2: a
#: state that forgets at once, or never, would show nothing.
AFFINITY = 16.0
ROUTER_COLUMN = 0.25
BIAS = 0.02
FIVE = 16
STAND_IN = 0.75
OUT = 0.02 / math.sqrt(2.0 * 42.0)
GATE = (-3.9, 1.1, 0.22)


def _matrix(z):
    return 0.02 * z


def _gain(z):
    return 1.0 + 0.02 * z


def _out(z):
    return OUT * z


def _mixer_shapes(a: Arch, at: str, lead: Tuple[int, ...], kind: str):
    D, H = a.d_model, a.n_heads
    out = {at + "ln_1/scale": (lead + (D,), _gain),
           at + "ln_2/scale": (lead + (D,), _gain),
           at + "attn_gate/kernel": (lead + (D, H), _matrix)}
    if kind == KDA:
        d = a.head_dim
        out.update({
            at + "lin_q/kernel": (lead + (D, H * d), _matrix),
            at + "lin_k/kernel": (lead + (D, H * d), _matrix),
            at + "lin_v/kernel": (lead + (D, H * d), _matrix),
            at + "lin_a/kernel": (lead + (D, H * d), _matrix),
            at + "lin_b/kernel": (lead + (D, H), _matrix),
            at + "conv_q": (lead + (a.conv_taps, H * d), None),
            at + "conv_k": (lead + (a.conv_taps, H * d), None),
            at + "conv_v": (lead + (a.conv_taps, H * d), None),
            at + "A_log": (lead + (H,), None),
            at + "dt_bias": (lead + (H * d,), lambda z: GATE[0] + GATE[1] * z),
            at + "o_norm/scale": (lead + (d,), _gain),
            at + "attn_out/kernel": (lead + (H * d, D), _out),
        })
    else:
        qk = a.qk_nope + a.qk_rope
        out.update({
            at + "mla_q/kernel": (lead + (D, H * qk), _matrix),
            at + "mla_kv_a/kernel": (lead + (D, a.kv_latent + a.qk_rope), _matrix),
            at + "kv_norm/scale": (lead + (a.kv_latent,), _gain),
            at + "mla_kv_b/kernel": (lead + (a.kv_latent, H * (a.qk_nope + a.v_head)), _matrix),
            at + "q_norm": (lead + (qk,), _gain),
            at + "k_norm": (lead + (qk,), _gain),
            at + "attn_out/kernel": (lead + (H * a.v_head, D), _out),
        })
    return out


def _shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], Optional[Callable]]]:
    """leaf path -> (shape, value of a standard normal draw; None: a uniform
    draw, ``seeded_params``'s). Paths are the program's (``lead/l<i>/...``;
    ``blocks/l<i>/...`` with a leading axis of periods)."""
    P, D = a.n_periods, a.d_model
    out: Dict[str, Tuple[Tuple[int, ...], Optional[Callable]]] = {
        "wte": ((a.vocab_size, D), lambda z: z),
        "lm_head": ((a.vocab_size, D), _matrix),
        "ln_f/scale": ((D,), _gain),
    }
    for i in range(a.lead):
        at = f"lead/l{i}/"
        out.update(_mixer_shapes(a, at, (), a.kinds[i]))
        out.update({at + "mlp_gate/kernel": ((D, a.d_dense), _matrix),
                    at + "mlp_in/kernel": ((D, a.d_dense), _matrix),
                    at + "mlp_out/kernel": ((a.d_dense, D), _out)})
    for i in range(a.period):
        at = f"blocks/l{i}/"
        out.update(_mixer_shapes(a, at, (P,), a.kinds[a.lead + i]))
        F, S = a.d_expert, a.d_shared
        out.update({
            at + "router": ((P, D, a.experts), _matrix),
            at + "router_bias": ((P, a.experts), lambda z: BIAS * z),
            at + "we_gate": ((P, a.held, D, F), _matrix),
            at + "we_up": ((P, a.held, D, F), _matrix),
            at + "we_down": ((P, a.held, F, D), _out),
            at + "shared_gate/kernel": ((P, D, S), _matrix),
            at + "shared_in/kernel": ((P, D, S), _matrix),
            at + "shared_out/kernel": ((P, S, D), _out),
        })
    return out


def token_columns(a: Arch, key):
    """(the ``top_k`` frame columns every token id leans to (V, k), its
    stand-in column (V,), whether it is a five-group id (V,)): the draw in
    groups of the note above. Comparisons, sorts and gathers only."""
    V, E, G, k = a.vocab_size, a.experts, a.groups, a.top_k
    per = E // G
    keys = [jax.random.fold_in(key, i) for i in range(3)]
    draw = jax.random.uniform(keys[0], (V, G, per))
    order = jnp.argsort(jax.random.uniform(keys[1], (V, G)), axis=-1)     # groups, best first
    u = jax.random.uniform(keys[2], (V,))
    five = u < 1.0 / FIVE
    n_groups = jnp.where(u < 0.5 + 0.5 / FIVE, max(a.groups_kept - 1, 1), a.groups_kept)
    rank = jnp.argsort(order, axis=-1)                                    # a group's place
    # 3 or 4 groups: the k largest draws among the experts of the first groups
    masked = jnp.where((rank < n_groups[:, None])[..., None], draw, -1.0).reshape(V, E)
    plain = jax.lax.top_k(masked, k)[1]
    # 5 groups, 2 + 2 + 2 + 1 + 1 (k = 8), the stand-in the fourth group's second
    best2 = jax.lax.top_k(draw, 2)[1] + (jnp.arange(G) * per)[None, :, None]   # (V, G, 2)
    in_order = jnp.take_along_axis(best2, order[..., None], axis=1)       # (V, G, 2)
    if 5 <= G and k == 8 and per >= 2:
        spread = jnp.concatenate([in_order[:, :3].reshape(V, 6), in_order[:, 3:5, 0]], axis=1)
        stand_in = in_order[:, 3, 1]
    else:       # a preset too small for the pattern: no such id
        spread, stand_in, five = plain, plain[:, 0], jnp.zeros_like(five)
    return jnp.where(five[:, None], spread, plain), stand_in, five


def expert_permutations(a: Arch, key, n: int):
    """(n, E) int: router column ``e`` of routed layer ``l`` is frame column
    ``perm[l, e]``; groups stay whole (a permutation of the groups, one of the
    places; all the layers' in two sorts)."""
    per = a.experts // a.groups
    order = lambda i, size: jnp.argsort(
        jax.random.uniform(jax.random.fold_in(key, i), (n, size)), axis=-1)
    return (order(0, a.groups)[:, :, None] * per + order(1, per)[:, None, :]).reshape(n, -1)


def seeded_params(a: Arch, key) -> Dict[str, Any]:
    """Float32 weights from ``key`` (``seed_key(seed)``), every leaf random
    (the norms' gains too), **cut from one normal and one uniform draw** (a
    draw a leaf was 7 MiB more, compressed, in each of the three compiled
    programs that hold the seeded init, in a compile cache the cell nearly
    fills: PERF.md, Findings PR 45); the routers' columns one orthonormal frame under a
    permutation a layer, the embedding's rows leaning towards their columns
    (the note above). Traceable, and free of matrix products: what is seeded
    must not depend on the precision a program is traced at."""
    shapes = sorted(_shapes(a).items())
    sizes = [0, 0]                     # of the normal leaves, of the uniform ones
    for _, (shape, value) in shapes:
        sizes[value is None] += math.prod(shape)
    draws = [jax.random.normal(jax.random.fold_in(key, 0), (sizes[0],), jnp.float32),
             jax.random.uniform(jax.random.fold_in(key, 1), (sizes[1],), jnp.float32, -1.0, 1.0)]
    out, at = {}, [0, 0]
    for path, (shape, value) in shapes:
        which, n = value is None, math.prod(shape)
        z = draws[which][at[which]:at[which] + n].reshape(shape)
        at[which] += n
        if not which:
            out[path] = value(z)
        else:           # A_log; a convolution's taps: +-1 / sqrt(taps)
            out[path] = z * (GATE[2] if path.endswith("/A_log")
                             else 1.0 / math.sqrt(a.conv_taps))
    if a.n_periods * a.period:
        lanes = 1 << (a.d_model.bit_length() - 1)
        frame = jnp.pad(_orthonormal_frame(lanes, a.experts, jax.random.fold_in(key, 999)),
                        ((0, a.d_model - lanes), (0, 0)))                 # (D, E)
        perms = expert_permutations(a, jax.random.fold_in(key, 2000), a.period * a.n_periods)
        for n, (i, p) in enumerate((i, p) for i in range(a.period)
                                   for p in range(a.n_periods)):
            out[f"blocks/l{i}/router"] = out[f"blocks/l{i}/router"].at[p].set(
                ROUTER_COLUMN * frame[:, perms[n]])
        own, stand_in, five = token_columns(a, jax.random.fold_in(key, 1000))
        lean = jnp.zeros_like(out["wte"])
        for slot in range(a.top_k):      # sums of rows, in a fixed order: no product
            lean = lean + frame.T[own[:, slot]]
        lean = lean + jnp.where(five[:, None], STAND_IN, 0.0) * frame.T[stand_in]
        out["wte"] = out["wte"] + AFFINITY * lean
    return _nest(out)


def _rope_perm(a: Arch, heads: int):
    """Lane order of the program's q (one head after another: nope lanes,
    then the rope lanes' evens, then their odds) in terms of the published
    one (interleaved pairs)."""
    qk = a.qk_nope + a.qk_rope
    head = list(range(a.qk_nope)) + list(range(a.qk_nope, qk, 2)) \
        + list(range(a.qk_nope + 1, qk, 2))
    return [h * qk + j for h in range(heads) for j in head]


def _layers_of(a: Arch):
    for i in range(a.lead):
        yield "lead", f"l{i}", a.kinds[i]
    for i in range(a.period):
        yield "blocks", f"l{i}", a.kinds[a.lead + i]


def program_layout(a: Arch, tree: Dict[str, Any], xp=jnp) -> Dict[str, Any]:
    """A tree of the parameters' structure (weights, gradients, Adam moments)
    in the layout ``saturn_tpu/models/gpt2.py`` trains: an MLA layer's rotary
    lanes in split-half order (q's columns a head, the shared key's columns
    of ``mla_kv_a``, the two head norms' gains)."""
    out = dict(tree)
    take = lambda t, idx: xp.take(t, xp.asarray(idx, dtype=xp.int32), axis=-1)
    for where, name, kind in _layers_of(a):
        if kind != MLA:
            continue
        out[where] = dict(out[where])
        layer = dict(out[where][name])
        one = _rope_perm(a, 1)
        layer["mla_q"] = {"kernel": take(layer["mla_q"]["kernel"], _rope_perm(a, a.n_heads))}
        L = a.kv_latent
        layer["mla_kv_a"] = {"kernel": take(
            layer["mla_kv_a"]["kernel"],
            list(range(L)) + [L + j - a.qk_nope for j in one[a.qk_nope:]])}
        layer["q_norm"], layer["k_norm"] = take(layer["q_norm"], one), take(layer["k_norm"], one)
        out[where][name] = layer
    return out


def program_params(a: Arch, key) -> Dict[str, Any]:
    """The seeded weights as the program is handed them. Traceable."""
    return program_layout(a, seeded_params(a, key))


def _layer_weights(a: Arch, params, n: int):
    """Layer ``n``'s own weights out of the tree."""
    if n < a.lead:
        return params["lead"][f"l{n}"]
    period, i = divmod(n - a.lead, a.period)
    return jax.tree_util.tree_map(lambda x: x[period], params["blocks"][f"l{i}"])


# ------------------------------------------------------------------ forward
def _l2(t):
    return t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)


def _causal_conv(x, taps):
    """Depthwise, causal: lane by lane, ``out_t = sum_j taps[j] x_{t - (K-1-j)}``
    (tokens before the first are zeros); reach K."""
    K, T = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + T] for j in range(K))


def kda_recurrence(q, k, v, g, beta, fault: Optional[str] = None):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T;
    o_t = S_t^T q_t``, token by token: ``q`` / ``k`` / ``v`` / ``g`` (B, T, H,
    d), ``beta`` (B, T, H) -> (B, T, H, d). The scan runs in pieces of
    ``SCAN_PIECE`` tokens, each rematerialised in the backward. ``fault``
    plants one: "bf16_state" (the state rounded to bf16 after every token),
    "scalar_gate" (the channels' mean decay for every channel), "decay_after"
    (what is erased is read off the state before it decays)."""
    B, T, H, d = q.shape
    if fault == "scalar_gate":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)

    def token(S, xs):                                   # S: (B, H, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        before = S
        S = jnp.exp(g_t)[..., None] * S
        erased = jnp.sum(k_t[..., None] * (before if fault == "decay_after" else S), axis=-2)
        S = S + k_t[..., None] * (b_t[..., None] * (v_t - erased))[..., None, :]
        if fault == "bf16_state":
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, jnp.sum(q_t[..., None] * S, axis=-2)

    @jax.checkpoint
    def piece(S, xs):
        return jax.lax.scan(token, S, xs)

    n = SCAN_PIECE if T % SCAN_PIECE == 0 else T
    seq = lambda t: jnp.moveaxis(t, 1, 0).reshape(T // n, n, *t.shape[:1], *t.shape[2:])
    _, o = jax.lax.scan(piece, jnp.zeros((B, H, d, v.shape[-1]), jnp.float32),
                        tuple(seq(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(T, B, H, v.shape[-1]), 0, 1)


def kda_mixer(a: Arch, mm: Callable, p, y, fault: Optional[str] = None):
    B, T, _ = y.shape
    d = a.head_dim
    H = p["lin_b"]["kernel"].shape[-1]          # the heads these weights hold
    conv = lambda name, w: jax.nn.silu(_causal_conv(mm(y, p[name]["kernel"]), p[w]))
    q = _l2(conv("lin_q", "conv_q").reshape(B, T, H, d)) / math.sqrt(d)
    k = _l2(conv("lin_k", "conv_k").reshape(B, T, H, d))
    v = conv("lin_v", "conv_v").reshape(B, T, H, d)
    beta = jax.nn.sigmoid(mm(y, p["lin_b"]["kernel"]))
    logit = jnp.repeat(jnp.exp(p["A_log"]), d) * (mm(y, p["lin_a"]["kernel"]) + p["dt_bias"])
    g = (a.gate_floor * jax.nn.sigmoid(logit)).reshape(B, T, H, d)
    o = kda_recurrence(q, k, v, g, beta, fault)
    o = _rms_norm(o, p["o_norm"]["scale"], a.norm_eps)
    o = o * jax.nn.sigmoid(mm(y, p["attn_gate"]["kernel"]))[..., None]
    return mm(o.reshape(B, T, H * d), p["attn_out"]["kernel"])


def _rotary_tail(t, rope: int, theta: float):
    """Interleaved rotary on the last ``rope`` lanes of (B, T, H, lanes):
    lanes (2j, 2j + 1) of them rotated by position x ``theta^(-2j / rope)``."""
    T = t.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(angles)[None, :, None, :], jnp.cos(angles)[None, :, None, :]
    rest, rot = t[..., :-rope], t[..., -rope:]
    even, odd = rot[..., 0::2], rot[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return jnp.concatenate([rest, turned.reshape(rot.shape)], axis=-1)


def _dense_attention(q, k, v, scale: float):
    """Causal softmax attention on q, k (B, T, H, d_qk) and v (B, T, H, d_v),
    as a masked dense softmax, by blocks of ``ATTN_Q_BLOCK`` query rows (a
    ``lax.scan``, each block rematerialised in the backward)."""
    B, T, H, _ = q.shape
    block = ATTN_Q_BLOCK if T % ATTN_Q_BLOCK == 0 else T

    @jax.checkpoint
    def rows(q_rows, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) * scale
        seen = jnp.arange(T)[None, :] <= (first + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def one_block(_, xs):
        return None, rows(*xs)

    _, out = jax.lax.scan(one_block, None, (
        jnp.moveaxis(q.reshape(B, T // block, block, H, q.shape[-1]), 1, 0),
        jnp.arange(0, T, block, dtype=jnp.int32)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, v.shape[-1])


def mla_mixer(a: Arch, mm: Callable, p, y, fault: Optional[str] = None):
    """``fault``: "scale_128" (the scores over sqrt(128)), "key_not_shared"
    (every head but the first reads the rotary key a lane on)."""
    B, T, _ = y.shape
    dn, dr, dv, L = a.qk_nope, a.qk_rope, a.v_head, a.kv_latent
    H = p["attn_gate"]["kernel"].shape[-1]
    q = mm(y, p["mla_q"]["kernel"]).reshape(B, T, H, dn + dr)
    kva = mm(y, p["mla_kv_a"]["kernel"])
    c = _rms_norm(kva[..., :L], p["kv_norm"]["scale"], a.norm_eps)
    kvb = mm(c, p["mla_kv_b"]["kernel"]).reshape(B, T, H, dn + dv)
    kr = jnp.broadcast_to(kva[:, :, None, L:], (B, T, H, dr))
    if fault == "key_not_shared":
        kr = jnp.concatenate([kr[:, :, :1], jnp.roll(kr[:, :, 1:], 1, axis=-1)], axis=2)
    k = jnp.concatenate([kvb[..., :dn], kr], axis=-1)
    q = _rotary_tail(_rms_norm(q, p["q_norm"], a.norm_eps), dr, a.rope_theta)
    k = _rotary_tail(_rms_norm(k, p["k_norm"], a.norm_eps), dr, a.rope_theta)
    scale = 1.0 / math.sqrt(dn if fault == "scale_128" else dn + dr)
    o = _dense_attention(q, k, kvb[..., dn:], scale)
    o = o * jax.nn.sigmoid(mm(y, p["attn_gate"]["kernel"]))[..., None]
    return mm(o.reshape(B, T, H * dv), p["attn_out"]["kernel"])


def routing_of(a: Arch, p, y, limit: bool = True, bias_on_weights: bool = False):
    """(the experts chosen (.., k), their weights (.., k)) of the normed rows
    ``y``: sigmoid scores over all the experts in float32 (never through the
    control's lower-precision product), ``t`` = scores + selection bias; the
    ``groups_kept`` groups with the largest sum of their two largest ``t``
    stay, the choice is the ``top_k`` largest ``t`` among their experts; the
    weights come from the scores alone, normalised, times the scaling factor.
    ``limit=False`` leaves the group limit out; ``bias_on_weights`` plants a
    fault."""
    scores = jax.nn.sigmoid(y @ p["router"])
    biased = scores + p["router_bias"]
    choice = biased
    if limit and a.groups > 1:
        grouped = biased.reshape(*biased.shape[:-1], a.groups, a.experts // a.groups)
        of_group = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)          # (.., G)
        kept = jax.lax.top_k(of_group, a.groups_kept)[1]
        stays = jnp.any(kept[..., None] == jnp.arange(a.groups), axis=-2)  # (.., G)
        choice = jnp.where(stays[..., None], grouped, -jnp.inf).reshape(biased.shape)
    _, chosen = jax.lax.top_k(choice, a.top_k)
    top = jnp.take_along_axis(biased if bias_on_weights else scores, chosen, axis=-1)
    return chosen, a.routed_scale * top / jnp.sum(top, axis=-1, keepdims=True)


def routed_part(a: Arch, mm: Callable, p, y, first_expert: Optional[int] = None,
                fault: Optional[str] = None):
    """The held experts' part of the routed layer's output for normed rows
    ``y`` (B, T, D): each held expert over every token, times the weight of
    the tokens that chose it (0 for the rest); the experts one after another,
    each rematerialised in the backward. ``first_expert`` overrides the
    architecture's share (a test adds all the shares up)."""
    first = a.first_expert if first_expert is None else first_expert
    chosen, weights = routing_of(a, p, y, limit=fault != "no_group_limit",
                                 bias_on_weights=fault == "bias_on_weights")
    mine = chosen[..., None] == first + jnp.arange(a.held)            # (B, T, k, held)
    masks = jnp.moveaxis(jnp.sum(jnp.where(mine, weights[..., None], 0.0), axis=-2), -1, 0)
    if fault == "drop_pair":
        hit = jnp.argmax(masks.reshape(-1) > 0)
        masks = masks.reshape(-1).at[hit].set(0.0).reshape(masks.shape)

    @jax.checkpoint
    def expert(y, gate, up, down, m):
        return _swiglu(mm, y, gate, up, down) * m[..., None]

    def one_more(out, xs):
        return out + expert(y, *xs), None

    out, _ = jax.lax.scan(one_more, jnp.zeros_like(y),
                          (p["we_gate"], p["we_up"], p["we_down"], masks))
    return out


def routed_ff(a: Arch, mm: Callable, p, y, fault: Optional[str] = None,
              first_expert: Optional[int] = None, shared: bool = True):
    out = routed_part(a, mm, p, y, first_expert, fault)
    if shared and fault != "no_shared":
        out = out + _swiglu(mm, y, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
                            p["shared_out"]["kernel"])
    return out


#: a layer's leaves that belong to its second half (the feed-forward); the
#: rest are its mixer's
FF_LEAVES = ("ln_2", "mlp_gate", "mlp_in", "mlp_out", "router", "router_bias", "we_gate",
             "we_up", "we_down", "shared_gate", "shared_in", "shared_out")


def _halves(p):
    """A layer's weights as (its mixer's, its feed-forward's)."""
    return ({k: v for k, v in p.items() if k not in FF_LEAVES},
            {k: v for k, v in p.items() if k in FF_LEAVES})


def _mixer_half(a: Arch, mm: Callable, kind: str, p, x, fault: Optional[str] = None):
    """``h = x + Mixer(N(x))``. ``fault`` plants one for ``perf/tests``:
    "bf16_state", "scalar_gate", "decay_after" (KDA), "scale_128",
    "key_not_shared" (MLA)."""
    y = _rms_norm(x, p["ln_1"]["scale"], a.norm_eps)
    return x + (kda_mixer if kind == KDA else mla_mixer)(a, mm, p, y, fault)


def _ff_half(a: Arch, mm: Callable, ff: str, p, h, fault: Optional[str] = None,
             routing: Optional[list] = None):
    """``out = h + FF(N(h))``. ``fault``: "bias_on_weights", "no_group_limit",
    "drop_pair", "no_shared" (the routed layer's). ``routing``, a list, gains
    a routed layer's chosen experts."""
    y = _rms_norm(h, p["ln_2"]["scale"], a.norm_eps)
    if ff == DENSE:
        return h + _swiglu(mm, y, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                           p["mlp_out"]["kernel"])
    if routing is not None:
        routing.append(routing_of(a, p, y)[0])
    return h + routed_ff(a, mm, p, y, fault)


def _layer(a: Arch, mm: Callable, kind: str, ff: str, p, x,
           fault: Optional[str] = None, routing: Optional[list] = None):
    """A block: its mixer's half, then its feed-forward's."""
    return _ff_half(a, mm, ff, p, _mixer_half(a, mm, kind, p, x, fault), fault, routing)


def _head(a: Arch, mm: Callable, top, x):
    """``top``: the leaves outside the stack (``ln_f``, ``lm_head``)."""
    return mm(_rms_norm(x, top["ln_f"]["scale"], a.norm_eps), top["lm_head"].T)


def _sig(a: Arch, n: int):
    return a.kinds[n], a.ffs[n]


def forward(a: Arch, params, tokens, mm: Optional[Callable] = None,
            fault: Optional[str] = None, routing: Optional[list] = None):
    """(B, T) int tokens -> (B, T, V) float32 logits. ``mm(x, w)`` is the
    matrix product of activations (..., K) and weights (K, N); the control of
    ``perf/lib/refcheck.py`` passes a lower-precision one and changes nothing
    else. ``routing``, a list, gains every routed layer's chosen experts."""
    mm = mm or _plain_mm
    x = params["wte"][tokens]
    for n in range(a.n_layers):
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
    kind of **half a layer** (the KDA mixer, the MLA mixer, the dense
    feed-forward, the routed one), none for a whole layer or the whole model.
    (By whole layers the leading layer's KDA mixer and a routed layer's
    feed-forward were each compiled twice, and the cell's entries came to
    194 MB where the machine's compile cache keeps 192: every run compiled
    everything again, 710 s a run: my chip run, PR 45.) A feed-forward's
    forward also returns the experts its router chose (an empty array where
    it has none)."""
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
           "embed_back": jax.jit(lambda wte, tokens, dx: jnp.zeros_like(wte).at[tokens].add(dx)),
           "head": jax.jit(functools.partial(_head, a, mul)),
           "head_back": jax.jit(head_back)}
    for kind in set(a.kinds):
        out["mixer", kind] = jax.jit(functools.partial(mixer, kind))
        out["mixer_back", kind] = jax.jit(functools.partial(mixer_back, kind))
    for which in set(a.ffs):
        out["ff", which] = jax.jit(functools.partial(ff, which))
        out["ff_back", which] = jax.jit(functools.partial(ff_back, which))
    return out


#: programs compiled at a time by ``_compile_ahead`` (each holds a compiler's
#: working memory on a host that the program's own state already fills half of)
COMPILE_AHEAD = 6
_COMPILED_AHEAD: set = set()


def _compile_ahead(a: Arch, mm: Optional[Callable], fns, tokens) -> None:
    """Compile the programs of ``_jitted`` (the seeded weights, every half
    layer forward and backward, the head) for ``tokens``' shape side by side,
    ``COMPILE_AHEAD`` at a time and the backward ones (the longest) first,
    before the first of them is called: they are independent, each takes the chip's compiler 6-18 s (one big
    float32 product at ``highest`` alone takes it 4.6 s), and called one after
    another on an empty compile cache they held the cell's first run for 148 s
    (my chip run, PR 45). ``jit`` keeps what ``lower().compile()`` made, on
    whatever thread, so the calls that follow compile nothing. ``train`` and
    ``logits_of`` ask for it where JAX's persistent compile cache is on, which
    is where the benchmark runs; a CPU test compiles what it calls and no
    more. Once for an architecture, a matmul and a shape."""
    import concurrent.futures

    shape = tuple(jnp.shape(tokens))
    if (a, mm, shape) in _COMPILED_AHEAD:
        return
    _COMPILED_AHEAD.add((a, mm, shape))
    key = seed_key(0)
    params = jax.eval_shape(fns["params"], key)
    pieces = jax.eval_shape(functools.partial(_unstack, a), params)
    x = jax.ShapeDtypeStruct(shape + (a.d_model,), jnp.float32)
    ids = jax.ShapeDtypeStruct(shape, jnp.asarray(tokens).dtype)
    head = {k: pieces["top"][k] for k in ("ln_f", "lm_head")}
    jobs = [("params", (key,)), ("layout", (params,)),
            ("head", (head, x)), ("head_back", (head, x, ids))]
    halves = {_sig(a, n): _halves(pieces["layers"][n]) for n in range(a.n_layers)}
    for (kind, which), (mine, theirs) in halves.items():
        jobs += [(("mixer", kind), (mine, x)), (("mixer_back", kind), (mine, x, x)),
                 (("ff", which), (theirs, x)), (("ff_back", which), (theirs, x, x))]

    def one(job):
        name, args = job
        with jax.default_matmul_precision("highest"):   # (a thread's own setting)
            fns[name].lower(*args).compile()

    first = lambda job: "back" not in str(job[0]) and job[0] != "params"
    with concurrent.futures.ThreadPoolExecutor(COMPILE_AHEAD) as pool:
        list(pool.map(one, sorted(dict(jobs).items(), key=first)))


def _layer_forward(a: Arch, fns, n: int, p, x):
    """Layer ``n`` by its two programs -> (its mixer's output, its output,
    the experts its router chose)."""
    kind, which = _sig(a, n)
    mine, theirs = _halves(p)
    h = fns["mixer", kind](mine, x)
    out, chosen = fns["ff", which](theirs, h)
    return h, out, chosen


def _unstack(a: Arch, params) -> Dict[str, Any]:
    """{"top": the leaves outside the layers, "layers": [each layer's own
    weights]}: what ``train`` updates piece by piece."""
    return {"top": {k: v for k, v in params.items() if k not in ("blocks", "lead")},
            "layers": [_layer_weights(a, params, n) for n in range(a.n_layers)]}


def _restack(a: Arch, pieces, xp) -> Dict[str, Any]:
    lead = {f"l{i}": pieces["layers"][i] for i in range(a.lead)}
    blocks = {}
    for i in range(a.period):
        mine = [flat(pieces["layers"][a.lead + p * a.period + i])
                for p in range(a.n_periods)]
        blocks[f"l{i}"] = _nest({k: xp.stack([m[k] for m in mine]) for k in mine[0]})
    return dict(pieces["top"], lead=lead, blocks=blocks)


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
    for n in range(a.n_layers):
        h, out, _ = _layer_forward(a, fns, n, p["layers"][n], x)
        inputs.append((x, h))
        x = out
    head = {k: p["top"][k] for k in ("ln_f", "lm_head")}
    loss, dhead, dx = fns["head_back"](head, x, tokens)
    for k, g in dhead.items():
        put("top", k, g)
    del dhead, x
    for n in reversed(range(a.n_layers)):
        kind, which = _sig(a, n)
        mine, theirs = _halves(p["layers"][n])
        x, h = inputs.pop()
        dff, dh = fns["ff_back", which](theirs, h, dx)
        dmixer, dx = fns["mixer_back", kind](mine, x, dh)
        put("layers", n, {**dmixer, **dff})
        del dff, dmixer, dh, x, h
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
    if jax.config.jax_compilation_cache_dir:
        _compile_ahead(a, mm, fns, batches[0])
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


def _held_pairs(a: Arch, mine) -> None:
    """Print the held experts' pairs a routed layer on the check's tokens,
    beside the mean: what a row buffer must take."""
    import numpy as np

    mine = np.stack([np.asarray(m).reshape(-1, a.top_k) for m in mine])
    held = (mine >= a.first_expert) & (mine < a.first_expert + a.held)
    print("perf: routing: the reference holds "
          f"{held.mean() * a.top_k:.3f} pairs a token; held pairs by layer "
          + ", ".join(str(int(x)) for x in held.reshape(held.shape[0], -1).sum(-1))
          + f" (mean {mine.shape[1] * a.top_k * a.held / a.experts:.0f})", flush=True)


def logits_of(a: Arch, seed: int, tokens, mm: Optional[Callable] = None):
    """Float32 logits of the seeded weights on ``tokens``. The reference's own
    call (no ``mm``) also prints the held experts' pairs a layer. (The
    program's own routing of ``tokens`` is not compared here as ``laguna.py``
    does it: that is one more whole-model program, 30 MiB of a compile cache
    this cell nearly fills; ``tests/test_ling.py`` holds the choice.)"""
    if mm is None:
        # what the search's compiles left in the allocator goes back first
        _say_host_memory("the program's search and window")
    fns = _jitted(a, mm)
    if jax.config.jax_compilation_cache_dir:
        _compile_ahead(a, mm, fns, tokens)
    with jax.default_matmul_precision("highest"):
        params = _unstack(a, fns["params"](seed_key(seed)))
        x = fns["embed"](params["top"]["wte"], jnp.asarray(tokens))
        routing = []
        for n in range(a.n_layers):
            _, x, chosen = _layer_forward(a, fns, n, params["layers"][n], x)
            if chosen.size:
                routing.append(chosen)
        logits = fns["head"]({k: params["top"][k] for k in ("ln_f", "lm_head")}, x)
        del params, x
    if mm is None:      # the program at its own precision, outside "highest"
        _held_pairs(a, routing)
        _say_host_memory("the logits")
    return logits
