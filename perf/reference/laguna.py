"""The plain reference for Laguna (poolside/Laguna-XS.2): a leading dense
layer, then sliding-window and full-attention layers over shared k/v heads
whose feed-forward is a shared expert beside top-k routed experts, in
straightforward ``jax.numpy``, float32, matmul precision ``highest``.

A Python loop over the layers; plain loops (``lax.scan``) over the held
experts (each over every token, under the mask of the tokens that chose it: no
sort, no grouped product, no row buffer) and over blocks of query rows (64
heads x 8192 x 8192 scores never exist at once); attention as two einsums and a softmax under a mask; the loss
as a log-softmax over the materialised logits; AdamW written out
(``perf/reference/gpt.py``'s, optax's defaults). No kernels, no fused head, no
flax, nothing of ``saturn_tpu/ops``. Same module contract as ``gpt.py``:
``arch_from_config``, ``seed_key``, ``program_params``, ``logits_of``,
``train``.

The model (``config.json`` gives sizes, ``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``, the rotary parameters; what it does not say
is listed under ``assumed`` in the configuration file). With ``N(x) = x /
sqrt(mean(x^2) + eps) * g`` and no bias anywhere, every layer is

    h   = x + Mixer(N1(x))
    out = h + FF(N2(h))

Mixer of a layer with H q heads (48 full, 64 sliding), 8 k/v heads of 128:

    q = y Wq (H x 128), k = y Wk, v = y Wv (8 x 128); rotary on q and k
    o_i = softmax_{j in A(i)} (q_i k_j / sqrt(128)) v_j,  q head n reads k/v
          head n // (H / 8);  full: A(i) = {j <= i};  sliding: 0 <= i - j < 512
    g = sigmoid(y Wg) (one scalar a head);  Mixer = [g_n o_n] Wo

Rotary, on interleaved lane pairs (2j, 2j+1) as published (the program rotates
split halves and is handed permuted q / k columns, ``program_layout``): a
sliding layer all 128 lanes at theta 10000; a full layer the first 64 lanes at
theta 500000 with YaRN (each frequency between itself and itself / factor by a
linear ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
times in the original 4096 positions) and sin, cos times the attention factor.

FF of the leading layer: ``Wd (silu(Wg y) * Wu y)`` at 8192. FF of the others:

    s = sigmoid(y Wr)  (float32, all 256 experts);  I = the 8 largest
    w_e = 2.5 s_e / sum_{e' in I} s_e'
    FF = E_shared(y) + sum_{e in I, e held} w_e E_e(y),   E a SwiGLU of 512

then the final norm and an untied head: ``logits = Nf(x_L) W_head^T``.

**The held share.** ``Arch.held`` experts from ``Arch.first_expert`` on have
tables here (32 of the published 256 in the benchmark's configuration); the
router scores all 256 and keeps its 8 a token; a chosen expert that is not
held adds nothing (it lives on another chip). ``routed_part`` exposes the
piece ``tests/test_laguna.py`` adds the eight shares up with.

**How it fits a 16 GB chip at the published widths** (692 M parameters:
weights and two moments 8.3 GB in float32). As ``olmo_hybrid.py``: ``train``
takes the gradient layer by layer (one layer's gradient on the chip at a
time), attention goes by blocks of ``ATTN_Q_BLOCK`` query rows and the held
experts one after another, each block and each expert one program run in a
``lax.scan`` and rematerialised in the backward (unrolled in Python, 32
blocks and 32 experts a layer, the reference's programs took 166 + 357 s to
compile and run two steps and left 12 GiB in the host's allocator: my chip
run, PR 36).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from perf.reference.gpt import _nest, adamw_step, flat, seed_key

__all__ = ["Arch", "arch_from_config", "seed_key", "seeded_params",
           "program_layout", "program_params", "forward", "loss_fn", "train",
           "logits_of", "routed_part", "routing_of", "yarn_inv_freq"]

ATTN_Q_BLOCK = 128
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""

    vocab_size: int                  # rows of the embedding and the head held
    d_model: int
    kinds: Tuple[str, ...]           # the mixer kind of every layer held
    ffs: Tuple[str, ...]             # "dense" | "sparse", every layer held
    heads: Tuple[int, ...]           # q heads of every layer held
    n_kv_heads: int
    head_dim: int
    window: int
    d_dense: int                     # the leading layer's SwiGLU width
    experts: int                     # the router's outputs
    held: int                        # experts whose tables are here
    first_expert: int
    top_k: int
    d_expert: int
    d_shared: int
    routed_scale: float
    full_rope: Tuple[float, float, float, int, float, float, float]
    # (theta, partial factor, yarn factor, original positions, beta_fast,
    #  beta_slow, attention factor)
    sliding_theta: float
    norm_eps: float
    preset: str = ""                 # the program's preset and overrides, for
    overrides: Tuple[Tuple[str, Any], ...] = ()   # the routing comparison
    builder: str = ""
    family: str = "laguna"

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
        return next(p for p in range(1, len(rest) + 1)
                    if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p))

    @property
    def n_periods(self) -> int:
        return (self.n_layers - self.lead) // self.period

    def full_rotary_dim(self) -> int:
        return int(self.head_dim * self.full_rope[1])


def arch_from_config(cfg: Dict[str, Any], seq_len: int) -> Arch:
    """``cfg`` is a file of ``perf/configs``; the model has no position table,
    so ``seq_len`` sizes nothing. The per-layer lists keep their published
    entries and the held layers are the first ``num_hidden_layers``."""
    del seq_len
    n = int(cfg["num_hidden_layers"])
    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if full.get("rope_type") != "yarn" or sliding.get("rope_type") != "default":
        raise ValueError("the reference knows YaRN on the full layers and plain "
                         "rotary on the sliding ones")
    run = cfg["run"]
    held = int(run.get("overrides", {}).get("held_experts", cfg["num_experts"]))
    return Arch(
        vocab_size=int(run["vocab_size"]),
        d_model=int(cfg["hidden_size"]),
        kinds=tuple(cfg["layer_types"][:n]),
        ffs=tuple(cfg["mlp_layer_types"][:n]),
        heads=tuple(int(h) for h in cfg["num_attention_heads_per_layer"][:n]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        window=int(cfg["sliding_window"]),
        d_dense=int(cfg["intermediate_size"]),
        experts=int(cfg.get("published", {}).get("num_experts", cfg["num_experts"])),
        held=held,
        first_expert=0,
        top_k=int(cfg["num_experts_per_tok"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        d_shared=int(cfg["shared_expert_intermediate_size"]),
        routed_scale=float(cfg["moe_routed_scaling_factor"]),
        full_rope=(float(full["rope_theta"]), float(full["partial_rotary_factor"]),
                   float(full["factor"]), int(full["original_max_position_embeddings"]),
                   float(full["beta_fast"]), float(full["beta_slow"]),
                   float(full["attention_factor"])),
        sliding_theta=float(sliding["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        preset=str(run.get("preset", "")),
        overrides=tuple(sorted(run.get("overrides", {}).items())),
        builder=str(run.get("builder", "")),
    )


# ------------------------------------------------------------------ weights
#: The two seeded values that are not plain normal draws (the benchmark's to
#: choose, listed under ``assumed``), and why: **routing is discrete**. With
#: every leaf a plain normal draw a token's 256 scores are 256 normal logits
#: whose 8th and 9th largest lie 0.055 standard deviations apart on average,
#: and bf16's rounding of the router's input (relative 2^-9 a lane, which the
#: program's residual stream carries whatever the router's own precision)
#: moves the difference of two logits by 0.0016: one token in 45 then swaps
#: its 8th expert for its 9th against the float32 reference, a quarter of the
#: swaps touch a held expert, and the held tables' gradients differ by
#: sqrt(0.25 / 45) = 0.07 of their norm with nothing wrong (limit 0.03).
#: So a token's routing follows its identity, as a trained router's largely
#: does: every token id is given, layer by layer, ``top_k`` of the experts by
#: a draw from the weight seed, and its embedding row is a unit-RMS normal
#: row plus ``AFFINITY`` times the sum of those experts' unit router columns.
#: ``ROUTER_COLUMN``: the routers' columns, all layers' together (4 x 256 in
#: 2048 lanes), are an orthonormal frame (``_orthonormal_frame``: signed
#: Hadamard columns) at this length, so that one expert's lean is no other
#: expert's logit: with plain
#: normal columns the 31 other leans of a row add 0.53 of noise to every
#: logit beside the row's own 0.72, the chosen experts stand 4.8 and not 6
#: deviations out, and the first chip reading at 6 (my chip run, PR 36) had
#: 0.11-0.23 % of the pairs routed differently, a token in a hundred.
#: ``AFFINITY`` 16 (8 until the review of PR 36): the lean is diluted by what
#: the layers add to the stream, so at 8 the chosen logits stood at 1.16 and
#: not the 1.41 of the arithmetic, over a background of deviation 0.20: the
#: smallest of a token's 8 chosen at 0.88 on average, the largest of its 248
#: others at 0.55, and for one token in a thousand less than 0.003 apart (the
#: reference's forward on 2048 of the harness's tokens, on the CPU), which
#: bf16's rounding of the stream crosses; AdamW's sign-like steps at lr 1e-5
#: move a logit of a frequent id by up to 0.016 a step besides, so over the
#: check's 8 steps every token less than 0.13 apart can cross in the program
#: a step before or after the reference. At 16 the chosen stand at 1.65
#: (sigmoid 0.84, not saturated) over a background of 0.14, the smallest
#: chosen at 1.45 against the largest other at 0.38, and the smallest gap of
#: 2048 tokens is 0.43-0.56 in the four routed layers.
AFFINITY = 16.0
ROUTER_COLUMN = 0.25


def _matrix(z):
    return 0.02 * z


def _gain(z):
    return 1.0 + 0.02 * z


def _mixer_shapes(a: Arch, at: str, lead: Tuple[int, ...], heads: int):
    D, hd, kv = a.d_model, a.head_dim, a.n_kv_heads
    return {
        at + "ln_1/scale": (lead + (D,), _gain),
        at + "ln_2/scale": (lead + (D,), _gain),
        at + "q/kernel": (lead + (D, heads * hd), _matrix),
        at + "k/kernel": (lead + (D, kv * hd), _matrix),
        at + "v/kernel": (lead + (D, kv * hd), _matrix),
        at + "attn_gate/kernel": (lead + (D, heads), _matrix),
        at + "attn_out/kernel": (lead + (heads * hd, D), _matrix),
    }


def _shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], Callable]]:
    """leaf path -> (shape, value of a standard normal draw). Paths are the
    program's (``lead/l<i>/...``; ``blocks/l<i>/...`` with a leading axis of
    periods), except that a layer's q, k and v are three leaves here."""
    P, D = a.n_periods, a.d_model
    out: Dict[str, Tuple[Tuple[int, ...], Callable]] = {
        "wte": ((a.vocab_size, D), lambda z: z),
        "lm_head": ((a.vocab_size, D), _matrix),
        "ln_f/scale": ((D,), _gain),
    }
    for i in range(a.lead):
        at = f"lead/l{i}/"
        out.update(_mixer_shapes(a, at, (), a.heads[i]))
        out.update({at + "mlp_gate/kernel": ((D, a.d_dense), _matrix),
                    at + "mlp_in/kernel": ((D, a.d_dense), _matrix),
                    at + "mlp_out/kernel": ((a.d_dense, D), _matrix)})
    for i in range(a.period):
        at = f"blocks/l{i}/"
        out.update(_mixer_shapes(a, at, (P,), a.heads[a.lead + i]))
        F, S = a.d_expert, a.d_shared
        out.update({
            at + "router": ((P, D, a.experts), _matrix),
            at + "we_gate": ((P, a.held, D, F), _matrix),
            at + "we_up": ((P, a.held, D, F), _matrix),
            at + "we_down": ((P, a.held, F, D), _matrix),
            at + "shared_gate/kernel": ((P, D, S), _matrix),
            at + "shared_in/kernel": ((P, D, S), _matrix),
            at + "shared_out/kernel": ((P, S, D), _matrix),
        })
    return out


def _orthonormal_frame(d: int, n: int, key):
    """``n`` orthonormal columns of ``d`` lanes whose entries are all +-1 /
    sqrt(d): columns of the Sylvester-Hadamard matrix of order ``d`` (entry
    (i, j) is -1 to the number of bits i and j share), chosen and ordered by
    ``key``, each row under a sign from ``key``. Integer and sign work only:
    every program that builds it, at whatever matmul precision, builds the
    same bits. (The Q of a normal matrix, which this replaced, is a product's
    result: the program's init, traced at the default precision, and the
    reference's, at ``highest``, made router columns and embedding rows
    that differed by a thousandth, and ``update_rel_rms`` read 6.6 with
    every gradient right: my chip run, PR 36.)"""
    if d & (d - 1) or n > d:
        raise ValueError(f"a Hadamard frame needs d a power of two and n <= d: {d}, {n}")
    rows = jnp.arange(d, dtype=jnp.uint32)[:, None]
    cols = jax.random.permutation(jax.random.fold_in(key, 0), d)[:n].astype(jnp.uint32)[None, :]
    shared = jax.lax.population_count(rows & cols)
    signs = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (d, 1)), 1.0, -1.0)
    return jnp.where(shared % 2 == 0, 1.0, -1.0) * signs * jnp.float32(1.0 / math.sqrt(d))


def seeded_params(a: Arch, key) -> Dict[str, Any]:
    """Float32 weights from ``key`` (``seed_key(seed)``), every leaf random
    (the norms' gains too); the routers' columns an orthonormal frame, the
    embedding's rows leaning towards their experts' columns (``AFFINITY``,
    ``ROUTER_COLUMN``). Traceable, and free of matrix products: what is
    seeded must not depend on the precision a program is traced at."""
    out = {}
    for i, (path, (shape, value)) in enumerate(sorted(_shapes(a).items())):
        out[path] = value(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
    routers = [(i, p) for i in range(a.period) for p in range(a.n_periods)]
    frame = _orthonormal_frame(a.d_model, len(routers) * a.experts,
                               jax.random.fold_in(key, 999))            # (D, n E)
    lean = jnp.zeros_like(out["wte"])
    for n, (i, p) in enumerate(routers):
        unit = frame[:, n * a.experts:(n + 1) * a.experts]               # (D, E)
        out[f"blocks/l{i}/router"] = out[f"blocks/l{i}/router"].at[p].set(
            ROUTER_COLUMN * unit)
        draw = jax.random.uniform(
            jax.random.fold_in(key, 1000 + p * a.period + i),
            (a.vocab_size, a.experts))
        _, own = jax.lax.top_k(draw, a.top_k)                            # (V, k)
        for slot in range(a.top_k):      # sums of rows, in a fixed order: no product
            lean = lean + unit.T[own[:, slot]]
    out["wte"] = out["wte"] + AFFINITY * lean
    return _nest(out)


def _lane_perm(a: Arch, kind: str, heads: int):
    """Column order of the program's q (or k) projection in terms of the
    published one: per head, even rotary lanes, odd rotary lanes, the rest."""
    hd = a.head_dim
    rd = hd if kind == SLIDING else a.full_rotary_dim()
    head = list(range(0, rd, 2)) + list(range(1, rd, 2)) + list(range(rd, hd))
    return [h * hd + j for h in range(heads) for j in head]


def _layers_of(a: Arch):
    """(where, name, kind, q heads) of every distinct layer leaf group."""
    for i in range(a.lead):
        yield "lead", f"l{i}", a.kinds[i], a.heads[i]
    for i in range(a.period):
        yield "blocks", f"l{i}", a.kinds[a.lead + i], a.heads[a.lead + i]


def program_layout(a: Arch, tree: Dict[str, Any], xp=jnp) -> Dict[str, Any]:
    """A tree of the parameters' structure (weights, gradients, Adam moments)
    in the layout ``saturn_tpu/models/gpt2.py`` trains: q, k, v side by side
    in one ``qkv`` kernel, q's and k's lanes in split-half rotary order."""
    out = dict(tree)
    for where, name, kind, heads in _layers_of(a):
        out[where] = dict(out[where])
        layer = dict(out[where][name])
        q, k, v = (layer.pop(n)["kernel"] for n in ("q", "k", "v"))
        q = xp.take(q, xp.asarray(_lane_perm(a, kind, heads), dtype=xp.int32), axis=-1)
        k = xp.take(k, xp.asarray(_lane_perm(a, kind, a.n_kv_heads), dtype=xp.int32),
                    axis=-1)
        layer["qkv"] = {"kernel": xp.concatenate([q, k, v], axis=-1)}
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
def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def yarn_inv_freq(rotary_dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's frequencies, written out: dimension j's plain frequency
    ``theta^(-2j / rotary_dim)`` is kept below the dimension that turns
    ``beta_fast`` times in ``original`` positions, divided by ``factor`` above
    the one that turns ``beta_slow`` times, and ramps linearly between."""
    out = []
    def dim_of(turns):
        return rotary_dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    for j in range(rotary_dim // 2):
        plain = theta ** (-2.0 * j / rotary_dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def _rotary(a: Arch, kind: str, t):
    """Interleaved rotary on (B, T, H, hd): lanes (2j, 2j+1) of the first
    ``rd`` rotated by position x frequency j."""
    T, hd = t.shape[1], t.shape[-1]
    if kind == SLIDING:
        rd, scale = hd, 1.0
        inv = 1.0 / (a.sliding_theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    else:
        theta, _, factor, original, fast, slow, scale = a.full_rope
        rd = a.full_rotary_dim()
        inv = yarn_inv_freq(rd, theta, factor, original, fast, slow)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]      # (T, rd/2)
    sin = (jnp.sin(angles) * scale)[None, :, None, :]
    cos = (jnp.cos(angles) * scale)[None, :, None, :]
    rot, rest = t[..., :rd], t[..., rd:]
    even, odd = rot[..., 0::2], rot[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return jnp.concatenate([turned.reshape(rot.shape), rest], axis=-1)


def _attention(q, k, v, window: Optional[int]):
    """Softmax attention on q (B, T, H, hd) over k, v (B, T, KV, hd), q head n
    reading k/v head n // (H / KV); causal, and within ``window`` where given.
    By blocks of ``ATTN_Q_BLOCK`` query rows, one block's program run block
    after block (a ``lax.scan``, each block rematerialised in the backward):
    a full layer's block reads every key under the causal mask, a sliding
    layer's the ``window - 1`` keys before it and its own."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    block = ATTN_Q_BLOCK if T % ATTN_Q_BLOCK == 0 else T
    back = 0 if window is None else min(window - 1, T)     # keys before a block
    span = T if window is None else back + block
    q = q.reshape(B, T // block, block, KV, H // KV, hd)
    k_pad = jnp.pad(k, ((0, 0), (back, 0), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (back, 0), (0, 0), (0, 0)))

    @jax.checkpoint
    def rows(q_rows, first):
        # keys first - back .. first + block - 1 (all of them in a full layer)
        lo = 0 if window is None else first
        keys = jax.lax.dynamic_slice_in_dim(k_pad, lo, span, axis=1)
        values = jax.lax.dynamic_slice_in_dim(v_pad, lo, span, axis=1)
        scores = jnp.einsum("bqcgd,bkcd->bcgqk", q_rows, keys) / math.sqrt(hd)
        i = (first + jnp.arange(block))[:, None]
        j = (lo - back + jnp.arange(span))[None, :]
        seen = (j <= i) & (j >= 0)
        if window is not None:
            seen = seen & (i - j < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bcgqk,bkcd->bqcgd", probs, values)

    def one_block(_, xs):
        return None, rows(*xs)

    _, out = jax.lax.scan(one_block, None, (
        jnp.moveaxis(q, 1, 0), jnp.arange(0, T, block, dtype=jnp.int32)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, hd)


def _mixer(a: Arch, mm: Callable, kind: str, heads: int, p, y, gate: bool = True):
    B, T, _ = y.shape
    hd, kv = a.head_dim, a.n_kv_heads
    q = _rotary(a, kind, mm(y, p["q"]["kernel"]).reshape(B, T, heads, hd))
    k = _rotary(a, kind, mm(y, p["k"]["kernel"]).reshape(B, T, kv, hd))
    v = mm(y, p["v"]["kernel"]).reshape(B, T, kv, hd)
    o = _attention(q, k, v, a.window if kind == SLIDING else None)
    if gate:
        o = o * jax.nn.sigmoid(mm(y, p["attn_gate"]["kernel"]))[..., None]
    return mm(o.reshape(B, T, heads * hd), p["attn_out"]["kernel"])


def _swiglu(mm, y, gate, up, down):
    return mm(jax.nn.silu(mm(y, gate)) * mm(y, up), down)


def routing_of(a: Arch, router, y):
    """(the experts chosen (.., k), their weights (.., k)) of the normed rows
    ``y``: sigmoid scores over all the experts in float32 (never through the
    control's lower-precision product: the configuration states the router in
    float32), the ``top_k`` largest, normalised, times the scaling factor."""
    scores = jax.nn.sigmoid(y @ router)
    top, chosen = jax.lax.top_k(scores, a.top_k)
    return chosen, a.routed_scale * top / jnp.sum(top, axis=-1, keepdims=True)


def routed_part(a: Arch, mm: Callable, p, y, first_expert: Optional[int] = None,
                drop_pair: bool = False):
    """The held experts' part of the routed layer's output for normed rows
    ``y`` (B, T, D): each held expert over every token, times the weight of
    the tokens that chose it (0 for the rest); the experts one after another
    (a ``lax.scan`` over the tables' expert axis: one expert's program, run
    ``held`` times, each rematerialised in the backward). ``first_expert``
    overrides the architecture's share (a test adds all the shares up);
    ``drop_pair`` is a planted fault (one held pair is left out)."""
    first = a.first_expert if first_expert is None else first_expert
    chosen, weights = routing_of(a, p["router"], y)
    # (held, B, T): the weight of each token for each held expert
    mine = chosen[..., None] == first + jnp.arange(a.held)            # (B, T, k, held)
    masks = jnp.moveaxis(jnp.sum(jnp.where(mine, weights[..., None], 0.0), axis=-2), -1, 0)
    if drop_pair:
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


def _layer(a: Arch, mm: Callable, kind: str, ff: str, heads: int, p, x,
           fault: Optional[str] = None, routing: Optional[list] = None):
    """``fault`` plants one for ``perf/tests``: "no_gate", "no_shared",
    "drop_pair", "window_less_one". ``routing``, a list, gains a routed layer's
    chosen experts."""
    eps = a.norm_eps
    if fault == "window_less_one" and kind == SLIDING:
        a = replace(a, window=a.window - 1)
    h = x + _mixer(a, mm, kind, heads, p, _rms_norm(x, p["ln_1"]["scale"], eps),
                   gate=fault != "no_gate")
    y = _rms_norm(h, p["ln_2"]["scale"], eps)
    if ff == DENSE:
        return h + _swiglu(mm, y, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                           p["mlp_out"]["kernel"])
    if routing is not None:
        routing.append(routing_of(a, p["router"], y)[0])
    out = h + routed_part(a, mm, p, y, drop_pair=fault == "drop_pair")
    if fault != "no_shared":
        out = out + _swiglu(mm, y, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
                            p["shared_out"]["kernel"])
    return out


def _head(a: Arch, mm: Callable, top, x):
    """``top``: the leaves outside the stack (``ln_f``, ``lm_head``)."""
    return mm(_rms_norm(x, top["ln_f"]["scale"], a.norm_eps), top["lm_head"].T)


def _xent(logits, tokens):
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def _plain_mm(x, w):
    return x @ w


def _sig(a: Arch, n: int):
    return a.kinds[n], a.ffs[n], a.heads[n]


def forward(a: Arch, params, tokens, mm: Optional[Callable] = None,
            fault: Optional[str] = None, routing: Optional[list] = None):
    """(B, T) int tokens -> (B, T, V) float32 logits. ``mm(x, w)`` is the
    matrix product of activations (..., K) and weights (K, N); the control of
    ``perf/lib/refcheck.py`` passes a lower-precision one and changes nothing
    else. ``routing``, a list, gains every routed layer's chosen experts."""
    mm = mm or _plain_mm
    x = params["wte"][tokens]
    for n in range(a.n_layers):
        layer = functools.partial(_layer, a, mm, *_sig(a, n), fault=fault,
                                  routing=routing)
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
    distinct layer (kind, feed-forward, q heads), none for the whole model
    (whose executable, five layers unrolled, was larger than the machine's
    compile cache: my chip run, PR 36). A layer's forward also returns the
    experts its router chose (an empty array where it has none)."""
    mul = mm or _plain_mm

    def layer(sig, p, x):
        routing: list = []
        out = _layer(a, mul, *sig, p, x, routing=routing)
        return out, routing[0] if routing else jnp.zeros((0,), jnp.int32)

    def layer_back(sig, p, x, dy):
        _, vjp = jax.vjp(functools.partial(_layer, a, mul, *sig), p, x)
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
    for sig in {_sig(a, n) for n in range(a.n_layers)}:
        out["layer", sig] = jax.jit(functools.partial(layer, sig))
        out["layer_back", sig] = jax.jit(functools.partial(layer_back, sig))
    return out


@functools.lru_cache(maxsize=None)
def _update(lr: float) -> Callable:
    def update(p, g, m, v, t):
        new_p, opt = adamw_step(p, g, {"m": m, "v": v, "t": t}, lr)
        return new_p, opt["m"], opt["v"]

    return jax.jit(update, donate_argnums=(0, 1, 2, 3))


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
    """One AdamW step, the gradient layer by layer (``olmo_hybrid.py``'s).
    ``state``: ``{"p", "m", "v"}``, each ``{"top", "layers"}``, and ``"t"``."""
    p, m, v, t = state["p"], state["m"], state["v"], state["t"]

    def put(where, key, grads):
        new = update(p[where][key], grads, m[where][key], v[where][key], t)
        for tree, leaf in zip((p, m, v), new):
            tree[where][key] = leaf

    x = fns["embed"](p["top"]["wte"], tokens)
    inputs = []
    for n in range(a.n_layers):
        inputs.append(x)
        x, _ = fns["layer", _sig(a, n)](p["layers"][n], x)
    head = {k: p["top"][k] for k in ("ln_f", "lm_head")}
    loss, dhead, dx = fns["head_back"](head, x, tokens)
    for k, g in dhead.items():
        put("top", k, g)
    del dhead, x
    for n in reversed(range(a.n_layers)):
        dp, dx = fns["layer_back", _sig(a, n)](p["layers"][n], inputs.pop(), dx)
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
    another expert than this reference does, layer by layer, on ``tokens``:
    the program's own model (the configuration's builder, preset and
    overrides; kernels where the backend has them) from the same seeded
    weights, its routers' choices through ``hints["routed"]["routing_fn"]``.
    Routing is discrete: a pair routed differently is another expert's output,
    not a rounding of the same one, and the held tables' gradients carry every
    such pair (PERF.md section 4)."""
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


def _say_host_memory(where: str) -> None:
    """Hand freed host memory back to the system (``malloc_trim``: what the
    compiler used for the reference's programs stays in the allocator's free
    lists otherwise) and say what the process holds. A one-chip machine has
    40 GiB, 13 of them taken by the device's runtime at start (my chip run,
    PR 36), for the reference's host copy of its state (5.5 GB), the
    program's (8.3), a checkpoint's buffers and every compiler's leavings."""
    import ctypes
    import gc
    import resource

    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass
    try:
        with open("/proc/self/status") as f:
            now = next(int(x.split()[1]) for x in f if x.startswith("VmRSS")) / 2 ** 20
    except (OSError, StopIteration):
        now = float("nan")
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"perf: reference: host memory {now:.2f} GiB resident (peak {peak:.2f}) "
          f"after {where}", flush=True)


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
        for n in range(a.n_layers):
            x, chosen = fns["layer", _sig(a, n)](params["layers"][n], x)
            if chosen.size:
                routing.append(chosen)
        logits = fns["head"]({k: params["top"][k] for k in ("ln_f", "lm_head")}, x)
        del params, x
    if mm is None:      # the program at its own precision, outside "highest"
        _routing_disagreement(a, seed, tokens, routing)
        _say_host_memory("the logits")
    return logits
