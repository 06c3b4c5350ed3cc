"""The plain reference for SmallThinker (PowerInfer/SmallThinker-21BA3B-
Instruct): layers that differ in their attention's kind alone (a rotary-less
full-attention layer to three rotated sliding-window layers, over grouped k/v
heads), every feed-forward top-k routed ReGLU experts with no shared one, **the
router reading the block's input ahead of the first norm**, in straightforward
``jax.numpy``, float32, matmul precision ``highest``.

A Python loop over the layers; plain loops (``lax.scan``) over the held
experts (each over every token, under the mask of the tokens that chose it: no
sort, no grouped product, no row buffer) and over blocks of query rows
(``laguna.py::_attention``: two einsums and a softmax under a mask, the window
a mask); the loss a log-softmax over the materialised logits; AdamW written
out (``perf/reference/gpt.py``'s, optax's defaults). No kernels, no fused
head, no flax, nothing of ``saturn_tpu``. Same module contract as ``gpt.py``:
``arch_from_config``, ``seed_key``, ``program_params``, ``logits_of``,
``train``.

The model (``config.json`` gives sizes, ``rope_layout``,
``sliding_window_layout`` and the router's switches; what it does not say is
listed under ``assumed`` in the configuration file). With ``N(x) = x /
sqrt(mean(x^2) + eps) * g`` and no bias anywhere, every layer is

    rho = Route(x)                      from the block's input, un-normed
    h   = x + Attn(N1(x))
    out = h + Experts(N2(h); rho)

Route: ``z = x Wr`` (float32, all 64 experts); ``I`` = the 6 largest of ``z``;
``w_e = exp(z_e) / sum_{e' in I} exp(z_e')``. (``moe_primary_router_apply_
softmax`` with ``norm_topk_prob``: the softmax over all 64, its 6 largest
renormalised, is the softmax over the 6 chosen logits: the same function.)

Experts: ``sum_{e in I, e held} w_e (relu(u Wg_e) * (u Wu_e)) Wd_e`` (ReGLU,
2560 -> 768 -> 2560), no shared expert, no dense layer anywhere.

Attn, 28 q heads over 4 k/v heads of 128 (q 3584 wide): ``q = y Wq, k = y Wk,
v = y Wv``; a layer with ``rope_layout`` 0 (every fourth, from layer 0) does
**not** rotate q and k and reads every key j <= i; a layer with 1 rotates all
128 lanes at theta 1.5e6 (half-split pairs (j, j + 64), as the public
modelling code's ``rotate_half``; the program rotates the same pairs, so no
lane is permuted) and reads keys ``0 <= i - j < 4096``. Softmax over ``q . k /
sqrt(128)``; ``Wo`` 3584 -> 2560. Then the final norm and an untied head.

**The held share.** ``Arch.held`` experts from ``Arch.first_expert`` on have
tables here (16 of the published 64 in the benchmark's configuration); the
router scores all 64 and keeps its 6 a token; a chosen expert that is not held
adds nothing (it lives on another chip). ``routed_part`` exposes the piece
``tests/test_smallthinker.py`` adds the four shares up with.

**How it fits a 16 GB chip at the published widths** (560 M parameters:
weights and two moments 6.7 GB in float32): as ``laguna.py``, ``train`` takes
the gradient layer by layer, attention goes by blocks of query rows and the
held experts one after another, and no program holds the whole model.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from perf.reference.gpt import _nest, adamw_step, flat, seed_key
from perf.reference.laguna import (_attention, _orthonormal_frame, _plain_mm,
                                   _rms_norm, _say_host_memory, _update, _xent)

__all__ = ["Arch", "arch_from_config", "seed_key", "seeded_params",
           "program_layout", "program_params", "forward", "loss_fn", "train",
           "logits_of", "routed_part", "routing_of", "route_logits", "FAULTS"]

FULL, SLIDING = "full_attention", "sliding_attention"
SPARSE = "sparse"
#: the faults ``_layer`` can plant for ``perf/tests`` and ``tests``
FAULTS = ("router_on_n2", "router_on_n1", "rotated_full", "unrotated_sliding",
          "window_less_one", "window_plus_one", "silu", "sigmoid_weights")


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file (under
    the names ``perf/lib/flops_laguna.attn_call`` / ``gmm_call`` read)."""

    vocab_size: int                  # rows of the embedding and the head held
    d_model: int
    kinds: Tuple[str, ...]           # the attention kind of every layer held
    rotated: Tuple[bool, ...]        # whether the layer rotates q and k
    heads: Tuple[int, ...]           # q heads of every layer held
    n_kv_heads: int
    head_dim: int
    window: int
    experts: int                     # the router's outputs
    held: int                        # experts whose tables are here
    first_expert: int
    top_k: int
    d_expert: int
    rope_theta: float
    norm_eps: float
    preset: str = ""                 # the program's preset and overrides, for
    overrides: Tuple[Tuple[str, Any], ...] = ()   # the routing comparison
    builder: str = ""
    family: str = "smallthinker"

    @property
    def n_layers(self) -> int:
        return len(self.kinds)

    @property
    def ffs(self) -> Tuple[str, ...]:
        """Every layer's feed-forward is routed: the model has no dense one."""
        return (SPARSE,) * self.n_layers

    @property
    def period(self) -> int:
        sigs = tuple(zip(self.kinds, self.rotated))
        return next(p for p in range(1, len(sigs) + 1)
                    if len(sigs) % p == 0 and sigs == sigs[:p] * (len(sigs) // p))

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period


def arch_from_config(cfg: Dict[str, Any], seq_len: int) -> Arch:
    """``cfg`` is a file of ``perf/configs``; the model has no position table,
    so ``seq_len`` sizes nothing. The per-layer lists keep their published
    entries and the held layers are the first ``num_hidden_layers``."""
    del seq_len
    n = int(cfg["num_hidden_layers"])
    if cfg.get("rope_scaling") is not None or not (
            cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]):
        raise ValueError("the reference knows plain rotary and a softmax router "
                         "whose chosen weights are renormalised")
    run = cfg["run"]
    experts = int(cfg.get("published", {}).get(
        "moe_num_primary_experts", cfg["moe_num_primary_experts"]))
    return Arch(
        vocab_size=int(run["vocab_size"]),
        d_model=int(cfg["hidden_size"]),
        kinds=tuple(SLIDING if w else FULL for w in cfg["sliding_window_layout"][:n]),
        rotated=tuple(bool(r) for r in cfg["rope_layout"][:n]),
        heads=(int(cfg["num_attention_heads"]),) * n,
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        window=int(cfg["sliding_window_size"]),
        experts=experts,
        held=int(run.get("overrides", {}).get("held_experts", experts)),
        first_expert=0,
        top_k=int(cfg["moe_num_active_primary_experts"]),
        d_expert=int(cfg["moe_ffn_hidden_size"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        preset=str(run.get("preset", "")),
        overrides=tuple(sorted(run.get("overrides", {}).items())),
        builder=str(run.get("builder", "")),
    )


# ------------------------------------------------------------------ weights
#: The seeded values that are not plain normal draws (the benchmark's to
#: choose, listed under ``assumed``). **Routing is discrete**
#: (``perf/reference/laguna.py::AFFINITY`` has the arithmetic), so a token's
#: routing follows its identity: the routers' columns, all layers' side by
#: side (4 x 64 of the 2048 lanes of the largest power of two the stream
#: holds), are one orthonormal signed-Hadamard frame at length
#: ``ROUTER_COLUMN``; every token id is given, layer by layer, ``top_k`` of
#: the experts by a draw from the weight seed, and its embedding row is a
#: unit-RMS normal row plus ``AFFINITY`` times the sum of those experts' unit
#: columns. **Made for the stream the router reads here, the un-normed block
#: input**: no norm divides the lean, so a chosen logit stands at
#: ``ROUTER_COLUMN x AFFINITY`` whatever the layers have added since, over a
#: background whose deviation is ``ROUTER_COLUMN`` times the RMS of everything
#: else in the stream (the row's normal part, 1, and the layers' additions:
#: it grows layer by layer). At 0.25 x 16 the six chosen logits stand near 4
#: over a background of deviation 0.25-0.4, and the weights, a softmax over
#: the chosen logits alone, follow the differences between those six (0.1 to
#: 0.25 a pair: neither uniform nor saturated, whatever the level); the
#: configuration file has the smallest gap read between a token's 6th and
#: 7th logits, layer by layer.
#: ``GATE_UP``: an expert's up table leans towards its gate table (each entry
#: ``GATE_UP`` x the gate's + sqrt(1 - GATE_UP^2) x a draw of its own, so the
#: deviation stays 0.02), **because relu has a kink**: a ReGLU gate table's
#: gradient jumps by ``dout . Wd x (u Wu)`` where a pre-activation ``u Wg``
#: crosses zero, bf16's rounding of ``u`` and ``Wg`` puts about one
#: pre-activation in a thousand on the other side of zero, and with
#: independent tables the *reference against itself with only its products in
#: bf16* reads ``grad_rel_rms`` 0.016-0.025 at ``we_gate`` (CPU, d 512, nothing
#: of the program in it; every other leaf 0.003) and the sound program
#: 0.012-0.024 on the chip over nine token seeds (limit 0.03; the reading
#: wanders by a factor of two with the tokens, ``PERF.md`` Findings PR 49). With
#: the lean a unit's linear branch is small where its gate crosses zero
#: (``u Wu`` = 0.9 ``u Wg`` + a remainder of 0.44), the jump is 0.44 of what
#: it was, and the same two readings are 0.007-0.009 and those of
#: ``perf/reference/readings_smallthinker.json``. No sound run was over a
#: limit with independent tables (thirteen seeds, 0.0123-0.0284, all correct):
#: the motive is the driver's unseen seeds under a check that refuses on one
#: reading. The price is a check that holds the program's ReGLU backward at
#: that smaller kink; ``perf/tests/gate_fault_on_chip.py`` reads, at the
#: cell's widths, that ``silu`` for ``relu`` in the program still comes out
#: not correct. What the cell cannot show for it is in ``PERF.md`` section 7.
AFFINITY = 16.0
ROUTER_COLUMN = 0.25
GATE_UP = 0.9


def _matrix(z):
    return 0.02 * z


def _gain(z):
    return 1.0 + 0.02 * z


def _shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], Callable]]:
    """leaf path -> (shape, value of a standard normal draw). Paths are the
    program's (``blocks/l<i>/...`` with a leading axis of periods), except
    that a layer's q, k and v are three leaves here."""
    P, D, hd, kv, F = a.n_periods, a.d_model, a.head_dim, a.n_kv_heads, a.d_expert
    out: Dict[str, Tuple[Tuple[int, ...], Callable]] = {
        "wte": ((a.vocab_size, D), lambda z: z),
        "lm_head": ((a.vocab_size, D), _matrix),
        "ln_f/scale": ((D,), _gain),
    }
    for i in range(a.period):
        at, H = f"blocks/l{i}/", a.heads[i]
        out.update({
            at + "ln_1/scale": ((P, D), _gain),
            at + "ln_2/scale": ((P, D), _gain),
            at + "q/kernel": ((P, D, H * hd), _matrix),
            at + "k/kernel": ((P, D, kv * hd), _matrix),
            at + "v/kernel": ((P, D, kv * hd), _matrix),
            at + "attn_out/kernel": ((P, H * hd, D), _matrix),
            at + "router": ((P, D, a.experts), _matrix),
            at + "we_gate": ((P, a.held, D, F), _matrix),
            at + "we_up": ((P, a.held, D, F), _matrix),
            at + "we_down": ((P, a.held, F, D), _matrix),
        })
    return out


def seeded_params(a: Arch, key) -> Dict[str, Any]:
    """Float32 weights from ``key`` (``seed_key(seed)``), every leaf random
    (the norms' gains too); the routers' columns an orthonormal frame, the
    embedding's rows leaning towards their experts' columns (``AFFINITY``,
    ``ROUTER_COLUMN``), an expert's up table towards its gate table
    (``GATE_UP``). Traceable, and free of matrix products: what is
    seeded must not depend on the precision a program is traced at."""
    out = {}
    for i, (path, (shape, value)) in enumerate(sorted(_shapes(a).items())):
        out[path] = value(jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
    routers = [(i, p) for i in range(a.period) for p in range(a.n_periods)]
    lanes = 1 << (a.d_model.bit_length() - 1)
    frame = jnp.pad(_orthonormal_frame(lanes, len(routers) * a.experts,
                                       jax.random.fold_in(key, 999)),
                    ((0, a.d_model - lanes), (0, 0)))                    # (D, n E)
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
    for i in range(a.period):       # elementwise: no product's result
        at = f"blocks/l{i}/"
        out[at + "we_up"] = (GATE_UP * out[at + "we_gate"]
                             + (1.0 - GATE_UP ** 2) ** 0.5 * out[at + "we_up"])
    return _nest(out)


def program_layout(a: Arch, tree: Dict[str, Any], xp=jnp) -> Dict[str, Any]:
    """A tree of the parameters' structure (weights, gradients, Adam moments)
    in the layout ``saturn_tpu/models/gpt2.py`` trains: q, k, v side by side
    in one ``qkv`` kernel (both sides rotate half-split pairs: no lane moves)."""
    out = dict(tree, blocks=dict(tree["blocks"]))
    for i in range(a.period):
        layer = dict(out["blocks"][f"l{i}"])
        q, k, v = (layer.pop(n)["kernel"] for n in ("q", "k", "v"))
        layer["qkv"] = {"kernel": xp.concatenate([q, k, v], axis=-1)}
        out["blocks"][f"l{i}"] = layer
    return out


def program_params(a: Arch, key) -> Dict[str, Any]:
    """The seeded weights as the program is handed them. Traceable."""
    return program_layout(a, seeded_params(a, key))


def _layer_weights(a: Arch, params, n: int):
    """Layer ``n``'s own weights out of the tree."""
    period, i = divmod(n, a.period)
    return jax.tree_util.tree_map(lambda x: x[period], params["blocks"][f"l{i}"])


# ------------------------------------------------------------------ forward
def _rotary(a: Arch, t):
    """Half-split rotary on (B, T, H, hd): lanes (j, j + hd / 2) of all the
    head's lanes rotated by position x theta^(-2j / hd)."""
    T, hd = t.shape[1], t.shape[-1]
    inv = 1.0 / (a.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]      # (T, hd/2)
    sin, cos = jnp.sin(angles)[None, :, None, :], jnp.cos(angles)[None, :, None, :]
    first, second = t[..., :hd // 2], t[..., hd // 2:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def _mixer(a: Arch, mm: Callable, kind: str, rotated: bool, heads: int, p, y):
    B, T, _ = y.shape
    hd, kv = a.head_dim, a.n_kv_heads
    q = mm(y, p["q"]["kernel"]).reshape(B, T, heads, hd)
    k = mm(y, p["k"]["kernel"]).reshape(B, T, kv, hd)
    v = mm(y, p["v"]["kernel"]).reshape(B, T, kv, hd)
    if rotated:
        q, k = _rotary(a, q), _rotary(a, k)
    o = _attention(q, k, v, a.window if kind == SLIDING else None)
    return mm(o.reshape(B, T, heads * hd), p["attn_out"]["kernel"])


def route_logits(router, x):
    """``z = x Wr`` in float32 (never through the control's lower-precision
    product: the configuration states the router in float32)."""
    return x @ router


def routing_of(a: Arch, router, x, sigmoid_weights: bool = False):
    """(the experts chosen (.., k), their weights (.., k)) of the rows ``x``
    the router reads: the ``top_k`` largest logits, a softmax over them.
    ``sigmoid_weights`` is a planted fault (Laguna's rule: sigmoid scores
    normalised over the chosen)."""
    z = route_logits(router, x)
    top, chosen = jax.lax.top_k(z, a.top_k)
    if sigmoid_weights:
        s = jax.nn.sigmoid(top)
        return chosen, s / jnp.sum(s, axis=-1, keepdims=True)
    return chosen, jax.nn.softmax(top, axis=-1)


def routed_part(a: Arch, mm: Callable, p, u, routing,
                first_expert: Optional[int] = None, act: Callable = jax.nn.relu):
    """The held experts' part of the routed layer's output for the rows ``u``
    (B, T, D) the experts read, under ``routing`` (``routing_of`` of the rows
    the router read): each held expert over every token, times the weight of
    the tokens that chose it (0 for the rest); the experts one after another
    (a ``lax.scan`` over the tables' expert axis: one expert's program, run
    ``held`` times, each rematerialised in the backward). ``first_expert``
    overrides the architecture's share (a test adds all the shares up)."""
    first = a.first_expert if first_expert is None else first_expert
    chosen, weights = routing
    mine = chosen[..., None] == first + jnp.arange(a.held)            # (B, T, k, held)
    masks = jnp.moveaxis(jnp.sum(jnp.where(mine, weights[..., None], 0.0), axis=-2), -1, 0)

    @jax.checkpoint
    def expert(u, gate, up, down, m):
        return mm(act(mm(u, gate)) * mm(u, up), down) * m[..., None]

    def one_more(out, xs):
        return out + expert(u, *xs), None

    out, _ = jax.lax.scan(one_more, jnp.zeros_like(u),
                          (p["we_gate"], p["we_up"], p["we_down"], masks))
    return out


def _layer(a: Arch, mm: Callable, kind: str, rotated: bool, heads: int, p, x,
           fault: Optional[str] = None, routing: Optional[list] = None):
    """``fault`` plants one of ``FAULTS``. ``routing``, a list, gains the
    layer's chosen experts."""
    eps = a.norm_eps
    if fault in ("window_less_one", "window_plus_one") and kind == SLIDING:
        a = replace(a, window=a.window + (1 if fault == "window_plus_one" else -1))
    if fault == "rotated_full" and kind == FULL:
        rotated = True
    if fault == "unrotated_sliding" and kind == SLIDING:
        rotated = False
    y1 = _rms_norm(x, p["ln_1"]["scale"], eps)
    h = x + _mixer(a, mm, kind, rotated, heads, p, y1)
    u = _rms_norm(h, p["ln_2"]["scale"], eps)
    # the router's rows: the block's own input, before any norm
    read = {"router_on_n2": u, "router_on_n1": y1}.get(fault, x)
    made = routing_of(a, p["router"], read, sigmoid_weights=fault == "sigmoid_weights")
    if routing is not None:
        routing.append(made[0])
    return h + routed_part(a, mm, p, u, made,
                           act=jax.nn.silu if fault == "silu" else jax.nn.relu)


def _head(a: Arch, mm: Callable, top, x):
    """``top``: the leaves outside the stack (``ln_f``, ``lm_head``)."""
    return mm(_rms_norm(x, top["ln_f"]["scale"], a.norm_eps), top["lm_head"].T)


def _sig(a: Arch, n: int):
    return a.kinds[n], a.rotated[n], a.heads[n]


def forward(a: Arch, params, tokens, mm: Optional[Callable] = None,
            fault: Optional[str] = None, routing: Optional[list] = None):
    """(B, T) int tokens -> (B, T, V) float32 logits. ``mm(x, w)`` is the
    matrix product of activations (..., K) and weights (K, N); the control of
    ``perf/lib/refcheck.py`` passes a lower-precision one and changes nothing
    else. ``routing``, a list, gains every layer's chosen experts."""
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
    distinct layer (kind, rotation, q heads), none for the whole model. A
    layer's forward also returns the experts its router chose."""
    mul = mm or _plain_mm

    def layer(sig, p, x):
        routing: list = []
        out = _layer(a, mul, *sig, p, x, routing=routing)
        return out, routing[0]

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


def _unstack(a: Arch, params) -> Dict[str, Any]:
    """{"top": the leaves outside the layers, "layers": [each layer's own
    weights]}: what ``train`` updates piece by piece."""
    return {"top": {k: v for k, v in params.items() if k != "blocks"},
            "layers": [_layer_weights(a, params, n) for n in range(a.n_layers)]}


def _restack(a: Arch, pieces, xp) -> Dict[str, Any]:
    blocks = {}
    for i in range(a.period):
        mine = [flat(pieces["layers"][p * a.period + i]) for p in range(a.n_periods)]
        blocks[f"l{i}"] = _nest({k: xp.stack([m[k] for m in mine]) for k in mine[0]})
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
    another expert than this reference does, layer by layer, on ``tokens``
    (``laguna.py``'s comparison: the program's own model from the same seeded
    weights, its routers' choices through ``hints["routed"]["routing_fn"]``),
    and the held pairs a layer beside their mean: what a row buffer must
    take."""
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
    rows = held.sum(axis=1)                                            # (layers, k)
    per_expert = np.stack([
        np.bincount(layer[h] - a.first_expert, minlength=a.held)
        for layer, h in zip(mine, held)])
    print("perf: routing: share of (token, slot) pairs the program routes to "
          "another expert than the reference, by routed layer: "
          + ", ".join(f"{x:.6f}" for x in per_layer)
          + f"; all layers {float(np.mean(per_layer)):.6f}; the reference holds "
          f"{held.mean() * a.top_k:.3f} pairs a token; held pairs by layer "
          + ", ".join(str(int(x)) for x in rows.sum(-1))
          + f" (mean {mine.shape[1] * a.top_k * a.held / a.experts:.0f}), the "
          f"fullest held expert's rows {int(per_expert.max())}", flush=True)


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
            routing.append(chosen)
        logits = fns["head"]({k: params["top"][k] for k in ("ln_f", "lm_head")}, x)
        del params, x
    if mm is None:      # the program at its own precision, outside "highest"
        _routing_disagreement(a, seed, tokens, routing)
        _say_host_memory("the logits")
    return logits
