"""The plain reference: GPT-2 and GPT-J forward, loss, gradients and an AdamW
step in straightforward ``jax.numpy``, float32, matmul precision ``highest``.

No scan, no kernels, no remat, no fused head: a Python loop over the layers,
attention as two einsums and a softmax, the loss as a log-softmax over the
materialised logits, the optimizer as the AdamW formulas written out. It
takes nothing the program has made: its weights come from ``seeded_params``
(one jitted call from the benchmark's ``--seed``), its batches from the
benchmark's dataset, and the program is handed the *same* weights through
``program_params`` (the layout the package's ``models/gpt2.py`` trains).

Published blocks followed here
  GPT-2 (openai-community/gpt2*): learned positions, pre-LN, sequential
    residual (x += attn(ln_1 x); x += mlp(ln_2 x)), ``gelu_new`` (tanh
    approximation), tied head.
  GPT-J (EleutherAI/gpt-j-6b): no learned positions, rotary embedding on the
    first ``rotary_dim`` lanes of every q/k head, *interleaved* (rotate every
    two) as published, one LayerNorm per block feeding attention and MLP in
    parallel (x += attn(ln_1 x) + mlp(ln_1 x)).

Departures, each following the package so that the two can be compared at all
(they are listed in the configuration files under ``assumed`` as well):
  1. LayerNorm epsilon is 1e-6 (flax's default, which the package uses); both
     models publish 1e-5. At random-init scale (|x| ~ 0.02) that is visible.
  2. GPT-J publishes attention projections without bias and an untied
     ``lm_head`` with bias; the package has a bias on every Dense and ties the
     head to ``wte``. The reference does what the package does.
  3. GPT-J's q, k and v are three matrices as published; the package fuses
     them into one ``qkv`` kernel. Same arithmetic, another layout.
  4. The package rotates split halves where GPT-J rotates interleaved pairs.
     The reference keeps the published maths; ``program_params`` permutes the
     q/k lanes of the weights it hands the program (q.k is a sum over lanes, so
     a permutation common to q and k changes nothing else).
  5. The optimizer is optax's ``adamw`` at its defaults, which is what
     ``HParams.make_optimizer`` builds: b1 0.9, b2 0.999, eps 1e-8 added to
     the root, weight decay 1e-4 on every leaf.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

LN_EPS = 1e-6          # departure 1
ADAM_B1, ADAM_B2, ADAM_EPS, WEIGHT_DECAY = 0.9, 0.999, 1e-8, 1e-4


@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""

    family: str            # "gpt2" | "gptj"
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    d_ff: int
    n_positions: int       # rows of the learned position table (gpt2 only)
    rotary_dim: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def arch_from_config(cfg: Dict[str, Any], seq_len: int) -> Arch:
    """``cfg`` is a file of ``perf/configs``; ``seq_len`` the job's context
    (the package sizes its position table to the job's ``seq_len``)."""
    family = cfg["family"]
    d = int(cfg["n_embd"])
    return Arch(
        family=family,
        vocab_size=int(cfg["run"]["vocab_size"]),
        d_model=d,
        n_layers=int(cfg["n_layer"]),
        n_heads=int(cfg["n_head"]),
        d_ff=int(cfg["n_inner"] or 4 * d),
        n_positions=int(seq_len),
        rotary_dim=int(cfg.get("rotary_dim") or 0),
    )


# ------------------------------------------------------------------ weights
def _shapes(a: Arch) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """leaf path -> (shape, scale). ``scale`` is the std of a matrix, or of
    the noise around 0 (bias) / 1 (LayerNorm scale)."""
    L, D, F = a.n_layers, a.d_model, a.d_ff
    out = {
        "wte": ((a.vocab_size, D), 0.02),
        "ln_f/scale": ((D,), 0.02), "ln_f/bias": ((D,), 0.02),
        "blocks/ln_1/scale": ((L, D), 0.02), "blocks/ln_1/bias": ((L, D), 0.02),
        "blocks/qkv/kernel": ((L, D, 3 * D), 0.02), "blocks/qkv/bias": ((L, 3 * D), 0.02),
        "blocks/attn_out/kernel": ((L, D, D), 0.02), "blocks/attn_out/bias": ((L, D), 0.02),
        "blocks/mlp_in/kernel": ((L, D, F), 0.02), "blocks/mlp_in/bias": ((L, F), 0.02),
        "blocks/mlp_out/kernel": ((L, F, D), 0.02), "blocks/mlp_out/bias": ((L, D), 0.02),
    }
    if a.family == "gpt2":
        out["wpe"] = ((a.n_positions, D), 0.01)
        out["blocks/ln_2/scale"] = ((L, D), 0.02)
        out["blocks/ln_2/bias"] = ((L, D), 0.02)
    return out


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31), as a
    concrete array: jitted functions take it as an argument, so that a new
    seed compiles nothing anew."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def seeded_params(a: Arch, key) -> Dict[str, Any]:
    """Float32 weights from ``key`` (``seed_key(seed)``), every leaf random
    (biases and LayerNorm scales too, so that a leaf put in the wrong place
    shows). Traceable: under ``jax.jit`` the weights are made on the device in
    one call. Layer-stacked leaves (leading axis ``n_layers``) are a layout
    only."""
    flat = {}
    for i, (path, (shape, scale)) in enumerate(sorted(_shapes(a).items())):
        x = scale * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        flat[path] = 1.0 + x if path.endswith("/scale") else x
    return _nest(flat)


def _rotary_lane_perm(a: Arch):
    """Column order of the package's q (or k) projection in terms of the
    published one: per head, even rotary lanes, odd rotary lanes, the rest."""
    rd, hd = a.rotary_dim, a.head_dim
    head = list(range(0, rd, 2)) + list(range(1, rd, 2)) + list(range(rd, hd))
    return [h * hd + j for h in range(a.n_heads) for j in head]


def program_layout(a: Arch, tree: Dict[str, Any], xp=jnp) -> Dict[str, Any]:
    """A tree of the parameters' structure (weights, gradients, Adam moments)
    in the layout ``saturn_tpu/models/gpt2.py`` trains. For GPT-2 that is the
    reference's own; for GPT-J the q and k lanes are permuted (departure 4).
    ``xp`` is ``jnp`` (traceable) or ``numpy`` (host arrays)."""
    if a.family != "gptj":
        return tree
    D = a.d_model
    perm = xp.asarray(_rotary_lane_perm(a), dtype=xp.int32)
    cols = xp.concatenate([perm, D + perm, 2 * D + xp.arange(D, dtype=xp.int32)])
    out = dict(tree, blocks=dict(tree["blocks"]))
    out["blocks"]["qkv"] = {k: xp.take(v, cols, axis=-1)
                            for k, v in tree["blocks"]["qkv"].items()}
    return out


def program_params(a: Arch, key) -> Dict[str, Any]:
    """The seeded weights as the program is handed them. Traceable, like
    ``seeded_params``."""
    return program_layout(a, seeded_params(a, key))


def flat(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    """{leaf path: leaf}, paths joined by "/" as the package's checkpoints
    have them; the inverse of ``_nest``."""
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}/"))
        else:
            out[prefix + k] = v
    return out


# ------------------------------------------------------------------ forward
def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _rotate_every_two(x):
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)


def _rotary_interleaved(t, rotary_dim):
    """GPT-J's ``apply_rotary_pos_emb`` on (B, T, H, hd): the first
    ``rotary_dim`` lanes, pairs (2j, 2j+1) rotated by position x theta_j."""
    T = t.shape[1]
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                                  / rotary_dim))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[None, :, None, :]
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[None, :, None, :]
    rot, rest = t[..., :rotary_dim], t[..., rotary_dim:]
    rot = rot * cos + _rotate_every_two(rot) * sin
    return jnp.concatenate([rot, rest], axis=-1)


def forward(a: Arch, params, tokens, mm: Optional[Callable] = None):
    """(B, T) int tokens -> (B, T, V) float32 logits. ``mm(x, w)`` is the
    matrix product of activations ``x`` (..., K) and weights ``w`` (K, N); the
    default is float32. The control of ``perf/lib/refcheck.py`` passes a
    lower-precision one and changes nothing else."""
    mm = mm or (lambda x, w: x @ w)
    B, T = tokens.shape
    H, hd = a.n_heads, a.head_dim
    x = params["wte"][tokens]
    if a.family == "gpt2":
        x = x + params["wpe"][:T]
    blocks = params["blocks"]
    mask = jnp.tril(jnp.ones((T, T), dtype=bool))
    for l in range(a.n_layers):
        p = jax.tree_util.tree_map(lambda leaf: leaf[l], blocks)
        h = _layer_norm(x, p["ln_1"]["scale"], p["ln_1"]["bias"])
        qkv = mm(h, p["qkv"]["kernel"]) + p["qkv"]["bias"]
        q, k, v = (t.reshape(B, T, H, hd) for t in jnp.split(qkv, 3, axis=-1))
        if a.family == "gptj":
            q = _rotary_interleaved(q, a.rotary_dim)
            k = _rotary_interleaved(k, a.rotary_dim)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, H * hd)
        attn = mm(attn, p["attn_out"]["kernel"]) + p["attn_out"]["bias"]

        def mlp(inp):
            m = _gelu_new(mm(inp, p["mlp_in"]["kernel"]) + p["mlp_in"]["bias"])
            return mm(m, p["mlp_out"]["kernel"]) + p["mlp_out"]["bias"]

        if a.family == "gptj":
            x = x + attn + mlp(h)
        else:
            x = x + attn
            x = x + mlp(_layer_norm(x, p["ln_2"]["scale"], p["ln_2"]["bias"]))
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return mm(x, params["wte"].T)  # tied head (departure 2)


def loss_fn(a: Arch, params, tokens, mm: Optional[Callable] = None):
    """Next-token cross entropy, mean over the B x (T-1) targets."""
    logits = forward(a, params, tokens, mm)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


# ---------------------------------------------------------------- optimizer
def adamw_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adamw_step(params, grads, opt, lr: float):
    t = opt["t"] + 1
    tf = t.astype(jnp.float32)
    m = jax.tree_util.tree_map(
        lambda m_, g: ADAM_B1 * m_ + (1 - ADAM_B1) * g, opt["m"], grads)
    v = jax.tree_util.tree_map(
        lambda v_, g: ADAM_B2 * v_ + (1 - ADAM_B2) * g * g, opt["v"], grads)

    def new(p, m_, v_):
        m_hat = m_ / (1 - ADAM_B1 ** tf)
        v_hat = v_ / (1 - ADAM_B2 ** tf)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + ADAM_EPS) + WEIGHT_DECAY * p)

    return jax.tree_util.tree_map(new, params, m, v), {"m": m, "v": v, "t": t}


def param_shardings(a: Arch, devices) -> Dict[str, Any]:
    """For a state larger than one chip: a ``NamedSharding`` for every leaf
    of the parameter tree over a one-axis mesh of ``devices``, each leaf
    split along its largest axis that the chip count divides (the stacked
    layer axis is left whole: the layer loop indexes it) and replicated where
    none does. Plain ``jit`` shardings and nothing else: the compiler
    partitions the same float32 arithmetic, no technique of the program is
    involved."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(list(devices)), ("chips",))
    n = len(devices)
    out = {}
    for path, (shape, _) in _shapes(a).items():
        axes = range(1 if path.startswith("blocks/") else 0, len(shape))
        fit = [i for i in axes if shape[i] % n == 0 and shape[i] >= n]
        spec = [None] * len(shape)
        if fit:
            spec[max(fit, key=lambda i: shape[i])] = "chips"
        out[path] = NamedSharding(mesh, PartitionSpec(*spec))
    return out


@functools.lru_cache(maxsize=None)
def _jitted(a: Arch, lr: float, mm: Optional[Callable],
            devices: Optional[Tuple[Any, ...]] = None) -> Dict[str, Callable]:
    """The jitted pieces of ``train`` and ``logits_of``, made once for an
    architecture, a learning rate and a matmul (a process that reads many
    seeds traces and compiles each once). With ``devices`` (more than one
    chip) weights and moments are sharded over them (``param_shardings``),
    and a step takes its gradient one sequence at a time and averages: the
    sequences are equally long, so the mean of their losses is the batch's
    loss, and the float32 stash of a whole batch would not fit beside
    16 B/param."""

    def loss_and_grads(params, tokens):
        def of(t):
            return jax.value_and_grad(lambda p: loss_fn(a, p, t, mm))(params)

        if not devices or tokens.shape[0] == 1:
            return of(tokens)

        def add(acc, one):
            return jax.tree_util.tree_map(jnp.add, acc, of(one[None])), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree_util.tree_map(jnp.zeros_like, params))
        total, _ = jax.lax.scan(add, zero, tokens)
        return jax.tree_util.tree_map(lambda x: x / tokens.shape[0], total)

    def step(params, opt, tokens):
        loss, grads = loss_and_grads(params, tokens)
        params, opt = adamw_step(params, grads, opt, lr)
        return params, opt, loss

    by_path = param_shardings(a, devices) if devices else {}
    p_sh = _nest(by_path) if devices else None

    def make_params(k):
        seeded = seeded_params(a, k)
        return seeded if p_sh is None else jax.lax.with_sharding_constraint(seeded, p_sh)

    def moved(params, key):
        return jax.tree_util.tree_map(
            lambda p, p0: jnp.sqrt(jnp.sum(jnp.square(p - p0))),
            params, make_params(key))

    def logits(k, t):
        return forward(a, make_params(k), t, mm)

    if not devices:
        return {"params": jax.jit(make_params), "opt": jax.jit(adamw_init),
                "step": jax.jit(step, donate_argnums=(0, 1)),
                "moved": jax.jit(moved), "logits": jax.jit(logits)}
    from jax.sharding import NamedSharding, PartitionSpec

    whole = NamedSharding(by_path["wte"].mesh, PartitionSpec())  # on every chip
    o_sh = {"m": p_sh, "v": p_sh, "t": whole}
    return {"params": jax.jit(make_params, in_shardings=whole, out_shardings=p_sh),
            "opt": jax.jit(adamw_init, in_shardings=(p_sh,), out_shardings=o_sh),
            "step": jax.jit(step, in_shardings=(p_sh, o_sh, whole),
                            out_shardings=(p_sh, o_sh, whole), donate_argnums=(0, 1)),
            "moved": jax.jit(moved, in_shardings=(p_sh, whole), out_shardings=whole),
            "logits": jax.jit(logits, in_shardings=(whole, whole),
                              out_shardings=whole)}


def _over(devices) -> Optional[Tuple[Any, ...]]:
    """``devices`` as ``_jitted`` keys them: None for one chip or none."""
    return tuple(devices) if devices is not None and len(devices) > 1 else None


def train(a: Arch, seed: int, batches, lr: float,
          mm: Optional[Callable] = None, keep_state: bool = False,
          devices=None):
    """``len(batches)`` AdamW steps from the seeded weights: one jitted step,
    called in a Python loop. Returns (the loss before each step, as floats;
    the final state). The state is None unless ``keep_state``; then it is
    host arrays by leaf path, in the program's layout:
    ``{"m": first moments, "params": weights, "moved": ||weights - seeded
    weights|| per leaf}`` -- what a checkpoint of the program is held
    against. ``devices`` (more than one chip): the state is sharded over
    them, for a configuration whose 16 B/param overfill one chip."""
    fns = _jitted(a, float(lr), mm, _over(devices))
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
            import numpy as np

            m = opt["m"]
            del opt  # the second moments are not compared: free them first
            state = {"moved": {k: float(v) for k, v in
                               flat(fns["moved"](params, key)).items()}}
            for name, tree in (("m", m), ("params", params)):
                # the program's layout on the device, then one copy to the host
                state[name] = flat(jax.tree_util.tree_map(
                    np.asarray, program_layout(a, tree)))
            del m
    del params
    return out, state


def logits_of(a: Arch, seed: int, tokens, mm: Optional[Callable] = None,
              devices=None):
    """Float32 logits of the seeded weights on ``tokens``."""
    with jax.default_matmul_precision("highest"):
        return _jitted(a, 0.0, mm, _over(devices))["logits"](
            seed_key(seed), jnp.asarray(tokens))
