"""Ring attention + sharded LM loss: sequence parallelism over an ICI ring.

Long-context support the reference lacks entirely (SURVEY.md §5
"Long-context / sequence parallelism: entirely absent"; its context was pinned
to 512 tokens, ``dataloaders.py:58``, ``GPTJ.py:507``). Delivered the way the
reference delivers every capability — as a technique behind the UDP plugin
interface (``Technique.py:24``) — but built TPU-first:

- The sequence dimension is sharded over a ``seq`` mesh axis. Each device
  holds a (B, T/S) token chunk and its q/k/v blocks.
- **Ring attention** (Liu et al. 2023): k/v blocks rotate around the ring
  with ``lax.ppermute`` (neighbor hops that ride ICI) while each device
  accumulates its queries' attention with the online-softmax (flash)
  recurrence in fp32. Peak activation memory per device drops from O(T²) to
  O(T²/S²) score blocks; compute overlaps the permute because XLA sees the
  whole loop.
- Causality is global: position offsets come from ``axis_index``, so block
  (i,j) is fully masked when j > i, lower-triangle-masked on the diagonal,
  and unmasked below — masked blocks contribute nothing thanks to the
  -inf-safe accumulator.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = True,
    overlap: bool = False,
) -> jax.Array:
    """Blockwise causal attention over a sharded sequence axis.

    Must be called inside ``shard_map``. ``q``/``k``/``v`` are the local
    chunks, shape (B, H, Tc, D) with Tc = T / axis_size; returns the local
    (B, H, Tc, D) attention output. fp32 softmax accumulation; matmuls feed
    the MXU in the input dtype with fp32 accumulation.

    ``overlap=True`` double-buffers the neighbor hop: the scan body issues
    the ``ppermute`` shipping block s+1 BEFORE folding block s, so the hop's
    DMA is in flight while the MXU chews the current block. Same values
    through the same accumulate ops in the same order — bit-identical to the
    serial schedule (asserted by tests/test_overlap.py) — only the program
    order of the hop changes, which is what the TPU scheduler keys on.
    """
    B, H, Tc, D = q.shape
    idx = lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(D)
    qpos = idx * Tc + jnp.arange(Tc)

    # The accumulators become device-varying inside the scan (they fold q and
    # the axis index), and shard_map's type check wants the initial carry to
    # say so already.
    varying = tuple(sorted(set(jax.typeof(q).vma) | {axis_name}))

    def init(shape, fill):
        return lax.pcast(
            jnp.full(shape, fill, jnp.float32), varying, to="varying"
        )

    o0 = init((B, H, Tc, D), 0.0)
    l0 = init((B, H, Tc), 0.0)
    m0 = init((B, H, Tc), -jnp.inf)
    # Rotate kv blocks one hop per step: after s steps this device holds the
    # block originally on shard (idx - s) mod S.
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def accumulate(o, l, m, kc, vc, s):
        """Fold kv block ``(idx - s) mod S`` into the flash recurrence."""
        scores = (
            jnp.einsum(
                "bhqd,bhkd->bhqk", q, kc, preferred_element_type=jnp.float32
            )
            * scale
        )
        if causal:
            kpos = ((idx - s) % axis_size) * Tc + jnp.arange(Tc)
            mask = qpos[:, None] >= kpos[None, :]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        # A still-all-masked row has m_new == -inf; exp(x - 0) with x = -inf
        # gives exactly 0, so the safe substitute keeps every term finite.
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(scores - safe_m[..., None])
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        l_new = corr * l + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhqk,bhkd->bhqd",
            p.astype(v.dtype),
            vc,
            preferred_element_type=jnp.float32,
        )
        return corr[..., None] * o + pv, l_new, m_new

    def step(carry, s):
        o, l, m, kc, vc = carry
        o, l, m = accumulate(o, l, m, kc, vc, s)
        kc, vc = lax.ppermute((kc, vc), axis_name, perm)
        return (o, l, m, kc, vc), None

    def step_overlapped(carry, s):
        # Hop first: ship block s+1 while block s is still being folded.
        # The ppermute's operands come straight from the carry, so it has no
        # data dependence on this step's accumulate.
        o, l, m, kc, vc = carry
        kc_next, vc_next = lax.ppermute((kc, vc), axis_name, perm)
        o, l, m = accumulate(o, l, m, kc, vc, s)
        return (o, l, m, kc_next, vc_next), None

    # S-1 (accumulate, rotate) steps in the scan; the final block is folded
    # outside it so no dead ppermute ships k/v nobody reads.
    o, l, m, kc, vc = o0, l0, m0, k, v
    if axis_size > 1:
        (o, l, m, kc, vc), _ = lax.scan(
            step_overlapped if overlap else step,
            (o, l, m, kc, vc),
            jnp.arange(axis_size - 1),
        )
    o, l, _ = accumulate(o, l, m, kc, vc, axis_size - 1)
    out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.astype(q.dtype)


def sharded_lm_loss_terms(
    logits: jax.Array, tokens: jax.Array, *, axis_name: str, axis_size: int
) -> Tuple[jax.Array, jax.Array]:
    """Local (loss_sum, count) for shifted next-token CE over a sharded sequence.

    The label for a chunk's last position is the *next* chunk's first token,
    fetched with one ppermute; the final chunk's last position (no successor
    anywhere) is masked out. psum the two outputs over all axes and divide to
    get the same scalar ``models.loss.pretraining_loss`` computes densely.
    """
    idx = lax.axis_index(axis_name)
    # shard i receives shard (i+1)'s first token: source j sends to j-1.
    perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
    next_first = lax.ppermute(tokens[:, :1], axis_name, perm)
    labels = jnp.concatenate([tokens[:, 1:], next_first], axis=1)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    valid = jnp.ones_like(ce).at[:, -1].set(
        jnp.where(idx == axis_size - 1, 0.0, 1.0)
    )
    return (ce * valid).sum(), valid.sum()


def ring_loss_and_grads(
    params: Any,
    tokens: jax.Array,
    *,
    mesh: Any,
    apply_fn: Callable[[Any, jax.Array], jax.Array],
    data_axis: str = "data",
    seq_axis: str = "seq",
):
    """(loss, grads) for one sequence-parallel step over a ('data','seq') mesh.

    ``apply_fn`` must be ring-aware (built with ``seq_axis`` set so its
    attention calls :func:`ring_attention`); it receives the local (Bd, Tc)
    token chunk. Params are replicated; grads psum over both axes — the
    TPU-native analog of the reference's NCCL allreduce, riding ICI.
    """
    S = mesh.shape[seq_axis]

    def local_fn(p, tokens_local):
        def loss_of(pp):
            logits = apply_fn(pp, tokens_local)
            lsum, cnt = sharded_lm_loss_terms(
                logits, tokens_local, axis_name=seq_axis, axis_size=S
            )
            lsum = lax.psum(lsum, (data_axis, seq_axis))
            cnt = lax.psum(cnt, (data_axis, seq_axis))
            return lsum / cnt

        loss, grads = jax.value_and_grad(loss_of)(p)
        grads = jax.tree.map(lambda g: lax.psum(g, (data_axis, seq_axis)), grads)
        return loss, grads

    param_specs = jax.tree.map(lambda _: P(), params)
    mapped = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, P(data_axis, seq_axis)),
        out_specs=(P(), param_specs),
        check_vma=False,
    )
    return mapped(params, tokens)
