"""Collective matmul and ZeRO-3 prefetch: overlap-friendly sharded matmuls.

GSPMD lowers a ZeRO-sharded matmul as ``all-gather(W) -> dot_general``: every
MAC waits for the last gather hop (arxiv 2105.04663 §3.3 calls this out and
shows the fix). The collective-matmul decomposition splits the gather into S
ring hops interleaved with S partial ``dot_general``s, so hop s+1 streams
behind partial product s. The same idea applied across the scanned block
stack is ZeRO-3 prefetch: gather layer k+1's shards while layer k computes.

Both rewrites live behind config knobs on the fsdp/tp executors
(``parallel/fsdp.py``, ``parallel/tp.py``) and are profiled as grid
dimensions — realized cost picks overlapped vs serial, never faith. The
serial and prefetched ZeRO-3 programs are bit-identical (gathers are pure
data movement; the compute order never changes); the interleaved collective
matmul reassociates the contraction, so it is compared to the plain lowering
with a tolerance, never bitwise.
"""

from __future__ import annotations

import re
from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from saturn_tpu.ops.pipeline import run_passes

# Version tag for profile-cache fingerprints: bump when the overlapped
# lowering changes shape (a serial profile must never price an overlapped
# program, and vice versa).
OVERLAP_SET_VERSION = 1


def overlap_signature() -> str:
    """Content signature of the overlap machinery for cache identities."""
    return f"comm-overlap-v{OVERLAP_SET_VERSION}"


# ------------------------------------------------------------ ring gather
def ring_all_gather(
    x: jax.Array, *, axis_name: str, axis_size: int, axis: int = 0
) -> jax.Array:
    """All-gather ``x`` along ``axis`` via S-1 neighbor hops.

    Must be called inside ``shard_map``. Equivalent to
    ``lax.all_gather(..., tiled=True)`` but decomposed into ``ppermute``
    hops so the caller's scan can float each hop under unrelated compute
    (the ZeRO-3 prefetch consumer below). Chunk placement is by source
    index, so the result is the in-order concatenation — identical on every
    device and independent of hop scheduling.
    """
    S = int(axis_size)
    if S == 1:
        return x
    idx = lax.axis_index(axis_name)
    # Send my current chunk to the next device: after s hops I hold the
    # chunk that originated at (idx - s) % S.
    perm = [(j, (j + 1) % S) for j in range(S)]
    c = x.shape[axis]
    buf = jnp.zeros(
        x.shape[:axis] + (c * S,) + x.shape[axis + 1 :], dtype=x.dtype
    )

    def place(b, piece, s):
        src = (idx - s) % S
        return lax.dynamic_update_slice_in_dim(b, piece, src * c, axis)

    def step(carry, s):
        b, cur = carry
        nxt = lax.ppermute(cur, axis_name, perm)
        b = place(b, cur, s)
        return (b, nxt), None

    (buf, last), _ = lax.scan(step, (buf, x), jnp.arange(S - 1))
    return place(buf, last, S - 1)


# ------------------------------------------------------ collective matmul
def allgather_matmul(
    x: jax.Array,
    w_shard: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    overlap: bool = True,
) -> jax.Array:
    """``x @ unshard(w_shard)`` for a weight sharded on its contracting dim.

    ``w_shard`` is the local ``(K/S, N)`` row block of a ``(K, N)`` weight;
    ``x`` is ``(..., K)`` and replicated. Serial (``overlap=False``) is the
    GSPMD lowering: chain the S-1 gather hops, then one ``dot_general`` —
    the first MAC waits on the last hop. Overlapped interleaves: each hop's
    chunk feeds a partial ``dot_general`` accumulated immediately, so hop
    s+1 streams behind partial product s. The two forms reassociate the K
    contraction (chunked sum vs one reduction) — numerically close, not
    bitwise equal.
    """
    S = int(axis_size)
    if S == 1:
        return x @ w_shard
    idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % S) for j in range(S)]
    c = w_shard.shape[0]

    def x_block(src):
        return lax.dynamic_slice_in_dim(x, src * c, c, axis=-1)

    if not overlap:
        w = ring_all_gather(
            w_shard, axis_name=axis_name, axis_size=S, axis=0
        )
        return x @ w

    def step(carry, s):
        acc, cur = carry
        nxt = lax.ppermute(cur, axis_name, perm)
        acc = acc + x_block((idx - s) % S) @ cur
        return (acc, nxt), None

    acc = jnp.zeros(x.shape[:-1] + (w_shard.shape[-1],), dtype=x.dtype)
    (acc, last), _ = lax.scan(step, (acc, w_shard), jnp.arange(S - 1))
    return acc + x_block((idx - (S - 1)) % S) @ last


# --------------------------------------------------------- ZeRO-3 program
def _block_dim(shape: Tuple[int, ...], n_shard: int, min_size: int) -> Optional[int]:
    """Shard dim for a stacked block leaf ``(L, ...)``: largest trailing dim
    divisible by the axis size (ties prefer later dims, matching
    ``sharding.fsdp_rules``); ``None`` keeps the leaf replicated."""
    if len(shape) < 2 or int(np.prod(shape)) < min_size:
        return None
    best, best_size = None, -1
    for i, s in enumerate(shape[1:], start=1):
        if s % n_shard == 0 and s >= best_size:
            best, best_size = i, s
    return best


def zero3_block_rules(block_key: str = "blocks", axis: str = "data",
                      min_size: int = 1024):
    """Sharding rules matching :func:`zero3_loss_and_grads` in_specs: block
    stack leaves shard their largest non-layer dim over ``axis``; everything
    else (embeddings, norms, head) stays replicated. Works on full state
    paths ('params/blocks/w', 'opt_state/0/mu/blocks/w', ...)."""
    seg = re.compile(rf"(^|/){re.escape(block_key)}(/|$)")

    def rules(path: str, shape: Tuple[int, ...], mesh_axes) -> P:
        if not seg.search(path):
            return P()
        d = _block_dim(tuple(shape), mesh_axes[axis], min_size)
        if d is None:
            return P()
        spec = [None] * len(shape)
        spec[d] = axis
        return P(*spec)

    return rules


def zero3_loss_and_grads(
    params: Any,
    tokens: jax.Array,
    *,
    mesh: Any,
    embed_fn: Callable[[Any, jax.Array], jax.Array],
    block_fn: Callable[[Any, jax.Array], jax.Array],
    head_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    block_key: str = "blocks",
    shard_axis: str = "data",
    batch_axes: Optional[Sequence[str]] = None,
    prefetch: bool = True,
    remat: bool = False,
    min_size: int = 1024,
    passes: int = 1,
    between_fn: Optional[Callable[[Any, jax.Array], jax.Array]] = None,
):
    """(loss, grads) for one ZeRO-3 step with explicit, prefetchable gathers.

    ``passes`` / ``between_fn``: a looped model (``hints["pipeline"]``'s
    ``passes`` and ``between``) sends the activations through the whole
    gathered stack ``passes`` times, ``between_fn(other, h)`` between one
    pass and the next; each pass gathers every layer again.

    The block stack enters sharded per :func:`zero3_block_rules`; the scan
    over layers gathers each layer's shards with :func:`ring_all_gather`.
    ``prefetch=True`` gathers layer k+1 inside layer k's scan step (the
    hops carry no dependence on the step's compute, so they ride under it);
    ``prefetch=False`` gathers layer k on the critical path, the GSPMD-like
    serial lowering. Both orders see identical values — bit-identical loss
    and grads, proven by tests/test_overlap.py.

    ``batch_axes``: mesh axes the batch dim shards over (default: every
    mesh axis), letting tp reuse the program as its weight-gathered
    lowering — batch over ('data','model'), shards over 'model'.
    """
    axes = tuple(batch_axes) if batch_axes is not None else tuple(mesh.axis_names)
    S = int(mesh.shape[shard_axis])
    n_members = int(np.prod([mesh.shape[a] for a in axes]))

    blocks = params[block_key]
    leaves = jax.tree_util.tree_leaves(blocks)
    if not leaves:
        raise ValueError(f"params[{block_key!r}] has no leaves")
    L = int(leaves[0].shape[0])

    # Static per-leaf shard dims (-1 = replicated; None would vanish as an
    # empty pytree): the in_specs and the in-scan gather must agree
    # leaf-for-leaf or the program reshards silently.
    dims = jax.tree.map(
        lambda a: _block_dim(tuple(a.shape), S, min_size) or -1, blocks
    )

    def _pspec(ndim: int, d: int) -> P:
        spec = [None] * ndim
        if d >= 0:
            spec[d] = shard_axis
        return P(*spec)

    in_block_specs = jax.tree.map(
        lambda a, d: _pspec(a.ndim, d), blocks, dims
    )
    param_specs = {
        k: (in_block_specs if k == block_key
            else jax.tree.map(lambda a: P(), v))
        for k, v in params.items()
    }
    batch_spec = P(axes)

    def gather_layer(lp):
        def one(a, d):
            if d < 0:
                return a
            return ring_all_gather(
                a, axis_name=shard_axis, axis_size=S, axis=d - 1
            )

        return jax.tree.map(one, lp, dims)

    def layer_shard(stack, k):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, k, axis=0, keepdims=False),
            stack,
        )

    blk = jax.checkpoint(block_fn) if remat else block_fn

    def run_stack(stack, h):
        if prefetch:
            def body(carry, k):
                hh, cur_full = carry
                # Issue layer k+1's gather hops before layer k's
                # compute: no data dependence, the DMA rides under it.
                nxt = gather_layer(
                    layer_shard(stack, jnp.minimum(k + 1, L - 1))
                )
                hh = blk(cur_full, hh)
                return (hh, nxt), None

            first = gather_layer(layer_shard(stack, 0))
            (h_out, _), _ = lax.scan(body, (h, first), jnp.arange(L))
        else:
            def body(hh, k):
                return blk(gather_layer(layer_shard(stack, k)), hh), None

            h_out, _ = lax.scan(body, h, jnp.arange(L))
        return h_out

    def local_fn(p, tok):
        def loss_of(pp):
            stack = pp[block_key]
            other = {k: v for k, v in pp.items() if k != block_key}
            h_out = run_passes(
                partial(run_stack, stack), embed_fn(other, tok), passes,
                between_fn and partial(between_fn, other),
            )
            logits = head_fn(other, h_out)
            # LOCAL mean only: differentiating a psum'd scalar bakes the
            # psum transpose convention (identity vs psum — it changed
            # across jax releases) into the grad scale. Normalizing outside
            # the grad is convention-independent.
            return loss_fn(logits, tok)

        loss, grads = jax.value_and_grad(loss_of)(p)
        loss = lax.psum(loss, axes) / n_members
        # Sharded leaves already hold the total over the gather ring (every
        # remote use along ``shard_axis`` backpropagates home through the
        # reversed ring) — psum the remaining batch axes. Replicated leaves
        # hold only the local contribution and psum everything.
        rest = tuple(a for a in axes if a != shard_axis)
        out = {}
        for k, v in grads.items():
            if k == block_key:
                out[k] = jax.tree.map(
                    lambda g, d: (
                        (lax.psum(g, rest) if rest else g) if d >= 0
                        else lax.psum(g, axes)
                    ) / n_members,
                    v, dims,
                )
            else:
                out[k] = jax.tree.map(
                    lambda g: lax.psum(g, axes) / n_members, v
                )
        return loss, out

    mapped = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, batch_spec),
        out_specs=(P(), param_specs),
        check_vma=False,
    )
    return mapped(params, tokens)
