"""GPipe microbatch schedule over a ``stage`` mesh axis, as one jittable op.

TPU-native replacement for torchgpipe (reference ``Pipeline.py:24-167``,
SURVEY.md §2.2): where torchgpipe partitions an ``nn.Sequential`` across GPUs
and streams microbatches over CUDA copies, here the scanned layer stack is
*sharded* over a ``stage`` mesh axis and microbatch activations rotate between
neighbor stages with ``lax.ppermute`` — point-to-point hops that ride ICI.

The whole schedule lives inside ``shard_map`` and is differentiated with
``jax.value_and_grad`` *inside* the mapped body: ``ppermute``'s transpose is
the inverse permutation, so reverse-mode AD automatically yields the reverse
pipeline schedule (activations flow last→first stage in the backward pass),
with no hand-written backward.

Schedule shape (classic GPipe, bubble fraction (S-1)/(M+S-1)):

    t:      0    1    2    ...                    M+S-2
    stage0  mb0  mb1  mb2  ...  mbM-1  -    -
    stage1  -    mb0  mb1  ...         mbM-1 -
    stage2  -    -    mb0  ...               mbM-1

The language-model head is *not* computed inside the schedule loop (which
would redo it on every stage every tick): last-stage outputs are
``psum_scatter``-ed so each stage receives exactly its M/S chunk and computes
the head + loss for it — balancing the vocab-sized matmul across the gang at
half the wire cost of a full psum broadcast, with no (M, ...) activation
buffer materialized per stage. Embeddings are likewise computed lazily, one
microbatch per tick and only on stage 0 (``lax.cond``), instead of all M
up front on every stage (VERDICT r1 weak item 8).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

#: Version tag for the *set* of pipeline schedules this module implements.
#: Folded into the profile-cache fingerprint so entries profiled before a
#: schedule was added (or after its program changes) miss instead of serving
#: stale GPipe-only timings.  v2: double-buffered (overlap=True) variants of
#: both schedules — hop latency H=2, ppermute issued before the tick's
#: compute.
SCHEDULE_SET_VERSION = "gpipe+1f1b:v2"

PIPELINE_SCHEDULES = ("gpipe", "1f1b")


def _hop_latency(overlap: bool) -> int:
    """Ticks an activation spends in flight between neighbor stages.

    Serial schedule: the hop is issued after the tick's compute and consumed
    next tick (H=1).  Double-buffered: the hop is issued at the TOP of the
    tick from the previous tick's output, so its DMA rides under this tick's
    compute and the value lands one tick later (H=2).  Every schedule
    quantity below is a function of H; H=1 reproduces the v1 programs
    exactly.
    """
    return 2 if overlap else 1


def schedule_signature() -> str:
    """Fingerprint component identifying the available schedule programs."""
    return SCHEDULE_SET_VERSION


def schedule_bubble_fraction(
    schedule: str, n_stages: int, n_microbatches: int, overlap: bool = False
) -> float:
    """Analytic idle (ramp) fraction of one pipelined step, per stage.

    GPipe runs forwards and backwards as two separate M+S-1-tick waves, so a
    stage idles for the full 2(S-1)-tick ramp of a 2(M+S-1)-tick wall:
    (S-1)/(M+S-1).  1F1B packs one forward and one backward into each steady
    tick, shrinking the wall to M+2(S-1) ticks with the same 2(S-1) ramp:
    2(S-1)/(2(M+2(S-1))) = (S-1)/(M+2(S-1)) — *smaller*, which is exactly
    why a 1F1B job leaves fewer gaps for a co-scheduled partner to fill
    (the solver's co-location term prices this, see ``solver/milp.py``).

    ``overlap=True`` (hop latency H=2) deepens the ramp H-fold in ticks —
    the price of double-buffering; what it buys (the hop leaving each tick's
    critical path) is modeled by the per-op-class overlap factor in
    ``analysis/shardflow/prior.py``, not here.
    """
    S, M = int(n_stages), int(n_microbatches)
    if S <= 1:
        return 0.0
    H = _hop_latency(overlap)
    if schedule == "1f1b":
        return H * (S - 1) / (M + 2 * H * (S - 1))
    return H * (S - 1) / (M + H * (S - 1))


def stash_depth(
    n_stages: int, n_microbatches: int, schedule: str = "1f1b",
    overlap: bool = False,
) -> int:
    """In-flight forward-activation stash depth of the staged schedule.

    A microbatch's stage input is stashed at its forward tick ``H·s + m``
    and freed at its backward tick ``m + C2 + H(S-1-s)`` (C2 = H(S-1) for
    1F1B), so at most ``C2 + H(S-1) + 1`` microbatches are live per stage —
    O(S), independent of M.  The staged-GPipe ordering flushes all M
    forwards first, so its stash is the full ``M`` — the memory cliff 1F1B
    exists to avoid.  Serial (H=1) 1F1B: ``2S-1``.
    """
    S, M = int(n_stages), int(n_microbatches)
    H = _hop_latency(overlap)
    c2 = H * (S - 1) if schedule == "1f1b" else M + H * (S - 1)
    return max(1, min(M, c2 + H * (S - 1) + 1))


def balance_stages(costs: Sequence[float], n_stages: int) -> Tuple[int, ...]:
    """Contiguous layer->stage partition minimizing the max per-stage cost.

    Returns per-stage layer counts (len ``n_stages``, sums to ``len(costs)``,
    every span >= 1). The TPU-native analog of torchgpipe's
    ``balance_by_time`` (reference ``Pipeline.py:94-103``): the reference
    timed each layer on one GPU and block-partitioned; here the costs come
    from the model's ``layer_costs`` hint (profiled or FLOP-derived) and the
    exact DP replaces the reference's heuristic — L is tens, so the
    O(S·L²) linear-partition DP is free at trace time.
    """
    L = len(costs)
    S = n_stages
    if S < 1 or S > L:
        raise ValueError(f"cannot split {L} layers into {S} stages")
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + float(c))

    def span_cost(i, j):  # layers [i, j)
        return prefix[j] - prefix[i]

    INF = (float("inf"), float("inf"))
    # best[s][j] = lexicographic (max stage cost, max span length) splitting
    # layers [0, j) into s stages. The secondary criterion breaks max-cost
    # ties toward the smallest longest span: n_max sets every stage's padded
    # param residency and scan length, so a tie spent on a longer span is
    # pure memory/schedule waste.
    best = [[INF] * (L + 1) for _ in range(S + 1)]
    cut = [[0] * (L + 1) for _ in range(S + 1)]
    best[0][0] = (0.0, 0)
    for s in range(1, S + 1):
        for j in range(s, L - (S - s) + 1):
            for i in range(s - 1, j):
                prev = best[s - 1][i]
                cand = (max(prev[0], span_cost(i, j)), max(prev[1], j - i))
                if cand < best[s][j]:
                    best[s][j] = cand
                    cut[s][j] = i
    spans = []
    j = L
    for s in range(S, 0, -1):
        i = cut[s][j]
        spans.append(j - i)
        j = i
    return tuple(reversed(spans))


def _pad_stack(blocks: Any, spans: Sequence[int], n_max: int):
    """Repack a (L, ...) stacked layer tree into (S*n_max, ...) span-major
    order, zero-padding each stage's span to ``n_max`` — the equal-shard
    layout ``shard_map`` needs. Returns (padded_tree, active_mask).

    Implemented as a gather + mask, NOT ``jnp.concatenate``: on jax 0.4.x,
    feeding a concat-built intermediate into a shard_map in_spec that shards
    only some mesh axes mis-lowers the reshard as a reduction over the
    unsharded axes — every data replica after the first silently received
    the layer stack multiplied by the replica count (d=1 meshes and eager
    execution were unaffected, which is how it went unnoticed).
    """
    bounds = [0]
    for s in spans:
        bounds.append(bounds[-1] + s)
    src = jnp.asarray(
        [bounds[i] + min(k, s - 1) for i, s in enumerate(spans) for k in range(n_max)],
        dtype=jnp.int32,
    )
    active = jnp.asarray(
        [k < s for s in spans for k in range(n_max)], dtype=jnp.bool_
    )

    def pad_leaf(a):
        taken = jnp.take(a, src, axis=0)
        m = active.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, taken, jnp.zeros((), a.dtype))

    return jax.tree.map(pad_leaf, blocks), active


def _unpad_stack(padded: Any, spans: Sequence[int], n_max: int):
    """Inverse of :func:`_pad_stack` for the gradient tree.

    Also a gather, for the same reason ``_pad_stack`` is: the padded grad
    tree leaves ``shard_map`` sharded on the stage axis only, and a
    concat-built consumer of such an operand triggers the 0.4.x
    reshard-as-reduction mis-lowering — block grads came back multiplied
    by the data-replica count. Each global layer has exactly one active
    slot (inactive slots carry zero grad), so the gather is exact.
    """
    src = jnp.asarray(
        [i * n_max + k for i, s in enumerate(spans) for k in range(s)],
        dtype=jnp.int32,
    )
    return jax.tree.map(lambda a: jnp.take(a, src, axis=0), padded)


def _resolve_spans(params, block_key, S, stage_spans):
    """Validate/normalize ``stage_spans`` and pad the layer stack if unequal.

    Returns ``(params, spans, n_max)`` where ``spans`` is None on the
    equal-split fast path.  Shared by both schedule programs so they accept
    identical (spans, microbatches) inputs.

    The per-stage active mask is NOT returned: it must be derived from
    ``lax.axis_index`` inside the mapped body (see ``_local_active``), never
    passed as a shard_map operand — a closed-over *constant* with a sharded
    in_spec is mis-sharded under jit on multi-axis meshes (devices beyond
    the first data row receive the wrong shard), which silently corrupted
    the uneven-span schedule for every data-parallel replica but the first.
    """
    L = jax.tree.leaves(params[block_key])[0].shape[0]
    spans = tuple(stage_spans) if stage_spans is not None else None
    if spans is not None:
        if len(spans) != S or sum(spans) != L or min(spans) < 1:
            raise ValueError(
                f"stage_spans {spans} must be {S} positive counts summing "
                f"to {L} layers"
            )
        if len(set(spans)) == 1:
            spans = None  # equal spans: take the unpadded fast path
    if spans is None and L % S != 0:
        raise ValueError(
            f"{L} layers not divisible by {S} stages; pass stage_spans "
            "(see balance_stages)"
        )
    n_max = max(spans) if spans is not None else L // S
    if spans is not None:
        padded_blocks, _ = _pad_stack(params[block_key], spans, n_max)
        params = dict(params)
        params[block_key] = padded_blocks
    return params, spans, n_max


def _local_active(spans, n_max, idx):
    """This stage's active-slot mask, computed per device from its stage
    index (replicated (S,) constant + local iota — safe inside shard_map,
    unlike a stage-sharded constant operand; see ``_resolve_spans``)."""
    if spans is None:
        return None
    spans_arr = jnp.asarray(spans, jnp.int32)
    return jnp.arange(n_max, dtype=jnp.int32) < spans_arr[idx]


def _make_stage_runner(block_fn, remat):
    """Per-stage forward over the local (padded) span of scanned layers."""
    one_block = jax.checkpoint(block_fn) if remat else block_fn

    def run_stage(local_blocks, active_loc, x):
        if active_loc is None:
            def body(h, layer_params):
                return one_block(layer_params, h), None

            y, _ = lax.scan(body, x, local_blocks)
        else:
            # padded slot -> identity; lax.cond (not select) so the skipped
            # block never executes — a padded stage costs only its real span
            def body(h, xs):
                layer_params, act = xs
                h2 = lax.cond(
                    act, lambda hh: one_block(layer_params, hh),
                    lambda hh: hh, h,
                )
                return h2, None

            y, _ = lax.scan(body, x, (local_blocks, active_loc))
        return y

    return run_stage


def pipeline_loss_and_grads(
    params: Any,
    tokens: jax.Array,
    *,
    mesh: Any,
    block_key: str,
    embed_fn: Callable[[Any, jax.Array], jax.Array],
    block_fn: Callable[[Any, jax.Array], jax.Array],
    head_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    n_microbatches: int,
    remat: bool = False,
    data_axis: str = "data",
    stage_axis: str = "stage",
    stage_spans: Optional[Sequence[int]] = None,
):
    """(loss, grads) for one pipelined step over a ('data','stage') mesh.

    ``params`` is the full param tree; ``params[block_key]`` carries a
    leading layer axis (the model-structure contract the reference imposed
    via ``nn.Sequential`` flattening, ``GPTJ.py:502-526``). ``tokens`` is
    the global (B, T) batch; each data shard is split into
    ``n_microbatches`` microbatches.

    ``stage_spans``: per-stage layer counts for an UNEQUAL partition (from
    :func:`balance_stages`); default is the even split, which requires the
    layer count to divide by the stage count. Unequal spans are executed by
    zero-padding each stage's span to the longest one and skipping padded
    slots with ``lax.cond`` — stages still hold equal-shaped shards (the
    ``shard_map`` contract) but run only their real layers.
    """
    S = mesh.shape[stage_axis]
    M = n_microbatches
    if M % S != 0:
        raise ValueError(f"n_microbatches {M} must be a multiple of stages {S}")

    params, spans, n_max = _resolve_spans(params, block_key, S, stage_spans)
    run_stage = _make_stage_runner(block_fn, remat)

    block_specs = jax.tree.map(lambda _: P(stage_axis), params[block_key])
    param_specs = {
        k: (block_specs if k == block_key else jax.tree.map(lambda _: P(), v))
        for k, v in params.items()
    }

    def local_fn(p, local_tokens):
        """Runs on one (data shard, stage): local_tokens (Bd, T) int32."""
        idx = lax.axis_index(stage_axis)
        active_loc = _local_active(spans, n_max, idx)
        blocks = p[block_key]
        other = {k: v for k, v in p.items() if k != block_key}

        Bd, T = local_tokens.shape
        if Bd % M != 0:
            raise ValueError(f"per-shard batch {Bd} not divisible by M={M}")
        mb = Bd // M
        tokens_r = local_tokens.reshape(M, mb, T)

        def loss_of(p_local):
            blocks_, other_ = p_local
            # Activation shape/dtype without computing anything.
            act = jax.eval_shape(lambda t: embed_fn(other_, t), tokens_r[0])
            act_shape, act_dtype = act.shape, act.dtype
            outs0 = jnp.zeros((M,) + act_shape, act_dtype)
            zero = jnp.zeros(act_shape, act_dtype)

            def tick(carry, t):
                prev, outs = carry
                # Lazy, stage-0-only embedding: one microbatch per tick via
                # lax.cond, so stages 1..S-1 never pay the gather and no
                # (M, ...) embedding buffer exists anywhere (r1 embedded all
                # M microbatches on every stage).
                inp0 = lax.cond(
                    jnp.logical_and(idx == 0, t < M),
                    lambda tt: embed_fn(
                        other_,
                        lax.dynamic_index_in_dim(
                            tokens_r, jnp.minimum(tt, M - 1), keepdims=False
                        ),
                    ).astype(act_dtype),
                    lambda tt: zero,
                    t,
                )
                x_in = jnp.where(idx == 0, inp0, prev)
                y = run_stage(blocks_, active_loc, x_in)
                # Record last-stage finished microbatch t-(S-1).
                slot = jnp.clip(t - (S - 1), 0, M - 1)
                cur = lax.dynamic_index_in_dim(outs, slot, keepdims=False)
                new = jnp.where(t >= S - 1, y, cur)
                outs = lax.dynamic_update_index_in_dim(outs, new, slot, 0)
                # Rotate activations one stage forward.
                y_next = lax.ppermute(
                    y, stage_axis, [(i, (i + 1) % S) for i in range(S)]
                )
                return (y_next, outs), None

            (_, outs), _ = lax.scan(
                tick, (zero, outs0), jnp.arange(M + S - 1)
            )

            # Scatter last-stage outputs: each stage receives exactly its
            # M/S chunk (psum_scatter = half a psum's wire bytes, and the
            # full (M, ...) buffer is never broadcast), then computes the
            # vocab-sized head + loss for that chunk.
            chunk = M // S
            my_outs = lax.psum_scatter(
                jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)),
                stage_axis, scatter_dimension=0, tiled=True,
            )
            my_tokens = lax.dynamic_slice_in_dim(tokens_r, idx * chunk, chunk, 0)

            def one_loss(h, t):
                return loss_fn(head_fn(other_, h), t)

            # Return the per-stage PARTIAL loss (own chunk / S) and psum
            # *outside* the differentiated function.  Differentiating through
            # a trailing psum(·)/S per-device is the check_vma=False psum
            # footgun: psum's transpose re-sums the already-replicated
            # cotangent across stages, and the later g_other psum counted the
            # stage sum a second time — every gradient came out exactly S×
            # too large (masked in training only because Adam's second-moment
            # normalization is scale-invariant).
            return jnp.mean(jax.vmap(one_loss)(my_outs, my_tokens)) / S

        loss, (g_blocks, g_other) = jax.value_and_grad(loss_of)((blocks, other))
        loss = lax.psum(loss, stage_axis)
        # Cotangent bookkeeping shard_map leaves to us: replicated params get
        # per-device partial grads — sum over stages; everything averages
        # over the data axis (the DP grad sync NCCL did for the reference).
        g_other = jax.tree.map(lambda g: lax.psum(g, stage_axis), g_other)
        grads = dict(g_other)
        grads[block_key] = g_blocks
        grads = jax.tree.map(lambda g: lax.pmean(g, data_axis), grads)
        loss = lax.pmean(loss, data_axis)
        return loss, grads

    grad_specs = dict(param_specs)
    mapped = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, P(data_axis)),
        out_specs=(P(), grad_specs),
        check_vma=False,
    )
    loss, grads = mapped(params, tokens)
    if spans is not None:
        grads = dict(grads)
        grads[block_key] = _unpad_stack(grads[block_key], spans, n_max)
    return loss, grads


def staged_pipeline_loss_and_grads(
    params: Any,
    tokens: jax.Array,
    *,
    mesh: Any,
    block_key: str,
    embed_fn: Callable[[Any, jax.Array], jax.Array],
    block_fn: Callable[[Any, jax.Array], jax.Array],
    head_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    n_microbatches: int,
    remat: bool = False,
    data_axis: str = "data",
    stage_axis: str = "stage",
    stage_spans: Optional[Sequence[int]] = None,
    schedule: str = "1f1b",
    overlap: bool = False,
):
    """(loss, grads) with an *explicitly staged* backward — 1F1B by default.

    Unlike :func:`pipeline_loss_and_grads` (which differentiates the whole
    GPipe scan with ``jax.value_and_grad`` and lets AD derive the reverse
    wave), this program stages the backward by hand: each scan tick has a
    forward phase and a backward phase, and the schedule is a pair of index
    maps over a single backward launch offset ``C``::

        forward  of microbatch m on stage s at tick  s + m
        backward of microbatch m on stage s at tick  m + C - s

        schedule="1f1b":   C = 2(S-1)      — steady state interleaves one
                                             forward and one backward per
                                             tick; wall M + 2(S-1) ticks;
                                             activation stash depth 2S-1
        schedule="gpipe":  C = M + 2(S-1)  — all forwards flush first
                                             (classic GPipe order); wall
                                             2(M+S-1) ticks; stash depth M

    The two schedules share one scan body — they differ only in the Python
    constant ``C`` and the trip count — so every per-microbatch forward,
    vjp, and gradient accumulation (increasing-m order per stage) is the
    *same jaxpr* with the same inputs in both: summed gradients come out
    bit-identical, which is what lets the trial runner pick the schedule on
    realized cost alone (``tests/test_pipeline.py`` proves it on a CPU mesh).

    ``overlap=True`` double-buffers both hops: each tick FIRST issues the
    ppermutes shipping the PREVIOUS tick's activation/cotangent (held in two
    pending carry slots), then runs its forward/backward phases — the hop's
    operands predate the tick's compute, so its DMA rides underneath it.
    Index maps generalize with hop latency H (= 2 overlapped, 1 serial)::

        forward  of microbatch m on stage s at tick  H·s + m
        backward of microbatch m on stage s at tick  m + C2 + H(S-1-s)
        C2 = H(S-1) (1f1b) | M + H(S-1) (gpipe);  wall M + C2 + H(S-1)

    Per-microbatch jaxpr and per-stage accumulation order are unchanged, so
    overlapped grads are bit-identical to serial (``tests/test_overlap.py``)
    — the schedule only stretches the ramp by H.

    The backward phase recomputes the stage forward from a stashed stage
    *input* under ``jax.vjp`` (torchgpipe-style per-microbatch
    checkpointing): residency is the depth-``stash_depth(S, M, schedule)``
    input stash plus one transient set of span residuals, instead of the AD
    path's per-tick residuals for all M+S-1 dense ticks.  Unlike the GPipe
    program there is no ``M % S`` constraint (no ``psum_scatter`` head
    chunking — the last stage runs head+loss per microbatch at its own
    tick), so microbatch counts only need to divide the per-shard batch.
    """
    S = mesh.shape[stage_axis]
    M = n_microbatches
    if schedule not in PIPELINE_SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    if M < 1:
        raise ValueError(f"n_microbatches must be >= 1, got {M}")
    H = _hop_latency(overlap)
    C2 = H * (S - 1) if schedule == "1f1b" else M + H * (S - 1)
    n_ticks = M + C2 + H * (S - 1)
    D = max(1, min(M, C2 + H * (S - 1) + 1))

    params, spans, n_max = _resolve_spans(params, block_key, S, stage_spans)
    run_stage = _make_stage_runner(block_fn, remat)

    block_specs = jax.tree.map(lambda _: P(stage_axis), params[block_key])
    param_specs = {
        k: (block_specs if k == block_key else jax.tree.map(lambda _: P(), v))
        for k, v in params.items()
    }

    def local_fn(p, local_tokens):
        idx = lax.axis_index(stage_axis)
        active_loc = _local_active(spans, n_max, idx)
        blocks = p[block_key]
        other = {k: v for k, v in p.items() if k != block_key}

        Bd, T = local_tokens.shape
        if Bd % M != 0:
            raise ValueError(f"per-shard batch {Bd} not divisible by M={M}")
        mb = Bd // M
        tokens_r = local_tokens.reshape(M, mb, T)

        act = jax.eval_shape(lambda t: embed_fn(other, t), tokens_r[0])
        act_shape, act_dtype = act.shape, act.dtype
        zero_act = jnp.zeros(act_shape, act_dtype)
        loss_sd = jax.eval_shape(
            lambda a, t: loss_fn(head_fn(other, a), t),
            jax.ShapeDtypeStruct(act_shape, act_dtype),
            tokens_r[0],
        )
        zero_loss = jnp.zeros(loss_sd.shape, loss_sd.dtype)
        one_ct = jnp.ones(loss_sd.shape, loss_sd.dtype)

        def mb_fn(blocks_, other_, x_in, tok_mb):
            # One microbatch through the local span, unified across stages:
            # stage 0 embeds (its ring input is garbage and the cond
            # transpose zeros its cotangent), the last stage runs head+loss.
            # Forward ticks and the vjp-recompute backward both trace exactly
            # this function, so the per-microbatch jaxpr is
            # schedule-independent — the bit-identity anchor.
            x0 = lax.cond(
                idx == 0,
                lambda: embed_fn(other_, tok_mb).astype(act_dtype),
                lambda: x_in,
            )
            y = run_stage(blocks_, active_loc, x0)
            loss_m = lax.cond(
                idx == S - 1,
                lambda: loss_fn(head_fn(other_, y), tok_mb).astype(loss_sd.dtype),
                lambda: zero_loss,
            )
            return y, loss_m

        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]

        def phases(t, fwd_in, bwd_ct, stash, g_blocks, g_other, loss_acc):
            """One tick's forward + backward phases (hop-free).  Returns the
            produced activation/cotangent for the schedule wrapper to ship.
            Identical jaxpr per active microbatch for both hop latencies —
            the bit-identity anchor."""
            # -- forward phase: stage idx runs microbatch t - H*idx --
            mf = t - H * idx
            act_f = jnp.logical_and(mf >= 0, mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            tok_f = lax.dynamic_index_in_dim(tokens_r, mf_c, keepdims=False)

            def fwd_run():
                y, loss_m = mb_fn(blocks, other, fwd_in, tok_f)
                # Stash the stage INPUT (not output): the backward phase
                # recomputes this stage's forward from it under vjp.  Slot
                # m % D is free by then — a microbatch is live for
                # C2 + H(S-1) - 2Hs + 1 ticks, and D = min(M, C2 + H(S-1)+1)
                # covers the worst (stage-0) span.
                new_stash = lax.dynamic_update_index_in_dim(
                    stash, fwd_in, jnp.mod(mf_c, D), 0
                )
                return y, loss_m, new_stash

            def fwd_skip():
                return zero_act, zero_loss, stash

            y, loss_m, stash = lax.cond(act_f, fwd_run, fwd_skip)
            loss_acc = loss_acc + loss_m

            # -- backward phase: stage idx pulls mb t - C2 - H*(S-1-idx) --
            mbk = t - C2 - H * (S - 1 - idx)
            act_b = jnp.logical_and(mbk >= 0, mbk < M)
            mb_c = jnp.clip(mbk, 0, M - 1)
            tok_b = lax.dynamic_index_in_dim(tokens_r, mb_c, keepdims=False)
            x_b = lax.dynamic_index_in_dim(
                stash, jnp.mod(mb_c, D), keepdims=False
            )
            # The last stage's y feeds the ring wrap (garbage at stage 0's
            # embed cond) — its activation cotangent is identically zero;
            # the loss drives its backward through ct 1.0 instead.
            ct_y = jnp.where(idx == S - 1, jnp.zeros_like(zero_act), bwd_ct)

            def bwd_run():
                _, pull = jax.vjp(
                    lambda b, o, x: mb_fn(b, o, x, tok_b), blocks, other, x_b
                )
                d_blocks, d_other, dx = pull((ct_y, one_ct))
                return (
                    jax.tree.map(jnp.add, g_blocks, d_blocks),
                    jax.tree.map(jnp.add, g_other, d_other),
                    dx,
                )

            def bwd_skip():
                return g_blocks, g_other, zero_act

            g_blocks, g_other, gx = lax.cond(act_b, bwd_run, bwd_skip)
            return y, gx, stash, g_blocks, g_other, loss_acc

        def tick(carry, t):
            # Serial (H=1): compute, then hop — the produced activation and
            # cotangent land on the neighbor for the NEXT tick.  Collective
            # hops stay OUTSIDE the phase conds — every device executes both
            # ppermutes every tick (cond branches must not diverge on
            # collectives across the gang).
            fwd_in, bwd_ct, stash, g_blocks, g_other, loss_acc = carry
            y, gx, stash, g_blocks, g_other, loss_acc = phases(
                t, fwd_in, bwd_ct, stash, g_blocks, g_other, loss_acc
            )
            fwd_next = lax.ppermute(y, stage_axis, fwd_perm)
            bwd_next = lax.ppermute(gx, stage_axis, bwd_perm)
            return (
                fwd_next, bwd_next, stash, g_blocks, g_other, loss_acc
            ), None

        def tick_overlapped(carry, t):
            # Double-buffered (H=2): the hops shipping the PREVIOUS tick's
            # activation/cotangent are issued at the TOP of the tick, before
            # the phases — their operands predate this tick's compute, so
            # the DMA rides underneath it and the hopped values are consumed
            # on the neighbor NEXT tick (2-tick effective latency, hence the
            # H=2 index maps).
            (y_pend, fwd_in, gx_pend, bwd_ct, stash,
             g_blocks, g_other, loss_acc) = carry
            fwd_next = lax.ppermute(y_pend, stage_axis, fwd_perm)
            bwd_next = lax.ppermute(gx_pend, stage_axis, bwd_perm)
            y, gx, stash, g_blocks, g_other, loss_acc = phases(
                t, fwd_in, bwd_ct, stash, g_blocks, g_other, loss_acc
            )
            return (
                y, fwd_next, gx, bwd_next, stash,
                g_blocks, g_other, loss_acc,
            ), None

        stash0 = jnp.zeros((D,) + act_shape, act_dtype)
        g0 = (
            jax.tree.map(jnp.zeros_like, blocks),
            jax.tree.map(jnp.zeros_like, other),
        )
        if overlap:
            carry0 = (
                zero_act, zero_act, zero_act, zero_act, stash0,
                g0[0], g0[1], zero_loss,
            )
            (_, _, _, _, _, g_blocks, g_other, loss_acc), _ = lax.scan(
                tick_overlapped, carry0, jnp.arange(n_ticks)
            )
        else:
            carry0 = (zero_act, zero_act, stash0, g0[0], g0[1], zero_loss)
            (_, _, _, g_blocks, g_other, loss_acc), _ = lax.scan(
                tick, carry0, jnp.arange(n_ticks)
            )

        # loss_acc is nonzero only on the last stage; each loss_m is a
        # per-microbatch mean, so /M matches the dense/GPipe convention.
        loss = lax.psum(loss_acc, stage_axis) / M
        g_other = jax.tree.map(lambda g: lax.psum(g, stage_axis), g_other)
        grads = dict(g_other)
        grads[block_key] = g_blocks
        grads = jax.tree.map(lambda g: g / M, grads)
        grads = jax.tree.map(lambda g: lax.pmean(g, data_axis), grads)
        loss = lax.pmean(loss, data_axis)
        return loss, grads

    grad_specs = dict(param_specs)
    mapped = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, P(data_axis)),
        out_specs=(P(), grad_specs),
        check_vma=False,
    )
    loss, grads = mapped(params, tokens)
    if spans is not None:
        grads = dict(grads)
        grads[block_key] = _unpad_stack(grads[block_key], spans, n_max)
    return loss, grads


def pipeline_hints(spec: Any) -> Dict[str, Any]:
    """Extract and validate the model's pipeline decomposition hints."""
    h = spec.hints.get("pipeline")
    if h is None:
        raise ValueError(
            "model does not expose pipeline hints "
            "(hints['pipeline'] with embed/block/head fns)"
        )
    return h


def run_passes(
    run_stack: Callable[[jax.Array], jax.Array],
    h: jax.Array,
    passes: int = 1,
    between: Optional[Callable[[jax.Array], jax.Array]] = None,
) -> jax.Array:
    """``h`` through ``run_stack`` (the whole block stack, once) ``passes``
    times, ``between`` applied between one pass and the next: the outer loop
    of a looped model (``hints["pipeline"]``'s ``passes`` / ``between``) for
    a technique that rebuilds the model from the hints. One pass is
    ``run_stack(h)`` and nothing else; more are one ``lax.scan`` over the
    passes, as in the model, so the stack is traced and compiled once."""
    if passes == 1:
        return run_stack(h)

    def one_pass(x, t):
        if between is not None:
            x = lax.cond(t > 0, between, lambda v: v, x)
        return run_stack(x), None

    return lax.scan(one_pass, h, jnp.arange(passes))[0]
