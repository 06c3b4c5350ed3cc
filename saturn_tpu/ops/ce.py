"""Fused linear-cross-entropy for TPU: head matmul + softmax CE, Pallas.

The single largest non-matmul cost in the round-3 profiler trace of the
config-#1 step (GPT-2-small b8x512 on v5e) was the logits pipeline: XLA
materializes f32 logits (B,T,V) = 824 MiB for the loss, a bf16 stash for the
backward, a separately-fused dlogits (softmax gradient) tensor, and three
reduce/broadcast fusions over (B,T,V) — together ~10% of device time at zero
FLOPs utilization, and the allocation that runs a long sequence out of
memory first. The reference hit the same wall differently: its 6B
example shrank batch sizes until torch's unfused CE fit
(``/root/reference/examples/wikitext103/WikiText103.py:62-71``).

This op computes ``mean CE(x @ W^T, labels)`` without ever materializing f32
logits or the softmax gradient:

- **fwd** tiles (token-block x vocab-block), runs the head matmul per tile,
  and carries the online-logsumexp recurrence (flash-attention-style, over
  the vocab axis) plus a masked gather of the label logit in VMEM scratch.
  In stash mode it also writes ONE (N, V) tensor — a bf16 logits stash for
  the backward, the same thing XLA's own CE backward keeps (round-3 trace:
  ``fusion.227``'s bf16 output); in recompute mode it writes no (N, V)
  tensor at all.
- **bwd** forms ``ds = softmax(logits) - onehot(labels)`` in registers and
  feeds it straight to the MXU — dx = ds @ W over vocab blocks, dW =
  ds^T @ x over token blocks. Two source modes (``stash`` arg): read the
  fwd's bf16 logits stash (same three matmul passes as XLA, none of the
  elementwise (N, V) fusions), or — long-context mode — recompute each
  score block from x·W^T in-kernel, which costs one extra matmul pass per
  backward kernel and needs ZERO O(N·V) memory.

Who chooses the mode: a caller that states it (``stash=True`` / ``False``;
a model's ``ce_mode``, :func:`stash_of`), else :func:`ce_plan` by the stash's
size alone. Under ``STASH_BYTES_MAX`` (0.5 GiB) the stash is kept and nobody
is asked. Over it an unasked call recomputes, because a constant cannot know
whether the program has room: the stash lives from the head's forward to its
backward, when no layer's recomputed activations are alive, so where the
program's peak lies elsewhere it costs nothing. Compiled for a v5e, the 0.77
GiB stash of 8192 tokens x d 4096 x 50400 adds 0.39 GiB to a remat program's
11.34, the 0.75 GiB at d 2048 x 49152 nothing to 14.11 of the 14.49 GiB the
memory rule allows, and either sits on top of a peak that is over the rule
already without remat. The compile knows, so the trial runner asks it
(``parallel/spmd_base.py``, the head's rungs: a grid point whose head
:func:`stash_over_the_constant` names is prepared with ``ce_mode="stash"``
first and as the grid has it, recomputing, only where the compiler or the
memory rule refuses that program; PR 51). The stash saves a
matmul pass of two in ``saturn_ce_dx`` and in ``saturn_ce_dw``: 35.2 -> 17.8
and 41.5 -> 23.7 ms a step at the first shape, the forward 19.6 -> 20.3 for
writing the stash (``gptj-6b-1chip.steady``'s traced pair, PR 51; alone:
the readings at :func:`_dx_compute_bound_block`).

Masked tokens use label -1 (the standard ignore index): they never match a
vocab column, and the wrapper zeros their loss and (via the mean's cotangent)
their gradient. The vocab axis is padded to a block multiple inside the op —
padded columns get -1e30 logits, so they vanish from the softmax and the
gradient; the pad is fused into the bf16 weight cast XLA performs anyway.

Like ``ops/flash.py``, real lowering needs the TPU backend; interpret mode
exists for CPU numerics tests (``tests/test_ce.py``). Off-TPU (or for token
counts no block divides) :func:`fused_linear_cross_entropy` itself computes
the identical objective through plain XLA ops
(:func:`dense_linear_cross_entropy`), so callers — ``models/gpt2.py``'s
``fused_loss_fn`` — can use it unconditionally.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from saturn_tpu.ops import plans

NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _col_ids(vb, block_n, block_v):
    """(BN, BV) int32 absolute vocab column ids for vocab block vb."""
    return vb * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1
    )


# --------------------------------------------------------------------- fwd
def _fwd_kernel(x_ref, w_ref, lab_ref, *refs,
                block_n, block_v, n_vocab, masked, stash):
    if stash:
        logits_ref, loss_ref, lse_ref, m_scr, l_scr, lbl_scr = refs
    else:
        loss_ref, lse_ref, m_scr, l_scr, lbl_scr = refs
    vb = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(vb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        lbl_scr[:] = jnp.zeros_like(lbl_scr)

    s = _dot(x_ref[...], w_ref[...], ((1,), (1,)))        # (BN, BV) f32
    col = _col_ids(vb, block_n, block_v)
    if masked:
        # pad columns → -inf logits; the stash (or the bwd recompute, which
        # applies the same mask) carries them into the backward, where
        # exp(-1e30 - lse) = 0 kills their gradient too
        s = jnp.where(col < n_vocab, s, NEG_INF)
    if stash:
        logits_ref[...] = s.astype(logits_ref.dtype)

    lab = lab_ref[...]                                     # (BN, 1) int32
    lbl_scr[:, 0] += jnp.sum(jnp.where(col == lab, s, 0.0), axis=1)

    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    l_scr[:, 0] = (
        jnp.exp(m_prev - m_new) * l_scr[:, 0]
        + jnp.exp(s - m_new[:, None]).sum(axis=-1)
    )
    m_scr[:, 0] = m_new

    @pl.when(vb == n_v - 1)
    def _finalize():
        lse = m_scr[:, 0] + jnp.log(l_scr[:, 0])
        lse_ref[...] = lse[:, None]
        loss_ref[...] = (lse - lbl_scr[:, 0])[:, None]


# ----------------------------------------------------------- bwd: shared ds
def _ds_block(s_f32, vb, lab_ref, lse_ref, g_ref, block_n, block_v):
    """softmax(logits) - onehot(labels), scaled by the upstream cotangent."""
    p = jnp.exp(s_f32 - lse_ref[...])
    col = _col_ids(vb, block_n, block_v)
    onehot = (col == lab_ref[...]).astype(jnp.float32)
    return (p - onehot) * g_ref[...]                       # (BN, BV) f32


def _recomputed_s(x_ref, w_ref, vb, block_n, block_v, n_vocab, masked):
    s = _dot(x_ref[...], w_ref[...], ((1,), (1,)))         # (BN, BV) f32
    if masked:
        s = jnp.where(_col_ids(vb, block_n, block_v) < n_vocab, s, NEG_INF)
    return s


# ---------------------------------------------------------------- bwd: dx
def _dx_kernel(src_ref, w_ref, lab_ref, lse_ref, g_ref, dx_ref, acc_scr,
               *, block_n, block_v, n_vocab, masked, stash):
    vb = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(vb == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if stash:  # src = bf16 logits stash block (BN, BV)
        s = src_ref[...].astype(jnp.float32)
    else:      # src = x block (BN, D): recompute the score block
        s = _recomputed_s(src_ref, w_ref, vb, block_n, block_v, n_vocab,
                          masked)
    ds = _ds_block(s, vb, lab_ref, lse_ref, g_ref, block_n, block_v)
    acc_scr[:] = acc_scr[:] + _dot(
        ds.astype(w_ref.dtype), w_ref[...], ((1,), (0,))
    )

    @pl.when(vb == n_v - 1)
    def _finalize():
        dx_ref[...] = acc_scr[:].astype(dx_ref.dtype)


# ---------------------------------------------------------------- bwd: dW
def _dw_kernel(src_ref, x_ref, lab_ref, lse_ref, g_ref, dw_ref, acc_scr,
               *, block_n, block_v, n_vocab, masked, stash):
    vb, nb = pl.program_id(0), pl.program_id(1)
    n_n = pl.num_programs(1)

    @pl.when(nb == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if stash:  # src = bf16 logits stash block (BN, BV)
        s = src_ref[...].astype(jnp.float32)
    else:      # src = W block (BV, D): recompute from this kernel's x input
        s = _recomputed_s(x_ref, src_ref, vb, block_n, block_v, n_vocab,
                          masked)
    ds = _ds_block(s, vb, lab_ref, lse_ref, g_ref, block_n, block_v)
    acc_scr[:] = acc_scr[:] + _dot(
        ds.astype(x_ref.dtype), x_ref[...], ((0,), (0,))
    )

    @pl.when(nb == n_n - 1)
    def _finalize():
        dw_ref[...] = acc_scr[:]


# ------------------------------------------------------------- vjp plumbing
# ``blocks`` is the static tuple (bn_fwd, bv_fwd, bn_dw, bv_dw, bn_dx): fwd/dx
# tile tokens wide and vocab narrow (the f32 score block is the VMEM hog under
# the compiler's ~16 MiB scoped-vmem limit; W re-streams once per token row),
# while dW tiles vocab wide and tokens narrow (its accumulator spans the
# vocab block; x re-streams once per vocab row). dx has a token block of its
# own because it alone holds an f32 (bn, D) accumulator beside the
# double-buffered (bn, D) output and (bv, D) weight streams. ``dx_vmem_limit``
# is the scoped VMEM dx asks the compiler for where its block needs more than
# the default (:func:`ce_plan`), else None.
def _run_fwd(x, w_p, lab, block_n, block_v, n_vocab, interpret, stash):
    N, D = x.shape
    Vp = w_p.shape[0]
    grid = (N // block_n, Vp // block_v)
    out_specs = [
        pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
        pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((N, 1), jnp.float32),         # per-token loss
        jax.ShapeDtypeStruct((N, 1), jnp.float32),         # lse
    ]
    if stash:
        out_specs.insert(
            0, pl.BlockSpec((block_n, block_v), lambda nb, vb: (nb, vb))
        )
        out_shape.insert(0, jax.ShapeDtypeStruct((N, Vp), jnp.bfloat16))
    outs = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_n=block_n, block_v=block_v, n_vocab=n_vocab,
            masked=Vp != n_vocab, stash=stash,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, D), lambda nb, vb: (nb, 0)),
            pl.BlockSpec((block_v, D), lambda nb, vb: (vb, 0)),
            pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),         # running max
            pltpu.VMEM((block_n, 1), jnp.float32),         # running denom
            pltpu.VMEM((block_n, 1), jnp.float32),         # label logit
        ],
        name="saturn_ce_fwd",
        interpret=interpret,
    )(x, w_p, lab)
    if stash:
        return outs  # (logits, loss, lse)
    loss, lse = outs
    return None, loss, lse


# The compute-dtype cast and the vocab pad happen INSIDE the custom_vjp
# boundary: the primal w is f32 (the wrapper casts; a no-op for the f32
# params of every preset), so the bwd's f32 dW matches its primal exactly —
# no reliance on JAX's temporary cotangent-dtype exception — and the f32
# head gradient reaches the optimizer at full precision, the same contract
# as XLA's unfused path.
def _padded_vocab(n_vocab, blocks):
    # Pad to a common multiple of BOTH vocab block sizes: the fwd/dx grids
    # step by bv and the dW grid by bv_dw, so each must tile Vp exactly —
    # padding to only the larger block truncates the other's grid and drops
    # real vocab columns from the logsumexp (round-3 advisor finding).
    mult = int(np.lcm(blocks[1], blocks[3]))
    return ((n_vocab + mult - 1) // mult) * mult


def _prep_w(w, x_dtype, Vp):
    w_p = w.astype(x_dtype)
    if Vp != w.shape[0]:
        w_p = jnp.pad(w_p, ((0, Vp - w.shape[0]), (0, 0)))
    return w_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce(x, w, lab, blocks, n_vocab, interpret, stash, dx_vmem_limit):
    bn, bv = blocks[:2]
    w_p = _prep_w(w, x.dtype, _padded_vocab(n_vocab, blocks))
    _, loss, _ = _run_fwd(x, w_p, lab, bn, bv, n_vocab, interpret,
                          stash=False)
    return loss


def _fused_ce_fwd(x, w, lab, blocks, n_vocab, interpret, stash, dx_vmem_limit):
    bn, bv = blocks[:2]
    w_p = _prep_w(w, x.dtype, _padded_vocab(n_vocab, blocks))
    logits, loss, lse = _run_fwd(
        x, w_p, lab, bn, bv, n_vocab, interpret, stash=stash
    )
    return loss, (x, w_p, lab, logits, lse)


def _fused_ce_bwd(blocks, n_vocab, interpret, stash, dx_vmem_limit, res, g):
    _, block_v, bn_dw, bv_dw, block_n = blocks
    x, w_p, lab, logits, lse = res
    N, D = x.shape
    Vp = w_p.shape[0]
    masked = Vp != n_vocab
    g = g.astype(jnp.float32)

    # stash mode reads the bf16 logits; recompute mode re-derives the score
    # block from x·W^T inside each kernel (one extra matmul pass per kernel,
    # zero O(N, V) memory — the long-context mode)
    dx_src = logits if stash else x
    dx_src_spec = (
        pl.BlockSpec((block_n, block_v), lambda nb, vb: (nb, vb))
        if stash else pl.BlockSpec((block_n, D), lambda nb, vb: (nb, 0))
    )
    # under the default scoped limit the call is what it always was
    dx_params = {} if dx_vmem_limit is None else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=dx_vmem_limit)
    }
    dx = pl.pallas_call(
        functools.partial(
            _dx_kernel, block_n=block_n, block_v=block_v, n_vocab=n_vocab,
            masked=masked, stash=stash,
        ),
        grid=(N // block_n, Vp // block_v),
        in_specs=[
            dx_src_spec,
            pl.BlockSpec((block_v, D), lambda nb, vb: (vb, 0)),
            pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
            pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
            pl.BlockSpec((block_n, 1), lambda nb, vb: (nb, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, D), lambda nb, vb: (nb, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, D), jnp.float32)],
        name="saturn_ce_dx",
        interpret=interpret,
        **dx_params,
    )(dx_src, w_p, lab, lse, g)

    dw_src = logits if stash else w_p
    dw_src_spec = (
        pl.BlockSpec((bn_dw, bv_dw), lambda vb, nb: (nb, vb))
        if stash else pl.BlockSpec((bv_dw, D), lambda vb, nb: (vb, 0))
    )
    dw = pl.pallas_call(
        functools.partial(
            _dw_kernel, block_n=bn_dw, block_v=bv_dw, n_vocab=n_vocab,
            masked=masked, stash=stash,
        ),
        grid=(Vp // bv_dw, N // bn_dw),
        in_specs=[
            dw_src_spec,
            pl.BlockSpec((bn_dw, D), lambda vb, nb: (nb, 0)),
            pl.BlockSpec((bn_dw, 1), lambda vb, nb: (nb, 0)),
            pl.BlockSpec((bn_dw, 1), lambda vb, nb: (nb, 0)),
            pl.BlockSpec((bn_dw, 1), lambda vb, nb: (nb, 0)),
        ],
        out_specs=pl.BlockSpec((bv_dw, D), lambda vb, nb: (vb, 0)),
        out_shape=jax.ShapeDtypeStruct((Vp, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bv_dw, D), jnp.float32)],
        name="saturn_ce_dw",
        interpret=interpret,
    )(dw_src, x, lab, lse, g)

    dlab = np.zeros(lab.shape, dtype=jax.dtypes.float0)
    return dx, dw[:n_vocab], dlab


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


# ------------------------------------------------------------------ public
# The chip the blocks are sized for: one TPU v5e core. 197 TFLOP/s in bf16
# over 819 GB/s of HBM (Google Cloud documentation, "TPU v5e") is a ridge of
# 240 operations per HBM byte; a core has 128 MiB of VMEM, of which Mosaic
# gives one kernel 16 MiB unless the ``pallas_call`` asks for more
# (``pltpu.CompilerParams(vmem_limit_bytes=...)``). dx asks (below); every
# other kernel here is sized to the default.
_VMEM_LIMIT = 16 << 20
_VMEM_PHYSICAL = 128 << 20
_VMEM_REQUEST_MAX = _VMEM_PHYSICAL // 2   # the most one kernel asks for
_RIDGE_FLOP_PER_BYTE = 240

# What the v5e compiler puts in VMEM for one grid step of a backward kernel,
# as far as shapes say it: every streamed block twice (double buffering), the
# f32 accumulator once and, for dx, the temporaries of one step. Readings,
# each compiled for a described v5e at bv = 512 ("from" is the smallest
# ``vmem_limit_bytes`` the compiler admits the kernel at, bisected to 64 KiB;
# PR 31. PR 28's "wants" figures were the allocation the compiler stopped at,
# 2-4 MiB under these):
#   dx  sum 15.69 MiB  D 4096 recompute bn 64     from 15.17
#       sum 19.38      D 4096 recompute bn 128    from 18.34
#       sum 26.75      D 4096 recompute bn 256    from 25.64
#       sum 41.50      D 4096 recompute bn 512    from 40.28
#       sum 13.75      D 2048 recompute bn 256    from 13.15
#       sum 21.50      D 2048 recompute bn 512    from 20.82
#       sum 11.50      D 1024 recompute bn 512    from 10.95
#       sum 20.00      D 1024 recompute bn 1024   from 19.21
#       sum 17.75      D 1664 recompute bn 512    from 17.10
#       sum 14.00      D 1600 recompute bn 512    from 10.58
#       sum 12.38      D 4096 stash bn 128        from 12.28
#       sum 16.75      D 4096 stash bn 256        from 16.69
#       sum 25.50      D 4096 stash bn 512        from 25.50
#       sum 13.00      D 1024 stash bn 1024       from 13.02
#       sum 13.50      D 2048 stash bn 512        from 13.47
#   dW  sum 16.0       D 1024 stash     (bn 512, bv 1024)  admitted (PR 28)
#       sum 15.6       D 1600 recompute (512, 512)         admitted
#       sum 16.5       D 1024 recompute (128, 1024)        refused
#       sum 17.0       D 2048 recompute (128, 512)         refused
# dx's sum is the compiler's figure to within 6 %, from above, at every width
# that is a multiple of the 128-lane tile; at D 1600 the compiler lays the
# (., D) blocks out another way and stays under even the streams' sum, so the
# sum errs on the safe side there. dW's sum is within a few percent too (its
# temporaries hide under its larger streams) and is held to the default limit.
_DX_REQUEST_MARGIN = 1.125   # asked for over the sum, then rounded up to a MiB


def _dx_vmem(bn: int, bv: int, d: int, stash: bool) -> int:
    """dx: the score source (bf16 logits block, or the x block it recomputes
    from), the weight block and the output block streamed, (bn, D) f32
    accumulated; then one step's temporaries: the f32 score block with its
    exp / ds copies (half a block in stash mode, where the scores arrive in
    bf16; a block and a half in recompute mode) and, in recompute mode at a
    lane-aligned D, one more copy of each matmul operand block (the weight
    block laid out for x.W^T, the token block)."""
    src = bn * bv * 2 if stash else bn * d * 2
    streams = 2 * (src + bv * d * 2 + bn * d * 2) + bn * d * 4
    if stash:
        return streams + bn * bv * 2
    operands = bv * d * 2 + bn * d * 2 if d % 128 == 0 else 0
    return streams + operands + bn * bv * 6


def _dx_compute_bound_block(stash: bool) -> int:
    """The token block from which dx's own weight stream hides under its
    matmuls: per (bv, D) weight block it streams 2 bv D bytes once and does
    2 bn bv D operations a matmul pass (two passes in recompute mode), and
    the block is the smallest power of two at which that is twice the chip's
    ridge: 256 tokens in recompute mode, 512 in stash mode; 512 is the upper
    end. Read on the chip, dx alone (``tools/ce_dx_blocks.py``; PR 31), ms at
    bn 64 / 128 / 256 / 512 / 1024:
      8192 x 4096 x 50400 recompute  70.50  37.41  35.35  35.06
      8192 x 2048 x 49152 recompute                17.48  17.20
      8192 x 1024 x 50257 recompute                 9.36   9.06   8.91
      2048 x 4096 x 50400 stash              9.18   4.91   4.56
    The smallest block within 2 % of the best of its row is the rule's in
    every row (256, 256, 512 with what fits the default limit, 512).
    The shape the trial runner asks the compile about (PR 51), same tool:
      8192 x 4096 x 50400 stash             36.41  19.32  17.92
    (512 again). Beside it, ms: the forward 23.62 (recompute 22.98: the
    stash's 0.77 GiB written), the whole call forward + dx + dW in one
    program 67.60 (recompute 99.35), dW at its own blocks (bn 256, bv 128)
    44.18 recompute and 40.22 stash *alone*, its residuals handed over as
    arguments, but 67.60 - 23.62 - 17.92 = 26.1 inside the whole call and
    23.7 a step in the traced cell (41.5 recompute): alone, the stash-mode
    dW reads 14 ms long for a reason nobody has found (PERF.md section 7);
    the whole call and the cell are the readings to go by."""
    passes = 1 if stash else 2
    bn = 128
    while passes * bn < 2 * _RIDGE_FLOP_PER_BYTE and bn < 512:
        bn *= 2
    return bn


def _dx_vmem_limit(bn: int, bv: int, d: int, stash: bool) -> Optional[int]:
    """What dx asks the compiler for: nothing while its sum is under the
    default scoped limit, else the sum with a margin, in whole MiB."""
    need = _dx_vmem(bn, bv, d, stash)
    if need <= _VMEM_LIMIT:
        return None
    return -(-int(need * _DX_REQUEST_MARGIN) // (1 << 20)) << 20


def _dx_block_under(bn: int, bv: int, d: int, stash: bool, limit: float) -> int:
    """The largest halving of ``bn`` (16 at the least) whose sum is under
    ``limit``."""
    while bn % 32 == 0 and _dx_vmem(bn, bv, d, stash) > limit:
        bn //= 2
    return bn


def _dw_vmem(bn: int, bv: int, d: int, stash: bool) -> int:
    """dW: the score source (bf16 logits block, or the weight block it
    recomputes from), the x block and the f32 output block streamed, (bv, D)
    f32 accumulated."""
    src = bn * bv * 2 if stash else bv * d * 2
    return 2 * (src + bn * d * 2 + bv * d * 4) + bv * d * 4


def _auto_bv_dw(d_model: int, bn_dw: int = 512, stash: bool = True) -> int:
    """dW vocab block: the largest power of two in 128..1024 whose kernel
    fits (:func:`_dw_vmem`). A power of two >= the 128-lane tile because a
    non-128-multiple (819 @ D=1280) breaks Mosaic tiling and a
    non-power-of-two 128-multiple (e.g. 640) makes lcm(bv, bv_dw) inflate
    the vocab pad by up to ~4% dead columns in every kernel. Recompute mode
    streams the (bv_dw, D) weight block as well, so it gets half the block
    stash mode gets from D = 1024 up."""
    for bv in (1024, 512, 256):
        if _dw_vmem(bn_dw, bv, d_model, stash) <= _VMEM_LIMIT:
            return bv
    return 128


def _auto_blocks(n_tokens: int, d_model: int, n_vocab: int, bn: int,
                 stash: bool, block_n: Optional[int] = None,
                 block_v: Optional[int] = None):
    """(bn, bv, bn_dw, bv_dw, bn_dx) for one backward strategy. fwd and dx
    tile tokens wide (``bn``) and vocab narrow; dW the transpose. dx has a
    token block of its own: the largest halving of ``bn`` that fits the
    default scoped VMEM, raised to the block that keeps the kernel
    compute-bound (:func:`_dx_compute_bound_block`) where ``n_tokens`` allows,
    and halved again only where the request would pass the most a kernel
    asks for (an explicit ``block_n`` / ``block_v`` is the caller's word and
    is kept)."""
    if block_v is not None:
        bv = bv_dw = block_v
        bn_dw = block_n or bn
    else:
        bn_dw = min(512, bn)
        if n_vocab >= 2048:
            bv, bv_dw = 512, _auto_bv_dw(d_model, bn_dw, stash)
        else:
            bv = bv_dw = ((n_vocab + 127) // 128) * 128
    if n_tokens % bn_dw != 0:  # possible only with an explicit non-power-of-2 bn
        bn_dw = bn
    bn_dx = bn
    if block_n is None:
        bn_dx = _dx_block_under(bn_dx, bv, d_model, stash, _VMEM_LIMIT)
        target = _dx_compute_bound_block(stash)
        while bn_dx < target and n_tokens % (2 * bn_dx) == 0:
            bn_dx *= 2
        bn_dx = _dx_block_under(bn_dx, bv, d_model, stash,
                                _VMEM_REQUEST_MAX / _DX_REQUEST_MARGIN)
    return (bn, bv, bn_dw, bv_dw, bn_dx)


def _pick_block(n: int, candidates) -> Optional[int]:
    for b in candidates:
        if n % b == 0:
            return b
    return None


def _dense_per_token(x2, w, labels1):
    """Per-token CE through plain XLA ops — the one implementation behind
    both the test oracle and the production odd-shape/CPU fallback."""
    logits = _dot(x2, w.astype(x2.dtype), ((1,), (1,)))
    lse = jax.nn.logsumexp(logits, axis=-1)
    lbl = jnp.take_along_axis(
        logits, jnp.maximum(labels1, 0)[:, None], axis=-1
    )[:, 0]
    return lse - lbl


def dense_linear_cross_entropy(x, w, labels, *, ignore_index=-1):
    """Unfused reference: same math through plain XLA ops. Used as the
    CPU/odd-shape fallback and as the numerics oracle in tests."""
    *lead, D = x.shape
    N = int(np.prod(lead)) if lead else 1
    per_tok = _dense_per_token(x.reshape(N, D), w, labels.reshape(N))
    valid = labels.reshape(N) != ignore_index
    count = jnp.maximum(valid.sum(), 1)
    return jnp.where(valid, per_tok, 0.0).sum() / count


# The size under which nobody is asked: a bf16 logits stash this small (it
# saves one recompute matmul pass in each backward kernel) is kept by every
# call that states no mode. Over it such a call recomputes and holds no
# O(N·V) memory at all — the difference between b8x2048 GPT-2 fitting on a
# v5e chip or not — and the mode is the caller's to state where it knows
# better: the trial runner states ``stash`` where the compiled program has
# room for it (``parallel/spmd_base.py``, the head's rungs). The constant is
# no verdict on what fits; it only says from where on the question is worth a
# compile.
STASH_BYTES_MAX = 512 * 1024 * 1024

#: a model's ``ce_mode`` -> the ``stash`` argument of the fused call
_STASH_OF = {None: None, "stash": True, "recompute": False}


def stash_of(mode: Optional[str]) -> Optional[bool]:
    """``stash`` of :func:`fused_linear_cross_entropy` for a mode by name:
    ``"stash"``, ``"recompute"``, or None for the automatic choice."""
    try:
        return _STASH_OF[mode]
    except KeyError:
        raise ValueError(f"unknown fused-head mode (ce_mode) {mode!r}; "
                         f"options: 'stash', 'recompute' or None") from None


def _stash_bytes(n_tokens: int, n_vocab: int, blocks) -> int:
    """The bf16 logits stash of a stash-mode call at these blocks."""
    return n_tokens * _padded_vocab(n_vocab, blocks) * 2


class CEPlan(NamedTuple):
    """How one fused call runs: the five blocks, the backward's mode, each
    backward kernel's VMEM sum and what dx asks the compiler for (None: the
    default scoped limit). ``_asdict()`` is the ``ce_plan`` a ``trial_config``
    event carries."""

    bn: int
    bv: int
    bn_dw: int
    bv_dw: int
    bn_dx: int
    mode: str                      # "stash" | "recompute"
    dx_vmem: int
    dw_vmem: int
    dx_vmem_limit: Optional[int]

    @property
    def blocks(self):
        return tuple(self[:5])


def ce_plan(n_tokens: int, d_model: int, n_vocab: int, *,
            stash: Optional[bool] = None, block_n: Optional[int] = None,
            block_v: Optional[int] = None) -> Optional[CEPlan]:
    """The plan :func:`fused_linear_cross_entropy` follows for these shapes
    (pure: shapes in, plan out), or None where no token block tiles
    ``n_tokens`` and the call computes through plain XLA ops.

    fwd/dx tile tokens wide and vocab narrow, dW the transpose. One bf16
    byte-pair of token block per D column (bn*D*2B <= 2 MiB) fits the fwd and
    dW kernels under the default scoped VMEM up to d_model 4096 (gptj-6b; the
    round-5 chip run measured the stash-mode fwd at bn=2048/bv=512/D=768 at
    17.18 MiB). The backward kernels hold more and size one block each from
    their own sums (``_dx_vmem`` / ``_dw_vmem`` above, with the readings they
    were set from): dW's vocab block to the default limit, dx's token block
    to what keeps it compute-bound, with the VMEM that takes asked for
    (tests/test_tpu_compile.py compiles both modes at every width the
    presets have)."""
    bn_cap = max((1 << 20) // max(d_model, 1), 128)  # 1024 @ D<=1024, 256 @ 4096
    bn = block_n or _pick_block(
        n_tokens, tuple(b for b in (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
                        if b <= bn_cap)
    )
    if bn is None or n_tokens % bn != 0:  # an explicit block_n must tile N
        return None
    def blocks_at(stash_):
        return _auto_blocks(n_tokens, d_model, n_vocab, bn, stash_,
                            block_n, block_v)

    if stash is None:
        stash = (_stash_bytes(n_tokens, n_vocab, blocks_at(True))
                 <= STASH_BYTES_MAX)
    stash = bool(stash)
    blocks = blocks_at(stash)
    _, bv, bn_dw, bv_dw, bn_dx = blocks
    return CEPlan(
        *blocks, "stash" if stash else "recompute",
        _dx_vmem(bn_dx, bv, d_model, stash),
        _dw_vmem(bn_dw, bv_dw, d_model, stash),
        _dx_vmem_limit(bn_dx, bv, d_model, stash),
    )


def call_plan(n_tokens: int, d_model: int, n_vocab: int, *,
              stash: Optional[bool] = None, block_n: Optional[int] = None,
              block_v: Optional[int] = None,
              interpret: Optional[bool] = None) -> Optional[CEPlan]:
    """What a :func:`fused_linear_cross_entropy` call with these shapes and
    arguments runs as on this backend: :func:`ce_plan`, or None where the
    call computes through plain XLA ops (no block tiles the tokens, no TPU
    and no interpret mode, a vocab block the chip cannot tile)."""
    plan = ce_plan(n_tokens, d_model, n_vocab, stash=stash, block_n=block_n,
                   block_v=block_v)
    # Real TPU lowering needs lane-aligned vocab blocks (Mosaic tiles the
    # last dim in 128-lane units); _padded_vocab's LCM padding already makes
    # every grid tile Vp exactly, so misalignment — possible only with an
    # explicit non-128-multiple block_v — is the one way left to reach the
    # kernel with a shape the chip can't lower. Route it to dense. Interpret
    # mode (CPU numerics tests) has no such constraint.
    if plan is not None and not interpret and (
        _use_interpret() or plan.bv % 128 != 0 or plan.bv_dw % 128 != 0
    ):
        return None
    return plan


def stash_over_the_constant(n_tokens: int, d_model: int,
                            n_vocab: int) -> Optional[int]:
    """The bytes of the bf16 logits stash a call with these shapes would
    keep in stash mode, where a call that states no mode recomputes *only*
    because that is more than ``STASH_BYTES_MAX``: the head whose mode is
    worth asking the compile about. None where the automatic choice keeps
    the stash already, or where the call runs no kernel."""
    plan = call_plan(n_tokens, d_model, n_vocab)
    if plan is None or plan.mode == "stash":
        return None
    kept = ce_plan(n_tokens, d_model, n_vocab, stash=True)
    return _stash_bytes(n_tokens, n_vocab, kept.blocks)


def fused_linear_cross_entropy(
    x: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    *,
    ignore_index: int = -1,
    block_n: Optional[int] = None,
    block_v: Optional[int] = None,
    interpret: Optional[bool] = None,
    reduction: str = "mean",
    stash: Optional[bool] = None,
) -> Any:
    """Cross-entropy of ``x @ w.T`` against ``labels``, fused.

    ``x``: (..., N, D) hidden states (any leading dims are flattened with N);
    ``w``: (V, D) head weights — the tied embedding table for the LM zoo;
    ``labels``: int32 matching x's leading dims, ``ignore_index`` masks.
    Differentiable in x and w.

    ``reduction="mean"`` (default) returns the mean over unmasked tokens;
    ``"sum_count"`` returns ``(loss_sum, valid_count)`` so a sharded caller
    (the data-parallel shard_map wrapper, ``parallel/spmd_base.py``) can
    psum both parts and divide globally — per-shard means would weight
    shards with different mask counts incorrectly.

    ``stash`` picks the backward strategy: True keeps the fwd's bf16 logits
    for the backward (fastest — XLA's own choice for the unfused path);
    False recomputes score blocks from x·W^T in each backward kernel (one
    extra matmul pass per kernel, ZERO O(N·V) memory — long-context mode).
    None (default) stashes only while the stash stays under
    ``STASH_BYTES_MAX``, the size under which nobody is asked; over it the
    mode is for a caller to state who knows what the program has room for
    (the trial runner, through a model's ``ce_mode``).

    Falls back to :func:`dense_linear_cross_entropy` math when the kernel
    cannot lower for these shapes on this backend.
    """
    if ignore_index >= 0:
        raise ValueError("ignore_index must be negative (labels are matched "
                         "against vocab columns inside the kernel)")
    if reduction not in ("mean", "sum_count"):
        raise ValueError(f"unknown reduction {reduction!r}")
    *lead, D = x.shape
    N = int(np.prod(lead)) if lead else 1
    V = w.shape[0]
    # interpret=None means production: real lowering on TPU, dense fallback
    # elsewhere. Tests pass interpret=True to exercise kernel numerics on CPU.
    interp = False if interpret is None else interpret

    def reduce(per_tok, valid):
        count = valid.sum()
        total = jnp.where(valid, per_tok, 0.0).sum()
        if reduction == "sum_count":
            return total, count
        return total / jnp.maximum(count, 1)

    def dense_fallback():
        lab1 = labels.reshape(N)
        per_tok = _dense_per_token(x.reshape(N, D), w, lab1)
        return reduce(per_tok, lab1 != ignore_index)

    plan = call_plan(N, D, V, stash=stash, block_n=block_n, block_v=block_v,
                     interpret=interp)
    plans.record("ce", plan)   # None: the call falls back to plain XLA ops
    if plan is None:
        return dense_fallback()
    x2 = x.reshape(N, D)
    lab = labels.reshape(N, 1).astype(jnp.int32)

    # f32 primal: a no-op for the zoo's f32 params; the compute-dtype cast
    # and vocab pad live inside _fused_ce so dW's dtype matches its primal
    per_tok = _fused_ce(
        x2, w.astype(jnp.float32), lab, plan.blocks, V, interp,
        plan.mode == "stash", plan.dx_vmem_limit,
    )[:, 0]
    return reduce(per_tok, lab[:, 0] != ignore_index)
