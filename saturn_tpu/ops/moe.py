"""Switch-style mixture-of-experts layer, built for the MXU.

A capability extension beyond the reference (no MoE/expert parallelism exists
anywhere in its tree — SURVEY.md §2.3 "EP ... absent"), delivered through the
same plugin interface as every other technique (``parallel/ep.py``).

TPU-first formulation (GShard/Switch): routing is expressed as dense one-hot
dispatch/combine einsums with a *static* per-expert capacity, so the whole
layer is three large batched matmuls plus elementwise — no dynamic shapes, no
scatter/gather, everything tiles onto the systolic array. Under expert
parallelism the (experts, capacity, d_model) intermediate is sharded over the
``expert`` mesh axis and XLA lowers the dispatch/combine einsums to
all-to-alls over ICI.
"""

from __future__ import annotations

import functools
import math
from dataclasses import asdict, dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from saturn_tpu.ops import plans


def expert_capacity(n_tokens: int, n_experts: int, capacity_factor: float) -> int:
    """Static per-expert token budget (Switch Transformer's capacity)."""
    return max(1, int(math.ceil(n_tokens / n_experts * capacity_factor)))


def switch_moe(
    x: jax.Array,
    router_w: jax.Array,
    we_in: jax.Array,
    be_in: jax.Array,
    we_out: jax.Array,
    be_out: jax.Array,
    *,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """Top-1 routed expert MLP.

    Shapes: ``x`` (B, T, D); ``router_w`` (D, E); ``we_in`` (E, D, F);
    ``be_in`` (E, F); ``we_out`` (E, F, D); ``be_out`` (E, D).
    Returns (output (B, T, D), load-balance aux loss scalar fp32).

    Tokens beyond an expert's capacity are dropped (contribute zero and pass
    through the residual) — the standard Switch behavior that keeps shapes
    static. Router math runs in fp32; expert matmuls in the input dtype.
    """
    B, T, D = x.shape
    E = router_w.shape[-1]
    S = B * T
    xf = x.reshape(S, D)

    logits = jnp.einsum(
        "sd,de->se", xf, router_w, preferred_element_type=jnp.float32
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (S, E) fp32
    gate = probs.max(axis=-1)
    expert = probs.argmax(axis=-1)

    C = expert_capacity(S, E, capacity_factor)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)          # (S, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot                    # 1-based slot
    keep = (pos > 0) & (pos <= C)
    slot = jnp.clip(pos - 1, 0, C - 1)
    dispatch = (
        jax.nn.one_hot(slot, C, dtype=x.dtype)
        * keep.astype(x.dtype)[..., None]
    )                                                            # (S, E, C)
    combine = dispatch * gate.astype(x.dtype)[:, None, None]

    xe = jnp.einsum("sec,sd->ecd", dispatch, xf)                 # (E, C, D)
    h = jnp.einsum("ecd,edf->ecf", xe, we_in) + be_in[:, None, :]
    h = jax.nn.gelu(h, approximate=True)
    ye = jnp.einsum("ecf,efd->ecd", h, we_out) + be_out[:, None, :]
    y = jnp.einsum("sec,ecd->sd", combine, ye)

    # Switch load-balance loss: E * Σ_e (token fraction) * (mean router prob).
    frac = onehot.astype(jnp.float32).mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return y.reshape(B, T, D), aux


# ===================================================================== routed
# A routed layer that drops nothing: top-k over all the experts, the (token,
# expert) pairs sorted by expert, a grouped matrix product over the experts
# *held* here, the combine back by the router's weights.
#
# Static shapes under run-time group sizes: the held pairs go to a row buffer
# in which every held expert's rows start at a multiple of the row tile (each
# group padded to whole tiles, an empty one to one tile of zeros), so a tile
# of rows belongs to one expert and the grouped product is a tiled product
# whose right-hand block is chosen per tile (``tile_expert``, a prefetched
# scalar table). Both directions between token order and row order are
# gathers: a row names its token (``tok``), a (token, slot) pair names its row
# (``pos``), and the two maps are each other's transposes, so neither the
# forward nor the backward scatters. A buffer smaller than the worst case
# needs an exact second path for the steps that overflow it
# (``_masked_experts``: every held expert over every token, under a mask).
@dataclass(frozen=True)
class RoutedPlan:
    """How one routed layer runs (``moe_plan`` on the ``trial_config``
    event)."""

    impl: str        # "kernel" (saturn_gmm_*) | "xla" (jax.lax.ragged_dot)
    tokens: int
    experts: int     # the router's outputs
    held: int        # experts whose tables are here
    top_k: int
    row_tile: int
    rows: int        # the row buffer
    worst_rows: int  # the buffer no step can overflow
    act: str = "swiglu"   # an expert's kind: "swiglu" | "reglu" (relu for the
    #                       gate's silu) | "relu2" (no gate)
    latent: int = 0       # the width the experts read and write, where it
    #                       is not the stream's (0: the stream's)
    bias: bool = False    # a selection bias is added to the scores, for the choice
    groups: int = 0       # the experts lie in this many groups of consecutive
    #                       ones (0: no group limit) and a token chooses among
    groups_kept: int = 0  # the experts of its ``groups_kept`` best groups
    score: str = "sigmoid"        # "sigmoid": w_e = scale s_e / sum of the chosen
    #                               s; "softmax": a softmax over the chosen logits
    route_from: str = "ff_input"  # the rows the router reads: the experts' own
    #                               ("ff_input") or the block's input, ahead of
    #                               its mixer and un-normed ("block_input")
    eps: float = 0.0      # added to the sum the chosen sigmoid scores are
    #                       normalised by (0: the bare sum)

    @property
    def second_path(self) -> bool:
        return self.rows < self.worst_rows

    def as_event(self) -> Dict[str, Any]:
        return dict(asdict(self), second_path=self.second_path)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


#: the row buffer as a multiple of the mean held pairs: past it a step takes
#: the exact second path. Twice the mean is the one value a chip has run
#: (PERF.md, Findings PR 36).
BUFFER = 2.0
#: the name ``routed_layout``'s tables carry for a checkpoint policy
LAYOUT_NAME = "saturn_moe_layout"
#: an expert's kinds: a gated pair under silu or relu, or relu(x W_up)^2 alone
ACTS = ("swiglu", "reglu", "relu2")
#: rows of one tile of the grouped product (every expert's rows are padded to
#: whole tiles): the MXU's 128, or 8 where an expert's mean rows are fewer
ROW_TILE = 128


def routed_plan(tokens: int, experts: int, held: int, top_k: int, *,
                buffer: Optional[float] = None, row_tile: Optional[int] = None,
                impl: str = "xla", act: str = "swiglu", latent: int = 0,
                bias: bool = False, groups: int = 0,
                groups_kept: int = 0, score: str = "sigmoid",
                route_from: str = "ff_input", eps: float = 0.0) -> RoutedPlan:
    """``buffer`` (``BUFFER``) x the mean held pairs (tokens x top_k x held /
    experts), plus a tile an expert for the padding, capped at the worst case
    (every token's every choice held). ``groups`` / ``groups_kept``: the
    group limit of the choice (``limited_choice``)."""
    if impl not in ("kernel", "xla"):
        raise ValueError(f"impl must be 'kernel' or 'xla', got {impl!r}")
    if act not in ACTS:
        raise ValueError(f"act must be one of {ACTS}, got {act!r}")
    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"score must be 'sigmoid' or 'softmax', got {score!r}")
    if route_from not in ("ff_input", "block_input"):
        raise ValueError(
            f"route_from must be 'ff_input' or 'block_input', got {route_from!r}")
    if eps and (eps < 0 or score != "sigmoid"):
        raise ValueError(f"eps ({eps}) is a positive term of the sigmoid "
                         "scores' normalising sum")
    if groups and (experts % groups or not 1 <= groups_kept <= groups
                   or top_k > groups_kept * (experts // groups)
                   or experts // groups < 2):
        raise ValueError(
            f"a group limit needs experts ({experts}) in whole groups ({groups}) "
            f"of two or more, 1 <= groups_kept ({groups_kept}) <= groups, and "
            f"top_k ({top_k}) experts among the kept groups'")
    buffer = BUFFER if buffer is None else buffer
    if row_tile is None:
        row_tile = ROW_TILE if tokens * top_k >= experts * ROW_TILE else 8
    pad = held * row_tile
    worst = _round_up(tokens * min(top_k, held), row_tile) + pad
    mean = tokens * top_k * held / experts
    rows = min(worst, _round_up(int(math.ceil(buffer * mean)), row_tile) + pad)
    return RoutedPlan(impl, tokens, experts, held, top_k, row_tile, rows, worst,
                      act, latent, bias, groups, groups_kept if groups else 0,
                      score, route_from, eps)


def limited_choice(choice, groups: int, groups_kept: int):
    """``choice`` (T, experts), the scores a token chooses by, with the
    experts outside the token's ``groups_kept`` best groups at -inf: the
    experts are ``groups`` groups of consecutive ones, a group's score is the
    sum of its two largest entries (group-limited routing with a selection
    bias, DeepSeek-V3's)."""
    t, e = choice.shape
    grouped = choice.reshape(t, groups, e // groups)
    best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)               # (T, groups)
    kept = jax.lax.top_k(best, groups_kept)[1]                          # (T, kept)
    keep = jnp.any(kept[:, :, None] == jnp.arange(groups)[None, None, :], axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, e)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------------ kernels
def _gmm_kernel(te_ref, na_ref, x_ref, w_ref, o_ref, *, transpose_rhs):
    del te_ref
    i = pl.program_id(0)

    @pl.when(i < na_ref[0])
    def _product():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims, preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)

    @pl.when(i >= na_ref[0])
    def _past_the_rows():
        o_ref[...] = jnp.zeros_like(o_ref)


class GmmPlan(NamedTuple):
    """How ``saturn_gmm_fwd`` / ``_dx`` hold an expert's matrix (``gmm_plan``
    on the ``trial_config`` event)."""

    table_bytes: int            # one expert's matrix, the kernel's one block of it
    vmem: int                   # what a grid step holds, by the shapes
    vmem_limit: Optional[int]   # what the call asks the compiler for (None:
    #                             the default scoped limit)


#: the largest matrix a call holds double-buffered inside the compiler's
#: default scoped VMEM (16 MiB a kernel on a v5e, ``ops/ce.py``): 3/8 of it,
#: which leaves a quarter for the row tiles and the float32 product. The
#: tables the cells ran before it are under it (2048 x 512: 2 MiB; 2560 x
#: 768: 3.75; 1024 x 2688: 5.25) and keep the call they had; 2048 x 1792 is
#: 7 MiB, 16.75 by the sum below, and asks: unasked the compiler refuses
#: ``saturn_gmm_fwd`` inside the step program, on the chip and for a described
#: v5e alike ("Scoped allocation with size 16.32M and limit 16.00M exceeded
#: scoped vmem limit by 332.0K"; PERF.md, PR 52, call F), though the routed
#: layer alone compiles unasked. A request is the sum and a quarter, in whole
#: MiB (21-22).
_GMM_TABLE_MAX = 6 << 20
_GMM_REQUEST_MARGIN = 1.25


def gmm_plan(row_tile: int, lanes_in: int, lanes_out: int, table: int,
             itemsize: int) -> GmmPlan:
    """``table`` entries of an expert's matrix, a row tile of ``lanes_in``
    lanes in and ``lanes_out`` out: every streamed block twice (double
    buffering) and the float32 product once. Over ``_GMM_TABLE_MAX`` the call
    asks for that sum with a margin, in whole MiB; the matrix stays one block
    (fetched once an expert: consecutive row tiles of an expert name the same
    block), so the least HBM traffic is kept."""
    table_bytes = table * itemsize
    vmem = (2 * (table_bytes + row_tile * (lanes_in + lanes_out) * itemsize)
            + row_tile * lanes_out * 4)
    limit = None
    if table_bytes > _GMM_TABLE_MAX:
        limit = -(-int(vmem * _GMM_REQUEST_MARGIN) // (1 << 20)) << 20
    return GmmPlan(table_bytes, vmem, limit)


def _gmm_call(x, w, tile_expert, n_active, *, row_tile, transpose_rhs, name):
    """(R, A) x (held, P, Q) -> (R, Q), or (R, P) against the transposes: a
    row tile times its expert's whole matrix (2 MiB at 2048 x 512 in bf16),
    under the scoped VMEM ``gmm_plan`` says it needs."""
    R, A = x.shape
    _, Pw, Qw = w.shape
    out = Pw if transpose_rhs else Qw
    last = lambda na: jnp.maximum(na[0] - 1, 0)   # noqa: E731
    plan = gmm_plan(row_tile, A, out, Pw * Qw, x.dtype.itemsize)
    plans.record("gmm", plan)
    asked = {} if plan.vmem_limit is None else {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=plan.vmem_limit)}
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // row_tile,),
            in_specs=[
                # past the active tiles the blocks stay where they were: no
                # fetch for rows no pair fills
                pl.BlockSpec((row_tile, A),
                             lambda i, te, na: (jnp.minimum(i, last(na)), 0)),
                pl.BlockSpec((1, Pw, Qw),
                             lambda i, te, na: (te[jnp.minimum(i, last(na))], 0, 0)),
            ],
            out_specs=pl.BlockSpec((row_tile, out), lambda i, te, na: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((R, out), x.dtype),
        name=name,
        interpret=_interpret(),
        **asked,
    )(tile_expert, n_active, x, w)


def _gmm_dw_kernel(te_ref, na_ref, x_ref, dy_ref, dw_ref):
    i = pl.program_id(1)
    first = jnp.logical_or(i == 0, te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(i < na_ref[0])
    def _accumulate():
        dw_ref[0] = dw_ref[0] + jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _gmm_dw_call(x, dy, tile_expert, n_active, held, *, row_tile):
    """(R, P)^T x (R, Q) by expert -> (held, P, Q) float32: an expert's block
    stays in VMEM across its consecutive row tiles (every expert has at least
    one, so every block is written)."""
    R, Pw = x.shape
    Qw = dy.shape[1]
    if Pw % 128 == 0:
        # the largest whole part of Pw, in lanes of 128, whose float32 block
        # keeps to 2 MiB (2688 = 21 x 128 against 1024 columns: 384)
        lanes = Pw // 128
        tp = 128 * max(m for m in range(1, lanes + 1)
                       if lanes % m == 0 and (m == 1 or 128 * m * Qw * 4 <= (2 << 20)))
    else:   # widths under a lane tile (the CPU tests'): halves
        tp = Pw
        while tp * Qw * 4 > (2 << 20) and tp % 2 == 0 and tp > 128:
            tp //= 2
    last = lambda na: jnp.maximum(na[0] - 1, 0)   # noqa: E731
    return pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Pw // tp, R // row_tile),
            in_specs=[
                pl.BlockSpec((row_tile, tp),
                             lambda p, i, te, na: (jnp.minimum(i, last(na)), p)),
                pl.BlockSpec((row_tile, Qw),
                             lambda p, i, te, na: (jnp.minimum(i, last(na)), 0)),
            ],
            out_specs=pl.BlockSpec((1, tp, Qw), lambda p, i, te, na: (te[i], p, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((held, Pw, Qw), jnp.float32),
        name="saturn_gmm_dw",
        interpret=_interpret(),
    )(tile_expert, n_active, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm_kernels(x, w, tile_expert, n_active, row_tile):
    return _gmm_call(x, w.astype(x.dtype), tile_expert, n_active,
                     row_tile=row_tile, transpose_rhs=False, name="saturn_gmm_fwd")


def _gmm_kernels_fwd(x, w, tile_expert, n_active, row_tile):
    return (_gmm_kernels(x, w, tile_expert, n_active, row_tile),
            (x, w, tile_expert, n_active))


def _gmm_kernels_bwd(row_tile, kept, dy):
    x, w, tile_expert, n_active = kept
    dx = _gmm_call(dy, w.astype(x.dtype), tile_expert, n_active,
                   row_tile=row_tile, transpose_rhs=True, name="saturn_gmm_dx")
    dw = _gmm_dw_call(x, dy, tile_expert, n_active, w.shape[0], row_tile=row_tile)
    return dx, dw.astype(w.dtype), None, None


_gmm_kernels.defvjp(_gmm_kernels_fwd, _gmm_kernels_bwd)


def grouped_matmul(x, w, layout: Dict[str, Any], plan: RoutedPlan):
    """Rows of ``x`` (R, K) times their expert's matrix of ``w`` (held, K, N)
    under ``layout`` (``routed_layout``): the Pallas kernels
    (``saturn_gmm_fwd`` / ``_dx`` / ``_dw``) or ``jax.lax.ragged_dot``, whose
    transposes JAX derives. ``w`` comes in the parameters' dtype and is
    rounded to ``x``'s here; the kernels' table gradient leaves in float32
    without passing through that rounding."""
    if plan.impl == "kernel":
        return _gmm_kernels(x, w, layout["tile_expert"], layout["n_active"],
                            plan.row_tile)
    return jax.lax.ragged_dot(
        x, w.astype(x.dtype), layout["group_rows"],
        preferred_element_type=jnp.float32).astype(x.dtype)


# ------------------------------------------------------- token <-> row order
@jax.custom_vjp
def _to_rows(y, tok, valid, pos):
    """(T, D) -> (R, D): row r holds its token's row, or zeros."""
    del pos
    return jnp.where(valid[:, None], y[tok], jnp.zeros((), y.dtype))


@jax.custom_vjp
def _from_rows(o, tok, valid, pos):
    """(R, D) -> (T, D) float32: a token's row is the sum of its pairs' rows
    (``pos`` (T, k) names each pair's row, R where it has none)."""
    del tok, valid
    padded = jnp.concatenate([o, jnp.zeros((1, o.shape[1]), o.dtype)])
    out = jnp.zeros((pos.shape[0], o.shape[1]), jnp.float32)
    for s in range(pos.shape[1]):
        out = out + padded[pos[:, s]].astype(jnp.float32)
    return out


def _to_rows_fwd(y, tok, valid, pos):
    return _to_rows(y, tok, valid, pos), (tok, valid, pos, jnp.zeros((), y.dtype))


def _to_rows_bwd(kept, dx):
    tok, valid, pos, like = kept
    return _from_rows(dx, tok, valid, pos).astype(like.dtype), None, None, None


def _from_rows_fwd(o, tok, valid, pos):
    return _from_rows(o, tok, valid, pos), (tok, valid, pos, jnp.zeros((), o.dtype))


def _from_rows_bwd(kept, dout):
    tok, valid, pos, like = kept
    return _to_rows(dout.astype(like.dtype), tok, valid, pos), None, None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)
_from_rows.defvjp(_from_rows_fwd, _from_rows_bwd)


def routed_layout(local, plan: RoutedPlan) -> Dict[str, Any]:
    """The row buffer's layout for one step, from ``local`` (T, k) int32: the
    held experts' own numbers 0 .. held-1 of each (token, slot) pair, ``held``
    for a pair whose expert is not here. Integer work only."""
    T, k = local.shape
    held, tm, R = plan.held, plan.row_tile, plan.rows
    i32 = jnp.int32
    key = local.reshape(-1).astype(i32)
    n = key.shape[0]
    iota = jnp.arange(n, dtype=i32)
    mine = key[:, None] == jnp.arange(held, dtype=i32)[None, :]
    seen = jnp.cumsum(mine.astype(i32), axis=0)               # (n, held)
    counts = seen[-1]                                         # rows an expert
    rank_of = jnp.sum(jnp.where(mine, seen, 0), axis=1) - 1   # a pair's, in its expert
    _, order = jax.lax.sort((key, iota), num_keys=1, is_stable=True)
    tiles = jnp.maximum(1, -(-counts // tm))
    zero = jnp.zeros((1,), i32)
    start = jnp.concatenate([zero, jnp.cumsum(counts)])        # in sorted order
    tile_end = jnp.cumsum(tiles)
    pad_start = jnp.concatenate([zero, tile_end * tm])         # in the buffer
    n_active = tile_end[-1:]                                   # (1,) tiles in use
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(R // tm, dtype=i32), side="right"), held - 1).astype(i32)
    # a row -> its pair
    rows = jnp.arange(R, dtype=i32)
    e_row = tile_expert[rows // tm]
    rank = rows - pad_start[e_row]
    valid = (rank < counts[e_row]) & (rows < n_active[0] * tm)
    pair = order[jnp.clip(start[e_row] + rank, 0, n - 1)]
    # a pair -> its row (R: none)
    row_of = pad_start[key] + rank_of
    pos = jnp.where((key < held) & (row_of < R), row_of, R).reshape(T, k)
    layout = {"tok": pair // k, "pair": pair, "valid": valid, "pos": pos,
              "tile_expert": tile_expert, "n_active": n_active.astype(i32),
              "group_rows": (tiles * tm).astype(i32), "counts": counts,
              "overflow": n_active[0] * tm > R}
    # integer tables of a megabyte: a rematerialised layer keeps them
    # (``LAYOUT_NAME`` in its policy) and its backward sorts nothing again
    return {name: checkpoint_name(leaf, LAYOUT_NAME) for name, leaf in layout.items()}


def _relu2(u):
    return jnp.square(jax.nn.relu(u))


def _gate_act(act: str):
    """What the gate's product goes through before it multiplies ``up``."""
    return jax.nn.relu if act == "reglu" else jax.nn.silu


def _expert_rows(x, w_gate, w_up, w_down, layout, plan):
    """The row buffer through its experts: a gated pair (three grouped
    products; ``plan.act`` says silu or relu) or, with no gate,
    ``relu(x W_up)^2 W_down`` (two)."""
    u = grouped_matmul(x, w_up, layout, plan).astype(jnp.float32)
    if w_gate is None:
        a = _relu2(u)
    else:
        a = _gate_act(plan.act)(
            grouped_matmul(x, w_gate, layout, plan).astype(jnp.float32)) * u
    return grouped_matmul(a.astype(x.dtype), w_down, layout, plan)


def _masked_experts(y, weights, local, w_gate, w_up, w_down, act):
    """The exact second path: every held expert over every token, its output
    weighted by the token's weight for it (0 where it was not chosen). One
    expert at a time, each rematerialised in the backward: held x the dense
    work, and no buffer to overflow."""
    held = w_up.shape[0]
    f32 = jnp.float32
    gate_act = _gate_act(act)

    @jax.checkpoint
    def one(y, wg, wu, wd, m):
        u = jnp.dot(y, wu.astype(y.dtype), preferred_element_type=f32)
        if wg is None:
            a = _relu2(u).astype(y.dtype)
        else:
            h = jnp.dot(y, wg.astype(y.dtype), preferred_element_type=f32)
            a = (gate_act(h) * u).astype(y.dtype)
        return jnp.dot(a, wd.astype(y.dtype), preferred_element_type=f32) * m[:, None]

    def step(acc, xs):
        e, wg, wu, wd = xs
        m = jnp.sum(jnp.where(local == e, weights, 0.0), axis=-1)
        return acc + one(y, wg, wu, wd, m), None

    acc, _ = jax.lax.scan(step, jnp.zeros(y.shape, f32),
                          (jnp.arange(held, dtype=jnp.int32), w_gate, w_up, w_down))
    return acc


# A routed layer is two halves with one seam. The **route** reads the router's
# rows and makes everything that is integer or a weight: scores -> the choice
# (kept under ``LAYOUT_NAME``) -> the weights -> the row buffer's tables. The
# **experts under a route** take the rows the experts read through the held
# tables. ``routed_experts`` is the two in a row on one row set; a block whose
# router reads its input ahead of the mixer (``models/gpt2.py``,
# ``route_from="block_input"``) makes the route first and hands it across.
def route(y, router, *, plan: RoutedPlan, first_expert: int = 0,
          scale: float = 1.0, bias=None) -> Dict[str, Any]:
    """The route of rows ``y`` (T, D) under ``router`` (D, experts):

        z = y router                            float32, all the experts
        I = the top_k largest of s (+ bias), among the experts of the token's
            best groups under a group limit (``plan.groups``: ``limited_choice``)
        "sigmoid": s = sigmoid(z);  w_e = scale * s_e / (sum_{e' in I} s_e' + eps)
        "softmax": s = softmax(z);  w_e = scale * exp(z_e) / sum_{e' in I} exp(z_e')
                   (the softmax over all the experts, its top_k renormalised,
                   is the softmax over the chosen logits)

    ``bias`` (experts,): a selection bias, added to the scores for the choice
    only (its gradient is exactly zero). Returns ``weights`` (T, k) float32,
    ``local`` (T, k) (a pair's held expert, ``held`` where it is not here),
    ``layout`` (``routed_layout``) and ``chosen`` (T, k), the experts the
    router chose."""
    f32 = jnp.float32
    held = plan.held
    if (plan.tokens, plan.experts, plan.bias) != (
            y.shape[0], router.shape[1], bias is not None):
        raise ValueError(f"plan {plan} is not for {y.shape[0]} tokens, "
                         f"{router.shape[1]} experts, bias {bias is not None}")
    logits = jnp.dot(y.astype(f32), router.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    soft = plan.score == "softmax"
    scores = jax.nn.softmax(logits, axis=-1) if soft else jax.nn.sigmoid(logits)
    # the choice is kept with the tables it makes (``LAYOUT_NAME``), and the
    # chosen scores are read at it: a rematerialised layer's backward would
    # otherwise choose again from scores recomputed to another last bit, and
    # two scores a rounding apart then change slots (or experts) under tables
    # built for the forward's order, so one expert's weight gradient lands on
    # another's router column
    choice = scores if bias is None else scores + jax.lax.stop_gradient(
        bias.astype(f32))
    if plan.groups:
        choice = limited_choice(choice, plan.groups, plan.groups_kept)
    chosen = checkpoint_name(jax.lax.top_k(choice, plan.top_k)[1], LAYOUT_NAME)
    if soft:    # over the chosen logits themselves: no quotient of small shares
        weights = scale * jax.nn.softmax(
            jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)
    else:
        top = jnp.take_along_axis(scores, chosen, axis=-1)
        scaled = scale * top
        total = jnp.sum(top, axis=-1, keepdims=True)
        if plan.eps:
            total = total + plan.eps
        weights = scaled / total                                       # (T, k)
    local = chosen.astype(jnp.int32) - first_expert
    local = jnp.where((local >= 0) & (local < held), local, held)
    return {"weights": weights, "local": local, "chosen": chosen,
            "layout": routed_layout(local, plan)}


def experts_under(made: Dict[str, Any], u, w_gate, w_up, w_down, *,
                  plan: RoutedPlan, dtype: Any = jnp.bfloat16):
    """The held experts over rows ``u`` (T, L) under the route ``made``
    (:func:`route`), each pair times its weight:

        out = sum_{e in I, e held} w_e (act(u Wg_e) * (u Wu_e)) Wd_e

    Every pair whose expert is held is computed: through the row buffer where
    the step's padded rows fit it, through ``_masked_experts`` where they do
    not. Returns (out (T, L) in ``dtype``, counters: ``pairs_held``,
    ``rows_max`` (the fullest held expert), ``second_path`` (0 / 1) as int32
    scalars, and ``chosen`` (T, top_k))."""
    f32 = jnp.float32
    layout, local = made["layout"], made["local"]
    if (plan.held, plan.act == "relu2") != (w_up.shape[0], w_gate is None):
        raise ValueError(f"plan {plan} is not for {w_up.shape[0]} held experts, "
                         f"gate {w_gate is not None}")

    def through_rows(u, weights, w_gate, w_up, w_down):
        tok, valid, pos = layout["tok"], layout["valid"], layout["pos"]
        x = _to_rows(u, tok, valid, pos)
        o = _expert_rows(x, w_gate, w_up, w_down, layout, plan)
        w_row = jnp.where(valid, weights.reshape(-1)[layout["pair"]], 0.0)
        o = (o.astype(f32) * w_row[:, None]).astype(dtype)
        return _from_rows(o, tok, valid, pos)

    def through_mask(u, weights, w_gate, w_up, w_down):
        return _masked_experts(u, weights, local, w_gate, w_up, w_down, plan.act)

    operands = (u, made["weights"], w_gate, w_up, w_down)
    if plan.second_path:
        out = jax.lax.cond(layout["overflow"], through_mask, through_rows, *operands)
        second = layout["overflow"].astype(jnp.int32)
    else:
        out, second = through_rows(*operands), jnp.zeros((), jnp.int32)
    stats = {"pairs_held": jnp.sum(layout["counts"]),
             "rows_max": jnp.max(layout["counts"]),
             "second_path": second, "chosen": made["chosen"]}
    return out.astype(dtype), stats


def routed_experts(y, router, w_gate, w_up, w_down, *, plan: RoutedPlan,
                   first_expert: int = 0, scale: float = 1.0,
                   dtype: Any = jnp.bfloat16, bias=None, latent=None):
    """The held experts' part of a top-k routed layer whose router reads the
    rows its experts read: :func:`route` of ``y``, then :func:`experts_under`
    it on ``y``.

    ``y`` (T, D); ``router`` (D, experts); ``w_gate`` / ``w_up`` (held, D, F)
    and ``w_down`` (held, F, D): experts ``first_expert .. + held``.
    ``w_gate`` None (``plan.act`` "relu2"): an expert is ``relu(u Wu_e)^2
    Wd_e``, two products. ``latent`` (T, L): the rows ``u`` the experts read
    where they are not ``y`` (a projection of it; the router still reads
    ``y``); the tables are then (held, L, F) / (held, F, L) and the result
    (T, L). Returns what :func:`experts_under` does."""
    if (plan.latent, plan.route_from) != (
            0 if latent is None else latent.shape[1], "ff_input"):
        raise ValueError(f"plan {plan} is not for latent rows "
                         f"{None if latent is None else latent.shape} under a "
                         "router that reads the experts' own rows")
    plans.record("moe", plan)
    y = y.astype(dtype)
    made = route(y, router, plan=plan, first_expert=first_expert, scale=scale,
                 bias=bias)
    return experts_under(made, y if latent is None else latent.astype(dtype),
                         w_gate, w_up, w_down, plan=plan, dtype=dtype)
