"""Fused multi-model stacking: N sweep members as ONE compiled SPMD program.

Saturn's headline workload is batches of jobs sharing an architecture and
differing only in hyperparameters (HPO sweeps, model selection). Co-scheduling
(round 6) and bubble-filling (round 15) still pay one Python dispatch, one
data pipeline and one compiled program *per job*. Fusion stacks the members'
params/opt-state along a leading ``model`` axis and vmaps the train step over
it, so N jobs pay those costs once — per-member hyperparameters (LR today;
the vector generalizes) ride along as stacked ``(N,)`` arrays, keeping every
member's trajectory distinct AND bit-identical to its solo run (the
trajectory-equivalence suite in ``tests/test_fused.py`` proves it, the same
way ``tests/test_coschedule.py`` proves interleaving safety).

Layout: the ``model`` axis is vmapped on-device and, when the group runs on a
multi-chip block, sharded across the block via a leading ``PartitionSpec``
prefix (``P("model")`` on every stacked leaf, the batch stack and the hparam
vector) — GSPMD lays it out like any other mesh axis, so each chip advances
``N / n_devices`` members with zero cross-member collectives.

Lifecycle (docs/architecture.md round 21): ``fusion_candidates`` proposes
fusable sets (same :func:`fusion_fingerprint`), the trial runner profiles the
stacked program like any other grid point (``Strategy.fused_per_batch_time``),
the MILP picks fused vs co-scheduled vs solo on measured cost
(``solver/milp.py``), and the engine's fused launcher drives
:func:`run_fused_interval`. The **unfuse path** slices a diverged member's
leaves out of the stack mid-interval (guardian detach, early stop, or a
sentinel fault on its per-member loss column), checkpoints the slice through
the sharded manifest, journals the transition, and hands the member back to
the engine as a solo job — no lost or duplicated steps.
"""

from __future__ import annotations

import hashlib
import json
import logging
import time as _time
import timeit as _timeit
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from saturn_tpu.analysis import concurrency as tsan
from saturn_tpu.core.mesh import make_submesh
from saturn_tpu.ops import stacking
from saturn_tpu.parallel.spmd_base import choose_window
from saturn_tpu.utils import checkpoint as ckpt
from saturn_tpu.utils import metrics as _metrics

log = logging.getLogger("saturn_tpu")

#: Version of the fusion machinery baked into the profile-cache fingerprint
#: and the AOT-cache runtime identity (the ``SCHEDULE_SET_VERSION`` pattern,
#: round 15): bump when the stacked program's semantics change, so stale
#: per-job profiles re-trial instead of silently warm-starting a different
#: dispatch mode.
FUSION_SET_VERSION = 1


def fusion_signature() -> str:
    """Content signature of the fusion machinery for cache identities."""
    return f"fused-stack-v{FUSION_SET_VERSION}"


# ----------------------------------------------------------- fingerprinting
def fusion_fingerprint(task: Any) -> Optional[str]:
    """Compatibility key: two tasks may share a stack iff fingerprints match.

    Captures everything the stacked program's shape depends on — model config,
    abstract param tree, batch shape/dtype, optimizer family, loss objective —
    and *excludes* everything that rides along as a stacked hparam (LR).
    ``None`` means the task cannot fuse at all (callable optimizer, model
    factory failure): callers must treat ``None`` as matching nothing.
    """
    cached = getattr(task, "_fusion_fingerprint", False)
    if cached is not False:
        return cached
    fp = _fingerprint_uncached(task)
    task._fusion_fingerprint = fp
    return fp


def _fingerprint_uncached(task: Any) -> Optional[str]:
    opt = task.hparams.optimizer
    if not isinstance(opt, str):
        return None  # a callable optimizer factory has no comparable identity
    try:
        spec = task.get_model()
        ds = task.get_dataset()
        eb = ds.example_batch()
        shapes = jax.eval_shape(lambda: spec.init_fn(jax.random.PRNGKey(0)))
    except Exception as e:
        log.debug("fusion_fingerprint(%s) failed: %r", getattr(task, "name", "?"), e)
        return None
    cfg = getattr(spec, "config", None)
    try:
        cfg_sig = sorted(
            (k, repr(v)) for k, v in vars(cfg).items()
        ) if cfg is not None and hasattr(cfg, "__dict__") else repr(cfg)
    except TypeError:
        cfg_sig = repr(cfg)
    param_sig = [
        (jax.tree_util.keystr(p), tuple(l.shape), str(l.dtype))
        for p, l in jax.tree_util.tree_flatten_with_path(shapes)[0]
    ]
    loss_tag = getattr(task.loss_fn, "supports_fused_head", None) or getattr(
        task.loss_fn, "__name__", repr(task.loss_fn)
    )
    payload = json.dumps(
        {
            "fusion": fusion_signature(),
            "config": cfg_sig,
            "params": param_sig,
            "batch": [tuple(np.shape(eb)), str(np.asarray(eb).dtype)],
            "optimizer": opt,
            "loss": loss_tag,
        },
        sort_keys=True, default=repr,
    )
    return hashlib.sha1(payload.encode()).hexdigest()


def fusion_candidates(
    task_list: Sequence[Any], min_members: int = 2, max_members: int = 8
) -> List[List[str]]:
    """Fusable sets among ``task_list``: groups of task *names* whose members
    share a :func:`fusion_fingerprint` (same ModelSpec shape, batch/seq,
    optimizer family, loss). The ``coschedule_candidates`` analog for
    stacking — the solver prices each proposed set against its co-scheduled
    and solo alternatives (``solver/milp.py``). Oversized cohorts split into
    chunks of ``max_members``.
    """
    by_fp: Dict[str, List[str]] = {}
    for t in task_list:
        fp = fusion_fingerprint(t)
        if fp is None:
            continue
        # The stacked program runs every member as a whole-model replica on
        # its model-axis shard — one chip, full batch, no data-axis psum. A
        # task whose allowed solo widths include >1 chip would see different
        # floating-point arithmetic (split batch + cross-chip grad reduce)
        # depending on whether the scheduler happened to fuse it, breaking
        # trajectory bit-identity under rescheduling (tests/test_chaos.py
        # compares faulted campaigns against an uninterrupted reference).
        # Only single-chip tasks are arithmetic-neutral to fuse.
        widths = getattr(t, "chip_range", None) or []
        if any(int(c) != 1 for c in widths):
            continue
        by_fp.setdefault(fp, []).append(t.name)
    groups: List[List[str]] = []
    for names in by_fp.values():
        for i in range(0, len(names), max(int(max_members), 2)):
            chunk = names[i : i + max(int(max_members), 2)]
            if len(chunk) >= max(int(min_members), 2):
                groups.append(chunk)
    return groups


# ----------------------------------------------------------- stacked program
def _make_tx(opt_name: str) -> Callable[[Any], Any]:
    """lr -> optax transformation, traceable: constructed INSIDE the vmapped
    step so each member's update closes over its own (traced) LR. Bitwise
    equal to the solo program's concrete-float construction — adamw/adam/sgd
    scale by lr as a plain multiply, so a traced scalar lowers to the same
    HLO the constant did (verified by the trajectory-equivalence tests)."""
    if opt_name == "adamw":
        return optax.adamw
    if opt_name == "adam":
        return optax.adam
    return optax.sgd


def _member_step_fns(
    spec: Any, loss_fn: Any, opt_name: str, fused_loss_ok: bool = True
) -> Tuple[Callable, Callable]:
    """(member_init(lr) -> state, member_step(state, batch, lr) -> (state,
    loss)) for ONE member — the exact solo scaffold
    (``SPMDTechnique.step_fns_from_loss_and_grads``) with the LR lifted from
    a closure constant to a traced argument.

    The loss path mirrors ``step_fns_from_forward``'s single-device decision:
    the member program inside the vmap is a whole-model replica (the model
    axis is the only sharded one), so the fused head+loss (ops/ce.py)
    engages exactly when the member's solo single-device program would use
    it — which is what keeps a fused member's loss trajectory bit-identical
    to its solo run.
    """
    fused = getattr(spec, "fused_loss_fn", None)
    tag = getattr(loss_fn, "supports_fused_head", None)
    use_fused_ce = (
        fused is not None
        and fused_loss_ok
        and spec.apply_with_aux_fn is None
        and tag is not None
        and tag == getattr(spec, "fused_loss_objective", None)
    )
    if use_fused_ce:
        def loss_of(params, batch):
            return fused(params, batch)
    elif spec.apply_with_aux_fn is not None:
        def loss_of(params, batch):
            logits, aux = spec.apply_with_aux_fn(params, batch)
            return loss_fn(logits, batch) + aux
    else:
        def loss_of(params, batch):
            return loss_fn(spec.apply_fn(params, batch), batch)

    tx_of = _make_tx(opt_name)

    def member_init(lr):
        params = spec.init_fn(jax.random.PRNGKey(0))
        return {
            "params": params,
            "opt_state": tx_of(lr).init(params),
            "step": jnp.zeros((), dtype=jnp.int32),
        }

    def member_step(state, batch, lr):
        tx = tx_of(lr)
        loss, grads = jax.value_and_grad(loss_of)(state["params"], batch)
        updates, new_opt = tx.update(grads, state["opt_state"], state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {
            "params": new_params,
            "opt_state": new_opt,
            "step": state["step"] + 1,
        }, loss

    return member_init, member_step


@dataclass
class FusedProgram:
    """Compiled artifacts for one (fingerprint, config, N, block) stack."""

    n_members: int
    mesh: Any
    member_shapes: Any            # solo-shaped ShapeDtypeStruct tree
    stacked_shapes: Any           # (N, ...) ShapeDtypeStruct tree
    state_shardings: Any          # P("model") prefix on every stacked leaf
    batch_sharding: Any           # (N, B, T) stack
    lr_sharding: Any              # (N,) hparam vector
    member_batch_shape: Tuple[int, ...]
    batch_dtype: Any
    member_init: Any              # lr -> solo-shaped state (python fn)
    _stacked_step: Any            # raw (state, batch, lrs) -> (state, loss)
    _single: Any = None
    _windows: Dict[int, Any] = field(default_factory=dict)

    def _devices(self) -> List[Any]:
        return list(self.mesh.devices.flat)

    def _lr_sds(self):
        return jax.ShapeDtypeStruct((self.n_members,), jnp.float32)

    def single_compiled(self):
        """AOT-compiled one-step stacked program: (state, (N,B,T), (N,)) ->
        (state, (N,) per-member losses). State donated; lrs are not."""
        with _CACHE_LOCK:
            hit = self._single
        if hit is not None:
            return hit
        from saturn_tpu.utils import aot_cache

        batch_sds = jax.ShapeDtypeStruct(
            (self.n_members, *self.member_batch_shape), self.batch_dtype
        )
        jitted = jax.jit(
            self._stacked_step,
            in_shardings=(self.state_shardings, self.batch_sharding,
                          self.lr_sharding),
            out_shardings=(self.state_shardings,
                           NamedSharding(self.mesh, P())),
            donate_argnums=(0,),
        )
        compiled = aot_cache.load_or_compile(
            jitted.lower(self.stacked_shapes, batch_sds, self._lr_sds()),
            self._devices(),
        )
        with _CACHE_LOCK:
            if self._single is None:
                self._single = compiled
            return self._single

    def window_compiled(self, k: int):
        """AOT-compiled fused K-window: ``lax.scan`` of the stacked step over
        a (K, N, B, T) staging stack — one dispatch and one (K, N) loss
        readback amortize over K lockstep batches for all N members. State
        AND the window stack are donated (fresh stack per call)."""
        k = int(k)
        with _CACHE_LOCK:
            hit = self._windows.get(k)
        if hit is not None:
            return hit
        from saturn_tpu.utils import aot_cache

        step = self._stacked_step

        def window_step(state, window, lrs):
            def body(s, b):
                return step(s, b, lrs)

            return jax.lax.scan(body, state, window)

        window_sharding = NamedSharding(
            self.mesh, P(None, *tuple(self.batch_sharding.spec))
        )
        window_sds = jax.ShapeDtypeStruct(
            (k, self.n_members, *self.member_batch_shape), self.batch_dtype
        )
        jitted = jax.jit(
            window_step,
            in_shardings=(self.state_shardings, window_sharding,
                          self.lr_sharding),
            out_shardings=(self.state_shardings,
                           NamedSharding(self.mesh, P())),
            donate_argnums=(0, 1),
        )
        compiled = aot_cache.load_or_compile(
            jitted.lower(self.stacked_shapes, window_sds, self._lr_sds()),
            self._devices(),
        )
        with _CACHE_LOCK:
            return self._windows.setdefault(k, compiled)

    def window_sharding(self):
        return NamedSharding(
            self.mesh, P(None, *tuple(self.batch_sharding.spec))
        )

    def init_member_host(self, lr: float) -> Any:
        """One member's freshly-initialized state as host numpy — identical
        values to the solo program's ``bundle.init()`` (same PRNGKey(0)
        init), so a fused-from-scratch member matches its solo twin from
        step 0."""
        dev = jax.jit(self.member_init)(jnp.float32(lr))
        return jax.tree_util.tree_map(np.asarray, dev)


#: Compiled-program cache: (fingerprint, config, N, block) -> FusedProgram.
#: Keyed on the GROUP's shape identity, not member names — an unfuse from
#: N to N-1 members reuses any previously compiled (N-1)-stack of the same
#: fingerprint, and re-fusing next interval hits the cache outright.
_PROGRAMS: Dict[Any, FusedProgram] = {}
_CACHE_LOCK = tsan.lock("fused.programs")


def usable_devices(devices: Sequence[Any], n_members: int) -> List[Any]:
    """Largest prefix of ``devices`` the model axis can span: N must divide
    the axis size so every chip carries the same member count. Walks the
    block size down by powers of two; worst case a single device carries the
    whole (vmapped, unsharded) stack."""
    n_dev = max(len(devices), 1)
    while n_dev > 1 and int(n_members) % n_dev != 0:
        n_dev //= 2
    return list(devices[:n_dev])


def build_fused_program(
    members: Sequence[Any],
    devices: Sequence[Any],
    inner: Optional[Any] = None,
    config: Optional[Dict[str, Any]] = None,
) -> FusedProgram:
    """Build (or fetch from cache) the stacked program for ``members``.

    ``inner`` is the wrapped SPMD technique (defaults to member 0's selected
    strategy executor); its ``fused_loss_ok`` and model-override policy apply
    to the member program exactly as they would solo. All members must share
    a :func:`fusion_fingerprint` — enforced here, because a mismatched member
    would otherwise surface as an XLA shape error inside vmap.
    """
    if not members:
        raise ValueError("build_fused_program: empty member list")
    rep = members[0]
    if inner is None and rep.selected_strategy is not None:
        inner = rep.selected_strategy.executor
    if config is None:
        sel = rep.selected_strategy
        config = dict(sel.params or {}) if sel is not None else {}
    fp = fusion_fingerprint(rep)
    if fp is None:
        raise ValueError(
            f"task {rep.name!r} is not fusable (no fusion fingerprint)"
        )
    for m in members[1:]:
        if fusion_fingerprint(m) != fp:
            raise ValueError(
                f"fused member {m.name!r} has a different fusion fingerprint "
                f"than {rep.name!r} — the group is not stack-compatible"
            )
    devs = usable_devices(devices, len(members))
    key = (
        fp,
        tuple(sorted(config.items())),
        len(members),
        tuple(getattr(d, "id", i) for i, d in enumerate(devs)),
    )
    with _CACHE_LOCK:
        hit = _PROGRAMS.get(key)
    if hit is not None:
        return hit
    prog = _build_program_uncached(rep, members, devs, inner, config)
    with _CACHE_LOCK:
        return _PROGRAMS.setdefault(key, prog)


def _build_program_uncached(
    rep: Any, members: Sequence[Any], devs: List[Any],
    inner: Optional[Any], config: Dict[str, Any],
) -> FusedProgram:
    n = len(members)
    overrides = inner._model_overrides(config) if inner is not None else {}
    spec = rep.get_model(**overrides)
    fused_loss_ok = bool(getattr(inner, "fused_loss_ok", True))
    member_init, member_step = _member_step_fns(
        spec, rep.loss_fn, rep.hparams.optimizer, fused_loss_ok
    )
    mesh = make_submesh(devs, ("model",), (len(devs),))
    member_shapes = jax.eval_shape(
        member_init, jax.ShapeDtypeStruct((), jnp.float32)
    )
    stacked_shapes = stacking.stacked_shapes(member_shapes, n)
    state_shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P("model")), stacked_shapes
    )
    ds = rep.get_dataset()
    eb = np.asarray(ds.example_batch())

    def stacked_step(state, batch, lrs):
        return jax.vmap(member_step)(state, batch, lrs)

    return FusedProgram(
        n_members=n,
        mesh=mesh,
        member_shapes=member_shapes,
        stacked_shapes=stacked_shapes,
        state_shardings=state_shardings,
        batch_sharding=NamedSharding(mesh, P("model")),
        lr_sharding=NamedSharding(mesh, P("model")),
        member_batch_shape=tuple(eb.shape),
        batch_dtype=eb.dtype,
        member_init=member_init,
        _stacked_step=stacked_step,
    )


# --------------------------------------------------------- interval execution
@dataclass
class MemberResult:
    """One member's outcome for a fused interval."""

    name: str
    steps: int = 0                      # batches retired IN the stack
    final_loss: Optional[float] = None
    fault: Optional[BaseException] = None   # sentinel fault (state discarded)
    detached_at: Optional[int] = None   # unfuse point (interval-relative)


@dataclass
class FusedIntervalReport:
    """What :func:`run_fused_interval` hands back to the engine's launcher."""

    n_steps: int
    window: int
    members: Dict[str, MemberResult]
    detached: List[Tuple[Any, int]]     # (task, steps retired at unfuse)
    per_step_s: float = 0.0             # steady-state lockstep seconds
    samples_per_sec: float = 0.0        # aggregate across the stack
    elapsed_s: float = 0.0


def _fused_live_key(fp: str, config: Dict[str, Any], devs: Sequence[Any]):
    return (
        "fused", fp, tuple(sorted(config.items())),
        tuple(getattr(d, "id", i) for i, d in enumerate(devs)),
    )


def _resume_member_host(m: Any, prog: FusedProgram, live_key: Any) -> Any:
    """Member state as a host tree: live cache, checkpoint, or fresh init —
    the same resume ladder as ``SPMDTechnique.interval_dispatches``, with the
    data cursor re-derived from the trained-step count on a ckpt restore."""
    live = getattr(m, "_live_state", None)
    if live is not None and live[0] == live_key:
        m._live_state = None
        return live[1]
    m._live_state = None
    if m.has_ckpt():
        state = ckpt.restore(m.ckpt_path, prog.member_shapes)
        m.current_batch = m.cursor_for_step(int(np.asarray(state["step"])))
        return state
    return prog.init_member_host(m.hparams.lr)


def _member_host_slices(state: Any, indices: Sequence[int]) -> List[Any]:
    """Device->host member slices (the per-member checkpoint view)."""
    return [
        jax.tree_util.tree_map(
            np.asarray, stacking.member_slice(state, i)
        )
        for i in indices
    ]


def run_fused_interval(
    members: Sequence[Any],
    devices: Sequence[Any],
    tid: int = 0,
    batch_counts: Optional[Sequence[int]] = None,
    inner: Optional[Any] = None,
    config: Optional[Dict[str, Any]] = None,
    window_size: Optional[int] = None,
    detach_requested: Optional[Callable[[Any], bool]] = None,
) -> FusedIntervalReport:
    """One engine interval for a fused group: lockstep batches for all
    members through one compiled program.

    The lockstep budget is ``min`` over the members' interval budgets — the
    engine re-forecasts the shortfall next interval, exactly as it does for
    any under-retired job. Dispatch shape mirrors the solo path: ``n // K``
    fused windows (scanned (K, N, B, T) stacks) plus an ``n % K`` per-step
    tail, batches staged one unit ahead by the prefetcher.

    ``detach_requested`` is polled at every unit boundary (defaults to the
    member's ``_fused_detach`` flag, which the guardian's detach/quarantine
    path and early stopping set): a detaching member is **unfused** —
    state sliced out of the stack, checkpointed through the sharded
    manifest (crash barrier ``"fused.unfuse"`` fires first, so the chaos
    harness can kill inside the transition), journaled as a
    ``fused_unfuse`` metrics event — and returned in ``report.detached``
    for the engine to resume solo. Survivors continue on a rebuilt
    (cache-hit) N-1 stack.

    Sentinel faults are per member: each member's (n,) loss column is folded
    exactly as its solo interval would fold it; a faulted member's state is
    discarded (no checkpoint, no live-state publish — its last durable
    checkpoint is the rollback target) while healthy members commit.
    """
    if not members:
        raise ValueError("run_fused_interval: empty group")
    detach_requested = detach_requested or (
        lambda t: bool(getattr(t, "_fused_detach", False))
    )
    cur: List[Any] = list(members)
    if inner is None and cur[0].selected_strategy is not None:
        inner = cur[0].selected_strategy.executor
    if config is None:
        sel = cur[0].selected_strategy
        config = dict(sel.params or {}) if sel is not None else {}

    budgets = [
        int(b) for b in (
            batch_counts if batch_counts is not None
            else [m.total_batches for m in cur]
        )
    ]
    n = max(min(budgets), 0) if budgets else 0
    report = FusedIntervalReport(
        n_steps=n, window=1,
        members={m.name: MemberResult(name=m.name) for m in cur},
        detached=[],
    )
    if n <= 0:
        return report
    # The ``fused_interval`` event is this span (same fields, plus its start):
    # from before the stacked program is built to after the members'
    # snapshots, so the group's ``ckpt.*`` spans are its children.
    with _metrics.span("fused_interval") as sp:
        return _run_fused(
            sp, cur, devices, tid, n, report, inner, config, window_size,
            detach_requested,
        )


def _run_fused(
    sp, cur, devices, tid, n, report, inner, config, window_size,
    detach_requested,
) -> FusedIntervalReport:
    """The body of :func:`run_fused_interval`'s ``fused_interval`` span."""
    ts_launch = _time.time()  # before any compile this interval needs
    fp = fusion_fingerprint(cur[0])
    prog = build_fused_program(cur, devices, inner=inner, config=config)
    live_key = _fused_live_key(fp, config, prog._devices())

    host_states = [_resume_member_host(m, prog, live_key) for m in cur]
    starts = {m.name: m.current_batch for m in cur}

    from saturn_tpu.core import distributed as _dist

    state = _dist.put_tree_global(
        stacking.stack_trees(host_states), prog.state_shardings
    )
    del host_states

    # -------- window plan (identical unit algebra to the solo path)
    fused_ok = inner._fused_ok(config) if inner is not None else True
    k = choose_window(n) if window_size is None else int(window_size)
    k = max(1, min(k, n))
    if k > 1 and not fused_ok:
        k = 1
    n_windows = n // k if k > 1 else 0
    units: List[Tuple[bool, int]] = [(True, w * k) for w in range(n_windows)]
    units += [(False, j) for j in range(n_windows * k, n)]
    report.window = k
    first_unit_batches = k if (units and units[0][0]) else 1

    # Per-segment loss buffers: (member names at that segment, device
    # (steps, N_seg) matrices). Membership only changes at unfuse points.
    segments: List[Tuple[List[str], List[Any]]] = []
    seg_losses: List[Any] = []

    def close_segment() -> None:
        if seg_losses:
            segments.append(([m.name for m in cur], list(seg_losses)))
            seg_losses.clear()

    from saturn_tpu.data.prefetch import DevicePrefetcher

    batch_size = int(prog.member_batch_shape[0]) if prog.member_batch_shape else 1
    n_members0 = len(cur)
    names0 = [m.name for m in cur]
    t_all0 = _timeit.default_timer()
    t_steady = t_all0
    steps_done = 0
    u = 0
    while u < len(units):
        # ---- unfuse check at the unit boundary
        leaving = [m for m in cur if detach_requested(m)]
        if leaving and len(cur) - len(leaving) >= 1:
            close_segment()
            for m in leaving:
                idx = cur.index(m)
                member_host = _member_host_slices(state, [idx])[0]
                # Crash barrier FIRST: a kill here leaves nothing durable
                # from this interval, so replay re-runs it bit-identically
                # and unfuses at the same boundary — exactly once.
                ckpt._barrier(
                    "fused.unfuse", task=m.name, step=steps_done, tid=tid
                )
                ckpt.save(m.ckpt_path, member_host)
                _metrics.event(
                    "fused_unfuse", task=m.name, group=names0,
                    step=steps_done, n_remaining=len(cur) - 1,
                )
                log.info(
                    "fused group: unfused member %s at interval step %d "
                    "(%d member(s) remain)", m.name, steps_done, len(cur) - 1,
                )
                report.members[m.name].steps = steps_done
                report.members[m.name].detached_at = steps_done
                report.detached.append((m, steps_done))
                survivors = [j for j in range(len(cur)) if j != idx]
                host_survivors = _member_host_slices(state, survivors)
                cur.pop(idx)
                prog = build_fused_program(
                    cur, devices, inner=inner, config=config
                )
                state = _dist.put_tree_global(
                    stacking.stack_trees(host_survivors), prog.state_shardings
                )
        elif leaving:
            log.warning(
                "fused group: detach requested for every member — "
                "finishing the interval fused (nothing to unfuse into)"
            )

        # ---- run until the next boundary event (or interval end)
        n_cur = len(cur)
        lrs_dev = _dist.put_global(
            np.asarray([m.hparams.lr for m in cur], dtype=np.float32),
            prog.lr_sharding,
        )
        seg_u0 = u
        member_names = [m.name for m in cur]

        def stage(j: int, _u0=seg_u0, _members=list(cur),
                  _names=list(member_names), _prog=prog):
            fused_u, off = units[_u0 + j]
            if fused_u:
                host = np.stack([
                    stacking.stack_member_batches(
                        [m.batch_at(starts[m.name] + off + i) for m in _members],
                        member_names=_names,
                        expect=_prog.member_batch_shape,
                    )
                    for i in range(k)
                ])
                return _dist.put_global(host, _prog.window_sharding())
            host = stacking.stack_member_batches(
                [m.batch_at(starts[m.name] + off) for m in _members],
                member_names=_names, expect=_prog.member_batch_shape,
            )
            return _dist.put_global(host, _prog.batch_sharding)

        single_fn = (
            prog.single_compiled()
            if any(not f for f, _ in units[seg_u0:]) else None
        )
        fused_fn = (
            prog.window_compiled(k)
            if any(f for f, _ in units[seg_u0:]) else None
        )
        expect = (
            (k, n_cur, *prog.member_batch_shape),
            (n_cur, *prog.member_batch_shape),
        )
        prefetch = DevicePrefetcher(
            len(units) - seg_u0, stage, depth=2,
            expect_shapes=expect, member_names=member_names,
        )
        try:
            while u < len(units):
                if u > seg_u0 and any(detach_requested(m) for m in cur):
                    break  # handle the unfuse at the outer boundary
                try:
                    dev_batch = next(prefetch)
                except StopIteration:
                    break
                if units[u][0]:
                    state, loss = fused_fn(state, dev_batch, lrs_dev)  # (K, N)
                    seg_losses.append(jnp.reshape(loss, (k, n_cur)))
                    steps_done += k
                else:
                    state, loss = single_fn(state, dev_batch, lrs_dev)  # (N,)
                    seg_losses.append(jnp.reshape(loss, (1, n_cur)))
                    steps_done += 1
                if u == seg_u0 == 0 and len(units) > 1:
                    # Warmup fence: keep executable load + first staging out
                    # of the steady-state window (realized feedback).
                    jax.block_until_ready(loss)  # lint: sanctioned-host-sync
                    t_steady = _timeit.default_timer()
                u += 1
        finally:
            # SimulatedKill is a BaseException: never leak a staging thread.
            prefetch.close()

    close_segment()

    # -------- finalization: per-member sentinel folds, checkpoints, timing
    t_end = _timeit.default_timer()
    elapsed_all = t_end - t_all0
    from saturn_tpu.health import sentinel as _sentinel

    scfg = _sentinel.get_config()
    # Per-member loss columns across segments (a detached member's column
    # ends at its unfuse point — its solo continuation owns the rest).
    columns: Dict[str, List[Any]] = {m.name: [] for m in cur}
    for names, mats in segments:
        for mat in mats:
            for i, nm in enumerate(names):
                if nm in columns:
                    columns[nm].append(mat[:, i])

    final_losses: Dict[str, float] = {}
    faulted: set = set()
    for m in cur:
        col = columns.get(m.name) or []
        poison = m.__dict__.pop("_health_poison", None)
        if not col:
            continue
        vec = jnp.concatenate(col)
        if scfg.enabled:
            if poison is not None:
                # Chaos injection corrupts the OBSERVED member column only
                # (train state untouched), exactly like the solo path
                # (spmd_base interval finalization) — without this, faults
                # scheduled onto a fused member were silently dropped and
                # the chaos campaign never saw a rollback.
                ov = _sentinel.poison_overrides(
                    poison, int(vec.shape[0]),
                    lambda j: m.dataset_index(starts[m.name] + j),
                )
                if ov is not None:
                    vec = vec.at[ov[0]].set(ov[1])
            carry = getattr(m, "_sentinel_carry", None)
            if carry is None:
                carry = _sentinel.carry_init()
            rep = np.asarray(
                _dist.host_array(_sentinel.fold(carry, vec, scfg))
            )
            loss_val = float(rep[_sentinel.REP_LAST_LOSS])
            fault = _sentinel.inspect(rep)
            if fault is not None:
                cause, first_off, bad_count = fault
                bad = tuple(sorted({
                    m.dataset_index(starts[m.name] + int(j)) for j in
                    set(np.flatnonzero(
                        ~np.isfinite(np.asarray(_dist.host_array(vec)))
                    )) | {max(int(first_off), 0)}
                }))
                err = _sentinel.NumericFaultError(
                    m.name, first_off // max(k, 1), cause, step=first_off,
                    loss=loss_val, batch_indices=bad, bad_count=bad_count,
                )
                log.warning(
                    "fused member %s: sentinel tripped (%s) at interval "
                    "step %d — discarding the member's interval",
                    m.name, cause, first_off,
                )
                report.members[m.name].fault = err
                faulted.add(m.name)
                continue
            m._sentinel_carry = rep[:2].copy()
        else:
            loss_val = float(
                np.asarray(_dist.host_array(vec)).reshape(-1)[-1]
            )
        final_losses[m.name] = loss_val
        report.members[m.name].final_loss = loss_val
        report.members[m.name].steps = n

    # Per-member checkpoint slices through the sharded manifest; a faulted
    # member's state is NOT persisted (its previous checkpoint is the
    # rollback target, exactly like the solo fault path).
    healthy = [i for i, m in enumerate(cur) if m.name not in faulted]
    slices = _member_host_slices(state, healthy)
    for i, host in zip(healthy, slices):
        m = cur[i]
        ckpt.save_async(m.ckpt_path, host)
        m._live_state = (live_key, host)

    per_step = (
        (t_end - t_steady) / max(n - first_unit_batches, 1)
        if len(units) > 1 else elapsed_all / max(n, 1)
    )
    report.per_step_s = per_step
    report.elapsed_s = elapsed_all
    report.samples_per_sec = (
        n * n_members0 * batch_size / max(elapsed_all, 1e-9)
    )
    sp.set(
        members=names0, n_members=n_members0,
        batches=n, window=k,
        per_step_s=per_step,
        samples_per_sec=round(report.samples_per_sec, 2),
        losses={nm: round(v, 6) for nm, v in final_losses.items()},
        detached=[m.name for m, _ in report.detached],
        faulted=sorted(faulted),
        # ``task_interval``'s meaning: when the group took its block, and
        # the seconds from its first dispatch to its last loss read back
        ts_launch=ts_launch, elapsed_s=elapsed_all,
    )
    log.info(
        "fused group %s: ran %d lockstep batches (K=%d, %d members, "
        "%.1f samples/s aggregate)",
        names0, n, k, n_members0, report.samples_per_sec,
    )
    return report
