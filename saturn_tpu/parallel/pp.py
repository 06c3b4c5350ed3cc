"""Pipeline-parallel executor: GPipe / 1F1B over a ``stage`` mesh axis.

Replaces the reference's torchgpipe UDP (``examples/wikitext103/executors/
Pipeline.py:24-167``). Reference behavior preserved: partition the layer
stack across workers (``balance_by_time``, ``Pipeline.py:94-103`` → here
:func:`balance_stages`, an exact DP over the model's ``layer_costs`` hint
— profiled or FLOP-derived per-layer costs, uniform when absent), and
autotune the microbatch count (``Pipeline.py:139-159`` halving sweep → grid
over {M} multiples of the stage count). The schedule itself lives in
``saturn_tpu.ops.pipeline`` (shard_map + ppermute); unequal stage spans
(uneven costs, or a layer count the stage count doesn't divide) run via
the padded-span schedule there.

A ``data`` axis composes data parallelism with the pipeline: a mesh of
``n`` devices runs ``n/S`` pipeline replicas of ``S`` stages each.

The schedule is a profiled grid dimension, not a default: candidate configs
carry ``schedule: "gpipe" | "1f1b"`` and the trial runner times both, so the
solver picks per task from realized cost rather than the analytic bubble
formula. ``layout: "stage_major"`` additionally lets the stage axis span
slice boundaries (activation hops over DCN, per-stage data all-reduce on
ICI) when no single slice fits the model.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from jax.sharding import PartitionSpec as P

from saturn_tpu.ops.pipeline import (
    PIPELINE_SCHEDULES,
    balance_stages,
    pipeline_hints,
    pipeline_loss_and_grads,
    schedule_bubble_fraction,
    staged_pipeline_loss_and_grads,
)
from saturn_tpu.parallel.spmd_base import SPMDTechnique
from saturn_tpu.core.strategy import Techniques
from saturn_tpu.core.technique import InfeasibleConfig


def _layer_costs(spec, n_layers: int) -> Optional[list]:
    """Per-layer cost vector from the model hints, or None for uniform.
    Validated here so a stale hint fails search loudly, not mid-step."""
    costs = spec.hints.get("layer_costs")
    if costs is None:
        return None
    costs = list(costs)
    if len(costs) != n_layers or min(costs) <= 0:
        raise ValueError(
            f"layer_costs must be {n_layers} positive entries, got {costs!r}"
        )
    return costs


class Pipeline(SPMDTechnique):
    name = "pp"
    technique = Techniques.PIPELINE

    def mesh_spec(self, n_devices, task, config) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
        s = config.get("stages", 2)
        if n_devices % s != 0:
            raise InfeasibleConfig(f"{n_devices} devices not divisible by {s} stages")
        if config.get("layout") == "stage_major":
            # Cross-slice stage placement: with slice-major device ordering
            # (``core/mesh.py``) the LEADING mesh axis is the one whose
            # collectives cross DCN once the block outgrows a slice. Putting
            # ``stage`` first sends the per-tick ppermute activation hop over
            # DCN (one activation tensor per tick — the cheap collective)
            # while each stage's data-parallel grad all-reduce stays inside
            # its slice. Shardflow's ``crossing_axes`` prices exactly this.
            return ("stage", "data"), (s, n_devices // s)
        return ("data", "stage"), (n_devices // s, s)

    def batch_spec(self, config) -> P:
        return P("data")

    def param_rules(self, task, config):
        spec = task.get_model()
        bkey = spec.hints.get("block_param_key", "blocks")
        s = config.get("stages", 2)

        def rules(path: str, shape: Tuple[int, ...], mesh_axes) -> P:
            # At-rest layout: NamedSharding requires the sharded dim to
            # divide by the axis size, so a stack the stage count doesn't
            # divide stays replicated at rest (param memory = dp's; the
            # padded-span repack inside the step still distributes compute).
            # Cost-uneven stacks whose length DOES divide keep the sharded
            # rest layout — the repack moves only boundary-crossing layers.
            if bkey in path and shape and shape[0] % s == 0:
                return P("stage")
            return P()

        return rules

    def candidate_configs(self, task, n_devices) -> List[Dict[str, Any]]:
        spec = task.get_model()
        n_layers = getattr(spec.config, "n_layers", 1)
        if "pipeline" not in spec.hints:
            return []
        if self._aux_incompatible(spec):
            return []  # staged forward would drop the model's aux loss
        costs = _layer_costs(spec, n_layers)
        batch = task.get_dataset().batch_size
        # Cross-slice stage placement is only worth its DCN hops when the
        # block genuinely spans slices (``search`` stamps ``topology``).
        topo = getattr(self, "topology", None)
        slice_size = getattr(topo, "slice_size", None) if topo is not None else None
        cross_slice = bool(slice_size) and int(slice_size) < int(n_devices)
        grid: List[Dict[str, Any]] = []
        # Every divisor of the device count, not just powers of two: the old
        # ``s <<= 1`` sweep meant a 6-device slice never considered s=3/s=6.
        for s in range(2, min(n_devices, n_layers) + 1):
            if n_devices % s != 0:
                continue
            d = n_devices // s
            if batch % d != 0:
                continue
            per_replica = batch // d
            # Balanced boundaries (reference balance_by_time analog):
            # needed when per-layer costs are uneven OR the stage count
            # doesn't divide the stack (pre-round-4 both cases silently
            # produced no pp candidates).
            spans: Optional[Tuple[int, ...]] = None
            if costs is not None:
                spans = balance_stages(costs, s)
            elif n_layers % s != 0:
                spans = balance_stages([1.0] * n_layers, s)
            # Microbatch sweep, most-microbatches (smallest bubble) first —
            # the analog of the reference's halving search (Pipeline.py:139).
            gpipe_ms = [m for m in (4 * s, 2 * s, s) if per_replica % m == 0]
            if not gpipe_ms:
                # Fallback: the largest stage-count multiple <= 4s dividing
                # the per-replica batch (the old sweep silently emitted no
                # pp candidates here).
                fb = [m for m in range(s, 4 * s + 1, s) if per_replica % m == 0]
                if fb:
                    gpipe_ms = [max(fb)]
            onef_ms = list(gpipe_ms)
            if not onef_ms:
                # 1F1B has no M % S constraint (the staged program runs any
                # M >= 1) — any divisor of the per-replica batch works.
                fb = [m for m in range(2, min(per_replica, 4 * s) + 1)
                      if per_replica % m == 0]
                if fb:
                    onef_ms = [max(fb)]
            layouts: List[Optional[str]] = [None]
            if cross_slice:
                layouts.append("stage_major")
            for layout in layouts:
                for schedule, ms in (("gpipe", gpipe_ms), ("1f1b", onef_ms)):
                    for m in ms:
                        base: Dict[str, Any] = {
                            "stages": s, "microbatches": m,
                            "schedule": schedule,
                        }
                        if spans is not None:
                            base["spans"] = spans
                        if layout is not None:
                            base["layout"] = layout
                        grid.append(dict(base, remat=False))
                        grid.append(dict(base, remat=True))
                        # Double-buffered stage hops (ops/pipeline.py H=2):
                        # next tick's ppermute issued before this tick's
                        # stage compute. Own grid points — realized cost
                        # decides, the bubble model prices H into the prior.
                        grid.append(dict(base, remat=False, overlap=True))
                        grid.append(dict(base, remat=True, overlap=True))
        return grid

    def config_bubble_fraction(self, config) -> float:
        """Analytic pipeline-bubble fraction of a steady-state step: the
        device-idle share a co-scheduled partner's windows could fill. 1F1B
        drains its bubble faster — (S-1)/(M+2(S-1)) vs GPipe's
        (S-1)/(M+S-1) — which makes a 1F1B job a WORSE gap-filler partner;
        the solver's co-location term prices exactly that difference."""
        s = int(config.get("stages", 2))
        m = int(config.get("microbatches", 2 * s))
        return schedule_bubble_fraction(
            str(config.get("schedule", "gpipe")), s, m,
            overlap=bool(config.get("overlap", False)),
        )

    def make_step_fns(self, spec, task, config, mesh, ds):
        self._require_no_aux(spec)  # staged forward would drop an aux loss
        if spec.stack_kinds:
            # stages are spans of ``config.n_layers`` equal layers; a period
            # of several kinds is one scanned unit of unequal layers
            # (ROADMAP.md, Reach: per-kind stage costs in balance_stages)
            raise InfeasibleConfig(
                f"pp: the model's stack holds several block kinds "
                f"{spec.stack_kinds}; pp stages a stack of one kind")
        if spec.stack_passes != 1:
            # A looped stack would need the last stage's output (after the
            # between-passes norm) fed back to the first stage, which no
            # schedule of ops/pipeline.py has (ROADMAP.md, Reach, M7).
            raise InfeasibleConfig(
                f"pp: the model passes its stack {spec.stack_passes} times; "
                f"the stage schedules run each block once"
            )
        s = config.get("stages", 2)
        m = config.get("microbatches", 2 * s)
        schedule = str(config.get("schedule", "gpipe"))
        if schedule not in PIPELINE_SCHEDULES:
            raise ValueError(
                f"unknown pipeline schedule {schedule!r}; "
                f"choices: {PIPELINE_SCHEDULES}"
            )
        spans = config.get("spans")
        n_layers = getattr(spec.config, "n_layers", 1)
        if spans is None and n_layers % s != 0:
            raise InfeasibleConfig(
                f"{n_layers} layers not divisible by {s} stages — pass "
                "config['spans'] (candidate_configs computes balanced ones)"
            )
        hints = pipeline_hints(spec)
        bkey = spec.hints.get("block_param_key", "blocks")
        loss_fn = task.loss_fn
        common = dict(
            mesh=mesh,
            block_key=bkey,
            embed_fn=hints["embed"],
            block_fn=hints["block"],
            head_fn=hints["head"],
            loss_fn=loss_fn,
            n_microbatches=m,
            remat=bool(config.get("remat", False)),
            stage_spans=spans,
        )

        overlap = bool(config.get("overlap", False))
        if schedule == "1f1b" or overlap:
            # Explicitly staged program: bounded stash (min(M, 2S-1) vs AD's
            # M live microbatch residuals), backward launched C2 ticks behind
            # forward. Bit-identical summed grads vs the staged GPipe
            # ordering (same body jaxpr, same accumulation order). Overlapped
            # GPipe also routes here — only the staged scan can hoist the
            # stage hop above the tick's compute (H=2 double buffering).
            def loss_and_grads(params, batch):
                return staged_pipeline_loss_and_grads(
                    params, batch, schedule=schedule, overlap=overlap,
                    **common
                )
        else:
            def loss_and_grads(params, batch):
                return pipeline_loss_and_grads(params, batch, **common)

        return self.step_fns_from_loss_and_grads(spec.init_fn, task, loss_and_grads)
