"""Sequence-parallel executor: ring attention over a ('data', 'seq') mesh.

Long-context capability the reference does not have (SURVEY.md §5) — its only
length levers were activation checkpointing and offload. Delivered as a
library technique through the same two-method plugin contract
(``Technique.py:24``), so the trial runner profiles it and the MILP can pick
it per task like any other technique.

Each device holds a (B/dp, T/sp) token chunk; attention rotates k/v blocks
around the ``seq`` ring (``ops/ring.py``), so the T×T score matrix never
materializes on one chip — activation memory scales 1/sp², enabling context
lengths that are infeasible for every dense technique. The autotune knob is
the (data × seq) mesh factorization plus remat.

Assumes the next-token CE objective (the label for a chunk boundary comes
from the neighbor shard): the technique declares itself infeasible for tasks
with any other loss, which the trial runner handles like every infeasible
(task × technique) pair (``PerformanceEvaluator.py:110``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from jax.sharding import PartitionSpec as P

from saturn_tpu.models.loss import pretraining_loss
from saturn_tpu.ops.ring import ring_loss_and_grads
from saturn_tpu.parallel import sharding as shr
from saturn_tpu.parallel.spmd_base import SPMDTechnique
from saturn_tpu.core.strategy import Techniques
from saturn_tpu.core.technique import InfeasibleConfig


class RingSequenceParallel(SPMDTechnique):
    name = "ring"
    technique = Techniques.RING

    def mesh_spec(self, n_devices, task, config) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
        sp = config.get("sp", 2)  # same default as _model_overrides
        if n_devices % sp != 0:
            raise InfeasibleConfig(f"{n_devices} devices not divisible by sp={sp}")
        # 'seq' minor: ring neighbors are adjacent devices on the ICI ring.
        return ("data", "seq"), (n_devices // sp, sp)

    def batch_spec(self, config) -> P:
        return P("data", "seq")

    def param_rules(self, task, config):
        return shr.replicated_rules

    def candidate_configs(self, task, n_devices) -> List[Dict[str, Any]]:
        if task.loss_fn is not pretraining_loss:
            return []  # boundary-label exchange assumes next-token CE
        spec = task.get_model()
        if not spec.hints.get("seq_parallel"):
            return []
        if self._aux_incompatible(spec):
            return []  # shard_map loss path would drop the model's aux loss
        ds = task.get_dataset()
        T = ds.context_length  # the dimension actually sharded over 'seq'
        grid: List[Dict[str, Any]] = []
        sp = 2
        while sp <= n_devices and T % sp == 0:
            if ds.batch_size % (n_devices // sp) == 0:
                # overlap = double-buffered k/v hop (ops/ring.py): profiled
                # as its own grid point so realized cost, not faith, picks.
                grid.append({"sp": sp, "remat": False})
                grid.append({"sp": sp, "remat": False, "overlap": True})
                grid.append({"sp": sp, "remat": True})
                grid.append({"sp": sp, "remat": True, "overlap": True})
            sp <<= 1
        return grid

    def _model_overrides(self, config: Dict[str, Any]) -> Dict[str, Any]:
        out = super()._model_overrides(config)
        out["seq_axis"] = "seq"
        out["seq_axis_size"] = config.get("sp", 2)
        out["seq_overlap"] = bool(config.get("overlap", False))
        return out

    def make_step_fns(self, spec, task, config, mesh, ds):
        self._require_no_aux(spec)  # shard_map loss path would drop an aux loss
        # init runs OUTSIDE shard_map: use a dense-attention twin (identical
        # param tree — seq parallelism adds no params) for shape/init.
        plain = dict(self._model_overrides(config))
        plain["seq_axis"] = None
        plain["seq_axis_size"] = 1
        spec_plain = task.get_model(**plain)

        def loss_and_grads(params, batch):
            return ring_loss_and_grads(
                params, batch, mesh=mesh, apply_fn=spec.apply_fn
            )

        return self.step_fns_from_loss_and_grads(
            spec_plain.init_fn, task, loss_and_grads
        )
