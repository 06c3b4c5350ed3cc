"""Data-parallel executor: batch-sharded pjit over a 1-D ``data`` mesh.

Replaces the reference's DDP UDP (``examples/wikitext103/executors/DDP.py``):
instead of per-GPU processes + NCCL allreduce, the batch is sharded over the
``data`` axis and XLA emits the gradient psum over ICI. Unlike the reference's
DDP — whose ``search`` returned None and could never be selected
(``DDP.py:72``, SURVEY.md §2 C17) — this one is a first-class citizen.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from saturn_tpu.parallel import sharding as shr
from saturn_tpu.parallel.spmd_base import SPMDTechnique
from saturn_tpu.core.strategy import Techniques


class DataParallel(SPMDTechnique):
    name = "dp"
    technique = Techniques.DP
    # Params replicated + batch sharded over 'data': the fused head+loss
    # runs on multi-chip blocks too, via the shard_map sum/count wrapper
    # (spmd_base.step_fns_from_forward).
    fused_loss_shardable = True

    def mesh_spec(self, n_devices, task, config) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
        return ("data",), (n_devices,)

    def param_rules(self, task, config):
        return shr.replicated_rules

    def candidate_configs(self, task, n_devices) -> List[Dict[str, Any]]:
        # remat off and on, crossed with flash attention on TPU: ``search``
        # times every point that fits and keeps the fastest, so this order
        # only breaks a tie (remat off wins one); which point is *prepared*
        # first is ``search``'s to say (the remat points, PR 37).
        return self._with_attention_variants(
            task, [{"remat": False}, {"remat": True}], n_devices
        )
