"""FSDP executor: GSPMD fully-sharded params over the ``data`` axis.

Replaces the reference's torch-FSDP UDP (``FSDP.py:57-245``). Where torch FSDP
wraps modules and manually all-gathers flat params, here every param's largest
dim is sharded over ``data`` (ZeRO-3) and XLA emits the all-gather before use
and reduce-scatter on grads. The autotune grid mirrors the reference's
{activation checkpointing} × {CPU offload} search (``FSDP.py:72-78``): remat
toggles block rematerialization, offload moves persistent state to host
memory ('pinned_host') where the platform supports it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from saturn_tpu.ops.collective_matmul import (
    zero3_block_rules,
    zero3_loss_and_grads,
)
from saturn_tpu.ops.pipeline import pipeline_hints
from saturn_tpu.parallel import sharding as shr
from saturn_tpu.parallel.spmd_base import SPMDTechnique
from saturn_tpu.core.strategy import Techniques


def host_offload_supported() -> bool:
    import jax

    try:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            # The CPU backend advertises pinned_host but its SPMD
            # partitioner rejects device-placement annotations (RET_CHECK
            # "Side-effect HLO must have sharding"); restrict real
            # offloading to TPU, where XLA host offload is production-grade.
            return False
        kinds = {m.kind for m in dev.addressable_memories()}
        return "pinned_host" in kinds
    except Exception:
        return False


class FSDP(SPMDTechnique):
    name = "fsdp"
    technique = Techniques.FSDP

    def mesh_spec(self, n_devices, task, config) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
        return ("data",), (n_devices,)

    def param_rules(self, task, config):
        if config.get("overlap"):
            # Must match the zero3 program's in_specs leaf-for-leaf, or the
            # outer jit reshards at every shard_map boundary.
            spec = task.get_model()
            return zero3_block_rules(
                block_key=spec.hints.get("block_param_key", "blocks"),
                axis="data",
            )
        return shr.fsdp_rules(axis="data")

    def param_memory_kind(self, config) -> Optional[str]:
        return "pinned_host" if config.get("offload") else None

    def candidate_configs(self, task, n_devices) -> List[Dict[str, Any]]:
        grid: List[Dict[str, Any]] = [
            {"remat": False, "offload": False},
            {"remat": True, "offload": False},
        ]
        if host_offload_supported() and n_devices == 1:
            # One-chip blocks only: sharded over a multi-chip block the
            # pinned-host state needs a lane-sliced async update, which the
            # v5e compiler refuses ("Lane slice updating is not supported in
            # async dynamic update slice yet" — compiled for a described
            # 2x2, PR 24). Offering the points there would make every
            # multi-chip sweep on a TPU report errors.
            grid += [
                {"remat": True, "offload": True},
                {"remat": False, "offload": True},
            ]
        if self._overlap_ok(task, n_devices):
            # ZeRO-3 prefetch (ops/collective_matmul.py): layer k+1's shard
            # gather rides under layer k's compute. Own grid points — the
            # trial runner times overlapped vs serial and realized cost
            # picks; bit-identical grads either way.
            grid += [
                {"remat": False, "offload": False, "overlap": True},
                {"remat": True, "offload": False, "overlap": True},
            ]
        return self._with_attention_variants(task, grid, n_devices)

    def _overlap_ok(self, task, n_devices: int) -> bool:
        """The explicit zero3 program needs the model's pipeline
        decomposition (scanned stack) and an evenly sharded batch."""
        try:
            spec = task.get_model()
            ds = task.get_dataset()
        except Exception:
            return False
        if "pipeline" not in spec.hints or self._aux_incompatible(spec):
            return False
        return ds.batch_size % n_devices == 0

    def make_step_fns(self, spec, task, config, mesh, ds):
        if not config.get("overlap"):
            return super().make_step_fns(spec, task, config, mesh, ds)
        self._require_no_aux(spec)  # shard_map loss path would drop aux loss
        hints = pipeline_hints(spec)
        bkey = spec.hints.get("block_param_key", "blocks")

        def loss_and_grads(params, batch):
            return zero3_loss_and_grads(
                params, batch,
                mesh=mesh,
                embed_fn=hints["embed"],
                block_fn=hints["block"],
                head_fn=hints["head"],
                loss_fn=task.loss_fn,
                block_key=bkey,
                shard_axis="data",
                batch_axes=("data",),
                prefetch=True,
                remat=bool(config.get("remat", False)),
                passes=spec.stack_passes,
                between_fn=hints.get("between"),
            )

        return self.step_fns_from_loss_and_grads(
            spec.init_fn, task, loss_and_grads
        )
