"""Tensor-parallel executor: Megatron-style sharding over a 2-D (data, model) mesh.

Realizes the reference's declared-but-never-implemented ``MEGATRON`` technique
(``Strategy.py:34``, SURVEY.md §2.3). Column-parallel qkv/mlp-in, row-parallel
attn-out/mlp-out, vocab-sharded embedding; XLA inserts the activation psums
that Megatron's f/g conjugate operators do by hand. The autotune knob is the
(data × model) mesh factorization plus remat, searched best-guess-first.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from jax.sharding import PartitionSpec as P

from saturn_tpu.ops.collective_matmul import (
    zero3_block_rules,
    zero3_loss_and_grads,
)
from saturn_tpu.ops.pipeline import pipeline_hints
from saturn_tpu.parallel import sharding as shr
from saturn_tpu.parallel.spmd_base import SPMDTechnique
from saturn_tpu.core.strategy import Techniques


class TensorParallel(SPMDTechnique):
    name = "tp"
    technique = Techniques.TENSOR
    # wte is vocab-sharded over 'model' (megatron embedding): the fused CE
    # kernel can't consume a vocab shard — keep the GSPMD logits path, which
    # partitions the head matmul + softmax along vocab natively.
    fused_loss_ok = False

    def mesh_spec(self, n_devices, task, config) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
        tp = config.get("tp", min(n_devices, 2))
        return ("data", "model"), (n_devices // tp, tp)

    def param_rules(self, task, config):
        if config.get("overlap"):
            # Weight-gathered lowering: must match the zero3 program's
            # in_specs leaf-for-leaf (blocks sharded over 'model', rest
            # replicated) or the outer jit reshards every step.
            spec = task.get_model()
            return zero3_block_rules(
                block_key=spec.hints.get("block_param_key", "blocks"),
                axis="model",
            )
        # TP rules first; FSDP-over-data fills remaining axes when the grid
        # asks for it (2-D sharding: params split over both model and data).
        if config.get("zero"):
            return shr.compose_rules(
                shr.tensor_parallel_rules("model"), shr.fsdp_rules("data")
            )
        return shr.tensor_parallel_rules("model")

    def batch_spec(self, config) -> P:
        if config.get("overlap"):
            # The weight-gathered lowering replicates compute over 'model'
            # unless the batch shards over it too.
            return P(("data", "model"))
        return P("data")

    def candidate_configs(self, task, n_devices) -> List[Dict[str, Any]]:
        spec = task.get_model()
        n_heads = getattr(spec.config, "n_heads", 1)
        overlap_ok = self._overlap_ok(task, n_devices)
        grid: List[Dict[str, Any]] = []
        tp = 2
        while tp <= n_devices and n_heads % tp == 0:
            grid.append({"tp": tp, "remat": False, "zero": False})
            grid.append({"tp": tp, "remat": True, "zero": True})
            if overlap_ok:
                # Collective-matmul lowering of the same (data, model) mesh
                # (ops/collective_matmul.py): block weights stay sharded
                # over 'model' (memory parity with zero), but instead of
                # GSPMD's activation psums the program gathers each layer's
                # weight shards chunk-by-chunk, layer-ahead, under the
                # previous layer's compute. Profiled as its own grid point.
                grid.append(
                    {"tp": tp, "remat": False, "zero": True, "overlap": True}
                )
                grid.append(
                    {"tp": tp, "remat": True, "zero": True, "overlap": True}
                )
            tp <<= 1
        return self._with_attention_variants(task, grid, n_devices)

    def _overlap_ok(self, task, n_devices: int) -> bool:
        """The zero3 program needs the model's pipeline decomposition and a
        batch that shards over the full (data, model) mesh."""
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
                shard_axis="model",
                batch_axes=("data", "model"),
                prefetch=True,
                remat=bool(config.get("remat", False)),
                passes=spec.stack_passes,
                between_fn=hints.get("between"),
            )

        return self.step_fns_from_loss_and_grads(
            spec.init_fn, task, loss_and_grads
        )
