"""Expert-parallel executor: shard the expert axis over the ICI mesh.

Capability extension beyond the reference (SURVEY.md §2.3: "EP (expert) ...
absent"), delivered exactly the way the reference delivers every parallelism
— as a technique class behind the plugin interface (``Technique.py:24``).

Mesh is 2-D ``(data, expert)``. The MoE weight tables carry an explicit
expert axis ((layers, experts, ...) after the layer scan — ``models/gpt2.py``
``_moe_mlp``), which is sharded over ``expert``; dense trunk params follow
ZeRO-style sharding over ``data``. With the (experts, capacity, d_model)
dispatch intermediate sharded on its expert dim, XLA lowers the
dispatch/combine einsums of ``ops/moe.py`` to all-to-alls over ICI — the
GSPMD equivalent of hand-written MoE a2a kernels.

The train step adds the model's sown load-balance aux loss via
``ModelSpec.apply_with_aux_fn``.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Tuple

from jax.sharding import PartitionSpec as P

from saturn_tpu.parallel import sharding as shr
from saturn_tpu.parallel.spmd_base import SPMDTechnique
from saturn_tpu.core.strategy import Techniques
from saturn_tpu.core.technique import InfeasibleConfig

_EXPERT_PARAM = re.compile(r"(^|/)(we_in|we_out|be_in|be_out)$")


def expert_rules(axis: str, n_experts: int):
    """Shard the expert dim of MoE tables; router stays replicated.

    The expert dim is positional, not size-matched: dim 1 under the layer
    scan ((n_layers, E, ...), ``models/gpt2.py`` ``_moe_mlp``), dim 0 for an
    unscanned table. Size-matching would shard the scan dim whenever
    n_layers == n_experts.
    """

    def rules(path: str, shape: Tuple[int, ...], mesh_axes) -> P:
        n_shard = mesh_axes[axis]
        spec = [None] * len(shape)
        if _EXPERT_PARAM.search(path):
            dim = 1 if len(shape) >= 2 and shape[1] == n_experts else 0
            if shape[dim] == n_experts and n_experts % n_shard == 0:
                spec[dim] = axis
        return P(*spec)

    return rules


class ExpertParallel(SPMDTechnique):
    name = "ep"
    technique = Techniques.EXPERT

    def mesh_spec(self, n_devices, task, config) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
        ep = config.get("ep", min(n_devices, 2))
        if n_devices % ep != 0:
            raise InfeasibleConfig(f"{n_devices} devices not divisible by ep={ep}")
        return ("data", "expert"), (n_devices // ep, ep)

    def _n_experts(self, task) -> int:
        moe = task.get_model().hints.get("moe")
        return moe["n_experts"] if moe else 0

    def param_rules(self, task, config):
        rules = [expert_rules("expert", self._n_experts(task))]
        if config.get("zero"):
            rules.append(shr.fsdp_rules("data"))
        return shr.compose_rules(*rules)

    def make_step_fns(self, spec, task, config, mesh, ds):
        routed = spec.hints.get("routed")
        if routed:
            # The one place (``execute`` on a hand-made strategy comes here
            # too). A routed layer (ops/moe.py::routed_experts) computes the
            # part of its result that the experts held by *this* program
            # give; dividing the held tables over an ``expert`` axis needs the
            # exchange of token rows between the shares (an all-to-all each
            # way around the grouped product), which is not built
            # (ROADMAP.md, Reach).
            raise InfeasibleConfig(
                f"ep: the model's routed layer holds {routed['held']} of "
                f"{routed['experts']} experts as one share and computes only "
                f"their part; the exchange of tokens between shares on an "
                f"expert axis is not built")
        return super().make_step_fns(spec, task, config, mesh, ds)

    def candidate_configs(self, task, n_devices) -> List[Dict[str, Any]]:
        E = self._n_experts(task)
        if not E and task.get_model().hints.get("routed") and n_devices >= 2:
            # one grid point, refused where its step is built: the reason
            # lands on the ``trial.config`` span like pp's
            return [{"ep": 2, "remat": False, "zero": False}]
        if not E:
            return []  # dense model: EP infeasible, search returns (None, None)
        # No custom train step: the aux load-balance loss is added by the
        # shared scaffold (step_fns_from_forward prefers apply_with_aux_fn),
        # so EP's objective matches dp/fsdp/tp exactly.
        grid: List[Dict[str, Any]] = []
        ep = 2
        while ep <= n_devices and E % ep == 0:
            if n_devices % ep == 0:
                grid.append({"ep": ep, "remat": False, "zero": False})
                grid.append({"ep": ep, "remat": True, "zero": True})
            ep <<= 1
        return grid
