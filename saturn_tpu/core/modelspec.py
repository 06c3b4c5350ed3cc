"""ModelSpec: the functional model contract techniques consume.

The reference's ``Task.get_model`` returned an ``nn.Sequential`` torch module
(``GPTJ.py:502-526`` flattens GPT-J into a Sequential precisely so GPipe /
OffloadModel can partition it). The TPU-native analog is a *functional* spec:
pure ``init``/``apply`` functions plus a config that exposes the structure
techniques need (layer count for pipeline balancing, hints for remat and
tensor-parallel rules). Params are a plain pytree, so every technique shards
the same arrays with its own ``PartitionSpec`` rules — no wrapper classes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple


@dataclass
class ModelSpec:
    """Functional model bundle returned by a task's ``get_model`` factory.

    - ``init_fn(rng) -> params``: build (host or device) params.
    - ``apply_fn(params, inputs) -> logits``: pure forward pass, jit-safe.
    - ``abstract_init() -> params_shapes``: ``jax.eval_shape`` of ``init_fn`` —
      lets the trial runner do memory analysis without materializing weights
      (honoring the reference's lazy-instantiation rule, ``Task.py:92-97``).
    - ``config``: model hyperparams; must expose ``n_layers`` and example input
      shapes via ``example_inputs`` for tracing.
    - ``hints``: free-form dict mirroring the reference's transformer hints
      (``Task.py:121-124``), e.g. ``{"block_param_key": "blocks"}`` telling
      pipeline/FSDP executors where the scanned layer stack lives.
    """

    init_fn: Callable[[Any], Any]
    apply_fn: Callable[[Any, Any], Any]
    config: Any
    hints: Dict[str, Any] = field(default_factory=dict)
    # Optional: ``(params, inputs) -> (logits, aux_loss)`` for models with an
    # auxiliary training loss (e.g. MoE load balancing); techniques that know
    # about it (parallel/ep.py) add ``aux_loss`` to the objective, everything
    # else uses the plain ``apply_fn``.
    apply_with_aux_fn: Optional[Callable[[Any, Any], Tuple[Any, Any]]] = None
    # Optional: ``(params, inputs) -> loss`` computing the model's STANDARD
    # training objective end-to-end with a fused head+loss (ops/ce.py — no
    # (B,T,V) logits tensor). Executors use it in place of
    # ``loss_fn(apply_fn(...))`` only when the task's loss_fn carries a
    # ``supports_fused_head`` tag equal to ``fused_loss_objective`` — the
    # tag pairing guarantees the fused function computes exactly the task's
    # loss (custom/mismatched losses always get the logits path).
    fused_loss_fn: Optional[Callable[[Any, Any], Any]] = None
    # Same objective as ``(loss_sum, valid_count)`` — for sharded execution
    # (the data-parallel shard_map wrapper psums both parts globally before
    # dividing; per-shard means would misweight uneven mask counts).
    fused_loss_parts_fn: Optional[Callable[[Any, Any], Any]] = None
    fused_loss_objective: Optional[str] = None
    # Optional: ``(params, inputs) -> final hidden states`` (pre-head
    # forward) — lets wrappers (models/bert.py) build their own fused
    # objectives on top of this model's trunk.
    hidden_fn: Optional[Callable[[Any, Any], Any]] = None
    # Optional: ``fused_loss_fn``'s loss with the step's counters beside it,
    # ``(params, inputs) -> (loss, {name: scalar})`` (a routed-expert layer's
    # pairs, fullest expert, second path). A technique that takes the fused
    # loss on one device differentiates this instead (``has_aux``); the
    # counters then ride the step's loss output to the ``task_interval``
    # event and are read back with the losses.
    fused_loss_stats_fn: Optional[Callable[[Any, Any], Any]] = None

    @property
    def stack_passes(self) -> int:
        """How many times a token passes the block stack: the ``passes`` of
        ``hints["pipeline"]`` (a looped model), 1 for every model that says
        nothing."""
        return int((self.hints.get("pipeline") or {}).get("passes", 1))

    @property
    def stack_layers(self) -> Optional[int]:
        """Layers the block stack holds, of every kind (each applied
        ``stack_passes`` times a token), if the model says."""
        n = self.hints.get("n_layers", getattr(self.config, "n_layers", None))
        return None if n is None else int(n)

    @property
    def stack_kinds(self) -> Optional[Dict[str, int]]:
        """Layers of each kind in one scanned unit (a period) of a stack of
        several block kinds, e.g. ``{"linear_attention": 3,
        "full_attention": 1}``: ``hints["stack_kinds"]``. None for a stack
        of one kind. ``stack_layers`` counts the layers of every kind; the
        scan runs ``stack_layers / sum(stack_kinds.values())`` periods, and
        ``hints["pipeline"]["block"]`` is one period."""
        kinds = self.hints.get("stack_kinds")
        return None if not kinds else {str(k): int(v) for k, v in kinds.items()}

    @property
    def stack_lead(self) -> Optional[Dict[str, int]]:
        """Layers before the scanned periods, by kind, e.g.
        ``{"full_attention_dense": 1}`` or ``{"conv_dense": 1}`` (the mixer's
        kind before the dense MLP): ``hints["stack_lead"]``; they are in
        ``stack_layers``, not in a period, and run inside
        ``hints["pipeline"]["embed"]``. None where the stack has none."""
        lead = self.hints.get("stack_lead")
        return None if not lead else {str(k): int(v) for k, v in lead.items()}

    def abstract_init(self):
        import jax

        return jax.eval_shape(self.init_fn, jax.random.PRNGKey(0))
