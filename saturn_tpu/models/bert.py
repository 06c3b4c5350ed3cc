"""BERT-class encoder family: bidirectional transformer + masked-LM objective.

Second model family alongside GPT-2/GPT-J (the target workload class is a
"GPT-2/BERT-class sweep", BASELINE.md). Reuses the scanned GPT-2 stack
(``models/gpt2.py``) with ``causal=False`` — parallelism techniques see the
identical param-tree structure, so dp/fsdp/tp/pp/offload all work unchanged;
sequence-parallel techniques correctly report infeasible (their
boundary-label loss assumes causal next-token training).

Masking is *static-positional* (every ``MASK_STRIDE``-th token): the mask
derives from position alone, so the jitted train step needs no RNG plumbing
or dynamic shapes, and the loss and forward agree on exactly which positions
are masked. This trades BERT's random 15% masking for determinism; the
compute/communication profile — what the profiler and solver care about —
is identical.
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp
import optax

from saturn_tpu.core.modelspec import ModelSpec
from saturn_tpu.models import gpt2

MASK_STRIDE = 7   # ~14% of positions masked, close to BERT's 15%
MASK_OFFSET = 3

BERT_PRESETS: Dict[str, Dict[str, Any]] = {
    "bert-test-tiny": dict(
        d_model=64, n_layers=2, n_heads=4, vocab_size=256, seq_len=64,
    ),
    "bert-base": dict(d_model=768, n_layers=12, n_heads=12),
    "bert-large": dict(d_model=1024, n_layers=24, n_heads=16),
}

# Encoder presets live in the shared preset table so config_for/build_gpt2
# machinery (validation, overrides) applies unchanged.
for _name, _kw in BERT_PRESETS.items():
    gpt2.PRESETS.setdefault(_name, dict(_kw, causal=False))


def _mask(T: int):
    return (jnp.arange(T) % MASK_STRIDE) == MASK_OFFSET


def mlm_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Mean cross-entropy at the masked positions vs the ORIGINAL tokens.

    Pairs with :func:`build_bert`, whose forward replaces the same positions
    with the [MASK] id — ``tokens`` is the unmasked batch the dataloader
    serves, exactly like the causal ``pretraining_loss`` contract.
    """
    B, T = tokens.shape
    m = _mask(T)[None, :].astype(jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, tokens)
    return (ce * m).sum() / (m.sum() * B)


# Fused-head tag (see models/loss.py): the MLM objective is ignore-index CE
# over the masked positions — exactly what ops/ce.py computes when unmasked
# positions carry label -1.
mlm_loss.supports_fused_head = "mlm"


def build_bert(name: str = "bert-base", **overrides) -> ModelSpec:
    """Encoder ModelSpec for ``Task(get_model=...)``; train with :func:`mlm_loss`.

    The top vocab id serves as [MASK]. That id must never occur in the data —
    otherwise unmasked occurrences are indistinguishable from [MASK] and
    masked positions whose label is the top id leak. The data pipeline
    enforces this: pair BERT tasks with ``make_lm_dataset(...,
    reserved_ids=1)``, which keeps ids in ``[0, vocab_size - 1)`` on every
    path (synthetic generation, word vocab cap, byte-tokenizer validation).
    The [MASK] substitution is applied inside every forward entry point —
    including the pipeline-stage ``embed`` hint, so pp/offload-streaming
    train the same objective as dp/fsdp/tp.
    """
    if name not in BERT_PRESETS:
        raise KeyError(f"unknown BERT preset {name!r}; options: {list(BERT_PRESETS)}")
    spec = gpt2.build_gpt2(name, **overrides)
    cfg = spec.config
    mask_id = cfg.vocab_size - 1

    def mask_tokens(tokens):
        return jnp.where(_mask(tokens.shape[-1])[None, :], mask_id, tokens)

    inner_apply = spec.apply_fn

    def apply_fn(params, tokens):
        return inner_apply(params, mask_tokens(tokens))

    hints = dict(spec.hints)
    if "pipeline" in hints:
        pipe = dict(hints["pipeline"])
        inner_embed = pipe["embed"]
        pipe["embed"] = lambda other, tokens: inner_embed(other, mask_tokens(tokens))
        hints["pipeline"] = pipe

    fused_loss_fn = fused_loss_parts_fn = None
    if spec.hidden_fn is not None:
        # Fused head+loss for MLM (ops/ce.py): hidden states of the MASKED
        # input against the original tokens, unmasked positions ignored via
        # label -1 — the same mean-over-masked objective as mlm_loss.
        def _fused(params, tokens, reduction):
            from saturn_tpu.ops.ce import fused_linear_cross_entropy, stash_of

            x = spec.hidden_fn(params, mask_tokens(tokens))
            labels = jnp.where(
                _mask(tokens.shape[-1])[None, :],
                tokens.astype(jnp.int32), -1,
            )
            return fused_linear_cross_entropy(
                x, params["wte"], labels, reduction=reduction,
                stash=stash_of(cfg.ce_mode),
            )

        def fused_loss_fn(params, tokens):
            return _fused(params, tokens, "mean")

        def fused_loss_parts_fn(params, tokens):
            return _fused(params, tokens, "sum_count")

    return ModelSpec(
        init_fn=spec.init_fn,
        apply_fn=apply_fn,
        config=cfg,
        hints=hints,
        apply_with_aux_fn=None,
        fused_loss_fn=fused_loss_fn,
        fused_loss_parts_fn=fused_loss_parts_fn,
        fused_loss_objective="mlm" if fused_loss_fn else None,
        hidden_fn=spec.hidden_fn,
    )
