"""What a SmallThinker stack (``perf/reference/smallthinker.py``'s ``Arch``:
full and sliding-window attention layers at one q-head count over grouped k/v
heads, every feed-forward top-k routed ReGLU experts of which a share is held,
no gate a head, no shared expert, no dense layer) needs, from shapes alone:
the numerator of ``mfu_smallthinker``.

``required_flops_per_token``: forward + backward of one training token, by
``perf/lib/flops.py``'s rule (recomputation not counted, the head counted):
6 x the parameters in matrices that multiply a token *as multiplied* (each
mixer's q, k, v and output projections; the router; ``top_k x held /
experts`` routed experts a token and layer: 1.5 at 6 of 64 with 16 held; the
held rows of the head) + attention: ``12 H hd`` x the mean keys a query reads
(``flops_laguna.reach`` for a sliding layer, the causal half for a full one).
(``flops_laguna.matmul_params`` counts Laguna's gate a head in every mixer and
a shared expert a layer: it cannot count this model.)

The kernels' operations and bytes are ``flops_laguna.attn_call`` /
``gmm_call``: the same kernels under the same names, read off this ``Arch``.
"""

from __future__ import annotations

from typing import Any, Dict

from perf.lib.flops_laguna import SLIDING, reach


def matmul_params(a: Any) -> Dict[str, float]:
    """Parameters that multiply a token, by part (the embedding's lookup is a
    gather and multiplies nothing; a routed expert multiplies the tokens that
    chose it: ``top_k x held / experts`` experts a token on average)."""
    D, hd, kv, n = a.d_model, a.head_dim, a.n_kv_heads, a.n_layers
    return {"mixers": float(sum(2 * D * h * hd + 2 * D * kv * hd for h in a.heads)),
            "router": float(D * a.experts * n),
            "routed": 3.0 * D * a.d_expert * n * a.top_k * a.held / a.experts,
            "head": float(D * a.vocab_size)}


def attention_flops_per_token(a: Any, seq: int) -> float:
    total = 0.0
    for kind, h in zip(a.kinds, a.heads):
        keys = reach(seq, a.window) / seq if kind == SLIDING else (seq + 1) / 2.0
        total += 12.0 * h * a.head_dim * keys      # 2 products x 2 x 3 (fwd + bwd)
    return total


def required_flops_per_token(a: Any, seq: int) -> float:
    return 6.0 * sum(matmul_params(a).values()) + attention_flops_per_token(a, seq)
