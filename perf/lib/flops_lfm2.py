"""What an LFM2 stack (``perf/reference/lfm2.py``'s ``Arch``: doubly gated
short-convolution layers and grouped-query attention layers, a leading dense
SwiGLU, then top-k routed SwiGLU experts of which a share is held, no shared
expert, the head tied to the embedding) needs, from shapes alone: the
numerator of ``mfu_lfm2``.

``required_flops_per_token``: forward + backward of one training token, by
``perf/lib/flops.py``'s rule (recomputation not counted, the head counted):
6 x the parameters in matrices that multiply a token *as multiplied* (a conv
mixer's input and output projections ``4 d^2``; an attention mixer's q, k, v
and output projections; the dense SwiGLU of the leading layers; the routers;
``top_k x held / experts`` routed experts a token and routed layer: 1 at 4 of
32 with 8 held; the held rows of the tied head) + attention ``12 H hd`` x the
causal half of the keys. The convolution's taps and the gates are elementwise
(``3 x taps + 2`` operations a channel and token) and are not counted, as no
norm or activation is.
(``flops_smallthinker.matmul_params`` counts q, k, v, o in every mixer and no
dense layer: it cannot count this model.)

The kernels' operations and bytes are ``flops_laguna.attn_call`` /
``gmm_call`` and ``flops.flash_call`` / ``ce_call``: the same kernels under the
same names, read off this ``Arch``.
"""

from __future__ import annotations

from typing import Any, Dict

CONV, FULL = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"


def matmul_params(a: Any) -> Dict[str, float]:
    """Parameters that multiply a token, by part (the embedding's lookup is a
    gather and multiplies nothing; a routed expert multiplies the tokens that
    chose it: ``top_k x held / experts`` experts a token on average)."""
    D, hd = a.d_model, a.head_dim
    n_conv, n_full = a.kinds.count(CONV), a.kinds.count(FULL)
    n_sparse = sum(f == SPARSE for f in a.ffs)
    return {"conv_mixers": 4.0 * D * D * n_conv,
            "attention_mixers": float(2 * D * a.n_heads * hd
                                      + 2 * D * a.n_kv_heads * hd) * n_full,
            "dense_ff": 3.0 * D * a.d_ff * sum(f == DENSE for f in a.ffs),
            "router": float(D * a.experts * n_sparse),
            "routed": 3.0 * D * a.d_expert * n_sparse * a.top_k * a.held / a.experts,
            "head": float(D * a.vocab_size)}


def attention_flops_per_token(a: Any, seq: int) -> float:
    """2 products x 2 x 3 (forward + backward) a q head's lane and key, over
    the causal half of the keys, a full-attention layer."""
    return 12.0 * a.n_heads * a.head_dim * (seq + 1) / 2.0 * a.kinds.count(FULL)


def required_flops_per_token(a: Any, seq: int) -> float:
    return 6.0 * sum(matmul_params(a).values()) + attention_flops_per_token(a, seq)
