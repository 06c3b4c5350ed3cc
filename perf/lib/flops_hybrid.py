"""What a hybrid stack (gated-delta-rule layers between full-attention
layers, ``perf/reference/olmo_hybrid.py``'s ``Arch``) needs, from shapes
alone: the numerators of ``mfu_hybrid`` and ``gdn_roofline``.

``required_flops_per_token``: forward + backward of one training token, by
``perf/lib/flops.py``'s rule (recomputation not counted, the head counted):
6 x the parameters in matrices that multiply a token, *as held* (each mixer's
projections over the held heads, the convolution's taps, SwiGLU's three
matrices, the held rows of the head) + 12 S x (held heads x head_dim) for each
full layer's causal-blind scores and values + the chunked rule's products for
each linear layer. The GPT count of ``flops.py`` (``6 (4 d^2 + 2 d ff) L +
12 L S d``) would count four attention layers of width ``d_model`` where one
of the held width runs.

``gdn_call``: operations and least bytes of one call of a ``saturn_gdn_*``
kernel. The yardstick is the algorithm's, whatever implements the layer: the
chunked form's products at chunk 64 and each operand crossing HBM once.
"""

from __future__ import annotations

from typing import Any, Dict

CHUNK = 64
LINEAR, FULL = "linear_attention", "full_attention"


def rule_flops_per_token_head(dk: int, dv: int, chunk: int = CHUNK) -> float:
    """Forward products of the chunked gated delta rule, per token and head.
    A chunk of C tokens: K K^T and Q K^T (2 C^2 dk each), the triangular
    transform applied to the chunk's keys (2 C^2 dk) and values (2 C^2 dv),
    the intra-chunk product (2 C^2 dv), and three products with the (dk, dv)
    state: the pseudo-values' W S, Q S and the update K^T U (2 C dk dv each).
    Building the transform (the inverse of a C x C triangle) is not counted:
    how it is built is an implementation's affair."""
    per_chunk = 2.0 * chunk * (chunk * (3 * dk + 2 * dv) + 3 * dk * dv)
    return per_chunk / chunk


def matmul_params(a: Any) -> Dict[str, int]:
    """Parameters that multiply a token, by part (the embedding's lookup is a
    gather and multiplies nothing)."""
    D, H = a.d_model, a.n_heads
    linear = (D * H * (2 * a.key_dim + 2 * a.value_dim + 2)      # q k v gate a b
              + H * a.value_dim * D                              # o
              + a.conv_taps * H * (2 * a.key_dim + a.value_dim))  # the taps
    full = 4 * D * H * a.head_dim
    return {"linear_mixers": linear * a.kinds.count(LINEAR),
            "full_mixers": full * a.kinds.count(FULL),
            "swiglu": 3 * D * a.d_inner * a.n_layers,
            "head": D * a.vocab_size}


def required_flops_per_token(a: Any, seq: int) -> float:
    attention = 12.0 * seq * a.n_heads * a.head_dim * a.kinds.count(FULL)
    rule = 3.0 * a.n_heads * rule_flops_per_token_head(a.key_dim, a.value_dim) \
        * a.kinds.count(LINEAR)
    return 6.0 * sum(matmul_params(a).values()) + attention + rule


def gdn_call(kernel: str, batch: int, n_heads: int, seq: int, dk: int, dv: int,
             bytes_per: int = 2) -> Dict[str, float]:
    """One call of a forward kernel of the rule (``saturn_gdn_fwd``,
    ``saturn_gdn_fwd_only``): q, k, v in in the step's dtype, o out and the
    two gates in in float32, once. (A backward kernel would take the same in
    with do and write five gradients; none exists yet, and an unknown name
    is an error, not a guess.)"""
    if kernel not in ("saturn_gdn_fwd", "saturn_gdn_fwd_only"):
        raise KeyError(f"no count for kernel {kernel!r}")
    rows = batch * n_heads * seq
    return {"flops": rows * rule_flops_per_token_head(dk, dv),
            "bytes": float(rows * ((2 * dk + dv) * bytes_per + (dv + 2) * 4))}
