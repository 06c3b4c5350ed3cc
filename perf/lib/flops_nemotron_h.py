"""What a Nemotron-H stack (``perf/reference/nemotron_h.py``'s ``Arch``: layers
that are each one mixer alone -- Mamba-2, LatentMoE, attention -- at a held
share of their heads and experts) needs, from shapes alone: the numerators of
``mfu_nemotron``, ``ssd_roofline`` and ``gmm_latent_roofline``.

``required_flops_per_token``: forward + backward of one training token, by
``perf/lib/flops.py``'s rule (recomputation not counted, the head counted):
6 x the parameters in matrices that multiply a token *as multiplied* (a
Mamba-2 layer's ``in_proj`` and ``out_proj`` at the held heads and its
convolution's taps; an attention layer's q, k, v, o at the held heads; a
LatentMoE layer's router, latent projections and shared expert whole and
``top_k x held / experts`` routed experts a token: 22 x 8 / 512; the held rows
of the head) + causal attention ``6 S H hd`` a token and attention layer + the
recurrence's chunked products for each Mamba-2 layer.

``ssd_call`` / ``gmm_call``: operations and least bytes of one call of a
``saturn_ssd_*`` kernel (the chunked form's products at the published chunk,
``C B^T`` once a group; each operand and the kept states crossing HBM once)
or of a grouped-product kernel over latent rows (``saturn_gmm_*`` at
``d_latent x d_expert``).
"""

from __future__ import annotations

from typing import Any, Dict

MAMBA, ATTENTION, MOE = "mamba2", "attention_only", "latent_moe"


def recurrence_flops_per_token_head(p: int, n: int, chunk: int, per_group: int) -> float:
    """Forward products of the chunked recurrence, per token and head. A
    chunk of C tokens: ``C B^T`` (2 C^2 N, once a group of ``per_group``
    heads), the masked product with the chunk's inputs (2 C^2 P), ``C S^T``
    and the state's update (2 C N P each)."""
    per_chunk = 2.0 * chunk * (chunk * n / per_group + chunk * p + 2 * n * p)
    return per_chunk / chunk


def matmul_params(a: Any) -> Dict[str, float]:
    """Parameters that multiply a token, by part (the embedding's lookup is a
    gather and multiplies nothing; a routed expert multiplies the tokens that
    chose it: ``top_k x held / experts`` experts a token on average)."""
    D = a.d_model
    lanes = a.d_inner + 2 * a.d_bc
    mamba = D * (a.d_inner + lanes + a.ssm_heads) + a.d_inner * D + a.conv_taps * lanes
    q, kv = a.n_heads * a.head_dim, a.n_kv_heads * a.head_dim
    n = {kind: a.kinds.count(kind) for kind in (MAMBA, ATTENTION, MOE)}
    return {"mamba": float(mamba * n[MAMBA]),
            "attention": float((2 * D * q + 2 * D * kv) * n[ATTENTION]),
            "router": float(D * a.experts * n[MOE]),
            "latent": 2.0 * D * a.d_latent * n[MOE],
            "shared": 2.0 * D * a.d_shared * n[MOE],
            "routed": 2.0 * a.d_latent * a.d_expert * n[MOE] * a.top_k * a.held / a.experts,
            "head": float(D * a.vocab_size)}


def required_flops_per_token(a: Any, seq: int) -> float:
    attention = 12.0 * a.n_heads * a.head_dim * (seq + 1) / 2.0 * a.kinds.count(ATTENTION)
    recurrence = 3.0 * a.ssm_heads * recurrence_flops_per_token_head(
        a.ssm_head_dim, a.ssm_state, a.chunk, a.ssm_heads // a.ssm_groups) \
        * a.kinds.count(MAMBA)
    return 6.0 * sum(matmul_params(a).values()) + attention + recurrence


def ssd_call(kernel: str, a: Any, batch: int, seq: int, bytes_per: int = 2) -> Dict[str, float]:
    """One call of a forward kernel of the recurrence (``saturn_ssd_fwd``,
    ``saturn_ssd_fwd_only``): x, B, C in in the step's dtype, the heads' decay
    sums in twice (a column and a row) and o out in float32, once; the
    differentiated call also writes the state every chunk starts from. (A
    backward kernel does not exist yet, and an unknown name is an error, not
    a guess.)"""
    if kernel not in ("saturn_ssd_fwd", "saturn_ssd_fwd_only"):
        raise KeyError(f"no count for kernel {kernel!r}")
    H, G, P, N = a.ssm_heads, a.ssm_groups, a.ssm_head_dim, a.ssm_state
    rows = float(batch * seq)
    moved = rows * ((H * P + 2 * G * N) * bytes_per + H * (P + 2) * 4)
    if kernel == "saturn_ssd_fwd":
        moved += -(-seq // a.chunk) * batch * H * P * N * 4.0
    return {"flops": rows * H * recurrence_flops_per_token_head(P, N, a.chunk, H // G),
            "bytes": moved}


def gmm_call(kernel: str, a: Any, rows: float, bytes_per: int = 2) -> Dict[str, float]:
    """One call of a grouped product over ``rows`` routed latent rows: rows x
    (d_latent x d_expert) either way round; the rows in and out once, the
    held tables once (a table gradient leaves in float32)."""
    if kernel not in ("saturn_gmm_fwd", "saturn_gmm_dx", "saturn_gmm_dw"):
        raise KeyError(f"no count for kernel {kernel!r}")
    L, F = a.d_latent, a.d_expert
    table = a.held * L * F * (4.0 if kernel == "saturn_gmm_dw" else float(bytes_per))
    return {"flops": 2.0 * rows * L * F,
            "bytes": rows * (L + F) * bytes_per + table}
