"""What the algorithm needs, from shapes alone: the yardstick's numerators.

``required_flops_per_token``: forward + backward of one training token, the
operations the model *requires*. Recomputation (remat) is not counted, the
output head is. Rule: 6 x (parameters in matrices that multiply a token) +
12 x L x S x d for causal-blind attention scores and values (the usual
"6N + 12LSd" accounting; causal masking halves the useful part, and the
convention counts it whole, as the kernels below do not).

``flash_call`` / ``ce_call``: operations and least bytes of one call of a
Pallas kernel, by the kernel's name in the trace, for its share of the
roofline. Operations count what the kernel must do (causal attention
skips the blocks above the diagonal, so half), bytes count each operand and
result crossing HBM once.
"""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(d_model: int, n_layers: int, d_ff: int, vocab: int) -> int:
    """Parameters that multiply a token: q, k, v, o (4 d^2), the two MLP
    matrices (2 d ff) per layer, and the vocabulary head (d V; tied to the
    embedding, whose lookup is a gather and costs no multiply)."""
    return n_layers * (4 * d_model * d_model + 2 * d_model * d_ff) + d_model * vocab


def required_flops_per_token(d_model: int, n_layers: int, d_ff: int,
                             vocab: int, seq: int) -> float:
    return (6.0 * matmul_params(d_model, n_layers, d_ff, vocab)
            + 12.0 * n_layers * seq * d_model)


def total_params(d_model: int, n_layers: int, d_ff: int, vocab: int,
                 n_positions: int = 0, ln_per_block: int = 2) -> int:
    """Every trained scalar of the package's tree (biases on every Dense)."""
    per_layer = (4 * d_model * d_model + 4 * d_model          # qkv + attn_out
                 + 2 * d_model * d_ff + d_ff + d_model        # mlp
                 + ln_per_block * 2 * d_model)
    return (n_layers * per_layer + vocab * d_model + n_positions * d_model
            + 2 * d_model)


#: matmuls of (S x hd) x (hd x S) size per call, by kernel name. The forward
#: does QK^T and PV; dq recomputes the scores, then dP and dQ; dkv recomputes
#: the scores, then dP, dV and dK. The recomputation is the algorithm's own
#: (flash attention stores no S x S matrix), so it counts.
_FLASH_MATMULS = {"saturn_flash_fwd": 2, "saturn_flash_dq": 3, "saturn_flash_dkv": 4}
#: (B, H, S, hd) tensors crossing HBM per call: q k v -> o; q k v do o -> dq;
#: q k v do -> dk dv (the (B, H, S) row statistics are left out).
_FLASH_TENSORS = {"saturn_flash_fwd": 4, "saturn_flash_dq": 6, "saturn_flash_dkv": 6}


def flash_call(kernel: str, batch: int, n_heads: int, seq: int, head_dim: int,
               causal: bool = True, bytes_per: int = 2) -> Dict[str, float]:
    """One call of a flash-attention kernel. Causal attention needs the
    blocks on and under the diagonal only: half the S^2 products."""
    one = 2.0 * batch * n_heads * seq * seq * head_dim * (0.5 if causal else 1.0)
    tensor = float(batch * n_heads * seq * head_dim * bytes_per)
    return {"flops": _FLASH_MATMULS[kernel] * one,
            "bytes": _FLASH_TENSORS[kernel] * tensor}


def ce_call(kernel: str, tokens: int, d_model: int, vocab: int,
            bytes_per: int = 2) -> Dict[str, float]:
    """One call of a fused head + cross-entropy kernel over ``tokens`` rows.
    Each of fwd (x W^T), dx (dS W) and dw (dS^T x) needs one N x d x V
    matmul; what a kernel recomputes beyond that (the scores again, in
    recompute mode) is its own affair and is not counted. Bytes: the operands
    in and the result out, once (dW leaves in float32)."""
    mm = 2.0 * tokens * d_model * vocab
    x_b = float(tokens * d_model * bytes_per)
    w_b = float(vocab * d_model * bytes_per)
    nbytes = {"saturn_ce_fwd": x_b + w_b,
              "saturn_ce_dx": x_b + w_b + x_b,
              "saturn_ce_dw": x_b + x_b + vocab * d_model * 4.0}[kernel]
    return {"flops": mm, "bytes": nbytes}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: Dict[str, Any]) -> Dict[str, Any]:
    """Least time the chip could take over the time it took, in percent, and
    which of the two bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return {"share_pct": 100.0 * least / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "least_s": least}
