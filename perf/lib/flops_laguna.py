"""What a Laguna stack (``perf/reference/laguna.py``'s ``Arch``: a leading
dense layer, sliding-window and full-attention layers at their own q-head
counts over shared k/v heads, a shared expert beside top-k routed experts of
which a share is held) needs, from shapes alone: the numerators of
``mfu_laguna``, ``attn_mixed_roofline`` and ``gmm_roofline``.

``required_flops_per_token``: forward + backward of one training token, by
``perf/lib/flops.py``'s rule (recomputation not counted, the head counted):
6 x the parameters in matrices that multiply a token *as multiplied* (each
mixer at its own head count; the leading layer's SwiGLU; router, shared expert
and ``top_k x held / experts`` routed experts a token and routed layer: 1 at 8
of 256 with 32 held; the held rows of the head) + attention: ``6 S H hd`` a
token for a full layer (the causal half of the scores and values, forward and
backward) and ``6 H hd mean_i min(i + 1, window)`` for a sliding one. The GPT
count of ``flops.py`` would count five equal layers of ``4 d^2``.

``attn_call`` / ``gmm_call``: operations and least bytes of one call of an
attention kernel (``saturn_flash_*``: a full layer's; ``saturn_swa_*``: a
sliding layer's) or of a grouped-product kernel (``saturn_gmm_*``). The
yardstick is the algorithm's: the causal (windowed) half of the products, q-
side tensors at the layer's q heads and k/v-side tensors at the k/v heads
crossing HBM once; a grouped product over the rows really routed with the held
tables crossing HBM once.
"""

from __future__ import annotations

from typing import Any, Dict

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def reach(seq: int, window: int) -> float:
    """sum_i min(i + 1, window): keys the queries of one sequence read."""
    w = min(window, seq)
    return w * (w + 1) / 2.0 + (seq - w) * float(w)


def matmul_params(a: Any) -> Dict[str, float]:
    """Parameters that multiply a token, by part (the embedding's lookup is a
    gather and multiplies nothing; a routed expert multiplies the tokens that
    chose it: ``top_k x held / experts`` experts a token on average)."""
    D, hd, kv = a.d_model, a.head_dim, a.n_kv_heads
    mixers = sum(D * h * hd * 2 + 2 * D * kv * hd + D * h for h in a.heads)
    n_sparse = sum(f == SPARSE for f in a.ffs)
    return {"mixers": float(mixers),
            "dense_ff": 3.0 * D * a.d_dense * sum(f == DENSE for f in a.ffs),
            "router": float(D * a.experts * n_sparse),
            "shared": 3.0 * D * a.d_shared * n_sparse,
            "routed": 3.0 * D * a.d_expert * n_sparse * a.top_k * a.held / a.experts,
            "head": float(D * a.vocab_size)}


def attention_flops_per_token(a: Any, seq: int) -> float:
    total = 0.0
    for kind, h in zip(a.kinds, a.heads):
        keys = reach(seq, a.window) / seq if kind == SLIDING else (seq + 1) / 2.0
        total += 12.0 * h * a.head_dim * keys      # 2 products x 2 x 3 (fwd + bwd)
    return total


def required_flops_per_token(a: Any, seq: int) -> float:
    return 6.0 * sum(matmul_params(a).values()) + attention_flops_per_token(a, seq)


#: products of (S x hd) x (hd x keys) size a call, and the tensors crossing
#: HBM at the q heads and at the k/v heads, by the kernel's role
_ROLE = {"fwd": (2, 2, 2),     # q o | k v
         "dq": (3, 4, 2),      # q do o dq | k v
         "dkv": (4, 2, 4)}     # q do | k v dk dv
ATTN_KERNELS = {f"{family}_{role}": (family, role)
                for family in ("saturn_flash", "saturn_swa") for role in _ROLE}


def attn_call(kernel: str, a: Any, batch: int, seq: int,
              bytes_per: int = 2) -> Dict[str, Any]:
    """One call of an attention kernel: ``saturn_flash_*`` is a full layer's
    (its q heads, every key up to the query), ``saturn_swa_*`` a sliding
    layer's (its q heads, the window's keys)."""
    family, role = ATTN_KERNELS[kernel]
    kind = SLIDING if family == "saturn_swa" else FULL
    heads = {h for k, h in zip(a.kinds, a.heads) if k == kind}
    if len(heads) != 1:
        raise KeyError(f"no single q-head count for the {kind} layers: {heads}")
    h = heads.pop()
    keys = reach(seq, a.window) if kind == SLIDING else seq * (seq + 1) / 2.0
    products, at_q, at_kv = _ROLE[role]
    tensor = float(batch * seq * a.head_dim * bytes_per)
    return {"kind": kind,
            "flops": products * 2.0 * batch * h * a.head_dim * keys,
            "bytes": (at_q * h + at_kv * a.n_kv_heads) * tensor}


def gmm_call(kernel: str, a: Any, rows: float, bytes_per: int = 2) -> Dict[str, float]:
    """One call of a grouped product over ``rows`` routed rows: rows x
    (d_model x d_expert) either way round (gate / up and down have the same
    count); the rows in and out once, the held tables once (a table gradient
    leaves in float32)."""
    if kernel not in ("saturn_gmm_fwd", "saturn_gmm_dx", "saturn_gmm_dw"):
        raise KeyError(f"no count for kernel {kernel!r}")
    D, F = a.d_model, a.d_expert
    table = a.held * D * F * (4.0 if kernel == "saturn_gmm_dw" else float(bytes_per))
    return {"flops": 2.0 * rows * D * F,
            "bytes": rows * (D + F) * bytes_per + table}
