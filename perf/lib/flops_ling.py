"""What a Ling stack (``perf/reference/ling.py``'s ``Arch``: Kimi-delta-
attention and latent-attention layers, each before a dense SwiGLU or a shared
expert beside group-limited top-k routed experts of which a share is held)
needs, from shapes alone: the numerators of ``mfu_ling`` and
``mla_flash_roofline``. (The delta rule runs as plain XLA ops: no kernel of it,
so no call of it is counted; its products are in ``mfu_ling``.)

``required_flops_per_token``: forward + backward of one training token, by
``perf/lib/flops.py``'s rule (recomputation not counted, the head counted):
6 x the parameters in matrices that multiply a token *as multiplied* (a KDA
layer's q, k, v, channel gate, beta, head gate, output projection at the held
heads and its three convolutions' taps; an MLA layer's q, the latent
down-projection whole, the latent's up-projection to the held heads' keys and
values, head gate and output projection; a leading layer's SwiGLU; router,
shared expert and ``top_k x held / experts`` routed experts a token and routed
layer; the held rows of the head) + latent attention: the causal half of
``Q K^T`` over 192 lanes and of ``P V`` over 128, forward and backward, + the
delta rule's chunked products for each KDA layer.

``mla_flash_call``: operations and least bytes of one call of a
``saturn_mla_*`` kernel (the causal half of the products, q / k / dq / dk at
192 lanes, v / o / do / dv at 128). **The count is of the work the equations need, whatever a kernel pads.**
"""

from __future__ import annotations

from typing import Any, Dict

KDA, MLA = "kda", "mla"
DENSE, SPARSE = "dense", "sparse"
CHUNK = 64


def rule_flops_per_token_head(dk: int, dv: int, chunk: int = CHUNK) -> float:
    """Forward products of the chunked delta rule with a decay a channel, per
    token and head. A chunk of C tokens: the decayed ``K K^T`` and ``Q K^T``
    (2 C^2 dk each: the sub-blocks' products add up to the whole C x C
    contraction, the part above the diagonal counted as ``flops_hybrid`` counts
    it), the triangular transform applied to the chunk's decayed keys
    (2 C^2 dk) and values (2 C^2 dv), the intra-chunk product (2 C^2 dv), and
    three products with the (dk, dv) state: ``W S``, ``Q S`` and the update
    ``K^T U`` (2 C dk dv each). Building the transform and the decays'
    exponentials are not counted."""
    per_chunk = 2.0 * chunk * (chunk * (3 * dk + 2 * dv) + 3 * dk * dv)
    return per_chunk / chunk


def matmul_params(a: Any) -> Dict[str, float]:
    """Parameters that multiply a token, by part (the embedding's lookup is a
    gather and multiplies nothing; a routed expert multiplies the tokens that
    chose it: ``top_k x held / experts`` experts a token on average)."""
    D, H, d = a.d_model, a.n_heads, a.head_dim
    kda = D * H * d * 4 + 2 * D * H + H * d * D + 3 * a.conv_taps * H * d
    qk = a.qk_nope + a.qk_rope
    mla = (D * H * qk + D * (a.kv_latent + a.qk_rope)
           + a.kv_latent * H * (a.qk_nope + a.v_head) + D * H + H * a.v_head * D)
    n_sparse = sum(f == SPARSE for f in a.ffs)
    return {"kda_mixers": float(kda * a.kinds.count(KDA)),
            "mla_mixers": float(mla * a.kinds.count(MLA)),
            "dense_ff": 3.0 * D * a.d_dense * sum(f == DENSE for f in a.ffs),
            "router": float(D * a.experts * n_sparse),
            "shared": 3.0 * D * a.d_shared * n_sparse,
            "routed": 3.0 * D * a.d_expert * n_sparse * a.top_k * a.held / a.experts,
            "head": float(D * a.vocab_size)}


def attention_flops_per_token(a: Any, seq: int) -> float:
    """Latent attention: (Q K^T over nope + rope lanes, P V over v lanes) x 2
    x 3 (forward + backward) x the causal mean of keys a query reads."""
    lanes = a.qk_nope + a.qk_rope + a.v_head
    return 6.0 * a.n_heads * lanes * (seq + 1) / 2.0 * a.kinds.count(MLA)


def required_flops_per_token(a: Any, seq: int) -> float:
    rule = 3.0 * a.n_heads * rule_flops_per_token_head(a.head_dim, a.head_dim) \
        * a.kinds.count(KDA)
    return 6.0 * sum(matmul_params(a).values()) + attention_flops_per_token(a, seq) + rule


#: (products over the scores' lanes, products over the values' lanes; tensors
#: of the scores' width, tensors of the values' width) a call: fwd Q K^T | P V,
#: q k | v o; dq S, dQ | dP, q k dq | v do o; dkv S, dK | dP, dV, q k dk | v do dv
_MLA = {"saturn_mla_fwd": (1, 1, 2, 2),
        "saturn_mla_dq": (2, 1, 3, 3),
        "saturn_mla_dkv": (2, 2, 3, 3)}


def mla_flash_call(kernel: str, a: Any, batch: int, seq: int,
                   bytes_per: int = 2) -> Dict[str, float]:
    """One call of a latent-attention kernel: the causal half of every
    product, each at its own width (192 or 128 lanes), every head its own key
    (the shared rotary key is broadcast before the heads' norm)."""
    at_qk, at_v, wide, narrow = _MLA[kernel]
    qk, dv = a.qk_nope + a.qk_rope, a.v_head
    pairs = batch * a.n_heads * seq * (seq + 1) / 2.0
    return {"flops": 2.0 * pairs * (at_qk * qk + at_v * dv),
            "bytes": float(batch * a.n_heads * seq * (wide * qk + narrow * dv) * bytes_per)}
