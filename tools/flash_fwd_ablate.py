"""Where the causal forward kernel's time goes, by ablation (PR 41): the
queries-down forward of ``ops/flash.py`` rebuilt here with one step of its
loop body taken out or swapped at a time, each variant timed alone on one
chip at gpt2-medium's and Ouro's shapes, beside the tree's own forward in
both orientations.

    chiprun -- python tools/flash_fwd_ablate.py

Variants: ``base`` (the body as ``_fwd_kernel`` has it), ``colbcast`` (the
row statistics broadcast out of lane 0, as the kernels before PR 41 held
them), ``nomask``, ``noexp``, ``nomax``, ``nosum``, ``nopv`` (that step left
out: the result is wrong, the time is what is read), ``scalescore`` (the
scale on the scores, not on q), ``alloff`` (mask, exp, max and sum out), and
``tree-queries-down`` / ``tree-keys-down`` (``flash._fwd`` itself with
``_keys_down`` forced). One JSON line a row on stdout and in
``chiprun_out/flash_fwd_ablate.jsonl``; ``base`` and the tree's two are also
compared with each other (``o_maxdiff``). No CPU branch: without a TPU it
exits 1 (``--interpret`` runs tiny shapes in interpret mode, for a test of
the script itself).
"""
import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from saturn_tpu.ops import flash as F  # noqa: E402
from tools.flash_blocks import timed_ms  # noqa: E402

VARIANTS = {"base": "", "colbcast": "colbcast", "nomask": "nomask",
            "noexp": "noexp", "nomax": "nomax", "nosum": "nosum",
            "nopv": "nopv", "scalescore": "scalescore",
            "alloff": "nomask,noexp,nomax,nosum"}
#: name -> (batch x heads, T, head dim)
SHAPES = {"gpt2-medium": (256, 1024, 64), "ouro": (32, 4096, 128)}


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            bq, bk, seq, scale, off):
    iq = pl.program_id(1)
    d = acc_scr.shape[1]
    m_scr[:] = jnp.full_like(m_scr, F.NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    def lanes(x, n):
        if "colbcast" in off:
            return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
        return F._lanes(x, n)

    def body(jk):
        rows = pl.ds(pl.multiple_of(jk * bk, bk), bk)
        kb, vb = k_ref[0, rows, :], v_ref[0, rows, :]
        if "scalescore" in off:
            s = F._dot(q_ref[0], kb, ((1,), (1,))) * scale
        else:
            s = F._scores(q_ref[0], kb, scale)
        if "nomask" not in off:
            s = F._masked(s, iq * bq, jk * bk, 0, None)
        m_prev = m_scr[:]
        m_new = m_prev if "nomax" in off else jnp.maximum(
            m_prev, s.max(axis=-1, keepdims=True))
        p = s - lanes(m_new, bk)
        if "noexp" not in off:
            p = jnp.exp(p)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = corr * l_scr[:] + (
            0.0 if "nosum" in off else p.sum(axis=-1, keepdims=True))
        acc_scr[:] = lanes(corr, d) * acc_scr[:] + (
            p[:, :d] if "nopv" in off
            else F._dot(p.astype(vb.dtype), vb, ((1,), (0,))))

    F._for_blocks(body, F._reach(iq, bq, bk, seq // bk, True, None, True),
                  None, seq // bk)
    l = l_scr[:]
    o_ref[0] = (acc_scr[:] / lanes(l, d)).astype(o_ref.dtype)
    lse_ref[0] = (m_scr[:] + jnp.log(l)).T[:1]


def ablated(q, k, v, *, bq, bk, off, interpret):
    bh, t, d = q.shape
    whole = pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, seq=t,
                          scale=1.0 / math.sqrt(d), off=off),
        grid=(bh, t // bq),
        in_specs=[pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)), whole, whole],
        out_specs=[pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        name="abl_fwd", interpret=interpret)(q, k, v)[0]


def of_the_tree(q, k, v, *, bq, bk, keys_down, interpret):
    """``flash._fwd`` with the orientation forced (traced inside the patch:
    the launcher's tracing cache would answer the second from the first)."""
    bh, t, d = q.shape
    kept, F._keys_down = F._keys_down, lambda _d: keys_down
    try:
        return F._fwd.__wrapped__(
            q, k, v, block_q=bq, block_k=bk, chunk=t,
            scale=1.0 / math.sqrt(d), causal=True, h=1, kv=1,
            interpret=interpret)[0]
    finally:
        F._keys_down = kept


def main(argv):
    interpret = "--interpret" in argv
    if not interpret and jax.devices()[0].platform != "tpu":
        print("no TPU here: nothing measured", file=sys.stderr)
        return 1
    shapes = {"tiny": (2, 256, 64)} if interpret else SHAPES
    bq = bk = 128 if interpret else 512
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/flash_fwd_ablate.jsonl", "a")
    for name, (bh, t, d) in shapes.items():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(x, (bh, t, d)).astype(jnp.bfloat16)
                   for x in ks)
        fns = {n: functools.partial(ablated, bq=bq, bk=bk, interpret=interpret,
                                    off=frozenset(o.split(",")) - {""})
               for n, o in VARIANTS.items()}
        for n, down in (("tree-queries-down", False), ("tree-keys-down", True)):
            fns[n] = functools.partial(of_the_tree, bq=bq, bk=bk,
                                       keys_down=down, interpret=interpret)
        ref = None
        for variant, fn in fns.items():
            row = {"shape": name, "bq": bq, "bk": bk, "variant": variant}
            fn = jax.jit(fn)
            try:
                row["ms"] = round(timed_ms(fn, q, k, v), 4)
                if variant == "base" or variant.startswith("tree-"):
                    o = fn(q, k, v).astype(jnp.float32)
                    ref = o if ref is None else ref
                    row["o_maxdiff"] = float(jnp.abs(o - ref).max())
            except Exception as e:  # the compiler's refusal is the reading
                row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
            line = json.dumps(row)
            print(line, flush=True)
            sink.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
