"""The gap between a token's 4th and 5th biased router scores, routed layer by
routed layer, at the LFM2 cell's own widths -- the builder's chip script behind
the configuration's ``assumed.weights`` (after ``route_gap_on_chip.py``, whose
router reads the block's un-normed input; this one's reads ``N2(h)``), not
part of the benchmark's runs.

    chiprun -- python3 perf/tests/lfm2_route_gap_on_chip.py [--tokens 2048] [--seed N]

The reference's forward (float32, ``highest``) by halves of a layer on
``--tokens`` of the harness's own tokens; in each routed layer the scores
``sigmoid(N2(h) W_r) + b`` of the rows the router reads are sorted and the
smallest, median and mean of ``t_(k) - t_(k+1)`` over the tokens printed,
beside the RMS of the stream, the level of the chosen logits and the deviation
of the others, and the spread of a token's four weights. Routing is discrete: a
gap that bf16's rounding of the stream could cross is a pair routed
differently.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="lfm2-8b-a1b-1chip.steady-8k")
    p.add_argument("--tokens", type=int, default=2048)
    p.add_argument("--seed", type=int, default=2_147_483_659)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perf.lib import bench, harness, refcheck

    cell = bench.load_cell(args.workload)
    ref = harness.reference_module(cell.config)
    a = ref.arch_from_config(cell.config, args.tokens)
    _, (tokens,) = refcheck.sample_batches(a.vocab_size, args.tokens, 1, 1, args.seed)
    fns = ref._jitted(a, None)
    rows = []
    with jax.default_matmul_precision("highest"):
        params = ref._unstack(a, fns["params"](ref.seed_key(harness.weight_seed(cell.config))))
        x = fns["embed"](params["top"]["wte"], jnp.asarray(tokens))
        for n in range(a.n_layers):
            p_n = params["layers"][n]
            h, out, _ = ref._layer_forward(a, fns, n, p_n, x)
            if a.ffs[n] == ref.SPARSE:
                u = ref._rms_norm(h, p_n["ln_2"]["scale"], a.norm_eps)
                z = u @ p_n["router"]
                t = jnp.sort(jax.nn.sigmoid(z) + p_n["router_bias"], axis=-1)[..., ::-1]
                gap = np.asarray(t[..., a.top_k - 1] - t[..., a.top_k]).ravel()
                zs = jnp.sort(z, axis=-1)[..., ::-1]
                _, weights = ref.routing_of(a, p_n, u)
                rows.append({"layer": n, "smallest_gap": float(gap.min()),
                             "median_gap": float(np.median(gap)), "mean_gap": float(gap.mean()),
                             "chosen_logit_mean": float(zs[..., :a.top_k].mean()),
                             "others_logit_std": float(zs[..., a.top_k:].std()),
                             "weights_min": float(weights.min()),
                             "weights_max": float(weights.max()),
                             "stream_rms": float(jnp.sqrt(jnp.mean(jnp.square(h))))})
                print("perf: route gap: " + json.dumps(rows[-1]), flush=True)
            x = out
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"route_gap.{args.workload}.json"), "w") as f:
        json.dump({"tokens": args.tokens, "seed": args.seed, "layers": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
