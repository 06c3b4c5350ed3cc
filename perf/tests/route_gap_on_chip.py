"""The gap between a token's 6th and 7th router logits, layer by layer, at the
SmallThinker cell's own widths -- the builder's chip script behind the
configuration's ``assumed.weights``, not part of the benchmark's runs.

    chiprun -- python3 perf/tests/route_gap_on_chip.py [--tokens 2048] [--seed N]

The reference's forward (float32, ``highest``) layer by layer on ``--tokens``
of the harness's own tokens; in each layer the logits of the rows the router
reads (the block's un-normed input) are sorted and the smallest, median and
mean of ``z_(k) - z_(k+1)`` over the tokens printed, beside the RMS of the
stream and the level of the chosen logits. Routing is discrete: a gap that
bf16's rounding of the stream could cross is a pair routed differently.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="smallthinker-21b-1chip.steady-8k")
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
            z = jnp.sort(ref.route_logits(params["layers"][n]["router"], x), axis=-1)[..., ::-1]
            gap = np.asarray(z[..., a.top_k - 1] - z[..., a.top_k]).ravel()
            rows.append({"layer": n, "smallest_gap": float(gap.min()),
                         "median_gap": float(np.median(gap)), "mean_gap": float(gap.mean()),
                         "chosen_mean": float(z[..., :a.top_k].mean()),
                         "others_std": float(z[..., a.top_k:].std()),
                         "stream_rms": float(jnp.sqrt(jnp.mean(jnp.square(x))))})
            print("perf: route gap: " + json.dumps(rows[-1]), flush=True)
            x, _ = fns["layer", ref._sig(a, n)](params["layers"][n], x)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"route_gap.{args.workload}.json"), "w") as f:
        json.dump({"tokens": args.tokens, "seed": args.seed, "layers": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
