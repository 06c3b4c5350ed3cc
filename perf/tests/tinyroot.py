"""A benchmark root of tiny size for the CPU tests: its own BENCHMARK.json,
one configuration and one traffic mix, the metric readers of the real one."""

import json
import os

from perf.lib import bench

TINY_CONFIG = {
    "name": "tiny-gpt2", "source": "test", "family": "gpt2", "n_embd": 64,
    "n_head": 4, "n_inner": None, "n_layer": 2, "n_positions": 64,
    "vocab_size": 256, "reduced": [],
    "run": {"builder": "saturn_tpu.models.gpt2:build_gpt2",
            "reference": "perf.reference.gpt", "preset": "test-tiny",
            "overrides": {}, "vocab_size": 256},
}
TINY_TRAFFIC = {
    "jobs": [{"name": "a", "seq": 64, "batch": 4, "lr": 1e-3, "share": 0.5},
             {"name": "b", "seq": 32, "batch": 8, "lr": 1e-3, "share": 0.5}],
    "technique_names": ["dp"], "chip_range": [1],
    "steps_per_window_second": 10.0, "round_steps_to": 1,
    "interval": {"window_fraction": 0.34}, "solver_time_limit": 1.0,
    "dataset_batches": 8, "reference_check": {"sequences": 2, "steps": 4},
}


#: ``sweep2`` at tiny size: intervals that end inside a fused window, so the
#: 1-step tail and the partial windows are met, and the warm-up that builds them
TINY_SWEEP2 = dict(TINY_TRAFFIC, round_steps_to=8, steps_per_window_second=16.0)
#: ``fsdp-2k-b16`` at tiny size: one job on a four-chip block
TINY_FSDP = dict(TINY_TRAFFIC, technique_names=["fsdp"], chip_range=[4],
                 jobs=[{"name": "a", "seq": 64, "batch": 8, "lr": 1e-3, "share": 1.0}],
                 round_steps_to=8, steps_per_window_second=8.0,
                 interval={"window_fraction": 100.0},
                 reference_check={"sequences": 4, "steps": 4})


def write(root: str, traffic=None, chips: int = 1) -> str:
    """Returns the name of the one cell of the root written at ``root``."""
    traffic = TINY_TRAFFIC if traffic is None else traffic
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    os.makedirs(os.path.join(root, "perf", "configs"))
    os.makedirs(os.path.join(root, "perf", "traffic"))
    with open(os.path.join(root, "perf", "configs", "tiny-gpt2.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(root, "perf", "traffic", "tiny-sweep.json"), "w") as f:
        json.dump(traffic, f)
    for metric in real["end_to_end"] + real["per_layer"]:
        metric.pop("workloads", None)
    real["configs"] = [{"name": "tiny-gpt2", "source": "test",
                        "file": "perf/configs/tiny-gpt2.json", "reduced": [],
                        "why": "test"}]
    real["workloads"] = [{"name": "tiny.sweep", "config": "tiny-gpt2",
                          "traffic": "tiny-sweep", "chips": chips, "why": "test"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(real, f)
    return "tiny.sweep"
