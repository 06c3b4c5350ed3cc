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


def write(root: str) -> str:
    """Returns the name of the one cell of the root written at ``root``."""
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    os.makedirs(os.path.join(root, "perf", "configs"))
    os.makedirs(os.path.join(root, "perf", "traffic"))
    with open(os.path.join(root, "perf", "configs", "tiny-gpt2.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(root, "perf", "traffic", "tiny-sweep.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    for metric in real["per_layer"]:
        metric.pop("workloads", None)
    real["configs"] = [{"name": "tiny-gpt2", "source": "test",
                        "file": "perf/configs/tiny-gpt2.json", "reduced": [],
                        "why": "test"}]
    real["workloads"] = [{"name": "tiny.sweep", "config": "tiny-gpt2",
                          "traffic": "tiny-sweep", "chips": 1, "why": "test"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(real, f)
    return "tiny.sweep"
