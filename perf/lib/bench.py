"""Finding a cell's files by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds ``perf/configs/<config>.json``,
``perf/traffic/<mix>.json`` or ``perf/metrics/<name>.py`` and an entry in
``BENCHMARK.json``, and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF_DIR)


class BenchmarkError(Exception):
    """The benchmark's own files do not fit together."""


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"no such file: {path}") from None


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: Dict[str, Any]       # perf/configs/<config>.json
    traffic_name: str
    traffic: Dict[str, Any]      # perf/traffic/<mix>.json
    end_to_end: List[Dict[str, Any]]   # the BENCHMARK.json entries that hold here
    per_layer: List[Dict[str, Any]]
    root: str                    # where BENCHMARK.json was read from
    bench_dir: str               # <root>/<paths[0]>


def _holds_in(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _per_layer_of(bench: Dict[str, Any], cell: str,
                  end_to_end: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The per-layer metrics of a cell: those that list it, and those with
    no list whose ``moves`` names an end-to-end metric the cell reports (an
    end-to-end metric may list its cells too: ``train_tokens_per_s`` leaves
    the four-chip cell out, PR 32). A metric that lists a cell which does not
    report what it should move is a fault of ``BENCHMARK.json``."""
    reported = {m["name"] for m in end_to_end}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m and cell in m["workloads"] and m["moves"] not in reported:
            raise BenchmarkError(
                f"per-layer metric {m['name']!r} lists {cell!r}, which does not "
                f"report {m['moves']!r}, the metric it should move")
        if _holds_in(m, cell) and m["moves"] in reported:
            out.append(m)
    return out


def load_cell(workload: str, root: Optional[str] = None) -> Cell:
    root = os.path.abspath(root or REPO)
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkError(f"workload {workload!r} names no known config")
    bench_dir = os.path.join(root, bench["paths"][0])
    end_to_end = [m for m in bench["end_to_end"] if _holds_in(m, workload)]
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        why=entry["why"],
        config_name=entry["config"],
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic_name=entry["traffic"],
        traffic=_load_json(os.path.join(bench_dir, "traffic", entry["traffic"] + ".json")),
        end_to_end=end_to_end,
        per_layer=_per_layer_of(bench, workload, end_to_end),
        root=root,
        bench_dir=bench_dir,
    )


def load_reader(cell: Cell, metric: str) -> Callable[[Any], Optional[float]]:
    """``read(run) -> float | None`` of ``metrics/<metric>.py``: looked for
    beside the BENCHMARK.json that was read, then beside this harness. A
    metric named ``<reader>.<anything>`` with no file of its own is read by
    ``metrics/<reader>.py``: a metric that lists its cells (a kernel's
    roofline, which only the cells that run the kernel can report) gets a
    new cell through a new *entry* ``<reader>.<cell>`` that lists it, with no
    copied reader."""
    names = [metric] + ([metric.split(".", 1)[0]] if "." in metric else [])
    for name, base in ((n, b) for n in names for b in (cell.bench_dir, PERF_DIR)):
        path = os.path.join(base, "metrics", name + ".py")
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(
                "perf_metric_" + metric.replace(".", "_").replace("-", "_"), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            if not callable(getattr(module, "read", None)):
                raise BenchmarkError(f"{path} has no read(run)")
            return module.read
    raise BenchmarkError(
        f"metric {metric!r} is in BENCHMARK.json and has no reader "
        f"metrics/{metric}.py")


def load_peaks(device_kind: str) -> Dict[str, float]:
    table = _load_json(os.path.join(PERF_DIR, "lib", "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in perf/lib/peaks.json: add "
            f"its published peaks with their source, do not guess")
    return table[device_kind]
