"""Lint session: ruff + mypy with the repo's tiered strictness.

The analyzer package (``saturn_tpu/analysis/``) is held to the strict
configuration in ``pyproject.toml`` — it is the gate every plan-adoption
site trusts, so it gets the strongest static guarantees in the tree; the
rest of the repo runs the permissive baseline.

Neither tool is baked into the CI image, so this session *skips* (exit 0,
with a notice) when one is missing rather than failing the build — the
same gate-on-absence rule as the hypothesis-optional differential test.

Run: ``python tools/lint.py`` — exit 1 only on real findings.
"""

from __future__ import annotations

import ast
import glob
import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Packages where a silently swallowed exception eats a training fault the
#: guardian was supposed to see — the recovery path itself must never lose
#: an error.
SWALLOW_ROOTS = ("saturn_tpu/executor", "saturn_tpu/health")

#: A handler that calls one of these (method or bare name) is observing the
#: failure, not swallowing it: logging, metrics, or an error-ledger write.
_OBSERVERS = frozenset({
    "debug", "info", "warning", "error", "exception", "critical",
    "log", "event", "append", "record", "put", "add",
})


def _observes(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, (ast.Raise, ast.Return, ast.Yield, ast.Continue,
                             ast.Break)):
            return True
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            if name in _OBSERVERS:
                return True
        # ``except Exception as e`` whose body reads ``e`` is capturing the
        # failure into state someone inspects later, not dropping it.
        if (handler.name and isinstance(node, ast.Name)
                and node.id == handler.name):
            return True
    return False


def _swallow_findings(roots=SWALLOW_ROOTS) -> list:
    """Flag ``except Exception:`` / bare ``except:`` handlers in the
    executor and health packages whose body neither re-raises, diverts
    control flow, nor records the failure (log/metric/ledger). Returns
    ``{"path", "line", "message"}`` dicts; empty means clean."""
    findings = []
    for root in roots:
        for path in sorted(glob.glob(os.path.join(REPO, root, "**", "*.py"),
                                     recursive=True)):
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.ExceptHandler):
                    continue
                broad = node.type is None or (
                    isinstance(node.type, ast.Name)
                    and node.type.id in ("Exception", "BaseException")
                )
                if broad and not _observes(node):
                    findings.append({
                        "path": os.path.relpath(path, REPO),
                        "line": node.lineno,
                        "message": "broad except swallows the error "
                                   "silently — re-raise, log, or record it",
                    })
    return findings


#: Calls that would reintroduce a full-tree gather/materialization funnel
#: into the sharded checkpoint writer. Round 19 removed the last sanctioned
#: ones; any new use in utils/checkpoint.py is a format regression.
_CKPT_FORBIDDEN_CALLS = frozenset({"process_allgather", "device_get"})


def _ckpt_format_findings(
    path: str = "saturn_tpu/utils/checkpoint.py",
) -> list:
    """The checkpoint-format gate: the sharded writer must stay zero-gather.
    Flags any call to ``process_allgather`` or ``jax.device_get`` of a whole
    tree/leaf inside ``utils/checkpoint.py`` — per-shard ``shard.data``
    copies are the only sanctioned device→host traffic there."""
    findings = []
    full = os.path.join(REPO, path)
    with open(full) as f:
        tree = ast.parse(f.read(), filename=full)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if name not in _CKPT_FORBIDDEN_CALLS:
            continue
        if name == "device_get":
            # the per-shard copy (device_get of shard.data) is the sharded
            # format's one legitimate transfer; a device_get of anything
            # else in this file is a full-leaf materialization
            arg = node.args[0] if node.args else None
            if (isinstance(arg, ast.Attribute) and arg.attr == "data"):
                continue
        findings.append({
            "path": path,
            "line": node.lineno,
            "message": f"{name}() in the checkpoint writer reintroduces a "
                       "full-tree gather funnel — the sharded manifest "
                       "format writes per-shard local copies only",
        })
    return findings


def _have(tool: str) -> bool:
    return importlib.util.find_spec(tool) is not None


def _run(argv: list) -> int:
    r = subprocess.run(argv, cwd=REPO)
    return r.returncode


def main() -> int:
    results = {}
    failed = False

    # The memlens gate below traces techniques at a probe sub-mesh size;
    # the virtual-device flag must land before anything imports jax.
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if _have("ruff"):
        rc = _run([sys.executable, "-m", "ruff", "check", "saturn_tpu",
                   "tests", "tools", "perf"])
        results["ruff"] = "ok" if rc == 0 else f"failed rc={rc}"
        failed |= rc != 0
    else:
        results["ruff"] = "skipped (not installed; pip install -e '.[lint]')"

    if _have("mypy"):
        # Strictness tiers live in pyproject [tool.mypy]; scoping the run to
        # the analyzer keeps the permissive baseline from drowning signal.
        rc = _run([sys.executable, "-m", "mypy", "saturn_tpu/analysis"])
        results["mypy"] = "ok" if rc == 0 else f"failed rc={rc}"
        failed |= rc != 0
    else:
        results["mypy"] = "skipped (not installed; pip install -e '.[lint]')"

    # Always available: the repo's own static passes over its own hot path.
    # A lint session that can't even self-host the analyzer is not a lint
    # session, so these run regardless of which external tools exist.
    sys.path.insert(0, REPO)
    from saturn_tpu.analysis import jax_lint
    from saturn_tpu.parallel.spmd_base import SPMDTechnique

    diags = jax_lint.lint_host_syncs(SPMDTechnique.interval_dispatches)
    diags += jax_lint.lint_donation(
        SPMDTechnique.interval_dispatches,
        {"fused_fn": (0, 1), "single_fn": (0, 1)},
    )
    results["saturn-lint"] = (
        "ok" if not diags else [d.to_json() for d in diags]
    )
    failed |= bool(diags)

    swallows = _swallow_findings()
    results["swallowed-exceptions"] = "ok" if not swallows else swallows
    failed |= bool(swallows)

    # checkpoint-format: the sharded writer must never regress to a gather
    # funnel (process_allgather / full-leaf device_get in checkpoint.py).
    ckpt_regressions = _ckpt_format_findings()
    results["ckpt-format"] = "ok" if not ckpt_regressions else ckpt_regressions
    failed |= bool(ckpt_regressions)

    # saturn-tsan: the concurrency pass over the thread-bearing packages.
    # Gates on unsanctioned SAT-C findings (errors); sanctioned cases are
    # info-severity and pass.
    from saturn_tpu.analysis.concurrency import static_pass

    tsan_report = static_pass.run(static_pass.default_paths(REPO)).report
    results["saturn-tsan"] = (
        "ok" if tsan_report.ok
        else [d.to_json() for d in tsan_report.errors]
    )
    failed |= not tsan_report.ok

    # saturn-shardflow: the source half of the sharding pass (SAT-X002
    # gather-to-replicated funnels) over the technique and kernel packages.
    # AST-only — no jax, no devices — so it gates in any environment; the
    # full jaxpr trace audit is ``python -m saturn_tpu.analysis shardflow``.
    from saturn_tpu.analysis.diagnostics import AnalysisReport
    from saturn_tpu.analysis.shardflow import passes as sf_passes

    sf_report = AnalysisReport(subject="shardflow-sources")
    sf_passes.scan_sources(sf_passes.default_source_paths(REPO), sf_report)
    results["saturn-shardflow"] = (
        "ok" if sf_report.ok
        else [d.to_json() for d in sf_report.errors]
    )
    failed |= not sf_report.ok

    # saturn-memlens: the peak-liveness audit over every in-tree
    # technique's traced step. Gates on unsanctioned SAT-M001/M003 errors
    # (predicted OOM / missed donation); without a known HBM capacity only
    # M003 can fire, which is exactly the source invariant — in-tree step
    # functions must donate their state. An environment whose jax cannot
    # trace at all skips, per the gate-on-absence rule.
    from saturn_tpu.analysis.memlens import passes as ml_passes

    try:
        ml_report, _ = ml_passes.audit_intree(size=4)
    except Exception as e:
        results["saturn-memlens"] = f"skipped ({type(e).__name__}: {e})"
    else:
        results["saturn-memlens"] = (
            "ok" if ml_report.ok
            else [d.to_json() for d in ml_report.errors]
        )
        failed |= not ml_report.ok

    print(json.dumps({"metric": "lint", "results": results,
                      "status": "failed" if failed else "ok"}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
