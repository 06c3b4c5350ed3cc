"""saturn_tpu: a TPU-native multi-model training orchestrator.

A brand-new JAX/XLA/pjit framework with the capabilities of knagrecha/saturn
(the SPASE multi-query optimizer: Select Parallelism, Apportion resources,
SchedulE). Public API mirrors the reference's four calls (SURVEY.md §0):

1. ``saturn_tpu.library.register(name, technique_cls)``
2. ``saturn_tpu.search(tasks)``           — profile (task × sub-mesh × technique)
3. ``saturn_tpu.orchestrate(task_list)``  — solve + gang-execute to completion
4. ``Task`` / ``HParams`` / ``Strategy``  — job description dataclasses
"""

from saturn_tpu.core.strategy import Strategy, Techniques
from saturn_tpu.core.task import HParams, Task
from saturn_tpu.core.technique import BaseTechnique
from saturn_tpu.core.modelspec import ModelSpec
from saturn_tpu import library

__version__ = "0.1.0"

__all__ = [
    "Task",
    "HParams",
    "Strategy",
    "Techniques",
    "BaseTechnique",
    "ModelSpec",
    "library",
    "search",
    "orchestrate",
    "serve",
]


def search(tasks, technique_names=None, log=False, topology=None, **kw):
    """Profile every (task × sub-mesh size × technique) combination.

    Reference: ``saturn/trial_runner/PerformanceEvaluator.py:33``.
    """
    from saturn_tpu.utils import metrics

    with metrics.span("import", module="saturn_tpu.trial_runner"):
        from saturn_tpu.trial_runner.evaluator import search as _search

    return _search(
        tasks, technique_names=technique_names, log=log, topology=topology, **kw
    )


def orchestrate(
    task_list,
    log=False,
    interval=1000.0,
    topology=None,
    threshold=0.0,
    solver_time_limit=None,
    failure_policy="raise",
    max_task_retries=1,
    metrics_path=None,
    trace_dir=None,
    fault_injector=None,
    health_monitor=None,
    recovery_policy="pause-resolve-resume",
    replan_degrade_factor=2.0,
    resume_dir=None,
    health_guardian=None,
    crash_barrier=None,
):
    """Solve the SPASE problem and run the batch to completion.

    Reference: ``saturn/orchestrator.py:32``. Mirrors
    ``executor.orchestrator.orchestrate`` exactly (parameter names, order
    and defaults — a signature-parity test enforces it) so callers get
    introspectable keywords instead of an opaque ``**kw`` passthrough.
    """
    from saturn_tpu.utils import metrics

    # The first call in a process imports the executor, the solver and
    # SciPy's HiGHS behind it: seconds, before ``orchestrate`` proper (and
    # its sink) exists. The span puts them into a profiler's trace
    # (``saturn.import``) and into a sink the caller configured.
    with metrics.span("import", module="saturn_tpu.executor"):
        from saturn_tpu.executor.orchestrator import orchestrate as _orch

    return _orch(
        task_list,
        log=log,
        interval=interval,
        topology=topology,
        threshold=threshold,
        solver_time_limit=solver_time_limit,
        failure_policy=failure_policy,
        max_task_retries=max_task_retries,
        metrics_path=metrics_path,
        trace_dir=trace_dir,
        fault_injector=fault_injector,
        health_monitor=health_monitor,
        recovery_policy=recovery_policy,
        replan_degrade_factor=replan_degrade_factor,
        resume_dir=resume_dir,
        health_guardian=health_guardian,
        crash_barrier=crash_barrier,
    )


def serve(topology=None, **kw):
    """Start an online job service (``saturn_tpu.service.SaturnService``)
    and return (service, client): the always-on counterpart to the batch
    ``orchestrate`` — jobs submit over time, admission profiles them through
    the profile cache, and each interval boundary re-solves incrementally.
    """
    from saturn_tpu.service import SaturnService, ServiceClient

    svc = SaturnService(topology=topology, **kw).start()
    return svc, ServiceClient(svc)
