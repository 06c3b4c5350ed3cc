"""What the ops of a traced program ran as: one recorder for every op family.

An op that chooses between forms when it is traced (a kernel or plain XLA
ops, which blocks, how much VMEM) says so here, once a call::

    plans.record("gdn", GDNPlan(...))

and who traces a program asks here what its calls ran as::

    with plans.traced() as got:
        jax.make_jaxpr(step)(...)
    got["gdn"]   # the plans this thread traced inside the block, in order

(``parallel/spmd_base.py`` puts the first of each family on the grid
point's ``trial_config`` event as ``<family>_plan``.) The collector belongs
to its thread: grid points traced side by side (``trial_runner/evaluator.py``
runs up to four trial threads) each get their own plans and nobody else's.
Recording is a Python side effect at trace time: the jaxpr holds nothing of
it. This module imports nothing of the package, so an op family is added in
its own file alone.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Iterator, List, Mapping

import jax

_collecting = threading.local()


def record(name: str, plan: Any) -> None:
    """Family ``name``'s call was traced as ``plan`` (None: it fell back to
    plain XLA ops). Kept by the innermost :func:`traced` block of this
    thread; dropped where the thread has none."""
    got = getattr(_collecting, "got", None)
    if got is not None:
        got.setdefault(name, []).append(plan)


@contextlib.contextmanager
def traced() -> Iterator[Dict[str, List[Any]]]:
    """Collects every plan this thread records inside the block: family ->
    its plans in the order traced, no key for a family that recorded
    nothing. A block inside another gets its own; the outer one sees nothing
    of it and is restored after."""
    outer = getattr(_collecting, "got", None)
    got: Dict[str, List[Any]] = {}
    _collecting.got = got
    try:
        yield got
    finally:
        _collecting.got = outer


def as_event(plan: Any) -> Any:
    """A plan in the form an event carries: a dict of plain values."""
    if plan is None:
        return None
    if hasattr(plan, "as_event"):
        return plan.as_event()
    if hasattr(plan, "_asdict"):
        return plan._asdict()
    if isinstance(plan, Mapping):
        return dict(plan)
    raise TypeError(f"no event form for a plan of type {type(plan).__name__}")


def _traced_once(*static_argnames: str):
    """Puts a launcher behind ``jit``'s tracing cache, inlined where it is
    called: a call with shapes and static arguments seen before (the layer
    again under remat, the next grid point of a search) binds what was traced
    the first time and traces no kernel body again; the caller's jaxpr holds
    the launcher's equations themselves, as if it had been called bare."""
    def wrap(fn):
        return jax.jit(fn, static_argnames=static_argnames, inline=True)
    return wrap
