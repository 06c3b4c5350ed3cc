"""``ops/plans.py``: the one recorder of what the ops of a traced program ran
as. Grid points traced side by side (``trial_runner/evaluator.py`` runs up to
four trial threads) must each read their own plans."""

import dataclasses
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest

from saturn_tpu.ops import gdn, kda, moe, plans


def _delta_rule_inputs(t, dk=24, dv=40):
    f32 = jnp.float32
    return (jnp.zeros((1, 2, t, dk)), jnp.zeros((1, 2, t, dk)), jnp.zeros((1, 2, t, dv)),
            jnp.zeros((1, 2, t), f32), jnp.zeros((1, 2, t), f32))


# a fresh function each call: ``eval_shape`` keeps a tracing cache keyed by the
# function, and a call it has seen before runs no Python and records nothing
def _trace_gdn(t):
    jax.eval_shape(lambda *a: gdn.gated_delta_rule(*a), *_delta_rule_inputs(t))


def _trace_kda(t):
    q, k, v, g, beta = _delta_rule_inputs(t)
    jax.eval_shape(lambda *a: kda.kda(*a), q, k, v, jnp.zeros(q.shape, jnp.float32), beta)


def _side_by_side(*traces):
    """Each of ``traces`` (a list of calls) on a thread of its own, inside a
    collector of its own; every thread is inside its collector before any
    traces, and none leaves before all have traced their first call. ->
    what each thread's collector held when it left."""
    barrier = threading.Barrier(len(traces))
    got = [None] * len(traces)
    errors = []

    def run(i):
        try:
            with plans.traced() as mine:
                barrier.wait(timeout=60)
                first, *rest = traces[i]
                first()
                barrier.wait(timeout=60)     # both have opened and traced once
                for call in rest:
                    call()
                barrier.wait(timeout=60)     # nobody has left yet
            got[i] = {name: list(p) for name, p in mine.items()}
        except BaseException as e:           # a broken barrier included
            errors.append(e)
            barrier.abort()

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(traces))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(120)
    assert not errors, errors
    return got


@pytest.mark.parametrize("case", ["two-families", "one-family-two-shapes"])
def test_threads_tracing_side_by_side_each_get_their_own_plans(case):
    if case == "two-families":
        a, b = _side_by_side(
            [lambda: _trace_gdn(128), lambda: _trace_gdn(64)],
            [lambda: _trace_kda(192), lambda: _trace_kda(64)])
        assert set(a) == {"gdn"} and set(b) == {"kda"}
        assert [p.chunks for p in a["gdn"]] == [2, 1]
        assert [p.chunks for p in b["kda"]] == [3, 1]
    else:
        a, b = _side_by_side(
            [lambda: _trace_gdn(128), lambda: _trace_gdn(128)],
            [lambda: _trace_gdn(320), lambda: _trace_gdn(320)])
        assert [p.chunks for p in a["gdn"]] == [2, 2]
        assert [p.chunks for p in b["gdn"]] == [5, 5]


def test_the_collector_opened_first_may_close_first():
    """Collectors of two threads are not nested in each other: the one opened
    first closing first must not close the other's, whose later plans are
    still its own."""
    opened, closed = threading.Event(), threading.Event()
    late = {}

    def second():
        with plans.traced() as mine:
            _trace_gdn(128)
            opened.set()
            assert closed.wait(60)
            _trace_gdn(64)              # after the first collector has closed
        late.update(mine)

    th = threading.Thread(target=second)
    with plans.traced() as first:
        _trace_gdn(320)
        th.start()
        assert opened.wait(60)
    closed.set()
    th.join(120)
    assert [p.chunks for p in first["gdn"]] == [5]
    assert [p.chunks for p in late["gdn"]] == [2, 1]


def test_a_record_outside_any_collector_keeps_nothing():
    plans.record("moe", "dropped")
    plan = moe.routed_plan(64, 16, 8, 2)
    tables = (jnp.zeros((8, 16, 32)),) * 2 + (jnp.zeros((8, 32, 16)),)
    trace = lambda: jax.eval_shape(
        lambda y, r, *w: moe.routed_experts(y, r, *w, plan=plan)[0],
        jnp.zeros((64, 16)), jnp.zeros((16, 16)), *tables)
    trace()                                   # a routed layer traced with no block open
    with plans.traced() as got:
        assert got == {}
        trace()
    assert got == {"moe": [plan]}             # and not the ones before the block


def test_collectors_nest():
    with plans.traced() as outer:
        plans.record("a", 1)
        with plans.traced() as inner:
            plans.record("a", 2)
            plans.record("b", None)           # a call that fell back
        plans.record("a", 3)
    assert inner == {"a": [2], "b": [None]}
    assert outer == {"a": [1, 3]}
    with pytest.raises(RuntimeError):
        with plans.traced() as failed:
            plans.record("a", 4)
            raise RuntimeError("a trace that raised")
    assert failed == {"a": [4]}
    with plans.traced() as after:             # the failed block left nothing open
        pass
    assert after == {}


class _Tuple(NamedTuple):
    impl: str
    chunk: int


_ROUTED = moe.routed_plan(64, 16, 8, 2)      # a plan that says its own event form


@pytest.mark.parametrize("plan, want", [
    (None, None),
    (_Tuple("xla", 64), {"impl": "xla", "chunk": 64}),
    ({"block": 256}, {"block": 256}),
    (_ROUTED, dict(dataclasses.asdict(_ROUTED), second_path=_ROUTED.second_path)),
], ids=["fallback", "named-tuple", "mapping", "as_event"])
def test_as_event_is_the_one_place_a_plan_becomes_its_event_form(plan, want):
    got = plans.as_event(plan)
    assert got == want and (got is None or type(got) is dict)
    if isinstance(plan, dict):
        assert got is not plan                # a copy: the event owns its fields


def test_as_event_refuses_what_is_no_plan():
    with pytest.raises(TypeError, match="no event form"):
        plans.as_event(3)
