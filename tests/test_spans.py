"""``metrics.span``: the record, nesting, the thread hand-offs, failures, the
no-sink contract — and that a tiny ``search`` -> ``orchestrate`` on virtual
devices emits every span of the table in ``docs/architecture.md`` ("Metrics stream & spans")
with a sound tree under it.

No timing assertion here is tighter than 10x; ``saturn_tpu.library`` is left
as found (the module fixture restores the registry it registered into).
"""

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import pytest

from perf.lib import spans as interval_math  # union / clip / subtract / length
from saturn_tpu.resilience.crash import SimulatedKill
from saturn_tpu.utils import metrics

SEQ, BATCH, VOCAB, STEPS = 32, 4, 256, 16


# ------------------------------------------------------------------ helpers
@pytest.fixture()
def sink(tmp_path):
    """A scoped sink; yields a function that reads what was written so far."""
    path = str(tmp_path / "ev.jsonl")
    with metrics.scoped(path):
        def read(kind=None):
            metrics.flush()
            return metrics.read_events(path, kind)
        yield read


def spans_of(events):
    return [e for e in events if "id" in e and "dur_s" in e]


# ---------------------------------------------------------------- the record
def test_record_has_every_field(sink):
    before = time.time()
    with metrics.span("unit.work", task="t0", bytes=7) as sp:
        time.sleep(0.01)
        sp.set(outcome="ok")
    after = time.time()
    (e,) = sink("unit.work")
    assert set(e) == {"ts", "kind", "ts_start", "dur_s", "id", "parent", "root",
                      "thread", "task", "bytes", "outcome"}
    assert e["kind"] == "unit.work" and e["task"] == "t0" and e["bytes"] == 7
    assert e["outcome"] == "ok"
    assert isinstance(e["id"], int) and e["parent"] is None and e["root"] == e["id"]
    assert e["thread"] == threading.current_thread().name
    assert before <= e["ts_start"] <= e["ts"] <= after
    # perf_counter's duration against the two wall stamps: same stretch
    assert 0.01 <= e["dur_s"] <= 10 * max(e["ts"] - e["ts_start"], 0.01)
    assert abs(e["dur_s"] - (e["ts"] - e["ts_start"])) < 0.1


def test_ids_are_unique_across_threads(sink):
    def burst():
        for _ in range(200):
            with metrics.span("unit.burst"):
                pass

    threads = [threading.Thread(target=burst) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ids = [e["id"] for e in sink("unit.burst")]
    assert len(ids) == 800 and len(set(ids)) == 800


def test_nesting_parent_and_root_on_one_thread(sink):
    with metrics.span("unit.root") as root:
        assert metrics.current_span() is root
        with metrics.span("unit.mid") as mid:
            with metrics.span("unit.leaf"):
                assert metrics.current_span().name == "unit.leaf"
            assert metrics.current_span() is mid
        with metrics.span("unit.sibling"):
            pass
    assert metrics.current_span() is None
    by = {e["kind"]: e for e in sink()}
    assert by["unit.mid"]["parent"] == by["unit.root"]["id"]
    assert by["unit.leaf"]["parent"] == by["unit.mid"]["id"]
    assert by["unit.sibling"]["parent"] == by["unit.root"]["id"]
    assert {e["root"] for e in by.values()} == {by["unit.root"]["id"]}
    # a child is emitted before its parent and lies inside it
    assert by["unit.leaf"]["ts"] <= by["unit.mid"]["ts"] <= by["unit.root"]["ts"]
    assert by["unit.root"]["ts_start"] <= by["unit.mid"]["ts_start"]


def test_explicit_parent_wins_over_the_open_span(sink):
    with metrics.span("unit.a") as a:
        pass
    with metrics.span("unit.b"):
        with metrics.span("unit.c", parent=a):
            pass
    by = {e["kind"]: e for e in sink()}
    assert by["unit.c"]["parent"] == by["unit.a"]["id"]
    assert by["unit.c"]["root"] == by["unit.a"]["root"]


def test_open_without_entering_gives_ids_and_children(sink):
    """``task_interval``'s shape: an event emitted by hand with the span's
    ids, its phases made children with ``under`` / ``parent=``."""
    with metrics.span("unit.outer") as outer:
        held = metrics.span("unit.by_hand").open()
        assert metrics.current_span() is outer  # not on the stack
        with metrics.under(held):
            with metrics.span("unit.phase"):
                pass
        metrics.event("unit.by_hand", **held.ids())
    by = {e["kind"]: e for e in sink()}
    assert by["unit.by_hand"]["parent"] == by["unit.outer"]["id"]
    assert by["unit.phase"]["parent"] == by["unit.by_hand"]["id"]
    assert by["unit.phase"]["root"] == by["unit.outer"]["id"]
    assert "dur_s" not in by["unit.by_hand"]


@pytest.mark.parametrize("exc", [RuntimeError, KeyboardInterrupt, SimulatedKill],
                         ids=lambda c: c.__name__)
def test_exception_is_recorded_and_propagates(sink, exc):
    with pytest.raises(exc):
        with metrics.span("unit.outer"):
            with metrics.span("unit.boom", task="t"):
                raise exc("stop")
    by = {e["kind"]: e for e in sink()}
    assert by["unit.boom"]["error"] == exc.__name__
    assert by["unit.outer"]["error"] == exc.__name__
    assert by["unit.boom"]["parent"] == by["unit.outer"]["id"]
    assert metrics.current_span() is None  # the stack unwound


def test_no_sink_no_event_no_field(tmp_path, monkeypatch):
    assert not metrics.enabled()
    stamped = []
    monkeypatch.setattr(metrics.time, "perf_counter",
                        lambda: stamped.append(1) or 0.0)
    monkeypatch.setattr(metrics.time, "time",
                        lambda: stamped.append(1) or 0.0)
    with metrics.span("unit.off", task="t") as sp:
        assert metrics.current_span() is None
        with metrics.under(sp):
            pass
        # JAX's four durations and a pass of the collector, as the listeners
        # get them (they stay registered once a sink has been configured)
        for name in metrics._HOST_SECONDS:
            metrics._on_duration(name, 0.25, fun_name="unit")
        metrics._on_gc("start", {"generation": 2})
        metrics._on_gc("stop", {"generation": 2})
    monkeypatch.undo()
    assert sp.id is None and sp.ids() == {} and not stamped
    assert sp._seconds is None and sp._gc is None and sp._stamped() == {}
    assert metrics._gc_t0 is None and not metrics._ROOTS
    assert not hasattr(metrics._OPEN, "cache_read")
    held = metrics.span("unit.off").open()
    assert held.id is None and held.ids() == {}
    # and nothing reaches a sink configured afterwards
    path = str(tmp_path / "late.jsonl")
    with metrics.scoped(path):
        pass
    assert metrics.read_events(path) == []


def test_span_without_a_sink_is_cheap():
    """The acceptance bound is 2 us a call on the builder's machine; a shared
    CI core gets 10x that."""
    assert not metrics.enabled()
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with metrics.span("unit.cost"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, f"{per_call * 1e9:.0f} ns a span with no sink"


# ------------------------------------------------ hand-offs, one at a time
def test_under_hands_a_parent_to_a_thread(sink):
    out = {}

    def work(above):
        with metrics.under(above):
            with metrics.span("unit.child"):
                out["inside"] = metrics.current_span().name
        out["after"] = metrics.current_span()

    with metrics.span("unit.parent"):
        t = threading.Thread(target=work, args=(metrics.current_span(),),
                             name="handoff-thread")
        t.start()
        t.join()
    by = {e["kind"]: e for e in sink()}
    assert out == {"inside": "unit.child", "after": None}
    assert by["unit.child"]["parent"] == by["unit.parent"]["id"]
    assert by["unit.child"]["root"] == by["unit.parent"]["id"]
    assert by["unit.child"]["thread"] == "handoff-thread"


def test_compile_event_names_program_and_span(sink):
    import jax.numpy as jnp

    def saturn_unit_probe(x):
        return jnp.tanh(x) * 3.0 + 1.0

    with metrics.span("unit.compiling") as sp:
        jax.block_until_ready(jax.jit(saturn_unit_probe)(jnp.ones((5, 3))))
    mine = [e for e in sink("compile") if "saturn_unit_probe" in e["program"]]
    assert len(mine) == 1
    e = mine[0]
    assert e["in_span"] == {"name": "unit.compiling", "id": sp.id}
    assert e["seconds"] > 0 and e["cached"] is False
    assert e["thread"] == threading.current_thread().name


# ---------------------------------- the host's seconds, on the span they fell in
TRACE, LOWER, COMPILE, CACHE_READ = metrics._HOST_SECONDS


def test_a_jit_traced_inside_a_trace_is_counted_once(sink):
    """``trace_s`` is JAX's own clock, nested once: tracing ``outer`` traces
    ``inner`` twice (two shapes), each trace of ``inner`` sleeps 50 ms, and
    JAX reports the three durations, the outer one holding the other two."""
    import jax.monitoring
    import jax.numpy as jnp

    reported = []

    def listen(name, secs, **kw):
        if name == TRACE and "saturn_unit" in kw.get("fun_name", ""):
            reported.append((kw["fun_name"], secs))

    @jax.jit
    def saturn_unit_inner(x):
        time.sleep(0.05)
        return jnp.tanh(x)

    def saturn_unit_outer(x):
        return saturn_unit_inner(x).sum() + saturn_unit_inner(x[:2]).sum()

    x = jnp.ones((4, 3))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with metrics.span("unit.tracing"):
            jax.jit(saturn_unit_outer).lower(x)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    names = [n for n, _ in reported]
    assert names == ["saturn_unit_inner"] * 2 + ["saturn_unit_outer"], names
    outer, naive = reported[-1][1], sum(secs for _, secs in reported)
    assert 0.1 <= outer and naive >= outer + 0.1
    (e,) = sink("unit.tracing")
    # the outer duration, once
    assert outer - 1e-5 <= e["trace_s"] < outer + 0.05 < naive
    assert e["trace_s"] <= e["dur_s"] and e["lower_s"] > 0
    assert "compile_s" not in e and "cache_read_s" not in e


def test_durations_one_after_another_add_up(sink):
    with metrics.span("unit.adds") as sp:
        for secs in (0.002, 0.003):
            metrics._on_duration(LOWER, secs, fun_name="unit")
            time.sleep(0.005)
    (e,) = sink("unit.adds")
    assert e["lower_s"] == pytest.approx(0.005) and "trace_s" not in e
    assert set(sp._seconds) == {"lower_s"}


def test_a_cache_read_is_no_compile(sink):
    """JAX clocks a persistent-cache retrieval under the backend compile's
    event too: the span counts it under ``cache_read_s`` alone, and the
    ``compile`` event says ``cached``."""
    with metrics.span("unit.reads"):
        metrics._on_duration(CACHE_READ, 0.2)
        metrics._on_duration(COMPILE, 0.21, fun_name="jit(unit_cached)")
        time.sleep(0.002)
        metrics._on_duration(COMPILE, 0.4, fun_name="jit(unit_fresh)")
    (e,) = sink("unit.reads")
    assert e["cache_read_s"] == pytest.approx(0.2)
    assert e["compile_s"] == pytest.approx(0.4)
    by = {c["program"]: c for c in sink("compile") if "unit_" in c["program"]}
    assert by["jit(unit_cached)"]["cached"] is True
    assert by["jit(unit_fresh)"]["cached"] is False
    assert by["jit(unit_fresh)"]["in_span"]["name"] == "unit.reads"


def test_a_duration_with_no_open_span_adds_nowhere(sink):
    out = {}

    def bare():
        try:
            for name in (TRACE, LOWER, CACHE_READ, COMPILE):  # JAX's order
                metrics._on_duration(name, 0.5, fun_name="jit(unit_bare)")
            out["span"] = metrics.current_span()
        except BaseException as e:  # the assertion below names it
            out["raised"] = e

    with metrics.span("unit.elsewhere"):
        t = threading.Thread(target=bare, name="bare-thread")
        t.start()
        t.join()
    assert out == {"span": None}
    (e,) = sink("unit.elsewhere")
    assert not set(e) & {"trace_s", "lower_s", "compile_s", "cache_read_s"}
    # the backend compile is still an event of its own, in no span
    (c,) = [c for c in sink("compile") if c["program"] == "jit(unit_bare)"]
    assert c["in_span"] is None and c["thread"] == "bare-thread"
    assert c["cached"] is True  # the retrieval just before it, on that thread


class _Cycle:
    def __init__(self):
        self.me = self


@pytest.mark.parametrize("where", ["collect", "undisturbed_clock"])
def test_the_collectors_passes_are_counted_on_the_span(sink, where):
    """A full pass made inside a span is the span's ``gc_full`` (and the
    root's: the process's passes over its extent); inside
    ``undisturbed_clock`` no full pass runs, however much is allocated."""
    import gc

    from saturn_tpu.utils import timing

    with metrics.span("unit.root"):
        with metrics.span("unit.other"):
            pass
        if where == "collect":
            with metrics.span("unit.gc"):
                gc.collect()
        else:
            with timing.undisturbed_clock(), metrics.span("unit.gc"):
                kept = [[_Cycle() for _ in range(100)] for _ in range(300)]
                del kept
    by = {e["kind"]: e for e in sink()}
    e, root = by["unit.gc"], by["unit.root"]
    if where == "collect":
        assert e["gc_full"] == 1 and e["gc_n"] >= 1
        assert 0 < e["gc_full_max_s"] <= e["gc_s"] <= e["dur_s"]
        assert root["gc_full"] >= 1 and root["gc_s"] >= e["gc_s"]
    else:
        assert e["gc_n"] >= 3 and e["gc_full"] == 0 and e["gc_s"] > 0
        assert "gc_full_max_s" not in e
        assert root["gc_n"] >= e["gc_n"]  # and whatever ran beside it
    assert not set(by["unit.other"]) & {"gc_s", "gc_n", "gc_full"}


def test_passes_on_many_threads_all_reach_the_root(sink):
    """More allocating threads than cores under one root span, each inside a
    span of its own, a short switch interval: the root's count is every pass
    of the process (an independent callback counts them), the threads' spans
    share them out, and none is lost to a racing update."""
    import gc
    import sys

    seen = []

    def count(phase, info):
        if phase == "stop":
            seen.append(threading.current_thread().name)

    def churn(above):
        with metrics.under(above), metrics.span("unit.churn"):
            for _ in range(40):
                kept = [_Cycle() for _ in range(2000)]
                del kept

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with metrics.span("unit.root") as root:
            gc.callbacks.append(count)
            threads = [threading.Thread(target=churn, args=(root,),
                                        name=f"churn-{i}")
                       for i in range(2 * (os.cpu_count() or 4))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            gc.callbacks.remove(count)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        if count in gc.callbacks:
            gc.callbacks.remove(count)
    (e,) = sink("unit.root")
    assert e["gc_n"] == len(seen) >= len(threads)
    on_threads = sum(c.get("gc_n", 0) for c in sink("unit.churn"))
    assert on_threads == sum(1 for name in seen if name.startswith("churn-"))


@pytest.mark.parametrize("ended", ["short", "long", "short_error"])
def test_a_span_under_its_min_s_emits_nothing(sink, ended):
    try:
        with metrics.span("unit.wait", min_s=0.05, ahead=True):
            if ended == "long":
                time.sleep(0.06)
            if ended == "short_error":
                raise RuntimeError("stop")
    except RuntimeError:
        pass
    found = sink("unit.wait")
    assert len(found) == (0 if ended == "short" else 1)
    if ended == "short_error":
        assert found[0]["error"] == "RuntimeError"


@pytest.mark.parametrize("exc, marked", [(SimulatedKill, True),
                                         (KeyboardInterrupt, True),
                                         (RuntimeError, False)],
                         ids=lambda v: getattr(v, "__name__", str(v)))
def test_a_span_closed_on_a_kills_way_out_carries_it(sink, exc, marked):
    """A ``finally`` that waits (``_measured_behind``'s join) while a kill
    unwinds its thread: the span around the wait raised nothing itself and
    still says what ended it. An ``Exception`` in flight is ordinary
    business (a handler that does spanned work) and marks nothing."""
    with pytest.raises(exc):
        try:
            raise exc("stop")
        finally:
            with metrics.span("unit.join"):
                pass
    (e,) = sink("unit.join")
    assert e.get("error") == (exc.__name__ if marked else None)


def test_public_calls_span_their_lazy_import(sink):
    """``saturn_tpu.orchestrate`` / ``search`` import their subsystem on the
    first call (seconds, SciPy's solver among it): a span, so that a trace
    or a caller's own sink shows where a first window's head went."""
    import saturn_tpu

    assert saturn_tpu.orchestrate([]) == {"completed": [], "failed": {}}
    by = {e["kind"]: e for e in sink()}
    assert by["import"]["module"] == "saturn_tpu.executor"
    assert by["import"]["ts"] <= by["orchestrate"]["ts_start"] + 0.005
    assert by["orchestrate"]["n_tasks"] == 0 and "error" not in by["orchestrate"]


# ------------------------------------------- the tiny search -> orchestrate
def _task(save_dir, name, lr, batch_count=STEPS):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    return Task(
        get_model=lambda **kw: build_gpt2("test-tiny", seq_len=SEQ, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=SEQ, batch_size=BATCH, vocab_size=VOCAB,
            n_tokens=SEQ * BATCH * 8, seed=3),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=lr, batch_count=batch_count),
        chip_range=[1], name=name, save_dir=save_dir,
    )


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory, devices8):
    """Two jobs of different shape on two one-chip blocks: two trial threads,
    two gangs side by side, a journal, then one job resumed from its
    checkpoint and a two-member fused interval. Returns the event lists."""
    import saturn_tpu
    from saturn_tpu import library
    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.parallel import fused
    from saturn_tpu.utils import checkpoint

    root = tmp_path_factory.mktemp("spans")
    registry = dict(library._REGISTRY)
    env = os.environ.get("SATURN_TPU_HBM_BYTES")
    # a capacity for the static memory prior to run against (the CPU reports
    # none); large, so that it prunes nothing
    os.environ["SATURN_TPU_HBM_BYTES"] = str(64 * 2 ** 30)
    try:
        library.register_default_library()
        topo = SliceTopology(list(devices8[:2]))
        tasks = [_task(str(root / "ck"), "span-a", 1e-3),
                 _task(str(root / "ck"), "span-b", 3e-4, batch_count=STEPS + 8)]
        ev = {k: str(root / f"{k}.jsonl")
              for k in ("search", "window", "resume", "fused")}
        stats = saturn_tpu.search(
            tasks, technique_names=["dp"], topology=topo,
            metrics_path=ev["search"], profile_cache=False, parallel_trials=2)
        assert stats["errors"] == 0 and stats["trials_run"] == 2
        # the window the tests read is two gangs side by side. On a loaded
        # host the two trial threads can time their jobs slow enough for the
        # stack of both, priced alone after them, to win the plan (then no
        # ``launch.*`` span: nine tests fail together); an unpriced stack is
        # never fused, and the fused launcher has its own phase below
        for task in tasks:
            for strategy in task.strategies.values():
                strategy.fused_per_batch_time = None
        result = saturn_tpu.orchestrate(
            tasks, interval=60.0, topology=topo, metrics_path=ev["window"],
            solver_time_limit=2.0, resume_dir=str(root / "journal"))
        assert sorted(result["completed"]) == ["span-a", "span-b"]
        assert not metrics.enabled()  # orchestrate restored the sink
        # a job launched again with no live state: the restore path
        again = tasks[0]
        again.release_live_state()
        with metrics.scoped(ev["resume"]), metrics.span("orchestrate"):
            again.selected_strategy.executor.execute(
                again, list(devices8[:1]), 0, override_batch_count=8)
            checkpoint.flush()
        # a two-member stack through the fused launcher's call
        members = [_task(str(root / "fu"), f"span-f{i}", lr, batch_count=8)
                   for i, lr in enumerate((1e-3, 2e-3))]
        for m in members:
            m.strategies[1] = tasks[0].strategies[1]
            m.select_strategy(1)
        with metrics.scoped(ev["fused"]), metrics.span("orchestrate"):
            fused.run_fused_interval(members, list(devices8[:1]), 0,
                                     batch_counts=[8, 8])
            checkpoint.flush()
        yield {k: metrics.read_events(p) for k, p in ev.items()}
    finally:
        library._REGISTRY.clear()
        library._REGISTRY.update(registry)
        if env is None:
            os.environ.pop("SATURN_TPU_HBM_BYTES", None)
        else:
            os.environ["SATURN_TPU_HBM_BYTES"] = env


#: every span of the table that search -> orchestrate runs, with the phase
#: whose event file holds it. ``prior.shardflow`` is the service's admission
#: path (no search runs it) and has its own case below.
PATH_SPANS = [
    ("search", "search"), ("search", "trial"), ("search", "trial.config"),
    ("search", "trial.identity"),   # PR 47: emitted where no record is on, too
    ("search", "trial.build"), ("search", "trial.compile"),
    ("search", "trial.memory_check"), ("search", "trial.memlens"),
    ("search", "trial.init"), ("search", "trial.stage"),
    ("search", "trial.timing"), ("search", "prior.memlens"),
    # the measuring thread waits at least for its first point (PR 39)
    ("search", "trial.wait_prepared"),
    ("window", "orchestrate"), ("window", "solver.resolve"),
    ("window", "forecast"), ("window", "interval"),
    ("window", "launch.build"), ("window", "launch.init"),
    ("window", "launch.compile"), ("window", "readback"),
    ("window", "step_flops"), ("window", "ckpt.wait_pending"),
    ("window", "ckpt.snapshot"), ("window", "ckpt.write"),
    ("window", "ckpt.lane"),
    ("window", "ckpt.flush"), ("window", "journal.commit"),
    ("resume", "launch.restore"), ("fused", "fused_interval"),
]


@pytest.mark.parametrize("phase,name", PATH_SPANS,
                         ids=[n for _, n in PATH_SPANS])
def test_path_emits_span(tiny_run, phase, name):
    found = [e for e in spans_of(tiny_run[phase]) if e["kind"] == name]
    assert found, f"no {name!r} span in the {phase} events"
    for e in found:
        assert e["ts_start"] <= e["ts"] and e["dur_s"] >= 0
        assert e["root"] is not None and isinstance(e["thread"], str)


@pytest.mark.parametrize("phase", ["search", "window", "resume", "fused"])
def test_tree_is_sound(tiny_run, phase):
    events = tiny_run[phase]
    ids = {e["id"]: e for e in events if "id" in e}
    roots = [e for e in ids.values() if e["parent"] is None]
    assert len(roots) == 1 and roots[0]["kind"] in ("search", "orchestrate")
    for e in ids.values():
        assert e["root"] == roots[0]["id"]
        if e["parent"] is None:
            continue
        assert e["parent"] in ids, f"{e['kind']} has no parent event"
        p = ids[e["parent"]]
        if "dur_s" not in e or "dur_s" not in p:
            continue  # task_interval is stamped by hand, with its own meaning
        if e["kind"] in ("ckpt.write", "ckpt.lane"):
            continue  # outlive their snapshot by design; the root holds them
        assert p["ts_start"] - 0.005 <= e["ts_start"], (e["kind"], p["kind"])
        assert e["ts"] <= p["ts"] + 0.005, (e["kind"], p["kind"])
    for e in spans_of(events):
        if e["kind"] in ("ckpt.write", "ckpt.lane"):
            assert e["ts"] <= roots[0]["ts"] + 0.005  # joined inside the root


def _inside(stretches, lo, hi):
    return interval_math.length(interval_math.clip(stretches, lo, hi))


def test_leaves_cover_the_orchestrate_wall(tiny_run):
    """The CPU twin of the benchmark's ``idle_unattributed``, held by
    structure and not as a share of a wall clock that five other workers
    load: what no leaf span below ``orchestrate`` accounts for lies in
    stretches that have a name, each bounded by the spans before and after
    it: before the first solve, between the solve and the interval (the
    forecast lies in it), after the interval (the journal's commit and the
    checkpoint flush lie in it). Those are the call's fixed costs (5-30 ms
    each alone: bounded in seconds, a share of a 0.5 s window says nothing);
    inside the interval, where the steps are, under a tenth is unaccounted
    (under a hundredth alone). A gang's dispatch loop holds no span (the
    cost rule), so its steps are ``task_interval``'s own [ts_start, ts]."""
    events = tiny_run["window"]
    (root,) = [e for e in events if e["kind"] == "orchestrate"]
    (interval,) = [e for e in events if e["kind"] == "interval"]
    solve = min((e for e in events if e["kind"] == "solver.resolve"),
                key=lambda e: e["ts_start"])
    parents = {e["parent"] for e in events if "id" in e}
    leaves = [(e["ts_start"], e["ts"]) for e in spans_of(events)
              if e["id"] not in parents]
    leaves += [(e["ts_start"], e["ts"]) for e in events
               if e["kind"] == "task_interval"]
    lo, hi = root["ts_start"], root["ts"]
    gaps = interval_math.subtract([(lo, hi)], leaves)
    named = {
        "before the first solve": (lo, solve["ts_start"]),
        "between the solve and the interval": (solve["ts"],
                                               interval["ts_start"]),
        "after the interval": (interval["ts"], hi),
    }
    seconds = {}
    for name, (s, e) in named.items():
        assert lo - 0.005 <= s <= e + 0.005 <= hi + 0.01, (name, s - lo, e - lo)
        seconds[name] = _inside(gaps, s, e)
        assert seconds[name] <= 1.0, (name, seconds)
    steps = _inside(gaps, interval["ts_start"], interval["ts"])
    assert steps <= 0.1 * interval["dur_s"], (steps, interval["dur_s"])
    # and that is all of it: no unaccounted stretch outside the four
    total = sum(e - s for s, e in gaps)
    assert total == pytest.approx(steps + sum(seconds.values()), abs=0.01)
    # the named spans that lie in the fixed stretches are where they belong
    by = {k: [e for e in events if e["kind"] == k]
          for k in ("forecast", "journal.commit", "ckpt.flush")}
    assert all(solve["ts"] - 0.005 <= e["ts_start"]
               and e["ts"] <= interval["ts_start"] + 0.005
               for e in by["forecast"]) and by["forecast"]
    assert all(e["ts_start"] >= interval["ts"] - 0.005
               for e in by["journal.commit"] + by["ckpt.flush"])


HANDOFFS = {
    "engine launcher thread": ("window", ("launch.build",), "launch-", "task_interval"),
    # its snapshot's sibling: the two overlap (PR 27)
    "checkpoint writer thread": ("window", ("ckpt.write",), "ckpt-", "task_interval"),
    # a lane of the save, started by the writer thread; its write's sibling
    # (PR 46; a tiny state takes one lane: the case below takes four)
    "checkpoint lane thread": ("window", ("ckpt.lane",), "ckpt-", "task_interval"),
    "trial thread": ("search", ("trial",), "trial-g1", "search"),
    # a grid point's chip half, on the thread that measures behind the trial
    # thread while that one prepares the next points (PR 37); a timed point's
    # ``trial.config`` is opened by the trial thread and closed by this one
    "measuring thread": ("search", ("trial.init", "trial.stage",
                                    "trial.timing"),
                         "meas-trial-g1", "trial.config"),
}


@pytest.mark.parametrize("who", sorted(HANDOFFS))
def test_handoff_in_the_program(tiny_run, who):
    phase, kinds, thread_prefix, parent_kind = HANDOFFS[who]
    events = tiny_run[phase]
    ids = {e["id"]: e for e in events if "id" in e}
    mine = [e for e in spans_of(events) if e["kind"] in kinds]
    assert {e["kind"] for e in mine} == set(kinds)
    for e in mine:
        assert e["thread"].startswith(thread_prefix), e["thread"]
        assert ids[e["parent"]]["kind"] == parent_kind
        above = ids[e["parent"]]
        if who == "measuring thread":
            # a timed point's ``trial.config`` names the thread it ended on,
            # this one; it was opened under the trial thread's ``trial``
            above = ids[above["parent"]]
            assert above["kind"] == "trial"
        assert (above.get("thread", "") != e["thread"]
                or kinds == ("launch.build",))
    if who == "engine launcher thread":
        # task_interval is the launcher thread's, its parent the main thread's
        for ti in (e for e in events if e["kind"] == "task_interval"):
            assert ids[ti["parent"]]["kind"] == "interval"
            assert ids[ti["parent"]]["thread"] == "MainThread"
        assert len({e["thread"] for e in mine}) == 2  # two gangs, two threads
    if who == "measuring thread":
        # one measuring thread a trial thread, named after it; the host's
        # half stays on the trial thread, under the same ``trial.config``
        assert len({e["thread"] for e in mine}) == 2
        for e in spans_of(events):
            if e["kind"] in ("trial.build", "trial.compile",
                             "trial.memory_check"):
                assert e["thread"].startswith("trial-g1"), e
                assert ids[e["parent"]]["kind"] == "trial.config"
                # closed by the thread the point ended on
                assert ids[e["parent"]]["thread"] in (
                    e["thread"], "meas-" + e["thread"])


@pytest.mark.parametrize("mode", ["save", "save_async"])
def test_one_write_span_a_save_and_one_lane_span_a_lane(
        sink, tmp_path, monkeypatch, mode):
    """PR 46: the benchmark's ``ckpt_write_gb_per_s`` divides the bytes by
    the seconds of the spans named ``ckpt.write``, so there is one a save
    however many lanes wrote it, with ``lanes`` on it; each lane has a
    ``ckpt.lane`` on a thread of its own, and ``ckpt_stall``'s three spans
    (the caller's two, the join) are what they were."""
    import numpy as np

    from saturn_tpu.utils import checkpoint as ckpt

    monkeypatch.setattr(ckpt, "_LANE_MIN_BYTES", 1)
    tree = {f"m{i}": np.full((64, 64 + i), i, np.float32) for i in range(6)}
    tree["step"] = np.asarray(3, np.int32)
    nbytes = sum(v.nbytes for v in tree.values())
    with metrics.span("task_interval_like") as outer:
        getattr(ckpt, mode)(str(tmp_path / "t.npz"), tree)
    ckpt.flush()
    assert not [t for t in threading.enumerate() if t.name.startswith("ckpt-")]
    (write,) = sink("ckpt.write")
    assert {"bytes", "lanes", "n_shards", "overlap_s", "starved_s",
            "path"} <= set(write)
    assert write["bytes"] == nbytes and write["n_shards"] == 7
    assert write["lanes"] == ckpt._LANES == 4 and write["path"] == "t.npz"
    assert write["thread"] == "ckpt-t.npz" and write["parent"] == outer.id
    assert 0 <= write["overlap_s"] <= write["dur_s"] + 1e-6
    lanes = sink("ckpt.lane")
    assert len(lanes) == write["lanes"]
    assert (sorted(e["thread"] for e in lanes)
            == [f"ckpt-t.npz.l{k}" for k in range(4)])
    assert sum(e["bytes"] for e in lanes) == write["bytes"]
    assert sum(e["n_members"] for e in lanes) == write["n_shards"]
    assert write["starved_s"] == pytest.approx(
        max(e["starved_s"] for e in lanes))
    files = sorted(e["file"] for e in lanes)
    assert files == sorted(n for n in os.listdir(tmp_path)
                           if ckpt._SHARD_RE.search(n))
    for e in lanes:
        assert {"bytes", "n_members", "starved_s", "file"} <= set(e)
        assert e["parent"] == outer.id and e["root"] == outer.id
        assert write["ts_start"] - 0.005 <= e["ts_start"]
        assert e["ts"] <= write["ts"] + 0.005 and "error" not in e
    # ckpt_stall's spans: the caller's thread, then the join(s)
    (wait,), (snap,) = sink("ckpt.wait_pending"), sink("ckpt.snapshot")
    me = threading.current_thread().name
    assert wait["thread"] == snap["thread"] == me
    assert wait["parent"] == snap["parent"] == outer.id
    assert snap["bytes"] == nbytes and snap["n_streamed"] == 7
    assert wait["path"] == snap["path"] == "t.npz"
    flushes = sink("ckpt.flush")
    assert [e["n_pending"] for e in flushes] == (
        [1, 0] if mode == "save" else [1])
    assert all(e["thread"] == me for e in flushes)


def test_handoff_to_the_solver_pool(tiny_run, sink, devices8):
    """The orchestrator's own submit shape, on a pool thread."""
    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.executor import orchestrator

    class Job:
        def __init__(self, name, strategies):
            self.name, self.strategies = name, strategies

        def feasible_strategies(self):
            return {g: s for g, s in self.strategies.items() if s.feasible}

    from saturn_tpu.core.strategy import Strategy

    jobs = [Job(n, {1: Strategy(object(), 1, {}, 4.0, 0.5)}) for n in ("p", "q")]
    topo = SliceTopology(list(devices8[:2]))
    with metrics.span("orchestrate") as root:
        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="solver") as pool:
            plan = pool.submit(
                orchestrator._resolve_under, metrics.current_span(),
                jobs, topo, None, 30.0, deadline=1.0, source="test",
            ).result()
    assert set(plan.assignments) == {"p", "q"}
    (e,) = sink("solver.resolve")
    assert e["parent"] == root.id and e["root"] == root.id
    assert e["thread"].startswith("solver") and e["source"] == "test"
    assert e["n_tasks"] == 2 and e["makespan_s"] == pytest.approx(plan.makespan)


def test_task_interval_keeps_its_fields_and_gains_ids(tiny_run):
    events = tiny_run["window"]
    mine = [e for e in events if e["kind"] == "task_interval"]
    assert sorted(e["task"] for e in mine) == ["span-a", "span-b"]
    old = {"task", "technique", "batches", "loss", "samples_per_sec",
           "per_batch_s", "window", "fused_windows", "coscheduled", "devices",
           "ts_launch", "ts_start", "elapsed_s", "losses", "ts", "kind"}
    for e in mine:
        assert old <= set(e) and {"id", "parent", "root"} <= set(e)
        assert "dur_s" not in e and "thread" not in e  # not a span record
        assert e["ts_launch"] <= e["ts_start"] <= e["ts"]
        assert len(e["losses"]) == e["batches"]
        assert 0 < e["elapsed_s"] <= e["ts"] - e["ts_launch"] + 0.005
        kids = {k["kind"] for k in events if k.get("parent") == e["id"]}
        assert {"launch.build", "launch.init", "launch.compile", "readback",
                "step_flops", "ckpt.wait_pending", "ckpt.snapshot"} <= kids
        # launch.* lie between ts_launch and ts_start, as the table says
        for k in events:
            if k.get("parent") == e["id"] and k["kind"].startswith("launch."):
                assert e["ts_launch"] - 0.005 <= k["ts_start"]
                assert k["ts"] <= e["ts_start"] + 0.005


def test_existing_kinds_keep_their_fields(tiny_run):
    s, w = tiny_run["search"], tiny_run["window"]
    (trial_a,) = [e for e in s if e["kind"] == "trial" and e["task"] == "span-a"]
    assert {"task", "size", "technique", "feasible", "per_batch_s",
            "est_total_s", "params", "host_fraction"} <= set(trial_a)
    configs = [e for e in s if e["kind"] == "trial_config"]
    spans = [e for e in s if e["kind"] == "trial.config"]
    assert len(configs) == len(spans) > 0  # one span per trial_config event
    assert all(e["outcome"] == "timed" for e in spans)
    assert all("id" not in e for e in configs)
    solves = [e for e in w if e["kind"] == "solve"]
    assert solves and all("plan" in e and "id" not in e for e in solves)
    (iv,) = [e for e in w if e["kind"] == "interval"]
    assert {"elapsed_s", "planned_s", "n_tasks", "failed", "preempted"} <= set(iv)
    assert iv["elapsed_s"] == pytest.approx(iv["dur_s"], abs=0.05)


def test_trial_config_says_how_the_fused_head_ran(tiny_run):
    """A grid point whose loss is the fused head + cross-entropy carries
    ``ce_plan``; off the chip the op computes through plain XLA ops, which the
    event says as None (on a chip: the blocks, the mode, the backward kernels'
    VMEM sums and what dx asked for; ``tests/test_tpu_compile.py`` reads one
    from a step built for a described v5e)."""
    configs = [e for e in tiny_run["search"] if e["kind"] == "trial_config"]
    assert configs and all("ce_plan" in e and e["ce_plan"] is None
                           for e in configs)


def test_fused_interval_has_a_start_and_elapsed(tiny_run):
    (e,) = [e for e in tiny_run["fused"] if e["kind"] == "fused_interval"]
    assert {"members", "n_members", "batches", "window", "per_step_s",
            "samples_per_sec", "losses", "detached", "faulted"} <= set(e)
    assert {"ts_launch", "ts_start", "elapsed_s", "dur_s", "id"} <= set(e)
    assert e["ts_start"] <= e["ts_launch"] <= e["ts"]
    assert 0 < e["elapsed_s"] <= e["dur_s"]
    kids = [k["kind"] for k in tiny_run["fused"] if k.get("parent") == e["id"]]
    assert kids.count("ckpt.snapshot") == 2  # one a member, on the gang's thread


def test_window_compiles_are_named(tiny_run):
    compiles = [e for e in tiny_run["window"] if e["kind"] == "compile"]
    assert compiles, "the tiny window compiles its read-back programs"
    for e in compiles:
        assert e["in_span"] is not None and e["program"]
    assert any("saturn_sentinel_fold" in e["program"] for e in compiles)
    in_search = [e for e in tiny_run["search"] if e["kind"] == "compile"]
    assert {"jit(saturn_window)", "jit(saturn_init)"} <= {
        e["program"] for e in in_search}
    assert {e["in_span"]["name"] for e in in_search
            if e["program"] == "jit(saturn_window)"} == {"trial.compile"}


def test_prior_shardflow_span(sink, tiny_task, devices8):
    """The admission controller's static prior (the one caller of the
    shardflow pass on a job's way in)."""
    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.service.admission import AdmissionController

    class Rec:
        job_id, name = "j1", "job-1"

    ctl = AdmissionController.__new__(AdmissionController)
    ctl.technique_names = ["dp"]
    ctl._synthesize_priors(Rec(), tiny_task, SliceTopology(list(devices8[:1])))
    (e,) = sink("prior.shardflow")
    assert e["task"] == "job-1" and "n_points" in e or "error" in e


# ---------------------------------------------------------- under a profiler
def test_profile_trace_holds_the_spans_and_no_python_calls(tmp_path):
    from saturn_tpu.utils.trace import profile_trace

    import jax.numpy as jnp

    def plain_python_function(x):
        return x + 1

    d = str(tmp_path / "trace")
    with metrics.scoped(str(tmp_path / "ev.jsonl")), profile_trace(d):
        with metrics.span("orchestrate"):
            with metrics.span("launch.build"):
                plain_python_function(1)
                jax.block_until_ready(jnp.ones((16, 16)) @ jnp.ones((16, 16)))
    found = []
    for base, _, files in os.walk(d):
        found += [os.path.join(base, f) for f in files if f.endswith(".xplane.pb")]
    assert len(found) == 1
    data = jax.profiler.ProfileData.from_file(found[0])
    names = {ev.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert {"saturn.orchestrate", "saturn.launch.build"} <= names
    # the Python tracer's per-call events ("$file.py:123 function") are absent
    assert not any(n.startswith("$") or "plain_python_function" in n
                   for n in names), sorted(names)[:20]
