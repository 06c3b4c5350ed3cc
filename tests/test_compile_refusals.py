"""Refusal records (``utils/aot_cache``): a program the chip's compiler
refuses for memory is recorded beside JAX's persistent compilation cache and
not compiled again; ``SPMDTechnique.search`` takes the refusal, fresh or
recorded, as the memory check's verdict.

CPU only. The compiler is a stand-in: ``lowered`` objects that count their
``compile()`` calls, and for the search a ``jax.stages.Lowered.compile`` that
refuses everything. The store's directory is handed in through the one
function that decides it (``profile_cache.maybe_enable_persistent_compile_cache``).
"""

import json
import os
import sys
import threading

import jax
import pytest

from saturn_tpu.utils import aot_cache, metrics, profile_cache
from saturn_tpu.utils.aot_cache import CompileRefused

HBM = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of memory "
       "in memory space hbm. Used 18.19G of 15.75G hbm. Exceeded hbm capacity "
       "by 2.44G.\n\nTotal hbm usage >= 18.44G:\n    reserved  258.00M")
VMEM = ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
        "allocating on stack for %transpose_jvp_saturn_ce_dw__.13")
ALLOC = ("RESOURCE_EXHAUSTED: Allocation (size=51539607552) would exceed "
         "memory (size=17179869184)")


class Lowered:
    """What ``load_or_compile`` needs of a ``jit(...).lower(...)`` result."""

    def __init__(self, body="%0 = add", raises=HBM, name="jit_saturn_window"):
        self.text = f"module @{name} attributes {{}} {{\n  {body}\n}}\n"
        self.raises = raises
        self.compiles = 0
        self._lock = threading.Lock()

    def as_text(self):
        return self.text

    def compile(self):
        with self._lock:
            self.compiles += 1
        if self.raises is None:
            return "executable"
        raise RuntimeError(self.raises)


@pytest.fixture()
def store(tmp_path, monkeypatch):
    """The persistent compile cache 'on' at a temp directory; the records'
    directory is returned."""
    root = tmp_path / "xla-cache"
    root.mkdir()
    monkeypatch.setattr(profile_cache, "maybe_enable_persistent_compile_cache",
                        lambda: str(root))
    monkeypatch.delenv("SATURN_TPU_AOT_CACHE", raising=False)
    return root / "saturn-refused"


def records(store):
    return sorted(os.listdir(store)) if store.exists() else []


def counts():
    s = aot_cache.stats()
    return s["refusals_fresh"], s["refusals_replayed"]


# ------------------------------------------------------------ record, replay
@pytest.mark.parametrize("message", [HBM, VMEM, ALLOC], ids=["hbm", "vmem", "alloc"])
def test_fresh_refusal_is_recorded_and_raised_typed(store, message):
    low = Lowered(raises=message)
    fresh0, replayed0 = counts()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(low)
    e = err.value
    assert e.refusal == "fresh" and e.program == "jit_saturn_window"
    assert str(e) == message and "RESOURCE_EXHAUSTED" in repr(e)
    assert isinstance(e, RuntimeError) and isinstance(e.__cause__, RuntimeError)
    assert e.first_line == message.splitlines()[0][:300]
    assert low.compiles == 1
    (name,) = records(store)
    assert name.endswith(".json") and ".tmp." not in name
    with open(store / name) as f:
        rec = json.load(f)
    assert rec["message"] == message and rec["program"] == "jit_saturn_window"
    assert rec["jax"] == jax.__version__
    assert rec["compiler"] == aot_cache._compiler_identity()
    assert set(rec) == {"program", "message", "jax", "compiler"}
    assert counts() == (fresh0 + 1, replayed0)


def test_second_call_replays_without_compiling(store):
    first, second = Lowered(), Lowered()  # same text, as in a later process
    with pytest.raises(CompileRefused):
        aot_cache.load_or_compile(first)
    fresh0, replayed0 = counts()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(second, devices=jax.devices()[:1])
    assert second.compiles == 0 and first.compiles == 1
    assert err.value.refusal == "recorded" and str(err.value) == HBM
    assert err.value.program == "jit_saturn_window"
    assert counts() == (fresh0, replayed0 + 1)
    assert len(records(store)) == 1


def test_device_block_is_not_part_of_the_key(store):
    """The verdict does not depend on which chips: a refusal for one block
    answers for every other."""
    devs = jax.devices()
    with pytest.raises(CompileRefused):
        aot_cache.load_or_compile(Lowered(), devices=devs[:4])
    again = Lowered()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(again, devices=devs[4:])
    assert again.compiles == 0 and err.value.refusal == "recorded"


def test_long_message_is_truncated_in_the_record_only(store):
    long = HBM + "\n" + "x" * 100_000
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(Lowered(raises=long))
    assert str(err.value) == long
    (name,) = records(store)
    assert os.path.getsize(store / name) < 16_384
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(Lowered(raises=long))
    assert err.value.refusal == "recorded" and str(err.value).startswith(HBM)


# -------------------------------------------------------------------- misses
def _refuse_then(change):
    """Refuse once, apply ``change``, and compile the 'same' program again:
    returns the second stand-in and the error it raised."""
    with pytest.raises(CompileRefused):
        aot_cache.load_or_compile(Lowered())
    second = change() or Lowered()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(second)
    return second, err.value


def test_other_program_text_misses(store, monkeypatch):
    second, e = _refuse_then(lambda: Lowered(body="%0 = multiply"))
    assert second.compiles == 1 and e.refusal == "fresh"
    assert len(records(store)) == 2


@pytest.mark.parametrize("variable", ["XLA_FLAGS", "LIBTPU_INIT_ARGS"])
def test_other_compiler_flags_miss(store, monkeypatch, variable):
    first = os.environ.get(variable, "")

    def change():
        monkeypatch.setenv(variable, first + " --xla_tpu_scoped_vmem_limit_kib=32768")
    second, e = _refuse_then(change)
    assert second.compiles == 1 and e.refusal == "fresh"
    assert len(records(store)) == 2
    # back under the first flags the first record still answers
    monkeypatch.setenv(variable, first)
    third = Lowered()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(third)
    assert third.compiles == 0 and err.value.refusal == "recorded"


def test_other_platform_version_misses(store, monkeypatch):
    real = aot_cache._compiler_identity()
    assert [part.split(":")[0] for part in real] == [
        "platform_version", "XLA_FLAGS", "LIBTPU_INIT_ARGS"]

    def change():
        monkeypatch.setattr(aot_cache, "_compiler_identity",
                            lambda: [real[0] + " libtpu-next"] + real[1:])
    second, e = _refuse_then(change)
    assert second.compiles == 1 and e.refusal == "fresh"
    assert len(records(store)) == 2


def test_platform_version_is_read_from_the_backend():
    version = str(jax.devices()[0].client.platform_version)
    assert f"platform_version:{version}" in aot_cache._compiler_identity()


@pytest.mark.parametrize("exc", [ValueError("INVALID_ARGUMENT: Mosaic failed to compile"),
                                 RuntimeError("INTERNAL: RET_CHECK failure"),
                                 KeyboardInterrupt()],
                         ids=["value", "internal", "interrupt"])
def test_other_exceptions_are_reraised_and_not_recorded(store, exc):
    low = Lowered()
    low.compile = lambda: (_ for _ in ()).throw(exc)
    before = counts()
    with pytest.raises(type(exc)) as err:
        aot_cache.load_or_compile(low)
    assert err.value is exc
    assert records(store) == [] and counts() == before


def test_a_program_that_compiles_leaves_nothing(store):
    low = Lowered(raises=None)
    before = counts()
    assert aot_cache.load_or_compile(low) == "executable"
    assert aot_cache.load_or_compile(low) == "executable"
    assert low.compiles == 2 and records(store) == [] and counts() == before


@pytest.mark.parametrize("content", [
    b"",
    b"{\"schema\": 1, \"message\": \"RESOURCE_EXHAU",          # cut short
    b"[1, 2, 3]",
    b"{\"schema\": 1}",
    b"{\"message\": 7}",
    b"{\"message\": \"all is well\"}",
    b"\xff\xfe\x00garbage",
], ids=["empty", "truncated", "list", "no-message", "number", "no-verdict", "bytes"])
def test_malformed_record_is_a_miss(store, content):
    with pytest.raises(CompileRefused):
        aot_cache.load_or_compile(Lowered())
    (name,) = records(store)
    with open(store / name, "wb") as f:
        f.write(content)
    # the compiler is asked again; had it changed its mind, that would stand
    fits = Lowered(raises=None)
    assert aot_cache.load_or_compile(fits) == "executable" and fits.compiles == 1
    # it has not: refused fresh, and the record is whole again
    low = Lowered()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(low)
    assert low.compiles == 1 and err.value.refusal == "fresh"
    with open(store / name) as f:
        assert json.load(f)["message"] == HBM


def test_unreadable_record_is_a_miss(store):
    with pytest.raises(CompileRefused):
        aot_cache.load_or_compile(Lowered())
    (name,) = records(store)
    os.unlink(store / name)
    os.mkdir(store / name)  # open() raises IsADirectoryError; so does replace()
    low = Lowered()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(low)
    assert low.compiles == 1 and err.value.refusal == "fresh"


def test_program_without_text_is_compiled_and_never_recorded(store):
    low = Lowered()
    low.as_text = lambda: (_ for _ in ()).throw(NotImplementedError())
    for _ in range(2):
        with pytest.raises(CompileRefused) as err:
            aot_cache.load_or_compile(low)
        assert err.value.refusal == "fresh" and err.value.program is None
    assert low.compiles == 2 and records(store) == []


# ------------------------------------------------------------------- threads
def test_threads_refusing_one_program_leave_one_valid_record(store):
    n = 16
    lows = [Lowered() for _ in range(n)]
    barrier = threading.Barrier(n)
    seen, lock = [], threading.Lock()

    def refuse(low):
        barrier.wait(timeout=30)
        try:
            aot_cache.load_or_compile(low)
        except CompileRefused as e:
            with lock:
                seen.append(e.refusal)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=refuse, args=(low,)) for low in lows]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert len(seen) == n and "fresh" in seen
    # whoever did not meet a whole record compiled; nobody read half a one
    assert sum(low.compiles for low in lows) == seen.count("fresh")
    (name,) = records(store)  # no temp file left either
    with open(store / name) as f:
        assert json.load(f)["message"] == HBM
    late = Lowered()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(late)
    assert late.compiles == 0 and err.value.refusal == "recorded"


# ----------------------------------------------------- no compile cache: off
def test_without_a_compile_cache_nothing_is_written(tmp_path, monkeypatch):
    """The CPU default: no persistent compile cache, so no store. The
    refusal is still typed; every call asks the compiler, as before."""
    monkeypatch.setattr(profile_cache, "maybe_enable_persistent_compile_cache",
                        lambda: None)
    monkeypatch.chdir(tmp_path)
    low = Lowered()
    before = counts()
    for i in range(3):
        with pytest.raises(CompileRefused) as err:
            aot_cache.load_or_compile(low)
        assert err.value.refusal == "fresh" and low.compiles == i + 1
        assert "RESOURCE_EXHAUSTED" in str(err.value)
    assert counts() == (before[0] + 3, before[1])
    assert os.listdir(tmp_path) == []
    fits = Lowered(raises=None)
    assert aot_cache.load_or_compile(fits) == "executable"


def test_the_store_is_off_on_the_cpu_by_default(monkeypatch):
    """No switch of its own: on exactly when JAX's persistent cache is."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    profile_cache.maybe_enable_persistent_compile_cache.cache_clear()
    try:
        assert profile_cache.maybe_enable_persistent_compile_cache() is None
        assert aot_cache._refusal_path(aot_cache._program(Lowered())) is None
    finally:
        profile_cache.maybe_enable_persistent_compile_cache.cache_clear()


def test_independent_of_the_executable_cache(store, tmp_path, monkeypatch):
    """``SATURN_TPU_AOT_CACHE=1`` (serialized executables) on or off, the
    refusal is recorded and replayed the same, and never stored as one."""
    monkeypatch.setenv("SATURN_TPU_AOT_CACHE", "1")
    monkeypatch.setenv("SATURN_TPU_PROFILE_CACHE_DIR", str(tmp_path / "profiles"))
    before = aot_cache.stats()
    with pytest.raises(CompileRefused):
        aot_cache.load_or_compile(Lowered())
    again = Lowered()
    with pytest.raises(CompileRefused) as err:
        aot_cache.load_or_compile(again)
    assert again.compiles == 0 and err.value.refusal == "recorded"
    assert aot_cache.stats()["stores"] == before["stores"]


def test_prewarm_meets_the_record_too(store):
    with pytest.raises(CompileRefused):
        aot_cache.prewarm(Lowered())
    again = Lowered()
    with pytest.raises(CompileRefused) as err:
        aot_cache.prewarm(again)
    assert again.compiles == 0 and err.value.refusal == "recorded"


# ------------------------------------------------- search: a memory verdict
SEQ, BATCH, VOCAB = 32, 4, 256


def _task(save_dir, name):
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
        hparams=HParams(lr=1e-3, batch_count=16),
        chip_range=[1], name=name, save_dir=save_dir,
    )


@pytest.fixture()
def refusing_compiler(monkeypatch):
    """Every ahead-of-time compile is refused as the chip refuses a program
    over its HBM; the calls are counted."""
    calls = []

    def compile(self, *a, **kw):
        calls.append(self)
        raise RuntimeError(HBM)

    monkeypatch.setattr(jax.stages.Lowered, "compile", compile)
    return calls


def _search(tmp_path, devices, tag, name="refused-a"):
    """One ``saturn_tpu.search`` of one tiny job over dp's grid with a fresh
    technique instance (no bundle cache): stats, events, the technique."""
    import saturn_tpu
    from saturn_tpu import library
    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.parallel.dp import DataParallel

    registry = dict(library._REGISTRY)
    try:
        library.register("dp", DataParallel)
        ev = str(tmp_path / f"{tag}.jsonl")
        task = _task(str(tmp_path / "ck"), name)
        stats = saturn_tpu.search(
            [task], technique_names=["dp"], topology=SliceTopology(list(devices[:1])),
            metrics_path=ev, profile_cache=False)
        return stats, metrics.read_events(ev), task
    finally:
        library._REGISTRY.clear()
        library._REGISTRY.update(registry)


def _by_remat(points):
    """(the points the compiler saw: ``remat: True``; those that ended over
    memory because their ``remat: True`` twin had: never built)."""
    seen = [e for e in points if e["config"].get("remat") is True]
    implied = [e for e in points if e["config"].get("remat") is not True]
    assert seen and implied and all(e.get("implied_by") == "remat" for e in implied)
    return seen, implied


def test_search_takes_refusals_as_the_memory_verdict(
        store, tmp_path, devices8, refusing_compiler):
    stats, events, task = _search(tmp_path, devices8, "first")
    points = [e for e in events if e["kind"] == "trial_config"]
    assert len(points) >= 2  # dp's grid: remat x attention
    seen, implied = _by_remat(points)
    n = len(seen)
    assert stats["errors"] == 0 and stats["first_error"] is None
    assert stats["refusals_fresh"] == n and stats["refusals_replayed"] == 0
    assert len(refusing_compiler) == n and len(records(store)) >= 1
    for e in seen:
        assert e["memory_rejected"] is True and e["refusal"] == "fresh"
        assert e["compiler"] == HBM.splitlines()[0]
        assert "error" not in e and "per_batch_s" not in e
    for e in implied:   # over memory with rematerialisation: over it without
        assert e["memory_rejected"] is True and "refusal" not in e
        assert "error" not in e and "per_batch_s" not in e and "step_traces" not in e
    spans = [e for e in events if e["kind"] == "trial.config"]
    assert [e["outcome"] for e in spans] == ["refused"] * n + ["memory_rejected"] * len(implied)
    assert [e.get("refusal") for e in spans] == ["fresh"] * n + [None] * len(implied)
    compiles = [e for e in events if e["kind"] == "trial.compile"]
    assert [e["refusal"] for e in compiles] == ["fresh"] * n
    assert all(e["error"] == "CompileRefused" for e in compiles)
    assert len([e for e in events if e["kind"] == "trial.build"]) == n
    (trial,) = [e for e in events if e["kind"] == "trial"]
    assert trial["feasible"] is False and trial["memory_infeasible"] is True
    assert not task.feasible_strategies()

    # a later sweep of the same programs: the records answer, no compile
    stats, events, _ = _search(tmp_path, devices8, "second")
    assert len(refusing_compiler) == n
    assert stats["errors"] == 0
    assert stats["refusals_fresh"] == 0 and stats["refusals_replayed"] == n
    seen, implied = _by_remat([e for e in events if e["kind"] == "trial_config"])
    assert [e["refusal"] for e in seen] == ["recorded"] * n
    assert all(e["memory_rejected"] is True and e["compiler"] == HBM.splitlines()[0]
               for e in seen)
    assert all(e["memory_rejected"] is True for e in implied)
    assert [e["refusal"] for e in events if e["kind"] == "trial.compile"] == ["recorded"] * n
    assert [e["outcome"] for e in events if e["kind"] == "trial.config"] == \
        ["refused"] * n + ["memory_rejected"] * len(implied)
    assert not [e for e in events if e["kind"] == "compile"
                and "saturn_window" in e["program"]]


def test_search_report_says_memory_infeasible(store, devices8, refusing_compiler, tmp_path):
    """``SPMDTechnique.search`` itself: every config refused, or over memory
    because its ``remat: True`` twin was refused => the report the
    evaluator's monotone pruning reads says memory, with no error."""
    from saturn_tpu.parallel.dp import DataParallel

    tech = DataParallel()
    task = _task(str(tmp_path / "ck"), "refused-b")
    assert tech.search(task, list(devices8[:1]), 0) == (None, None)
    report = tech.search_report(task.name, 1)
    n = report["configs"]
    assert n >= 2 and report["memory_infeasible"] is True
    assert report["memory_rejected"] == n and report["errors"] == 0
    assert report["first_error"] is None
    # the compiler saw the ``remat: True`` half of the grid
    assert report["refusals_fresh"] + report["refusals_replayed"] == n // 2
    assert len(refusing_compiler) == n // 2


def test_a_refusal_from_running_is_an_error_and_is_not_recorded(
        store, devices8, tmp_path, monkeypatch):
    """Only the compile call records: RESOURCE_EXHAUSTED out of a program
    that *runs* (an init, a step) depends on what else the chip holds, so
    it stays a config that raised."""
    from saturn_tpu.parallel.dp import DataParallel

    def no_room(self, task, prepared):
        raise RuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer")

    monkeypatch.setattr(DataParallel, "_measure", no_room)
    tech = DataParallel()
    task = _task(str(tmp_path / "ck"), "refused-c")
    ev = str(tmp_path / "ev.jsonl")
    with metrics.scoped(ev):
        assert tech.search(task, list(devices8[:1]), 0) == (None, None)
    report = tech.search_report(task.name, 1)
    assert report["errors"] == report["configs"] >= 2
    assert report["memory_infeasible"] is False and report["memory_rejected"] == 0
    assert report["refusals_fresh"] == 0 and report["refusals_replayed"] == 0
    assert "RESOURCE_EXHAUSTED" in report["first_error"]
    events = metrics.read_events(ev)
    assert all("RESOURCE_EXHAUSTED" in e["error"] and "refusal" not in e
               for e in events if e["kind"] == "trial_config")
    assert {e["outcome"] for e in events if e["kind"] == "trial.config"} == {"error"}
    assert records(store) == []


# ------------------------------------- the program's own memory rule (PR 42)
class Weighed(Lowered):
    """A program the compiler accepts, whose compile leaves an entry in
    JAX's cache directory as the persistent cache does."""

    class Executable:
        pass

    def __init__(self, root, **kw):
        super().__init__(raises=None, **kw)
        self.root = root

    def compile(self):
        super().compile()
        for suffix in ("-cache", "-atime"):
            (self.root / f"jit_saturn_window-0123abcd{suffix}").write_bytes(b"x")
        return self.Executable()


def test_a_program_the_memory_rule_rejects_is_recorded_and_its_entry_removed(store):
    root = store.parent
    (root / "jit_saturn_window-other-cache").write_bytes(b"y")   # another program's
    low = Weighed(root)
    exe = aot_cache.load_or_compile(low)
    assert low.compiles == 1 and sorted(os.listdir(root)) == [
        "jit_saturn_window-0123abcd-atime", "jit_saturn_window-0123abcd-cache",
        "jit_saturn_window-other-cache"]
    assert aot_cache.reject(exe, 15 * 2 ** 30, 15.75 * 2 ** 30) is True
    assert sorted(os.listdir(root)) == ["jit_saturn_window-other-cache", "saturn-refused"]
    (record,) = records(store)
    with open(store / record) as f:
        said = json.load(f)["message"]
    assert said.startswith("RESOURCE_EXHAUSTED: the program's memory rule")
    assert "15.000 GiB" in said and "0.92 x 15.750 GiB" in said
    fresh0, replayed0 = counts()
    with pytest.raises(CompileRefused) as err:      # the next search: not compiled, not read
        aot_cache.load_or_compile(Weighed(root))
    assert err.value.refusal == "recorded" and "memory rule" in err.value.first_line
    assert counts() == (fresh0, replayed0 + 1)
    assert aot_cache.reject(exe, 1, 1) is False      # said once
    assert aot_cache.reject("executable", 1, 1) is False   # not a compile of this process


def test_the_memory_check_records_what_it_rejects(store, monkeypatch, tmp_path):
    from saturn_tpu.parallel import spmd_base
    from saturn_tpu.parallel.dp import DataParallel

    exe = aot_cache.load_or_compile(Weighed(store.parent))
    monkeypatch.setattr(spmd_base, "device_hbm_bytes", lambda d: 16 * 2 ** 30)
    monkeypatch.setattr(spmd_base, "hbm_bytes_required", lambda c: 15 * 2 ** 30)
    events = str(tmp_path / "ev.jsonl")
    with metrics.scoped(events):
        assert DataParallel()._fits_compiled(exe, [object()]) is False
    (span,) = metrics.read_events(events, kind="trial.memory_check")
    assert span["recorded"] is True and len(records(store)) == 1
    monkeypatch.setattr(spmd_base, "hbm_bytes_required", lambda c: 14 * 2 ** 30)
    assert DataParallel()._fits_compiled(aot_cache.load_or_compile(
        Weighed(store.parent, body="%0 = mul")), [object()]) is True
    assert len(records(store)) == 1


# --------------------------- a stack the techniques cannot rebuild (PR 45)
@pytest.mark.parametrize("name, reason", [
    ("pp", "several block kinds"), ("ep", "exchange of tokens"),
    ("ring", None), ("ulysses", None)])
def test_a_ling_task_is_refused_with_a_reason_on_the_trial_config_span(
        name, reason, tmp_path, devices8):
    """``pp`` stages a stack of one kind and ``ep`` would need the exchange of
    token rows between shares: every grid point ends as an infeasible
    ``trial.config`` span that says so. A KDA layer's state crosses the whole
    sequence, so the model says it is not sequence-parallel and ``ring`` /
    ``ulysses`` offer no grid point at all."""
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES
    from tests import test_ling_techniques as ling_tests

    tech, devices = BUILTIN_TECHNIQUES[name](), list(devices8[:4])
    task = ling_tests._task(tmp_path, f"ling-refused-{name}", batch=4)
    configs = tech.candidate_configs(task, len(devices))
    if reason is None:
        assert not configs and task.get_model().hints["seq_parallel"] is False
        events = str(tmp_path / "ev.jsonl")
        with metrics.scoped(events):
            assert tech.search(task, devices, 0) == (None, None)
        assert not metrics.read_events(events, kind="trial.config")
        return
    ling_tests.refused(tech, task, devices, configs, tmp_path, reason)


def test_a_non_zero_swiglu_limit_refuses_at_build():
    from saturn_tpu.models.gpt2 import build_ling

    with pytest.raises(ValueError, match="clamp is not built"):
        build_ling("ling-test-tiny", swiglu_limit=7.0)
    assert build_ling("ling-test-tiny", swiglu_limit=0.0).config.swiglu_limit == 0.0
