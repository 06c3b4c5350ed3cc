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

    # a later sweep of the same programs: the records answer, no compile.
    # Each point ends on its point record first, with nothing built (PR 47);
    # nothing is timed, so rule 4 runs them again in full, and there the
    # text-keyed records answer as before.
    stats, events, _ = _search(tmp_path, devices8, "second")
    assert len(refusing_compiler) == n
    assert stats["errors"] == 0
    assert stats["refusals_fresh"] == 0 and stats["refusals_replayed"] == n
    assert stats["refusals_unbuilt"] == 0   # the report is of the full walk
    notes = [e for e in events if e["kind"] == "trial_config"]
    unbuilt, full = notes[:len(notes) // 2], notes[len(notes) // 2:]
    assert [e.get("unbuilt") for e in unbuilt if e["config"]["remat"]] == [True] * n
    assert all("unbuilt" not in e for e in full)
    seen, implied = _by_remat(full)
    assert [e["refusal"] for e in seen] == ["recorded"] * n
    assert all(e["memory_rejected"] is True and e["compiler"] == HBM.splitlines()[0]
               for e in seen)
    assert all(e["memory_rejected"] is True for e in implied)
    assert [e["refusal"] for e in events if e["kind"] == "trial.compile"] == ["recorded"] * n
    assert [e["outcome"] for e in events if e["kind"] == "trial.config"] == \
        (["refused"] * n + ["memory_rejected"] * len(implied)) * 2
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
    monkeypatch.setattr(spmd_base, "hbm_limit", lambda d: 16 * 2 ** 30)
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


@pytest.mark.parametrize("name, reason", [
    ("pp", "several block kinds"), ("ep", "exchange of tokens"),
    ("ring", None), ("ulysses", None)])
def test_a_smallthinker_task_is_refused_with_a_reason_on_the_trial_config_span(
        name, reason, tmp_path, devices8):
    """(PR 49) ``pp`` stages a stack of one kind and ``ep`` would need the
    exchange of token rows between shares: every grid point ends as an
    infeasible ``trial.config`` span that says so, none fails inside a trace.
    A sliding layer's mask and a routed layer are single-program, so the
    model says it is not sequence-parallel and ``ring`` / ``ulysses`` offer no
    grid point at all. (A technique whose block walk had to carry a route
    across a mixer would refuse here too: none has to, the walk's unit is the
    period and a route never leaves its block; fsdp / tp overlap and
    offload's stream run it against the reference in
    ``tests/test_smallthinker_techniques.py``.)"""
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES
    from tests import test_smallthinker_techniques as st_tests

    tech, devices = BUILTIN_TECHNIQUES[name](), list(devices8[:4])
    task = st_tests._task(tmp_path, f"smallthinker-refused-{name}", batch=4)
    configs = tech.candidate_configs(task, len(devices))
    if reason is None:
        return st_tests.offers_nothing(tech, task, devices, configs, tmp_path)
    st_tests.refused(tech, task, devices, configs, tmp_path, reason)


def test_a_non_zero_swiglu_limit_refuses_at_build():
    from saturn_tpu.models.gpt2 import build_ling

    with pytest.raises(ValueError, match="clamp is not built"):
        build_ling("ling-test-tiny", swiglu_limit=7.0)
    assert build_ling("ling-test-tiny", swiglu_limit=0.0).config.swiglu_limit == 0.0


# ----------------- point records: the verdict by what a point is made from (PR 47)
from saturn_tpu.utils import point_records  # noqa: E402


def point_records_of(store):
    return [n for n in records(store) if n.startswith("point-")]


def _made(save_dir, name="made-a", seq=SEQ, batch=BATCH, seed=3, optimizer="adamw",
          **model):
    """``_task`` with whatever a case changes about what the point is made from."""
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    return Task(
        get_model=lambda **kw: build_gpt2("test-tiny", seq_len=seq, **model, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=seq, batch_size=batch, vocab_size=VOCAB,
            n_tokens=seq * batch * 8, seed=seed),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=16, optimizer=optimizer),
        chip_range=[1], name=name, save_dir=save_dir,
    )


def _dp():
    from saturn_tpu.parallel.dp import DataParallel

    return DataParallel()


def _record(task, devices, config=None, k=8, tech=None):
    return point_records.of(tech or _dp(), task, list(devices),
                            config or {"remat": False}, k)


class Choosy:
    """Mixed into dp: a grid of two, whose ``remat: False`` point ends in full
    as ``verdict`` says, after a real build; what was built is counted."""

    verdict = "refused"

    def __init__(self):
        super().__init__()
        self.built = []

    def candidate_configs(self, task, n_devices):
        return [{"remat": False}, {"remat": True}]

    def build(self, task, devices, config, use_cache=True):
        self.built.append(dict(config))
        return super().build(task, devices, config, use_cache)

    def _prepare(self, task, devices, config):
        if config["remat"] is True or self.verdict == "fits":
            return super()._prepare(task, devices, config)
        self._spanned_build("trial.build", task, devices, config)
        if self.verdict == "refused":
            raise CompileRefused(HBM, "fresh", "jit_saturn_window")
        if self.verdict == "memory_rejected":
            return None
        if self.verdict == "infeasible":
            from saturn_tpu.core.technique import InfeasibleConfig

            raise InfeasibleConfig("batch_size 4 not divisible by data=3")
        raise ValueError("kernel variant failed to lower")


from saturn_tpu.parallel.dp import DataParallel  # noqa: E402


class ChoosyDP(Choosy, DataParallel):
    """At module level: a technique class made inside a function has no
    name to be found by again, and its points no identity."""


def _choosy(verdict):
    tech = ChoosyDP()
    tech.verdict = verdict
    return tech


def _choosy_search(tmp_path, devices, verdict, tag, task=None):
    tech = _choosy(verdict)
    task = task or _task(str(tmp_path / "ck"), "choosy")
    ev = str(tmp_path / f"{tag}.jsonl")
    with metrics.scoped(ev):
        best = tech.search(task, list(devices[:1]), 0)
    notes = {e["config"]["remat"]: e for e in metrics.read_events(ev, kind="trial_config")}
    return tech, best, tech.search_report(task.name, 1), notes, metrics.read_events(ev)


@pytest.mark.parametrize("verdict", ["refused", "memory_rejected"])
def test_a_point_over_memory_in_full_is_not_built_by_the_next_search(
        store, tmp_path, devices8, verdict):
    tech, best, report, notes, _ = _choosy_search(tmp_path, devices8, verdict, "first")
    assert best[0] == {"remat": True} and report["memory_rejected"] == 1
    assert report["refusals_unbuilt"] == 0 and "unbuilt" not in notes[False]
    assert {"remat": False} in tech.built and notes[False]["step_traces"] == 1
    (name,) = point_records_of(store)
    with open(store / name) as f:
        rec = json.load(f)
    assert rec["outcome"] == verdict and rec["schema"] == point_records.SCHEMA_VERSION
    assert rec["compiler"] == (HBM.splitlines()[0] if verdict == "refused" else None)
    assert "saturn_tpu.parallel.spmd_base" in rec["manifest"]
    assert __name__ in rec["manifest"]          # the caller's own module
    assert not any(n.split(".")[0] in ("jax", "numpy", "optax", "json")
                   for n in rec["manifest"])
    assert "choosy" not in json.dumps({k: v for k, v in rec.items() if k != "manifest"})

    unbuilt0 = aot_cache.stats()["refusals_unbuilt"]
    tech, best, report, notes, events = _choosy_search(tmp_path, devices8, verdict, "second")
    assert best[0] == {"remat": True}
    assert {"remat": False} not in tech.built   # ``build`` not called for it
    e = notes[False]
    assert e["unbuilt"] is True and e["refusal"] == "recorded"
    assert e["memory_rejected"] is True and "step_traces" not in e
    assert e.get("compiler") == rec["compiler"]
    (span,) = [s for s in events if s["kind"] == "trial.config"
               and s["config"] == {"remat": False}]
    assert span["outcome"] == verdict and span["unbuilt"] is True
    assert span["refusal"] == "recorded"
    inside = [s["kind"] for s in events if s.get("parent") == span["id"]]
    assert inside == ["trial.identity"]         # no trial.build, no trial.compile
    (ident,) = [s for s in events if s["kind"] == "trial.identity"
                and s["parent"] == span["id"]]
    assert ident["identity"] is True and ident["hit"] is True and ident["dur_s"] < 5.0
    assert report["memory_rejected"] == 1 and report["memory_infeasible"] is False
    assert report["refusals_unbuilt"] == 1 and report["refusals_replayed"] == 1
    assert aot_cache.stats()["refusals_unbuilt"] == unbuilt0 + 1
    assert "unbuilt" not in notes[True] and notes[True]["step_traces"] == 1


@pytest.mark.parametrize("verdict", ["infeasible", "error", "fits"])
def test_another_end_leaves_no_point_record_and_takes_a_standing_one_away(
        store, tmp_path, devices8, verdict):
    _choosy_search(tmp_path, devices8, "refused", "first")
    assert len(point_records_of(store)) == 1
    # the record is stale (its cause has gone): make it miss, as an edited
    # file would, so that the point takes the full path and ends otherwise
    point_records._hashes.clear()
    (name,) = point_records_of(store)
    with open(store / name) as f:
        rec = json.load(f)
    rec["manifest"][__name__][1] = "0" * 64
    with open(store / name, "w") as f:
        json.dump(rec, f)
    tech, _, report, notes, _ = _choosy_search(tmp_path, devices8, verdict, "second")
    assert {"remat": False} in tech.built and "unbuilt" not in notes[False]
    assert report["refusals_unbuilt"] == 0
    assert point_records_of(store) == []


def test_a_refusal_from_running_leaves_no_point_record(store, tmp_path, devices8, monkeypatch):
    from saturn_tpu.parallel.dp import DataParallel

    def no_room(self, task, prepared):
        raise RuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer")

    monkeypatch.setattr(DataParallel, "_measure", no_room)
    _, best, report, notes, _ = _choosy_search(tmp_path, devices8, "fits", "ran")
    assert best == (None, None) and report["errors"] == 2
    assert point_records_of(store) == []


def test_an_implied_end_leaves_no_point_record(store, tmp_path, devices8, refusing_compiler):
    _, events, _ = _search(tmp_path, devices8, "implied")
    seen, implied = _by_remat([e for e in events if e["kind"] == "trial_config"])
    assert len(point_records_of(store)) == len(seen)   # none for an implied point


#: what changes about a point -> the record written before the change misses
def _miss_grid_config(ctx):
    return dict(config={"remat": False, "attention": "dense"})


def _miss_window(ctx):
    return dict(k=1)


def _miss_batch_shape(ctx):
    return dict(task=_made(ctx.ck, batch=BATCH * 2))


def _miss_parameter_shape(ctx):
    # the same config, another tree: as the benchmark does, ``init_fn`` replaced
    import dataclasses

    import jax.numpy as jnp

    task = _made(ctx.ck)
    inner = task._get_model
    task._get_model = lambda **kw: dataclasses.replace(
        inner(**kw), init_fn=lambda rng: {"w": jnp.zeros((3, 5))})
    return dict(task=task)


def _miss_spec_config(ctx):
    return dict(task=_made(ctx.ck, rope_theta=5e5))


def _miss_hbm_limit(ctx):
    ctx.monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(8 * 2 ** 30))
    return {}


def _miss_device_count(ctx):
    return dict(devices=ctx.devices[:2])


def _miss_saturn_variable(ctx):
    ctx.monkeypatch.setenv("SATURN_TPU_CE_BLOCK", "256")
    return {}


def _miss_xla_flags(ctx):
    ctx.monkeypatch.setenv(
        "XLA_FLAGS", os.environ.get("XLA_FLAGS", "") + " --xla_tpu_scoped_vmem_limit_kib=32768")
    return {}


def _miss_platform_version(ctx):
    real = aot_cache._compiler_identity()
    ctx.monkeypatch.setattr(aot_cache, "_compiler_identity",
                            lambda: [real[0] + " libtpu-next"] + real[1:])
    return {}


def _miss_lr(ctx):
    task = _made(ctx.ck)
    task.hparams.lr = 3e-4
    return dict(task=task)


def _miss_technique(ctx):
    from saturn_tpu.parallel.fsdp import FSDP

    return dict(tech=FSDP())


def _miss_file_edited(ctx):
    with open(ctx.helper, "a") as f:
        f.write("\nTILE = 256\n")
    point_records._hashes.clear()       # as a new process would find it
    return {}


def _miss_file_gone(ctx):
    os.unlink(ctx.helper)
    point_records._hashes.clear()
    return {}


MISSES = {f.__name__[len("_miss_"):]: f for f in (
    _miss_grid_config, _miss_window, _miss_batch_shape, _miss_parameter_shape,
    _miss_spec_config, _miss_hbm_limit, _miss_device_count, _miss_saturn_variable,
    _miss_xla_flags, _miss_platform_version, _miss_lr, _miss_technique,
    _miss_file_edited, _miss_file_gone)}


@pytest.mark.parametrize("case", sorted(MISSES))
def test_a_point_made_of_anything_else_misses(store, tmp_path, devices8, monkeypatch, case):
    import importlib.util
    import types

    helper = tmp_path / "callers_helper.py"
    helper.write_text("TILE = 128\n")
    spec = importlib.util.spec_from_file_location("callers_helper_pr47", helper)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    monkeypatch.setitem(sys.modules, "callers_helper_pr47", module)
    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(16 * 2 ** 30))
    ctx = types.SimpleNamespace(ck=str(tmp_path / "ck"), monkeypatch=monkeypatch,
                                devices=list(devices8), helper=str(helper))
    first = dict(task=_made(ctx.ck), devices=ctx.devices[:1])
    written = _record(**first)
    assert written.path is not None and written.verdict is None
    written.note("refused", "RESOURCE_EXHAUSTED: over")
    point_records._hashes.clear()
    again = _record(**dict(first, task=_made(ctx.ck)))
    assert again.path == written.path and again.verdict == {
        "outcome": "refused", "compiler": "RESOURCE_EXHAUSTED: over"}
    changed = _record(**dict(first, **MISSES[case](ctx)))
    assert changed.verdict is None
    if case.startswith("file_"):
        assert changed.path == written.path     # the key stands, the manifest fails
        changed.note("memory_rejected")         # the full path rewrites the record
        if case == "file_edited":
            assert _record(**first).verdict["outcome"] == "memory_rejected"
    else:
        assert changed.path not in (None, written.path)


def test_the_datas_seed_and_the_tasks_name_are_not_in_the_key(store, tmp_path, devices8):
    ck = str(tmp_path / "ck")
    one = _record(_made(ck, name="job-a", seed=3), devices8[:1])
    other = _record(_made(str(tmp_path / "elsewhere"), name="job-b", seed=2 ** 31 + 11),
                    devices8[4:5])
    assert one.path is not None and one.path == other.path


def _closure_optimizer(scale):
    import optax

    def make(lr):
        return optax.sgd(lr * scale)
    return make


def _module_level_optimizer(lr):
    import optax

    return optax.sgd(lr)


def test_what_cannot_be_written_down_is_no_identity(store, tmp_path, devices8):
    ck = str(tmp_path / "ck")
    named = _record(_made(ck, optimizer=_module_level_optimizer), devices8[:1])
    assert named.path is not None and named.path != _record(_made(ck), devices8[:1]).path
    task = _made(ck, optimizer=_closure_optimizer(0.5))
    ev = str(tmp_path / "ev.jsonl")
    with metrics.scoped(ev):
        closure = _record(task, devices8[:1])
    assert closure.path is None and closure.verdict is None
    (span,) = metrics.read_events(ev, kind="trial.identity")
    assert span["identity"] is False and "no module-level name" in span["why"]
    closure.note("refused", "x")
    assert point_records_of(store) == []
    # ... and the search takes today's path: built in every search, no record
    for tag in ("first", "second"):
        tech, best, report, notes, _ = _choosy_search(
            tmp_path, devices8, "refused", tag, task=task)
        assert {"remat": False} in tech.built and "unbuilt" not in notes[False]
        assert report["refusals_unbuilt"] == 0 and best[0] == {"remat": True}
    assert point_records_of(store) == []


def test_a_name_whose_source_nothing_vouches_for_is_no_identity(
        store, tmp_path, devices8, monkeypatch):
    """A function of a module without a source file (a notebook's or a
    ``python -c``'s ``__main__``) has a module-level name and can change
    without a trace: neither a version string nor the manifest covers it."""
    import types

    notebook = types.ModuleType("notebook_main_pr47")
    exec("import optax\ndef make(lr):\n    return optax.sgd(lr)\n", notebook.__dict__)
    monkeypatch.setitem(sys.modules, "notebook_main_pr47", notebook)
    assert notebook.make.__module__ == "notebook_main_pr47"
    point_records._vouched.cache_clear()
    ev = str(tmp_path / "ev.jsonl")
    with metrics.scoped(ev):
        rec = _record(_made(str(tmp_path / "ck"), optimizer=notebook.make), devices8[:1])
    assert rec.path is None
    (span,) = metrics.read_events(ev, kind="trial.identity")
    assert "nothing vouches" in span["why"]
    point_records._vouched.cache_clear()


@pytest.mark.parametrize("value", [
    jax.numpy.zeros((2,)), threading.Lock(), lambda: threading.Lock()],
    ids=["array", "lock", "closure-free-lambda"])
def test_a_spec_that_holds_what_has_no_canonical_form(store, tmp_path, devices8, value):
    task = _made(str(tmp_path / "ck"))
    inner = task._get_model

    def get_model(**kw):
        spec = inner(**kw)
        spec.hints["extra"] = value
        return spec
    task._get_model = get_model
    rec = _record(task, devices8[:1])
    # a lambda without free variables is written down by its name: an identity
    assert (rec.path is None) == (not callable(value))


def test_without_a_compile_cache_a_point_has_no_record(tmp_path, devices8, monkeypatch):
    monkeypatch.setattr(profile_cache, "maybe_enable_persistent_compile_cache",
                        lambda: None)
    monkeypatch.chdir(tmp_path)
    rec = _record(_made(str(tmp_path / "ck")), devices8[:1])
    assert rec.path is None and rec.verdict is None
    rec.note("refused", "x")
    assert os.listdir(tmp_path) == ["ck"]


@pytest.mark.parametrize("content", [
    b"", b"{\"schema\": 1, \"outcome\": \"refus", b"[1, 2]", b"{\"schema\": 1}",
    b"{\"schema\": 1, \"outcome\": \"timed\", \"manifest\": {}}",
    b"{\"schema\": 1, \"outcome\": \"refused\", \"manifest\": {}}",
    b"{\"schema\": 1, \"outcome\": \"refused\", \"manifest\": {\"m\": 7}}",
    b"{\"schema\": 0, \"outcome\": \"refused\", \"manifest\": {\"m\": [\"/x\", \"0\"]}}",
    b"\xff\xfe\x00garbage",
], ids=["empty", "truncated", "list", "bare", "no-verdict", "no-manifest",
        "bad-manifest", "old-schema", "bytes"])
def test_a_malformed_point_record_is_a_miss(store, tmp_path, devices8, content):
    task = _made(str(tmp_path / "ck"))
    rec = _record(task, devices8[:1])
    rec.note("refused", "RESOURCE_EXHAUSTED: over")
    assert _record(task, devices8[:1]).verdict is not None
    with open(rec.path, "wb") as f:
        f.write(content)
    assert _record(task, devices8[:1]).verdict is None
    rec.note("refused", "RESOURCE_EXHAUSTED: over")      # whole again
    assert _record(task, devices8[:1]).verdict["outcome"] == "refused"


def test_threads_recording_one_point_leave_one_valid_record(store, tmp_path, devices8):
    n = 8
    task = _made(str(tmp_path / "ck"))
    recs = [_record(task, devices8[:1]) for _ in range(n)]
    assert len({r.path for r in recs}) == 1
    barrier = threading.Barrier(n)
    seen, lock = [], threading.Lock()

    def record(rec):
        barrier.wait(timeout=30)
        rec.note("refused", "RESOURCE_EXHAUSTED: over")
        hit = _record(task, devices8[:1]).verdict
        with lock:
            seen.append(hit)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=record, args=(r,)) for r in recs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and len(seen) == n
    # whoever read met a whole record: nobody read half a one
    assert all(v == {"outcome": "refused", "compiler": "RESOURCE_EXHAUSTED: over"}
               for v in seen)
    (name,) = records(store)                    # no temp file left either
    assert name.startswith("point-") and name.endswith(".json")


def test_the_two_kinds_of_record_share_a_directory_and_no_name(store, tmp_path, devices8):
    with pytest.raises(CompileRefused):
        aot_cache.load_or_compile(Lowered())
    _record(_made(str(tmp_path / "ck")), devices8[:1]).note("refused", "x")
    text, point = sorted(records(store), key=lambda n: n.startswith("point-"))
    assert not text.startswith("point-") and point.startswith("point-")
    # the text-keyed reader never takes a point record for one of its own
    assert aot_cache._read_refusal(str(store / point)) is None
