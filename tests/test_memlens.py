"""Unit tests for the memlens liveness model and SAT-M pass plumbing.

The differential oracle against ``compiled.memory_analysis()`` lives in
``test_memlens_differential.py``; these tests pin the *model semantics* on
toy jaxprs (donation frees, scan carries persist, remat bodies are
transient-only, windows stack batch shards) and the pass contracts
(sanctions downgrade, capacity resolution, verdicts fail open).
"""

import pytest

import jax
import jax.numpy as jnp

from saturn_tpu.analysis.memlens import liveness
from saturn_tpu.analysis.memlens import passes as ml_passes
from saturn_tpu.analysis.shardflow.interp import _replicated

pytestmark = pytest.mark.analysis

MB = 1 << 20
N = 512  # 512x512 f32 = 1 MiB per buffer


def _closed(fn, *args):
    return jax.make_jaxpr(fn)(*args)


def _analyze(closed, donated, **kw):
    jaxpr = closed.jaxpr
    in_specs = [_replicated(v.aval) for v in jaxpr.invars]
    return liveness.analyze_closed(closed, in_specs, {}, donated=donated, **kw)


def _x():
    return jnp.zeros((N, N), jnp.float32)


# ----------------------------------------------------------------- liveness
def test_donation_reduces_simulated_peak():
    def f(x, y):
        z = x * 2.0
        return z + y

    closed = _closed(f, _x(), _x())
    plain = _analyze(closed, donated=[False, False])
    donated = _analyze(closed, donated=[True, True])
    assert donated.peak_bytes < plain.peak_bytes
    assert donated.donated_bytes == 2 * MB
    # donation releases x at its last read: one fewer buffer at the peak
    assert plain.peak_bytes - donated.peak_bytes == MB


def test_missed_donation_flagged_only_when_undonated():
    def f(x, y):
        z = x * 2.0
        return z + y

    closed = _closed(f, _x(), _x())
    plain = _analyze(closed, donated=[False, False])
    # both inputs match the output's shape/dtype and neither is donated
    assert len(plain.missed_donations) == 2
    assert plain.missed_donations[0]["bytes"] == MB
    donated = _analyze(closed, donated=[True, True])
    assert donated.missed_donations == []


def test_scan_carry_persists_across_iterations():
    def f(c, xs):
        def body(c, x):
            t = c * 2.0
            return t + x, t

        return jax.lax.scan(body, c, xs)

    xs = jnp.zeros((4, N, N), jnp.float32)
    prof = _analyze(_closed(f, _x(), xs), donated=[False, False])
    # carry + the full stacked xs/ys must be resident; body temps from all
    # 4 iterations must NOT stack up (one-iteration residency)
    assert prof.peak_bytes >= 9 * MB  # c + xs(4) + ys(4)
    assert prof.peak_bytes <= 13 * MB


def test_remat_body_is_transient_only():
    def g(x):
        a = x * 2.0
        b = a + 1.0
        c = b * 3.0
        return c.sum()

    def plain(x):
        return g(x) + 1.0

    def rematted(x):
        return jax.checkpoint(g)(x) + 1.0

    p_plain = _analyze(_closed(plain, _x()), donated=[False])
    p_remat = _analyze(_closed(rematted, _x()), donated=[False])
    # the remat frame force-frees its residuals on exit, so its peak can
    # never exceed the inline version's
    assert p_remat.peak_bytes <= p_plain.peak_bytes
    assert p_remat.peak_bytes >= MB  # the input itself stays live


def test_per_shard_bytes_divides_by_mesh_axes():
    aval = jax.ShapeDtypeStruct((N, N), jnp.float32)
    full = liveness.per_shard_bytes(aval, ((), ()), {"dp": 4})
    sharded = liveness.per_shard_bytes(aval, (("dp",), ()), {"dp": 4})
    assert full == MB
    assert sharded == MB // 4


# ---------------------------------------------------------------- sanctions
def test_sanction_marker_on_line_and_comment_block():
    lines = [
        "x = 1",
        "# sanctioned-memlens: audited 2026-08",
        "y = big_gather(x)",
        "z = y + 1",
    ]
    assert ml_passes._sanction_in_lines(lines, 3) == "audited 2026-08"
    assert ml_passes._sanction_in_lines(lines, 2) == "audited 2026-08"
    assert ml_passes._sanction_in_lines(lines, 4) is None


def test_sanction_at_resolves_file_line(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text("# sanctioned-memlens: fits with offload\nval = f()\n")
    assert ml_passes._sanction_at(f"{src}:2") == "fits with offload"
    assert ml_passes._sanction_at(f"{src}:1") == "fits with offload"
    assert ml_passes._sanction_at("eqn#7(dot_general)") is None
    assert ml_passes._sanction_at("") is None


# ----------------------------------------------------------------- capacity
def test_hbm_capacity_env_precedence(monkeypatch):
    monkeypatch.setenv(ml_passes.ENV_CAPACITY, str(16 * 1024**3))
    assert ml_passes.hbm_capacity_bytes() == 16 * 1024**3
    monkeypatch.setenv(ml_passes.ENV_CAPACITY, "not-a-number")
    assert ml_passes.hbm_capacity_bytes() == 0
    monkeypatch.delenv(ml_passes.ENV_CAPACITY)
    assert ml_passes.hbm_capacity_bytes() == 0  # no devices, no env


def test_audit_point_fires_both_directions():
    assert ml_passes.audit_point(300, 100, "dp", 4) is not None
    assert ml_passes.audit_point(100, 300, "dp", 4) is not None
    assert ml_passes.audit_point(100, 120, "dp", 4) is None
    assert ml_passes.audit_point(0, 100, "dp", 4) is None
    assert ml_passes.audit_point(100, 0, "dp", 4) is None
    d = ml_passes.audit_point(1000, 100, "tp", 8, k=2)
    assert d.code == "SAT-M005" and d.severity == "warning"


# ------------------------------------------------- traced-technique behavior
@pytest.fixture()
def dp_traced(tiny_task, devices8):
    # by name from the package's own table, not from the library's registry:
    # other test files of the same worker deregister techniques
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    tech = BUILTIN_TECHNIQUES["dp"]()
    config = tech.candidate_configs(tiny_task, 4)[0]
    return tech, tech.trace_step(tiny_task, devices8[:4], config)


def test_window_adds_one_batch_shard_per_extra_step(dp_traced):
    _, traced = dp_traced
    shard = liveness.per_shard_bytes(
        traced["batch_sds"],
        liveness._from_pspec(traced["batch_spec"],
                             len(traced["batch_sds"].shape)),
        dict(traced["mesh_axes"]),
    )
    assert shard > 0
    p2 = liveness.analyze(traced, window=2)
    p3 = liveness.analyze(traced, window=3)
    assert p3.peak_bytes - p2.peak_bytes == shard


def test_sat_m001_deterministic_under_small_capacity(dp_traced):
    _, traced = dp_traced
    report, profile = ml_passes.analyze_traced(traced, capacity_bytes=1024)
    assert profile.peak_bytes > 1024
    assert any(d.code == "SAT-M001" and d.severity == "error"
               for d in report.diagnostics)
    report2, _ = ml_passes.analyze_traced(traced, capacity_bytes=1 << 60)
    assert not any(d.code == "SAT-M001" for d in report2.diagnostics)


def test_grid_point_infeasible_is_conservative(dp_traced, tiny_task, devices8):
    tech, _ = dp_traced
    devices = devices8[:4]
    # unknown capacity: never prunes
    assert not ml_passes.grid_point_infeasible(tech, tiny_task, devices, 0)
    # generous capacity: fits, never prunes
    assert not ml_passes.grid_point_infeasible(
        tech, tiny_task, devices, 1 << 60)
    # absurdly small capacity: every config predicts OOM -> prune
    assert ml_passes.grid_point_infeasible(tech, tiny_task, devices, 1024)

    class NoTrace:
        name = "opaque"

    # a technique without trace_step can never be pruned statically
    assert not ml_passes.grid_point_infeasible(
        NoTrace(), tiny_task, devices, 1024)


def test_prediction_feeds_fits_compiled_calibration(dp_traced, tiny_task,
                                                    devices8, tmp_path):
    """_fits_memory's calibration hook emits predicted-vs-compiled bytes."""
    import json

    from jax.sharding import NamedSharding, PartitionSpec

    from saturn_tpu.core.mesh import make_submesh
    from saturn_tpu.utils import metrics

    tech, traced = dp_traced
    config = tech.candidate_configs(tiny_task, 4)[0]
    axis_names, axis_sizes = tech.mesh_spec(4, tiny_task, config)
    mesh = make_submesh(devices8[:4], axis_names, axis_sizes)
    spec = tiny_task.get_model()
    ds = tiny_task.get_dataset()
    _, train_step = tech.make_step_fns(spec, tiny_task, config, mesh, ds)
    state_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else PartitionSpec()),
        traced["state_specs"],
        is_leaf=lambda x: x is None or isinstance(x, PartitionSpec),
    )
    compiled = (
        jax.jit(train_step,
                in_shardings=(state_sh, NamedSharding(mesh,
                                                      traced["batch_spec"])),
                donate_argnums=(0,))
        .lower(traced["state_shapes"], traced["batch_sds"])
        .compile()
    )
    path = str(tmp_path / "metrics.jsonl")
    with metrics.scoped(path):
        assert tech._fits_compiled(compiled, devices8[:4], task=tiny_task,
                                   config=config, k=1)
    events = [json.loads(l) for l in open(path) if l.strip()]
    cal = [e for e in events if e.get("kind") == "memlens_calibration"]
    assert len(cal) == 1
    # PR 34: the audit reads the trace of the bundle ``dp_traced`` built
    # (``trace_step`` answers from the bundle cache) and traces nothing itself
    (audit,) = [e for e in events if e.get("kind") == "trial.memlens"]
    assert audit["trace"] == "shared"
    assert tech.build(tiny_task, devices8[:4], config).step_traces == 1
    assert cal[0]["technique"] == "dp" and cal[0]["k"] == 1
    assert cal[0]["predicted_bytes"] > 0
    assert cal[0]["compiled_bytes"] >= 0


# -------------------------------------------------------------- env margins
def test_prune_margin_env_default():
    assert ml_passes.OOM_MARGIN >= 1.0  # never prune inside capacity
    assert 0.0 < ml_passes.HEADROOM_MARGIN < 1.0


def test_env_hbm_bytes_backstop(monkeypatch):
    from saturn_tpu.utils import timing

    monkeypatch.delenv(ml_passes.ENV_CAPACITY, raising=False)
    assert timing.env_hbm_bytes() == 0
    monkeypatch.setenv(ml_passes.ENV_CAPACITY, "123456")
    assert timing.env_hbm_bytes() == 123456
    monkeypatch.setenv(ml_passes.ENV_CAPACITY, "junk")
    assert timing.env_hbm_bytes() == 0


# ------------------------------------------------- pipeline stash residency
class TestPipelineStashResidency:
    """Round 20 (SAT-M regression): the staged pipeline's activation stash.

    1F1B's whole memory claim is that the stash ring is ``min(M, 2S-1)``
    deep — O(S), independent of the microbatch count — while the GPipe
    ordering keeps all ``M`` in-flight inputs resident. The analytic model
    (``ml_passes.pipeline_stash_bytes``) pins the formula; the traced check
    holds the generic scan-carry liveness rule to the same delta, so a
    liveness change that stops seeing the stash (or a schedule change that
    silently grows it) breaks here before it mis-prices feasibility.
    """

    def test_analytic_model_bounds(self):
        unit = 1024
        S = 4
        # 1F1B plateaus at 2S-1 = 7 stashed microbatches...
        assert ml_passes.pipeline_stash_bytes("1f1b", S, 2, unit) == 2 * unit
        assert ml_passes.pipeline_stash_bytes("1f1b", S, 8, unit) == 7 * unit
        assert ml_passes.pipeline_stash_bytes("1f1b", S, 64, unit) == 7 * unit
        # ...the GPipe ordering grows linearly in M
        assert ml_passes.pipeline_stash_bytes("gpipe", S, 8, unit) == 8 * unit
        assert (ml_passes.pipeline_stash_bytes("gpipe", S, 64, unit)
                == 64 * unit)
        for m in (2, 4, 8, 64):
            assert (ml_passes.pipeline_stash_bytes("1f1b", S, m, unit)
                    <= ml_passes.pipeline_stash_bytes("gpipe", S, m, unit))

    def test_analytic_model_matches_ops_depth(self):
        from saturn_tpu.ops.pipeline import stash_depth

        for sched in ("1f1b", "gpipe"):
            for s in (2, 4):
                for m in (2, 7, 16):
                    assert (ml_passes.pipeline_stash_bytes(sched, s, m, 3)
                            == 3 * stash_depth(s, m, sched))

    def test_traced_liveness_sees_the_stash_delta(self):
        """At equal per-microbatch size, the traced peak gap between the two
        staged schedules tracks the analytic stash delta (within the carry
        in/out double-residency factor of the liveness model)."""
        import numpy as np
        from jax.sharding import Mesh

        from saturn_tpu.ops.pipeline import staged_pipeline_loss_and_grads

        L, DM, V, T = 4, 16, 31, 12
        key = jax.random.PRNGKey(0)
        k1, k2, k3, k4 = jax.random.split(key, 4)
        params = {
            "emb": jax.random.normal(k1, (V, DM)) * 0.02,
            "blocks": {
                "w": jax.random.normal(k2, (L, DM, DM)) * 0.1,
                "b": jnp.zeros((L, DM)),
            },
            "head": jax.random.normal(k3, (DM, V)) * 0.02,
        }
        d, S, M, B = 2, 4, 14, 56
        devs = np.array(jax.devices()[:8]).reshape(d, S)
        mesh = Mesh(devs, ("data", "stage"))
        fns = dict(
            mesh=mesh, block_key="blocks",
            embed_fn=lambda o, t: o["emb"][t],
            block_fn=lambda lp, h: jnp.tanh(h @ lp["w"] + lp["b"]),
            head_fn=lambda o, h: h @ o["head"],
            loss_fn=lambda lg, t: -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(lg, axis=-1), t[..., None], axis=-1)),
        )
        tokens = jax.random.randint(k4, (B, T), 0, V)

        def peak(schedule):
            closed = jax.make_jaxpr(
                lambda p, t: staged_pipeline_loss_and_grads(
                    p, t, n_microbatches=M, schedule=schedule, **fns)
            )(params, tokens)
            in_specs = [_replicated(v.aval) for v in closed.jaxpr.invars]
            return liveness.analyze_closed(closed, in_specs, {}).peak_bytes

        gap = peak("gpipe") - peak("1f1b")
        assert gap > 0, "1f1b must be the smaller traced peak at M > 2S-1"
        # per-(stage, data)-shard stage-input microbatch: (B/d/M, T, DM) f32
        unit = (B // d // M) * T * DM * 4
        analytic = (ml_passes.pipeline_stash_bytes("gpipe", S, M, unit)
                    - ml_passes.pipeline_stash_bytes("1f1b", S, M, unit))
        assert 0.5 * analytic <= gap <= 4.0 * analytic, (gap, analytic)


# ------------------------------------ the static prune vs the compiled check
PRUNE_SIZE = 4
#: The second task: test-tiny's vocabulary and sequence at about thirty times
#: the parameter bytes, so that one capacity stands far from both peaks.
PRUNE_BIG = dict(d_model=256, n_layers=4)


def _prune_task(save_dir, name, big):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    overrides = dict(PRUNE_BIG) if big else {}
    return Task(
        get_model=lambda **kw: build_gpt2("test-tiny", **{**overrides, **kw}),
        get_dataloader=lambda: make_lm_dataset(
            context_length=64, batch_size=8, vocab_size=256,
            n_tokens=64 * 8 * 8),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=8),
        chip_range=[PRUNE_SIZE],
        name=name,
        save_dir=save_dir,
    )


@pytest.fixture(scope="module")
def prune_setup(tmp_path_factory):
    """(topology, the two tasks' static peaks, a capacity between them: the
    geometric mean, a factor of about five from either peak and so far
    outside the prune margin and the compiled check's headroom, whatever
    the static model's calibration)."""
    import math

    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    topo = SliceTopology(jax.devices())
    tech = BUILTIN_TECHNIQUES["dp"]()
    devices = topo.blocks(PRUNE_SIZE)[0].devices_of(topo.devices)
    peaks = {}
    for case in ("fits", "oom"):
        task = _prune_task(str(tmp_path_factory.mktemp(f"peak-{case}")),
                           f"peak-{case}", big=case == "oom")
        config = tech.candidate_configs(task, PRUNE_SIZE)[0]
        peaks[case] = ml_passes.predict_profile(
            tech, task, devices, config).peak_bytes
    return topo, peaks, int(math.sqrt(peaks["fits"] * peaks["oom"]))


@pytest.mark.parametrize("case", ["fits", "oom"])
def test_static_prune_never_contradicts_the_compiled_check(
        case, prune_setup, tmp_path, monkeypatch):
    """Under one capacity the search is asked about each task with the
    static prune on and, where it pruned, again with it off, so that the
    compiled memory check gives its own verdict on the same grid point. A
    point memlens prunes before lowering must be one the compiled check
    rejects, and a point the compiled check rejects must not be one memlens
    placed under the headroom margin."""
    import json

    import saturn_tpu
    from saturn_tpu import library

    topo, peaks, capacity = prune_setup
    monkeypatch.setenv(ml_passes.ENV_CAPACITY, str(capacity))
    monkeypatch.setattr(library, "_REGISTRY", dict(library._REGISTRY))
    library.register_default_library()

    def sweep(prune):
        monkeypatch.setenv("SATURN_TPU_MEMLENS_PRUNE", "1" if prune else "0")
        tag = f"{case}-{'on' if prune else 'off'}"
        path = str(tmp_path / f"{tag}.jsonl")
        task = _prune_task(str(tmp_path / tag), tag, big=case == "oom")
        saturn_tpu.search([task], technique_names=["dp"], topology=topo,
                          profile_cache=False, metrics_path=path)
        events = [json.loads(l) for l in open(path) if l.strip()]
        pruned = [e for e in events if e.get("kind") == "trial_pruned"
                  and e.get("reason") == "memlens_static"]
        rejected = [e for e in events if e.get("kind") == "trial"
                    and e.get("memory_infeasible")]
        return task, pruned, rejected

    task, pruned, rejected = sweep(prune=True)
    if case == "fits":
        # not pruned, so this one search also holds the compiled verdict
        assert not pruned and not rejected
        assert task.feasible_strategies()
    else:
        assert len(pruned) == 1 and not rejected  # refused, never lowered
        assert not task.feasible_strategies()
        task, pruned, rejected = sweep(prune=False)
        assert not pruned and len(rejected) == 1  # the compiler agrees
    blessed = peaks[case] <= ml_passes.HEADROOM_MARGIN * capacity
    assert not (rejected and blessed), (peaks, capacity)
