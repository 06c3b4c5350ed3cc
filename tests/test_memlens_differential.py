"""Differential oracle for the memlens liveness model: the static
per-device HBM peak must land within a calibrated band of what XLA's own
``memory_analysis()`` reports for the same step function, for every
built-in SPMD technique.

Each of the six strategies (dp/fsdp/tp/ep/ring/ulysses) is analyzed twice:

* **statically** — ``trace_step`` -> abstract jaxpr -> the memlens
  :class:`LivenessInterpreter` (no devices, no compile);
* **for real** — the same step jitted with the traced input shardings and
  ``donate_argnums=(0,)`` (the dispatch contract the profile models),
  compiled for 4 virtual CPU devices, and the peak taken from
  ``utils.timing.hbm_bytes_required`` (temp + argument + output - alias).

The comparable quantity is the *peak*, not a buffer-by-buffer match: XLA
legally fuses temporaries out of existence, schedules frees earlier than
linear-scan liveness, and pads for layout. Calibrated on this image the
static/compiled ratio sits at dp 0.71, fsdp 0.64, tp 1.01, ep 0.92,
ring 0.70, ulysses 0.67. The gate is a ratio in [0.4, 2.0] — wide enough
for scheduling slack, tight enough that a broken propagation rule (which
typically double-counts or drops whole state trees, i.e. >=4x) fails.

The fused ``lax.scan`` window (K>1) is held to the same band against the
real fused program, and the donation model is cross-checked: compiling a
step WITHOUT donation must raise the compiled peak exactly where memlens's
SAT-M003 pass predicts a missed donation.
"""

import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec

from saturn_tpu.analysis.memlens import liveness
from saturn_tpu.analysis.memlens import passes as ml_passes
from saturn_tpu.core.mesh import make_submesh
from saturn_tpu.utils.timing import hbm_bytes_required

pytestmark = pytest.mark.analysis

SIZE = 4

#: static peak / compiled peak must land here (see module doc)
PEAK_RATIO = (0.4, 2.0)

TECHNIQUES = ["dp", "fsdp", "tp", "ep", "ring", "ulysses"]


@pytest.fixture()
def moe_task(tmp_path):
    """The MoE sibling of ``tiny_task`` — required by the 'ep' technique."""
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss

    return Task(
        get_model=lambda **kw: build_gpt2("moe-test-tiny", **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=64, batch_size=8, vocab_size=256,
            n_tokens=64 * 8 * 2),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=4),
        save_dir=str(tmp_path / "moe-ckpts"),
    )


def _technique(name):
    # by name from the package's own table, not from the library's registry:
    # other test files of the same worker deregister techniques
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    return BUILTIN_TECHNIQUES[name]()


def _harness(name, task, devices):
    """(traced dict, mesh, train_step, state shardings, batch sharding)."""
    tech = _technique(name)
    config = tech.candidate_configs(task, SIZE)[0]
    traced = tech.trace_step(task, devices, config)

    axis_names, axis_sizes = tech.mesh_spec(SIZE, task, config)
    mesh = make_submesh(devices, axis_names, axis_sizes)
    spec = task.get_model(**tech._model_overrides(config)) \
        if hasattr(tech, "_model_overrides") else task.get_model()
    ds = task.get_dataset()
    _, train_step = tech.make_step_fns(spec, task, config, mesh, ds)

    state_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else PartitionSpec()),
        traced["state_specs"],
        is_leaf=lambda x: x is None or isinstance(x, PartitionSpec),
    )
    batch_sh = NamedSharding(mesh, traced["batch_spec"])
    return traced, mesh, train_step, state_sh, batch_sh


def _compiled_peak(train_step, state_sh, batch_sh, traced, donate=(0,)):
    compiled = (
        jax.jit(train_step, in_shardings=(state_sh, batch_sh),
                donate_argnums=donate)
        .lower(traced["state_shapes"], traced["batch_sds"])
        .compile()
    )
    return hbm_bytes_required(compiled)


# --------------------------------------------------------------------------
# the differential gate
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", TECHNIQUES)
def test_static_peak_matches_compiled(name, tiny_task, moe_task, devices8):
    task = moe_task if name == "ep" else tiny_task
    traced, _, train_step, state_sh, batch_sh = _harness(
        name, task, devices8[:SIZE])

    profile = liveness.analyze(traced)
    assert profile.peak_bytes > 0, f"{name}: empty static profile"
    assert profile.persistent_bytes > 0, f"{name}: no resident state"

    compiled_peak = _compiled_peak(train_step, state_sh, batch_sh, traced)
    if compiled_peak == 0:
        pytest.skip("memory_analysis unavailable on this backend")

    ratio = profile.peak_bytes / compiled_peak
    lo, hi = PEAK_RATIO
    assert lo <= ratio <= hi, (
        f"{name}: static {profile.peak_bytes}B vs compiled {compiled_peak}B "
        f"(ratio {ratio:.2f} outside [{lo}, {hi}]) — "
        f"contributors={profile.peak_contributors[:3]}"
    )
    # the drift auditor must agree these two are within its gate
    assert ml_passes.audit_point(
        profile.peak_bytes, compiled_peak, name, SIZE) is None


def test_fused_window_peak_matches_compiled(tiny_task, devices8):
    """The K>1 ``lax.scan`` path: K stacked batch shards join the peak."""
    K = 3
    traced, mesh, train_step, state_sh, batch_sh = _harness(
        "dp", tiny_task, devices8[:SIZE])

    def multi_step(state, window):
        return jax.lax.scan(train_step, state, window)

    batch_sds = traced["batch_sds"]
    window_sds = jax.ShapeDtypeStruct((K, *batch_sds.shape), batch_sds.dtype)
    stacked_sh = NamedSharding(
        mesh, PartitionSpec(None, *(traced["batch_spec"] or ())))
    compiled = (
        jax.jit(multi_step, in_shardings=(state_sh, stacked_sh),
                donate_argnums=(0, 1))
        .lower(traced["state_shapes"], window_sds)
        .compile()
    )
    compiled_peak = hbm_bytes_required(compiled)
    if compiled_peak == 0:
        pytest.skip("memory_analysis unavailable on this backend")

    profile = liveness.analyze(traced, window=K)
    p1 = liveness.analyze(traced, window=1)
    assert profile.peak_bytes > p1.peak_bytes  # the window costs memory

    ratio = profile.peak_bytes / compiled_peak
    lo, hi = PEAK_RATIO
    assert lo <= ratio <= hi, (
        f"fused K={K}: static {profile.peak_bytes}B vs compiled "
        f"{compiled_peak}B (ratio {ratio:.2f} outside [{lo}, {hi}])"
    )


def test_donation_delta_where_sat_m003_predicts_it(tiny_task, devices8):
    """Where memlens flags a missed donation, XLA's compiled peak must
    actually drop once the donation is added — the M003 counterexample is
    real aliasing, not a shape coincidence."""
    traced, _, train_step, state_sh, batch_sh = _harness(
        "dp", tiny_task, devices8[:SIZE])

    # static side: the undonated-state profile flags the missed donations
    undonated = liveness.analyze_closed(
        traced["jaxpr"],
        _in_specs(traced),
        dict(traced["mesh_axes"]),
        donated=[False] * (len(_in_specs(traced))),
        n_state_in=len(_in_specs(traced)) - 1,
        n_state_out=len(_in_specs(traced)) - 1,
    )
    assert undonated.missed_donations, "M003 should fire without donation"

    # compiled side: the donated program needs strictly fewer bytes
    peak_donated = _compiled_peak(train_step, state_sh, batch_sh, traced,
                                  donate=(0,))
    peak_plain = _compiled_peak(train_step, state_sh, batch_sh, traced,
                                donate=())
    if peak_donated == 0 or peak_plain == 0:
        pytest.skip("memory_analysis unavailable on this backend")
    assert peak_donated < peak_plain

    # and the static model agrees on the direction (equality is legal: when
    # mid-backward transients dominate, donation moves end-of-step residency
    # but not the global peak)
    donated_profile = liveness.analyze(traced)
    assert donated_profile.peak_bytes <= undonated.peak_bytes
    assert not donated_profile.missed_donations


def _in_specs(traced):
    from jax.tree_util import tree_leaves

    state_leaves = tree_leaves(traced["state_shapes"])
    spec_leaves = tree_leaves(
        traced["state_specs"],
        is_leaf=lambda x: x is None or isinstance(x, PartitionSpec),
    )
    specs = [
        liveness._from_pspec(ps, len(getattr(leaf, "shape", ())))
        for leaf, ps in zip(state_leaves, spec_leaves)
    ]
    specs.append(liveness._from_pspec(
        traced["batch_spec"], len(traced["batch_sds"].shape)))
    return specs
