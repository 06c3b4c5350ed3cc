"""The LFM2 reference (``perf/reference/lfm2.py``) on the CPU: its own
properties (causality, a convolution that reads two tokens back and no
further, the head norms ahead of the rotation, a selection bias that enters
the choice only); its training step by halves of a layer against ``jax.grad``
of the whole loss with the tied embedding's two gradients summed; planted
faults and the fp8 control against the committed limits; the two new readers
and the readers that were there on a hand-written trace of this model; and
the new cell's files: loaded the way ``test_loader.py`` loads, and run through
every phase of ``perf/run.py`` at tiny size behind the rehearsal override."""

import copy
import functools
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from perf.lib import bench, flops, flops_laguna, flops_lfm2, harness, refcheck
from perf.reference import lfm2 as ref
from perf.tests import tinyroot

CELL = "lfm2-8b-a1b-1chip.steady-8k"
SEED = 2_147_483_693
CONV, FULL = ref.CONV, ref.FULL


def tiny_config(**overrides):
    """The cell's configuration file at toy widths: the same keys, a leading
    dense layer and one period, 4 of 16 experts held, top-4."""
    cfg = copy.deepcopy(bench.load_cell(CELL).config)
    cfg.update(name="tiny-lfm2", vocab_size=256, hidden_size=64, num_attention_heads=8,
               num_key_value_heads=2, intermediate_size=128, moe_intermediate_size=32,
               num_experts=4, num_experts_per_tok=4)
    cfg["published"]["num_experts"] = 16
    cfg["run"].update(preset="lfm2-test-tiny", vocab_size=256,
                      overrides={"n_layers": 5, "lead_layers": 1, "held_experts": 4,
                                 "routed_buffer": 100.0, **overrides})
    return cfg


ARCH = ref.arch_from_config(tiny_config(), 64)


def _tokens(batch=2, seq=64, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (batch, seq)).astype(np.int32)


# ------------------------------------------------------- its own properties
def test_the_tiny_arch_is_the_tests_arch_and_imports_nothing_of_the_program():
    assert ARCH.kinds == (CONV, FULL, CONV, CONV, CONV)
    assert ARCH.ffs == ("dense",) + ("sparse",) * 4 and ARCH.heads == (0, 8, 0, 0, 0)
    assert (ARCH.n_heads, ARCH.n_kv_heads, ARCH.head_dim, ARCH.taps, ARCH.experts, ARCH.held,
            ARCH.top_k, ARCH.d_ff, ARCH.d_expert) == (8, 2, 8, 3, 16, 4, 4, 128, 32)
    assert (ARCH.lead, ARCH.period, ARCH.n_periods) == (1, 4, 1)
    with open(ref.__file__) as f:
        source = f.read()
    assert "import saturn_tpu" not in source and "from saturn_tpu" not in source


def test_a_later_token_changes_no_earlier_logit():
    import jax

    tokens = _tokens(1)
    changed = tokens.copy()
    changed[0, 40] = (changed[0, 40] + 1) % 256
    with jax.default_matmul_precision("highest"):
        a, b = (np.asarray(ref.logits_of(ARCH, 0, t, ref._plain_mm)) for t in (tokens, changed))
    assert np.array_equal(a[0, :40], b[0, :40]) and not np.allclose(a[0, 40:], b[0, 40:])


def test_the_convolution_reads_the_token_and_the_two_before_it():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=(1, 64, 64)), jnp.float32)
    p = ref._layer_weights(ARCH, ref.seeded_params(ARCH, ref.seed_key(0)), 2)
    mixer = functools.partial(ref.conv_mixer, ARCH, ref._plain_mm, p)
    base = np.asarray(mixer(y))
    for back, moves in ((0, True), (1, True), (2, True), (3, False)):
        out = np.asarray(mixer(y.at[0, 50 - back].add(1.0)))
        assert (not np.allclose(out[0, 50], base[0, 50], atol=1e-7)) == moves, back
        assert np.allclose(out[0, :50 - back], base[0, :50 - back], atol=1e-7)   # causal
    # the last tap multiplies the token itself: with the others at zero the
    # mixer is ((y W_c) * w_2 * (y W_b) * (y W_x)) W_out, token by token
    alone = dict(p, conv_w=p["conv_w"].at[:2].set(0.0))
    D = 64
    bcu = y @ p["conv_in"]["kernel"]
    want = (bcu[..., D:2 * D] * p["conv_w"][2] * bcu[..., :D] * bcu[..., 2 * D:]) \
        @ p["attn_out"]["kernel"]
    assert np.allclose(ref.conv_mixer(ARCH, ref._plain_mm, alone, y), want, atol=1e-6)


def test_the_head_norm_comes_before_the_rotation_and_is_a_heads_own():
    """Scaling one q head's lanes of the projection leaves the layer's output
    unchanged (the norm is that head's own, the gain shared): under a norm
    over all the lanes together it would not."""
    import jax.numpy as jnp

    y = jnp.asarray(np.random.default_rng(2).normal(size=(1, 64, 64)), jnp.float32)
    p = ref._layer_weights(ARCH, ref.seeded_params(ARCH, ref.seed_key(0)), 1)
    scaled = dict(p, q={"kernel": p["q"]["kernel"].at[:, 8:16].multiply(3.0)})
    sound = [ref.attention_mixer(ARCH, ref._plain_mm, w, y) for w in (p, scaled)]
    assert np.allclose(sound[0], sound[1], atol=2e-6)
    all_lanes = [ref.attention_mixer(ARCH, ref._plain_mm, w, y, "norm_all_lanes")
                 for w in (p, scaled)]
    assert not np.allclose(all_lanes[0], all_lanes[1], atol=1e-4)
    after = ref.attention_mixer(ARCH, ref._plain_mm, p, y, "norm_after_rotation")
    assert not np.allclose(after, sound[0], atol=1e-5)


def test_the_bias_enters_the_choice_only_and_the_weights_carry_the_eps():
    import jax

    params = ref.seeded_params(ARCH, ref.seed_key(0))
    p = dict(ref._layer_weights(ARCH, params, 2))
    u = 4.0 * jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    p["router_bias"] = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    chosen, weights = ref.routing_of(ARCH, p, u)
    scores = np.asarray(jax.nn.sigmoid(u @ p["router"]))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    assert np.allclose(weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), atol=1e-6)
    unbiased, _ = ref.routing_of(ARCH, dict(p, router_bias=0.0 * p["router_bias"]), u)
    assert not np.array_equal(np.sort(chosen, -1), np.sort(unbiased, -1))
    leaked = ref.routing_of(ARCH, p, u, "bias_on_weights")[1]
    assert not np.allclose(leaked, weights, atol=1e-3)
    assert weights.std() > 0.01      # neither uniform nor one-hot


def test_training_by_halves_is_the_whole_gradient_through_adamw():
    import jax

    from perf.reference.gpt import adamw_step

    batches = [_tokens(2, 64, s) for s in range(3)]
    losses, state = ref.train(ARCH, SEED, batches, 1e-3, keep_state=True)
    with jax.default_matmul_precision("highest"):
        params = ref.seeded_params(ARCH, ref.seed_key(SEED))
        opt = {"m": jax.tree_util.tree_map(np.zeros_like, params),
               "v": jax.tree_util.tree_map(np.zeros_like, params), "t": np.int32(0)}
        want = []
        for tokens in batches:
            loss, grads = jax.value_and_grad(lambda p: ref.loss_fn(ARCH, p, tokens))(params)
            params, opt = adamw_step(params, grads, opt, 1e-3)
            want.append(float(loss))
    assert np.allclose(losses, want, rtol=1e-5)
    want_p = ref.flat(ref.program_layout(ARCH, jax.tree_util.tree_map(np.asarray, params), np))
    want_m = ref.flat(ref.program_layout(ARCH, jax.tree_util.tree_map(np.asarray, opt["m"]), np))
    assert set(state["params"]) == set(want_p) and "wte" in want_p and "lm_head" not in want_p
    for k in want_p:
        assert np.linalg.norm(state["params"][k] - want_p[k]) <= \
            3e-3 * state["moved"][k] + 3e-3, k
        # (the tied embedding's first moment is the head's part plus the
        # lookup's: one leaf, both gradients)
        assert np.linalg.norm(state["m"][k] - want_m[k]) <= \
            2e-3 * np.linalg.norm(want_m[k]) + 1e-12, k


# ----------------------------------------------- planted faults, the control
def _numbers(ref_out, out):
    (ref_losses, ref_logits, ref_state), (losses, logits, state) = ref_out, out
    return {"logits_rel_rms": refcheck.logits_error(ref_logits, logits),
            **refcheck.loss_errors(ref_losses, losses),
            **refcheck.state_errors(ref_state, state)}


#: the control's size: heads of the published width at the published 4 q heads
#: a k/v head, enough lanes for a product's rounding to average as it does at
#: 2048
MID = ref.Arch(vocab_size=1024, d_model=512, kinds=(CONV, FULL, CONV, CONV, CONV),
               ffs=("dense",) + ("sparse",) * 4, n_heads=8, n_kv_heads=2, head_dim=64,
               taps=3, rope_theta=1e6, d_ff=1024, experts=32, held=8, first_expert=0,
               top_k=4, d_expert=256, routed_scale=1.0, route_eps=1e-6, norm_eps=1e-5)
LR = 1e-5


@pytest.fixture(scope="module")
def sound():
    _, batches = refcheck.sample_batches(1024, 256, 1, 4, SEED)
    return batches, refcheck.reference_side(ref, MID, SEED, batches, LR)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_comes_out_not_correct(sound, fault, monkeypatch):
    """The reference with one thing wrong against itself: outside the
    committed limits, by the numbers that fault can move. The period one
    place on is the nearest: the layers write little to the stream beside the
    embedding's rows (``ref.OUT``), so two layers nearly commute, the logits
    and the losses do not see their order (3e-5, 2e-7) and it shows in the
    gradients alone, at 1.6 x the limit here and 8.6 x at the cell's widths on
    the chip (``perf/reference/readings_lfm2.json``: it grows with the width)."""
    batches, ref_out = sound
    limits = refcheck.load_limits()
    for part in ("_mixer_half", "_ff_half", "_order"):
        monkeypatch.setattr(ref, part, functools.partial(getattr(ref, part), fault=fault))
    ref._jitted.cache_clear()
    try:
        numbers = _numbers(ref_out, refcheck.reference_side(ref, MID, SEED, batches, LR))
    finally:
        monkeypatch.undo()
        ref._jitted.cache_clear()
    assert not refcheck.verdict(numbers, limits, lambda s: None, fault), (fault, numbers)
    if fault == "period_rotated":
        assert numbers["grad_rel_rms"] > limits["grad_rel_rms"] > 100 * numbers["logits_rel_rms"]


def test_fp8_control_is_outside_the_committed_limits_and_bf16_inside(sound):
    batches, ref_out = sound
    limits = refcheck.load_limits()
    for kind in ("bf16", "fp8"):
        numbers = _numbers(ref_out, refcheck.reference_side(
            ref, MID, SEED, batches, LR, refcheck.lowp_mm(kind)))
        assert refcheck.verdict(numbers, limits, lambda s: None, kind) == (kind == "bf16"), \
            (kind, numbers)
        if kind == "fp8":
            assert numbers["grad_rel_rms"] > limits["grad_rel_rms"]


# ----------------------------------------------------------- the readers
class FakeRun:
    """One job of the cell's shape, 8 steps in [100, 104] s of wall clock, and
    a trace whose clock starts 90 s before the wall's."""

    def __init__(self, kernels, stack=True, counters=True, busy_s=3.0):
        self.cell = bench.load_cell(CELL)
        self.jobs = harness.plan_jobs(self.cell.traffic, 30.0)
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.devices = [object()]
        self.window = {"steps": 8}
        self.chosen = {self.jobs[0].name: {"technique": "dp", "per_batch_s": 0.5,
                                           "params": {"remat": True, "attention": "flash"}}}
        fields = {"stack_layers": 5, "stack_passes": 1, "stack_kinds": {FULL: 1, CONV: 3},
                  "stack_lead": {"conv_dense": 1}} if stack else {}
        if counters:
            fields.update(moe_pairs_held=40000.0, moe_rows_max=11000.0,
                          moe_rows_mean=5000.0, moe_second_path=0.0)
        self._events = [{"kind": "task_interval", "task": self.jobs[0].name,
                         "ts_start": 100.0, "ts": 104.0, "elapsed_s": 4.0, "batches": 8,
                         **fields}]
        self.trace = {"wall_offset_s": 90.0, "window_ns": (9e9, 16e9), "busy_s": busy_s,
                      "devices": {"/device:TPU:0": {"kernels": kernels}}}

    def job(self, name):
        return next(j for j in self.jobs if j.name == name)

    def arch(self, job):
        return ref.arch_from_config(self.cell.config, job.seq)

    def events(self, phase, kind):
        return [e for e in self._events if phase == "window" and e["kind"] == kind]


def _calls(n, dur_ns, first_ns=10.5e9):
    return [(first_ns + i * 1e7, dur_ns) for i in range(n)]


KERNELS = {"saturn_flash_dq": _calls(8, 50e6), "saturn_flash_fwd": _calls(16, 20e6),
           "saturn_gmm_fwd": _calls(96, 3e6), "saturn_gmm_dw": _calls(96, 5e6)}


def test_the_readers_on_a_trace_of_this_model_written_by_hand():
    cell, run = bench.load_cell(CELL), FakeRun(KERNELS)
    read = lambda name: bench.load_reader(cell, name)(run)   # noqa: E731
    a, job = run.arch(run.jobs[0]), run.jobs[0]
    assert read("full_layer_calls") == 8 / (8 * 1)               # 1.0 a period
    assert read("moe_rows_per_expert.lfm2") == 5000.0
    assert read("moe_second_path.lfm2") == 0.0
    assert read("moe_share.lfm2") == pytest.approx(100 * (96 * 3e-3 + 96 * 5e-3) / 3.0)
    fwd = flops_laguna.gmm_call("saturn_gmm_fwd", a, 40000.0)
    dw = flops_laguna.gmm_call("saturn_gmm_dw", a, 40000.0)
    least = 96 * (max(fwd["flops"] / 197e12, fwd["bytes"] / 819e9)
                  + max(dw["flops"] / 197e12, dw["bytes"] / 819e9))
    share = read("gmm_roofline.lfm2")
    assert share == pytest.approx(100 * least / (96 * 8e-3)) and share < 100
    dq = flops.flash_call("saturn_flash_dq", job.batch, 32, job.seq, 64)
    fw = flops.flash_call("saturn_flash_fwd", job.batch, 32, job.seq, 64)
    least = (8 * dq["flops"] + 16 * fw["flops"]) / 197e12
    share = read("flash_roofline.lfm2")
    assert share == pytest.approx(100 * least / (8 * 50e-3 + 16 * 20e-3)) and share < 100
    per_token = flops_lfm2.required_flops_per_token(a, job.seq)
    assert read("mfu_lfm2") == pytest.approx(
        100 * per_token * 8 * job.tokens_per_step / 4.0 / 197e12)
    assert read("step_ms.lfm2") == pytest.approx(500.0)


def test_the_new_readers_read_nothing_where_there_is_nothing_to_read():
    """A program without the layer kind (no ``conv`` among the events'
    ``stack_kinds``, as on the parent commit) or another model's ``Arch``:
    None, and no reader raises."""
    cell = bench.load_cell(CELL)
    without = FakeRun({"saturn_flash_dq": _calls(16, 5e6)}, stack=False, counters=False)
    assert bench.load_reader(cell, "full_layer_calls")(without) is None
    other = FakeRun(KERNELS)
    other._events[0]["stack_kinds"] = {"sliding_attention": 3, FULL: 1}
    assert bench.load_reader(cell, "full_layer_calls")(other) is None
    other.arch = lambda job: type("A", (), {})()
    assert bench.load_reader(cell, "mfu_lfm2")(other) is None
    untraced = FakeRun(KERNELS)
    untraced.trace = None
    assert bench.load_reader(cell, "full_layer_calls")(untraced) is None


# ------------------------------------------------------------- the cell
# Eleven, not the seventeen ISSUE 52 named: ``per_layer`` may hold 128 entries
# and held 117 (the six left out are in PERF.md section 7, "left by PR 52").
NEW_ENTRIES = ("window_tokens_per_s.lfm2", "step_ms.lfm2", "flash_roofline.lfm2",
               "ce_roofline.lfm2", "gmm_roofline.lfm2", "moe_share.lfm2",
               "moe_rows_per_expert.lfm2", "moe_second_path.lfm2",
               "device_idle.lfm2", "mfu_lfm2", "full_layer_calls")
PER_LAYER_MAX = 128


def test_the_new_cell_loads_with_its_readers_and_its_published_widths():
    cell = bench.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "steady-8k-conv"
    assert [m["name"] for m in cell.end_to_end] == ["search_s_per_job", "setup_s"]
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        assert len(json.load(f)["per_layer"]) <= PER_LAYER_MAX
    names = [m["name"] for m in cell.per_layer]
    for new in NEW_ENTRIES:
        assert new in names and callable(bench.load_reader(cell, new))
    assert not {"mfu", "train_tokens_per_s", "mfu_smallthinker", "gmm_roofline"} & set(names)
    for other in ("gptj-6b-1chip.steady", "smallthinker-21b-1chip.steady-8k",
                  "gptj-6b-4chip.fsdp"):
        assert not set(NEW_ENTRIES) & {m["name"] for m in bench.load_cell(other).per_layer}
    cfg = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():          # every published key, under its name
        assert cfg[key] == value or (key in cfg["reduced"] and cfg["published"][key] == value), key
    assert sorted(cfg["reduced"]) == ["layer_types", "num_dense_layers", "num_experts",
                                      "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 1, 8, 16384)
    # the held five: a leading layer like the published 0 and 1, then the
    # published layers 2..5, one whole period in the published order
    assert cfg["layer_types"] == [row["config"]["layer_types"][1]] \
        + row["config"]["layer_types"][2:6] == [CONV, FULL, CONV, CONV, CONV]
    for key in ("source", "published", "reduced", "assumed", "deployment", "share_rule"):
        assert cfg[key], key
    assert "GiB" in cfg["share_rule"] and "tie" in json.dumps(cfg["assumed"])
    a = ref.arch_from_config(cfg, 8192)
    assert (a.d_model, a.d_ff, a.d_expert, a.head_dim, a.n_heads, a.n_kv_heads, a.taps,
            a.experts, a.held, a.top_k, a.vocab_size, a.rope_theta, a.norm_eps,
            a.route_eps) == (2048, 7168, 1792, 64, 32, 8, 3, 32, 8, 4, 16384, 1e6, 1e-5, 1e-6)
    assert a.kinds == (CONV, FULL, CONV, CONV, CONV) and (a.lead, a.period, a.n_periods) == (
        1, 4, 1)
    run = harness.Run(cell, seed=1, seconds=30.0, trace=True, t_process_start=0.0)
    (job,) = run.jobs
    assert (job.seq, job.batch, job.batch_count % 8) == (8192, 4, 0) and job.lr == 1e-5
    # the rows a held expert sees a step where the routing is even: the
    # deployment's four data-parallel chips' at batch 1 each
    assert job.tokens_per_step * a.top_k / a.experts == 4096.0
    for new in NEW_ENTRIES:     # nothing measured yet: None, and no reader raises
        assert bench.load_reader(cell, new)(run) is None


def test_the_program_the_cell_builds_has_the_references_tree():
    import jax

    cell = bench.load_cell(CELL)
    a = ref.arch_from_config(cell.config, 8192)
    spec = harness._builder(cell.config)(
        cell.config["run"]["preset"], seq_len=8192, **cell.config["run"]["overrides"])
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: ref.program_params(a, ref.seed_key(0)))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(got))
    assert 507.7e6 < n < 507.9e6          # 8.12 GB of train state at 16 B/param
    held = sum(math.prod(x.shape) for k, x in ref.flat(got).items() if "/we_" in k)
    assert 0.69 < held / n < 0.70         # the held tables: 352 M
    assert (spec.stack_layers, spec.stack_kinds, spec.stack_lead) == (
        5, {FULL: 1, CONV: 3}, {"conv_dense": 1})
    cfg = spec.config
    assert (cfg.lead_kind, cfg.head_qk_norm, cfg.router_bias, cfg.route_eps, cfg.tie_head,
            cfg.conv_taps, cfg.experts_held) == ("conv", True, True, 1e-6, True, 3, 8)


def test_benchmark_json_appends_the_cell_and_edits_nothing():
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    configs = [c["name"] for c in b["configs"]]
    assert configs.index("lfm2-8b-a1b-1chip") == configs.index("smallthinker-21b-1chip") + 1
    cells = [w["name"] for w in b["workloads"]]
    assert cells.index(CELL) == cells.index("smallthinker-21b-1chip.steady-8k") + 1
    entry = b["workloads"][cells.index(CELL)]
    assert entry == {**entry, "chips": 1, "config": "lfm2-8b-a1b-1chip",
                     "traffic": "steady-8k-conv"} and len(entry["why"]) <= 200
    names = [m["name"] for m in b["per_layer"]]
    at = names.index(NEW_ENTRIES[0])
    assert tuple(names[at:at + len(NEW_ENTRIES)]) == NEW_ENTRIES
    for m in b["per_layer"][at:at + len(NEW_ENTRIES)]:
        assert m["workloads"] == [CELL] and m["moves"] == "search_s_per_job"
    assert [m["name"] for m in b["end_to_end"]] == [
        "train_tokens_per_s", "search_s_per_job", "setup_s"]
    assert CELL not in b["end_to_end"][0]["workloads"]
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


def test_rehearsal_of_a_tiny_lfm2_cell_runs_every_phase(tmp_path):
    root = str(tmp_path)
    tinyroot.write(root)
    # a float32 program: the rehearsal is of the phases, not of the precision
    with open(os.path.join(root, "perf", "configs", "tiny-lfm2.json"), "w") as f:
        json.dump(tiny_config(dtype="float32"), f)
    mix = dict(tinyroot.TINY_TRAFFIC, jobs=[
        {"name": "lfm2", "seq": 64, "batch": 2, "lr": 1e-3, "share": 1.0}])
    with open(os.path.join(root, "perf", "traffic", "tiny-lfm2.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-lfm2", "source": "test",
                         "file": "perf/configs/tiny-lfm2.json", "reduced": [], "why": "t"})
    b["workloads"].append({"name": "tiny-lfm2.lfm2", "config": "tiny-lfm2",
                           "traffic": "tiny-lfm2", "chips": 1, "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PERF_REHEARSAL_PLATFORM="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(bench.PERF_DIR, "run.py"), "--workload",
         "tiny-lfm2.lfm2", "--seed", "3000000011", "--seconds", "2", "--trace", "1",
         "--bench-root", root], capture_output=True, text=True, env=env, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["attempted"] == 1 and result["failed"] == 0
    assert result["metrics"] == {} and result["rehearsal"] is True
    said = "\n".join(lines[:-1])
    for phase in ("search:", "window:", "memory:", "reference check", "perf: routing:"):
        assert phase in said
    for number in ("logits_rel_rms", "grad_rel_rms", "update_rel_rms", "loss_max_rel"):
        assert f"{number} = " in said and "NOT OK" not in said
