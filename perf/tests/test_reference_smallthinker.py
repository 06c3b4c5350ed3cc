"""The SmallThinker reference (``perf/reference/smallthinker.py``) on the CPU:
its own properties (causality, a router that reads the block's un-normed
input, full layers without rotation, the window's reach); its layer-by-layer
training step against ``jax.grad`` of the whole loss; planted faults and the
fp8 control against the committed limits; the two new readers and the readers
that were there on a hand-written trace of this model; and the new cell's
files: loaded the way ``test_loader.py`` loads, and run through every phase of
``perf/run.py`` at tiny size behind the rehearsal override."""

import copy
import functools
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from perf.lib import bench, flops_laguna, flops_smallthinker, harness, refcheck
from perf.reference import smallthinker as st
from perf.tests import tinyroot

CELL = "smallthinker-21b-1chip.steady-8k"
SEED = 2_147_483_693
FULL, SLIDING = st.FULL, st.SLIDING


def tiny_config(**overrides):
    """The cell's configuration file at toy widths: the same keys, one
    period, 4 of 16 experts held, top-4, a window of half the sequence."""
    cfg = copy.deepcopy(bench.load_cell(CELL).config)
    cfg.update(name="tiny-smallthinker", vocab_size=256, hidden_size=64,
               num_attention_heads=14, num_key_value_heads=2, head_dim=16,
               moe_num_primary_experts=4, moe_num_active_primary_experts=4,
               moe_ffn_hidden_size=32, sliding_window_size=32)
    cfg["published"]["moe_num_primary_experts"] = 16
    cfg["run"].update(preset="smallthinker-test-tiny", vocab_size=256,
                      overrides={"n_layers": 4, "held_experts": 4,
                                 "routed_buffer": 100.0, **overrides})
    return cfg


ARCH = st.arch_from_config(tiny_config(), 64)


def _tokens(batch=2, seq=64, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (batch, seq)).astype(np.int32)


# ------------------------------------------------------- its own properties
def test_the_tiny_arch_is_the_tests_arch_and_imports_nothing_of_the_program():
    assert (ARCH.kinds, ARCH.rotated) == ((FULL, SLIDING, SLIDING, SLIDING),
                                          (False, True, True, True))
    assert (ARCH.heads, ARCH.n_kv_heads, ARCH.window, ARCH.experts, ARCH.held, ARCH.top_k) == (
        (14,) * 4, 2, 32, 16, 4, 4)
    assert ARCH.ffs == ("sparse",) * 4 and (ARCH.period, ARCH.n_periods) == (4, 1)
    with open(st.__file__) as f:
        source = f.read()
    assert "import saturn_tpu" not in source and "from saturn_tpu" not in source


def test_a_later_token_changes_no_earlier_logit():
    import jax

    tokens = _tokens(1)
    changed = tokens.copy()
    changed[0, 40] = (changed[0, 40] + 1) % 256
    with jax.default_matmul_precision("highest"):
        a, b = (np.asarray(st.logits_of(ARCH, 0, t, st._plain_mm)) for t in (tokens, changed))
    assert np.array_equal(a[0, :40], b[0, :40]) and not np.allclose(a[0, 40:], b[0, 40:])


def test_the_router_reads_the_blocks_own_input_before_any_norm():
    """Layer 0's routing is the top-k of ``wte[tokens] @ router``: neither
    norm's gain can move it, and the embedding's lean decides it (a token's
    chosen experts are the ones its id was given)."""
    import jax
    import jax.numpy as jnp

    params = st.seeded_params(ARCH, st.seed_key(0))
    tokens = jnp.arange(256)[None]
    scaled = jax.tree_util.tree_map(lambda x: x, params)
    for name in ("ln_1", "ln_2"):
        scaled["blocks"]["l0"][name]["scale"] = -3.0 * params["blocks"]["l0"][name]["scale"]
    routing, routing_scaled = [], []
    with jax.default_matmul_precision("highest"):
        st.forward(ARCH, params, tokens, routing=routing)
        st.forward(ARCH, scaled, tokens, routing=routing_scaled)
        z = params["wte"][tokens] @ params["blocks"]["l0"]["router"][0]
    assert np.array_equal(routing[0], routing_scaled[0])
    assert np.array_equal(np.sort(routing[0], -1), np.sort(jax.lax.top_k(z, 4)[1], -1))
    draw = jax.random.uniform(jax.random.fold_in(st.seed_key(0), 1000), (256, 16))
    own = np.sort(np.asarray(jax.lax.top_k(draw, 4)[1]), -1)
    assert (own == np.sort(np.asarray(routing[0])[0], -1)).mean() > 0.9
    # the same rows through the usual place, N2(h), choose otherwise somewhere
    usual = []
    with jax.default_matmul_precision("highest"):
        st.forward(ARCH, scaled, tokens, fault="router_on_n2", routing=usual)
    assert not np.array_equal(usual[0], routing[0])    # (the negative gain turns the lean)


def test_a_full_layer_is_not_rotated_and_a_sliding_layer_reaches_its_window():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=(1, 64, 64)), jnp.float32)
    params = st.seeded_params(ARCH, st.seed_key(0))
    p = st._layer_weights(ARCH, params, 0)
    full = functools.partial(st._mixer, ARCH, st._plain_mm, FULL, False, 14, p)
    # no position signal but the mask: the last token's output is unchanged
    # by a permutation of the tokens before it
    order = np.concatenate([rng.permutation(63), [63]])
    assert np.allclose(full(y)[0, 63], full(y[:, order])[0, 63], atol=1e-5)
    turned = functools.partial(st._mixer, ARCH, st._plain_mm, FULL, True, 14, p)
    assert not np.allclose(turned(y)[0, 63], turned(y[:, order])[0, 63], atol=3e-5)
    # query 50 of a sliding layer reads keys 19..50 (the token itself counted)
    sliding = functools.partial(st._mixer, ARCH, st._plain_mm, SLIDING, True, 14, p)
    base = np.asarray(sliding(y))
    for back, moves in ((0, True), (31, True), (32, False)):
        out = np.asarray(sliding(y.at[0, 50 - back].add(1.0)))
        assert (not np.allclose(out[0, 50], base[0, 50], atol=1e-7)) == moves, back


def test_a_tokens_weights_are_one_softmax_over_all_the_shares():
    import jax

    params = st.seeded_params(ARCH, st.seed_key(0))
    p = st._layer_weights(ARCH, params, 1)
    x = 4.0 * jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    chosen, weights = st.routing_of(ARCH, p["router"], x)
    assert chosen.shape == (2, 64, 4) and np.allclose(weights.sum(-1), 1.0, atol=1e-6)
    full = np.asarray(jax.nn.softmax(st.route_logits(p["router"], x), -1))
    picked = np.take_along_axis(full, np.asarray(chosen), -1)
    assert np.allclose(weights, picked / picked.sum(-1, keepdims=True), atol=1e-6)
    assert weights.std() > 0.01      # neither uniform nor one-hot


def test_layer_by_layer_training_is_the_whole_gradient_through_adamw():
    import jax

    batches = [_tokens(2, 64, s) for s in range(3)]
    losses, state = st.train(ARCH, SEED, batches, 1e-3, keep_state=True)
    with jax.default_matmul_precision("highest"):
        params = st.seeded_params(ARCH, st.seed_key(SEED))
        opt = {"m": jax.tree_util.tree_map(np.zeros_like, params),
               "v": jax.tree_util.tree_map(np.zeros_like, params), "t": np.int32(0)}
        want = []
        for tokens in batches:
            loss, grads = jax.value_and_grad(lambda p: st.loss_fn(ARCH, p, tokens))(params)
            params, opt = st.adamw_step(params, grads, opt, 1e-3)
            want.append(float(loss))
    assert np.allclose(losses, want, rtol=1e-5)
    want_p = st.flat(st.program_layout(ARCH, jax.tree_util.tree_map(np.asarray, params), np))
    want_m = st.flat(st.program_layout(ARCH, jax.tree_util.tree_map(np.asarray, opt["m"]), np))
    assert set(state["params"]) == set(want_p)
    for k in want_p:
        assert np.linalg.norm(state["params"][k] - want_p[k]) <= \
            3e-3 * state["moved"][k] + 3e-3, k
        # (a second norm's gain moves by 1e-7 at these widths: its gradient's
        # own rounding is a thousandth of it)
        assert np.linalg.norm(state["m"][k] - want_m[k]) <= 2e-3 * np.linalg.norm(want_m[k]), k


# ----------------------------------------------- planted faults, the control
def _numbers(ref_out, out):
    (ref_losses, ref_logits, ref_state), (losses, logits, state) = ref_out, out
    return {"logits_rel_rms": refcheck.logits_error(ref_logits, logits),
            **refcheck.loss_errors(ref_losses, losses),
            **refcheck.state_errors(ref_state, state)}


#: the control's size: heads of the published width and the published 7 q
#: heads a k/v head, enough lanes for a product's rounding to average as it
#: does at 2560, a window of an eighth of the sequence (a key in 32 lost or
#: gained by a window off by one)
MID = st.Arch(vocab_size=1024, d_model=512, kinds=(FULL, SLIDING, SLIDING, SLIDING),
              rotated=(False, True, True, True), heads=(7,) * 4, n_kv_heads=1,
              head_dim=128, window=32, experts=32, held=8, first_expert=0, top_k=4,
              d_expert=128, rope_theta=1.5e6, norm_eps=1e-6)
LR = 1e-5


@pytest.fixture(scope="module")
def sound():
    _, batches = refcheck.sample_batches(1024, 256, 1, 4, SEED)
    return batches, refcheck.reference_side(st, MID, SEED, batches, LR)


@pytest.mark.parametrize("fault", st.FAULTS)
def test_a_planted_fault_comes_out_not_correct(sound, fault, monkeypatch):
    """The reference with one thing wrong against itself: outside the
    committed limits, by the numbers that fault can move."""
    batches, ref_out = sound
    limits = refcheck.load_limits()
    real = st._layer
    monkeypatch.setattr(st, "_layer", functools.partial(real, fault=fault))
    st._jitted.cache_clear()
    try:
        numbers = _numbers(ref_out, refcheck.reference_side(st, MID, SEED, batches, LR))
    finally:
        monkeypatch.undo()
        st._jitted.cache_clear()
    assert not refcheck.verdict(numbers, limits, lambda s: None, fault), (fault, numbers)


def test_fp8_control_is_outside_the_committed_limits_and_bf16_inside(sound):
    batches, ref_out = sound
    limits = refcheck.load_limits()
    for kind in ("bf16", "fp8"):
        numbers = _numbers(ref_out, refcheck.reference_side(
            st, MID, SEED, batches, LR, refcheck.lowp_mm(kind)))
        assert refcheck.verdict(numbers, limits, lambda s: None, kind) == (kind == "bf16"), \
            (kind, numbers)
        if kind == "fp8":  # by the forward and by the backward, each alone
            assert numbers["logits_rel_rms"] > limits["logits_rel_rms"]
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
        fields = {"stack_layers": 4, "stack_passes": 1,
                  "stack_kinds": {FULL: 1, SLIDING: 3}} if stack else {}
        if counters:
            fields.update(moe_pairs_held=50000.0, moe_rows_max=9000.0,
                          moe_rows_mean=3125.0, moe_second_path=0.0)
        self._events = [{"kind": "task_interval", "task": self.jobs[0].name,
                         "ts_start": 100.0, "ts": 104.0, "elapsed_s": 4.0, "batches": 8,
                         **fields}]
        self.trace = {"wall_offset_s": 90.0, "window_ns": (9e9, 16e9), "busy_s": busy_s,
                      "devices": {"/device:TPU:0": {"kernels": kernels}}}

    def job(self, name):
        return next(j for j in self.jobs if j.name == name)

    def arch(self, job):
        return st.arch_from_config(self.cell.config, job.seq)

    def events(self, phase, kind):
        return [e for e in self._events if phase == "window" and e["kind"] == kind]


def _calls(n, dur_ns, first_ns=10.5e9):
    return [(first_ns + i * 1e7, dur_ns) for i in range(n)]


KERNELS = {"saturn_swa_dq": _calls(24, 40e6), "saturn_flash_dq": _calls(8, 50e6),
           "saturn_gmm_fwd": _calls(96, 2e6), "saturn_gmm_dw": _calls(96, 3e6)}


def test_the_readers_on_a_trace_of_this_model_written_by_hand(capsys):
    cell, run = bench.load_cell(CELL), FakeRun(KERNELS)
    read = lambda name: bench.load_reader(cell, name)(run)   # noqa: E731
    a, job = run.arch(run.jobs[0]), run.jobs[0]
    assert read("window_layer_calls.smallthinker") == 24 / (8 * 1)           # 3.0 a period
    assert read("moe_rows_per_expert") == 3125.0
    assert read("expert_rows_max_over_mean.smallthinker") == 9000.0 / 3125.0
    assert read("moe_second_path.smallthinker") == 0.0
    assert read("moe_share.smallthinker") == pytest.approx(
        100 * (96 * 2e-3 + 96 * 3e-3) / 3.0)
    fwd = flops_laguna.gmm_call("saturn_gmm_fwd", a, 50000.0)
    dw = flops_laguna.gmm_call("saturn_gmm_dw", a, 50000.0)
    least = 96 * (max(fwd["flops"] / 197e12, fwd["bytes"] / 819e9)
                  + max(dw["flops"] / 197e12, dw["bytes"] / 819e9))
    assert read("gmm_roofline.smallthinker") == pytest.approx(100 * least / (96 * 5e-3))
    full = flops_laguna.attn_call("saturn_flash_dq", a, job.batch, job.seq)
    window = flops_laguna.attn_call("saturn_swa_dq", a, job.batch, job.seq)
    least = 8 * full["flops"] / 197e12 + 24 * window["flops"] / 197e12
    share = read("attn_mixed_roofline.smallthinker")
    assert share == pytest.approx(100 * least / (8 * 50e-3 + 24 * 40e-3)) and share < 100
    said = capsys.readouterr().out
    assert SLIDING in said and FULL in said
    per_token = flops_smallthinker.required_flops_per_token(a, job.seq)
    assert read("mfu_smallthinker") == pytest.approx(
        100 * per_token * 8 * job.tokens_per_step / 4.0 / 197e12)
    assert read("step_ms.smallthinker") == pytest.approx(500.0)


def test_the_new_readers_read_nothing_where_there_is_nothing_to_read():
    """A program without the layer (no counters on the events) or another
    model's ``Arch``: None, and no reader raises."""
    cell = bench.load_cell(CELL)
    without = FakeRun({"saturn_flash_fwd": _calls(16, 5e6)}, stack=False, counters=False)
    assert bench.load_reader(cell, "moe_rows_per_expert")(without) is None
    other = FakeRun(KERNELS)
    other.arch = lambda job: type("A", (), {})()
    assert bench.load_reader(cell, "mfu_smallthinker")(other) is None
    assert bench.load_reader(cell, "attn_mixed_roofline.smallthinker")(other) is None


# ------------------------------------------------------------- the cell
NEW_ENTRIES = ("window_tokens_per_s.smallthinker", "step_ms.smallthinker",
               "ce_roofline.smallthinker", "device_idle.smallthinker",
               "hbm_peak.smallthinker", "engine_overhead.smallthinker",
               "ckpt_stall.smallthinker", "trial_vs_realized.smallthinker",
               "window_compiles.smallthinker", "mfu_smallthinker",
               "attn_mixed_roofline.smallthinker", "gmm_roofline.smallthinker",
               "moe_share.smallthinker", "window_layer_calls.smallthinker",
               "expert_rows_max_over_mean.smallthinker", "moe_second_path.smallthinker",
               "moe_rows_per_expert")


def test_the_new_cell_loads_with_its_readers_and_its_published_widths():
    cell = bench.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "steady-8k-w4k"
    assert [m["name"] for m in cell.end_to_end] == ["search_s_per_job", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for new in NEW_ENTRIES:
        assert new in names and callable(bench.load_reader(cell, new))
    assert not {"mfu", "train_tokens_per_s", "mfu_laguna", "gmm_roofline"} & set(names)
    for other in ("gptj-6b-1chip.steady", "laguna-xs2-1chip.steady-8k", "gptj-6b-4chip.fsdp"):
        assert not set(NEW_ENTRIES) & {m["name"] for m in bench.load_cell(other).per_layer}
    cfg = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():          # every published key, under its name
        assert cfg[key] == value or (key in cfg["reduced"] and cfg["published"][key] == value), key
    assert sorted(cfg["reduced"]) == ["moe_num_primary_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"], cfg["vocab_size"]) == (
        4, 16, 19072)
    for key in ("source", "published", "reduced", "assumed", "deployment", "share_rule"):
        assert cfg[key], key
    assert "GiB" in cfg["share_rule"] and 19072 == 149 * 128 >= 151936 / 8 > 148 * 128
    a = st.arch_from_config(cfg, 8192)
    assert (a.d_model, a.d_expert, a.head_dim, a.n_kv_heads, a.window, a.experts, a.held,
            a.top_k, a.vocab_size, a.rope_theta, a.norm_eps) == (
        2560, 768, 128, 4, 4096, 64, 16, 6, 19072, 1.5e6, 1e-6)
    assert a.kinds == (FULL, SLIDING, SLIDING, SLIDING) and a.heads == (28,) * 4
    assert a.rotated == (False, True, True, True) and (a.period, a.n_periods) == (4, 1)
    run = harness.Run(cell, seed=1, seconds=30.0, trace=True, t_process_start=0.0)
    (job,) = run.jobs
    assert (job.seq, job.batch_count % 8) == (8192, 0) and job.lr == 1e-5
    # the rows a held expert sees a step where the routing is even
    assert job.tokens_per_step * a.top_k / a.experts in (1536.0, 3072.0)
    for new in NEW_ENTRIES:     # nothing measured yet: None, and no reader raises
        assert bench.load_reader(cell, new)(run) is None


def test_the_program_the_cell_builds_has_the_references_tree():
    import jax

    cell = bench.load_cell(CELL)
    a = st.arch_from_config(cell.config, 8192)
    spec = harness._builder(cell.config)(
        cell.config["run"]["preset"], seq_len=8192, **cell.config["run"]["overrides"])
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: st.program_params(a, st.seed_key(0)))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(got))
    assert 559.6e6 < n < 559.8e6          # 8.95 GB of train state at 16 B/param
    held = sum(math.prod(x.shape) for k, x in st.flat(got).items() if "/we_" in k)
    assert 0.67 < held / n < 0.68         # the held tables: two thirds of the state
    assert (spec.stack_layers, spec.stack_kinds, spec.stack_lead) == (
        4, {FULL: 1, SLIDING: 3}, None)
    cfg = spec.config
    assert (cfg.route_from, cfg.router_score, cfg.expert_act, cfg.rotary_kinds) == (
        "block_input", "softmax", "reglu", (SLIDING,))


def test_benchmark_json_appends_the_cell_and_edits_nothing():
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    configs = [c["name"] for c in b["configs"]]
    assert configs.index("smallthinker-21b-1chip") == configs.index("ling3-flash-1chip") + 1
    cells = [w["name"] for w in b["workloads"]]
    assert cells.index(CELL) == cells.index("ling3-flash-1chip.steady-8k") + 1
    entry = b["workloads"][cells.index(CELL)]
    assert entry == {**entry, "chips": 1, "config": "smallthinker-21b-1chip",
                     "traffic": "steady-8k-w4k"} and len(entry["why"]) <= 200
    names = [m["name"] for m in b["per_layer"]]
    at = names.index(NEW_ENTRIES[0])
    assert tuple(names[at:at + len(NEW_ENTRIES)]) == NEW_ENTRIES
    for m in b["per_layer"][at:at + len(NEW_ENTRIES)]:
        assert m["workloads"] == [CELL] and m["moves"] == "search_s_per_job"
    assert [m["name"] for m in b["end_to_end"]] == [
        "train_tokens_per_s", "search_s_per_job", "setup_s"]
    assert CELL not in b["end_to_end"][0]["workloads"]
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


def test_rehearsal_of_a_tiny_smallthinker_cell_runs_every_phase(tmp_path):
    root = str(tmp_path)
    tinyroot.write(root)
    # a float32 program: the rehearsal is of the phases, not of the precision
    with open(os.path.join(root, "perf", "configs", "tiny-smallthinker.json"), "w") as f:
        json.dump(tiny_config(dtype="float32"), f)
    mix = dict(tinyroot.TINY_TRAFFIC, jobs=[
        {"name": "st", "seq": 64, "batch": 2, "lr": 1e-3, "share": 1.0}])
    with open(os.path.join(root, "perf", "traffic", "tiny-st.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-smallthinker", "source": "test",
                         "file": "perf/configs/tiny-smallthinker.json", "reduced": [],
                         "why": "t"})
    b["workloads"].append({"name": "tiny-smallthinker.st", "config": "tiny-smallthinker",
                           "traffic": "tiny-st", "chips": 1, "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PERF_REHEARSAL_PLATFORM="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(bench.PERF_DIR, "run.py"), "--workload",
         "tiny-smallthinker.st", "--seed", "3000000011", "--seconds", "2", "--trace", "1",
         "--bench-root", root], capture_output=True, text=True, env=env, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["attempted"] == 1 and result["failed"] == 0
    assert result["metrics"] == {} and result["rehearsal"] is True
    said = "\n".join(lines[:-1])
    for phase in ("search:", "window:", "memory:", "reference check", "perf: routing: share"):
        assert phase in said
    assert "by routed layer: 0.000000, 0.000000, 0.000000, 0.000000" in said
    for number in ("logits_rel_rms", "grad_rel_rms", "update_rel_rms", "loss_max_rel"):
        assert f"{number} = " in said and "NOT OK" not in said
