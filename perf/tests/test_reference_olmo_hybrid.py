"""The Olmo-Hybrid reference (``perf/reference/olmo_hybrid.py``) on the CPU:
against a forward written out by hand in float64 numpy (one token, one head
at a time); its layer-by-layer training step against ``jax.grad`` of the whole
loss; its fp8 control against the committed limits; ``flops_hybrid`` against
a count written out by hand; the four new readers on a hand-written trace;
and the new cell's files: loaded the way ``test_loader.py`` loads, and run
through every phase of ``perf/run.py`` at tiny size behind the rehearsal
override."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from perf.lib import bench, flops_hybrid, harness, refcheck
from perf.reference import olmo_hybrid as oh
from perf.tests import tinyroot

PERIOD = ("linear_attention",) * 3 + ("full_attention",)
ARCH = oh.Arch(vocab_size=512, d_model=128, kinds=PERIOD, period=4, n_heads=4,
               head_dim=32, key_dim=24, value_dim=48, conv_taps=4, neg_eigval=True,
               d_inner=352, norm_eps=1e-6)
# The control's size: heads of the published widths (96 / 192 / 128) and
# enough of them. At toy widths this model carries a rounding difference far
# (no norm before a mixer; keys of 24 lanes; an ``A_log`` of two elements):
# the bf16 control, which only rounds the matrices' operands, reads
# grad_rel_rms 0.034 at d 128 with 4 heads of 24 / 48 and 0.059 at d 256 with
# 2 heads of 96 / 192, over the limit of 0.03, and 0.015 here.
MID = oh.Arch(vocab_size=1024, d_model=512, kinds=PERIOD, period=4, n_heads=4,
              head_dim=128, key_dim=96, value_dim=192, conv_taps=4, neg_eigval=True,
              d_inner=1408, norm_eps=1e-6)
CELL = "olmo-hybrid-7b-1chip.steady-8k"
SEED = 2_147_483_659


# ------------------------------------------------- the forward, by hand
def _by_hand(a, params, tokens):
    """Every layer as the module docstring writes it: one sequence, one head,
    one token at a time, float64."""
    p = {k: np.asarray(v, np.float64) for k, v in oh.flat(params).items()}
    T, H = tokens.shape[1], a.n_heads

    def norm(x, g, eps=a.norm_eps):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def silu(x):
        return x / (1 + np.exp(-x))

    def conv(x, taps):          # y_t = sum_j taps[j] x_{t - 3 + j}
        y = np.zeros_like(x)
        for t in range(T):
            for j in range(a.conv_taps):
                if t - (a.conv_taps - 1) + j >= 0:
                    y[t] += taps[j] * x[t - (a.conv_taps - 1) + j]
        return y

    out = []
    for seq in tokens:
        x = p["wte"][seq]
        for n, kind in enumerate(a.kinds):
            w = lambda name: p[f"blocks/l{n % a.period}/{name}"][n // a.period]
            if kind == oh.FULL:
                q = norm(x @ w("q/kernel"), w("q_norm/scale"))
                k = norm(x @ w("k/kernel"), w("k_norm/scale"))
                v = x @ w("v/kernel")
                heads = []
                for i in range(H):
                    s = slice(i * a.head_dim, (i + 1) * a.head_dim)
                    scores = q[:, s] @ k[:, s].T / math.sqrt(a.head_dim)
                    scores[np.triu_indices(T, 1)] = -np.inf
                    e = np.exp(scores - scores.max(-1, keepdims=True))
                    heads.append(e / e.sum(-1, keepdims=True) @ v[:, s])
                mixed = np.concatenate(heads, -1) @ w("attn_out/kernel")
            else:
                dk, dv = a.key_dim, a.value_dim
                q = silu(conv(x @ w("lin_q/kernel"), w("conv_q")))
                k = silu(conv(x @ w("lin_k/kernel"), w("conv_k")))
                v = silu(conv(x @ w("lin_v/kernel"), w("conv_v")))
                beta = 2.0 / (1 + np.exp(-(x @ w("lin_b/kernel"))))
                step = np.log1p(np.exp(x @ w("lin_a/kernel") + w("dt_bias")))
                alpha = np.exp(-np.exp(w("A_log")) * step)
                heads = []
                for i in range(H):
                    qi, ki = q[:, i * dk:(i + 1) * dk], k[:, i * dk:(i + 1) * dk]
                    vi = v[:, i * dv:(i + 1) * dv]
                    S, o = np.zeros((dv, dk)), np.zeros((T, dv))
                    for t in range(T):
                        kt = ki[t] / np.sqrt(ki[t] @ ki[t] + 1e-6)
                        qt = qi[t] / np.sqrt(qi[t] @ qi[t] + 1e-6) / math.sqrt(dk)
                        S = alpha[t, i] * S @ (np.eye(dk) - beta[t, i] * np.outer(kt, kt)) \
                            + beta[t, i] * np.outer(vi[t], kt)
                        o[t] = S @ qt
                    heads.append(norm(o, w("o_norm/scale")))
                gated = np.concatenate(heads, -1) * silu(x @ w("lin_gate/kernel"))
                mixed = gated @ w("attn_out/kernel")
            h = x + norm(mixed, w("ln_1_post/scale"))
            f = (silu(h @ w("mlp_gate/kernel")) * (h @ w("mlp_in/kernel"))) @ w("mlp_out/kernel")
            x = h + norm(f, w("ln_2_post/scale"))
        out.append(norm(x, p["ln_f/scale"]) @ p["lm_head"].T)
    return np.stack(out)


def test_reference_agrees_with_the_forward_written_out_by_hand():
    tokens = np.random.default_rng(5).integers(0, 512, size=(2, 40), dtype=np.int32)
    params = oh.seeded_params(ARCH, oh.seed_key(SEED))
    want = _by_hand(ARCH, params, tokens)
    got = np.asarray(oh.logits_of(ARCH, SEED, tokens))
    # float32 against float64 through 4 layers and 40 steps of the rule
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert np.abs(want).max() > 0.3
    # the seeded gates do exercise a negative eigenvalue: beta above 1
    x = np.asarray(params["wte"])[tokens[0]]
    beta = 2 / (1 + np.exp(-(x @ np.asarray(params["blocks"]["l0"]["lin_b"]["kernel"][0]))))
    assert (beta > 1).mean() > 0.2 and (beta < 1).mean() > 0.2


def test_blocks_of_computation_change_nothing(monkeypatch):
    tokens = np.random.default_rng(6).integers(0, 512, size=(1, 64), dtype=np.int32)
    whole = np.asarray(oh.logits_of(ARCH, 7, tokens))
    monkeypatch.setattr(oh, "ATTN_Q_BLOCK", 16)
    monkeypatch.setattr(oh, "SCAN_PIECE", 8)
    oh._jitted.cache_clear()
    try:
        blocked = np.asarray(oh.logits_of(ARCH, 7, tokens))
    finally:
        oh._jitted.cache_clear()
    np.testing.assert_allclose(blocked, whole, rtol=0, atol=1e-6)


def test_layer_by_layer_training_is_the_whole_gradient_through_adamw():
    import jax
    import jax.numpy as jnp
    from perf.reference.gpt import adamw_init, adamw_step

    a = oh.Arch(**{**ARCH.__dict__, "kinds": PERIOD * 2})      # two periods
    batches = [np.random.default_rng(i).integers(0, 512, size=(2, 32), dtype=np.int32)
               for i in range(3)]
    losses, state = oh.train(a, SEED, batches, 1e-3, keep_state=True)
    with jax.default_matmul_precision("highest"):
        p = oh.seeded_params(a, oh.seed_key(SEED))
        opt, whole = adamw_init(p), []
        for b in batches:
            loss, g = jax.value_and_grad(lambda q: oh.loss_fn(a, q, jnp.asarray(b)))(p)
            whole.append(float(loss))
            p, opt = adamw_step(p, g, opt, 1e-3)
    np.testing.assert_allclose(losses, whole, rtol=1e-6)
    want_p = oh.flat(oh.program_layout(a, p))
    want_m = oh.flat(oh.program_layout(a, opt["m"]))
    assert set(want_p) == set(state["params"]) == set(state["m"]) == set(state["moved"])
    for k in want_p:
        # the same float32 arithmetic in another order; Adam's first steps
        # turn a last-bit difference of a gradient element near zero into
        # lr in that element, so the weights are held by norm, against how far
        # training moved them (``tests/test_ouro.py``'s figure)
        off = np.linalg.norm(state["params"][k] - np.asarray(want_p[k]))
        assert off <= 3e-3 * state["moved"][k], (k, off, state["moved"][k])
        assert np.linalg.norm(state["m"][k] - want_m[k]) <= 1e-3 * np.linalg.norm(want_m[k]), k


# --------------------------------------------------------------- control
def test_fp8_control_is_outside_the_committed_limits_and_bf16_inside(monkeypatch):
    limits = refcheck.load_limits()
    # matrices as large against the stream as at the published width:
    # 0.02 sqrt(3840) = 0.055 sqrt(512)
    monkeypatch.setattr(oh, "_matrix", lambda z: 0.055 * z)
    oh._jitted.cache_clear()
    _, batches = refcheck.sample_batches(1024, 256, 1, 4, SEED)
    ref_losses, ref_logits, ref_state = refcheck.reference_side(oh, MID, SEED, batches, 1e-3)
    for kind in ("bf16", "fp8"):
        losses, logits, state = refcheck.reference_side(
            oh, MID, SEED, batches, 1e-3, refcheck.lowp_mm(kind))
        numbers = {"logits_rel_rms": refcheck.logits_error(ref_logits, logits),
                   **refcheck.loss_errors(ref_losses, losses),
                   **refcheck.state_errors(ref_state, state)}
        assert refcheck.verdict(numbers, limits, lambda s: None, kind) == (kind == "bf16"), \
            (kind, numbers)
        if kind == "fp8":  # by the forward and by the backward, each alone
            assert numbers["logits_rel_rms"] > limits["logits_rel_rms"]
            assert numbers["grad_rel_rms"] > limits["grad_rel_rms"]


# ----------------------------------------------------------------- FLOPs
def test_flops_hybrid_against_the_count_by_hand():
    cell = bench.load_cell(CELL)
    a, seq = oh.arch_from_config(cell.config, 8192), 8192
    d, ff, vocab, held = 3840, 11008, 12544, 15
    linear = d * held * (96 + 96 + 192 + 192 + 1 + 1) + held * 192 * d + 4 * held * (96 + 96 + 192)
    full = 4 * d * held * 128
    matrices = 3 * linear + full + 4 * 3 * d * ff + d * vocab
    rule = 2 * (64 * (3 * 96 + 2 * 192) + 3 * 96 * 192)           # a token and head, forward
    by_hand = 6 * matrices + 12 * seq * held * 128 + 3 * 3 * held * rule
    assert flops_hybrid.required_flops_per_token(a, seq) == by_hand
    assert 36.5e12 < by_hand * seq < 37.5e12                       # ISSUE 33: 37.0 TFLOP a step
    parts = flops_hybrid.matmul_params(a)
    assert parts["swiglu"] > 0.7 * sum(parts.values())             # the matrices hold the work
    # the GPT count the shared ``mfu`` reader would use reads about 30 % high
    from perf.lib import flops
    gpt = flops.required_flops_per_token(a.d_model, a.n_layers, a.d_ff, a.vocab_size, seq)
    assert 1.2 < gpt / by_hand < 1.4
    call = flops_hybrid.gdn_call("saturn_gdn_fwd", 1, held, seq, 96, 192)
    assert call["flops"] == seq * held * rule
    assert call["bytes"] == seq * held * ((96 + 96 + 192) * 2 + (192 + 2) * 4)
    with pytest.raises(KeyError):
        flops_hybrid.gdn_call("saturn_gdn_bwd", 1, held, seq, 96, 192)


# ----------------------------------------------------------- the readers
class FakeRun:
    """One job of the cell's shape, 8 steps in [100, 104] s of wall clock, and
    a trace whose clock starts 90 s before the wall's."""

    def __init__(self, kernels, stack=True, busy_s=3.0):
        self.cell = bench.load_cell(CELL)
        self.jobs = harness.plan_jobs(self.cell.traffic, 30.0)
        self.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        self.devices = [object()]
        self.window = {"steps": 8}
        self.chosen = {self.jobs[0].name: {"technique": "dp", "per_batch_s": 0.5,
                                           "params": {"remat": True, "attention": "flash"}}}
        fields = {"stack_layers": 4, "stack_passes": 1,
                  "stack_kinds": {"linear_attention": 3, "full_attention": 1}} if stack else {}
        self._events = [{"kind": "task_interval", "task": self.jobs[0].name,
                         "ts_start": 100.0, "ts": 104.0, "elapsed_s": 4.0, "batches": 8,
                         **fields}]
        self.trace = {"wall_offset_s": 90.0, "window_ns": (9e9, 16e9), "busy_s": busy_s,
                      "devices": {"/device:TPU:0": {"kernels": kernels}}}

    def job(self, name):
        return next(j for j in self.jobs if j.name == name)

    def arch(self, job):
        return oh.arch_from_config(self.cell.config, job.seq)

    def events(self, phase, kind):
        return [e for e in self._events if phase == "window" and e["kind"] == kind]


def _calls(n, dur_ns, first_ns=10.5e9):
    return [(first_ns + i * 1e8, dur_ns) for i in range(n)]


KERNELS = {"saturn_gdn_fwd": _calls(48, 1.5e6),
           "saturn_flash_fwd": _calls(16, 5e6)}


def test_new_readers_on_a_trace_written_by_hand(capsys):
    cell, run = bench.load_cell(CELL), FakeRun(KERNELS)
    read = lambda name: bench.load_reader(cell, name)(run)
    assert read("linear_layer_calls") == 48 / (8 * 1 * 2)           # 3.0 a period, twice under remat
    run.chosen[run.jobs[0].name]["params"]["remat"] = False
    assert read("linear_layer_calls") == 6.0                        # ... and once without
    run.chosen[run.jobs[0].name]["params"]["remat"] = True
    assert read("linear_attn_share") == pytest.approx(100 * 48 * 1.5e-3 / 3.0)
    need = flops_hybrid.gdn_call("saturn_gdn_fwd", 1, 15, 8192, 96, 192)
    least = max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert need["bytes"] / 819e9 > need["flops"] / 197e12           # memory bounds it
    assert read("gdn_roofline") == pytest.approx(100 * 48 * least / (48 * 1.5e-3))
    assert "bound by memory" in capsys.readouterr().out
    per_token = flops_hybrid.required_flops_per_token(run.arch(run.jobs[0]), 8192)
    assert read("mfu_hybrid") == pytest.approx(100 * per_token * 8 * 8192 / 4.0 / 197e12)
    assert read("step_ms.hybrid") == pytest.approx(500.0)
    assert 0 < read("flash_roofline.hybrid") < 100


def test_new_readers_read_nothing_from_a_program_without_the_layer():
    """The parent commit on this benchmark, or a cell of another model: no
    ``saturn_gdn_*`` in the trace, no ``stack_kinds`` on the events. Every
    reader returns None and does not raise."""
    cell = bench.load_cell(CELL)
    without = FakeRun({"saturn_flash_fwd": _calls(16, 5e6)}, stack=False)
    for name in ("linear_layer_calls", "linear_attn_share", "gdn_roofline"):
        assert bench.load_reader(cell, name)(without) is None
    no_kinds = FakeRun(KERNELS, stack=False)
    assert bench.load_reader(cell, "linear_layer_calls")(no_kinds) is None
    untraced = FakeRun(KERNELS)
    untraced.trace = None
    for name in ("linear_layer_calls", "linear_attn_share", "gdn_roofline"):
        assert bench.load_reader(cell, name)(untraced) is None
    other = FakeRun(KERNELS)
    other.arch = lambda job: bench.load_cell("gptj-6b-1chip.steady") and type("A", (), {})()
    assert bench.load_reader(cell, "mfu_hybrid")(other) is None


# ------------------------------------------------------------- the cell
NEW_ENTRIES = ("window_tokens_per_s.hybrid", "step_ms.hybrid", "flash_roofline.hybrid",
               "ce_roofline.hybrid", "device_idle.hybrid", "hbm_peak.hybrid",
               "engine_overhead.hybrid", "ckpt_stall.hybrid", "trial_vs_realized.hybrid",
               "window_compiles.hybrid", "mfu_hybrid", "gdn_roofline", "linear_attn_share",
               "linear_layer_calls")


def test_the_new_cell_loads_with_its_readers_and_its_published_widths():
    cell = bench.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "steady-8k"
    assert [m["name"] for m in cell.end_to_end] == ["search_s_per_job", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for new in NEW_ENTRIES:
        assert new in names and callable(bench.load_reader(cell, new))
    # the GPT count is not reported here, and no older cell gains an entry
    assert "mfu" not in names and "train_tokens_per_s" not in names
    for other in ("gptj-6b-1chip.steady", "ouro-2.6b-1chip.steady-4k", "gptj-6b-4chip.fsdp"):
        assert not set(NEW_ENTRIES) & {m["name"] for m in bench.load_cell(other).per_layer}
    cfg = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():          # every published key, under its name
        assert cfg[key] == value or (key in cfg["reduced"] and cfg["published"][key] == value), key
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "linear_num_key_heads", "linear_num_value_heads", "vocab_size"])
    assert (cfg["num_hidden_layers"], cfg["num_attention_heads"], cfg["vocab_size"]) == (4, 15, 12544)
    assert cfg["run"]["overrides"] == {"n_layers": 4, "held_heads": 15, "vocab_size": 12544}
    a = oh.arch_from_config(cfg, 8192)
    assert (a.d_model, a.d_inner, a.n_heads, a.head_dim, a.key_dim, a.value_dim,
            a.conv_taps, a.neg_eigval, a.vocab_size) == (
        3840, 11008, 15, 128, 96, 192, 4, True, 12544)
    assert a.kinds == PERIOD and a.n_periods == 1
    run = harness.Run(cell, seed=1, seconds=30.0, trace=True, t_process_start=0.0)
    (job,) = run.jobs
    assert (job.seq, job.batch, job.batch_count % 8) == (8192, 1, 0)
    for new in NEW_ENTRIES:     # nothing measured yet: None, and no reader raises
        assert bench.load_reader(cell, new)(run) is None


def test_the_program_the_cell_builds_has_the_references_tree():
    import jax

    cell = bench.load_cell(CELL)
    a = oh.arch_from_config(cell.config, 8192)
    spec = harness._builder(cell.config)(
        cell.config["run"]["preset"], seq_len=8192, **cell.config["run"]["overrides"])
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: oh.program_params(a, oh.seed_key(0)))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(got))
    assert 766.0e6 < n < 766.5e6          # 12.26 GB of train state at 16 B/param
    assert (spec.stack_layers, spec.stack_kinds) == (
        4, {"linear_attention": 3, "full_attention": 1})


def test_benchmark_json_appends_the_cell_and_edits_nothing():
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert b["configs"][-1]["name"] == "olmo-hybrid-7b-1chip"
    assert b["workloads"][-1] == {**b["workloads"][-1], "name": CELL, "chips": 1,
                                  "config": "olmo-hybrid-7b-1chip", "traffic": "steady-8k"}
    assert tuple(m["name"] for m in b["per_layer"][-len(NEW_ENTRIES):]) == NEW_ENTRIES
    for m in b["per_layer"][-len(NEW_ENTRIES):]:
        assert m["workloads"] == [CELL] and m["moves"] == "search_s_per_job"
    assert [m["name"] for m in b["end_to_end"]] == [
        "train_tokens_per_s", "search_s_per_job", "setup_s"]
    assert CELL not in b["end_to_end"][0]["workloads"]
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1 and len(b["workloads"]) == 6


TINY_HYBRID = {
    "name": "tiny-hybrid", "source": "test", "family": "olmo_hybrid", "hidden_size": 64,
    "intermediate_size": 176, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 12, "linear_value_head_dim": 24, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "layer_types": list(PERIOD) * 2,
    "rms_norm_eps": 1e-06, "vocab_size": 256, "reduced": [],
    "run": {"builder": "saturn_tpu.models.gpt2:build_olmo_hybrid",
            "reference": "perf.reference.olmo_hybrid", "preset": "olmo-hybrid-test-tiny",
            # a float32 program: at d 64 with keys of 12 lanes the bf16 program
            # reads grad_rel_rms 0.45 against this reference (see ``MID``
            # above); the rehearsal is of the phases, not of the precision
            "overrides": {"dtype": "float32"}, "vocab_size": 256},
}


def test_rehearsal_of_a_tiny_hybrid_cell_runs_every_phase(tmp_path):
    root = str(tmp_path)
    tinyroot.write(root)
    with open(os.path.join(root, "perf", "configs", "tiny-hybrid.json"), "w") as f:
        json.dump(TINY_HYBRID, f)
    mix = dict(tinyroot.TINY_TRAFFIC, jobs=[
        {"name": "hyb", "seq": 64, "batch": 2, "lr": 1e-3, "share": 1.0}])
    with open(os.path.join(root, "perf", "traffic", "tiny-hyb.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-hybrid", "source": "test",
                         "file": "perf/configs/tiny-hybrid.json", "reduced": [], "why": "t"})
    b["workloads"].append({"name": "tiny-hybrid.hyb", "config": "tiny-hybrid",
                           "traffic": "tiny-hyb", "chips": 1, "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PERF_REHEARSAL_PLATFORM="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(bench.PERF_DIR, "run.py"), "--workload",
         "tiny-hybrid.hyb", "--seed", "3000000007", "--seconds", "2", "--trace", "1",
         "--bench-root", root], capture_output=True, text=True, env=env, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["attempted"] == 1 and result["failed"] == 0
    assert result["metrics"] == {} and result["rehearsal"] is True
    said = "\n".join(lines[:-1])
    for phase in ("search:", "window:", "memory:", "reference check"):
        assert phase in said
    for number in ("logits_rel_rms", "grad_rel_rms", "update_rel_rms", "loss_max_rel"):
        assert f"{number} = " in said and "NOT OK" not in said
