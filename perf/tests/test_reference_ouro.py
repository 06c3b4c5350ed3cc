"""The Ouro reference (``perf/reference/ouro.py``) on the CPU: against a
four-pass forward written out by hand in float64 numpy at d 128; its fp8
control against the committed limits there; the readers' FLOP count on its
``Arch`` against the count written out by hand; and the new cell's files:
loaded the way ``test_loader.py`` loads, and run through every phase of
``perf/run.py`` at tiny size behind the rehearsal override."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from perf.lib import bench, flops, harness, refcheck
from perf.reference import ouro
from perf.tests import tinyroot

ARCH = ouro.Arch(vocab_size=512, d_model=128, layers_held=2, ut_steps=4,
                 n_heads=4, d_inner=352, rope_theta=1e6, norm_eps=1e-6)
CELL = "ouro-2.6b-1chip.steady-4k"


# ------------------------------------------------- the forward, by hand
def _by_hand(a, params, tokens):
    """x_0 = E[tokens]; x_t = Nf(L_n(...L_1(x_{t-1}))), t = 1..4; logits =
    x_4 W_head. One sequence at a time, one head at a time, float64."""
    p = {k: np.asarray(v, np.float64) for k, v in ouro.flat(params).items()}
    hd, T = a.head_dim, tokens.shape[1]

    def norm(x, g):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + a.norm_eps) * g

    def rope(t):  # (T, hd): lanes j and j + hd/2 are a pair
        j = np.arange(hd // 2)
        ang = np.arange(T)[:, None] * a.rope_theta ** (-2.0 * j / hd)[None, :]
        t1, t2 = t[:, :hd // 2], t[:, hd // 2:]
        return np.concatenate([t1 * np.cos(ang) - t2 * np.sin(ang),
                               t2 * np.cos(ang) + t1 * np.sin(ang)], -1)

    out = []
    for seq in tokens:
        x = p["wte"][seq]
        for _ in range(a.ut_steps):
            for l in range(a.layers_held):
                w = lambda name: p[f"blocks/{name}"][l]
                h = norm(x, w("ln_1/scale"))
                q, k, v = h @ w("q/kernel"), h @ w("k/kernel"), h @ w("v/kernel")
                heads = []
                for i in range(a.n_heads):
                    s = slice(i * hd, (i + 1) * hd)
                    scores = rope(q[:, s]) @ rope(k[:, s]).T / math.sqrt(hd)
                    scores[np.triu_indices(T, 1)] = -np.inf
                    e = np.exp(scores - scores.max(-1, keepdims=True))
                    heads.append(e / e.sum(-1, keepdims=True) @ v[:, s])
                o = np.concatenate(heads, -1) @ w("attn_out/kernel")
                x = x + norm(o, w("ln_1_post/scale"))
                m = norm(x, w("ln_2/scale"))
                gate = m @ w("mlp_gate/kernel")
                f = (gate / (1 + np.exp(-gate)) * (m @ w("mlp_in/kernel"))) @ w("mlp_out/kernel")
                x = x + norm(f, w("ln_2_post/scale"))
            x = norm(x, p["ln_f/scale"])
        out.append(x @ p["lm_head"].T)
    return np.stack(out)


def test_reference_agrees_with_the_four_pass_forward_by_hand():
    tokens = np.random.default_rng(5).integers(0, 512, size=(2, 48), dtype=np.int32)
    params = ouro.seeded_params(ARCH, ouro.seed_key(2_147_483_659))
    want = _by_hand(ARCH, params, tokens)
    got = np.asarray(ouro.logits_of(ARCH, 2_147_483_659, tokens))
    # float32 against float64 through 8 layer applications: rounding only
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert np.abs(want).max() > 0.3


def test_attention_by_query_blocks_changes_nothing(monkeypatch):
    tokens = np.random.default_rng(6).integers(0, 512, size=(1, 64), dtype=np.int32)
    whole = np.asarray(ouro.logits_of(ARCH, 7, tokens))
    monkeypatch.setattr(ouro, "ATTN_Q_BLOCK", 16)
    ouro._jitted.cache_clear()
    try:
        blocked = np.asarray(ouro.logits_of(ARCH, 7, tokens))
    finally:
        ouro._jitted.cache_clear()
    np.testing.assert_allclose(blocked, whole, rtol=0, atol=1e-6)


# --------------------------------------------------------------- control
def test_fp8_control_is_outside_the_committed_limits_and_bf16_inside():
    limits, seed = refcheck.load_limits(), 2_147_483_659
    _, batches = refcheck.sample_batches(512, 128, 2, 4, seed)
    ref_losses, ref_logits, ref_state = refcheck.reference_side(
        ouro, ARCH, seed, batches, 1e-3)
    for kind in ("bf16", "fp8"):
        losses, logits, state = refcheck.reference_side(
            ouro, ARCH, seed, batches, 1e-3, refcheck.lowp_mm(kind))
        numbers = {"logits_rel_rms": refcheck.logits_error(ref_logits, logits),
                   **refcheck.loss_errors(ref_losses, losses),
                   **refcheck.state_errors(ref_state, state)}
        assert refcheck.verdict(numbers, limits, lambda s: None, kind) == (kind == "bf16"), \
            (kind, numbers)
        if kind == "fp8":  # by the forward and by the backward, each alone
            assert numbers["logits_rel_rms"] > limits["logits_rel_rms"]
            assert numbers["grad_rel_rms"] > limits["grad_rel_rms"]


# ----------------------------------------------------------------- FLOPs
def test_the_readers_flop_count_is_the_looped_models():
    with open(os.path.join(bench.PERF_DIR, "configs", "ouro-2.6b-1chip.json")) as f:
        cfg = json.load(f)
    a, seq = ouro.arch_from_config(cfg, 4096), 4096
    d, inner, vocab = 2048, 5632, 49152
    applications = cfg["num_hidden_layers"] * 4
    by_hand = (6 * (applications * (4 * d * d + 3 * d * inner) + d * vocab)
               + 12 * applications * seq * d)
    assert (a.d_model, a.d_inner, a.vocab_size, a.ut_steps) == (d, inner, vocab, 4)
    assert a.n_layers == applications and a.d_ff == 8448 and a.head_dim == 128
    assert flops.required_flops_per_token(
        a.d_model, a.n_layers, a.d_ff, a.vocab_size, seq) == by_hand
    # the head is counted once, not once a pass
    assert by_hand - 6 * d * vocab == applications * (
        6 * (4 * d * d + 3 * d * inner) + 12 * seq * d)


# ------------------------------------------------------------- the cell
def test_the_new_cell_loads_with_its_readers_and_its_published_widths():
    cell = bench.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "steady-4k"
    names = [m["name"] for m in cell.per_layer]
    for new in ("loop_passes", "loop_head_share", "flash_roofline", "ce_roofline"):
        assert new in names and callable(bench.load_reader(cell, new))
    # PR 32: the copied readers are gone, the cell is on the rooflines' lists
    assert "loop_flash_roofline" not in names and "loop_ce_roofline" not in names
    for other in ("gptj-6b-1chip.steady", "gpt2-medium.steady"):
        assert not {"loop_passes", "loop_head_share"} & {
            m["name"] for m in bench.load_cell(other).per_layer}
    cfg = cell.config
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["total_ut_steps"]) == (
        2048, 16, 128, 5632, 49152, 4)
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["published"] == {"num_hidden_layers": 48}
    assert 4 <= cfg["num_hidden_layers"] <= 9
    assert cfg["run"]["overrides"] == {"n_layers": cfg["num_hidden_layers"]}
    run = harness.Run(cell, seed=1, seconds=30.0, trace=True, t_process_start=0.0)
    (job,) = run.jobs
    assert (job.seq, job.batch, job.batch_count % 8) == (4096, 2, 0)
    # nothing measured yet: every new reader returns nothing and does not raise
    for new in ("loop_passes", "flash_roofline", "ce_roofline", "loop_head_share"):
        assert bench.load_reader(cell, new)(run) is None


def test_benchmark_json_keeps_the_looped_cells_own_metrics():
    """``loop_passes`` and ``loop_head_share`` are the looped cell's own and
    list it; the two copied roofline readers went in PR 32 (a ``benchmark``
    PR), when ``flash_roofline`` and ``ce_roofline`` took the cell onto their
    lists. The span entries' checks are ``test_span_metrics.py``'s again."""
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    by_name = {m["name"]: m for m in b["per_layer"]}
    assert "loop_flash_roofline" not in by_name and "loop_ce_roofline" not in by_name
    for name in ("loop_passes", "loop_head_share"):
        assert by_name[name]["workloads"] == [CELL]
    for name in ("flash_roofline", "ce_roofline"):
        assert CELL in by_name[name]["workloads"]
    assert not os.path.exists(os.path.join(bench.PERF_DIR, "metrics",
                                           "loop_flash_roofline.py"))


TINY_OURO = {
    "name": "tiny-ouro", "source": "test", "family": "ouro", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 176, "num_hidden_layers": 2, "total_ut_steps": 4,
    "rope_theta": 1000000, "rms_norm_eps": 1e-06, "vocab_size": 256, "reduced": [],
    "run": {"builder": "saturn_tpu.models.gpt2:build_ouro",
            "reference": "perf.reference.ouro", "preset": "ouro-test-tiny",
            "overrides": {}, "vocab_size": 256},
}


def test_rehearsal_of_a_tiny_looped_cell_runs_every_phase(tmp_path):
    root = str(tmp_path)
    tinyroot.write(root)
    with open(os.path.join(root, "perf", "configs", "tiny-ouro.json"), "w") as f:
        json.dump(TINY_OURO, f)
    mix = dict(tinyroot.TINY_TRAFFIC, jobs=[
        {"name": "loop", "seq": 64, "batch": 2, "lr": 1e-3, "share": 1.0}])
    with open(os.path.join(root, "perf", "traffic", "tiny-loop.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-ouro", "source": "test",
                         "file": "perf/configs/tiny-ouro.json", "reduced": [], "why": "t"})
    b["workloads"].append({"name": "tiny-ouro.loop", "config": "tiny-ouro",
                           "traffic": "tiny-loop", "chips": 1, "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PERF_REHEARSAL_PLATFORM="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(bench.PERF_DIR, "run.py"), "--workload",
         "tiny-ouro.loop", "--seed", "3000000007", "--seconds", "2", "--trace", "1",
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
