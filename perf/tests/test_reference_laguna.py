"""The Laguna reference (``perf/reference/laguna.py``) on the CPU: its own
properties (causality, the window's reach, a token's weights over all the
shares); its layer-by-layer training step against ``jax.grad`` of the whole
loss; planted faults and the fp8 control against the committed limits;
``flops_laguna`` against counts written out by hand; the six new readers on a
hand-written trace; and the new cell's files: loaded the way
``test_loader.py`` loads, and run through every phase of ``perf/run.py`` at
tiny size behind the rehearsal override."""

import copy
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from perf.lib import bench, flops_laguna, harness, refcheck
from perf.reference import laguna as lg
from perf.tests import tinyroot

CELL = "laguna-xs2-1chip.steady-8k"
SEED = 2_147_483_693
FULL, SLIDING = "full_attention", "sliding_attention"


def tiny_config(**overrides):
    """The cell's configuration file at toy widths: the same keys, a leading
    dense layer and one period, 4 of 16 experts held, top-4."""
    cfg = copy.deepcopy(bench.load_cell(CELL).config)
    cfg.update(name="tiny-laguna", vocab_size=256, hidden_size=64, intermediate_size=128,
               num_attention_heads=6, num_key_value_heads=2, head_dim=16, num_experts=4,
               num_experts_per_tok=4, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, sliding_window=24,
               num_attention_heads_per_layer=[6, 8, 8, 8] * 10)
    cfg["published"]["num_experts"] = 16
    cfg["rope_parameters"]["full_attention"].update(
        factor=64, original_max_position_embeddings=16, beta_fast=8, beta_slow=1)
    cfg["run"].update(preset="laguna-test-tiny", vocab_size=256,
                      overrides={"n_layers": 5, "held_experts": 4, **overrides})
    return cfg


ARCH = lg.arch_from_config(tiny_config(), 64)


def _tokens(batch=2, seq=64, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (batch, seq)).astype(np.int32)


# ------------------------------------------------------- its own properties
def test_a_later_token_changes_no_earlier_logit():
    import jax

    tokens = _tokens(1)
    changed = tokens.copy()
    changed[0, 40] = (changed[0, 40] + 1) % 256
    with jax.default_matmul_precision("highest"):
        a, b = (np.asarray(lg.logits_of(ARCH, 0, t, lg._plain_mm)) for t in (tokens, changed))
    assert np.array_equal(a[0, :40], b[0, :40]) and not np.allclose(a[0, 40:], b[0, 40:])


def test_the_window_reaches_its_own_token_and_window_minus_one_back():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 64, h, 16)), jnp.float32) for h in (4, 2, 2))
    base = np.asarray(lg._attention(q, k, v, 24))
    for back, moves in ((0, True), (23, True), (24, False)):      # query 50 reads keys 27..50
        v2 = v.at[0, 50 - back].add(1.0)
        out = np.asarray(lg._attention(q, k, v2, 24))
        assert (not np.allclose(out[0, 50], base[0, 50])) == moves, back
        assert np.array_equal(out[0, :50 - back], base[0, :50 - back])
    # blocks of query rows change nothing
    whole = np.asarray(lg._attention(q, k, v, None))
    old, lg.ATTN_Q_BLOCK = lg.ATTN_Q_BLOCK, 16
    try:
        assert np.allclose(np.asarray(lg._attention(q, k, v, None)), whole, atol=1e-6)
        assert np.allclose(np.asarray(lg._attention(q, k, v, 24)), base, atol=1e-6)
    finally:
        lg.ATTN_Q_BLOCK = old


def test_a_tokens_weights_sum_to_the_scaling_factor_over_all_the_shares():
    import jax

    params = lg.seeded_params(ARCH, lg.seed_key(0))
    p = lg._layer_weights(ARCH, params, 1)
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    chosen, weights = lg.routing_of(ARCH, p["router"], y)
    assert chosen.shape == (2, 64, 4) and np.allclose(weights.sum(-1), 2.5, atol=1e-5)
    total = np.zeros((2, 64))
    for share in range(ARCH.experts // ARCH.held):
        for e in range(ARCH.held):
            total += np.where(np.asarray(chosen) == share * ARCH.held + e,
                              np.asarray(weights), 0.0).sum(-1)
    assert np.allclose(total, 2.5, atol=1e-5)


def test_the_embedding_leans_towards_its_experts_and_routing_follows_it():
    """The seeded values' one structure (``AFFINITY``): a token's chosen
    experts in the first routed layer are the ones its id was given."""
    import jax
    import jax.numpy as jnp

    params = lg.seeded_params(ARCH, lg.seed_key(0))
    tokens = jnp.arange(256)[None]
    routing = []
    with jax.default_matmul_precision("highest"):
        lg.forward(ARCH, params, tokens, routing=routing)
    draw = jax.random.uniform(jax.random.fold_in(lg.seed_key(0), 1000), (256, 16))
    own = np.sort(np.asarray(jax.lax.top_k(draw, 4)[1]), -1)
    got = np.sort(np.asarray(routing[0])[0], -1)
    assert (own == got).mean() > 0.9


def test_layer_by_layer_training_is_the_whole_gradient_through_adamw():
    import jax

    batches = [_tokens(2, 64, s) for s in range(3)]
    losses, state = lg.train(ARCH, SEED, batches, 1e-3, keep_state=True)
    with jax.default_matmul_precision("highest"):
        params = lg.seeded_params(ARCH, lg.seed_key(SEED))
        opt = {"m": jax.tree_util.tree_map(np.zeros_like, params),
               "v": jax.tree_util.tree_map(np.zeros_like, params), "t": np.int32(0)}
        want = []
        for tokens in batches:
            loss, grads = jax.value_and_grad(lambda p: lg.loss_fn(ARCH, p, tokens))(params)
            params, opt = lg.adamw_step(params, grads, opt, 1e-3)
            want.append(float(loss))
    assert np.allclose(losses, want, rtol=1e-5)
    want_p = lg.flat(lg.program_layout(ARCH, jax.tree_util.tree_map(np.asarray, params), np))
    want_m = lg.flat(lg.program_layout(ARCH, jax.tree_util.tree_map(np.asarray, opt["m"]), np))
    assert set(state["params"]) == set(want_p)
    for k in want_p:
        # (one element whose gradient is rounding noise moves a whole step the
        # other way: 3e-3 in a leaf of 16384 elements is a single such element)
        assert np.linalg.norm(state["params"][k] - want_p[k]) <= \
            3e-3 * state["moved"][k] + 3e-3, k
        assert np.linalg.norm(state["m"][k] - want_m[k]) <= 1e-3 * np.linalg.norm(want_m[k]), k


# ----------------------------------------------- planted faults, the control
def _numbers(ref_out, out):
    (ref_losses, ref_logits, ref_state), (losses, logits, state) = ref_out, out
    return {"logits_rel_rms": refcheck.logits_error(ref_logits, logits),
            **refcheck.loss_errors(ref_losses, losses),
            **refcheck.state_errors(ref_state, state)}


#: the control's size: heads of the published width and the published
#: ratios of q heads to k/v heads, enough lanes for a product's rounding to
#: average as it does at 2048
MID = lg.Arch(vocab_size=1024, d_model=512, kinds=(FULL, SLIDING, SLIDING, SLIDING, FULL),
              ffs=("dense",) + ("sparse",) * 4, heads=(6, 8, 8, 8, 6), n_kv_heads=2,
              head_dim=128, window=8, d_dense=1024, experts=32, held=8, first_expert=0,
              top_k=4, d_expert=128, d_shared=128, routed_scale=2.5,
              full_rope=(500000.0, 0.5, 64.0, 64, 8.0, 1.0, 1.4158883083359672),
              sliding_theta=10000.0, norm_eps=1e-6)


#: the cell's learning rate. (At 1e-3 four steps move a router column by a
#: fifth of its length, a token's chosen experts change from step to step, and
#: the bf16 control swaps pairs in the last routed layer by the third step:
#: its tables read 0.06-0.13 with nothing wrong, every other leaf 0.008.)
LR = 1e-5


@pytest.fixture(scope="module")
def sound():
    _, batches = refcheck.sample_batches(1024, 256, 1, 4, SEED)
    return batches, refcheck.reference_side(lg, MID, SEED, batches, LR)


@pytest.mark.parametrize("fault", ["drop_pair", "window_less_one", "no_shared", "no_gate"])
def test_a_planted_fault_comes_out_not_correct(sound, fault, monkeypatch):
    """The reference with one thing wrong against itself: outside the
    committed limits, by the numbers that fault can move. (The window less
    one is seen at a window of 8, where a key in eight is lost; at the
    cell's 512 a window of 511 moves a sliding layer's output by a part in
    512 and the limits cannot see it: there the mask is held exactly by the
    kernels' own test, ``tests/test_laguna.py``, PERF.md Open question 10.)"""
    import functools

    batches, ref_out = sound
    limits = refcheck.load_limits()
    real = lg._layer
    monkeypatch.setattr(lg, "_layer", functools.partial(real, fault=fault))
    lg._jitted.cache_clear()
    try:
        numbers = _numbers(ref_out, refcheck.reference_side(lg, MID, SEED, batches, LR))
    finally:
        monkeypatch.undo()
        lg._jitted.cache_clear()
    assert not refcheck.verdict(numbers, limits, lambda s: None, fault), (fault, numbers)


def test_fp8_control_is_outside_the_committed_limits_and_bf16_inside(sound):
    batches, ref_out = sound
    limits = refcheck.load_limits()
    for kind in ("bf16", "fp8"):
        numbers = _numbers(ref_out, refcheck.reference_side(
            lg, MID, SEED, batches, LR, refcheck.lowp_mm(kind)))
        assert refcheck.verdict(numbers, limits, lambda s: None, kind) == (kind == "bf16"), \
            (kind, numbers)
        if kind == "fp8":  # by the forward and by the backward, each alone
            assert numbers["logits_rel_rms"] > limits["logits_rel_rms"]
            assert numbers["grad_rel_rms"] > limits["grad_rel_rms"]


# ----------------------------------------------------------------- FLOPs
def test_flops_laguna_against_the_counts_by_hand():
    a = lg.arch_from_config(bench.load_cell(CELL).config, 8192)
    parts = flops_laguna.matmul_params(a)
    full = 2048 * 6144 * 2 + 2 * 2048 * 1024 + 2048 * 48        # 29.5 M
    sliding = 2048 * 8192 * 2 + 2 * 2048 * 1024 + 2048 * 64     # 37.9 M
    assert round(full / 1e6, 1) == 29.5 and round(sliding / 1e6, 1) == 37.9
    assert parts["mixers"] == 2 * full + 3 * sliding
    assert parts["dense_ff"] == 3 * 2048 * 8192                  # 50.3 M
    assert parts["router"] == 4 * 2048 * 256 and parts["shared"] == 4 * 3 * 2048 * 512
    # 8 of 256 chosen, 32 held: one routed expert a token and layer
    assert parts["routed"] == 4 * 3 * 2048 * 512
    assert parts["head"] == 2048 * 12544
    seq = 8192
    per_token = flops_laguna.required_flops_per_token(a, seq)
    window_keys = (512 * 513 / 2 + (seq - 512) * 512) / seq       # mean_i min(i + 1, 512)
    attention = 2 * 6 * 48 * 128 * (seq + 1) + 3 * 12 * 64 * 128 * window_keys
    assert per_token == pytest.approx(6 * sum(parts.values()) + attention)
    assert 2.2e9 < per_token < 2.5e9                              # the issue's 2.3 GFLOP
    # a window call does the window's share of a full call's products
    f = flops_laguna.attn_call("saturn_flash_dq", a, 2, seq)
    w = flops_laguna.attn_call("saturn_swa_dq", a, 2, seq)
    assert f["kind"] == FULL and w["kind"] == SLIDING
    assert f["flops"] == 3 * 2 * 2 * 48 * 128 * seq * (seq + 1) / 2
    assert w["flops"] == 3 * 2 * 2 * 64 * 128 * window_keys * seq
    assert w["flops"] / f["flops"] == pytest.approx((64 / 48) * window_keys / ((seq + 1) / 2))
    assert f["bytes"] == (4 * 48 + 2 * 8) * 2 * seq * 128 * 2
    assert flops_laguna.attn_call("saturn_swa_dkv", a, 2, seq)["bytes"] == \
        (2 * 64 + 4 * 8) * 2 * seq * 128 * 2
    g = flops_laguna.gmm_call("saturn_gmm_fwd", a, 16384.0)
    assert g["flops"] == 2 * 16384 * 2048 * 512
    assert g["bytes"] == 16384 * (2048 + 512) * 2 + 32 * 2048 * 512 * 2
    assert flops_laguna.gmm_call("saturn_gmm_dw", a, 16384.0)["bytes"] == \
        16384 * (2048 + 512) * 2 + 32 * 2048 * 512 * 4
    with pytest.raises(KeyError):
        flops_laguna.gmm_call("saturn_gmm_bwd", a, 1.0)


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
        fields = {"stack_layers": 5, "stack_passes": 1,
                  "stack_lead": {"full_attention_dense": 1},
                  "stack_kinds": {SLIDING: 3, FULL: 1}} if stack else {}
        if counters:
            fields.update(moe_pairs_held=16000.0, moe_rows_max=2000.0,
                          moe_rows_mean=500.0, moe_second_path=0.0)
        self._events = [{"kind": "task_interval", "task": self.jobs[0].name,
                         "ts_start": 100.0, "ts": 104.0, "elapsed_s": 4.0, "batches": 8,
                         **fields}]
        self.trace = {"wall_offset_s": 90.0, "window_ns": (9e9, 16e9), "busy_s": busy_s,
                      "devices": {"/device:TPU:0": {"kernels": kernels}}}

    def job(self, name):
        return next(j for j in self.jobs if j.name == name)

    def arch(self, job):
        return lg.arch_from_config(self.cell.config, job.seq)

    def events(self, phase, kind):
        return [e for e in self._events if phase == "window" and e["kind"] == kind]


def _calls(n, dur_ns, first_ns=10.5e9):
    return [(first_ns + i * 1e7, dur_ns) for i in range(n)]


KERNELS = {"saturn_swa_dq": _calls(24, 4e6), "saturn_flash_dq": _calls(16, 30e6),
           "saturn_gmm_fwd": _calls(96, 0.5e6), "saturn_gmm_dw": _calls(96, 1e6)}


def test_new_readers_on_a_trace_written_by_hand(capsys):
    cell, run = bench.load_cell(CELL), FakeRun(KERNELS)
    read = lambda name: bench.load_reader(cell, name)(run)   # noqa: E731
    a, job = run.arch(run.jobs[0]), run.jobs[0]
    assert read("window_layer_calls") == 24 / (8 * 1)                       # 3.0 a period
    assert read("expert_rows_max_over_mean") == 4.0
    assert read("moe_second_path") == 0.0
    assert read("moe_share") == pytest.approx(100 * (96 * 0.5e-3 + 96 * 1e-3) / 3.0)
    fwd = flops_laguna.gmm_call("saturn_gmm_fwd", a, 16000.0)
    dw = flops_laguna.gmm_call("saturn_gmm_dw", a, 16000.0)
    least = 96 * (max(fwd["flops"] / 197e12, fwd["bytes"] / 819e9)
                  + max(dw["flops"] / 197e12, dw["bytes"] / 819e9))
    assert read("gmm_roofline") == pytest.approx(100 * least / (96 * 1.5e-3))
    full = flops_laguna.attn_call("saturn_flash_dq", a, job.batch, job.seq)
    window = flops_laguna.attn_call("saturn_swa_dq", a, job.batch, job.seq)
    least = 16 * full["flops"] / 197e12 + 24 * window["flops"] / 197e12
    assert read("attn_mixed_roofline") == pytest.approx(100 * least / (16 * 30e-3 + 24 * 4e-3))
    said = capsys.readouterr().out
    assert SLIDING in said and FULL in said
    # a window call counted as a full one would claim eight times the work:
    # over 100 % of the peak on this trace, where the window's own count reads under it
    as_full = 24 * flops_laguna.attn_call("saturn_flash_dq", a, job.batch, job.seq)["flops"] \
        * (64 / 48) / 197e12
    assert as_full / (24 * 4e-3) > 1.0 > 24 * window["flops"] / 197e12 / (24 * 4e-3)
    per_token = flops_laguna.required_flops_per_token(a, job.seq)
    assert read("mfu_laguna") == pytest.approx(
        100 * per_token * 8 * job.tokens_per_step / 4.0 / 197e12)
    assert read("step_ms.laguna") == pytest.approx(500.0)


def test_a_window_layer_run_through_the_full_kernel_reads_zero_calls():
    cell = bench.load_cell(CELL)
    through_full = FakeRun({"saturn_flash_dq": _calls(40, 30e6)})
    assert bench.load_reader(cell, "window_layer_calls")(through_full) == 0.0
    assert bench.load_reader(cell, "attn_mixed_roofline")(through_full) is None


def test_new_readers_read_nothing_from_a_program_without_the_layers():
    """The parent commit on this benchmark, or a cell of another model: no
    ``saturn_swa_*`` / ``saturn_gmm_*`` in the trace, no counters and no
    ``stack_kinds`` on the events. Every reader returns None and does not
    raise."""
    cell = bench.load_cell(CELL)
    new = ("window_layer_calls", "expert_rows_max_over_mean", "moe_second_path", "moe_share",
           "gmm_roofline", "attn_mixed_roofline")
    without = FakeRun({"saturn_flash_fwd": _calls(16, 5e6)}, stack=False, counters=False)
    for name in new:
        assert bench.load_reader(cell, name)(without) is None, name
    untraced = FakeRun(KERNELS)
    untraced.trace = None
    for name in ("window_layer_calls", "moe_share", "gmm_roofline", "attn_mixed_roofline"):
        assert bench.load_reader(cell, name)(untraced) is None, name
    other = FakeRun(KERNELS)
    other.arch = lambda job: type("A", (), {})()
    assert bench.load_reader(cell, "mfu_laguna")(other) is None
    assert bench.load_reader(cell, "attn_mixed_roofline")(other) is None


# ------------------------------------------------------------- the cell
NEW_ENTRIES = ("window_tokens_per_s.laguna", "step_ms.laguna", "ce_roofline.laguna",
               "device_idle.laguna", "hbm_peak.laguna", "engine_overhead.laguna",
               "ckpt_stall.laguna", "trial_vs_realized.laguna", "window_compiles.laguna",
               "mfu_laguna", "attn_mixed_roofline", "gmm_roofline", "moe_share",
               "window_layer_calls", "expert_rows_max_over_mean", "moe_second_path")


def test_the_new_cell_loads_with_its_readers_and_its_published_widths():
    cell = bench.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "steady-8k-moe"
    assert [m["name"] for m in cell.end_to_end] == ["search_s_per_job", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    for new in NEW_ENTRIES:
        assert new in names and callable(bench.load_reader(cell, new))
    # the GPT count and the full-attention-only roofline are not reported here
    assert not {"mfu", "train_tokens_per_s", "flash_roofline"} & set(names)
    assert not [n for n in names if n.startswith("flash_roofline")]
    for other in ("gptj-6b-1chip.steady", "olmo-hybrid-7b-1chip.steady-8k", "gptj-6b-4chip.fsdp"):
        assert not set(NEW_ENTRIES) & {m["name"] for m in bench.load_cell(other).per_layer}
    cfg = cell.config
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():          # every published key, under its name
        assert cfg[key] == value or (key in cfg["reduced"] and cfg["published"][key] == value), key
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 32, 12544)
    a = lg.arch_from_config(cfg, 8192)
    assert (a.d_model, a.d_dense, a.d_expert, a.d_shared, a.head_dim, a.n_kv_heads,
            a.window, a.experts, a.held, a.top_k, a.vocab_size, a.routed_scale) == (
        2048, 8192, 512, 512, 128, 8, 512, 256, 32, 8, 12544, 2.5)
    assert a.kinds == (FULL, SLIDING, SLIDING, SLIDING, FULL) and a.heads == (48, 64, 64, 64, 48)
    assert a.ffs == ("dense",) + ("sparse",) * 4 and (a.lead, a.period, a.n_periods) == (1, 4, 1)
    run = harness.Run(cell, seed=1, seconds=30.0, trace=True, t_process_start=0.0)
    (job,) = run.jobs
    assert (job.seq, job.batch_count % 8) == (8192, 0) and job.lr == 1e-5
    for new in NEW_ENTRIES:     # nothing measured yet: None, and no reader raises
        assert bench.load_reader(cell, new)(run) is None


def test_the_program_the_cell_builds_has_the_references_tree():
    import jax

    cell = bench.load_cell(CELL)
    a = lg.arch_from_config(cell.config, 8192)
    spec = harness._builder(cell.config)(
        cell.config["run"]["preset"], seq_len=8192, **cell.config["run"]["overrides"])
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: lg.program_params(a, lg.seed_key(0)))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(got))
    assert 691.5e6 < n < 691.7e6          # 11.07 GB of train state at 16 B/param
    held = sum(math.prod(x.shape) for k, x in lg.flat(got).items() if "/we_" in k)
    assert 0.58 < held / n < 0.59         # the held tables: 58 % of the state
    assert (spec.stack_layers, spec.stack_kinds, spec.stack_lead) == (
        5, {SLIDING: 3, FULL: 1}, {"full_attention_dense": 1})


def test_benchmark_json_appends_the_cell_and_edits_nothing():
    with open(os.path.join(bench.REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert b["configs"][-1]["name"] == "laguna-xs2-1chip"
    assert b["workloads"][-1] == {**b["workloads"][-1], "name": CELL, "chips": 1,
                                  "config": "laguna-xs2-1chip", "traffic": "steady-8k-moe"}
    assert tuple(m["name"] for m in b["per_layer"][-len(NEW_ENTRIES):]) == NEW_ENTRIES
    for m in b["per_layer"][-len(NEW_ENTRIES):]:
        assert m["workloads"] == [CELL] and m["moves"] == "search_s_per_job"
    assert [m["name"] for m in b["end_to_end"]] == [
        "train_tokens_per_s", "search_s_per_job", "setup_s"]
    assert CELL not in b["end_to_end"][0]["workloads"]
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1 and len(b["workloads"]) == 7


def test_rehearsal_of_a_tiny_laguna_cell_runs_every_phase(tmp_path):
    root = str(tmp_path)
    tinyroot.write(root)
    # a float32 program: the rehearsal is of the phases, not of the precision
    with open(os.path.join(root, "perf", "configs", "tiny-laguna.json"), "w") as f:
        json.dump(tiny_config(dtype="float32"), f)
    mix = dict(tinyroot.TINY_TRAFFIC, jobs=[
        {"name": "lag", "seq": 64, "batch": 2, "lr": 1e-3, "share": 1.0}])
    with open(os.path.join(root, "perf", "traffic", "tiny-lag.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-laguna", "source": "test",
                         "file": "perf/configs/tiny-laguna.json", "reduced": [], "why": "t"})
    b["workloads"].append({"name": "tiny-laguna.lag", "config": "tiny-laguna",
                           "traffic": "tiny-lag", "chips": 1, "why": "t"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PERF_REHEARSAL_PLATFORM="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, os.path.join(bench.PERF_DIR, "run.py"), "--workload",
         "tiny-laguna.lag", "--seed", "3000000011", "--seconds", "2", "--trace", "1",
         "--bench-root", root], capture_output=True, text=True, env=env, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["attempted"] == 1 and result["failed"] == 0
    assert result["metrics"] == {} and result["rehearsal"] is True
    said = "\n".join(lines[:-1])
    for phase in ("search:", "window:", "memory:", "reference check", "perf: routing: share"):
        assert phase in said
    for number in ("logits_rel_rms", "grad_rel_rms", "update_rel_rms", "loss_max_rel"):
        assert f"{number} = " in said and "NOT OK" not in said
