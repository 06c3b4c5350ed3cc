"""Fused linear-cross-entropy numerics vs the dense oracle (interpret mode).

Mirrors tests/test_flash.py's strategy: the Pallas kernel can't lower on the
CPU test mesh, so correctness runs in interpret mode against
``dense_linear_cross_entropy`` (plain XLA ops), fwd and grads, including
ignore-index masking and a non-block-multiple vocab (pad-column masking).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from saturn_tpu.ops.ce import (
    dense_linear_cross_entropy,
    fused_linear_cross_entropy,
)


def _case(n=128, d=64, v=256, masked=8, seed=0, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (jax.random.normal(k1, (n, d)) * 0.5).astype(dtype)
    w = (jax.random.normal(k2, (v, d)) * 0.5).astype(jnp.float32)
    labels = jax.random.randint(k3, (n,), 0, v).astype(jnp.int32)
    if masked:
        labels = labels.at[-masked:].set(-1)
    return x, w, labels


class TestFusedCE:
    # 300: not a lane multiple — pads to 384 with block_v=128, exercising the
    # in-kernel pad-column masking the production vocab (50304 → 51200) hits
    @pytest.mark.parametrize("v", [256, 300])
    def test_matches_dense_fwd(self, v):
        x, w, labels = _case(v=v)
        ref = dense_linear_cross_entropy(x, w, labels)
        got = fused_linear_cross_entropy(
            x, w, labels, block_n=64, block_v=128, interpret=True
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3)

    # v=300 pads: the masked-column branch must also be gradient-correct
    @pytest.mark.parametrize("v", [256, 300])
    def test_matches_dense_grads(self, v):
        x, w, labels = _case(v=v)

        ref_gx, ref_gw = jax.grad(
            lambda x_, w_: dense_linear_cross_entropy(x_, w_, labels),
            argnums=(0, 1),
        )(x, w)
        got_gx, got_gw = jax.grad(
            lambda x_, w_: fused_linear_cross_entropy(
                x_, w_, labels, block_n=64, block_v=128, interpret=True
            ),
            argnums=(0, 1),
        )(x, w)
        # bf16 logits stash in the kernel bwd: tolerances match what XLA's
        # own bf16-stash CE backward exhibits (atol covers near-zero
        # elements whose relative error the stash inflates)
        np.testing.assert_allclose(np.asarray(got_gx), np.asarray(ref_gx),
                                   rtol=2e-2, atol=3e-4)
        np.testing.assert_allclose(np.asarray(got_gw), np.asarray(ref_gw),
                                   rtol=2e-2, atol=3e-4)


    # recompute mode: no logits stash; bwd re-derives score blocks from
    # x@W^T — the long-context memory mode must match the oracle too
    @pytest.mark.parametrize("v", [256, 300])
    def test_recompute_mode_matches_dense(self, v):
        x, w, labels = _case(v=v)
        ref = dense_linear_cross_entropy(x, w, labels)
        got = fused_linear_cross_entropy(
            x, w, labels, block_n=64, block_v=128, interpret=True,
            stash=False,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3)
        ref_gx, ref_gw = jax.grad(
            lambda x_, w_: dense_linear_cross_entropy(x_, w_, labels),
            argnums=(0, 1),
        )(x, w)
        got_gx, got_gw = jax.grad(
            lambda x_, w_: fused_linear_cross_entropy(
                x_, w_, labels, block_n=64, block_v=128, interpret=True,
                stash=False,
            ),
            argnums=(0, 1),
        )(x, w)
        # recompute keeps f32 scores in bwd (no bf16 stash), so tolerances
        # are tighter than the stash-mode test
        np.testing.assert_allclose(np.asarray(got_gx), np.asarray(ref_gx),
                                   rtol=2e-3, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_gw), np.asarray(ref_gw),
                                   rtol=2e-3, atol=1e-5)

    # Auto block-picking at gpt2-large/-xl d_model (round-3 advisor finding):
    # (1<<20)//D is not 128-aligned for D in {1280, 1600}, and pre-fix
    # _padded_vocab padded Vp only to the larger block, so the fwd/dx grids
    # truncated — 128 real vocab columns dropped from the logsumexp at the
    # shipped gpt2-xl shapes (advisor repro: fused 31.845 vs dense 32.065 at
    # D=1280, V=2200). No explicit block_n/block_v here: this exercises the
    # V>=2048 auto branch end to end, both stash and recompute backwards.
    @pytest.mark.parametrize("d", [1280, 1600])
    @pytest.mark.parametrize("stash", [True, False])
    def test_auto_blocks_large_dmodel(self, d, stash):
        x, w, labels = _case(n=128, d=d, v=2200)
        ref = dense_linear_cross_entropy(x, w, labels)
        got = fused_linear_cross_entropy(
            x, w, labels, interpret=True, stash=stash
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3)
        ref_gx, ref_gw = jax.grad(
            lambda x_, w_: dense_linear_cross_entropy(x_, w_, labels),
            argnums=(0, 1),
        )(x, w)
        got_gx, got_gw = jax.grad(
            lambda x_, w_: fused_linear_cross_entropy(
                x_, w_, labels, interpret=True, stash=stash
            ),
            argnums=(0, 1),
        )(x, w)
        # stash mode quantizes logits to bf16; at D=1280/1600 the logit
        # magnitudes (~sqrt(D)/2 here) make the absolute quantization error
        # ~2e-3 on the grads — far below the pre-fix failure (dropped
        # columns shift the loss itself by 0.22)
        tol = dict(rtol=2e-2, atol=3e-3) if stash else dict(rtol=2e-3,
                                                            atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_gx), np.asarray(ref_gx),
                                   **tol)
        np.testing.assert_allclose(np.asarray(got_gw), np.asarray(ref_gw),
                                   **tol)

    def test_auto_vocab_blocks_are_lane_aligned(self):
        """Whatever the auto-picker chooses must be a multiple of the TPU's
        128-lane tile and must tile the padded vocab exactly."""
        from saturn_tpu.ops import ce as ce_mod

        for d in (768, 1024, 1280, 1600, 2048, 4096):
            bv_dw = ce_mod._auto_bv_dw(d)
            assert bv_dw % 128 == 0
            vp = ce_mod._padded_vocab(50304, (512, 512, 512, bv_dw))
            assert vp % 512 == 0 and vp % bv_dw == 0 and vp >= 50304

    def test_masked_tokens_zero_grad(self):
        x, w, labels = _case(masked=16)
        gx = jax.grad(
            lambda x_: fused_linear_cross_entropy(
                x_, w, labels, block_n=64, block_v=128, interpret=True
            )
        )(x)
        np.testing.assert_allclose(np.asarray(gx[-16:]), 0.0, atol=1e-7)

    def test_batch_shaped_input(self):
        x, w, labels = _case(n=128)
        ref = fused_linear_cross_entropy(
            x, w, labels, block_n=64, block_v=128, interpret=True
        )
        got = fused_linear_cross_entropy(
            x.reshape(2, 64, -1), w, labels.reshape(2, 64),
            block_n=64, block_v=128, interpret=True,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6)

    def test_fallback_on_cpu(self):
        # production path (interpret=None) on the CPU mesh: dense fallback,
        # same value as the oracle exactly
        x, w, labels = _case()
        got = fused_linear_cross_entropy(x, w, labels)
        ref = dense_linear_cross_entropy(x, w, labels)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6)

    def test_rejects_nonnegative_ignore_index(self):
        x, w, labels = _case()
        with pytest.raises(ValueError):
            fused_linear_cross_entropy(x, w, labels, ignore_index=0)


class TestModelFusedLoss:
    """The model-level fused objective equals pretraining_loss∘apply_fn."""

    def test_gpt2_fused_loss_matches_logits_path(self):
        from saturn_tpu.models.gpt2 import build_gpt2
        from saturn_tpu.models.loss import pretraining_loss

        spec = build_gpt2("test-tiny")
        assert spec.fused_loss_fn is not None
        params = spec.init_fn(jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, spec.config.seq_len), 0,
            spec.config.vocab_size,
        ).astype(jnp.int32)
        ref = pretraining_loss(spec.apply_fn(params, tokens), tokens)
        got = spec.fused_loss_fn(params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4)

    def test_moe_and_seq_parallel_have_no_fused_loss(self):
        from saturn_tpu.models.gpt2 import build_gpt2

        assert build_gpt2("moe-test-tiny").fused_loss_fn is None
        assert build_gpt2("test-tiny", seq_axis="sp",
                          seq_axis_size=2).fused_loss_fn is None

    def test_executor_step_routes_through_fused(self, monkeypatch):
        """step_fns_from_forward picks the fused path for standard tasks."""
        import saturn_tpu.models.gpt2 as gpt2_mod
        from saturn_tpu.core.task import HParams, Task
        from saturn_tpu.data.lm_dataset import make_lm_dataset
        from saturn_tpu.models.gpt2 import build_gpt2
        from saturn_tpu.models.loss import pretraining_loss
        from saturn_tpu.parallel.dp import DataParallel

        calls = {"fused": 0}
        spec = build_gpt2("test-tiny")
        orig = spec.fused_loss_fn

        def counting_fused(params, tokens):
            calls["fused"] += 1
            return orig(params, tokens)

        spec.fused_loss_fn = counting_fused
        task = Task(
            get_model=lambda **kw: spec,
            get_dataloader=lambda: make_lm_dataset(
                context_length=64, batch_size=2, vocab_size=256,
                n_tokens=64 * 2 * 4,
            ),
            loss_fn=pretraining_loss,
            hparams=HParams(lr=1e-3, batch_count=2),
            name="fused-route",
        )
        tech = DataParallel()
        init_state, train_step = tech.make_step_fns(
            spec, task, {"remat": False}, None, task.get_dataset()
        )
        params = spec.init_fn(jax.random.PRNGKey(0))
        jax.eval_shape(
            lambda p, b: train_step({"params": p,
                                     "opt_state": task.hparams.make_optimizer().init(p),
                                     "step": jnp.zeros((), jnp.int32)}, b),
            params, jnp.zeros((2, 64), jnp.int32),
        )
        assert calls["fused"] >= 1  # traced during step construction

    def test_tp_keeps_logits_path(self):
        """TP's vocab-sharded head must not route through the fused kernel."""
        from saturn_tpu.parallel.dp import DataParallel
        from saturn_tpu.parallel.tp import TensorParallel

        assert DataParallel().fused_loss_ok
        assert not TensorParallel().fused_loss_ok

    def test_explicit_bad_block_n_falls_back_to_dense(self):
        # N=128 not divisible by block_n=48: must not truncate the grid —
        # the wrapper falls back to the dense computation (exact oracle)
        x, w, labels = _case(n=128)
        got = fused_linear_cross_entropy(
            x, w, labels, block_n=48, block_v=128, interpret=True
        )
        ref = dense_linear_cross_entropy(x, w, labels)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6)

    def test_bert_fused_mlm_matches_logits_path(self):
        from saturn_tpu.models.bert import build_bert, mlm_loss

        spec = build_bert("bert-test-tiny")
        assert spec.fused_loss_fn is not None
        assert spec.fused_loss_objective == "mlm"
        params = spec.init_fn(jax.random.PRNGKey(0))
        # reserved top id (the [MASK] token) must not occur in data
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, spec.config.seq_len), 0,
            spec.config.vocab_size - 1,
        ).astype(jnp.int32)
        ref = mlm_loss(spec.apply_fn(params, tokens), tokens)
        got = spec.fused_loss_fn(params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4)

    def test_objective_tag_mismatch_keeps_logits_path(self):
        """A BERT spec driven with pretraining_loss must NOT take the fused
        MLM path — the tags differ, so the executor uses the logits path."""
        from saturn_tpu.models.bert import build_bert
        from saturn_tpu.models.loss import pretraining_loss

        spec = build_bert("bert-test-tiny")
        assert pretraining_loss.supports_fused_head == "causal-lm"
        assert spec.fused_loss_objective == "mlm"

    @staticmethod
    def _mesh_gate_case(technique, mesh_devices):
        from jax.sharding import Mesh
        from saturn_tpu.core.task import HParams, Task
        from saturn_tpu.data.lm_dataset import make_lm_dataset
        from saturn_tpu.models.gpt2 import build_gpt2
        from saturn_tpu.models.loss import pretraining_loss

        calls = {"fused": 0, "parts": 0}
        spec = build_gpt2("test-tiny")
        orig, orig_parts = spec.fused_loss_fn, spec.fused_loss_parts_fn

        def counting_fused(params, tokens):
            calls["fused"] += 1
            return orig(params, tokens)

        def counting_parts(params, tokens):
            calls["parts"] += 1
            return orig_parts(params, tokens)

        spec.fused_loss_fn = counting_fused
        spec.fused_loss_parts_fn = counting_parts
        task = Task(
            get_model=lambda **kw: spec,
            get_dataloader=lambda: make_lm_dataset(
                context_length=64, batch_size=2, vocab_size=256,
                n_tokens=64 * 2 * 4,
            ),
            loss_fn=pretraining_loss,
            hparams=HParams(lr=1e-3, batch_count=2),
            name="fused-mesh-gate",
        )
        mesh = Mesh(
            np.array(mesh_devices).reshape(len(mesh_devices)), ("data",)
        )
        init_state, train_step = technique.make_step_fns(
            spec, task, {"remat": False}, mesh, task.get_dataset()
        )
        params = spec.init_fn(jax.random.PRNGKey(0))
        jax.eval_shape(
            lambda p, b: train_step({"params": p,
                                     "opt_state": task.hparams.make_optimizer().init(p),
                                     "step": jnp.zeros((), jnp.int32)}, b),
            params, jnp.zeros((2, 64), jnp.int32),
        )
        return calls

    def test_multi_device_fsdp_keeps_logits_path(self):
        """fsdp shards params (incl. the vocab-dim wte), so multi-chip
        blocks must not route through the fused kernel — a pallas_call has
        no GSPMD partitioning rule (round-3 review finding)."""
        from saturn_tpu.parallel.fsdp import FSDP

        calls = self._mesh_gate_case(FSDP(), jax.devices()[:2])
        assert calls == {"fused": 0, "parts": 0}

    def test_multi_device_dp_routes_fused_parts(self):
        """dp (replicated params, batch-sharded) runs the fused loss on
        multi-chip blocks through the shard_map sum/count wrapper."""
        from saturn_tpu.parallel.dp import DataParallel

        calls = self._mesh_gate_case(DataParallel(), jax.devices()[:2])
        assert calls["parts"] >= 1 and calls["fused"] == 0

    @pytest.mark.slow
    def test_dp_sharded_fused_loss_matches_unsharded(self):
        """The psum'd (sum, count) mean over 2 batch shards equals the
        single-program fused mean."""
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from saturn_tpu.models.gpt2 import build_gpt2

        spec = build_gpt2("test-tiny")
        params = spec.init_fn(jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, spec.config.seq_len), 0,
            spec.config.vocab_size,
        ).astype(jnp.int32)
        ref = spec.fused_loss_fn(params, tokens)

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("data",))

        def local(p, b):
            s, c = spec.fused_loss_parts_fn(p, b)
            return (jax.lax.psum(s, ("data",))
                    / jnp.maximum(jax.lax.psum(c, ("data",)), 1))

        got = shard_map(
            local, mesh=mesh, in_specs=(P(), P("data")), out_specs=P()
        )(params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5)

        # Gradients through shard_map with replicated params (the psum
        # transpose): must match the unsharded fused grads (round-3 advisor
        # low finding — value-only coverage). On CPU the kernel falls back
        # to dense, so the TPU-pallas-under-shard_map case is for the chip
        # (``chip_smoke.py --chips 4`` runs fsdp on four chips against one).
        ref_val, ref_grads = jax.value_and_grad(spec.fused_loss_fn)(
            params, tokens
        )
        got_val, got_grads = jax.value_and_grad(
            shard_map(local, mesh=mesh, in_specs=(P(), P("data")),
                      out_specs=P())
        )(params, tokens)
        np.testing.assert_allclose(np.asarray(got_val), np.asarray(ref_val),
                                   rtol=1e-5)
        flat_ref = jax.tree_util.tree_leaves(ref_grads)
        flat_got = jax.tree_util.tree_leaves(got_grads)
        assert len(flat_ref) == len(flat_got)
        # f32 reduction order differs between the psum'd shards and the
        # single program; observed agreement is ~2.4e-4 absolute
        for a, b in zip(flat_got, flat_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=4e-4)


# ------------------------------------------- backward blocks (PRs 28, 31)
# (tokens, d_model, vocab, bn, stash) -> (bn, bv, bn_dw, bv_dw, bn_dx). The
# first four rows are the shapes the committed cells ran before the rule was
# a VMEM sum (d 1024 stash, d 4096 both modes, d 768); the next three are what
# the sum gives where fixed blocks were refused by the v5e's compiler
# (tests/test_tpu_compile.py compiles each). Since PR 31 dx's block is the one
# that keeps it compute-bound, with the VMEM asked for: only the d 4096 rows
# moved (64 -> 256 recompute, 128 -> 512 stash). The last two: stash mode
# forced at the GPT-J cell's shape, and a token count that 256 does not divide.
AUTO_BLOCKS = [
    ((4096, 1024, 50257, 1024, True), (1024, 512, 512, 1024, 1024)),
    ((8192, 4096, 50400, 256, False), (256, 512, 256, 128, 256)),
    ((2048, 4096, 50400, 256, True), (256, 512, 256, 128, 512)),
    ((4096, 768, 50257, 1024, True), (1024, 512, 512, 1024, 1024)),
    ((8192, 2048, 49152, 512, False), (512, 512, 512, 256, 256)),
    ((8192, 1024, 50257, 1024, False), (1024, 512, 512, 512, 512)),
    ((8192, 1600, 50257, 512, False), (512, 512, 512, 512, 512)),
    ((8192, 4096, 50400, 256, True), (256, 512, 256, 128, 512)),
    ((2176, 4096, 50400, 128, False), (128, 512, 128, 128, 128)),
]
AUTO_BLOCK_IDS = [f"d{a[1]}-n{a[0]}-{'stash' if a[4] else 'recompute'}"
                  for a, _ in AUTO_BLOCKS]


@pytest.mark.parametrize("args,want", AUTO_BLOCKS, ids=AUTO_BLOCK_IDS)
def test_backward_blocks_follow_the_vmem_sum(args, want):
    from saturn_tpu.ops import ce

    got = ce._auto_blocks(*args)
    assert got == want
    bn, bv, bn_dw, bv_dw, bn_dx = got
    d, stash = args[1], args[4]
    assert ce._dw_vmem(bn_dw, bv_dw, d, stash) <= ce._VMEM_LIMIT
    # dx asks for VMEM exactly where its sum is over the default limit, with
    # room over the sum, and never for more than a kernel may ask
    need, asked = ce._dx_vmem(bn_dx, bv, d, stash), ce._dx_vmem_limit(bn_dx, bv, d, stash)
    if need <= ce._VMEM_LIMIT:
        assert asked is None
    else:
        assert need < asked <= ce._VMEM_REQUEST_MAX and asked % (1 << 20) == 0


# the chip's figures, the test's own copy (Google Cloud documentation, "TPU v5e")
PEAK_FLOPS, HBM_BYTES_PER_S = 197e12, 819e9


@pytest.mark.parametrize("args,want", AUTO_BLOCKS, ids=AUTO_BLOCK_IDS)
def test_dx_block_hides_its_weight_stream(args, want):
    """The property the rule is for: at the chosen ``bn_dx`` the kernel's own
    weight stream (the padded head matrix once per token block) takes less
    HBM time than its matmul passes take the MXU at peak — or the block is
    the largest that ``n_tokens`` and the VMEM a kernel may ask for admit."""
    from saturn_tpu.ops import ce

    n, d, v, _, stash = args
    blocks = ce._auto_blocks(*args)
    bv, bn_dx = blocks[1], blocks[4]
    vp = ce._padded_vocab(v, blocks)
    stream_s = (n // bn_dx) * vp * d * 2 / HBM_BYTES_PER_S
    matmul_s = (1 if stash else 2) * 2 * n * vp * d / PEAK_FLOPS
    if stream_s < matmul_s:
        return
    larger = 2 * bn_dx
    assert (n % larger != 0 or ce._dx_vmem_limit(larger, bv, d, stash)
            > ce._VMEM_REQUEST_MAX), (stream_s, matmul_s)


def test_dx_weight_stream_was_the_bound_at_the_old_block():
    """The case PR 31 was written for, in numbers: at the GPT-J cell's shape a
    64-token block streams the head matrix 128 times, 64.9 ms of HBM time
    against 34.5 ms of matmul (at the padded vocab); at 256 tokens 16.2 ms."""
    n, d, vp = 8192, 4096, 50688

    def stream_ms(bn):
        return (n // bn) * vp * d * 2 / HBM_BYTES_PER_S * 1e3

    matmul_ms = 2 * 2 * n * vp * d / PEAK_FLOPS * 1e3
    assert round(stream_ms(64), 1) == 64.9 and round(stream_ms(256), 1) == 16.2
    assert round(matmul_ms, 1) == 34.5


def test_dx_block_is_halved_where_the_request_would_pass_the_cap(monkeypatch):
    """No width dW can run reaches the cap today (d 4096 at 512 tokens asks
    for 47 of 64 MiB), so lower it: a block that would need more is halved,
    and the smaller block still asks for what it needs."""
    from saturn_tpu.ops import ce

    monkeypatch.setattr(ce, "_VMEM_REQUEST_MAX", 24 << 20)
    blocks = ce._auto_blocks(8192, 4096, 50400, 256, False)
    assert blocks[4] == 128
    assert (16 << 20) < ce._dx_vmem_limit(128, 512, 4096, False) <= (24 << 20)


def test_explicit_blocks_are_kept():
    from saturn_tpu.ops import ce

    assert ce._auto_blocks(8192, 4096, 50400, 256, False, block_n=256)[4] == 256
    assert ce._auto_blocks(512, 64, 256, 128, True, block_v=128)[1::2] == (128, 128)


# (tokens, d_model, vocab) of the three cells' fused heads -> what the plan
# says: GPT-J moves to the 256-token dx block and asks for VMEM; gpt2-medium
# (stash mode, dx at the forward's block) and Ouro (recompute, 256 already)
# are what they were and ask for nothing.
CELL_PLANS = {
    "gptj-6b-1chip": ((8192, 4096, 50400),
                      (256, 512, 256, 128, 256), "recompute", True),
    "gpt2-medium": ((4096, 1024, 50257),
                    (1024, 512, 512, 1024, 1024), "stash", False),
    "ouro-2.6b-1chip": ((8192, 2048, 49152),
                        (512, 512, 512, 256, 256), "recompute", False),
}


@pytest.mark.parametrize("cell", list(CELL_PLANS))
def test_ce_plan_of_the_benchmark_cells(cell):
    from saturn_tpu.ops import ce

    shape, blocks, mode, asks = CELL_PLANS[cell]
    plan = ce.ce_plan(*shape)
    assert plan.blocks == blocks and plan.mode == mode
    assert (plan.dx_vmem_limit is not None) == asks
    assert plan.dx_vmem == ce._dx_vmem(plan.bn_dx, plan.bv, shape[1], mode == "stash")
    assert plan.dw_vmem <= ce._VMEM_LIMIT
    assert set(plan._asdict()) == {
        "bn", "bv", "bn_dw", "bv_dw", "bn_dx", "mode", "dx_vmem", "dw_vmem",
        "dx_vmem_limit"}


def test_ce_plan_is_none_where_no_block_tiles_the_tokens():
    from saturn_tpu.ops import ce

    assert ce.ce_plan(100, 64, 256) is None
    assert ce.ce_plan(128, 64, 256, block_n=48) is None


@pytest.mark.parametrize("interpret", [True, None], ids=["kernel", "dense-fallback"])
def test_traced_plans_say_what_a_call_ran_as(interpret):
    """``plans.traced`` collects the plan the op followed, None for a call
    that computed through plain XLA ops (off-TPU without interpret mode)."""
    from saturn_tpu.ops import ce, plans

    x, w, labels = _case(n=128, d=64, v=256)
    with plans.traced() as outer:
        with plans.traced() as got:
            jax.make_jaxpr(lambda x_: fused_linear_cross_entropy(
                x_, w, labels, interpret=interpret))(x)
        assert outer == {}          # the inner block kept its own
    want = ce.ce_plan(128, 64, 256) if interpret else None
    assert got == {"ce": [want]}
    fused_linear_cross_entropy(x, w, labels, interpret=interpret)  # no block open


# dx's token block only groups rows: every row's sum over the vocab blocks
# runs in the same order, so the gradients are bitwise what they were.
@pytest.mark.parametrize("stash", [False, True], ids=["recompute", "stash"])
def test_dx_token_block_changes_no_bit(stash):
    from saturn_tpu.ops import ce

    n, d, v = 512, 64, 300          # 300: the padded, masked vocab block too
    x, w, labels = _case(n=n, d=d, v=v, masked=16, dtype=jnp.bfloat16)
    lab = labels.reshape(n, 1)

    def run(bn_dx):
        blocks = (256, 128, 128, 128, bn_dx)
        loss, vjp = jax.vjp(
            lambda x_, w_: ce._fused_ce(x_, w_, lab, blocks, v, True, stash, None),
            x, w)
        valid = (lab != -1).astype(jnp.float32)
        return (loss,) + vjp(valid / valid.sum())

    small, large = run(64), run(256)
    for a, b in zip(small, large):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ref_gx, ref_gw = jax.grad(
        lambda x_, w_: dense_linear_cross_entropy(x_, w_, labels),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(
        np.asarray(large[1], np.float32), np.asarray(ref_gx, np.float32),
        rtol=3e-2, atol=2e-4)
    np.testing.assert_allclose(np.asarray(large[2]), np.asarray(ref_gw),
                               rtol=3e-2, atol=3e-4)


# ------------- the backward's mode, stated by a caller and asked of the compile
# (PR 51). Under ``STASH_BYTES_MAX`` nobody is asked and the automatic choice
# is what it was (``AUTO_BLOCKS`` and ``CELL_PLANS`` above); over it a model's
# ``ce_mode`` states the mode, and the trial runner states ``stash`` for a
# grid point whose compiled program has room (``SPMDTechnique._head_rungs``;
# the rungs themselves are ``tests/test_search_pipeline.py``'s).
#: (tokens, d_model, vocab) -> the bf16 logits a stash-mode call would keep,
#: None where an unasked call keeps them by itself (the first two are over
#: the constant: GPT-J's and Ouro's cells; the third is gpt2-medium's)
STASHES = {
    (8192, 4096, 50400): 8192 * 50688 * 2,
    (8192, 2048, 49152): 8192 * 49152 * 2,
    (4096, 1024, 50257): None,
    (2048, 4096, 50400): None,
}


@pytest.mark.parametrize("shape", list(STASHES))
def test_the_constant_says_whose_mode_is_worth_asking_about(shape, monkeypatch):
    from saturn_tpu.ops import ce

    assert ce.stash_over_the_constant(*shape) is None   # off the TPU: no kernel
    monkeypatch.setattr(ce, "_use_interpret", lambda: False)
    assert ce.stash_over_the_constant(*shape) == STASHES[shape]
    auto, kept = ce.ce_plan(*shape), ce.ce_plan(*shape, stash=True)
    assert (auto.mode == "recompute") == (STASHES[shape] is not None)
    assert ce.call_plan(*shape) == auto
    # stating the mode the op would choose changes nothing of the plan
    assert ce.ce_plan(*shape, stash=auto.mode == "stash") == auto
    assert kept.mode == "stash"
    assert (ce._stash_bytes(shape[0], shape[2], kept.blocks)
            > ce.STASH_BYTES_MAX) == (STASHES[shape] is not None)


def test_no_token_block_means_no_stash_to_ask_about(monkeypatch):
    from saturn_tpu.ops import ce

    monkeypatch.setattr(ce, "_use_interpret", lambda: False)
    monkeypatch.setattr(ce, "STASH_BYTES_MAX", 0)
    assert ce.stash_over_the_constant(100, 64, 256) is None
    assert ce.stash_over_the_constant(128, 64, 256) == 128 * 256 * 2


@pytest.mark.parametrize("mode", [None, "stash", "recompute"])
@pytest.mark.parametrize("family", ["gpt2", "bert"])
def test_a_models_ce_mode_reaches_the_plan(family, mode, monkeypatch):
    """Both callers of the fused head hand ``ce_mode`` on; a model that states
    nothing traces to the plan the op chooses (here ``stash``: 128 tokens x
    256 columns), one that states ``recompute`` to the other."""
    from saturn_tpu.models import bert, gpt2
    from saturn_tpu.ops import ce, plans

    monkeypatch.setattr(ce, "_use_interpret", lambda: False)
    spec = (bert.build_bert("bert-test-tiny", ce_mode=mode) if family == "bert"
            else gpt2.build_gpt2("test-tiny", ce_mode=mode))
    assert spec.config.ce_mode == mode
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    with plans.traced() as got:
        jax.make_jaxpr(jax.grad(spec.fused_loss_fn))(params, tokens)
    (plan,) = got["ce"]
    d, v = spec.config.d_model, spec.config.vocab_size
    assert plan == ce.ce_plan(128, d, v, stash=ce.stash_of(mode))
    assert plan.mode == (mode or "stash")


def test_an_unknown_ce_mode_is_refused_where_the_model_is_built():
    from saturn_tpu.models import gpt2
    from saturn_tpu.ops import ce

    with pytest.raises(ValueError, match="ce_mode"):
        gpt2.build_gpt2("test-tiny", ce_mode="keep")
    with pytest.raises(ValueError, match="ce_mode"):
        ce.stash_of("keep")
    assert [ce.stash_of(m) for m in (None, "stash", "recompute")] == [
        None, True, False]


def _lm_task(tmp_path, name="asked", preset="test-tiny", batch=2, **task_kw):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models import gpt2
    from saturn_tpu.models.loss import pretraining_loss

    kw = dict(
        get_model=lambda **kw: gpt2.build_gpt2(preset, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=64, batch_size=batch, vocab_size=256,
            n_tokens=64 * batch * 16),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=16),
        save_dir=str(tmp_path / f"ckpts-{name}"), name=name)
    kw.update(task_kw)
    return Task(**kw)


HBM = 1 << 30


@pytest.fixture()
def asked(monkeypatch):
    """A chip of 1 GiB, kernels that "lower", and a constant so small that
    the tiny model's 64 KiB of logits are over it."""
    from saturn_tpu.ops import ce

    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(HBM))
    monkeypatch.setattr(ce, "_use_interpret", lambda: False)
    monkeypatch.setattr(ce, "STASH_BYTES_MAX", 1024)


def _state_bytes(task):
    params = task.get_model().abstract_init()
    state = (params, jax.eval_shape(task.hparams.make_optimizer().init, params))
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))


def test_head_rungs_of_a_head_over_the_constant(tmp_path, asked, monkeypatch,
                                                devices8):
    from saturn_tpu.parallel.dp import DataParallel

    task = _lm_task(tmp_path, batch=4)
    tech = DataParallel()
    for config in ({"remat": False}, {"remat": True, "attention": "dense"}):
        assert tech._head_rungs(task, devices8[:1], config) == {
            "stash_bytes": 4 * 64 * 256 * 2}
    # under dp's shard_map the head sees the shard's tokens; the state is whole
    assert tech._head_rungs(task, devices8[:2], {"remat": False}) == {
        "stash_bytes": 2 * 64 * 256 * 2}
    for n in (1, 2):
        assert tech._room_after_state(task, devices8[:n]) \
            == int(0.92 * HBM) - _state_bytes(task)
    # no limit known (the CPU reports none), or a state that cannot be traced:
    # no static bound, the compile says
    monkeypatch.delenv("SATURN_TPU_HBM_BYTES")
    assert tech._room_after_state(task, devices8[:1]) is None
    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(HBM))
    broken = _lm_task(tmp_path, name="broken", get_model=_cannot_be_built)
    assert tech._room_after_state(broken, devices8[:1]) is None


def _tiny(preset="test-tiny", **fixed):
    from saturn_tpu.models import gpt2

    return lambda **kw: gpt2.build_gpt2(preset, **{**fixed, **kw})


def _without_the_field(**kw):
    import types

    spec = _tiny()(**kw)
    spec.config = types.SimpleNamespace(d_model=64, vocab_size=256)
    return spec


def _cannot_be_built(**kw):
    raise RuntimeError("no such checkpoint")


def _untagged_loss(logits, batch):
    from saturn_tpu.models.loss import pretraining_loss

    return pretraining_loss(logits, batch)


#: why nobody is asked -> (technique, chips, grid config, what the task is
#: made with beside ``_lm_task``'s own); the last two are the op's own reasons
NOBODY_ASKED = {
    "the-config-states-it": ("dp", 1, {"remat": False, "ce_mode": "recompute"}, {}),
    "the-users-kwargs-state-it": ("dp", 1, {"remat": False}, {
        "get_model": _tiny(ce_mode="recompute")}),
    "a-model-without-a-fused-head": ("dp", 1, {"remat": False}, {
        "get_model": _tiny("moe-test-tiny")}),
    "a-model-that-takes-no-ce-mode": ("dp", 1, {"remat": False}, {
        "get_model": _without_the_field}),
    "a-model-that-cannot-be-built": ("dp", 1, {"remat": False}, {
        "get_model": _cannot_be_built}),
    "a-loss-the-head-does-not-compute": ("dp", 1, {"remat": False}, {
        "loss_fn": _untagged_loss}),
    "a-technique-that-shards-the-vocabulary": ("tp", 1, {"remat": False}, {}),
    "params-sharded-over-chips": ("fsdp", 2, {"remat": False}, {}),
    "state-in-host-memory": ("fsdp", 1, {"remat": False, "offload": True}, {}),
    "under-the-constant": ("dp", 1, {"remat": False}, {}),
    "no-kernel-on-this-backend": ("dp", 1, {"remat": False}, {}),
}


@pytest.mark.parametrize("case", list(NOBODY_ASKED))
def test_head_rungs_is_none_where_nobody_is_asked(case, tmp_path, monkeypatch,
                                                  devices8):
    from saturn_tpu.ops import ce
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    monkeypatch.setenv("SATURN_TPU_HBM_BYTES", str(HBM))
    monkeypatch.setattr(ce, "STASH_BYTES_MAX",
                        ce.STASH_BYTES_MAX if case == "under-the-constant" else 1024)
    monkeypatch.setattr(ce, "_use_interpret",
                        lambda: case == "no-kernel-on-this-backend")
    name, n, config, task_kw = NOBODY_ASKED[case]
    task = _lm_task(tmp_path, **task_kw)
    tech = BUILTIN_TECHNIQUES[name]()
    assert tech._head_rungs(task, devices8[:n], config) is None
    if case in ("under-the-constant", "no-kernel-on-this-backend"):
        # ... and the same point is asked about once the op's reason is gone
        monkeypatch.setattr(ce, "STASH_BYTES_MAX", 1024)
        monkeypatch.setattr(ce, "_use_interpret", lambda: False)
        assert tech._head_rungs(task, devices8[:n], config) is not None


@pytest.fixture()
def kernels_in_interpret_mode(asked, monkeypatch):
    """``asked``, and every fused call runs its kernels in interpret mode, so
    that a whole search compiles and runs here."""
    import functools

    from saturn_tpu.ops import ce

    monkeypatch.setattr(ce, "fused_linear_cross_entropy", functools.partial(
        ce.fused_linear_cross_entropy, interpret=True))


def _jaxpr_text(bundle):
    import re

    return re.sub(r"0x[0-9a-f]+", "0x", str(bundle.traced["jaxpr"]))


@pytest.mark.parametrize("rung", ["fits", "over"])
def test_a_search_asks_the_compile_and_its_config_says_what_it_was_told(
        rung, tmp_path, kernels_in_interpret_mode, monkeypatch, devices8):
    """dp's own search over one grid point of a real (tiny) model. Where the
    stash rung fits: one build, the returned config carries ``ce_mode``, the
    ``trial_config`` event's ``ce_plan`` reads ``stash``, and a technique
    that knows nothing but that config (``execute()`` in a later process)
    builds the program the trial timed, text for text. Where the memory rule
    rejects it: a second build, the point timed as the grid has it."""
    from saturn_tpu.parallel.dp import DataParallel
    from saturn_tpu.utils import metrics

    class OnePoint(DataParallel):
        built = None

        def candidate_configs(self, task, n_devices):
            return [{"remat": False}]

        def _build_uncached(self, task, devices, config):
            self.built.append(dict(config))   # a Python trace of the step
            return super()._build_uncached(task, devices, config)

        def _fits_compiled(self, compiled, devices, *, config=None, said=None,
                           **kw):
            if rung == "over" and config.get("ce_mode") == "stash":
                said.update(need_bytes=HBM, limit_bytes=HBM)
                return False
            return super()._fits_compiled(compiled, devices, config=config,
                                          said=said, **kw)

    monkeypatch.setenv("SATURN_TPU_MAX_WINDOW", "2")
    task = _lm_task(tmp_path, name=f"asked-{rung}")
    tech = OnePoint()
    tech.built = []
    path = str(tmp_path / "events.jsonl")
    with metrics.scoped(path):
        config, per_batch = tech.search(task, devices8[:1], 0)
    (note,) = metrics.read_events(path, kind="trial_config")
    (span,) = metrics.read_events(path, kind="trial.config")
    stash = {"remat": False, "ce_mode": "stash"}
    if rung == "fits":
        assert config == stash and tech.built == [stash]
        assert note["ce_plan"]["mode"] == "stash"
        assert note["ce_ladder"]["tried"] == ["stash"]
        assert note["ce_ladder"]["kept"] == "stash"
    else:
        assert config == {"remat": False}
        assert tech.built == [stash, {"remat": False}]
        assert note["ce_plan"]["mode"] == "recompute"
        assert note["ce_ladder"]["tried"] == ["stash", "recompute"]
        assert note["ce_ladder"]["kept"] == "recompute"
        assert note["ce_ladder"]["refused"] == {
            "outcome": "memory_rejected", "need_bytes": HBM, "limit_bytes": HBM}
    assert note["config"] == span["config"] == config
    assert note["ce_ladder"] == span["ce_ladder"]
    assert note["ce_ladder"]["stash_bytes"] == 2 * 64 * 256 * 2
    assert note["ce_ladder"]["room_bytes"] == int(0.92 * HBM) - _state_bytes(task)
    assert span["outcome"] == "timed" and note["per_batch_s"] == per_batch > 0
    assert note["step_traces"] == 1 and "memory_rejected" not in note
    # the config alone gives the program again
    timed = tech._cached_bundle(task, devices8[:1], config)
    again = DataParallel().build(task, devices8[:1], dict(config))
    assert again is not timed and _jaxpr_text(again) == _jaxpr_text(timed)
    assert again.plans["ce"][0].mode == note["ce_plan"]["mode"]
    other = DataParallel().build(
        task, devices8[:1], stash if rung == "over" else {"remat": False})
    assert _jaxpr_text(other) != _jaxpr_text(timed)
