"""The main path's Pallas kernels, compiled for a described v5e.

No chip is attached here: ``jax.experimental.topologies`` describes a
v5e:2x2 and the TPU compiler that is installed lowers for it, so a kernel the
chip's compiler would refuse (VMEM over the scoped limit, a misaligned slice)
is refused in the CPU suite already. Nothing runs, so nothing here is a
measurement.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and under several
pytest-xdist workers a module that touched it while being imported would give
the workers different tests to collect. All cases stay in this one file for
the same reason (one worker holds the library).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from saturn_tpu.ops import ce as ce_mod
from saturn_tpu.ops import flash as flash_mod
from saturn_tpu.ops import gdn as gdn_mod
from saturn_tpu.ops import moe as moe_mod


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def real_lowering(monkeypatch):
    """The kernels pick interpret mode from the default backend, which is the
    CPU here; the compile is for the described chip, so steer them."""
    monkeypatch.setattr(flash_mod, "_use_interpret", lambda: False)
    monkeypatch.setattr(ce_mod, "_use_interpret", lambda: False)
    monkeypatch.setattr(gdn_mod, "_use_interpret", lambda: False)
    monkeypatch.setattr(moe_mod, "_interpret", lambda: False)


def _compile(fn, *shapes, kernels):
    """Compile for the described chip; every named kernel must be there as a
    ``tpu_custom_call`` (the names are the ``name=`` of the pallas_calls)."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    for kernel in kernels:
        assert any(kernel in line for line in calls), (kernel, len(calls))
    return text


# ------------------------------------------------------------------- flash
def _flash_loss(q, k, v):
    out = flash_mod.flash_attention(q, k, v)
    return jnp.sum(out.astype(jnp.float32))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize(
    "shape", [(8, 12, 512, 64), (8, 12, 1024, 64), (2, 16, 4096, 128),
              (1, 15, 8192, 128), (4, 16, 2048, 256)],
    ids=["t512", "t1024", "ouro-t4096-h128", "hybrid-15-heads-t8192-h128",
         "gptj-t2048-h256"],
)
def test_flash_attention_compiles_for_v5e(one_chip, real_lowering, shape, grad):
    sds = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fn = jax.grad(_flash_loss, argnums=(0, 1, 2)) if grad else _flash_loss
    kernels = ["saturn_flash_fwd"]
    if grad:
        kernels += ["saturn_flash_dq", "saturn_flash_dkv"]
    _compile(fn, sds, sds, sds, kernels=kernels)


@pytest.mark.parametrize("heads", [48, 64], ids=["6-a-kv-head", "8-a-kv-head"])
def test_window_and_grouped_flash_compile_for_v5e(one_chip, real_lowering, heads):
    """The Laguna cell's attention at its own shapes: 48 / 64 q heads over 8
    k/v heads of 128, seq 8192 x batch 2; the window kernels (window 512,
    blocks of 512: 2 key blocks a query block, fwd and dq walking them inside
    a chunk of all of T) under names of their own."""
    q = jax.ShapeDtypeStruct((2, heads, 8192, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 128), jnp.bfloat16, sharding=one_chip)
    window = 512 if heads == 64 else None

    def loss(q, k, v):
        return jnp.sum(flash_mod.flash_attention(q, k, v, window=window).astype(jnp.float32))

    family = "saturn_swa" if window else "saturn_flash"
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
                    kernels=[f"{family}_fwd", f"{family}_dq", f"{family}_dkv"])
    assert ("saturn_flash_" in text) == (window is None)


@pytest.mark.parametrize("window", [4096, None], ids=["window-of-half", "full"])
def test_flash_at_seven_q_heads_a_kv_head_compiles_for_v5e(one_chip, real_lowering, window):
    """The SmallThinker cell's attention at its own shapes: 28 q heads over 4
    k/v heads of 128 (7 a group: the dkv kernel walks a group's members), seq
    8192 x batch 4; the window kernels at a window of half the sequence (all
    three walk its at most 9 blocks of 512 by the loop inside a chunk of all
    of T), the full layer's under ``saturn_flash_*``."""
    q = jax.ShapeDtypeStruct((4, 28, 8192, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 4, 8192, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_mod.flash_attention(q, k, v, window=window).astype(jnp.float32))

    family = "saturn_swa" if window else "saturn_flash"
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
                    kernels=[f"{family}_fwd", f"{family}_dq", f"{family}_dkv"])
    assert ("saturn_flash_" in text) == (window is None)


def test_the_two_halves_of_a_reglu_routed_layer_compile_for_v5e(one_chip, real_lowering):
    """``saturn_gmm_*`` at the SmallThinker cell's shape, through the route
    and the experts under it: 32768 tokens, softmax top-6 of 64 experts, 16
    held ReGLU tables of 2560 x 768 (Ling's table shape at 24 times its
    rows), a row buffer of 1.5 x the mean pairs behind the exact second
    path, the router reading other rows than the experts."""
    sds = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(   # noqa: E731
        shape, dtype, sharding=one_chip)
    plan = moe_mod.routed_plan(32768, 64, 16, 6, buffer=1.5, impl="kernel", act="reglu",
                               score="softmax", route_from="block_input")
    assert (plan.rows, plan.row_tile, plan.second_path) == (73728 + 2048, 128, True)

    def loss(x, u, router, w_gate, w_up, w_down):
        made = moe_mod.route(x, router, plan=plan)
        out, _ = moe_mod.experts_under(made, u, w_gate, w_up, w_down, plan=plan)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)),
             sds(32768, 2560, dtype=jnp.bfloat16), sds(32768, 2560, dtype=jnp.bfloat16),
             sds(2560, 64), sds(16, 2560, 768), sds(16, 2560, 768), sds(16, 768, 2560),
             kernels=["saturn_gmm_fwd", "saturn_gmm_dx", "saturn_gmm_dw"])


def test_routed_layer_kernels_compile_for_v5e(one_chip, real_lowering):
    """``saturn_gmm_fwd`` / ``_dx`` / ``_dw`` at the Laguna cell's shape:
    16384 tokens, top-8 of 256 experts, 32 held, d 2048, experts of 512, a
    row buffer of 2 x the mean pairs in tiles of 128 rows, with the exact
    second path behind a ``cond``."""
    sds = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(   # noqa: E731
        shape, dtype, sharding=one_chip)
    plan = moe_mod.routed_plan(16384, 256, 32, 8, buffer=2.0, impl="kernel")
    assert (plan.rows, plan.second_path) == (36864, True)

    def loss(y, router, w_gate, w_up, w_down):
        out, _ = moe_mod.routed_experts(y, router, w_gate, w_up, w_down, plan=plan, scale=2.5)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
             sds(16384, 2048, dtype=jnp.bfloat16), sds(2048, 256), sds(32, 2048, 512),
             sds(32, 2048, 512), sds(32, 512, 2048),
             kernels=["saturn_gmm_fwd", "saturn_gmm_dx", "saturn_gmm_dw"])


# ------------------------------------------------------ gated delta rule
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_gated_delta_rule_kernel_compiles_for_v5e(one_chip, real_lowering, grad):
    """``saturn_gdn_fwd`` / ``saturn_gdn_fwd_only`` at the hybrid cell's own
    shape: 15 heads with keys of 96 and values of 192 lanes (neither a
    multiple of 128), 8192 tokens, bf16 operands, a float32 state in VMEM."""
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    args = (sds(1, 15, 8192, 96), sds(1, 15, 8192, 96), sds(1, 15, 8192, 192),
            sds(1, 15, 8192, dtype=jnp.float32), sds(1, 15, 8192, dtype=jnp.float32))

    def loss(*a):
        return jnp.sum(gdn_mod.gated_delta_rule(*a, impl="kernel").astype(jnp.float32))

    if grad:   # the differentiated forward keeps the chunks' states; the backward is XLA's
        text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *args,
                        kernels=["saturn_gdn_fwd"])
        assert "saturn_gdn_fwd_only" not in text
    else:
        _compile(loss, *args, kernels=["saturn_gdn_fwd_only"])


# ------------------------------- Kimi delta attention, latent attention
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_kda_scan_compiles_for_v5e(one_chip, real_lowering, grad):
    """The delta rule's chunked scan (plain XLA ops: no kernel of it yet) at
    the Ling cell's own shape: 32 heads of 128 keys and values, 8192 tokens
    in chunks of 64 with 16-token sub-blocks, bf16 operands, a gate a key
    channel in float32; the differentiated call keeps the chunks' states."""
    from saturn_tpu.ops import kda as kda_mod
    from saturn_tpu.ops import plans as op_plans

    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    args = (sds(1, 32, 8192, 128), sds(1, 32, 8192, 128), sds(1, 32, 8192, 128),
            sds(1, 32, 8192, 128, dtype=f32), sds(1, 32, 8192, dtype=f32))
    loss = lambda *a: jnp.sum(kda_mod.kda(*a))
    with op_plans.traced() as got:
        text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if grad else loss,
                        *args, kernels=[])
    plans = got["kda"]
    assert "tpu_custom_call" not in text
    assert (plans[0].sub, plans[0].chunks) == (16, 128)
    assert plans[0].state_bytes_kept == 128 * 32 * 128 * 128 * 4


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_latent_attention_flash_compiles_for_v5e(one_chip, real_lowering, grad):
    """``saturn_mla_*`` at the Ling cell's own shape: 32 heads, q and k of
    192 lanes (128 content + 64 rotary), v of 128, 8192 tokens: the walked
    side goes in two chunks of 4096 (8192 x 192 is over the chunk guard)."""
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    args = (sds(1, 32, 8192, 192), sds(1, 32, 8192, 192), sds(1, 32, 8192, 128))
    plan = flash_mod.flash_plan(8192, 192, d_v=128)
    assert (plan["d_qk"], plan["d_v"], plan["fwd"]["chunk"], plan["fwd"]["block_q"]) == (
        192, 128, 4096, 512)
    if grad:
        text = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)), *args,
                        kernels=["saturn_mla_fwd", "saturn_mla_dq", "saturn_mla_dkv"])
    else:
        text = _compile(_flash_loss, *args, kernels=["saturn_mla_fwd"])
    assert "saturn_flash_" not in text


# ------------------------------------------- a kernel program's cache key
@pytest.mark.parametrize("frames", [10, 4], ids=["ten-frames", "four-frames"])
def test_a_kernel_programs_text_holds_its_callers_unless_locations_are_short(
        one_chip, real_lowering, monkeypatch, frames):
    """A Pallas kernel's payload carries its trace's source locations, ten
    frames of traceback each by default: the same program lowered under
    another caller is then another text, so another entry of the compile
    cache (two ``jit_saturn_window`` entries a cell, PR 36). At four frames,
    which ``maybe_enable_persistent_compile_cache`` sets wherever the cache
    is on, the text is the program's alone and the compiled kernel keeps its
    name (what a device trace and every roofline reader know it by)."""
    from saturn_tpu.ops import ssd as ssd_mod

    monkeypatch.setattr(ssd_mod, "_use_interpret", lambda: False)
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    args = (sds(1, 256, 2, 64), sds(1, 256, 2, dtype=f32), sds(2, dtype=f32),
            sds(1, 256, 1, 128), sds(1, 256, 1, 128), sds(2, dtype=f32))

    def lowered():
        # (a new function each time: a jit of the same one answers from its
        # trace cache; the flash kernels' bodies are traced once a shape, PR 41)
        return jax.jit(lambda *a: ssd_mod.ssd(*a, impl="kernel")).lower(*args)

    def under_another_caller():
        return (lambda: lowered())()

    before = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", frames)
    try:
        one, other = lowered(), under_another_caller()
        same = one.as_text() == other.as_text()
        compiled = one.compile().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", before)
    assert same == (frames == 4)
    assert "%saturn_ssd_fwd_only" in compiled


# ------------------------------------------------ state-space recurrence
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_ssd_kernel_compiles_for_v5e(one_chip, real_lowering, monkeypatch, grad):
    """``saturn_ssd_fwd`` / ``saturn_ssd_fwd_only`` at the Nemotron cell's own
    shape: 32 heads of 64 lanes in 2 groups of a 128-wide state, 8192 tokens
    in chunks of 128, bf16 operands, sixteen float32 states in VMEM, the
    group's heads walked by a loop inside the kernel."""
    from saturn_tpu.ops import ssd as ssd_mod

    monkeypatch.setattr(ssd_mod, "_use_interpret", lambda: False)
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    args = (sds(1, 8192, 32, 64), sds(1, 8192, 32, dtype=f32), sds(32, dtype=f32),
            sds(1, 8192, 2, 128), sds(1, 8192, 2, 128), sds(32, dtype=f32))

    def loss(*a):
        return jnp.sum(ssd_mod.ssd(*a, impl="kernel"))

    if grad:   # the differentiated forward keeps the chunks' states; the backward is XLA's
        text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)), *args,
                        kernels=["saturn_ssd_fwd"])
        assert "saturn_ssd_fwd_only" not in text
    else:
        _compile(loss, *args, kernels=["saturn_ssd_fwd_only"])


def test_routed_layer_kernels_compile_for_v5e_at_a_latent_width(one_chip, real_lowering):
    """The grouped products at the Nemotron cell's shapes: 8 held relu2
    experts of 1024 x 2688 (2688 = 21 x 128: the table gradient's block keeps
    to whole lanes of 128), two products an expert, a 6656-row buffer."""
    plan = moe_mod.routed_plan(8192, 512, 8, 22, impl="kernel", act="relu2",
                               latent=1024, bias=True)
    assert (plan.rows, plan.row_tile) == (6656, 128)
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def loss(y, latent, router, bias, w_up, w_down):
        out, _ = moe_mod.routed_experts(y, router, None, w_up, w_down, plan=plan,
                                        scale=5.0, bias=bias, latent=latent)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2, 4, 5)),
             sds(8192, 4096), sds(8192, 1024), sds(4096, 512, dtype=jnp.float32),
             sds(512, dtype=jnp.float32), sds(8, 1024, 2688, dtype=jnp.float32),
             sds(8, 2688, 1024, dtype=jnp.float32),
             kernels=["saturn_gmm_fwd", "saturn_gmm_dx", "saturn_gmm_dw"])


def test_routed_layer_kernels_compile_for_v5e_at_a_table_over_the_default_vmem(
        one_chip, real_lowering):
    """The grouped products at the LFM2 cell's shapes: 32768 tokens, sigmoid
    top-4 of 32 experts under a selection bias, 8 held SwiGLU tables of 2048 x
    1792 (7 MiB in bf16: double-buffered 14 of the 16 MiB a kernel is given
    unasked), a row buffer of 1.5 x the mean pairs. ``saturn_gmm_fwd`` /
    ``_dx`` keep one block an expert and ask the compiler for the VMEM
    ``gmm_plan`` sums (21-22 MiB); ``saturn_gmm_dw`` asks for nothing."""
    sds = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(   # noqa: E731
        shape, dtype, sharding=one_chip)
    plan = moe_mod.routed_plan(32768, 32, 8, 4, buffer=1.5, impl="kernel", bias=True,
                               eps=1e-6)
    assert (plan.rows, plan.row_tile, plan.second_path) == (49152 + 1024, 128, True)

    def loss(y, router, bias, w_gate, w_up, w_down):
        out, _ = moe_mod.routed_experts(y, router, w_gate, w_up, w_down, plan=plan,
                                        bias=bias)
        return jnp.sum(out.astype(jnp.float32))

    args = (sds(32768, 2048, dtype=jnp.bfloat16), sds(2048, 32), sds(32),
            sds(8, 2048, 1792), sds(8, 2048, 1792), sds(8, 1792, 2048))
    grad = jax.grad(loss, argnums=(0, 1, 3, 4, 5))
    lowered = jax.jit(grad).lower(*args).as_text()
    asked = {m for line in lowered.splitlines() if "tpu_custom_call" in line
             for kernel in ("saturn_gmm_fwd", "saturn_gmm_dx", "saturn_gmm_dw")
             if f'kernel_name = "{kernel}"' in line
             for m in [(kernel, (re.search(r"scoped_memory_configs.*?size\\22:\s*(\d+)",
                                           line) or [None, None])[1])]}
    # (21 MiB where the row tile leaves 1792 lanes wide, 22 where it leaves 2048)
    both = {str(21 << 20), str(22 << 20)}
    assert asked == {(kernel, size) for kernel in ("saturn_gmm_fwd", "saturn_gmm_dx")
                     for size in both} | {("saturn_gmm_dw", None)}
    _compile(grad, *args, kernels=["saturn_gmm_fwd", "saturn_gmm_dx", "saturn_gmm_dw"])


def test_flash_at_four_q_heads_a_kv_head_of_64_lanes_compiles_for_v5e(
        one_chip, real_lowering):
    """``saturn_flash_*`` at the LFM2 cell's shape, which no cell had run:
    head 64 (gpt2-medium's, the kernels' weakest) under grouped k/v, 32 q
    heads over 8 k/v heads, 8192 positions, batch 4."""
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,   # noqa: E731
                                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_mod.flash_attention(q, k, v, causal=True).astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             sds(4, 32, 8192, 64), sds(4, 8, 8192, 64), sds(4, 8, 8192, 64),
             kernels=["saturn_flash_fwd", "saturn_flash_dq", "saturn_flash_dkv"])


# ---------------------------------------------------------------- fused CE
CE_SHAPES = {
    "gpt2-small": (4096, 768, 50257),
    "gpt2-xl": (8192, 1600, 50257),
    "gptj-6b": (2048, 4096, 50400),
    # the benchmark cell's own shape (seq 2048 x batch 4): over the stash
    # threshold, so auto is recompute mode; dx runs its compute-bound
    # 256-token block there and asks the compiler for the VMEM (PR 31)
    "gptj-6b-8k": (8192, 4096, 50400),
    # d 2048, the one width the other rows do not hold: 8192 tokens are over
    # the stash threshold, so auto is recompute mode, where dx at its full
    # 512-token block wanted 16.79 MiB and dW at a 512-row block 19.11 MiB
    # (PR 28); d 1024 at 8192 tokens is PERF.md Findings 1's case (dx 19.17,
    # dW 18.00 MiB) and falls out of the same rule
    "ouro-2.6b": (8192, 2048, 49152),
    "gpt2-medium-8k": (8192, 1024, 50257),
    # d 3840 = 30 x 128 lanes and the held eighth of a vocabulary (12544 rows
    # = 24.5 blocks of 512): the hybrid cell's head, stash mode (8192 x 12544
    # bf16 scores are 0.2 GB); dx asks for its VMEM as at d 4096
    "olmo-hybrid-8k": (8192, 3840, 12544),
    # d 2560 = 20 x 128 lanes and the held eighth of a vocabulary (19712 rows
    # = 154 x 128 = 38.5 blocks of 512): the Ling cell's head, a width no
    # other cell runs (PR 45)
    "ling-8k": (8192, 2560, 19712),
    # the SmallThinker cell's head: d 2560 again, 19072 rows = 149 x 128 (no
    # multiple of any larger block: padded under the mask to the blocks'
    # common multiple) at seq 8192 x batch 4 (PR 49)
    "smallthinker-8k-b4": (32768, 2560, 19072),
    # the LFM2 cell's head: d 2048 (Ouro's and Laguna's width) over the held
    # quarter of a tied vocabulary, 16384 rows, at seq 8192 x batch 4 (PR 52)
    "lfm2-8k-b4": (32768, 2048, 16384),
}


def _ce_args(one_chip, n, d, v):
    return (
        jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((v, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
    )


@pytest.mark.parametrize("name", ["gpt2-small", "gpt2-xl"])
def test_fused_ce_forward_compiles_for_v5e(one_chip, real_lowering, name):
    _compile(
        ce_mod.fused_linear_cross_entropy, *_ce_args(one_chip, *CE_SHAPES[name]),
        kernels=["saturn_ce_fwd"],
    )


def _vmem_limit_of(lowered_text, kernel):
    """The scoped VMEM the custom call of ``kernel`` asks for in a lowered
    (not yet compiled) program's text (what ``vmem_limit_bytes`` becomes: the
    call's ``scoped_memory_configs`` size); None where it asks for none."""
    calls = [line for line in lowered_text.splitlines()
             if "tpu_custom_call" in line and f'kernel_name = "{kernel}"' in line]
    assert len(calls) == 1, (kernel, len(calls))
    m = re.search(r"scoped_memory_configs.*?size\\22:\s*(\d+)", calls[0])
    return int(m.group(1)) if m else None


#: every shape unasked and recomputing, and stashing too where an unasked
#: call would not: the mode the trial runner asks the compile about (PR 51;
#: ``gptj-6b-8k`` is the row ``gptj-6b-1chip.steady`` runs since)
#: ...but one: at d 1024 from 8192 tokens on, stash-mode dW at its (512,
#: 1024) blocks is allocated 17.68 MiB of scoped VMEM where its sum says 16.0
#: (up to 4096 tokens the same blocks are admitted). The trial runner's stash
#: rung is then refused by the compiler and the point recomputes; a test
#: below holds the refusal so that a repaired rule is noticed (ROADMAP S3).
CE_STASH_REFUSED = ("gpt2-medium-8k",)
CE_GRADS = [(name, stash) for name in CE_SHAPES for stash in (None, False)] + [
    (name, True) for name, shape in CE_SHAPES.items()
    if ce_mod.ce_plan(*shape).mode == "recompute"
    and name not in CE_STASH_REFUSED]


@pytest.mark.parametrize("name,stash", CE_GRADS, ids=[
    f"{name}-{ {None: 'auto', False: 'recompute', True: 'stash'}[stash] }"
    for name, stash in CE_GRADS])
def test_fused_ce_grad_compiles_for_v5e(one_chip, real_lowering, name, stash):
    n, d, v = CE_SHAPES[name]

    def loss(x, w, labels):
        return ce_mod.fused_linear_cross_entropy(x, w, labels, stash=stash)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *_ce_args(one_chip, n, d, v))
    # dx carries a limit exactly where the plan asks for one; the other two
    # kernels never do
    plan = ce_mod.ce_plan(n, d, v, stash=stash)
    text = lowered.as_text()
    assert _vmem_limit_of(text, "saturn_ce_dx") == plan.dx_vmem_limit
    assert _vmem_limit_of(text, "saturn_ce_fwd") is None
    assert _vmem_limit_of(text, "saturn_ce_dw") is None
    assert (plan.dx_vmem_limit is not None) == (d in (2560, 3840, 4096)), plan
    compiled = lowered.compile().as_text()
    for kernel in ("saturn_ce_fwd", "saturn_ce_dx", "saturn_ce_dw"):
        assert any(kernel in line for line in compiled.splitlines()
                   if "tpu_custom_call" in line), kernel


@pytest.mark.parametrize("name", CE_STASH_REFUSED)
def test_a_stash_the_compiler_refuses_is_a_refusal_for_memory(
        one_chip, real_lowering, name):
    """What the trial runner's stash rung meets at this shape: the compiler's
    ``RESOURCE_EXHAUSTED`` for a kernel's scoped VMEM, which ``aot_cache``
    files as a refusal for memory (the rung is lost, not the point)."""
    from saturn_tpu.utils import aot_cache

    def loss(x, w, labels):
        return ce_mod.fused_linear_cross_entropy(x, w, labels, stash=True)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *_ce_args(one_chip, *CE_SHAPES[name]))
    with pytest.raises(Exception, match=aot_cache._REFUSAL_MARK) as refused:
        lowered.compile()
    assert "saturn_ce_dw" in str(refused.value)
    assert "vmem" in str(refused.value)


# ------------------------------------------- a whole step on the 2x2 mesh
def test_dp_step_with_sharded_fused_ce_compiles_for_v5e_2x2(
        topo, real_lowering, tmp_path):
    """On a multi-chip block dp runs the fused CE head under shard_map (a
    Mosaic kernel has no partitioning rule of its own). GPT-2-small width,
    depth cut to 2 (the layer stack is scanned, so depth changes nothing the
    compiler checks here)."""
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2
    from saturn_tpu.models.loss import pretraining_loss
    from saturn_tpu.parallel.dp import DataParallel

    task = Task(
        get_model=lambda **kw: build_gpt2(
            "gpt2-small", seq_len=512, n_layers=2, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=512, batch_size=8, vocab_size=50257,
            n_tokens=512 * 8 * 2),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=3e-4, batch_count=2),
        name="compile-dp-2x2",
        save_dir=str(tmp_path),
    )
    bundle = DataParallel()._build_uncached(
        task, list(topo.devices), {"remat": False, "attention": "dense"})
    # PR 34: the build keeps the step's one trace and lowers nothing; the
    # 1-step program is lowered here, on first use, from that trace (the
    # kernels' ``pallas_call``s and the ``shard_map`` around them bound again)
    assert bundle.step_traces == 1 and bundle._lowered is None
    text = bundle.lowered.compile().as_text()
    assert bundle.step_traces == 1
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert sum("saturn_ce_" in l for l in calls) == 3
    # what the grid point's ``trial_config`` event carries as ``ce_plan``: the
    # one fused call, traced on a shard of 8 x 512 / 4 tokens
    (plan,) = bundle.plans["ce"]
    assert plan == ce_mod.ce_plan(1024, 768, 50257)
    assert plan.mode == "stash" and plan.dx_vmem_limit is None


@pytest.mark.parametrize("name, config", [
    ("fsdp", {"remat": True, "offload": False, "overlap": True}),
    ("tp", {"tp": 2, "remat": True, "zero": True, "overlap": True}),
    ("offload", {"stream": True, "remat": True}),
])
def test_looped_stack_outside_the_model_compiles_for_v5e_2x2(
        topo, tmp_path, name, config):
    """The techniques that rebuild the model from ``hints["pipeline"]`` give a
    looped model its outer loop themselves (``ops/pipeline.py::run_passes``:
    a scan over the passes around the layer scan, ring gathers and host
    streaming inside). None of them has met a looped model on a chip (PR 28:
    one chip, ``dp``); the chip's compiler at least takes the programs."""
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_ouro
    from saturn_tpu.models.loss import pretraining_loss
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    task = Task(
        get_model=lambda **kw: build_ouro("ouro-test-tiny", **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=64, batch_size=4, vocab_size=256, n_tokens=64 * 4 * 4),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=2),
        name=f"compile-looped-{name}",
        save_dir=str(tmp_path),
    )
    tech = BUILTIN_TECHNIQUES[name]()
    assert config in tech.candidate_configs(task, 4)
    bundle = tech._build_uncached(task, list(topo.devices), dict(config))
    assert bundle._lowered is None   # lowered on first use (PR 34)
    assert "while" in bundle.lowered.compile().as_text()
    assert bundle.step_traces == 1
