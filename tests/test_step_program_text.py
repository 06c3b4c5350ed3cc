"""The step programs of the configurations that share ``models/gpt2.py``,
``ops/moe.py`` and ``ops/flash.py`` with the Ling stack trace to the text they
had before it (PR 45): the jaxpr of the gradient through a tiny preset with
the TPU lowering's ``pallas_call``s traced (not lowered), kernels on, with and
without remat, hashed on the commit before (9beeaa8; memory addresses
blanked): every layer's ops, the routed layers' sort and buffer, kernel
bodies, grids and names, character for character. To take them again:
``_step_text`` below, on that tree."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from saturn_tpu.models import gpt2
from saturn_tpu.ops import ce, flash, gdn, moe, ssd

_TEXT_BEFORE_LING = {
    ("olmo-hybrid-test-tiny", False): "5ff2beb17fb65d44",
    ("olmo-hybrid-test-tiny", True): "a23daf1e653c226c",
    ("laguna-test-tiny", False): "f80f51dcc02259b5",
    ("laguna-test-tiny", True): "d6916b18f19e0920",
    ("nemotron-test-tiny", False): "54a4610195bca7dc",
    ("nemotron-test-tiny", True): "144649470ea8c940",
    ("gptj-test-tiny", False): "7f583ada3586f5d7",
    ("gptj-test-tiny", True): "5ed40e5058a5f3dd",
}
#: the Ling stack's own tiny preset, taken on the commit before
#: ``ops/moe.py::routed_experts`` became two halves (PR 49; 2029792): the third
#: caller of the one-call form, whose text must not move either
_TEXT_BEFORE_THE_ROUTE_WAS_SPLIT = {
    ("ling-test-tiny", False): "f8253b6a1828715b",
    ("ling-test-tiny", True): "93a93dbfaf52f2f5",
}
#: SmallThinker's tiny preset, taken on the commit before the grouped product
#: got its plan and ``_softmax_mixer`` its norm a head (PR 52; a0578f3)
_TEXT_BEFORE_THE_GMM_PLAN = {
    ("smallthinker-test-tiny", False): "b42945fff53f7507",
    ("smallthinker-test-tiny", True): "c43749e06216ee3a",
}
_PINNED = {**_TEXT_BEFORE_LING, **_TEXT_BEFORE_THE_ROUTE_WAS_SPLIT,
           **_TEXT_BEFORE_THE_GMM_PLAN}


def _step_text(preset, remat):
    spec = gpt2.build_gpt2(preset, attention="flash", remat=remat)
    params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, spec.config.seq_len), jnp.int32)

    def loss(p, t):
        out = spec.apply_fn(p, t)
        return jnp.sum((out[0] if isinstance(out, tuple) else out).astype(jnp.float32))

    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(jax.grad(loss))(params, tokens)))


@pytest.mark.parametrize("preset,remat", list(_PINNED))
def test_a_step_program_traces_to_the_text_it_had_before_the_ling_stack(
        preset, remat, monkeypatch):
    for mod in (ce, flash, gdn, ssd):
        monkeypatch.setattr(mod, "_use_interpret", lambda: False)
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    text = _step_text(preset, remat)
    assert ("saturn_mla_" in text) == (preset == "ling-test-tiny")
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _PINNED[preset, remat]
