"""PartitionSpec rule engines: parallelism as sharding annotations.

The reference implemented each parallelism as a wrapper class (torch FSDP
``FSDP.py:111-118``, GPipe ``Pipeline.py:36-39``, OffloadModel
``Spilled.py:46``). The GSPMD-native equivalent (SURVEY.md §2.2) is a function
from *param tree path + shape* to a ``PartitionSpec`` — XLA inserts the
all-gathers / reduce-scatters / all-reduces that NCCL wrappers did manually.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from saturn_tpu.utils.treepath import path_str as _path_str


def replicated_rules(path: str, shape: Tuple[int, ...], mesh_axes) -> P:
    """DP: params replicated on every device; only the batch is sharded."""
    return P()


def fsdp_rules(axis: str = "data", min_size: int = 1024):
    """ZeRO-3-style rules: shard each param's largest dimension over ``axis``.

    Equivalent to torch-FSDP flat-param sharding (``FSDP.py:111-118``) but
    declarative: XLA emits the all-gather before use and reduce-scatter on
    grads. Small params (< min_size elements) stay replicated — sharding them
    costs more in collective latency than it saves in HBM.
    """

    def rules(path: str, shape: Tuple[int, ...], mesh_axes) -> P:
        n_shard = mesh_axes[axis]
        if int(np.prod(shape)) < min_size or not shape:
            return P()
        # Largest dim divisible by the axis size; prefer later dims on ties
        # (later dims of a scanned stack are the weight matrix dims).
        best, best_size = None, -1
        for i, s in enumerate(shape):
            if s % n_shard == 0 and s >= best_size:
                best, best_size = i, s
        if best is None:
            return P()
        spec = [None] * len(shape)
        spec[best] = axis
        return P(*spec)

    return rules


def tensor_parallel_rules(axis: str = "model"):
    """Megatron-style rules for the GPT-2 param tree (``models/gpt2.py``).

    Column-parallel: qkv and mlp_in kernels (shard output dim) — their
    activation outputs are sharded over heads/ff; row-parallel: attn_out and
    mlp_out kernels (shard input dim) — XLA inserts the psum on their output.
    Embeddings shard over vocab; XLA handles the gather + logits psum.
    Fills the reference's declared-but-unimplemented MEGATRON slot
    (``Strategy.py:34``).
    """

    # (``conv_b|conv_c|conv_x``: the three blocks of a short-convolution
    # mixer's input projection, a kernel each, so that a channel's two gates
    # and its convolved value land on one shard: ``Block._short_conv_mixer``)
    col = re.compile(r"(qkv|mlp_in|mlp_gate|conv_b|conv_c|conv_x)/kernel$")
    row = re.compile(r"(attn_out|mlp_out)/kernel$")
    colb = re.compile(r"(qkv|mlp_in|mlp_gate)/bias$")
    # Paths are full state paths ('params/wte', 'opt_state/0/mu/wte', ...),
    # so anchor on a path segment, not the whole string.
    vocab = re.compile(r"(^|/)wte$")

    def rules(path: str, shape: Tuple[int, ...], mesh_axes) -> P:
        n_shard = mesh_axes[axis]
        spec = [None] * len(shape)
        if col.search(path) and shape[-1] % n_shard == 0:
            spec[-1] = axis
        elif row.search(path) and shape[-2] % n_shard == 0:
            spec[-2] = axis
        elif colb.search(path) and shape[-1] % n_shard == 0:
            spec[-1] = axis
        elif vocab.search(path) and shape[0] % n_shard == 0:
            spec[0] = axis
        return P(*spec)

    return rules


def compose_rules(*rule_fns):
    """Merge rule functions; later rules fill axes earlier ones left None.

    Lets FSDP compose with TP (2-D mesh: params sharded over both 'model'
    and 'data') without either rule knowing about the other.
    """

    def rules(path: str, shape: Tuple[int, ...], mesh_axes) -> P:
        spec = [None] * len(shape)
        used_axes = set()
        for fn in rule_fns:
            sub = fn(path, shape, mesh_axes)
            for i, a in enumerate(tuple(sub)):
                if a is not None and spec[i] is None and a not in used_axes:
                    spec[i] = a
                    used_axes.add(a)
        return P(*spec)

    return rules


def pspec_tree(params_shapes: Any, rules: Callable, mesh) -> Any:
    """Apply a rule function over an abstract params tree -> PartitionSpec tree.

    Every emitted spec passes the static sharding lint
    (``analysis.jax_lint.enforce_pspec``): an unknown mesh axis or a spec
    longer than the tensor's rank raises ``ShardingLintError`` with the rule
    function's ``file:line`` here, on CPU — not as a GSPMD compile failure
    on the chips."""
    from saturn_tpu.analysis import jax_lint as _jlint

    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(path, leaf):
        spec = rules(_path_str(path), tuple(leaf.shape), mesh_axes)
        _jlint.enforce_pspec(spec, tuple(leaf.shape), mesh_axes,
                             path=_path_str(path), rules=rules)
        return spec

    return jax.tree_util.tree_map_with_path(one, params_shapes)


def sharding_tree(params_shapes: Any, rules: Callable, mesh, memory_kind=None) -> Any:
    """PartitionSpec tree -> NamedSharding tree over ``mesh``."""
    from jax.sharding import NamedSharding

    specs = pspec_tree(params_shapes, rules, mesh)

    def mk(spec):
        if memory_kind is not None:
            return NamedSharding(mesh, spec, memory_kind=memory_kind)
        return NamedSharding(mesh, spec)

    return jax.tree.map(mk, specs, is_leaf=lambda x: isinstance(x, P))
