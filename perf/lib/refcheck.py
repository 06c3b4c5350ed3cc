"""The comparison that decides the reference part of ``correct``.

For one job of the cell, at the configuration's published widths and outside
the timed window: the program search chose (its technique, its grid point,
with the kernels where it chose them) against the configuration's plain
reference (``run.reference``, ``perf/reference/gpt.py``) from the same seeded
weights and the same batches, on a sample of fewer sequences than the job's
batch (the float32 reference with its unrematerialised stash cannot hold the
job's batch beside 16 B/param of state on a 16 GB chip).

  (a) logits of the first sample batch, through the model's own forward at
      the chosen grid point;
  (b) ``steps`` consecutive AdamW steps through the chosen technique's own
      ``execute`` -- the call the engine makes -- the state it leaves and
      the checkpoint it writes of it: the forward pass, the fused head and
      loss, the backward pass (flash dq/dkv, CE dx/dw), the optimizer and
      the save.

Numbers compared (each printed beside its limit in every run):
  logits_rel_rms  ||sys - ref|| / ||ref|| over all logits of the sample: the
                  forward pass; separates precisions, steady from seed to seed
  grad_rel_rms    the backward pass: the state's first Adam moment (a
                  fixed linear combination of the ``steps`` gradients,
                  0.1 x sum 0.9^(steps-t) g_t) against the reference's, leaf
                  by leaf, ||sys - ref|| / ||ref||, the largest over the
                  leaves; separates precisions as the logits do
  update_rel_rms  the optimizer: the state's weights against the
                  reference's, ||sys - ref|| / ||ref - seeded|| over all
                  leaves together (the error of the weights over how far
                  training moved them)
  loss_max_rel    max_t |sys_t - ref_t| / |ref_t| over the training steps:
                  catches a wrong (not a less precise) backward or optimizer
  ckpt_leaves_differ  the checkpoint ``execute`` acknowledged, read back
                  (``read_back``): its leaves -- all of a one-chip cell's, 8
                  of the 21.8 GB of the four-chip cell's, drawn from the
                  seed -- restored as a resuming job restores them and
                  compared bit for bit with the state the numbers above are
                  taken from; an exact comparison, limit 0 (the control
                  writes no checkpoint and has no such number)
  loss_drop_rel   |(sys_0 - sys_last) - (ref_0 - ref_last)| / |ref_0 - ref_last|
                  (printed, not limited: it separates nothing)

The limits are in ``perf/reference/limits.json``; PERF.md section 4 has the
readings they were set from. ``lowp_mm`` builds the control: the reference's
own matmuls, forward and backward, in the next precision below the
configuration's bf16 (fp8, per-tensor scaled, float32 accumulation), which
has to come out as not correct.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, Optional, Sequence

import numpy as np

LIMITS_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "reference", "limits.json")
PRINTED = ("logits_rel_rms", "grad_rel_rms", "update_rel_rms", "loss_max_rel",
           "ckpt_leaves_differ", "loss_drop_rel")


def load_limits(path: str = LIMITS_FILE) -> Dict[str, float]:
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def lowp_mm(kind: str = "fp8") -> Callable:
    """``mm(x, w)`` with the operands of the forward product and of both
    backward products (dx = g w^T, dw = x^T g) rounded to a lower precision
    first; accumulation stays float32.
    fp8: the usual recipe -- e4m3 for activations and weights, e5m2 for the
    cotangent, each with a per-tensor scale to the format's largest finite
    value (an unscaled cotangent would flush to zero, a fault no real fp8 path
    has). bf16: plain rounding (the CPU test's stand-in for what the program
    computes in; on the TPU XLA removes an f32 -> bf16 -> f32 round trip, so
    there it reads 0)."""
    import jax
    import jax.numpy as jnp

    if kind not in ("fp8", "bf16"):
        raise ValueError(f"unknown control precision {kind!r}")

    def rounded(t, dtype, top):
        if kind == "bf16":
            return t.astype(jnp.bfloat16).astype(jnp.float32)
        scale = top / jnp.maximum(jnp.max(jnp.abs(t)), 1e-30)
        return (t * scale).astype(dtype).astype(jnp.float32) / scale

    def forward(x, w):
        xq = rounded(x, jnp.float8_e4m3fn, 448.0)
        wq = rounded(w, jnp.float8_e4m3fn, 448.0)
        return xq @ wq, (xq, wq)

    def backward(kept, g):
        xq, wq = kept
        gq = rounded(g, jnp.float8_e5m2, 57344.0)
        k, n = wq.shape
        return gq @ wq.T, xq.reshape(-1, k).T @ gq.reshape(-1, n)

    mm = jax.custom_vjp(lambda x, w: forward(x, w)[0])
    mm.defvjp(forward, backward)
    return mm


def logits_error(ref_logits: Any, sys_logits: Any) -> float:
    import jax.numpy as jnp

    ref = jnp.asarray(ref_logits, jnp.float32)
    diff = jnp.asarray(sys_logits, jnp.float32) - ref
    return float(jnp.sqrt(jnp.sum(diff * diff) / jnp.sum(ref * ref)))


def loss_errors(ref_losses: Sequence[float], sys_losses: Sequence[float]) -> Dict[str, float]:
    ref_l = np.asarray(ref_losses, dtype=np.float64)
    sys_l = np.asarray(sys_losses, dtype=np.float64)
    if ref_l.shape != sys_l.shape:
        return {"loss_drop_rel": float("inf"), "loss_max_rel": float("inf")}
    ref_drop = ref_l[0] - ref_l[-1]
    return {
        "loss_drop_rel": float(abs((sys_l[0] - sys_l[-1]) - ref_drop) / abs(ref_drop)),
        "loss_max_rel": float(np.max(np.abs(sys_l - ref_l) / np.abs(ref_l))),
    }


def checkpoint_state(arrays: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """First Adam moments and weights by leaf path, out of a train state of
    the package under a checkpoint's keys (``live_state``, or
    ``checkpoint.load_arrays``)."""
    return {"m": {k.split("/mu/", 1)[1]: v for k, v in arrays.items() if "/mu/" in k},
            "params": {k[len("params/"):]: v for k, v in arrays.items()
                       if k.startswith("params/")}}


def state_errors(ref_state: Dict[str, Any], sys_state: Dict[str, Any],
                 say: Optional[Callable[[str], None]] = None,
                 leaves: Optional[Dict[str, Any]] = None) -> Dict[str, float]:
    """``grad_rel_rms`` and ``update_rel_rms`` of one side's final state
    against the reference's; host arrays by leaf path. The gradients come
    from different kernels leaf by leaf, so theirs is the largest over the
    leaves; the optimizer is one elementwise rule, and a leaf whose true
    gradient is zero (GPT-2's key bias) moves by rounding noise alone under
    Adam, so its number is taken over all leaves together. ``leaves``, if
    given, is filled with each leaf's own numbers."""

    def norm(x, y=None, chunk=1 << 24):
        # ||x|| or, with y, ||x - y||: float32 products summed by BLAS in
        # chunks of 16 M elements, the chunks' sums in float64, and the
        # difference taken chunk by chunk (a leaf of a billion elements in a
        # second; ``np.sum(np.square(x - y), dtype=float64)`` takes ten, most
        # of them for the temporary)
        x = np.asarray(x, dtype=np.float32).ravel()
        y = None if y is None else np.asarray(y, dtype=np.float32).ravel()
        total = 0.0
        for i in range(0, x.size, chunk):
            d = x[i:i + chunk] if y is None else x[i:i + chunk] - y[i:i + chunk]
            total += float(np.dot(d, d))
        return float(np.sqrt(total))

    bad = {"grad_rel_rms": float("inf"), "update_rel_rms": float("inf")}
    if set(sys_state["m"]) != set(ref_state["m"]) or \
            set(sys_state["params"]) != set(ref_state["params"]):
        return bad
    worst, worst_leaf, off, moved = 0.0, "", 0.0, 0.0
    for leaf, ref_m in ref_state["m"].items():
        sys_m, sys_p = sys_state["m"][leaf], sys_state["params"][leaf]
        if sys_m.shape != ref_m.shape or sys_p.shape != ref_m.shape:
            return bad
        grad = norm(sys_m, ref_m) / max(norm(ref_m), 1e-30)
        leaf_off, leaf_moved = norm(sys_p, ref_state["params"][leaf]), ref_state["moved"][leaf]
        off, moved = off + leaf_off ** 2, moved + leaf_moved ** 2
        if say:
            say(f"  leaf {leaf}: grad_rel_rms {grad:.6g}, update_rel_rms "
                f"{leaf_off / max(leaf_moved, 1e-30):.6g}")
        if leaves is not None:
            leaves[leaf] = {"grad_rel_rms": grad, "off": leaf_off, "moved": leaf_moved}
        if not grad <= worst:  # a NaN is the worst
            worst, worst_leaf = grad, leaf
    if say:
        say(f"  grad_rel_rms is that of {worst_leaf}")
    return {"grad_rel_rms": worst,
            "update_rel_rms": float(np.sqrt(off) / max(np.sqrt(moved), 1e-30))}


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            say: Callable[[str], None], who: str,
            compared: Optional[Dict[str, Dict[str, Any]]] = None) -> bool:
    """Every number beside its limit; true if each limited one is inside.
    ``compared``, if given, gains ``<who>.<name>: {value, limit, ok}`` for
    every limited number (the result line carries them)."""
    ok = True
    for name in PRINTED:
        if name not in numbers:
            continue
        value = numbers[name]
        if name not in limits:
            say(f"reference check {who}: {name} = {value:.6g} (not limited)")
            continue
        good = bool(np.isfinite(value)) and value <= limits[name]
        ok = ok and good
        if compared is not None:
            compared[f"{who}.{name}"] = {"value": float(value),
                                         "limit": limits[name], "ok": good}
        say(f"reference check {who}: {name} = {value:.6g} (limit {limits[name]:.6g}) "
            f"{'ok' if good else 'NOT OK'}")
    return ok


def sample_batches(vocab: int, seq: int, sequences: int, steps: int, seed: int):
    """``steps`` batches of ``sequences`` sequences, from the seed, by the
    package's own synthetic-token generator (the data the jobs train on)."""
    from saturn_tpu.data.lm_dataset import make_lm_dataset

    ds = make_lm_dataset(context_length=seq, batch_size=sequences,
                         vocab_size=vocab, n_tokens=seq * sequences * steps,
                         seed=seed)
    return ds, [np.asarray(ds.batch(i)) for i in range(steps)]


def reference_side(ref, arch, seed: int, batches, lr: float,
                   mm: Optional[Callable] = None,
                   devices: Optional[Sequence[Any]] = None):
    """(losses, logits of the first batch, final state) of the plain
    reference ``ref`` (the configuration's ``run.reference`` module) or, with
    ``mm``, of the control. On more than one chip the reference is handed the
    ``devices`` and shards its state over them (a state of 16 B/param that
    overfills one chip); a one-chip cell's reference is called as before."""
    over = {"devices": list(devices)} if devices is not None and len(devices) > 1 else {}
    logits = ref.logits_of(arch, seed, batches[0], mm, **over)
    losses, state = ref.train(arch, seed, batches, lr, mm, keep_state=True, **over)
    return losses, logits, state


def system_logits(task, config: Dict[str, Any], tokens):
    """The model's own forward at the chosen grid point, on the task's own
    (seeded) initial weights."""
    import jax
    import jax.numpy as jnp

    overrides = {k: config[k] for k in ("remat", "attention") if k in config}
    spec = task.get_model(**overrides)
    fn = jax.jit(lambda t: spec.apply_fn(spec.init_fn(jax.random.PRNGKey(0)), t))
    return fn(jnp.asarray(tokens))


def live_state(task):
    """The state the technique's ``execute`` left on the devices, as host
    arrays under the keys a checkpoint of it has (``treepath.path_str``, the
    checkpoint's own naming), and each leaf's sharding beside it."""
    import jax
    from saturn_tpu.utils.treepath import path_str

    _, state = task._live_state
    arrays, shardings = {}, {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        arrays[path_str(path)] = np.asarray(leaf)
        shardings[path_str(path)] = leaf.sharding
    return arrays, shardings


#: bytes of a checkpoint that are restored and compared with what was on
#: the devices: the whole of a one-chip cell's (7.31 GB the largest), and of
#: the four-chip cell's 21.8 GB a sample drawn from the seed (restoring and
#: comparing gives 0.31 GB/s, and a run has to end within 360 s)
READ_BACK_BYTES = 8_000_000_000


def read_back(ckpt_path: str, arrays: Dict[str, np.ndarray], shardings: Dict[str, Any],
              seed: int, budget: int = READ_BACK_BYTES) -> Dict[str, float]:
    """The checkpoint ``execute`` acknowledged, read back and held to what
    was on the devices when it was taken (``arrays``, which the caller holds
    to the reference): leaves are restored the way a resuming job restores
    them -- ``restore_sharded`` under the sharding the leaf had, so each
    chip's block comes from the shard that holds it, and the reader checks
    each member's CRC as it reads -- in an order drawn from ``seed`` until
    ``budget`` bytes are read, and each is compared bit for bit. Returns
    ``ckpt_leaves_differ``: the leaves read that differ or cannot be read;
    its limit is 0. (Every byte of the *window's* checkpoint goes through
    ``checkpoint.verify`` in ``harness.check_window``.)"""
    import jax
    from saturn_tpu.utils import checkpoint

    t0, differ = time.perf_counter(), 0
    order = [sorted(arrays)[i] for i in
             np.random.default_rng(seed).permutation(len(arrays))]
    read, n = 0, 0
    for key in order:
        want = arrays[key]
        if read + want.nbytes > budget:
            continue
        try:
            got = checkpoint.restore_sharded(
                ckpt_path, {key: jax.ShapeDtypeStruct(want.shape, want.dtype)},
                shardings[key])[key]
            same = np.array_equal(np.asarray(got), want)
            del got
        except (KeyError, ValueError, OSError, checkpoint.CheckpointCorruptError) as e:
            # (the package moves a checkpoint it cannot read out of the way:
            # every later leaf then finds no file, and counts)
            print(f"perf: checkpoint leaf {key}: {e!r}"[:400], flush=True)
            same = False
        if not same:
            print(f"perf: checkpoint leaf {key} is not what the devices held",
                  flush=True)
            differ += 1
        read, n = read + want.nbytes, n + 1
    total = sum(a.nbytes for a in arrays.values())
    print(f"perf: checkpoint read back: {n} of {len(arrays)} leaves ({read / 1e9:.2f} "
          f"of {total / 1e9:.2f} GB) restored and compared bit for bit in "
          f"{time.perf_counter() - t0:.1f}s; {differ} differ", flush=True)
    return {"ckpt_leaves_differ": float(differ)}


def system_side(task, tech, config: Dict[str, Any], devices, steps: int,
                events_path: str, seed: int, release: bool = True):
    """``steps`` steps through the technique's own ``execute``: (the losses it
    recorded, the state it left, ``read_back``'s number). The state is copied
    off the chips while the checkpoint's writer still runs; the checkpoint
    is then held to that copy, whole or by a sample drawn from ``seed``
    (``read_back``). ``release`` drops the task's compiled programs
    afterwards (a run has no further use of them)."""
    from saturn_tpu.core.strategy import Strategy
    from saturn_tpu.utils import checkpoint, metrics

    n = len(devices)
    t_start = time.perf_counter()
    task.strategies[n] = Strategy(tech, n, dict(config), runtime=0.0)
    task.select_strategy(n)
    with metrics.scoped(events_path):
        tech.execute(task, list(devices), tid=0, override_batch_count=steps)
    t_ran = time.perf_counter()
    arrays, shardings = live_state(task)
    checkpoint.flush()
    events = [e for e in metrics.read_events(events_path, kind="task_interval")
              if e["task"] == task.name]
    task.release_live_state()
    if release:
        task.release_compiled()
    t_flushed = time.perf_counter()
    numbers = read_back(task.ckpt_path, arrays, shardings, seed)
    print(f"perf: program side: steps {t_ran - t_start:.1f}s, the state off the "
          f"chips and the checkpoint's flush {t_flushed - t_ran:.1f}s, the "
          f"checkpoint read back {time.perf_counter() - t_flushed:.1f}s", flush=True)
    return ([float(x) for x in events[-1]["losses"]], checkpoint_state(arrays),
            numbers)
