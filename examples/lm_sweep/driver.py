"""End-to-end HPO sweep driver — the canonical saturn_tpu usage.

Parity target: ``examples/wikitext103/WikiText103.py:35-106`` in the
reference. Same shape of flow:

1. register parallelism techniques into the library,
2. build a Task sweep varying batch size,
3. ``search`` — profile every (task × sub-mesh size × technique),
4. clone searched tasks across learning rates WITHOUT re-profiling
   (``WikiText103.py:87-99``: lr doesn't change step time),
5. ``orchestrate`` — solve the SPASE MILP and gang-execute to completion.

Runs on whatever ``jax.devices()`` offers: the real TPU chip, or an 8-device
virtual CPU mesh with ``--platform cpu`` (the multi-node-without-a-cluster
test mode, SURVEY.md §4).

Examples:
    python driver.py --preset test-tiny --platform cpu --batch-count 8
    python driver.py --preset gpt2-small --lrs 1e-4 3e-4 --batch-sizes 8 16
"""

from __future__ import annotations

import argparse
import os
import time

_BUNDLED_CORPUS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "data", "corpus.txt"
)


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", default="test-tiny",
                   help="model preset (test-tiny, gpt2-small, gptj-test-tiny, ...)")
    p.add_argument("--context-length", type=int, default=None,
                   help="sequence length (default: preset's)")
    p.add_argument("--batch-sizes", type=int, nargs="+", default=[8],
                   help="one task per batch size (reference varied 16/8)")
    p.add_argument("--lrs", type=float, nargs="+", default=[1e-3, 1e-4],
                   help="lr variants cloned from each searched task")
    p.add_argument("--batch-count", type=int, default=16,
                   help="batches per task (reference verification used 100)")
    p.add_argument("--interval", type=float, default=60.0,
                   help="scheduling interval seconds (reference default 1000)")
    p.add_argument("--techniques", nargs="+", default=None,
                   help="library names to profile (default: all registered)")
    p.add_argument("--chip-range", type=int, nargs="+", default=None,
                   help="sub-mesh sizes to profile (default: all powers of two)")
    p.add_argument("--corpus", default=_BUNDLED_CORPUS,
                   help="local text file to tokenize; 'synthetic' for the "
                        "deterministic Zipf stream (default: the bundled "
                        "examples/data/corpus.txt)")
    p.add_argument("--tokenizer", choices=["word", "byte"], default="word",
                   help="corpus tokenizer (native word vocab, or raw bytes)")
    p.add_argument("--save-dir", default="saturn_sweep_ckpts")
    p.add_argument("--platform", choices=["default", "cpu"], default="default",
                   help="cpu = 8 virtual XLA host devices (no TPU needed)")
    return p.parse_args()


def main():
    args = parse_args()
    if args.platform == "cpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")

    import saturn_tpu
    from saturn_tpu import HParams, Task, library
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.gpt2 import build_gpt2, config_for
    from saturn_tpu.models.loss import pretraining_loss

    # 1) register techniques (reference ``WikiText103.py:49-54`` registered
    #    its UDP classes; the built-in default library covers dp/fsdp/tp/
    #    pipeline/spilled/ring).
    names = library.register_default_library()
    print(f"registered techniques: {names}")

    ctx = args.context_length or config_for(args.preset).seq_len
    vocab = config_for(args.preset).vocab_size
    corpus = None if args.corpus in ("synthetic", "none") else args.corpus
    if corpus and not os.path.exists(corpus):
        raise SystemExit(f"corpus file not found: {corpus}")
    print(f"corpus: {corpus or 'synthetic'} (tokenizer={args.tokenizer})")

    # 2) one task per batch size (reference ``WikiText103.py:62-71``).
    base_tasks = []
    for bs in args.batch_sizes:
        task = Task(
            get_model=lambda **kw: build_gpt2(args.preset, seq_len=ctx, **kw),
            get_dataloader=lambda bs=bs: make_lm_dataset(
                context_length=ctx, batch_size=bs, vocab_size=vocab,
                n_tokens=ctx * bs * max(args.batch_count, 16),
                corpus_path=corpus, tokenizer=args.tokenizer,
            ),
            loss_fn=pretraining_loss,
            hparams=HParams(lr=args.lrs[0], batch_count=args.batch_count),
            chip_range=args.chip_range,
            name=f"{args.preset}-bs{bs}-lr{args.lrs[0]:g}",
            save_dir=args.save_dir,
        )
        base_tasks.append(task)

    # 3) profile (reference ``WikiText103.py:75``).
    t0 = time.time()
    saturn_tpu.search(base_tasks, technique_names=args.techniques, log=True)
    print(f"search took {time.time() - t0:.1f}s")

    # 4) lr variants reuse the profile (reference ``WikiText103.py:87-99``).
    tasks = list(base_tasks)
    for task in base_tasks:
        for lr in args.lrs[1:]:
            tasks.append(task.clone(name=task.name.rsplit("-lr", 1)[0] + f"-lr{lr:g}", lr=lr))

    for t in tasks:
        feas = {g: f"{s.runtime:.1f}s/{type(s.executor).name}"
                for g, s in t.feasible_strategies().items()}
        print(f"  {t.name}: {feas}")

    # 5) solve + execute (reference ``WikiText103.py:102``).
    t0 = time.time()
    saturn_tpu.orchestrate(tasks, log=True, interval=args.interval)
    print(f"orchestration took {time.time() - t0:.1f}s for {len(tasks)} tasks")

    from saturn_tpu.utils import checkpoint as ckpt_mod

    for t in tasks:
        step = int(ckpt_mod.load_arrays(t.ckpt_path)["step"])
        print(f"  {t.name}: trained steps={step} remaining={t.total_batches}")


if __name__ == "__main__":
    main()
