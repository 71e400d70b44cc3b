"""End-to-end CTR training on the recommender fast path
(docs/RECOMMENDER.md): a DeepFM-style model whose sparse tables live in
host RAM (`distributed_embedding`), fed from resilient recordio shards,
with checkpoint/kill/resume through the PR-4 manifest + DatasetCursor.

Run:  python examples/ctr.py                      # synchronous lookups
      python examples/ctr.py --prefetch           # async host prefetch
      python examples/ctr.py --prefetch --cache-rows 256   # + device cache
      python examples/ctr.py --checkpoint-dir /tmp/ctr_ckpt --max-steps 7
      python examples/ctr.py --checkpoint-dir /tmp/ctr_ckpt --resume

A `--max-steps`-truncated run plus `--resume` replays the byte-identical
record stream and converges to the byte-identical table state of one
uninterrupted run (pinned by tests/test_embedding_pipeline.py).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import _bootstrap

_bootstrap.ensure_devices(8)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import framework  # noqa: E402
from paddle_tpu.checkpoint import (restore_checkpoint,  # noqa: E402
                                   save_checkpoint, latest_checkpoint,
                                   host_embedding_state,
                                   load_host_embedding_state)
from paddle_tpu.core.scope import global_scope  # noqa: E402
from paddle_tpu.data_plane import DatasetCursor  # noqa: E402
from paddle_tpu.io import get_program_persistable_vars  # noqa: E402
from paddle_tpu.models import deepfm  # noqa: E402
from paddle_tpu.recordio_writer import \
    convert_reader_to_recordio_file  # noqa: E402

VOCAB = 512
FIELDS = 4


def write_shards(data_dir, n_shards=4, records_per_shard=192, seed=7):
    """Synthetic CTR shards in the fault-tolerant recordio format: each
    record is (ids [F] int64 already folded below VOCAB, label [1] f32).
    Zipf-ish id skew so the hot-row cache has something to admit."""
    os.makedirs(data_dir, exist_ok=True)
    paths = []
    for s in range(n_shards):
        path = os.path.join(data_dir, "ctr-%05d.recordio" % s)
        rng = np.random.RandomState(seed * 1000 + s)

        def reader():
            for _ in range(records_per_shard):
                hot = rng.rand(FIELDS) < 0.5
                ids = np.where(hot, rng.randint(0, 32, FIELDS),
                               rng.randint(0, VOCAB, FIELDS))
                yield (ids.astype(np.int64),
                       np.array([rng.randint(0, 2)], np.float32))

        if not os.path.exists(path):
            convert_reader_to_recordio_file(path, lambda: reader())
        paths.append(path)
    return paths


def build_model():
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        (ids, label), predict, avg_cost = deepfm.build_distributed(
            vocab_size=VOCAB, num_fields=FIELDS, embed_dim=8,
            mlp_dims=(32, 16), num_shards=2, learning_rate=0.05)
        fluid.optimizer.SGD(learning_rate=0.05).minimize(avg_cost)
    return main, startup, (ids, label), avg_cost


def checkpoint_state(main, cursor):
    """Everything a bitwise resume needs, as one manifest tree: dense
    params from the scope, every host table's shards + optimizer
    accumulators, and the stream position."""
    scope = global_scope()
    params = {v.name: np.asarray(scope.get(v.name))
              for v in get_program_persistable_vars(main)
              if scope.get(v.name) is not None}
    return {"params": params,
            "embed": host_embedding_state(),
            "cursor": cursor.to_array()}


def restore_state(main, state):
    scope = global_scope()
    for name, arr in state["params"].items():
        scope.set(name, np.asarray(arr))
    load_host_embedding_state(state["embed"])
    return DatasetCursor.from_array(state["cursor"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--data-dir", default="/tmp/ptpu_ctr_data")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--prefetch", action="store_true",
                    help="PTPU_EMBED_PREFETCH=1: stage batch t+1's rows "
                         "off the critical path")
    ap.add_argument("--cache-rows", type=int, default=0,
                    help="hot-row device cache capacity per table "
                         "(PTPU_EMBED_CACHE_ROWS)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint and continue the "
                         "byte-identical stream")
    ap.add_argument("--max-steps", type=int, default=0,
                    help="stop (and checkpoint) after N steps — the "
                         "'killed run' half of the resume contract")
    args = ap.parse_args(argv)

    if args.prefetch:
        os.environ["PTPU_EMBED_PREFETCH"] = "1"
    if args.cache_rows:
        os.environ["PTPU_EMBED_CACHE_ROWS"] = str(args.cache_rows)

    paths = write_shards(args.data_dir)
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(args.batch_size)
    ds.set_filelist(paths)

    main_prog, startup, (ids, label), avg_cost = build_model()
    ds.set_use_var([ids, label])

    exe = fluid.Executor()
    exe.run(startup)

    cursor = DatasetCursor()
    step = 0
    if args.resume:
        if not args.checkpoint_dir:
            ap.error("--resume needs --checkpoint-dir")
        path = latest_checkpoint(args.checkpoint_dir)
        if path is None:
            ap.error("no checkpoint under %s" % args.checkpoint_dir)
        state = restore_checkpoint(path)
        step = int(os.path.basename(path).split("_")[1])
        cursor = restore_state(main_prog, state)
        print("resumed step %d at %r" % (step, cursor))

    # the embed prefetch pipeline rides train_from_dataset transparently:
    # announce/gather/finalize happen inside the executor loop, and the
    # cursor mirrors into the scope at each batch's true consumption point
    if args.max_steps:
        # "killed run": manual loop so we can stop on a step boundary
        from paddle_tpu.parallel.embedding_pipeline import maybe_pipeline

        pipeline = maybe_pipeline(main_prog)
        batches = ds.resumable_batches(cursor, epochs=args.epochs,
                                       scope=global_scope())
        if pipeline is not None:
            batches = pipeline.announce_iter(batches)
        try:
            for feed in batches:
                if pipeline is not None:
                    feed = pipeline.finalize_into(feed)
                out = exe.run(main_prog, feed=feed, fetch_list=[avg_cost])
                step += 1
                if step >= args.max_steps:
                    break
        finally:
            if pipeline is not None:
                pipeline.close()
        print("stopped at step %d loss %.6f"
              % (step, float(np.asarray(out[0]).ravel()[0])))
    else:
        losses = exe.train_from_dataset(program=main_prog, dataset=ds,
                                        fetch_list=[avg_cost],
                                        cursor=cursor, epochs=args.epochs)
        # checkpoint numbering only orders publishes; the cursor inside
        # the state is what names the exact stream position
        step += 1
        if losses is not None:
            print("final loss %.6f"
                  % float(np.asarray(losses[0]).ravel()[0]))

    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir,
                        checkpoint_state(main_prog, cursor), step)
        print("checkpointed step %d to %s" % (step, args.checkpoint_dir))


if __name__ == "__main__":
    main()
