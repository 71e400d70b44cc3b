"""MultiSlot text data-feed tests (parity: framework/data_feed.cc
MultiSlotDataFeed + data_feed_test.cc — C16). Covers the C++ parser, the
pure-Python fallback agreement, malformed-line skipping (CheckFile
behavior), and train_from_dataset over a MultiSlot text file."""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core import native


def _write_file(path, lines):
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def test_parser_native_and_fallback_agree(tmp_path):
    p = str(tmp_path / "a.txt")
    # slots: label(int,1), ids(int,3), dense(float,2)
    _write_file(p, [
        "1 1 3 10 20 30 2 0.5 1.5",
        "1 0 3 11 21 31 2 -0.25 2.0",
    ])
    types = ["int64", "int64", "float"]
    recs_native, bad_n = native.parse_multislot_file(p, types)
    recs_py, bad_p = native._parse_multislot_py(
        p, [0 if t.startswith("int") else 1 for t in types])
    assert bad_n == 0 and bad_p == 0
    assert len(recs_native) == len(recs_py) == 2
    for rn, rp in zip(recs_native, recs_py):
        for a, b in zip(rn, rp):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(recs_native[0][1], [10, 20, 30])
    np.testing.assert_allclose(recs_native[1][2], [-0.25, 2.0])


def test_parser_skips_malformed_lines(tmp_path):
    p = str(tmp_path / "bad.txt")
    _write_file(p, [
        "1 1 2 5 6 1 0.5",          # ok
        "1 x 2 5 6 1 0.5",          # non-numeric id
        "1 1 5 5 6 1 0.5",          # count overruns the line
        "1 1 2 5 6 1 0.5 999",      # trailing garbage
        "1 0 2 7 8 1 1.25",         # ok
        "",                          # blank (ignored, not an error)
    ])
    types = ["int64", "int64", "float"]
    recs, bad = native.parse_multislot_file(p, types)
    assert len(recs) == 2 and bad == 3, (len(recs), bad)
    np.testing.assert_array_equal(recs[1][1], [7, 8])


def test_train_from_dataset_multislot_text(tmp_path):
    # learnable rule: label = 1 iff mean(dense) > 0
    rng = np.random.RandomState(0)
    lines = []
    for _ in range(256):
        d = rng.randn(4)
        label = int(d.mean() > 0)
        ids = rng.randint(0, 50, size=2)
        lines.append("1 %d 2 %d %d 4 %s" % (
            label, ids[0], ids[1], " ".join("%.4f" % v for v in d)))
    p = str(tmp_path / "train.txt")
    _write_file(p, lines)

    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    ids = fluid.layers.data(name="ids", shape=[2], dtype="int64")
    dense = fluid.layers.data(name="dense", shape=[4], dtype="float32")
    emb = fluid.layers.embedding(input=ids, size=[50, 8])
    h = fluid.layers.fc(input=[fluid.layers.flatten(emb, axis=1), dense],
                        size=16, act="relu")
    logit = fluid.layers.fc(input=h, size=1)
    loss = fluid.layers.mean(
        fluid.layers.sigmoid_cross_entropy_with_logits(
            x=logit, label=fluid.layers.cast(label, "float32")))
    fluid.optimizer.Adam(0.05).minimize(loss)

    desc = fluid.DataFeedDesc()
    desc.add_slot("label", "int64")
    desc.add_slot("ids", "int64")
    desc.add_slot("dense", "float")
    desc.set_batch_size(32)

    dataset = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    dataset.set_data_feed_desc(desc)
    dataset.set_filelist([p])
    dataset.set_use_var([label, ids, dense])
    dataset.load_into_memory()
    dataset.local_shuffle(seed=1)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    losses = []
    for epoch in range(6):
        last = exe.train_from_dataset(
            fluid.default_main_program(), dataset, fetch_list=[loss])
        losses.append(float(np.asarray(last[0]).mean()))
    assert losses[-1] < losses[0], losses


def test_parser_boundary_and_overflow_agreement(tmp_path):
    """Native and fallback must agree on the tricky malformed cases:
    float-prefix counts, uint64-overflow ids, and mid-token garbage."""
    p = str(tmp_path / "tricky.txt")
    _write_file(p, [
        "2.5 3.5",                       # float count token -> bad
        "1 9999999999999999999",         # id overflows int64 -> bad
        "1 42",                          # ok
        "1 4x2",                         # garbage inside token -> bad
    ])
    types = ["int64"]
    recs_n, bad_n = native.parse_multislot_file(p, types)
    recs_p, bad_p = native._parse_multislot_py(p, [0])
    assert (len(recs_n), bad_n) == (1, 3), (len(recs_n), bad_n)
    assert (len(recs_p), bad_p) == (1, 3), (len(recs_p), bad_p)
    np.testing.assert_array_equal(recs_n[0][0], [42])
    np.testing.assert_array_equal(recs_p[0][0], [42])


def test_variable_length_slots_pad_and_use_slots_filter(tmp_path):
    """Ragged id slots pad to the batch max; set_use_slots drops columns
    (reference MultiSlotDataFeed is_used semantics)."""
    p = str(tmp_path / "ragged.txt")
    _write_file(p, [
        "1 1 2 5 6 1 0.5",
        "1 0 3 5 6 7 1 1.5",
        "1 1 1 9 1 2.5",
    ])
    desc = fluid.DataFeedDesc()
    desc.add_slot("label", "int64")
    desc.add_slot("ids", "int64")
    desc.add_slot("dense", "float")
    desc.set_use_slots(["label", "ids"])  # dense parsed but not yielded

    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    ids = fluid.layers.data(name="ids", shape=[3], dtype="int64")
    dataset = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    dataset.set_data_feed_desc(desc)
    dataset.set_batch_size(3)     # desc default must NOT clobber this
    dataset.set_filelist([p])
    dataset.set_use_var([label, ids])
    dataset.load_into_memory()
    assert dataset._batch_size == 3
    feeds = list(dataset._batches())
    assert len(feeds) == 1
    np.testing.assert_array_equal(feeds[0]["label"], [[1], [0], [1]])
    np.testing.assert_array_equal(
        feeds[0]["ids"], [[5, 6, 0], [5, 6, 7], [9, 0, 0]])
    assert "dense" not in feeds[0]


def test_data_generator_roundtrips_through_native_parser(tmp_path):
    """incubate.data_generator writes MultiSlot lines the C++ feed parser
    reads back verbatim (write side <-> read side of the format)."""
    import io as _io

    from paddle_tpu.incubate.data_generator import MultiSlotDataGenerator

    class Gen(MultiSlotDataGenerator):
        def generate_sample(self, line):
            def it():
                for i in range(4):
                    yield [("ids", [i, i + 1]), ("score", [i * 0.5])]
            return it

    g = Gen()
    g.set_batch(2)
    buf = _io.StringIO()
    g.run_from_memory(out=buf)
    p = str(tmp_path / "gen.txt")
    with open(p, "w") as f:
        f.write(buf.getvalue())

    recs, bad = native.parse_multislot_file(p, ["int64", "float"])
    assert bad == 0 and len(recs) == 4
    np.testing.assert_array_equal(recs[2][0], [2, 3])
    np.testing.assert_allclose(recs[3][1], [1.5])

    # stdin driver: one sample per input line
    class LineGen(MultiSlotDataGenerator):
        def generate_sample(self, line):
            def it():
                if line is not None:
                    yield [("ids", [int(line.strip())])]
            return it

    g2 = LineGen()
    out2 = _io.StringIO()
    g2.run_from_stdin(inp=_io.StringIO("5\n9\n"), out=out2)
    assert out2.getvalue() == "1 5\n1 9\n"

    # inconsistent slot names across samples must raise
    class BadGen(MultiSlotDataGenerator):
        def generate_sample(self, line):
            def it():
                yield [("a", [1])]
                yield [("b", [2])]
            return it

    import pytest as _pytest

    g3 = BadGen()
    with _pytest.raises(ValueError, match="not match"):
        g3.run_from_memory(out=_io.StringIO())


def _make_shards(tmp_path, n_files=8, lines=200000):
    paths = []
    for k in range(n_files):
        p = str(tmp_path / ("part-%d.txt" % k))
        with open(p, "w") as f:
            for i in range(lines):
                v = (k * lines + i) % 97
                f.write("3 %d %d %d 1 %d\n" % (v, v + 1, v + 2, v % 2))
        paths.append(p)
    return paths


def test_threaded_dataset_matches_serial(tmp_path):
    """C15 Hogwild parity: set_thread(N) parses shards on N reader
    threads. With FLAGS_cpu_deterministic (default) sample order — hence
    every training loss — is identical to the serial read. (What the
    threads buy in wall time is not asserted here: a wall-clock
    comparison on the test box measures its scheduler.)"""
    import paddle_tpu as fluid

    paths = _make_shards(tmp_path)

    def batches(threads):
        desc = fluid.DataFeedDesc()
        desc.add_slot("ids", "uint64")
        desc.add_slot("label", "float")
        ds = fluid.DatasetFactory().create_dataset("QueueDataset")
        ds.set_data_feed_desc(desc)
        ds.set_batch_size(8192)
        ds.set_filelist(paths)
        ds.set_thread(threads)
        ds.set_use_var([type("V", (), {"name": "ids"})(),
                        type("V", (), {"name": "label"})()])
        return [int(b["ids"].sum()) for b in ds._batches()]

    serial, threaded = batches(1), batches(4)
    assert len(serial) == len(threaded) > 1
    assert serial == threaded  # deterministic: same batches, same order


def test_threaded_nondeterministic_covers_all_samples(tmp_path):
    import paddle_tpu as fluid
    from paddle_tpu.flags import set_flags

    paths = _make_shards(tmp_path, n_files=4, lines=500)
    desc = fluid.DataFeedDesc()
    desc.add_slot("ids", "uint64")
    desc.add_slot("label", "float")
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_data_feed_desc(desc)
    ds.set_batch_size(100)
    ds.set_filelist(paths)
    ds.set_thread(4)
    ds.set_use_var([type("V", (), {"name": "ids"})(),
                    type("V", (), {"name": "label"})()])
    set_flags({"FLAGS_cpu_deterministic": False})
    try:
        total = sum(b["ids"].shape[0] for b in ds._batches())
    finally:
        set_flags({"FLAGS_cpu_deterministic": True})
    assert total == 4 * 500


def test_train_from_dataset_threaded_matches_serial_losses(tmp_path):
    """train_from_dataset(thread=4): prefetched threaded batches give the
    EXACT serial loss trajectory under FLAGS_cpu_deterministic (C15
    Hogwild capability, determinism contract)."""
    import paddle_tpu as fluid

    paths = _make_shards(tmp_path, n_files=4, lines=2000)

    def run_once(threads):
        from paddle_tpu import layer_helper

        from paddle_tpu import initializer as _init

        layer_helper._op_seed_counter[0] = 1000  # identical init seeds
        _init._global_seed_counter[0] = 0
        fluid.framework.switch_main_program(fluid.Program())
        fluid.framework.switch_startup_program(fluid.Program())
        fluid.default_main_program().random_seed = 11
        fluid.default_startup_program().random_seed = 11
        desc = fluid.DataFeedDesc()
        desc.add_slot("ids", "uint64")
        desc.add_slot("label", "float")
        ds = fluid.DatasetFactory().create_dataset("QueueDataset")
        ds.set_data_feed_desc(desc)
        ds.set_batch_size(512)
        ds.set_filelist(paths)
        ids = fluid.layers.data(name="ids", shape=[3], dtype="int64",
                                append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="float32")
        ds.set_use_var([ids, label])
        emb = fluid.layers.embedding(input=ids, size=[100, 4])
        pred = fluid.layers.fc(
            input=fluid.layers.reshape(emb, [-1, 12]), size=1,
            act="sigmoid")
        loss = fluid.layers.mean(
            fluid.layers.log_loss(pred, label, epsilon=1e-6))
        fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(fluid.default_startup_program())
            out = exe.train_from_dataset(
                program=fluid.default_main_program(), dataset=ds,
                thread=threads, fetch_list=[loss])
        return float(np.asarray(out[0]).ravel()[0])

    serial = run_once(1)
    threaded = run_once(4)
    assert serial == threaded, (serial, threaded)
