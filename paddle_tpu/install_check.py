"""Install self-check (parity: python/paddle/fluid/install_check.py —
run_check() trains a tiny linear model single-device and, when more than
one device is visible, data-parallel, then prints the all-clear)."""

import numpy as np

__all__ = ["run_check"]


def run_check():
    """Build + train a 2-layer model one step on one device, and across
    all visible devices when there are several. Raises on failure; prints
    a success message like the reference."""
    import jax

    from . import (Executor, ParallelExecutor, Program, layers, optimizer,
                   program_guard)
    from .framework import switch_main_program, switch_startup_program

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data(name="inst_chk_x", shape=[4], dtype="float32")
        y = layers.data(name="inst_chk_y", shape=[1], dtype="float32")
        pred = layers.fc(input=x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        optimizer.SGD(learning_rate=0.01).minimize(loss)

    exe = Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {"inst_chk_x": rng.rand(8, 4).astype(np.float32),
            "inst_chk_y": rng.rand(8, 1).astype(np.float32)}
    out, = exe.run(main, feed=feed, fetch_list=[loss])
    assert np.isfinite(np.asarray(out)).all(), "single-device check failed"

    n_dev = len(jax.devices())
    if n_dev > 1:
        pe = ParallelExecutor(loss_name=loss.name, main_program=main)
        out, = pe.run(feed=feed, fetch_list=[loss.name])
        assert np.isfinite(np.asarray(out)).all(), "multi-device check failed"
        print("Your paddle_tpu works well on MULTIPLE devices (%d)!" % n_dev)
    else:
        print("Your paddle_tpu works well on SINGLE device.")
    print("Your paddle_tpu is installed successfully! Let's start deep "
          "Learning with paddle_tpu now")
