"""A control of a cell, run like a run of the cell.

    python3 perfbench/control.py --workload <cell> --seed <n> \
        --seconds <s> [--control <name>]

The same harness, runner, traffic, reference and limits as
``perfbench/run.py``, with one of the configuration file's ``controls``
switched on (the first, unless named). Kinds:

``program_train``  training: the program alone is built with these
                   ``train`` keys. ``param_dtype`` bfloat16 is the
                   program's own lower-precision path (parameters and
                   residual stream stored in that type, no fp32 master
                   weights); an ``lr`` 3 % off is an optimizer step of
                   the wrong size.
``int8_weights``   serving: ``GenerationModel.quantized()``, the
                   weight-only int8 store.
``kv_pool``        serving: the engine's ``KVBlockPool`` keeps keys and
                   values in ``dtype`` (and, where given, has only
                   ``num_blocks`` blocks). The engine has no switch for
                   it, so the class it builds its pool from is wrapped
                   while the control runs.

A sound benchmark reports a control as NOT correct: exit code 0 when
``correct`` came out false or the control crashed (a control that gives
no number has failed), 1 when it passed as correct. The
benchmark's own runs never run this; the builder does, on the chip, at
the cell's own size, before setting a limit (PERF.md gives the
readings), and ``perfbench/tests`` keeps it at toy size.
"""

import argparse
import contextlib
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run, spec  # noqa: E402


def pick(config, name=None):
    controls = config["controls"]
    for c in controls:
        if name in (None, c["name"]):
            return c
    raise spec.SpecError("no control %r (have %s)"
                         % (name, [c["name"] for c in controls]))


@contextlib.contextmanager
def switched_on(control):
    """The runner's hooks with the control switched on."""
    kind = control["kind"]
    if kind == "program_train":
        yield {"program_train": control["value"]}
    elif kind == "int8_weights":
        yield {"tamper": lambda model: model.quantized()}
    elif kind == "kv_pool":
        from paddle_tpu.serving import engine

        pool, value = engine.KVBlockPool, control["value"]

        @functools.wraps(pool)
        def smaller_type(n_layers, n_heads, head_dim, block_size,
                         num_blocks, **kw):
            return pool(n_layers, n_heads, head_dim, block_size,
                        value.get("num_blocks", num_blocks),
                        **dict(kw, dtype=value["dtype"]))

        engine.KVBlockPool = smaller_type
        try:
            yield {}
        finally:
            engine.KVBlockPool = pool
    else:
        raise spec.SpecError("unknown control %r" % (control,))


def main(argv=None, require_chip=True, root=spec.ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control")
    args = ap.parse_args(argv)
    _w, config, _t = spec.cell(spec.load_benchmark(root), args.workload,
                               root)
    control = pick(config, args.control)
    with switched_on(control) as hooks:
        try:
            line = run.run_cell(args.workload, args.seed, args.seconds, 0,
                                require_chip=require_chip, root=root,
                                hooks=hooks)
        except Exception as err:   # no number: the control has failed
            line = {"correct": False, "crashed": repr(err)[:2000]}
    line["control"] = control
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
