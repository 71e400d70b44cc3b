"""``perfbench/control.py`` with two kinds of control of the
latent-attention / routed-expert block, which ``control.py`` (not this
PR's to edit) does not know. Every other kind, the arguments, the
printed line and the exit code are ``control.py``'s.

``block``        the served model's block description with ``value``'s
                 keys changed: ``{"router_dtype": "bfloat16"}`` computes
                 the router's scores in bfloat16.
``expert_grid``  the routed experts' matrices put on the int8 grid (per
                 expert and output channel, symmetric, 127 steps) and
                 multiplied back out into their storage dtype: the values
                 an int8 store would compute with. The program has no
                 such store for this block yet (ROADMAP Queue 2a), so the
                 control lives here. Each leaf is rewritten IN PLACE
                 (donated): the experts are most of a chip.

    python3 perfbench/control_block.py --workload <cell> --seed <n> \
        --seconds <s> --control <name>
"""

import contextlib
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import control  # noqa: E402

_switched_on = control.switched_on


def with_block(model, **changes):
    """The same weights under a changed block description."""
    from paddle_tpu.serving import GenerationConfig

    cfg = GenerationConfig.from_dict(model.config.to_dict())
    cfg.block = model.config.block.replace(**changes)
    return type(model)(cfg, model.weights, name=model.name)


def experts_on_int8_grid(model):
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def on_grid(w):
        w32 = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(w32), axis=1, keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return (jnp.clip(jnp.round(w32 / s), -127, 127) * s).astype(w.dtype)

    weights = {k: on_grid(v) if k.rpartition("/")[2].startswith("we_")
               else v for k, v in model.weights.items()}
    return type(model)(model.config, weights, name=model.name + ".int8grid")


@contextlib.contextmanager
def switched_on(c):
    if c["kind"] == "block":
        yield {"tamper": lambda model: with_block(model, **c["value"])}
    elif c["kind"] == "expert_grid":
        yield {"tamper": experts_on_int8_grid}
    else:
        with _switched_on(c) as hooks:
            yield hooks


def main(argv=None, **kw):
    control.switched_on = switched_on
    try:
        return control.main(argv, **kw)
    finally:
        control.switched_on = _switched_on


if __name__ == "__main__":
    sys.exit(main())
