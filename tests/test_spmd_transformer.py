"""SPMD transformer trainer tests on the virtual 8-device CPU mesh:
numerical parity across mesh shapes (dp/pp/tp/sp), MoE expert-parallel
training, and the driver dryrun entry."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.transformer import TransformerConfig
from paddle_tpu.parallel.transformer import SPMDTrainer


def _data(rng, batch, seq, vocab):
    toks = rng.randint(0, vocab, size=(batch, seq)).astype(np.int32)
    labs = np.roll(toks, -1, axis=1).astype(np.int32)
    return toks, labs


def _run(cfg, shape, toks, labs, steps=3, **kw):
    tr = SPMDTrainer(cfg, mesh_shape=shape, learning_rate=1e-2, **kw)
    state = tr.init(0)
    losses = []
    for _ in range(steps):
        state, loss = tr.step(state, toks, labs)
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("shape", [(2, 2, 2), (8, 1, 1), (1, 1, 4),
                                   (1, 4, 1), (2, 1, 4)])
def test_mesh_parity(shape):
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                            d_ff=64, max_seq_len=16, n_experts=0,
                            remat=False, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    toks, labs = _data(rng, 8, 16, 64)
    base = _run(cfg, (1, 1, 1), toks, labs)
    got = _run(cfg, shape, toks, labs)
    np.testing.assert_allclose(got, base, rtol=2e-3)


def test_moe_expert_parallel_trains():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                            d_ff=64, max_seq_len=16, n_experts=4,
                            remat=True, dtype=jnp.float32)
    rng = np.random.RandomState(1)
    toks, labs = _data(rng, 8, 16, 64)
    losses = _run(cfg, (2, 2, 2), toks, labs, steps=8, num_microbatches=2)
    assert losses[-1] < losses[0], losses


def test_dryrun_multichip():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)


def test_resid_layout_packs_float0_residuals():
    """Activation-stash packing of float0 vjp residuals (the MoE argmax
    routing in the full SPMD step produces them): float0 leaves carry no
    bytes, so pack strips them and unpack re-materializes zeros — the
    regression that used to raise NotImplementedError from
    _ResidLayout and killed every stash-mode dryrun."""
    from paddle_tpu.parallel.pipeline_program import _ResidLayout

    leaves = [jnp.arange(6.0, dtype=jnp.float32).reshape(2, 3),
              np.zeros((4,), dtype=jax.dtypes.float0),
              jnp.arange(5, dtype=jnp.int32)]
    treedef = jax.tree.structure(leaves)
    avals = [(np.shape(l), l.dtype) for l in leaves]
    layout = _ResidLayout(treedef, avals, [None] * len(leaves))
    # float0 contributes nothing to either packed buffer
    assert layout.nf == 6 and layout.ni == 5
    f, i = layout.pack(leaves, layout.nf, layout.ni)
    out = layout.unpack(f, i, {})
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(leaves[0]))
    assert out[1].dtype == jax.dtypes.float0
    assert out[1].shape == (4,)
    np.testing.assert_array_equal(np.asarray(out[2]),
                                  np.asarray(leaves[2]))


def test_entry_compiles():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__

    fn, (params, tokens) = __graft_entry__.entry()
    shapes = jax.eval_shape(fn, params, tokens)
    assert shapes.shape == (8, 512, 32000)
