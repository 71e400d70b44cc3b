"""Deep Gradient Compression (parity: SURVEY §2.3 P9 —
details/sparse_all_reduce_op_handle.cc:43 `RunImplEncoded` top-k encode +
ncclAllGather :112-129; dgc_op.cc; optimizer.py:640 DGCMomentumOptimizer).

TPU-native: inside shard_map over the dp axis each rank keeps an error-
feedback residual (momentum correction), top-k selects the largest-magnitude
entries of (residual + grad), and only (values, indices) all_gather across
the ring — k/N of the allreduce bytes. The gathered sparse updates scatter-
add into a dense tensor on every rank, which stays bit-identical across
ranks (deterministic collective order parity: all_reduce_deps_pass).
"""

import functools

import jax
import jax.numpy as jnp


def topk_sparsify(x, k):
    """(values, indices) of the k largest-|x| entries of flat x; the dense
    complement (what stays in the residual)."""
    flat = x.reshape(-1)
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    picked = flat[idx]
    dense_kept = jnp.zeros_like(flat).at[idx].set(picked)
    residual = flat - dense_kept
    return picked, idx, residual.reshape(x.shape)


def dgc_allreduce(grad, residual, axis_name, sparsity=0.99, momentum=0.9):
    """One DGC round for one gradient tensor inside shard_map.

    Returns (dense averaged sparse-allreduced grad, new residual).
    residual carries momentum-corrected unsent mass (dgc_op.cc encode)."""
    n = jax.lax.psum(1, axis_name)
    acc = residual * momentum + grad
    k = max(1, int(acc.size * (1.0 - sparsity)))
    vals, idx, new_residual = topk_sparsify(acc, k)

    all_vals = jax.lax.all_gather(vals, axis_name)   # [n, k]
    all_idx = jax.lax.all_gather(idx, axis_name)     # [n, k]
    dense = jnp.zeros((acc.size,), acc.dtype)
    dense = dense.at[all_idx.reshape(-1)].add(all_vals.reshape(-1))
    return (dense / n).reshape(grad.shape), new_residual


def make_dgc_step(mesh, loss_fn, lr=0.1, momentum=0.9, sparsity=0.99,
                  axis_name="dp"):
    """jitted (params, residuals, velocities, *batch-shards) ->
    (params, residuals, velocities, loss) — momentum SGD over DGC-compressed
    gradients (DGCMomentumOptimizer parity)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def rank_step(params, residuals, velocities, *batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        loss = jax.lax.pmean(loss, axis_name)

        def upd(p, g, r, vel):
            g_avg, r_new = dgc_allreduce(g, r, axis_name, sparsity, momentum)
            vel_new = momentum * vel + g_avg
            return p - lr * vel_new, r_new, vel_new

        flat_p, tdef = jax.tree.flatten(params)
        out = [upd(p, g, r, v) for p, g, r, v in zip(
            flat_p, tdef.flatten_up_to(grads),
            tdef.flatten_up_to(residuals),
            tdef.flatten_up_to(velocities))]
        return (tdef.unflatten([o[0] for o in out]),
                tdef.unflatten([o[1] for o in out]),
                tdef.unflatten([o[2] for o in out]), loss)

    rep = P()
    data = P(axis_name)
    fn = shard_map(
        rank_step, mesh=mesh,
        in_specs=(rep, rep, rep, data, data),
        out_specs=(rep, rep, rep, rep),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1, 2))
