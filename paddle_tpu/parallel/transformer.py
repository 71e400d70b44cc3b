"""SPMD Transformer trainer: dp + pp + tp + sp + ep over one shard_map.

This is the TPU-native replacement for everything the reference built with
ParallelExecutor/NCCL/transpilers (SURVEY §2.3) *plus* the parallel modes
the 2019 reference lacked (tensor/pipeline/sequence/expert parallelism are
new design, per SURVEY §5.7).

Mesh: ("dp", "pp", "tp").
- dp  — data parallel: batch sharded; per-leaf gradient psum over replicated
        axes replaces AllReduceOpHandle (details/all_reduce_op_handle.cc:91).
- pp  — pipeline parallel: layers sharded on their leading [L] axis; GPipe
        microbatch schedule as a lax.scan whose carry rotates activations
        through the stage ring with ppermute (ICI neighbor exchange).
- tp  — tensor parallel (Megatron-style): attention heads + FFN hidden
        sharded; partial outputs reduce via reduce_scatter.
- sp  — sequence parallel on the SAME tp axis: the residual stream between
        blocks is sequence-sharded [B, T/tp, D]; all_gather before each
        matmul, reduce_scatter after — LN/dropout/residual math never
        duplicates across tp.
- ep  — expert parallel on the dp axis: MoE FFN tokens exchanged with
        all_to_all, one expert group per dp rank.

Gradients: jax.grad of the rank-local masked loss inside shard_map; the
collective transposes (all_gather ↔ reduce_scatter, ppermute ↔ reverse
ppermute, all_to_all ↔ all_to_all) route cross-rank cotangents, so the
result is the gradient of the GLOBAL loss wrt local shards. Each leaf is
then psummed over exactly the mesh axes it is replicated on (the axes
absent from its PartitionSpec) — the sharding-aware generalization of the
reference's single gradient allreduce.
"""

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
from jax.lax import axis_index as _axis_index

from ..models import transformer as T


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------


def param_specs(cfg: T.TransformerConfig):
    """PartitionSpec pytree congruent with init_params output."""
    specs = {
        "embed": P(None, None),
        "pos_embed": P(None, None),
        "final_ln_scale": P(None),
        "final_ln_bias": P(None),
        "layers": {
            "ln1_scale": P("pp", None),
            "ln1_bias": P("pp", None),
            "wqkv": P("pp", None, None, "tp", None),
            "wo": P("pp", "tp", None, None),
            "ln2_scale": P("pp", None),
            "ln2_bias": P("pp", None),
            "w1": P("pp", None, "tp"),
            "b1": P("pp", "tp"),
            "w2": P("pp", "tp", None),
            "b2": P("pp", None),
        },
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, None)
    if cfg.n_experts:
        specs["moe"] = {
            "router": P(None, None),
            "w1": P("dp", None, None),
            "w2": P("dp", None, None),
        }
    return specs


def _replicated_axes(spec, mesh_axes=("dp", "pp", "tp")):
    used = set(a for a in spec if a is not None)
    return tuple(a for a in mesh_axes if a not in used)


# ---------------------------------------------------------------------------
# rank-local building blocks (run inside shard_map)
# ---------------------------------------------------------------------------


def _block_sp(lp, h_s, cfg):
    """One transformer block on a sequence-sharded residual stream h_s
    [B, T/tp, D]. all_gather('tp') before matmuls, reduce_scatter after —
    Megatron-SP seams."""
    dtype = cfg.dtype

    x = T.layer_norm(h_s, lp["ln1_scale"], lp["ln1_bias"])
    x_full = jax.lax.all_gather(x, "tp", axis=1, tiled=True)  # [B, T, D]
    attn_partial = T.attention_block(lp, x_full, dtype)
    attn_s = jax.lax.psum_scatter(attn_partial, "tp", scatter_dimension=1,
                                  tiled=True)
    h_s = h_s + attn_s

    x = T.layer_norm(h_s, lp["ln2_scale"], lp["ln2_bias"])
    x_full = jax.lax.all_gather(x, "tp", axis=1, tiled=True)
    ffn_partial = T.ffn_block(lp, x_full, dtype)
    ffn_s = jax.lax.psum_scatter(ffn_partial, "tp", scatter_dimension=1,
                                 tiled=True)
    # b2 is tp-replicated; add once on the scattered output
    h_s = h_s + ffn_s + lp["b2"].astype(dtype)
    return h_s


def _moe_block(mp, h_s, cfg):
    """Top-1 switch MoE on the local token shard; experts sharded over the
    dp axis (expert parallelism). h_s: [B, t, D] -> same."""
    dtype = cfg.dtype
    E = cfg.n_experts
    ep = jax.lax.psum(1, "dp")  # ep group size
    e_local = E // ep
    B, t, D = h_s.shape
    N = B * t
    x = h_s.reshape(N, D)

    gates = jax.nn.softmax(
        jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                   mp["router"].astype(jnp.float32)))
    expert = jnp.argmax(gates, axis=-1)  # [N]
    gate = jnp.take_along_axis(gates, expert[:, None], axis=-1)[:, 0]

    cap = int(cfg.expert_capacity_factor * N / E) + 1
    # position of each token within its expert's capacity
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)  # [N, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1  # [N, E], -1 elsewhere
    pos1 = pos.max(axis=-1)  # [N]
    keep = pos1 < cap
    # dispatch [E, cap, D]
    disp = jnp.zeros((E, cap, D), dtype)
    idx_e = jnp.where(keep, expert, 0)
    idx_c = jnp.where(keep, pos1, 0)
    disp = disp.at[idx_e, idx_c].add(
        jnp.where(keep[:, None], x, 0).astype(dtype))
    # all_to_all over dp ("transpose"): send expert-group r's slice to rank
    # r; axis 0 of the result indexes the SOURCE rank.
    disp = disp.reshape(ep, e_local, cap, D)
    recv = jax.lax.all_to_all(disp, "dp", split_axis=0, concat_axis=0)
    toks = recv.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, D)
    # expert FFN (local experts)
    a = jnp.einsum("ecd,edf->ecf", toks, mp["w1"].astype(dtype))
    a = jax.nn.gelu(a)
    out = jnp.einsum("ecf,efd->ecd", a, mp["w2"].astype(dtype))
    # route back: inverse all_to_all
    out = out.reshape(e_local, ep, cap, D).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(out, "dp", split_axis=0, concat_axis=0)
    back = back.reshape(E, cap, D)
    # combine
    y = back[idx_e, idx_c]  # [N, D]
    y = jnp.where(keep[:, None], y, 0).astype(jnp.float32)
    y = y * gate[:, None]
    return h_s + y.reshape(B, t, D).astype(dtype)


def _stage_fn(stage_params, moe_params, h_s, cfg, layers_per_stage):
    """Run this pp rank's slice of layers (+ optional MoE) on a
    seq-sharded activation."""
    body = functools.partial(_block_sp, cfg=cfg)
    if cfg.remat:
        body = jax.checkpoint(body)
    for i in range(layers_per_stage):
        lp = jax.tree.map(lambda x: x[i], stage_params)
        h_s = body(lp, h_s)
    if moe_params is not None:
        mb = functools.partial(_moe_block, cfg=cfg)
        if cfg.remat:
            mb = jax.checkpoint(mb)
        h_s = mb(moe_params, h_s)
    return h_s


# ---------------------------------------------------------------------------
# the SPMD train step
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SPMDTrainer:
    """Builds and owns the jitted multi-parallel train step.

    mesh_shape: (dp, pp, tp). num_microbatches defaults to pp (minimum for
    a full pipeline)."""

    cfg: T.TransformerConfig
    mesh_shape: Tuple[int, int, int] = (1, 1, 1)
    num_microbatches: Optional[int] = None
    learning_rate: float = 1e-3
    adam_b1: float = 0.9
    adam_b2: float = 0.98
    devices: Any = None

    def __post_init__(self):
        from ..async_engine import setup_persistent_cache

        setup_persistent_cache()
        dp, pp, tp = self.mesh_shape
        devs = self.devices if self.devices is not None else jax.devices()
        n = dp * pp * tp
        if len(devs) < n:
            raise ValueError("need %d devices, have %d" % (n, len(devs)))
        self.mesh = Mesh(np.array(devs[:n]).reshape(dp, pp, tp),
                         ("dp", "pp", "tp"))
        self.M = self.num_microbatches or max(pp, 1)
        if self.cfg.n_layers % pp:
            raise ValueError("pp (%d) must divide n_layers (%d)" % (pp, self.cfg.n_layers))
        if self.cfg.n_heads % tp or self.cfg.d_ff % tp:
            raise ValueError("tp (%d) must divide n_heads (%d) and d_ff (%d)" % (tp, self.cfg.n_heads, self.cfg.d_ff))
        if self.cfg.max_seq_len % tp:
            raise ValueError("tp (%d) must divide max_seq_len (%d) for sequence parallelism" % (tp, self.cfg.max_seq_len))
        if self.cfg.n_experts and self.cfg.n_experts % dp:
            raise ValueError("dp (%d) must divide n_experts (%d) for expert parallelism" % (dp, self.cfg.n_experts))
        self.layers_per_stage = self.cfg.n_layers // pp
        self._specs = param_specs(self.cfg)
        self._build()

    # -- construction -------------------------------------------------------
    def _build(self):
        cfg = self.cfg
        dp, pp, tp = self.mesh_shape
        mesh = self.mesh
        M = self.M
        S = self.layers_per_stage

        pspecs = self._specs
        data_spec = P("dp", None)

        def local_loss(params, tokens, labels):
            """Rank-local loss for pp == 1 (no pipeline): embed -> stage ->
            head on the sequence shard; Σ over all ranks == global mean CE."""
            my_tp = _axis_index("tp")
            B_local, T_full = tokens.shape
            t_shard = T_full // tp
            moe_p = params.get("moe")

            h = T.embed_tokens(params, tokens, cfg)
            h = jax.lax.dynamic_slice_in_dim(
                h, my_tp * t_shard, t_shard, axis=1)
            h = _stage_fn(params["layers"], moe_p, h, cfg, S)
            h = T.layer_norm(h, params["final_ln_scale"],
                             params["final_ln_bias"])
            logits = T.lm_logits(params, h, cfg)  # [B, t_shard, V] fp32
            labs = jax.lax.dynamic_slice_in_dim(
                labels, my_tp * t_shard, t_shard, axis=1)
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(logp, labs[..., None], axis=-1)
            total_tokens = B_local * T_full * dp
            return -jnp.sum(picked) / total_tokens

        def pipeline_grads(params, tokens, labels):
            """1F1B pipeline (pp > 1): ONE scan where every tick runs one
            forward microbatch unit and one backward microbatch unit.

            Stage r forwards microbatch i at tick r+i and backwards it at
            tick 2pp-2-r+i; the last stage turns around immediately (its
            bwd of i lands the same tick as its fwd), so backward drains
            while forward fills — the activation stash is a ring buffer of
            stage INPUTS bounded by 2pp microbatches, O(pp) not O(M)
            (GPipe's whole-schedule stash). Backward ticks recompute the
            stage forward under jax.vjp from the stashed input
            (remat-style, the usual 1F1B+recompute cost model).

            Embedding runs ONLY on stage 0 and the vocab head ONLY on the
            last stage — both under lax.cond, whose branches are
            collective-free and therefore skip at run time on the other
            ranks (the round-2 review flagged the masked-GPipe version for
            burning head FLOPs on every stage). Stage compute + its vjp
            contain tp/dp collectives and run unconditionally in lockstep;
            invalid warmup/cooldown ticks process garbage activations whose
            contributions are masked out of the gradient accumulators.

            Returns (rank-local loss contribution, fp32 grads congruent
            with params)."""
            my_pp = _axis_index("pp")
            my_tp = _axis_index("tp")
            B_local, T_full = tokens.shape
            t_shard = T_full // tp
            mb = B_local // M
            has_moe = bool(cfg.n_experts)
            moe_p = params.get("moe") if has_moe else {}
            lp_local = params["layers"]
            total_tokens = B_local * T_full * dp
            tied = cfg.tie_embeddings

            microtoks = tokens.reshape(M, mb, T_full)
            microlabs = labels.reshape(M, mb, T_full)

            head_keys = ["final_ln_scale", "final_ln_bias"] + (
                ["embed"] if tied else ["lm_head"])
            head_p0 = {k: params[k] for k in head_keys}
            emb_p0 = {"embed": params["embed"],
                      "pos_embed": params["pos_embed"]}

            def embed_fn(e_p, toks):
                h = T.embed_tokens({**params, **e_p}, toks, cfg)
                return jax.lax.dynamic_slice_in_dim(
                    h, my_tp * t_shard, t_shard, axis=1)

            def stage_fwd(lp, mp, h_in):
                return _stage_fn(lp, mp if has_moe else None, h_in, cfg, S)

            def head_loss(h_p, h_out, labs_t):
                h = T.layer_norm(h_out, h_p["final_ln_scale"],
                                 h_p["final_ln_bias"])
                logits = T.lm_logits({**params, **h_p}, h, cfg)
                labs = jax.lax.dynamic_slice_in_dim(
                    labs_t, my_tp * t_shard, t_shard, axis=1)
                logp = jax.nn.log_softmax(logits, axis=-1)
                picked = jnp.take_along_axis(logp, labs[..., None], axis=-1)
                return -jnp.sum(picked) / total_tokens

            S_ring = 2 * pp
            zeros_act = jnp.zeros((mb, t_shard, cfg.d_model), cfg.dtype)
            K = M + 2 * pp - 2
            f32z = lambda tree: jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), tree)

            def acc(g_tree, d_tree, valid):
                return jax.tree.map(
                    lambda g, d: g + jnp.where(valid, d, 0).astype(
                        jnp.float32), g_tree, d_tree)

            def tick(carry, t):
                (fwd_recv, bwd_recv, stash,
                 gL, gM, gE, gH, loss_acc) = carry

                # ---- forward unit: microbatch i_f = t - r ----
                i_f = t - my_pp
                valid_f = (i_f >= 0) & (i_f < M)
                i_fc = jnp.clip(i_f, 0, M - 1)
                toks_f = jax.lax.dynamic_index_in_dim(
                    microtoks, i_fc, axis=0, keepdims=False)
                h_in = jax.lax.cond(
                    my_pp == 0,
                    lambda _: embed_fn(emb_p0, toks_f),
                    lambda _: fwd_recv, None)
                h_out = stage_fwd(lp_local, moe_p, h_in)
                stash2 = jax.lax.dynamic_update_index_in_dim(
                    stash, h_in, jnp.mod(i_fc, S_ring), axis=0)
                stash = jnp.where(valid_f, stash2, stash)

                # ---- backward unit: microbatch i_b = t - (2pp-2-r) ----
                i_b = t - (2 * pp - 2 - my_pp)
                valid_b = (i_b >= 0) & (i_b < M)
                i_bc = jnp.clip(i_b, 0, M - 1)
                labs_b = jax.lax.dynamic_index_in_dim(
                    microlabs, i_bc, axis=0, keepdims=False)
                toks_b = jax.lax.dynamic_index_in_dim(
                    microtoks, i_bc, axis=0, keepdims=False)

                # last stage: fwd of i_b happened THIS tick (t = pp-1+i_b),
                # so the head differentiates the h_out just computed
                def head_branch(_):
                    loss_i, hvjp = jax.vjp(
                        lambda hp, h: head_loss(hp, h, labs_b),
                        head_p0, h_out)
                    gh_i, g_out = hvjp(jnp.float32(1.0))
                    return loss_i, gh_i, g_out

                def relay_branch(_):
                    return (jnp.float32(0.0),
                            jax.tree.map(jnp.zeros_like, head_p0),
                            bwd_recv)

                loss_i, gh_i, g_out = jax.lax.cond(
                    my_pp == pp - 1, head_branch, relay_branch, None)

                h_in_b = jax.lax.dynamic_index_in_dim(
                    stash, jnp.mod(i_bc, S_ring), axis=0, keepdims=False)
                _, svjp = jax.vjp(stage_fwd, lp_local, moe_p, h_in_b)
                gl_i, gm_i, g_in = svjp(g_out)

                def emb_branch(_):
                    _, evjp = jax.vjp(
                        lambda ep: embed_fn(ep, toks_b), emb_p0)
                    (ge_i,) = evjp(g_in)
                    return ge_i

                ge_i = jax.lax.cond(
                    my_pp == 0, emb_branch,
                    lambda _: jax.tree.map(jnp.zeros_like, emb_p0), None)

                gL = acc(gL, gl_i, valid_b)
                gM = acc(gM, gm_i, valid_b)
                gE = acc(gE, ge_i, valid_b)
                gH = acc(gH, gh_i, valid_b)
                loss_acc = loss_acc + jnp.where(valid_b, loss_i, 0.0)

                # ---- ring exchanges (unconditional, all ranks) ----
                fwd_next = jax.lax.ppermute(
                    h_out, "pp", [(i, (i + 1) % pp) for i in range(pp)])
                bwd_next = jax.lax.ppermute(
                    g_in, "pp", [(i, (i - 1) % pp) for i in range(pp)])
                return (fwd_next, bwd_next, stash,
                        gL, gM, gE, gH, loss_acc), None

            init = (zeros_act, zeros_act,
                    jnp.zeros((S_ring, mb, t_shard, cfg.d_model), cfg.dtype),
                    f32z(lp_local), f32z(moe_p), f32z(emb_p0),
                    f32z(head_p0), jnp.float32(0.0))
            (_, _, _, gL, gM, gE, gH, loss_acc), _ = jax.lax.scan(
                tick, init, jnp.arange(K))

            grads = {
                "embed": gE["embed"] + (gH["embed"] if tied else 0.0),
                "pos_embed": gE["pos_embed"],
                "final_ln_scale": gH["final_ln_scale"],
                "final_ln_bias": gH["final_ln_bias"],
                "layers": gL,
            }
            if not tied:
                grads["lm_head"] = gH["lm_head"]
            if has_moe:
                grads["moe"] = gM
            return loss_acc, grads

        lr = self.learning_rate
        b1, b2 = self.adam_b1, self.adam_b2

        flat_specs = jax.tree.leaves(
            pspecs, is_leaf=lambda x: isinstance(x, P))

        def spmd_step(params, m_state, v_state, step, tokens, labels):
            if pp == 1:
                contrib, grads = jax.value_and_grad(local_loss)(
                    params, tokens, labels)
            else:
                contrib, grads = pipeline_grads(params, tokens, labels)
            # per-leaf psum over the axes each leaf is replicated on
            flat_g, gdef = jax.tree.flatten(grads)
            flat_g = [
                jax.lax.psum(g, _replicated_axes(s))
                if _replicated_axes(s) else g
                for g, s in zip(flat_g, flat_specs)
            ]
            grads = jax.tree.unflatten(gdef, flat_g)
            # contrib already carries the full 1/total_tokens scaling
            loss = jax.lax.psum(contrib, ("dp", "pp", "tp"))
            # Adam (fp32 state, local shards)
            stepf = (step + 1).astype(jnp.float32)
            bc1 = 1.0 - b1 ** stepf
            bc2 = 1.0 - b2 ** stepf

            def upd(p, g, m, v):
                gf = g.astype(jnp.float32)
                m2 = b1 * m + (1 - b1) * gf
                v2 = b2 * v + (1 - b2) * gf * gf
                p2 = p - lr * (m2 / bc1) / (jnp.sqrt(v2 / bc2) + 1e-8)
                return p2.astype(p.dtype), m2, v2

            flat_p, treedef = jax.tree.flatten(params)
            flat_g = jax.tree.leaves(grads)
            flat_m = jax.tree.leaves(m_state)
            flat_v = jax.tree.leaves(v_state)
            out_p, out_m, out_v = [], [], []
            for pleaf, gleaf, mleaf, vleaf in zip(flat_p, flat_g, flat_m,
                                                  flat_v):
                p2, m2, v2 = upd(pleaf, gleaf, mleaf, vleaf)
                out_p.append(p2)
                out_m.append(m2)
                out_v.append(v2)
            return (jax.tree.unflatten(treedef, out_p),
                    jax.tree.unflatten(treedef, out_m),
                    jax.tree.unflatten(treedef, out_v),
                    step + 1, loss)

        in_specs = (pspecs, pspecs, pspecs, P(), data_spec, data_spec)
        out_specs = (pspecs, pspecs, pspecs, P(), P())
        mapped = shard_map(spmd_step, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        self._step = jax.jit(mapped, donate_argnums=(0, 1, 2))
        self._loss_fn = local_loss

    # -- API ----------------------------------------------------------------
    def init(self, seed=0):
        cfg = self.cfg
        params = T.init_params(jax.random.PRNGKey(seed), cfg)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._specs,
            is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, shardings)
        m = jax.device_put(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            shardings)
        v = jax.device_put(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            shardings)
        step = jnp.zeros((), jnp.int32)
        return params, m, v, step

    def step(self, state, tokens, labels):
        params, m, v, step = state
        params, m, v, step, loss = self._step(params, m, v, step, tokens,
                                              labels)
        return (params, m, v, step), loss


class _noop:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
