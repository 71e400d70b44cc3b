"""Model FLOP/s utilization of training: tokens per second times the
forward+backward FLOPs a token needs (the family's own count: causal
attention halved, recomputation not counted) over chips times the
device's published bf16 peak."""

from perfbench import spec


def read(obs, rate="train_tokens_per_s"):
    tokens_per_s = obs.get(rate)
    if tokens_per_s is None:
        return None
    flops = spec.family(obs["config"], "flops")
    matmul, attention = flops.train_flops_per_token(obs["config"],
                                                    obs["seq_len"])
    peak = obs["chips"] * obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * tokens_per_s * (matmul + attention) / peak
