"""Operations and bytes the Kanana-2 (``deepseek_v3``) decoder needs,
computed from shapes and from the counters the steps return: what a
decode step HAS to move, whatever the program does to get there. A
weight is counted once a call, an expert only where at least one row
reached it, and rows in and out at the bytes the kernels are handed.
"""

WEIGHT_BYTES = 2      # bfloat16, the configuration's stored type
CACHE_BYTES = 2       # the latent pool
F32 = 4


def dims(cfg):
    return dict(
        D=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
        L=int(cfg["num_hidden_layers"]),
        dense=int(cfg["first_k_dense_replace"]),
        E=int(cfg["n_routed_experts"]), k=int(cfg["num_experts_per_tok"]),
        Fe=int(cfg["moe_intermediate_size"]),
        r=int(cfg["kv_lora_rank"]), dr=int(cfg["qk_rope_head_dim"]))


def expert_layers(cfg):
    d = dims(cfg)
    return d["L"] - d["dense"]


def gmm_bytes(cfg, experts_touched, pairs):
    """Bytes the three grouped matmuls of the expert layers had to
    move for steps whose counters sum to ``experts_touched`` (distinct
    experts with a row, summed over layers and steps) and ``pairs``
    (token-expert rows): each touched expert's gate, up and down
    matrices once; each row in (bf16, once per projection) and out
    (float32 from the kernel)."""
    d = dims(cfg)
    D, Fe = d["D"], d["Fe"]
    weights = experts_touched * 3 * D * Fe * WEIGHT_BYTES
    rows = pairs * ((2 * D + Fe) * WEIGHT_BYTES + (2 * Fe + D) * F32)
    return weights + rows


def gmm_flops(cfg, pairs):
    d = dims(cfg)
    return pairs * 3 * 2 * d["D"] * d["Fe"]


def latent_attention_bytes(cfg, cached_tokens, query_rows):
    """Bytes the latent attention calls of ALL layers had to move for
    steps whose context lengths sum to ``cached_tokens`` and that held
    ``query_rows`` one-token rows: each cached token's stored row once
    a layer (every head reads the same row), each row's queries in
    (the pool's type) and its latent-space context out (float32)."""
    d = dims(cfg)
    width = d["r"] + d["dr"]
    per_layer = (cached_tokens * width * CACHE_BYTES
                 + query_rows * d["H"] * (width * CACHE_BYTES
                                          + d["r"] * F32))
    return d["L"] * per_layer


def latent_attention_flops(cfg, cached_tokens):
    d = dims(cfg)
    return d["L"] * cached_tokens * d["H"] * 2 * (d["r"] + d["dr"] + d["r"])


def cache_bytes_per_token(cfg):
    d = dims(cfg)
    return d["L"] * (d["r"] + d["dr"]) * CACHE_BYTES
