"""Operations and bytes the Trinity (``afmoe``) decoder needs, computed
from shapes and from the counters the steps return and the step log
keeps: what a step HAS to move and to multiply, whatever the program
does to get there. A weight is counted once a call, an expert only where
at least one row reached it, a cached key or value once for the queries
that see it (not once a query tile), and rows in and out at the bytes
the kernels are handed.
"""

WEIGHT_BYTES = 2      # bfloat16, the configuration's stored type
CACHE_BYTES = 2       # the K/V pools
F32 = 4


def dims(cfg):
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    types = list(cfg["layer_types"])
    return dict(
        D=int(cfg["hidden_size"]), H=H, Hkv=Hkv, Dh=int(cfg["head_dim"]),
        L=int(cfg["num_hidden_layers"]),
        dense=int(cfg["num_dense_layers"]), F=int(cfg["intermediate_size"]),
        Eh=int(cfg["num_experts"]), k=int(cfg["num_experts_per_tok"]),
        E=int(cfg.get("router_experts", cfg["num_experts"])),
        Fe=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["num_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        V=int(cfg["vocab_size"]), window=int(cfg["sliding_window"]),
        global_layers=types.count("full_attention"),
        window_layers=types.count("sliding_attention"))


def expert_layers(cfg):
    d = dims(cfg)
    return d["L"] - d["dense"]


def gmm_bytes(cfg, experts_touched, pairs):
    """Bytes the three grouped matmuls of the expert layers had to move
    for steps whose counters sum to ``experts_touched`` (distinct held
    experts with a row, summed over layers and steps) and ``pairs``
    (token-expert rows placed on held experts): each touched expert's
    gate, up and down matrices once; each row in (bf16, once per
    projection) and out (float32 from the kernel)."""
    d = dims(cfg)
    D, Fe = d["D"], d["Fe"]
    weights = experts_touched * 3 * D * Fe * WEIGHT_BYTES
    rows = pairs * ((2 * D + Fe) * WEIGHT_BYTES + (2 * Fe + D) * F32)
    return weights + rows


def gmm_flops(cfg, pairs):
    d = dims(cfg)
    return pairs * 3 * 2 * d["D"] * d["Fe"]


def token_cache_bytes(cfg):
    """K and V of one token in one layer."""
    d = dims(cfg)
    return 2 * d["Hkv"] * d["Dh"] * CACHE_BYTES


def attention_bytes(cfg, global_keys, window_keys, query_tokens):
    """Bytes the attention kernel had to move for steps whose
    ``global_keys_attended`` and ``window_keys_attended`` sum to these
    (one-token rows: a key is attended by one query, so the keys ARE the
    cached tokens read, over the layers of each kind) and that held
    ``query_tokens`` query tokens: each key's K and V once; each query
    token's heads in (bf16) and context out (float32), every layer."""
    d = dims(cfg)
    return ((global_keys + window_keys) * token_cache_bytes(cfg)
            + query_tokens * d["L"] * d["H"] * d["Dh"]
            * (CACHE_BYTES + F32))


def chunk_attention_bytes(cfg, pages, query_tokens):
    """The same for the rows of a mixed step that hold a chunk, where a
    key is seen by many queries: from ``chunk_pages_walked`` (the
    distinct pages such a row's queries need, over both kinds' layers),
    each page once."""
    d = dims(cfg)
    page = int(cfg["engine"]["block_size"]) * token_cache_bytes(cfg)
    return (pages * page + query_tokens * d["L"] * d["H"] * d["Dh"]
            * (CACHE_BYTES + F32))


def chunk_attention_flops(cfg, keys):
    """From ``chunk_keys_attended``: both kinds' pairs in one count."""
    return attention_flops(cfg, keys, 0)


def attention_flops(cfg, global_keys, window_keys):
    """QK^T and PV over the (query, key) pairs the band keeps: ``2 * 2 *
    head_dim`` a pair and query head."""
    d = dims(cfg)
    return (global_keys + window_keys) * d["H"] * d["Dh"] * 4


def matmul_params_per_token(cfg):
    """Parameters a token's forward multiplies outside the routed
    experts and the head: attention projections of every layer, the
    dense layers' SwiGLU, and each expert layer's router and shared
    expert."""
    d = dims(cfg)
    attn = d["D"] * d["Dh"] * (3 * d["H"] + 2 * d["Hkv"])   # q, g, o, k, v
    moe = d["D"] * d["E"] + 3 * d["D"] * d["Fs"]
    return (d["L"] * attn + d["dense"] * 3 * d["D"] * d["F"]
            + expert_layers(cfg) * moe)


def step_flops(cfg, tokens, rows, pairs, global_keys, window_keys):
    """Forward FLOPs of steps that held ``tokens`` tokens in ``rows``
    rows (the head runs once a row), placed ``pairs`` token-expert rows
    on held experts and attended these keys."""
    d = dims(cfg)
    return (2 * tokens * matmul_params_per_token(cfg)
            + 2 * rows * d["D"] * d["V"] + gmm_flops(cfg, pairs)
            + attention_flops(cfg, global_keys, window_keys))


def cache_bytes_per_token(cfg):
    d = dims(cfg)
    return d["L"] * token_cache_bytes(cfg)
