"""Operations and bytes the ZAYA1 decoder needs, computed from shapes
and from the counters the steps return and the step log keeps: what a
step HAS to move and to multiply, whatever the program does to get
there (``perfbench/flops/trinity.py``'s rules and signatures, so that
the per-layer metric files of the kernels the two blocks share serve
both). A weight is counted once a call, an expert only where at least
one row reached it, a cached key or value once for the queries that see
it, and rows in and out at the bytes the kernels are handed. Every layer
attends every position: ``window_keys`` is 0 in this block's records.
"""

WEIGHT_BYTES = 2      # bfloat16, the configuration's stored type
CACHE_BYTES = 2       # the K/V pool
F32 = 4


def dims(cfg):
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return dict(
        D=int(cfg["hidden_size"]), H=H, Hkv=Hkv, Dh=int(cfg["head_dim"]),
        L=int(cfg["num_hidden_layers"]), E=int(cfg["num_experts"]),
        k=int(cfg["num_experts_per_tok"]),
        Fe=int(cfg["moe_intermediate_size"]),
        R=int(cfg["router_hidden_size"]), V=int(cfg["vocab_size"]),
        taps=int(cfg["cca_time1"]))


def gmm_bytes(cfg, experts_touched, pairs):
    """Bytes the three grouped matmuls of the expert layers had to move
    for steps whose counters sum to ``experts_touched`` (distinct
    experts with a row, summed over layers and steps) and ``pairs``
    (token-expert rows: one a token and layer): each touched expert's
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
    """K and V of one token in one layer: 2 x 2 cache heads x 128."""
    d = dims(cfg)
    return 2 * d["Hkv"] * d["Dh"] * CACHE_BYTES


def attention_bytes(cfg, global_keys, window_keys, query_tokens):
    """Bytes the decode attention kernel had to move for steps whose
    ``global_keys_attended`` sum to this (one-token rows: a key is
    attended by one query, so the keys ARE the cached tokens read, over
    the layers) and that held ``query_tokens`` query tokens: each key's
    K and V once; each query token's heads in (bf16) and context out
    (float32), every layer."""
    d = dims(cfg)
    return ((global_keys + window_keys) * token_cache_bytes(cfg)
            + query_tokens * d["L"] * d["H"] * d["Dh"]
            * (CACHE_BYTES + F32))


def chunk_attention_bytes(cfg, pages, query_tokens):
    """The same for the rows of a mixed step that hold a chunk, where a
    key is seen by many queries: from ``chunk_pages_walked`` (the
    distinct pages such a row's queries need, over the layers), each
    page once."""
    d = dims(cfg)
    page = int(cfg["engine"]["block_size"]) * token_cache_bytes(cfg)
    return (pages * page + query_tokens * d["L"] * d["H"] * d["Dh"]
            * (CACHE_BYTES + F32))


def chunk_attention_flops(cfg, keys):
    return attention_flops(cfg, keys, 0)


def attention_flops(cfg, global_keys, window_keys):
    """QK^T and PV over the (query, key) pairs under the causal band:
    ``2 * 2 * head_dim`` a pair and query head."""
    d = dims(cfg)
    return (global_keys + window_keys) * d["H"] * d["Dh"] * 4


def matmul_params_per_token(cfg):
    """Parameters a token's forward multiplies outside the routed
    experts and the head, every layer: ``W_q``, ``W_k``, ``W_v1``,
    ``W_v2``, ``W_o``, the grouped convolution's taps, and the router
    (its projection and three layers)."""
    d = dims(cfg)
    attn = d["D"] * d["Dh"] * (2 * d["H"] + d["Hkv"] + 2)
    conv = (d["H"] + d["Hkv"]) * d["taps"] * d["Dh"] * d["Dh"]
    router = d["D"] * d["R"] + 2 * d["R"] * d["R"] + d["R"] * d["E"]
    return d["L"] * (attn + conv + router)


def step_flops(cfg, tokens, rows, pairs, global_keys, window_keys):
    """Forward FLOPs of steps that held ``tokens`` tokens in ``rows``
    rows (the head runs once a row, over the whole tied vocabulary),
    placed ``pairs`` token-expert rows and attended these keys."""
    d = dims(cfg)
    return (2 * tokens * matmul_params_per_token(cfg)
            + 2 * rows * d["D"] * d["V"] + gmm_flops(cfg, pairs)
            + attention_flops(cfg, global_keys, window_keys))


def cache_bytes_per_token(cfg):
    d = dims(cfg)
    return d["L"] * token_cache_bytes(cfg)
