"""Operations and bytes the Ling-3.0 (``bailing_hybrid``) decoder needs,
computed from shapes and from the counters the steps return and the step
log keeps: what a step HAS to move and to multiply, whatever the program
does to get there. A weight is counted once a call, an expert only where
at least one row reached it, a row's scan state once in and once out a
step and layer, a cached latent row once for the queries that see it,
and rows in and out at the bytes the kernels are handed.
"""

WEIGHT_BYTES = 2      # bfloat16, the configuration's stored type
CACHE_BYTES = 2       # the latent pool
F32 = 4               # the scan state, and every kernel's float32 rows


def dims(cfg):
    group, first = int(cfg["layer_group_size"]), \
        int(cfg.get("published", {}).get("layers_held", [0])[0])
    L = int(cfg["num_hidden_layers"])
    mla = sum((first + j + 1) % group == 0 for j in range(L))
    return dict(
        D=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
        d=int(cfg["head_dim"]), L=L, mla=mla, kda=L - mla,
        dense=int(cfg["first_k_dense_replace"]),
        F=int(cfg["intermediate_size"]),
        Eh=int(cfg["num_experts"]), k=int(cfg["num_experts_per_tok"]),
        E=int(cfg.get("router_experts", cfg["num_experts"])),
        Fe=int(cfg["moe_intermediate_size"]),
        Fs=int(cfg["num_shared_experts"])
        * int(cfg["moe_shared_expert_intermediate_size"]),
        V=int(cfg["vocab_size"]), r=int(cfg["kv_lora_rank"]),
        dn=int(cfg["qk_nope_head_dim"]), dr=int(cfg["qk_rope_head_dim"]),
        dv=int(cfg["v_head_dim"]))


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


def kda_state_bytes(cfg, rows):
    """Bytes the one-token scan step had to move for steps that held
    ``rows`` rows between them: each row's ``H x dk x dv`` float32
    states of every KDA layer read once and written once, and the row's
    query, key, decay, value (float32) in and read-out out."""
    d = dims(cfg)
    state = d["H"] * d["d"] * d["d"] * F32
    vectors = d["H"] * (5 * d["d"] + 1) * F32
    return rows * d["kda"] * (2 * state + vectors)


def kda_chunk_bytes(cfg, scan_tokens, chunk_rows):
    """Bytes the chunked scan had to move for steps whose rows of more
    than one token held ``scan_tokens`` tokens in ``chunk_rows`` rows:
    each token's query, key, value, log-decay and beta in and read-out
    out (float32), each such row's states in and out, every KDA
    layer."""
    d = dims(cfg)
    state = d["H"] * d["d"] * d["d"] * F32
    vectors = d["H"] * (5 * d["d"] + 1) * F32
    return d["kda"] * (scan_tokens * vectors + chunk_rows * 2 * state)


def kda_flops(cfg, tokens):
    """The recurrence's own arithmetic a token, head and KDA layer: the
    decay (``dk x dv``), ``k^T S``, the rank-one update and ``S^T q``
    (``2 dk dv`` each), whichever form computes it."""
    d = dims(cfg)
    return tokens * d["kda"] * d["H"] * 7 * d["d"] * d["d"]


def kda_chunk_flops(cfg, scan_tokens):
    return kda_flops(cfg, scan_tokens)


def latent_attention_bytes(cfg, cached_tokens, query_rows):
    """Bytes the latent attention calls of the MLA layers had to move
    for steps whose context lengths sum to ``cached_tokens`` and that
    held ``query_rows`` one-token rows: each cached token's stored row
    once a layer (every head reads the same row), each row's queries in
    (the pool's type) and its latent-space context out (float32)."""
    d = dims(cfg)
    width = d["r"] + d["dr"]
    return d["mla"] * (cached_tokens * width * CACHE_BYTES
                       + query_rows * d["H"] * (width * CACHE_BYTES
                                                + d["r"] * F32))


def latent_attention_flops(cfg, keys):
    """From ``global_keys_attended`` (the (query, key) pairs, over the
    MLA layers already): the absorbed form's ``q . [c | k_pe]`` and ``p
    . c`` a pair and head."""
    d = dims(cfg)
    return keys * d["H"] * 2 * (d["r"] + d["dr"] + d["r"])


def matmul_params_per_token(cfg):
    """Parameters a token's forward multiplies outside the routed
    experts, the scan and the head: a KDA layer's four projections, its
    output projection and its two head-wise ones; an MLA layer's query
    and latent projections, the two absorbed maps of every head and its
    output projection and gate; the dense layers' SwiGLU; each expert
    layer's router and shared expert."""
    d = dims(cfg)
    D, H = d["D"], d["H"]
    kda = 5 * D * H * d["d"] + 2 * D * H
    mla = (D * H * (d["dn"] + d["dr"]) + D * (d["r"] + d["dr"])
           + H * d["r"] * (d["dn"] + d["dv"]) + H * d["dv"] * D + D * H)
    moe = D * d["E"] + 3 * D * d["Fs"]
    return (d["kda"] * kda + d["mla"] * mla + d["dense"] * 3 * D * d["F"]
            + expert_layers(cfg) * moe)


def step_flops(cfg, tokens, rows, pairs, global_keys, window_keys):
    """Forward FLOPs of steps that held ``tokens`` tokens in ``rows``
    rows (the head runs once a row), placed ``pairs`` token-expert rows
    on held experts and attended ``global_keys`` latent rows (the
    block keeps no window pages: ``window_keys`` is 0)."""
    d = dims(cfg)
    return (2 * tokens * matmul_params_per_token(cfg)
            + 2 * rows * d["D"] * d["V"] + gmm_flops(cfg, pairs)
            + kda_flops(cfg, tokens)
            + latent_attention_flops(cfg, global_keys + window_keys))


def cache_bytes_per_token(cfg):
    d = dims(cfg)
    return d["mla"] * (d["r"] + d["dr"]) * CACHE_BYTES


def row_state_bytes(cfg):
    """The scan's matrices (float32) and the convolutions' inputs (in
    the configuration's ``dtypes.activations``) of one batch row."""
    d = dims(cfg)
    conv = 2 if cfg.get("dtypes", {}).get("activations", "bfloat16") \
        == "bfloat16" else 4
    taps = int(cfg["short_conv_kernel_size"])
    return d["kda"] * (d["H"] * d["d"] * d["d"] * F32
                       + (taps - 1) * 3 * d["H"] * d["d"] * conv)
