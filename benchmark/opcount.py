"""Operations and bytes that the algorithms NEED, from shapes alone.

These are the numerators of every utilization and roofline share the
benchmark reports.  They count what the mathematics requires, not what a
particular implementation executes: recomputed operations, padded lanes and
dead pages count for nothing, so doing less of them shows as a gain.
"""


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg):
    """Parameters of one decoder block (matrices and the two norms)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    return h * q + 2 * h * kv + q * h + 3 * h * f + 2 * h


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def embedding_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def matmul_params(cfg):
    """Parameters outside the embedding table: every one is multiplied with
    every token (the final norm's H is counted with them)."""
    return (cfg["num_hidden_layers"] * layer_params(cfg) + head_params(cfg)
            + cfg["hidden_size"])


def total_params(cfg):
    return matmul_params(cfg) + embedding_params(cfg)


def attention_matmul_flops(cfg, batch, seq, causal=True):
    """One S x S x head_dim product over all heads of ONE layer: 2*S*S*d*nh a
    sequence, half of it under a causal mask."""
    full = 2.0 * seq * seq * head_dim(cfg) * cfg["num_attention_heads"] * batch
    return full / 2 if causal else full


def flash_attention_flops(cfg, batch, seq, causal=True):
    """(forward, backward) of one layer: the forward is two such products
    (QK^T, PV), the backward five (QK^T again, dP, dV, dK, dQ)."""
    unit = attention_matmul_flops(cfg, batch, seq, causal)
    return 2 * unit, 5 * unit


def flash_attention_bytes(cfg, batch, seq, itemsize=2):
    """(forward, backward) bytes of one layer: the forward reads Q, K, V and
    writes O; the backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    q = batch * seq * cfg["num_attention_heads"] * head_dim(cfg) * itemsize
    kv = batch * seq * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize
    return 2 * q + 2 * kv, 4 * q + 4 * kv


def train_step_flops(cfg, batch, seq):
    """Forward and backward of one step: 6 per matmul parameter and token,
    plus causal attention (3 x its forward) in every layer.  Recomputation
    is not counted."""
    fwd, _ = flash_attention_flops(cfg, batch, seq)
    return (6.0 * matmul_params(cfg) * batch * seq
            + 3.0 * fwd * cfg["num_hidden_layers"])


def fused_adamw_bytes(n_params, param_bytes, grad_bytes, moment_bytes):
    """AdamW reads the parameter, the gradient and both moments and writes
    the parameter and both moments."""
    return n_params * (2 * param_bytes + grad_bytes + 4 * moment_bytes)


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V rows of one token in ONE layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def serve_flops(cfg, processed_tokens, sampled_tokens, attended_tokens):
    """2 per block parameter and processed token (prefill or decode), the
    head for each sampled token, and 4*d*nh per (token, attended token) pair
    in every layer."""
    layers = cfg["num_hidden_layers"]
    return (2.0 * layers * layer_params(cfg) * processed_tokens
            + 2.0 * head_params(cfg) * sampled_tokens
            + 4.0 * head_dim(cfg) * cfg["num_attention_heads"] * layers
            * attended_tokens)


def serve_step_bytes(cfg, live_tokens, weight_itemsize=2, kv_itemsize=2):
    """What one scan step (decode or admit) must move: every block's weights
    and the head once, and the live K and V rows of every slot once."""
    weights = (cfg["num_hidden_layers"] * layer_params(cfg)
               + head_params(cfg)) * weight_itemsize
    kv = (sum(live_tokens) * kv_bytes_per_token(cfg, kv_itemsize)
          * cfg["num_hidden_layers"])
    return weights + kv


def paged_attention_bytes(cfg, live_tokens, page_size, kv_itemsize=2):
    """One step of paged attention over all layers: the LIVE pages of every
    slot (whole pages, since a page is the unit that moves), read once."""
    pages = sum(-(-n // page_size) for n in live_tokens)
    return (pages * page_size * kv_bytes_per_token(cfg, kv_itemsize)
            * cfg["num_hidden_layers"])


def roofline_seconds(flops, nbytes, peaks, chips=1):
    """(least seconds the chips could take, which bound)."""
    compute = flops / (peaks["bf16_flops_per_s"] * chips)
    memory = nbytes / (peaks["hbm_bytes_per_s"] * chips)
    return (compute, "compute") if compute >= memory else (memory, "memory")
