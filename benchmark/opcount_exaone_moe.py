"""Operations and bytes that a window-and-full-attention sparse-expert
decoder (`model_type` `exaone_moe`) NEEDS on the serving path, from the
configuration file's shapes and the program's own routing counts.  As in
`opcount.py`: what the mathematics requires of THIS chip's share, not what
an implementation executes, so padded lanes, absent experts' rows, dead
pages and rows outside a sliding layer's window count for nothing.
"""


def attention_params(cfg):
    """Wq, Wk, Wv, Wo of one layer (the kinds have the same four)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * nq + 2 * h * nkv + nq * h


def expert_params(cfg):
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return cfg["num_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    return cfg["hidden_size"] * cfg["router_width"]


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layers_of(cfg):
    """(sliding layers, full layers, dense-MLP layers, expert layers)."""
    n = cfg["num_hidden_layers"]
    sliding = sum(k == "sliding_attention" for k in cfg["layer_types"][:n])
    dense = sum(k == "dense" for k in cfg["mlp_layer_types"][:n])
    return sliding, n - sliding, dense, n - dense


def every_token_params(cfg):
    """Parameters every processed token is multiplied with, all layers:
    attention everywhere, the dense MLP in the dense layers, router and
    shared expert in the expert layers (the head and the routed experts
    are counted by what reaches them)."""
    _, _, dense, sparse = layers_of(cfg)
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * dense_mlp_params(cfg)
            + sparse * (router_params(cfg) + shared_params(cfg)))


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg):
    """Every parameter this chip holds (norms left out): embedding, layers
    with the experts HELD here, head."""
    _, _, _, sparse = layers_of(cfg)
    return (2 * head_params(cfg) + every_token_params(cfg)
            + sparse * cfg["num_experts"] * expert_params(cfg))


def kv_row_bytes(cfg, itemsize=2):
    """K and V rows of one token in ONE layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def attended_rows(cfg, live_tokens, lanes=1):
    """(rows a full layer's lanes attend, rows a sliding layer's do) summed
    over slots holding `live_tokens` rows each: the whole depth, or the
    window's rows and the lanes' own (window + lanes - 1) where the slot is
    deeper than that."""
    cap = cfg["sliding_window"] + lanes - 1
    return sum(live_tokens), sum(min(n, cap) for n in live_tokens)


def attended_row_steps(cfg, chunk_steps):
    """attended_rows of one lane a slot, summed over [(scan steps, live
    tokens of each slot)] (metrics._common.serve_chunk_steps)."""
    full = window = 0
    for steps, live in chunk_steps:
        rows = attended_rows(cfg, live)
        full, window = full + steps * rows[0], window + steps * rows[1]
    return full, window


def attention_flops_per_pair(cfg):
    """One (query token, attended row) pair in one layer: every head's score
    over d and its sum over d, 2 operations a product."""
    return 4.0 * cfg["head_dim"] * cfg["num_attention_heads"]


def serve_flops(cfg, processed_tokens, sampled_tokens, attended_full,
                attended_window, assignments_held):
    """2 per every-token parameter and processed valid token; 2 per expert
    parameter and assignment that landed on an expert held here; the head
    per sampled token; attention per (token, attended row) pair, a full
    layer's over the whole depth, a sliding layer's capped at its window."""
    sliding, full, _, _ = layers_of(cfg)
    return (2.0 * every_token_params(cfg) * processed_tokens
            + 2.0 * expert_params(cfg) * assignments_held
            + 2.0 * head_params(cfg) * sampled_tokens
            + attention_flops_per_pair(cfg)
            * (full * attended_full + sliding * attended_window))


def attention_bytes(cfg, rows_full, rows_window, kv_itemsize=2):
    """K and V rows the attention of all layers must read once: `rows_full`
    in every full layer, `rows_window` in every sliding one."""
    sliding, full, _, _ = layers_of(cfg)
    return (full * rows_full + sliding * rows_window) \
        * kv_row_bytes(cfg, kv_itemsize)


def serve_bytes(cfg, steps, rows_full, rows_window, expert_steps_hit,
                weight_itemsize=2, kv_itemsize=2):
    """What `steps` scan steps must move: the every-token weights and the
    head once a step, one expert's weights for every (layer, step, held
    expert) that got a token, and the K and V rows the lanes attend
    (`rows_full`, `rows_window`: summed over the steps, attended_rows)."""
    per_step = (every_token_params(cfg) + head_params(cfg)) * weight_itemsize
    return (steps * per_step
            + expert_steps_hit * expert_params(cfg) * weight_itemsize
            + attention_bytes(cfg, rows_full, rows_window, kv_itemsize))
