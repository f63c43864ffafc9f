"""Operations and bytes that serving a block-diffusion sparse-expert decoder
(`model_type` `sdar_moe`) NEEDS, from the configuration file's shapes and the
program's own counts of the PUBLISHED schedule's work (lanes processed,
assignments, denoise and commit passes).  As in `opcount.py`: what the
mathematics of that schedule requires, not what an implementation executes,
so padded lanes and dead pages count for nothing, and the counts do not
change with what implements the schedule (a fused commit + denoise pass
would process the same lanes in fewer steps, and read higher).
"""
import opcount


def attention_params(cfg):
    """Wq, Wk, Wv, Wo of one layer (grouped-query, explicit head size)."""
    h, d = cfg["hidden_size"], opcount.head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def expert_params(cfg):
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts"]


def every_token_params(cfg):
    """Parameters every processed lane is multiplied with, all layers:
    attention and the router (the head and the experts are counted by what
    reaches them)."""
    return cfg["num_hidden_layers"] * (attention_params(cfg)
                                       + router_params(cfg))


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops_per_pair(cfg):
    """One (query lane, attended row) pair in one layer: every query head's
    score and its weighted value, 2 operations a product."""
    return 4.0 * opcount.head_dim(cfg) * cfg["num_attention_heads"]


def serve_flops(cfg, processed_lanes, head_lanes, attended_pairs,
                assignments):
    """2 per every-token parameter and valid lane processed (prompt tokens
    prefilled and the L lanes of every denoise or commit pass); 2 per expert
    parameter and assignment; the head per block lane of a decode pass;
    attention per (lane, attended row) pair in every layer."""
    return (2.0 * every_token_params(cfg) * processed_lanes
            + 2.0 * expert_params(cfg) * assignments
            + 2.0 * head_params(cfg) * head_lanes
            + attention_flops_per_pair(cfg) * cfg["num_hidden_layers"]
            * attended_pairs)


def serve_bytes(cfg, steps, live_token_steps, expert_steps_hit,
                weight_itemsize=2, kv_itemsize=2):
    """What `steps` scan steps must move: the every-token weights and the
    head once a step, one expert's weights for every (layer, step, expert)
    that got a lane, and every live K and V row once a step in every layer
    (`live_token_steps`: live tokens summed over the steps)."""
    per_step = (every_token_params(cfg) + head_params(cfg)) * weight_itemsize
    return (steps * per_step
            + expert_steps_hit * expert_params(cfg) * weight_itemsize
            + live_token_steps * opcount.kv_bytes_per_token(cfg, kv_itemsize)
            * cfg["num_hidden_layers"])
