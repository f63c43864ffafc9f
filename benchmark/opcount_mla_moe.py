"""Operations and bytes that a latent-attention, sparse-expert decoder
(`model_type` `sarvam_mla`) NEEDS on the serving path, from the
configuration file's shapes and the program's own routing counts.  As in
`opcount.py`: what the mathematics requires of THIS chip's share, not what
an implementation executes, so padded lanes, absent experts' rows and dead
pages count for nothing.
"""


def attention_params(cfg):
    """Wq, Wkv_a, Wkv_b, Wo of one layer."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (h * nh * (nope + rope) + h * (rank + rope)
            + rank * nh * (nope + vd) + nh * vd * h)


def expert_params(cfg):
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return cfg["num_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    return cfg["hidden_size"] * cfg["router_width"]


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def every_token_params(cfg):
    """Parameters every processed token is multiplied with, all layers:
    attention everywhere, the dense MLP in the leading layers, router and
    shared expert in the expert layers (the head and the routed experts
    are counted by what reaches them)."""
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + cfg["first_k_dense_replace"] * dense_mlp_params(cfg)
            + expert_layers(cfg) * (router_params(cfg) + shared_params(cfg)))


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def latent_row_bytes(cfg, itemsize=2):
    """One token's cached row in ONE layer: [c | k_r]."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def absorbed_attention_flops_per_pair(cfg):
    """One (query token, attended token) pair in one layer, absorbed: every
    head's score over the row (rank + rope) and its sum over the latent
    (rank), 2 operations a product."""
    return 2.0 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def serve_flops(cfg, processed_tokens, sampled_tokens, attended_tokens,
                assignments_held):
    """2 per every-token parameter and processed valid token; 2 per expert
    parameter and assignment that landed on an expert held here; the head
    per sampled token; absorbed attention per (token, attended token) pair
    in every layer."""
    return (2.0 * every_token_params(cfg) * processed_tokens
            + 2.0 * expert_params(cfg) * assignments_held
            + 2.0 * head_params(cfg) * sampled_tokens
            + absorbed_attention_flops_per_pair(cfg)
            * cfg["num_hidden_layers"] * attended_tokens)


def serve_bytes(cfg, steps, live_token_steps, expert_steps_hit,
                weight_itemsize=2, kv_itemsize=2):
    """What `steps` scan steps must move: the every-token weights and the
    head once a step, one expert's weights for every (layer, step, held
    expert) that got a token, and every live latent row once a step in
    every layer (`live_token_steps`: live tokens summed over the steps)."""
    per_step = (every_token_params(cfg) + head_params(cfg)) * weight_itemsize
    return (steps * per_step
            + expert_steps_hit * expert_params(cfg) * weight_itemsize
            + live_token_steps * latent_row_bytes(cfg, kv_itemsize)
            * cfg["num_hidden_layers"])
