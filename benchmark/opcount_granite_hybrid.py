"""Operations and bytes that the hybrid state-space decoder (`model_type`
`granitemoehybrid`) NEEDS on the serving path, from the configuration
file's shapes.  As in `opcount.py`: what the mathematics requires, not what
an implementation executes, so padded lanes and dead pages count for
nothing; a slot's state is needed once a step, read and written, whatever
the slot holds (the recurrence reads it for its valid lanes, and a step
runs every slot).
"""
import weights_granite_hybrid as weights_mod

ITEMSIZE = {"float32": 4, "bfloat16": 2}
# operations a (lane, state element) of the recurrence needs: the decay's
# product, the input's product and sum, the output's product and sum
SCAN_OPS_PER_ELEMENT = 5


def layers_of(cfg):
    """(mamba layers, attention layers)."""
    n = cfg["num_hidden_layers"]
    mamba = sum(k == "mamba" for k in cfg["layer_types"][:n])
    return mamba, n - mamba


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mamba_params(cfg):
    """The weights of one mamba mixer a token is multiplied with: the in-
    and out-projections (the conv's taps, dt, A, D and the norm are vectors
    and left out)."""
    inner, conv, heads = weights_mod.mamba_widths(cfg)
    return cfg["hidden_size"] * (inner + conv + heads) \
        + inner * cfg["hidden_size"]


def attention_params(cfg):
    h, d = cfg["hidden_size"], head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return h * nq + 2 * h * nkv + nq * h


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def every_token_params(cfg):
    mamba, attn = layers_of(cfg)
    return mamba * mamba_params(cfg) + attn * attention_params(cfg) \
        + cfg["num_hidden_layers"] * mlp_params(cfg)


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def state_elements(cfg):
    """Elements of one slot's SSM state in one mamba layer: H x P x N."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]


def conv_state_bytes(cfg, itemsize=2):
    """One slot's conv window in one mamba layer."""
    _, conv, _ = weights_mod.mamba_widths(cfg)
    return (cfg["mamba_d_conv"] - 1) * conv * itemsize


def state_bytes(cfg, slots):
    """The states of `slots` slots in every mamba layer: SSM and conv."""
    mamba, _ = layers_of(cfg)
    return slots * mamba * (
        state_elements(cfg) * ITEMSIZE[cfg["mamba_state_dtype"]]
        + conv_state_bytes(cfg))


def kv_row_bytes(cfg, itemsize=2):
    """K and V rows of one token in ONE attention layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize


def scan_flops(cfg, lanes):
    """The recurrence of `lanes` valid lanes in ONE mamba layer."""
    return SCAN_OPS_PER_ELEMENT * state_elements(cfg) * lanes


def scan_lane_bytes(cfg, itemsize=2):
    """What the scan must read and write for one valid lane in ONE mamba
    layer beside the state: x, B, C in the serving dtype, dt in float32,
    and y in float32."""
    inner, _, heads = weights_mod.mamba_widths(cfg)
    n = cfg["mamba_d_state"]
    return (inner + 2 * n) * itemsize + heads * 4 + inner * 4


def attention_flops_per_pair(cfg):
    """One (query token, attended row) pair in one attention layer."""
    return 4.0 * head_dim(cfg) * cfg["num_attention_heads"]


def attended_row_steps(chunk_steps):
    """Rows one lane a slot attends, summed over [(scan steps, live tokens
    of each slot)] (metrics._common.serve_chunk_steps)."""
    return sum(steps * sum(live) for steps, live in chunk_steps)


def serve_flops(cfg, processed_tokens, sampled_tokens, attended_rows):
    """2 per every-token parameter and processed valid token, the head per
    sampled token, the recurrence per processed token in every mamba layer,
    attention per (token, attended row) pair in every attention layer."""
    mamba, attn = layers_of(cfg)
    return (2.0 * every_token_params(cfg) * processed_tokens
            + 2.0 * head_params(cfg) * sampled_tokens
            + mamba * scan_flops(cfg, processed_tokens)
            + attn * attention_flops_per_pair(cfg) * attended_rows)


def serve_bytes(cfg, steps, slots, attended_rows, weight_itemsize=2,
                kv_itemsize=2):
    """What `steps` scan steps must move: the weights and the head once a
    step, every slot's state read and written once a step, and the K and V
    rows the lanes attend (`attended_rows`, summed over the steps)."""
    _, attn = layers_of(cfg)
    per_step = (every_token_params(cfg) + head_params(cfg)) \
        * weight_itemsize + 2 * state_bytes(cfg, slots)
    return steps * per_step \
        + attn * attended_rows * kv_row_bytes(cfg, kv_itemsize)
