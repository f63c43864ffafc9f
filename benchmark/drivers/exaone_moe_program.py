"""The one place that knows how `paddle_tpu.models.llama` spells a
window-and-full-attention sparse-expert configuration (`model_type`
`exaone_moe`): it builds the model a configuration file describes and puts
the benchmark's weights (`weights_exaone_moe.py`) in."""
import weights_exaone_moe as weights_mod

_PROGRAM_LEAF = {"input_norm": "input_layernorm.weight",
                 "post_norm": "post_attention_layernorm.weight",
                 "q_proj": "self_attn.q_proj", "k_proj": "self_attn.k_proj",
                 "v_proj": "self_attn.v_proj", "o_proj": "self_attn.o_proj",
                 "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
                 "gate_proj": "mlp.gate_proj", "up_proj": "mlp.up_proj",
                 "down_proj": "mlp.down_proj",
                 "router": "mlp.gate.weight", "router_bias": "mlp.gate.bias",
                 "experts_w1": "mlp.w1", "experts_w2": "mlp.w2",
                 "shared_w1": "mlp.shared_w1", "shared_w2": "mlp.shared_w2"}


def program_name(name):
    """benchmark/weights_exaone_moe.py's leaf name -> the model's state
    name."""
    if name == "embed":
        return "llama.embed_tokens"
    if name == "final_norm":
        return "llama.norm.weight"
    if name == "lm_head":
        return "lm_head"
    _, i, leaf = name.split(".")
    return f"llama.layers.{i}.{_PROGRAM_LEAF[leaf]}"


def llama_config(cfg, param_dtype):
    from paddle_tpu.models.llama import LlamaConfig
    if cfg["model_class"] != "paddle_tpu.models.llama" \
            or cfg.get("model_type") != "exaone_moe":
        raise ValueError(f"no builder for model class {cfg['model_class']!r} "
                         f"of type {cfg.get('model_type')!r}")
    n = cfg["num_hidden_layers"]
    first, end = cfg["experts_held"]
    if end - first != cfg["num_experts"]:
        raise ValueError("experts_held does not name num_experts experts")
    mlps = list(cfg["mlp_layer_types"][:n])
    dense = cfg["first_k_dense_replace"]
    if mlps != ["dense"] * dense + ["sparse"] * (n - dense):
        raise ValueError("the program's dense layers are the leading "
                         "first_k_dense_replace ones")
    kinds = tuple(cfg["layer_types"][:n])
    if any((w != 0) != (k == "sliding_attention") or w not in
           (0, cfg["sliding_window"])
           for k, w in zip(kinds, cfg["sliding_windows"][:n])):
        raise ValueError("sliding_windows and layer_types disagree")
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"] \
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["num_nextn_predict_layers"] \
            or cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError("the program's sigmoid router renormalises the "
                         "chosen weights over ONE routing group, the rotary "
                         "is plain, and no multi-token-prediction module "
                         "is built")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"], num_hidden_layers=n,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], use_qk_norm=True,
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=None if param_dtype == cfg["torch_dtype"] else param_dtype,
        layer_types=kinds, sliding_window=cfg["sliding_window"],
        rope_layer_types=("sliding_attention",),
        first_k_dense_replace=dense,
        moe_gate="sigmoid", moe_num_experts=cfg["num_experts"],
        moe_first_expert=first, moe_router_width=cfg["router_width"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_experts=cfg["num_shared_experts"],
        moe_routed_scaling=cfg["routed_scaling_factor"],
        moe_router_bias=True)


def build_model(cfg, seed, param_dtype):
    """LlamaForCausalLM at the configuration's sizes and share, its state
    (parameters in `param_dtype`, the router's selection bias in float32)
    replaced by the benchmark's, leaf after leaf."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = LlamaForCausalLM(llama_config(cfg, param_dtype))
    state = dict(model.state_dict())
    held = {n: (t.value.shape, t.value.dtype) for n, t in state.items()}
    for t in state.values():
        t._value = None      # the constructor's own draw goes before ours
    #                          comes: the two never stand on the chip together
    for name, value in weights_mod.leaves(seed, cfg, param_dtype):
        t = state.pop(program_name(name))
        if held[program_name(name)] != (value.shape, value.dtype):
            raise ValueError(f"{name}: the model holds "
                             f"{held[program_name(name)]}, the benchmark "
                             f"made {value.shape} {value.dtype}")
        t._value = value
    if state:
        raise ValueError(f"the model has state the benchmark does not "
                         f"make: {sorted(state)}")
    return model
