"""The one place that knows how `paddle_tpu.models.llama` spells a
latent-attention, sparse-expert configuration (`model_type` `sarvam_mla`):
it builds the model a configuration file describes and puts the benchmark's
weights (`weights_mla_moe.py`) in."""
import weights_mla_moe as weights_mod

_PROGRAM_LEAF = {"input_norm": "input_layernorm.weight",
                 "post_norm": "post_attention_layernorm.weight",
                 "q_proj": "self_attn.q_proj", "q_norm": "self_attn.q_norm",
                 "kv_a_proj": "self_attn.kv_a_proj",
                 "kv_norm": "self_attn.kv_norm",
                 "kv_b_proj": "self_attn.kv_b_proj",
                 "o_proj": "self_attn.o_proj",
                 "gate_proj": "mlp.gate_proj", "up_proj": "mlp.up_proj",
                 "down_proj": "mlp.down_proj",
                 "router": "mlp.gate.weight", "router_bias": "mlp.gate.bias",
                 "experts_w1": "mlp.w1", "experts_w2": "mlp.w2",
                 "shared_w1": "mlp.shared_w1", "shared_w2": "mlp.shared_w2"}


def program_name(name):
    """benchmark/weights_mla_moe.py's leaf name -> the model's state name."""
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
            or cfg.get("model_type") != "sarvam_mla":
        raise ValueError(f"no builder for model class {cfg['model_class']!r} "
                         f"of type {cfg.get('model_type')!r}")
    first, end = cfg["experts_held"]
    if end - first != cfg["num_experts"]:
        raise ValueError("experts_held does not name num_experts experts")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_attention_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=None if param_dtype == cfg["torch_dtype"] else param_dtype,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], use_qk_norm=cfg["use_qk_norm"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        moe_gate="sigmoid", moe_num_experts=cfg["num_experts"],
        moe_first_expert=first, moe_router_width=cfg["router_width"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_experts=cfg["num_shared_experts"],
        moe_routed_scaling=cfg["routed_scaling_factor"],
        moe_router_bias=cfg["moe_router_enable_expert_bias"])


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
