"""The one place that knows how `paddle_tpu.models.llama` spells a
block-diffusion sparse-expert configuration (`model_type` `sdar_moe`): it
builds the model a configuration file describes and puts the benchmark's
weights (`weights_sdar_moe.py`) in."""
import weights_sdar_moe as weights_mod

_PROGRAM_LEAF = {"input_norm": "input_layernorm.weight",
                 "post_norm": "post_attention_layernorm.weight",
                 "q_proj": "self_attn.q_proj", "k_proj": "self_attn.k_proj",
                 "v_proj": "self_attn.v_proj", "o_proj": "self_attn.o_proj",
                 "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
                 "router": "mlp.gate.weight",
                 "experts_w1": "mlp.w1", "experts_w2": "mlp.w2"}


def program_name(name):
    """benchmark/weights_sdar_moe.py's leaf name -> the model's state name."""
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
            or cfg.get("model_type") != "sdar_moe":
        raise ValueError(f"no builder for model class {cfg['model_class']!r} "
                         f"of type {cfg.get('model_type')!r}")
    if not cfg["norm_topk_prob"] or cfg["decoder_sparse_step"] != 1 \
            or cfg["mlp_only_layers"] or cfg["use_sliding_window"] \
            or cfg["attention_bias"] or cfg["rope_scaling"] \
            or cfg["remasking_strategy"] != "low_confidence_dynamic" \
            or cfg["sampling"] != "greedy":
        raise ValueError("the program's softmax router renormalises the "
                         "chosen weights, every layer is an expert layer "
                         "with full attention, no bias and plain rotary, "
                         "and the schedule is greedy low_confidence_dynamic")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], use_qk_norm=True,
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=None if param_dtype == cfg["torch_dtype"] else param_dtype,
        moe_gate="naive", moe_num_experts=cfg["num_experts"],
        moe_top_k=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_expert_bias=False,
        block_length=cfg["block_length"],
        denoising_steps=cfg["denoising_steps"],
        mask_token_id=cfg["mask_token_id"],
        confidence_threshold=cfg["confidence_threshold"])


def build_model(cfg, seed, param_dtype):
    """LlamaForCausalLM at the configuration's sizes, its parameters (held
    in `param_dtype`) replaced by the benchmark's, leaf after leaf."""
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
