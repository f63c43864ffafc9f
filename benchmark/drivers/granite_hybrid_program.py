"""The one place that knows how `paddle_tpu.models.llama` spells a hybrid
state-space configuration (`model_type` `granitemoehybrid`): it builds the
model a configuration file describes and puts the benchmark's weights
(`weights_granite_hybrid.py`) in."""
import weights_granite_hybrid as weights_mod

_PROGRAM_LEAF = {"input_norm": "input_layernorm.weight",
                 "post_norm": "post_attention_layernorm.weight",
                 "in_proj": "mamba.in_proj", "conv_weight": "mamba.conv_weight",
                 "conv_bias": "mamba.conv_bias", "dt_bias": "mamba.dt_bias",
                 "A_log": "mamba.A_log", "D": "mamba.D",
                 "mixer_norm": "mamba.norm", "out_proj": "mamba.out_proj",
                 "q_proj": "self_attn.q_proj", "k_proj": "self_attn.k_proj",
                 "v_proj": "self_attn.v_proj", "o_proj": "self_attn.o_proj",
                 "gate_proj": "mlp.gate_proj", "up_proj": "mlp.up_proj",
                 "down_proj": "mlp.down_proj"}


def program_name(name):
    """benchmark/weights_granite_hybrid.py's leaf name -> the model's state
    name."""
    if name == "embed":
        return "llama.embed_tokens"
    if name == "final_norm":
        return "llama.norm.weight"
    _, i, leaf = name.split(".")
    return f"llama.layers.{i}.{_PROGRAM_LEAF[leaf]}"


def llama_config(cfg, param_dtype):
    from paddle_tpu.models.llama import LlamaConfig
    if cfg["model_class"] != "paddle_tpu.models.llama" \
            or cfg.get("model_type") != "granitemoehybrid":
        raise ValueError(f"no builder for model class {cfg['model_class']!r} "
                         f"of type {cfg.get('model_type')!r}")
    n = cfg["num_hidden_layers"]
    kinds = tuple(cfg["layer_types"][:n])
    if set(kinds) - {"mamba", "attention"} or cfg["num_local_experts"] \
            or cfg["position_embedding_type"] != "nope" \
            or cfg["attention_bias"] or cfg["mamba_proj_bias"] \
            or cfg["mamba_n_groups"] != 1 or not cfg["mamba_conv_bias"] \
            or cfg["hidden_act"] != "silu" \
            or cfg["normalization_function"] != "rmsnorm" \
            or not cfg["tie_word_embeddings"] \
            or cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
            != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("the program's hybrid decoder is mamba and NoPE "
                         "attention layers without bias, one group of B "
                         "and C, a conv with a bias, a dense SwiGLU MLP and "
                         "a tied head")
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["shared_intermediate_size"],
        num_hidden_layers=n,
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=d,
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=True, dtype=cfg["torch_dtype"],
        param_dtype=None if param_dtype == cfg["torch_dtype"] else param_dtype,
        layer_types=tuple("mamba" if k == "mamba" else "full_attention"
                          for k in kinds),
        rope_layer_types=(),
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_state_dtype=cfg["mamba_state_dtype"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"])


def build_model(cfg, seed, param_dtype):
    """LlamaForCausalLM at the configuration's sizes, its state (parameters
    in `param_dtype`, dt_bias, A_log and D in float32) replaced by the
    benchmark's, leaf after leaf."""
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
