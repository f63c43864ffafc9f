"""The one place that knows `paddle_tpu.models.llama`'s names: it builds the
model a configuration file describes and puts the benchmark's weights in."""
import weights as weights_mod

_PROGRAM_LEAF = {"input_norm": "input_layernorm.weight",
                 "post_norm": "post_attention_layernorm.weight",
                 "q_proj": "self_attn.q_proj", "k_proj": "self_attn.k_proj",
                 "v_proj": "self_attn.v_proj", "o_proj": "self_attn.o_proj",
                 "gate_proj": "mlp.gate_proj", "up_proj": "mlp.up_proj",
                 "down_proj": "mlp.down_proj"}


def program_name(name):
    """benchmark/weights.py's leaf name -> the model's parameter name."""
    if name == "embed":
        return "llama.embed_tokens"
    if name == "final_norm":
        return "llama.norm.weight"
    if name == "lm_head":
        return "lm_head"
    _, i, leaf = name.split(".")
    return f"llama.layers.{i}.{_PROGRAM_LEAF[leaf]}"


def build_model(cfg, seed, param_dtype):
    """LlamaForCausalLM at the configuration's sizes, bf16 compute, its
    parameters (held in `param_dtype`) replaced by the benchmark's."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    if cfg["model_class"] != "paddle_tpu.models.llama":
        raise ValueError(f"no builder for model class {cfg['model_class']!r}")
    lc = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=None if param_dtype == cfg["torch_dtype"] else param_dtype)
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = LlamaForCausalLM(lc)
    params = dict(model.named_parameters())
    held = {n: (p.value.shape, p.value.dtype) for n, p in params.items()}
    for p in params.values():
        p._value = None      # the constructor's own draw goes before ours
    #                          comes: the two never stand on the chip together
    made = weights_mod.make_weights(seed, cfg, param_dtype)
    for name, value in made.items():
        p = params.pop(program_name(name))
        if held[program_name(name)] != (value.shape, value.dtype):
            raise ValueError(f"{name}: the model holds "
                             f"{held[program_name(name)]}, the benchmark "
                             f"made {value.shape} {value.dtype}")
        p._value = value
    if params:
        raise ValueError(f"the model has parameters the benchmark does not "
                         f"make: {sorted(params)}")
    return model
