"""LFM2-MoE config shim (`model_type` "lfm2_moe"; the installed
transformers knows the dense family `lfm2` only).

Source: https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json.
A stack of gated short-convolution layers (`layer_types` "conv": a 3-tap
depthwise causal filter over a gated projection, `conv_L_cache` taps)
with a grouped-query attention layer every few (`"full_attention"`), the
first `num_dense_layers` with a dense SwiGLU and the rest with
`num_experts` sigmoid-scored experts of which `num_experts_per_tok` are
chosen by score + a per-expert bias (`use_expert_bias`) and weighted by
the score alone.

What the published file leaves to the family's defaults is set here:
`head_dim` = hidden / heads, `tie_word_embeddings` true.
`kv_cache_head_width` is this engine's own: the width a K/V head is
stored at, the head padded to whole 128-lane tiles
(`ModelConfig.get_cache_row_shape`).
"""
from transformers import PretrainedConfig


class Lfm2MoeConfig(PretrainedConfig):
    model_type = "lfm2_moe"

    def __init__(
        self,
        vocab_size=65536,
        hidden_size=2048,
        intermediate_size=7168,
        moe_intermediate_size=1792,
        num_hidden_layers=24,
        num_attention_heads=32,
        num_key_value_heads=8,
        head_dim=None,
        layer_types=None,
        conv_L_cache=3,
        conv_bias=False,
        num_dense_layers=2,
        num_experts=32,
        num_experts_per_tok=4,
        norm_topk_prob=True,
        use_expert_bias=True,
        routed_scaling_factor=1.0,
        max_position_embeddings=128000,
        norm_eps=1e-5,
        rope_theta=1000000.0,
        rope_parameters=None,
        tie_word_embeddings=True,
        pad_token_id=0,
        bos_token_id=1,
        eos_token_id=2,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        if layer_types is None:
            layer_types = ["full_attention"] * num_hidden_layers
        if len(layer_types) < num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(layer_types)} layers, "
                f"num_hidden_layers is {num_hidden_layers}")
        # A cut in depth keeps the first layers as published.
        self.layer_types = list(layer_types[:num_hidden_layers])
        self.conv_L_cache = conv_L_cache
        self.conv_bias = conv_bias
        self.num_dense_layers = num_dense_layers
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.use_expert_bias = use_expert_bias
        self.routed_scaling_factor = routed_scaling_factor
        self.max_position_embeddings = max_position_embeddings
        self.norm_eps = norm_eps
        if rope_parameters:         # the newer spelling of the same key
            rope_theta = rope_parameters.get("rope_theta", rope_theta)
        self.rope_theta = rope_theta
        self.kv_cache_head_width = -(-self.head_dim // 128) * 128
        super().__init__(pad_token_id=pad_token_id,
                         bos_token_id=bos_token_id,
                         eos_token_id=eos_token_id,
                         tie_word_embeddings=tie_word_embeddings, **kwargs)
