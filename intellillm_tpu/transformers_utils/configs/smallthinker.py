"""SmallThinker config shim (`model_type` "smallthinker"; the installed
transformers does not know the family).

Source: https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json.
A stack of grouped-query attention layers of two kinds, told layer by
layer: `sliding_window_layout[l]` 1 = the layer attends to its last
`sliding_window_size` tokens, 0 = to its whole context;
`rope_layout[l]` 1 = RoPE on q and k, 0 = no position encoding at all
(published: one full-attention NoPE layer in four). Every layer has
`moe_num_primary_experts` ReGLU experts of `moe_ffn_hidden_size`, of which
`moe_num_active_primary_experts` are chosen by a router that reads the
layer's normalised INPUT (before attention), weighted by a softmax over
the chosen (`moe_primary_router_apply_softmax`, `norm_topk_prob`).
"""
from transformers import PretrainedConfig


class SmallThinkerConfig(PretrainedConfig):
    model_type = "smallthinker"

    def __init__(
        self,
        vocab_size=151936,
        hidden_size=2560,
        num_hidden_layers=52,
        num_attention_heads=28,
        num_key_value_heads=4,
        head_dim=128,
        moe_ffn_hidden_size=768,
        moe_num_primary_experts=64,
        moe_num_active_primary_experts=6,
        moe_primary_router_apply_softmax=True,
        norm_topk_prob=True,
        rope_layout=None,
        sliding_window_layout=None,
        sliding_window_size=4096,
        max_position_embeddings=16384,
        rms_norm_eps=1e-6,
        rope_theta=1500000.0,
        rope_scaling=None,
        tie_word_embeddings=False,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.moe_ffn_hidden_size = moe_ffn_hidden_size
        self.moe_num_primary_experts = moe_num_primary_experts
        self.moe_num_active_primary_experts = moe_num_active_primary_experts
        self.moe_primary_router_apply_softmax = \
            moe_primary_router_apply_softmax
        self.norm_topk_prob = norm_topk_prob
        # Published pattern: layer l is full-attention + NoPE iff l % 4 == 0.
        period = [0, 1, 1, 1]
        default = [period[l % 4] for l in range(num_hidden_layers)]
        self.rope_layout = list(rope_layout or default)
        self.sliding_window_layout = list(sliding_window_layout or default)
        for name in ("rope_layout", "sliding_window_layout"):
            if len(getattr(self, name)) < num_hidden_layers:
                raise ValueError(
                    f"{name} has {len(getattr(self, name))} entries for "
                    f"{num_hidden_layers} layers")
        self.sliding_window_size = sliding_window_size
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        super().__init__(tie_word_embeddings=tie_word_embeddings, **kwargs)
