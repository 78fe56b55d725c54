"""A.X-K1 config shim (`model_type` "axk1"; `AutoConfig` does not know it).

Source: https://huggingface.co/skt/A.X-K1/blob/main/config.json. Latent
attention (a token caches one vector of `kv_lora_rank + qk_rope_head_dim`
values a layer) and sigmoid-scored, group-limited routing over
`n_routed_experts` experts with one shared expert; the first
`first_k_dense_replace` layers are dense.

Expert parallelism as this engine reads it: `ep_size` chips share each
layer's experts. With `ep_size` > 1, `n_routed_experts` counts the experts
THIS chip holds (rank `ep_rank` holds `[ep_rank * n, (ep_rank + 1) * n)`),
and the router keeps its published width `n_routed_experts * ep_size`.
"""
from transformers import PretrainedConfig


class AXK1Config(PretrainedConfig):
    model_type = "axk1"

    def __init__(
        self,
        vocab_size=163840,
        hidden_size=7168,
        intermediate_size=18432,
        moe_intermediate_size=2048,
        num_hidden_layers=61,
        num_attention_heads=64,
        num_key_value_heads=64,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_shared_experts=1,
        n_routed_experts=192,
        num_experts_per_tok=8,
        n_group=8,
        topk_group=4,
        topk_method="none",
        scoring_func="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        moe_layer_freq=1,
        first_k_dense_replace=1,
        ep_size=1,
        ep_rank=0,
        hidden_act="silu",
        max_position_embeddings=131072,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        rope_scaling=None,
        attention_bias=False,
        tie_word_embeddings=False,
        pad_token_id=None,
        bos_token_id=None,
        eos_token_id=None,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        # A query / key head as attention scores it (the value head is
        # `v_head_dim`); the cache holds no heads at all
        # (`ModelConfig.get_cache_row_shape`).
        self.head_dim = qk_nope_head_dim + qk_rope_head_dim
        self.n_shared_experts = n_shared_experts
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.topk_method = topk_method
        self.scoring_func = scoring_func
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.moe_layer_freq = moe_layer_freq
        self.first_k_dense_replace = first_k_dense_replace
        self.ep_size = ep_size
        self.ep_rank = ep_rank
        self.hidden_act = hidden_act
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.attention_bias = attention_bias
        super().__init__(pad_token_id=pad_token_id,
                         bos_token_id=bos_token_id,
                         eos_token_id=eos_token_id,
                         tie_word_embeddings=tie_word_embeddings, **kwargs)
