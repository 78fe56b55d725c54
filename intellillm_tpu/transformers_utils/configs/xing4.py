"""Xing4.0 config shim (`model_type` "xing4_0"; `AutoConfig` does not know it).

Source: https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
(29 B parameters, 4 B a token). The DeepSeek-V3 block (latent attention: a
token caches one vector of `kv_lora_rank + qk_rope_head_dim` values a layer;
sigmoid-scored routing over `n_routed_experts` experts chosen by score + a
per-expert bias, `topk_method` "noaux_tc", with one shared expert; the first
`first_k_dense_replace` layers dense) inside a residual path of `hc_mult`
streams mixed by manifold-constrained hyper-connections (`hc_sinkhorn_iters`
Sinkhorn sweeps with `hc_eps` in every denominator on logits clamped to
[`mhc_h_res_clamp_min`, `mhc_h_res_clamp_max`]: `layers/hyper_connections.py`).

`num_nextn_predict_layers` names a multi-token-prediction layer: a drafter
that reads the last hidden state and adds nothing to the model's own logits.
The engine neither builds nor loads it (`models/xing4.py`).

Expert parallelism as this engine reads it (`layers/moe.py`
`ExpertShare.from_config`): with `ep_size` > 1, `n_routed_experts` counts the
experts THIS chip holds and the router keeps its published width
`n_routed_experts * ep_size`.
"""
from transformers import PretrainedConfig


class Xing4Config(PretrainedConfig):
    model_type = "xing4_0"

    def __init__(
        self,
        vocab_size=131072,
        hidden_size=3584,
        intermediate_size=9216,
        moe_intermediate_size=1024,
        num_hidden_layers=40,
        num_attention_heads=32,
        num_key_value_heads=32,
        q_lora_rank=768,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        n_shared_experts=1,
        n_routed_experts=64,
        num_experts_per_tok=4,
        n_group=1,
        topk_group=1,
        topk_method="noaux_tc",
        scoring_func="sigmoid",
        norm_topk_prob=True,
        routed_scaling_factor=2.0,
        moe_layer_freq=1,
        first_k_dense_replace=2,
        num_nextn_predict_layers=1,
        hc_mult=4,
        hc_sinkhorn_iters=20,
        hc_eps=1e-6,
        mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30,
        ep_size=1,
        ep_rank=0,
        hidden_act="silu",
        max_position_embeddings=262144,
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
        # A query / key head as attention scores it (the cache holds no
        # heads at all: `ModelConfig.get_cache_row_shape`).
        self.head_dim = qk_nope_head_dim + qk_rope_head_dim
        # Not a published key: how THIS engine lays the cache out. The
        # forty layers' vectors share one pool, so that `models/xing4.py`
        # can walk the layers in a `lax.scan` (one compiled layer, not
        # forty: `ModelConfig.get_packed_latent_layers`).
        self.latent_layers_in_one_pool = True
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
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.hc_mult = hc_mult
        self.hc_sinkhorn_iters = hc_sinkhorn_iters
        self.hc_eps = hc_eps
        self.mhc_h_res_clamp_min = mhc_h_res_clamp_min
        self.mhc_h_res_clamp_max = mhc_h_res_clamp_max
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
