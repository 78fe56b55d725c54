"""Ling 3.0 config shim (`model_type` "bailing_hybrid"; `AutoConfig` does not
know it).

Source: https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json.
Layers come in groups of `layer_group_size`: the last layer of a group is
latent attention (MLA: a token caches one vector of `kv_lora_rank +
qk_rope_head_dim` values), the others Kimi Delta Attention (a float32
matrix a head and the three taps of its q, k, v filters, one slot a
sequence). The first `first_k_dense_replace` layers' FFN is a SwiGLU of
`intermediate_size`; the others route `num_experts_per_tok` of
`num_experts` sigmoid-scored experts in `n_group` groups (a group's score
the sum of its two largest, `topk_group` groups kept) beside
`num_shared_experts` shared.

The class describes its caches itself (`cache_description`, asked by
`ModelConfig._cache_description`): "state" entries beside "kv" entries
whose rows are LATENT rows (`ModelConfig.get_latent_cache_width` reads
`kv_lora_rank` and `qk_rope_head_dim`, as for every latent model).

Expert parallelism as this engine reads it (as
`transformers_utils/configs/axk1.py`): with `ep_size` > 1, `num_experts`
counts the experts THIS chip holds (rank `ep_rank` holds `[ep_rank * n,
(ep_rank + 1) * n)`), and the router keeps its published width
`num_experts * ep_size`.
"""
from transformers import PretrainedConfig


class BailingHybridConfig(PretrainedConfig):
    model_type = "bailing_hybrid"

    def __init__(
        self,
        vocab_size=157184,
        hidden_size=2560,
        intermediate_size=6144,
        moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768,
        num_hidden_layers=42,
        layer_group_size=6,
        first_k_dense_replace=2,
        num_attention_heads=32,
        num_key_value_heads=32,
        head_dim=128,
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=6000000,
        rope_scaling=None,
        rope_interleave=True,
        use_mla_nope=False,
        gated_attention_proj_granularity_type="head_wise",
        short_conv_kernel_size=4,
        linear_silu=True,
        kda_safe_gate=True,
        kda_lower_bound=-5,
        no_kda_lora=True,
        num_kv_heads_for_linear_attn=0,
        group_norm_size=1,
        use_qk_norm=True,
        num_experts=512,
        num_shared_experts=1,
        num_experts_per_tok=8,
        n_group=8,
        topk_group=4,
        score_function="sigmoid",
        moe_router_enable_expert_bias=True,
        topk_method="noaux_tc",
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        expert_swiglu_limit_list=None,
        share_expert_swiglu_limit_list=None,
        num_nextn_predict_layers=1,
        ep_size=1,
        ep_rank=0,
        hidden_act="silu",
        max_position_embeddings=262144,
        rms_norm_eps=1e-6,
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
        self.moe_shared_expert_intermediate_size = (
            moe_shared_expert_intermediate_size)
        self.num_hidden_layers = num_hidden_layers
        self.layer_group_size = layer_group_size
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.head_dim = head_dim
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.rope_interleave = rope_interleave
        self.use_mla_nope = use_mla_nope
        self.gated_attention_proj_granularity_type = (
            gated_attention_proj_granularity_type)
        self.short_conv_kernel_size = short_conv_kernel_size
        self.linear_silu = linear_silu
        self.kda_safe_gate = kda_safe_gate
        self.kda_lower_bound = kda_lower_bound
        self.no_kda_lora = no_kda_lora
        self.num_kv_heads_for_linear_attn = num_kv_heads_for_linear_attn
        self.group_norm_size = group_norm_size
        self.use_qk_norm = use_qk_norm
        self.num_experts = num_experts
        self.num_shared_experts = num_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.score_function = score_function
        self.moe_router_enable_expert_bias = moe_router_enable_expert_bias
        self.topk_method = topk_method
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.expert_swiglu_limit_list = list(
            expert_swiglu_limit_list or [0] * num_hidden_layers)
        self.share_expert_swiglu_limit_list = list(
            share_expert_swiglu_limit_list or [0] * num_hidden_layers)
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.ep_size = ep_size
        self.ep_rank = ep_rank
        self.hidden_act = hidden_act
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        super().__init__(pad_token_id=pad_token_id,
                         bos_token_id=bos_token_id,
                         eos_token_id=eos_token_id,
                         tie_word_embeddings=tie_word_embeddings, **kwargs)

    # --- the layout ----------------------------------------------------------

    def is_latent_layer(self, layer: int) -> bool:
        """The last layer of every group of `layer_group_size` is the
        softmax (latent) one."""
        return (layer + 1) % self.layer_group_size == 0

    def is_moe_layer(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    def cache_description(self, dtype: str) -> dict:
        """What `ModelConfig` reads the caches from
        (`transformers_utils/configs/phi4flash.py` has the keys):

        - `layers`: "kv" for a latent layer (a paged pool of latent rows:
          `ModelConfig.get_cache_row_shape` gives a latent model's row
          before it asks here), "state" for a KDA layer;
        - `state_arrays`: the last `short_conv_kernel_size - 1` inputs of
          the q, k and v filters side by side in the model's dtype, and a
          float32 matrix `[key channels, value channels]` a head."""
        heads, d = self.num_attention_heads, self.head_dim
        return {
            "layers": ["kv" if self.is_latent_layer(l) else "state"
                       for l in range(self.num_hidden_layers)],
            "state_arrays": (
                ((self.short_conv_kernel_size - 1, 3 * heads * d), dtype),
                ((heads, d, d), "float32")),
        }
