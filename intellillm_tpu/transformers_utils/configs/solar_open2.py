"""Solar Open 2 config shim (`model_type` "solar_open2"; `AutoConfig` does
not know it).

Source: https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json.
Every layer outside `gqa_layers` is a Kimi-Delta-Attention linear-attention
layer (`linear_attn_config`: heads, head size, the short filter's taps;
`kda_allow_neg_eigval`: the write strength is 2 x sigmoid;
`kda_use_full_proj` false: the decay's and the output gate's projections
are low rank); a layer in `gqa_layers` is softmax grouped-query attention
without position encoding (`use_rope` false) and with an output gate
(`use_gqa_gate`). Every layer is a mixture of `n_routed_experts`
sigmoid-scored experts + `n_shared_experts` shared
(`first_k_dense_replace` 0).

Expert parallelism as this engine reads it (as
`transformers_utils/configs/axk1.py`): with `ep_size` > 1,
`n_routed_experts` counts the experts THIS chip holds (rank `ep_rank`
holds `[ep_rank * n, (ep_rank + 1) * n)`), and the router keeps its
published width `n_routed_experts * ep_size`.
"""
from transformers import PretrainedConfig


class SolarOpen2Config(PretrainedConfig):
    model_type = "solar_open2"

    def __init__(
        self,
        vocab_size=196608,
        hidden_size=4096,
        intermediate_size=10240,
        moe_intermediate_size=1280,
        num_hidden_layers=48,
        num_attention_heads=64,
        num_key_value_heads=8,
        head_dim=128,
        linear_attn_config=None,
        gqa_interval=3,
        gqa_layers=None,
        use_rope=False,
        use_gqa_gate=True,
        kda_use_full_proj=False,
        kda_allow_neg_eigval=True,
        n_routed_experts=320,
        n_shared_experts=1,
        num_experts_per_tok=8,
        norm_topk_prob=True,
        routed_scaling_factor=1,
        first_k_dense_replace=0,
        ep_size=1,
        ep_rank=0,
        partial_rotary_factor=1,
        rope_theta=10000,
        max_position_embeddings=1048576,
        rms_norm_eps=1e-5,
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
        self.head_dim = head_dim
        linear = dict(linear_attn_config or {})
        linear.setdefault("short_conv_kernel_size", 4)
        linear.setdefault("head_dim", head_dim)
        linear.setdefault("num_heads", num_attention_heads)
        linear.setdefault("num_kv_heads", None)
        self.linear_attn_config = linear
        self.gqa_interval = gqa_interval
        if gqa_layers is None:
            gqa_layers = list(range(0, num_hidden_layers, gqa_interval + 1))
        self.gqa_layers = [i for i in gqa_layers if i < num_hidden_layers]
        self.use_rope = use_rope
        self.use_gqa_gate = use_gqa_gate
        self.kda_use_full_proj = kda_use_full_proj
        self.kda_allow_neg_eigval = kda_allow_neg_eigval
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.first_k_dense_replace = first_k_dense_replace
        self.ep_size = ep_size
        self.ep_rank = ep_rank
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        super().__init__(pad_token_id=pad_token_id,
                         bos_token_id=bos_token_id,
                         eos_token_id=eos_token_id,
                         tie_word_embeddings=tie_word_embeddings, **kwargs)
