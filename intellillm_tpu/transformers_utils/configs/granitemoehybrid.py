"""Granite 4.0-H config shim (`model_type` "granitemoehybrid"), for an
installed `transformers` whose `AutoConfig` does not know the type; one
that does parses the same keys, and the engine reads either alike.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json.
`layer_types` says layer by layer "mamba" (a Mamba-2 selective state-space
scan: `mamba_n_heads` heads of `mamba_d_head` channels, `mamba_d_state`
state channels, `mamba_n_groups` groups of B and C, a `mamba_d_conv`-tap
filter with a bias) or "attention" (softmax GQA, no position encoding with
`position_embedding_type` "nope"). Every layer is a mixture of
`num_local_experts` softmax-routed experts of width `intermediate_size`,
`num_experts_per_tok` a token, + a shared MLP of `shared_intermediate_size`.
Four multipliers: `embedding_multiplier` on the embeddings,
`residual_multiplier` on both residual writes, `attention_multiplier` as
the softmax scale, `logits_scaling` dividing the logits.

Expert parallelism as this engine reads it (as
`transformers_utils/configs/solar_open2.py`): with `ep_size` > 1,
`num_local_experts` counts the experts THIS chip holds (rank `ep_rank`
holds `[ep_rank * n, (ep_rank + 1) * n)`), and the router keeps its
published width `num_local_experts * ep_size`.
"""
from transformers import PretrainedConfig


class GraniteMoeHybridConfig(PretrainedConfig):
    model_type = "granitemoehybrid"

    def __init__(
        self,
        vocab_size=100352,
        hidden_size=4096,
        intermediate_size=768,
        shared_intermediate_size=1536,
        num_hidden_layers=40,
        layer_types=None,
        num_attention_heads=32,
        num_key_value_heads=8,
        attention_bias=False,
        position_embedding_type="nope",
        embedding_multiplier=12,
        residual_multiplier=0.22,
        attention_multiplier=0.0078125,
        logits_scaling=16,
        num_local_experts=72,
        num_experts_per_tok=10,
        mamba_n_heads=128,
        mamba_d_head=64,
        mamba_d_state=128,
        mamba_n_groups=1,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_chunk_size=256,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
        ep_size=1,
        ep_rank=0,
        hidden_act="silu",
        rope_theta=10000,
        rope_scaling=None,
        max_position_embeddings=131072,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        pad_token_id=None,
        bos_token_id=None,
        eos_token_id=None,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.shared_intermediate_size = shared_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        if layer_types is None:
            layer_types = ["attention" if i % 10 == 5 else "mamba"
                           for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.attention_bias = attention_bias
        self.position_embedding_type = position_embedding_type
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.attention_multiplier = attention_multiplier
        self.logits_scaling = logits_scaling
        self.num_local_experts = num_local_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_n_groups = mamba_n_groups
        self.mamba_d_conv = mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_chunk_size = mamba_chunk_size
        self.mamba_conv_bias = mamba_conv_bias
        self.mamba_proj_bias = mamba_proj_bias
        self.ep_size = ep_size
        self.ep_rank = ep_rank
        self.hidden_act = hidden_act
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        super().__init__(pad_token_id=pad_token_id,
                         bos_token_id=bos_token_id,
                         eos_token_id=eos_token_id,
                         tie_word_embeddings=tie_word_embeddings, **kwargs)
