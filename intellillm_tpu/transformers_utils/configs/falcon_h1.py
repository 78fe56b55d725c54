"""Falcon-H1 config shim (`model_type` "falcon_h1"), for an installed
`transformers` whose `AutoConfig` does not know the type; one that does
parses the same keys, and the engine reads either alike.

Source: https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json.
Every layer holds a Mamba-2 selective state-space scan (`mamba_n_heads`
heads of `mamba_d_head` channels = `mamba_d_ssm`, `mamba_d_state` state
channels, `mamba_n_groups` groups of B and C, a `mamba_d_conv`-tap filter
with a bias, the gated norm a group at a time with `mamba_rms_norm`) AND a
RoPE GQA attention (`attn_layer_indices` null: in every layer), both
reading one normed input, their outputs summed, then a SwiGLU MLP of
`intermediate_size`. Twelve scalars of the maximal-update parametrisation:
`embedding_multiplier`, `lm_head_multiplier`, `ssm_in_multiplier`,
`ssm_out_multiplier`, the five `ssm_multipliers` (one a segment of the
in-projection's output, `[z | x | B | C | dt]`), `attention_in_multiplier`,
`attention_out_multiplier`, `key_multiplier`, the two `mlp_multipliers`
(gate, down). `mamba_expand` and `mlp_expansion_factor` bind nothing where
`mamba_d_ssm` and `intermediate_size` are given; `mamba_chunk_size` is the
training kernel's.
"""
from transformers import PretrainedConfig


class FalconH1Config(PretrainedConfig):
    model_type = "falcon_h1"

    def __init__(
        self,
        vocab_size=261120,
        hidden_size=5120,
        intermediate_size=21504,
        num_hidden_layers=72,
        num_attention_heads=20,
        num_key_value_heads=4,
        head_dim=128,
        attention_bias=False,
        attn_layer_indices=None,
        mamba_d_ssm=4096,
        mamba_n_heads=32,
        mamba_d_head=128,
        mamba_d_state=256,
        mamba_n_groups=2,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_chunk_size=128,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
        mamba_norm_before_gate=False,
        mamba_rms_norm=True,
        mamba_use_mlp=True,
        mlp_bias=False,
        mlp_expansion_factor=8,
        projectors_bias=False,
        embedding_multiplier=1.0,
        lm_head_multiplier=1.0,
        ssm_in_multiplier=1.0,
        ssm_out_multiplier=1.0,
        ssm_multipliers=None,
        attention_in_multiplier=1.0,
        attention_out_multiplier=1.0,
        key_multiplier=1.0,
        mlp_multipliers=None,
        hidden_act="silu",
        rope_theta=100000000000,
        rope_scaling=None,
        max_position_embeddings=262144,
        rms_norm_eps=1e-5,
        num_logits_to_keep=1,
        tie_word_embeddings=False,
        pad_token_id=0,
        bos_token_id=1,
        eos_token_id=2,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.head_dim = head_dim
        self.attention_bias = attention_bias
        self.attn_layer_indices = attn_layer_indices
        self.mamba_d_ssm = mamba_d_ssm
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_n_groups = mamba_n_groups
        self.mamba_d_conv = mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_chunk_size = mamba_chunk_size
        self.mamba_conv_bias = mamba_conv_bias
        self.mamba_proj_bias = mamba_proj_bias
        self.mamba_norm_before_gate = mamba_norm_before_gate
        self.mamba_rms_norm = mamba_rms_norm
        self.mamba_use_mlp = mamba_use_mlp
        self.mlp_bias = mlp_bias
        self.mlp_expansion_factor = mlp_expansion_factor
        self.projectors_bias = projectors_bias
        self.embedding_multiplier = embedding_multiplier
        self.lm_head_multiplier = lm_head_multiplier
        self.ssm_in_multiplier = ssm_in_multiplier
        self.ssm_out_multiplier = ssm_out_multiplier
        self.ssm_multipliers = list(ssm_multipliers or [1.0] * 5)
        self.attention_in_multiplier = attention_in_multiplier
        self.attention_out_multiplier = attention_out_multiplier
        self.key_multiplier = key_multiplier
        self.mlp_multipliers = list(mlp_multipliers or [1.0, 1.0])
        self.hidden_act = hidden_act
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.num_logits_to_keep = num_logits_to_keep
        super().__init__(pad_token_id=pad_token_id,
                         bos_token_id=bos_token_id,
                         eos_token_id=eos_token_id,
                         tie_word_embeddings=tie_word_embeddings, **kwargs)
