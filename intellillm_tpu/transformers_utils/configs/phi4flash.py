"""Phi-4-mini-flash-reasoning config shim (`model_type` "phi4flash"), for an
installed `transformers` that does not know the type.

Source: https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json
(the SambaY decoder-hybrid-decoder, arXiv:2507.06607). The layout is a rule
over `n = num_hidden_layers` and `mb_per_layer` 2 (`layer_kind`): layer `l`
even is a Mamba-1 scan for `l <= n / 2` and a gated memory unit (GMU) for
`l >= n / 2 + 2`; `l` odd is an attention layer with a `sliding_window` for
`l < n / 2`, the ONE full-attention layer at `n / 2 + 1`, and after it a
cross layer that has a query and an output projection of its own and reads
the full layer's keys and values. The scan at `n / 2` hands its output to
every GMU.

The class also says what the layers CACHE (`cache_description`), which
`ModelConfig` asks a config class for before it reads a description off
the config's keys: this family is the first whose layers are neither all
alike nor told apart by one list of types.
"""
import math

from transformers import PretrainedConfig

# The kinds of layer (`Phi4FlashConfig.layer_kind`).
SCAN, WINDOW, FULL, GMU, CROSS = "scan", "window", "full", "gmu", "cross"


class Phi4FlashConfig(PretrainedConfig):
    model_type = "phi4flash"

    def __init__(
        self,
        vocab_size=200064,
        hidden_size=2560,
        intermediate_size=10240,
        num_hidden_layers=32,
        num_attention_heads=40,
        num_key_value_heads=20,
        mb_per_layer=2,
        sliding_window=512,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank="auto",
        mamba_conv_bias=True,
        mamba_proj_bias=False,
        mlp_bias=False,
        hidden_act="silu",
        layer_norm_eps=1e-5,
        max_position_embeddings=262144,
        tie_word_embeddings=True,
        pad_token_id=199999,
        bos_token_id=199999,
        eos_token_id=199999,
        **kwargs,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.mb_per_layer = mb_per_layer
        self.sliding_window = sliding_window
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_dt_rank = (math.ceil(hidden_size / 16)
                              if mamba_dt_rank == "auto" else mamba_dt_rank)
        self.mamba_conv_bias = mamba_conv_bias
        self.mamba_proj_bias = mamba_proj_bias
        self.mlp_bias = mlp_bias
        self.hidden_act = hidden_act
        self.layer_norm_eps = layer_norm_eps
        self.max_position_embeddings = max_position_embeddings
        super().__init__(pad_token_id=pad_token_id,
                         bos_token_id=bos_token_id,
                         eos_token_id=eos_token_id,
                         tie_word_embeddings=tie_word_embeddings, **kwargs)

    # --- the layout ----------------------------------------------------------

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def memory_layer(self) -> int:
        """The scan whose output the GMUs read."""
        return self.num_hidden_layers // 2

    @property
    def full_layer(self) -> int:
        """The one attention layer that keeps the whole context."""
        return self.num_hidden_layers // 2 + 1

    def layer_kind(self, layer: int) -> str:
        half = self.num_hidden_layers // 2
        if layer % 2 == 0:
            return SCAN if layer <= half else GMU
        if layer < half:
            return WINDOW
        return FULL if layer == half + 1 else CROSS

    def cache_description(self, dtype: str) -> dict:
        """What `ModelConfig` reads the caches from:

        - `layers`: for each layer "state", "kv_window" or "kv" (it holds
          an entry of the cache list of that kind), an int (it holds
          nothing and reads that layer's entry: a cross layer), or None
          (it holds and reads nothing: a GMU);
        - `state_arrays`: a "state" layer's arrays a sequence, (shape,
          dtype) each: the filter's three taps `[3, d_inner]` in the
          model's dtype and the scan's float32 matrix `[d_state, d_inner]`
          with its channels cut into 128-lane tiles, `[tiles, d_state,
          128]` (the state channels on the sublanes: `ops/pallas/
          selective_scan.py`);
        - `window`: the "kv_window" layers' window in tokens;
        - `row_shape`: (pools, heads, width) of a token's row in an
          attention layer's pools: differential attention reads K/V heads
          in pairs, so heads `2j, 2j + 1` are stored side by side as ONE
          head twice as wide (`layers/attention.py`
          `DifferentialAttention`)."""
        kinds = {SCAN: "state", WINDOW: "kv_window", FULL: "kv",
                 CROSS: self.full_layer, GMU: None}
        inner = self.mamba_d_inner
        head = self.hidden_size // self.num_attention_heads
        return {
            "layers": [kinds[self.layer_kind(l)]
                       for l in range(self.num_hidden_layers)],
            "state_arrays": (((self.mamba_d_conv - 1, inner), dtype),
                             ((inner // 128, self.mamba_d_state, 128),
                              "float32")),
            "window": int(self.sliding_window),
            "row_shape": (2, self.num_key_value_heads // 2, 2 * head),
        }
