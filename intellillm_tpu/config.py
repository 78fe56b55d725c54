"""Engine configuration objects.

Role parity: reference `vllm/config.py` (ModelConfig :18, CacheConfig :271,
ParallelConfig :349, SchedulerConfig :400, LoRAConfig :448). Re-designed for
TPU: parallelism is expressed as a `jax.sharding.Mesh` over ICI axes rather
than NCCL process-group world sizes, and cache sizing targets the HBM block
pool instead of torch CUDA allocations.
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from typing import List, Optional, Tuple, Union

from intellillm_tpu.logger import init_logger
from intellillm_tpu.transformers_utils.config import get_hf_config

logger = init_logger(__name__)

_GiB = 1024**3


def ssd_state_shape(inner: int, d_state: int,
                    groups: int = 1) -> Tuple[int, int, int]:
    """`(tiles, d_state, lanes)`: a state-space layer's matrix a sequence,
    `[d_state, inner]` with the `inner` channels (head-major) cut into
    whole 128-lane tiles where a group's channels divide so (one tile a
    group otherwise: a tiny test model), so that a tile reads one group of
    B and C. The state dimension lies on the sublanes: what a token
    contracts with C, and everything that is one number a channel (the
    head's decay, dt x) lies along the lanes
    (`ops/pallas/ssd.py`)."""
    per_group = inner // groups
    lanes = 128 if per_group % 128 == 0 else per_group
    return inner // lanes, d_state, lanes


class ModelConfig:
    """Model + tokenizer + dtype + length limits.

    Mirrors reference ModelConfig (`vllm/config.py:18-268`) introspection:
    head size, kv-head count, layer count, max length resolution, dtype
    verification — but dtype defaults to bfloat16 (TPU-native) and
    quantization methods are the TPU set.
    """

    def __init__(
        self,
        model: str,
        tokenizer: Optional[str] = None,
        tokenizer_mode: str = "auto",
        trust_remote_code: bool = False,
        dtype: str = "auto",
        seed: int = 0,
        revision: Optional[str] = None,
        max_model_len: Optional[int] = None,
        quantization: Optional[str] = None,
        enforce_eager: bool = False,
        load_format: str = "auto",
        max_context_len_to_capture: Optional[int] = None,
        hf_config_override=None,
    ) -> None:
        self.model = model
        self.tokenizer = tokenizer or model
        self.tokenizer_mode = tokenizer_mode
        self.trust_remote_code = trust_remote_code
        self.seed = seed
        self.revision = revision
        self.quantization = quantization
        self.enforce_eager = enforce_eager
        self.load_format = load_format

        self.hf_config = (hf_config_override if hf_config_override is not None
                          else get_hf_config(model, trust_remote_code,
                                             revision))
        self.dtype = _get_and_verify_dtype(self.hf_config, dtype)
        self.max_model_len = _get_and_verify_max_len(self.hf_config, max_model_len)
        self._verify_tokenizer_mode()
        self._verify_quantization()

    @classmethod
    def from_hf_config(cls, hf_config, dtype: str = "auto",
                       max_model_len: Optional[int] = None,
                       load_format: str = "dummy",
                       quantization: Optional[str] = None,
                       seed: int = 0) -> "ModelConfig":
        """Build a ModelConfig from an in-memory HF config (no checkpoint
        dir) — for dummy-weight benchmarking and multi-chip dry runs."""
        return cls(model=getattr(hf_config, "name_or_path", "") or "in-memory",
                   dtype=dtype, seed=seed, max_model_len=max_model_len,
                   load_format=load_format, quantization=quantization,
                   hf_config_override=hf_config)

    def _verify_tokenizer_mode(self) -> None:
        if self.tokenizer_mode not in ("auto", "slow"):
            raise ValueError(
                f"Unknown tokenizer mode: {self.tokenizer_mode}; "
                "must be 'auto' or 'slow'.")

    # Every supported method has a lossless TPU checkpoint loader
    # (weight_utils.load_linear): int8 quantize-on-load; AWQ/GPTQ →
    # packed int4 (act-order via an input-row permutation); SqueezeLLM →
    # exact per-channel LUT ({"q4lut","lut"}).
    _SUPPORTED_QUANT = ("awq", "gptq", "squeezellm", "int8")

    def _verify_quantization(self) -> None:
        if self.quantization is None:
            # Auto-detect from checkpoint config (reference config.py:166-184).
            hf_q = getattr(self.hf_config, "quantization_config", None)
            if hf_q is not None:
                if isinstance(hf_q, dict):
                    method = hf_q.get("quant_method", None)
                else:  # transformers may parse it into a *QuantConfig object
                    method = getattr(hf_q, "quant_method", None)
                # QuantizationMethod enum: use .value, not str(enum).
                method = getattr(method, "value", method)
                if method is not None:
                    self.quantization = str(method).lower()
        if self.quantization is not None and self.quantization not in self._SUPPORTED_QUANT:
            raise ValueError(
                f"Unknown quantization method: {self.quantization}; "
                f"supported: {self._SUPPORTED_QUANT}")
        # Bit-width check applies whether the method was auto-detected or
        # passed explicitly — only 4-bit AWQ/GPTQ/SqueezeLLM loads.
        if self.quantization in ("awq", "gptq", "squeezellm"):
            hf_q = getattr(self.hf_config, "quantization_config", None)
            bits = None
            if isinstance(hf_q, dict):
                bits = hf_q.get("bits", hf_q.get("w_bit"))
            elif hf_q is not None:
                bits = getattr(hf_q, "bits", getattr(hf_q, "w_bit", None))
            if bits is not None and int(bits) != 4:
                raise NotImplementedError(
                    f"{self.quantization} with {bits}-bit weights is not "
                    "supported (only 4-bit)")

    # --- HF config introspection (reference config.py:222-268) ---

    def get_hidden_size(self) -> int:
        return self.hf_config.hidden_size

    def get_latent_cache_width(self) -> Optional[int]:
        """Latent attention (MLA), told by its mechanism: a config with
        `kv_lora_rank` and `qk_rope_head_dim` caches ONE vector a token a
        layer, `[c_kv | k_rope]`, with no head axis and no V. Returned as
        stored: zero-padded up to whole 128-lane tiles (576 -> 640), which
        is what the device would pad it to anyway and what the kernels'
        DMA windows need. None for every per-head K/V model."""
        rank = getattr(self.hf_config, "kv_lora_rank", None)
        rope = getattr(self.hf_config, "qk_rope_head_dim", None)
        if not rank or not rope:
            return None
        return -(-(rank + rope) // 128) * 128

    def get_packed_latent_layers(self) -> Optional[int]:
        """A latent-attention model whose config says
        `latent_layers_in_one_pool` keeps ALL its layers' vectors in one
        pool `[num_blocks, layers, block_size, width]`: the layers lie
        where a per-head pool has its heads, which a latent cache has
        none of. The cache engine, the block manager and the runner then
        see one "kv" layer whose token stores `layers` rows (swap, copy,
        the decode stage and its commit move a block's every layer at
        once), and the model can walk its layers in a `lax.scan`: layer
        `l` reads the pool viewed as `[num_blocks * layers, 1, ...]`
        through block numbers `b * layers + l`
        (`layers/mla_attention.py`). Returns the layer count, None for
        every other model."""
        if (self.get_latent_cache_width() and getattr(
                self.hf_config, "latent_layers_in_one_pool", False)):
            return self.get_num_layers()
        return None

    def get_cache_row_shape(self) -> Tuple[int, int, int]:
        """(pools, heads, width) of what one token stores in one layer's
        cache: a layer's cache is `pools` arrays of
        `[num_blocks, heads, block_size, width]`. Per-head K/V: (2, kv
        heads, head size). Latent attention: (1, 1, stored latent width),
        or (1, layers, width) where the layers share one pool
        (`get_packed_latent_layers`).
        The cache engine, the worker's memory profile and the runner size
        everything from this; `get_head_size` / `get_total_num_kv_heads`
        keep describing the attention itself."""
        latent = self.get_latent_cache_width()
        if latent:
            return 1, self.get_packed_latent_layers() or 1, latent
        described = self._cache_description()
        if described:
            return tuple(described["row_shape"])
        # A config may state a stored head width wider than the head (the
        # head zero-padded to whole 128-lane tiles, which is what the
        # device pads a narrower pool row to anyway and what the Pallas
        # kernels' DMA windows need): `layers/attention.py` pads q, k, v
        # up to the pool's width and cuts the output back.
        width = getattr(self.hf_config, "kv_cache_head_width", None)
        return (2, self.get_total_num_kv_heads(),
                width or self.get_head_size())

    def _parallel_scan_layers(self) -> bool:
        """Whether every layer holds a selective state-space scan BESIDE
        its attention (both read one normed input, their outputs are
        summed): a config with the scan's sizes (`mamba_d_ssm`) and
        `attn_layer_indices` null, and no `layer_types` that would say
        one or the other layer by layer."""
        cfg = self.hf_config
        return (getattr(cfg, "mamba_d_ssm", None) is not None
                and not getattr(cfg, "layer_types", None)
                and getattr(cfg, "attn_layer_indices", None) is None)

    def _cache_description(self) -> Optional[dict]:
        """What the config CLASS says of its caches
        (`cache_description(dtype)`: `transformers_utils/configs/
        phi4flash.py` has the keys), None for a class that says nothing:
        its caches are then read off the config's keys by the five
        descriptions below. A new family whose layers differ states them
        there and adds nothing here."""
        describe = getattr(self.hf_config, "cache_description", None)
        return describe(self.dtype) if callable(describe) else None

    def get_layer_cache_entries(self) -> List[Optional[int]]:
        """For each MODEL layer, the entry of the cache list
        (`get_layer_cache_kinds`) it reads: its own, another layer's (a
        layer that holds nothing and attends to the keys and values of a
        layer before it), or None (a layer with no cache at all). Every
        family but a described one has one entry a layer, in order (or
        the two special shapes `get_layer_cache_kinds` names, which their
        models index themselves)."""
        described = self._cache_description()
        if not described:
            return list(range(len(self.get_layer_cache_kinds())))
        layers = described["layers"]
        holders = [i for i, kind in enumerate(layers)
                   if isinstance(kind, str)]
        entry = {layer: i for i, layer in enumerate(holders)}
        return [None if kind is None
                else entry[layer if isinstance(kind, str) else kind]
                for layer, kind in enumerate(layers)]

    def get_cache_walkers(self) -> Tuple[int, int, int]:
        """(layers that walk a "kv" pool, layers that walk a "kv_window"
        pool, those of the first that hold no pool of their own): what
        the attention kernels' walks are counted by. Without a described
        reader every walker is a holder."""
        kinds = self.get_layer_cache_kinds()
        entries = self.get_layer_cache_entries()
        full = [e for e in entries if e is not None and kinds[e] == "kv"]
        return (len(full), kinds.count("kv_window"),
                len(full) - len(set(full)))

    def get_layer_cache_kinds(self) -> List[str]:
        """What each entry of the cache list holds (`kv_caches[i]`, one
        entry a layer but for the two cases at the end), told by
        mechanism: "kv"
        (a paged pool of `get_cache_row_shape`, attention of either kind,
        the model's primary cache group), "kv_window" (the same pool rows,
        but the layer attends to the last `get_window_group_size()`
        tokens only and its blocks are a group of their own, beside
        layers that keep the whole context) or "state" (a fixed-size
        per-sequence state of `get_state_arrays`, one slot a running
        sequence, not paged). Three descriptions give "state" layers: a
        config that lists `layer_types` and a short-convolution cache
        length (`conv_L_cache`) has one where the type is not an
        attention; one with a `linear_attn_config` wherever `gqa_layers`
        does not list the layer; one whose `layer_types` say "mamba" (a
        selective state-space scan: `mamba_d_state`, `mamba_n_heads`)
        where they say it. One that lists a
        `sliding_window_layout` of ones and zeros and a
        `sliding_window_size` has "kv_window" where the layout says 1,
        unless it says 1 everywhere (then the window is the whole
        model's, `get_sliding_window`, and there is one group); every
        other config is "kv" throughout. A latent model whose layers
        share one pool (`get_packed_latent_layers`) is ONE "kv" entry. A
        layer that holds BOTH a paged K/V pool and a state slot (a scan
        beside its attention, `_parallel_scan_layers`) is TWO entries,
        "kv" then "state": layer l's caches are entries 2l and 2l + 1, so
        that it counts in both totals and every block op and every state
        op below finds its pools where it finds any other layer's. A
        config CLASS that describes its caches itself
        (`_cache_description`) is asked first: its list has one entry a
        layer that holds something, of any of the three kinds side by
        side, and `get_layer_cache_entries` maps the model's layers,
        those that hold nothing included, onto it."""
        n = self.get_num_layers()
        described = self._cache_description()
        if described:
            # One entry a layer that HOLDS something, in layer order.
            return [kind for kind in described["layers"]
                    if isinstance(kind, str)]
        if self.get_packed_latent_layers():
            return ["kv"]           # one pool for every layer
        if self._parallel_scan_layers():
            return ["kv", "state"] * n
        layout = self._window_layout()
        if layout is not None and not all(layout):
            return ["kv_window" if w else "kv" for w in layout]
        if getattr(self.hf_config, "linear_attn_config", None):
            # Linear-attention layers everywhere but where the config
            # lists a softmax layer.
            softmax = set(getattr(self.hf_config, "gqa_layers", None) or ())
            return ["kv" if i in softmax else "state" for i in range(n)]
        types = getattr(self.hf_config, "layer_types", None)
        if types and "mamba" in types[:n]:
            return ["state" if t == "mamba" else "kv" for t in types[:n]]
        if not types or not getattr(self.hf_config, "conv_L_cache", None):
            return ["kv"] * n
        return ["kv" if "attention" in t else "state" for t in types[:n]]

    def _window_layout(self) -> Optional[List[int]]:
        """Per layer, whether it has the sliding window, for a config
        that says so layer by layer; None for every other."""
        layout = getattr(self.hf_config, "sliding_window_layout", None)
        size = getattr(self.hf_config, "sliding_window_size", None)
        if not layout or not size or not any(layout):
            return None
        return [int(bool(w)) for w in layout[:self.get_num_layers()]]

    def get_window_group_size(self) -> Optional[int]:
        """The window, in tokens, of the "kv_window" layers; None for a
        model without such a group."""
        if "kv_window" not in self.get_layer_cache_kinds():
            return None
        described = self._cache_description()
        if described:
            return int(described["window"])
        return int(self.hf_config.sliding_window_size)

    def get_window_group_share(self, ahead_tokens: int) -> float:
        """Blocks of the window group a block of the primary group: what a
        sequence holds of the one (the window and what a step writes
        ahead of it) over what it holds of the other at `max_model_len`.
        0 without a window group."""
        window = self.get_window_group_size()
        if window is None:
            return 0.0
        return min(1.0, (window + ahead_tokens) / max(self.max_model_len, 1))

    def get_state_arrays(self) -> Tuple[Tuple[Tuple[int, ...], str], ...]:
        """What one sequence keeps in one "state" layer: a tuple of
        (shape, dtype), one entry an array; () for a model without such
        layers. Three descriptions:

        - a short convolution (`conv_L_cache`) keeps its last
          `conv_L_cache - 1` gated inputs, `hidden_size` channels each,
          in the model's dtype (the 1-tuple case);
        - a delta-rule linear-attention layer (`linear_attn_config`)
          keeps the last `short_conv_kernel_size - 1` inputs of its q, k
          and v filters side by side, in the model's dtype, and a float32
          matrix `[key channels, value channels]` a head;
        - a selective state-space scan (`layer_types` "mamba", or a scan
          beside the attention in every layer) keeps the
          last `mamba_d_conv - 1` inputs of the one filter over x, B and
          C (`d_inner + 2 x n_groups x d_state` channels), in the model's
          dtype, and the float32 matrix `[d_state, d_inner]`, its channels
          cut into lane tiles (`ssd_state_shape`).

        A matrix state is float32 whatever the model's dtype: it is
        summed over every token so far, and a 2-byte state's rounding
        would be as large as what a token adds to it.

        A 2-d entry (a few rows of activations) is stored as one row a
        slot and gathered into the decode programs' stage; an entry of
        more dimensions (a matrix a head) is updated in its pool, by
        slot (`state_array_in_place`)."""
        if "state" not in self.get_layer_cache_kinds():
            return ()
        described = self._cache_description()
        if described:
            return tuple(described["state_arrays"])
        cfg = self.hf_config
        linear = getattr(cfg, "linear_attn_config", None)
        if linear:
            heads, d = linear["num_heads"], linear["head_dim"]
            taps = linear["short_conv_kernel_size"] - 1
            return (((taps, 3 * heads * d), self.dtype),
                    ((heads, d, d), "float32"))
        if ("mamba" in (getattr(cfg, "layer_types", None) or ())
                or self._parallel_scan_layers()):
            inner = cfg.mamba_n_heads * cfg.mamba_d_head
            filtered = inner + 2 * cfg.mamba_n_groups * cfg.mamba_d_state
            return (((cfg.mamba_d_conv - 1, filtered), self.dtype),
                    (ssd_state_shape(inner, cfg.mamba_d_state,
                                     cfg.mamba_n_groups), "float32"))
        return (((self.hf_config.conv_L_cache - 1,
                  self.hf_config.hidden_size), self.dtype), )

    @staticmethod
    def state_array_in_place(shape: Tuple[int, ...]) -> bool:
        """Whether a state array is updated in its pool by slot (a matrix
        a head: megabytes a sequence) and not through a gathered stage (a
        few rows of activations)."""
        return len(shape) > 2

    def get_num_kv_layers(self) -> int:
        """Layers with a paged KV (or latent) pool of the primary group."""
        return self.get_layer_cache_kinds().count("kv")

    def get_num_window_layers(self) -> int:
        """Layers whose pool belongs to the window group."""
        return self.get_layer_cache_kinds().count("kv_window")

    def get_num_state_layers(self) -> int:
        return self.get_layer_cache_kinds().count("state")

    def get_head_size(self) -> int:
        if hasattr(self.hf_config, "head_dim") and self.hf_config.head_dim:
            return self.hf_config.head_dim
        return self.hf_config.hidden_size // self.hf_config.num_attention_heads

    def get_total_num_kv_heads(self) -> int:
        # Falcon (reference config.py:235-255): the old decoder arch stores
        # num_kv_heads == num_attention_heads in the config while the model
        # actually runs multi-query (1 shared KV head); only the new arch
        # honors num_kv_heads / n_head_kv.
        if getattr(self.hf_config, "model_type", "") in (
                "falcon", "RefinedWeb", "RefinedWebModel"):
            if (not getattr(self.hf_config, "new_decoder_architecture",
                            False)
                    and getattr(self.hf_config, "multi_query", False)):
                return 1
            # else fall through: GQA configs carry num_kv_heads/n_head_kv.
        attrs = ("num_key_value_heads", "n_head_kv", "num_kv_heads",
                 "multi_query_group_num")
        for attr in attrs:
            v = getattr(self.hf_config, attr, None)
            if v is not None:
                return v
        if getattr(self.hf_config, "multi_query", False):
            return 1
        return self.hf_config.num_attention_heads

    def get_num_kv_heads(self, parallel_config: "ParallelConfig") -> int:
        """KV heads per model-parallel shard (>=1; heads replicate when
        tp > total kv heads — reference config.py:256-264)."""
        total = self.get_total_num_kv_heads()
        return max(1, total // parallel_config.tensor_parallel_size)

    def get_num_attention_heads(self) -> int:
        return self.hf_config.num_attention_heads

    def get_num_layers(self) -> int:
        for attr in ("num_hidden_layers", "n_layer", "num_layers"):
            v = getattr(self.hf_config, attr, None)
            if v is not None:
                return v
        raise ValueError("Cannot determine number of layers from HF config")

    def get_vocab_size(self) -> int:
        return self.hf_config.vocab_size

    def get_sliding_window(self) -> Optional[int]:
        """The window every layer has (the primary group is then
        windowed); None where no layer or only some have one
        (`get_window_group_size`)."""
        layout = self._window_layout()
        if layout is not None and all(layout):
            return int(self.hf_config.sliding_window_size)
        if self._cache_description():
            # A described family's window is its "kv_window" layers'.
            return None
        return getattr(self.hf_config, "sliding_window", None)


class CacheConfig:
    """Paged KV-cache pool configuration.

    Mirrors reference CacheConfig (`vllm/config.py:271-346`): block size,
    device-memory utilization fraction, CPU swap space, cache dtype. The
    number of device blocks is filled in after the memory-profile step
    (reference `worker.py:95-136`), or forced via `num_device_blocks_override`
    for deterministic tests.
    """

    def __init__(
        self,
        block_size: int = 16,
        hbm_utilization: float = 0.90,
        swap_space_gib: float = 4.0,
        cache_dtype: str = "auto",
        num_device_blocks_override: Optional[int] = None,
        sliding_window: Optional[int] = None,
        window_group_size: Optional[int] = None,
    ) -> None:
        self.block_size = block_size
        self.hbm_utilization = hbm_utilization
        self.swap_space_bytes = int(swap_space_gib * _GiB)
        self.cache_dtype = cache_dtype
        self.num_device_blocks_override = num_device_blocks_override
        # The window every layer has, or (`window_group_size`) the window
        # of the layers that have one beside layers that have none.
        self.sliding_window = sliding_window
        self.window_group_size = window_group_size
        self._verify_args()

        # Filled after profiling / engine init.
        self.num_device_blocks: Optional[int] = None
        self.num_cpu_blocks: Optional[int] = None
        # Per-sequence state slots (models with "state" layers,
        # `ModelConfig.get_layer_cache_kinds`): one a running sequence on
        # the device, and as many on the host for swapped ones. 0 for
        # every other model.
        self.num_state_slots: int = 0
        # The window group's pools (models with "kv_window" layers): its
        # blocks on the device and on the host, derived from the primary
        # group's by `ModelConfig.get_window_group_share` of
        # `window_ahead_tokens` (the most tokens a step writes ahead of
        # its first query; the engine sets it from the scheduler's
        # limits). 0 for every other model.
        self.window_ahead_tokens: int = 0
        self.num_window_device_blocks: int = 0
        self.num_window_cpu_blocks: int = 0

    def size_window_group(self, model_config: "ModelConfig") -> None:
        """Fill the window group's block counts from the primary
        group's."""
        share = model_config.get_window_group_share(self.window_ahead_tokens)
        if share:
            self.num_window_device_blocks = math.ceil(
                self.num_device_blocks * share)
            self.num_window_cpu_blocks = math.ceil(
                (self.num_cpu_blocks or 0) * share)

    def _verify_args(self) -> None:
        if self.hbm_utilization > 1.0 or self.hbm_utilization <= 0:
            raise ValueError(
                f"hbm_utilization must be in (0, 1], got {self.hbm_utilization}")
        if self.cache_dtype not in ("auto", "fp8_e5m2", "bfloat16", "float16",
                                    "float32"):
            raise ValueError(f"Unknown kv cache dtype: {self.cache_dtype}")


class ParallelConfig:
    """Device-mesh parallelism.

    The reference models parallelism as NCCL world sizes + Ray workers
    (`vllm/config.py:349-397`). Here it is a logical mesh over TPU ICI:
    axes ("data", "model") built by `intellillm_tpu.parallel.mesh`. Tensor
    parallelism = size of the "model" axis; data parallelism = replica count
    on the "data" axis. Pipeline parallelism is accepted in config for parity
    but — like the reference (`config.py:385-387`) — rejected at validation
    until stage-sharded execution lands.
    """

    def __init__(
        self,
        tensor_parallel_size: int = 1,
        data_parallel_size: int = 1,
        pipeline_parallel_size: int = 1,
        max_parallel_loading_workers: Optional[int] = None,
        disable_custom_collectives: bool = False,
    ) -> None:
        self.tensor_parallel_size = tensor_parallel_size
        self.data_parallel_size = data_parallel_size
        self.pipeline_parallel_size = pipeline_parallel_size
        self.max_parallel_loading_workers = max_parallel_loading_workers
        # XLA owns ICI collectives; kept for CLI parity with the reference's
        # --disable-custom-all-reduce (subsumed by jax.lax.psum).
        self.disable_custom_collectives = disable_custom_collectives
        self.world_size = (tensor_parallel_size * data_parallel_size *
                           pipeline_parallel_size)
        self._verify_args()

    def _verify_args(self) -> None:
        if self.pipeline_parallel_size > 1:
            raise NotImplementedError(
                "Pipeline parallelism is not supported yet.")
        for name in ("tensor_parallel_size", "data_parallel_size"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1")


class SchedulerConfig:
    """Continuous-batching scheduler limits.

    Mirrors reference SchedulerConfig (`vllm/config.py:400-445`): token
    budget per step, max concurrent sequences, max padding waste — plus the
    fork's pluggable policy selection (its `core/policy.py` PolicyFactory is
    the intended SJF integration point; here `policy` is first-class).
    """

    def __init__(
        self,
        max_num_batched_tokens: Optional[int] = None,
        max_num_seqs: int = 256,
        max_model_len: int = 2048,
        max_paddings: int = 256,
        policy: str = "fcfs",
        num_decode_steps: int = 8,
        enable_chunked_prefill: bool = False,
        sjf_starvation_s: Optional[float] = None,
        predictor_path: Optional[str] = None,
        replica_role: str = "mixed",
        tenant_fairness: bool = True,
        min_block_table_width: int = 16,
    ) -> None:
        self.enable_chunked_prefill = enable_chunked_prefill
        if max_num_batched_tokens is not None:
            self.max_num_batched_tokens = max_num_batched_tokens
        elif enable_chunked_prefill:
            # Chunked mode: the budget is a per-step compute knob, not a
            # prompt-length ceiling (prompts longer than the budget are
            # split into chunks). Default to a batch that keeps decode
            # latency low while still amortizing weight reads
            # (Sarathi-Serve picks 256-512 on A100-class parts).
            self.max_num_batched_tokens = max(512, max_num_seqs)
        else:
            self.max_num_batched_tokens = max(max_model_len, 2048)
        self.max_num_seqs = max_num_seqs
        self.max_model_len = max_model_len
        self.max_paddings = max_paddings
        self.policy = policy
        # Aging deadline for the SJF policies: a waiting group older than
        # this is promoted to FCFS priority above every un-promoted group
        # (None / 0 disables aging; ignored by fcfs).
        self.sjf_starvation_s = sjf_starvation_s
        # Length-predictor checkpoint the engine loads at boot when a
        # non-FCFS policy needs predictions and no predictor was injected
        # (None -> PromptLengthHeuristic fallback).
        self.predictor_path = predictor_path
        # Decode iterations fused into one jitted device call (multi-step
        # decode). The host sees one dispatch + one result fetch per K
        # tokens instead of per token — the TPU-side answer to the
        # reference's CUDA-graph + async-loop host-latency hiding. Beam
        # search and penalty-bearing batches fall back to 1.
        self.num_decode_steps = num_decode_steps
        # The narrowest padded block table (in blocks) a step program is
        # compiled for. Every width bucket between it and
        # max_model_len / block_size is one more executable a row bucket;
        # a deployment whose prompts are all long raises it and warms up
        # fewer programs (at max_model_len / block_size: one width).
        self.min_block_table_width = min_block_table_width
        # Disaggregated serving role (docs/routing.md "Disaggregated
        # roles"): "mixed" (default) runs the normal chunked prefill +
        # decode loop; "prefill" finishes every request at
        # prefill-complete (first sampled token) and pins the prompt
        # prefix for KV export; "decode" expects imported prefixes and
        # runs pure decode steps.
        self.replica_role = replica_role
        # Per-tenant weighted admission caps (docs/multitenancy.md):
        # when >= 2 tenants are present, each tenant's RUNNING seats and
        # per-step prefill-chunk tokens are capped at its weighted share
        # so a noisy neighbor cannot starve other tenants' decodes.
        # --disable-tenant-fairness turns the caps off (A/B knob).
        self.tenant_fairness = tenant_fairness
        self._verify_args()

    def _verify_args(self) -> None:
        if (self.max_num_batched_tokens < self.max_model_len
                and not self.enable_chunked_prefill):
            raise ValueError(
                f"max_num_batched_tokens ({self.max_num_batched_tokens}) must "
                f"be >= max_model_len ({self.max_model_len}) with chunked "
                "prefill off (--disable-chunked-prefill): only a chunked "
                "prompt can be longer than the per-step token budget.")
        if (self.max_num_batched_tokens < self.max_num_seqs
                and not self.enable_chunked_prefill):
            # Chunked admission seats every runnable decode before the
            # token budget is consulted (the budget throttles chunk
            # admission only, with the starvation guard covering the
            # decode_rows > budget corner), so a budget below the seat
            # count is legal there.
            raise ValueError(
                "max_num_batched_tokens must be >= max_num_seqs")
        if self.num_decode_steps < 1:
            raise ValueError("num_decode_steps must be >= 1")
        if self.min_block_table_width < 1:
            raise ValueError("min_block_table_width must be >= 1")
        if self.sjf_starvation_s is not None and self.sjf_starvation_s < 0:
            raise ValueError("sjf_starvation_s must be >= 0 (0 disables)")
        if self.replica_role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"replica_role must be mixed | prefill | decode, got "
                f"{self.replica_role!r}")


@dataclass
class LoRAConfig:
    """Multi-LoRA limits (reference `vllm/config.py:448-503`)."""

    max_lora_rank: int = 16
    max_loras: int = 1
    max_cpu_loras: Optional[int] = None
    lora_dtype: Optional[str] = None
    lora_extra_vocab_size: int = 256

    _SUPPORTED_RANKS = (8, 16, 32, 64)

    def __post_init__(self) -> None:
        if self.max_lora_rank not in self._SUPPORTED_RANKS:
            raise ValueError(
                f"max_lora_rank ({self.max_lora_rank}) must be one of "
                f"{self._SUPPORTED_RANKS}.")
        if self.max_loras < 1:
            raise ValueError("max_loras must be >= 1")
        if self.max_cpu_loras is None:
            self.max_cpu_loras = self.max_loras
        elif self.max_cpu_loras < self.max_loras:
            raise ValueError("max_cpu_loras must be >= max_loras")

    def verify_with_model_config(self, model_config: ModelConfig) -> None:
        if self.lora_dtype in (None, "auto"):
            self.lora_dtype = model_config.dtype

    def verify_with_scheduler_config(self, scheduler_config: SchedulerConfig) -> None:
        if scheduler_config.max_num_batched_tokens > 65528:
            raise ValueError(
                "Due to limitations of the batched LoRA kernel bucketing, "
                "max_num_batched_tokens must be <= 65528 when LoRA is enabled.")


class SpeculativeConfig:
    """Draft-model speculative decoding.

    Reference role: `vllm/worker/spec_decode/multi_step_worker.py:22`
    (draft multi-step worker) + `vllm/layers/rejection_sampler.py:9` —
    scaffolding the reference never wired into its engine. Here it is
    engine-integrated for greedy batches: the draft model proposes
    `num_speculative_tokens` tokens with one fused scan, the target
    verifies all of them (plus a bonus token) in one teacher-forced fused
    call, and greedy acceptance keeps the longest agreeing prefix — the
    emitted stream is exactly the target model's greedy stream.
    """

    def __init__(self, draft_model_config: ModelConfig,
                 num_speculative_tokens: int,
                 k_min: Optional[int] = None,
                 k_max: Optional[int] = None) -> None:
        if num_speculative_tokens < 1:
            raise ValueError("num_speculative_tokens must be >= 1")
        self.draft_model_config = draft_model_config
        self.num_speculative_tokens = num_speculative_tokens
        # Adaptive draft-length band: the SLO-adaptive controller holds K
        # in [k_min, k_max] at runtime (boot warms the whole ladder of
        # draft/teacher executables). Defaults pin the band at the
        # configured K — a fixed draft length.
        self.k_min = k_min if k_min is not None else num_speculative_tokens
        self.k_max = k_max if k_max is not None else num_speculative_tokens
        if not 1 <= self.k_min <= self.k_max:
            raise ValueError(
                f"speculative K band invalid: need 1 <= spec_k_min "
                f"({self.k_min}) <= spec_k_max ({self.k_max})")
        if not self.k_min <= num_speculative_tokens <= self.k_max:
            raise ValueError(
                f"num_speculative_tokens ({num_speculative_tokens}) must "
                f"lie inside [spec_k_min={self.k_min}, "
                f"spec_k_max={self.k_max}] — it is the controller's "
                "initial K")

    def verify_with_model_config(self, model_config: ModelConfig) -> None:
        dv = self.draft_model_config.get_vocab_size()
        tv = model_config.get_vocab_size()
        if dv != tv:
            raise ValueError(
                f"Draft model vocab ({dv}) must match the target's ({tv}) "
                "— speculative tokens are compared by id.")


def _get_and_verify_dtype(hf_config, dtype: Union[str, "object"]) -> str:
    """Resolve dtype string. TPU-first: 'auto' maps fp16 checkpoints to
    bfloat16 (fp16 has no TPU advantage and risks overflow); fp32 stays fp32
    for golden tests (reference `config.py:506-554` keeps fp16)."""
    # Read `dtype` first (the current transformers field); fall back to a
    # raw __dict__ lookup for `torch_dtype` on older checkpoints/configs.
    # Never touch the `torch_dtype` attribute itself: on current
    # transformers it is a deprecated alias property whose mere ACCESS
    # logs "torch_dtype is deprecated! Use dtype instead!" at every
    # engine init.
    config_dtype = getattr(hf_config, "dtype", None)
    if config_dtype is None:
        config_dtype = hf_config.__dict__.get("torch_dtype")
    config_dtype = str(config_dtype).replace("torch.", "") if config_dtype else "float32"

    if isinstance(dtype, str):
        dtype = dtype.lower()
        if dtype == "auto":
            if config_dtype in ("float16", "half", "bfloat16"):
                return "bfloat16"
            return "float32"
        if dtype in ("half", "float16"):
            logger.warning(
                "float16 requested; using bfloat16 on TPU (same width, wider "
                "exponent, MXU-native).")
            return "bfloat16"
        if dtype in ("bfloat16", "bf16"):
            return "bfloat16"
        if dtype in ("float", "float32", "fp32"):
            return "float32"
    raise ValueError(f"Unknown dtype: {dtype}")


def _get_and_verify_max_len(hf_config, max_model_len: Optional[int]) -> int:
    """Resolve max model length from HF config keys (reference
    `config.py:557-612`), honoring rope-scaling factors."""
    derived = float("inf")
    keys = (
        "max_position_embeddings",
        "n_positions",
        "max_seq_len",
        "seq_length",
        "max_sequence_length",
        "model_max_length",
    )
    for key in keys:
        v = getattr(hf_config, key, None)
        if v is not None:
            derived = min(derived, v)
    if derived == float("inf"):
        if max_model_len is not None:
            return max_model_len
        derived = 2048
        logger.warning("No max length in HF config; defaulting to 2048.")

    rope_scaling = getattr(hf_config, "rope_scaling", None)
    if rope_scaling is not None:
        factor = rope_scaling.get("factor", 1.0)
        rtype = rope_scaling.get("type", rope_scaling.get("rope_type", ""))
        if rtype != "yarn":
            derived *= factor
        else:
            derived = rope_scaling.get(
                "original_max_position_embeddings", derived) * factor

    derived = int(derived)
    if max_model_len is None:
        return derived
    if max_model_len > derived:
        raise ValueError(
            f"max_model_len ({max_model_len}) is larger than the model's "
            f"derived maximum ({derived}).")
    return max_model_len
