"""CLI/engine argument plumbing.

Role parity: reference `vllm/engine/arg_utils.py` (EngineArgs :11,
add_cli_args :52, create_engine_configs :268, AsyncEngineArgs :303).
"""
from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

from intellillm_tpu.config import (CacheConfig, LoRAConfig, ModelConfig,
                                   ParallelConfig, SchedulerConfig)


@dataclass
class EngineArgs:
    model: str
    tokenizer: Optional[str] = None
    tokenizer_mode: str = "auto"
    trust_remote_code: bool = False
    seed: int = 0
    max_model_len: Optional[int] = None
    # Parallelism (mesh axes)
    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    # KV cache
    block_size: int = 16
    hbm_utilization: float = 0.90
    swap_space: float = 4.0  # GiB
    kv_cache_dtype: str = "auto"
    num_device_blocks_override: Optional[int] = None
    # Scheduler
    max_num_batched_tokens: Optional[int] = None
    max_num_seqs: int = 256
    max_paddings: int = 256
    scheduling_policy: str = "fcfs"
    sjf_starvation_s: Optional[float] = None
    predictor_path: Optional[str] = None
    num_decode_steps: int = 8
    min_block_table_width: int = 16
    enable_chunked_prefill: bool = True
    disable_chunked_prefill: bool = False
    replica_role: str = "mixed"
    disable_tenant_fairness: bool = False
    # Model
    dtype: str = "auto"
    load_format: str = "auto"
    revision: Optional[str] = None
    quantization: Optional[str] = None
    enforce_eager: bool = False
    # Speculative decoding (draft model + greedy verify). The adaptive
    # controller holds the live draft length K inside [spec_k_min,
    # spec_k_max]; both default to num_speculative_tokens (fixed K).
    speculative_model: Optional[str] = None
    num_speculative_tokens: int = 5
    spec_k_min: Optional[int] = None
    spec_k_max: Optional[int] = None
    # LoRA
    enable_lora: bool = False
    max_loras: int = 1
    max_lora_rank: int = 16
    lora_extra_vocab_size: int = 256
    lora_dtype: str = "auto"
    max_cpu_loras: Optional[int] = None
    # Logging
    disable_log_stats: bool = False
    # SLO telemetry (obs/slo.py): None -> INTELLILLM_SLO_*_MS env /
    # built-in defaults.
    slo_ttft_ms: Optional[float] = None
    slo_tpot_ms: Optional[float] = None
    # Device telemetry (obs/device_telemetry.py): None ->
    # INTELLILLM_HBM_HEADROOM_WARN env / built-in 0.05.
    hbm_headroom_warn: Optional[float] = None
    # Compute-efficiency telemetry (obs/efficiency.py): per-chip peak
    # FLOPs for the MFU gauge. None -> INTELLILLM_PEAK_FLOPS env / the
    # built-in per-chip table (NaN MFU when the chip is unknown).
    peak_flops: Optional[float] = None
    # Numerics sentinels (obs/numerics.py): per-step in-graph logit
    # statistics + anomaly quarantine. Opt-in — the enabled dispatch
    # carries an extra device output, so it is a distinct executable
    # family (warmed at boot). False also honours INTELLILLM_NUMERICS.
    enable_numerics: bool = False

    def __post_init__(self) -> None:
        if self.tokenizer is None:
            self.tokenizer = self.model

    @staticmethod
    def add_cli_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
        parser.add_argument("--model", type=str,
                            default="facebook/opt-125m")
        parser.add_argument("--tokenizer", type=str, default=None)
        parser.add_argument("--tokenizer-mode", type=str, default="auto",
                            choices=["auto", "slow"])
        parser.add_argument("--trust-remote-code", action="store_true")
        parser.add_argument("--seed", type=int, default=0)
        parser.add_argument("--max-model-len", type=int, default=None)
        parser.add_argument("--tensor-parallel-size", "-tp", type=int,
                            default=1)
        parser.add_argument("--data-parallel-size", "-dp", type=int,
                            default=1)
        parser.add_argument("--pipeline-parallel-size", "-pp", type=int,
                            default=1)
        parser.add_argument("--block-size", type=int, default=16,
                            choices=[8, 16, 32, 64, 128])
        parser.add_argument("--hbm-utilization", "--gpu-memory-utilization",
                            type=float, default=0.90, dest="hbm_utilization")
        parser.add_argument("--swap-space", type=float, default=4.0,
                            help="CPU swap space per chip (GiB)")
        parser.add_argument("--kv-cache-dtype", type=str, default="auto",
                            choices=["auto", "bfloat16", "fp8_e5m2"])
        parser.add_argument("--num-device-blocks-override", type=int,
                            default=None)
        parser.add_argument("--max-num-batched-tokens", type=int,
                            default=None)
        parser.add_argument("--max-num-seqs", type=int, default=256)
        parser.add_argument("--max-paddings", type=int, default=256)
        parser.add_argument("--scheduling-policy", type=str, default="fcfs",
                            help="fcfs | sjf | sjf_remaining")
        parser.add_argument("--sjf-starvation-s", type=float, default=None,
                            help="aging deadline for the SJF policies: a "
                            "waiting request older than this many seconds "
                            "is promoted to FCFS priority above every "
                            "un-promoted request, bounding max queue-wait "
                            "(default: disabled; ignored by fcfs; see "
                            "docs/scheduling.md)")
        parser.add_argument("--predictor-path", type=str, default=None,
                            help="response-length predictor checkpoint "
                            "loaded at engine boot when a non-FCFS policy "
                            "is selected (default: prompt-length "
                            "heuristic; see docs/scheduling.md)")
        parser.add_argument("--num-decode-steps", type=int, default=8,
                            help="decode iterations fused per device call")
        parser.add_argument("--min-block-table-width", type=int, default=16,
                            help="narrowest padded block table (in blocks) "
                            "a step program is compiled for; each width "
                            "bucket above it is one more executable a row "
                            "bucket, so a deployment of long prompts "
                            "raises it and warms up fewer programs")
        parser.add_argument("--replica-role", type=str, default="mixed",
                            choices=["mixed", "prefill", "decode"],
                            help="disaggregated-serving role: 'prefill' "
                            "finishes every request at prefill-complete "
                            "(first token) and pins the prompt prefix for "
                            "KV export; 'decode' imports prefilled KV and "
                            "runs pure decode; 'mixed' (default) does both "
                            "(see docs/routing.md)")
        parser.add_argument("--disable-chunked-prefill", action="store_true",
                            help="admit each prompt as a single "
                            "whole-prompt chunk (it must then fit "
                            "--max-num-batched-tokens whole). By default a "
                            "long prompt is split into token-budget-sized "
                            "chunks that ride along with decode rows in "
                            "mixed steps: running decodes are admitted "
                            "first, so a long prompt never stalls "
                            "generation, and --max-num-batched-tokens is "
                            "the per-step compute budget (default 512), "
                            "not a prompt-length ceiling")
        parser.add_argument("--disable-tenant-fairness", action="store_true",
                            help="turn off the per-tenant weighted "
                            "admission caps (seat + prefill-chunk-token "
                            "shares) that stop a noisy-neighbor tenant "
                            "from starving other tenants' decodes; with "
                            "one tenant the caps are inactive anyway "
                            "(see docs/multitenancy.md)")
        parser.add_argument("--dtype", type=str, default="auto",
                            choices=["auto", "bfloat16", "float32", "float16"])
        parser.add_argument("--load-format", type=str, default="auto",
                            choices=["auto", "safetensors", "pt", "dummy"],
                            help="dummy = random weights (bench/profiling "
                            "without a checkpoint)")
        parser.add_argument("--revision", type=str, default=None)
        parser.add_argument("--quantization", "-q", type=str, default=None)
        parser.add_argument("--enforce-eager", action="store_true")
        parser.add_argument("--enable-lora", action="store_true")
        parser.add_argument("--max-loras", type=int, default=1)
        parser.add_argument("--max-lora-rank", type=int, default=16)
        parser.add_argument("--lora-extra-vocab-size", type=int, default=256)
        parser.add_argument("--lora-dtype", type=str, default="auto")
        parser.add_argument("--max-cpu-loras", type=int, default=None)
        parser.add_argument("--disable-log-stats", action="store_true")
        parser.add_argument("--slo-ttft-ms", type=float, default=None,
                            help="time-to-first-token SLO for the goodput "
                            "gauge (default: INTELLILLM_SLO_TTFT_MS or "
                            "1000)")
        parser.add_argument("--slo-tpot-ms", type=float, default=None,
                            help="time-per-output-token SLO for the "
                            "goodput gauge (default: INTELLILLM_SLO_TPOT_MS "
                            "or 200)")
        parser.add_argument("--hbm-headroom-warn", type=float, default=None,
                            help="warn once per episode when the min "
                            "device HBM headroom ratio drops below this "
                            "(default: INTELLILLM_HBM_HEADROOM_WARN or "
                            "0.05)")
        parser.add_argument("--peak-flops", type=float, default=None,
                            help="per-chip peak FLOPs used as the MFU "
                            "denominator, e.g. 918e12 for v6e (default: "
                            "INTELLILLM_PEAK_FLOPS or a built-in "
                            "per-chip table; unknown chips report NaN)")
        parser.add_argument("--enable-numerics", action="store_true",
                            help="turn on the in-graph numerics "
                            "sentinels: per-step logit NaN/Inf/max-abs "
                            "statistics with anomaly quarantine "
                            "(equivalent to INTELLILLM_NUMERICS=1; see "
                            "docs/observability.md)")
        parser.add_argument("--speculative-model", type=str, default=None)
        parser.add_argument("--num-speculative-tokens", type=int,
                            default=5)
        parser.add_argument("--spec-k-min", type=int, default=None,
                            help="lower bound of the SLO-adaptive "
                            "speculative draft length K (default: "
                            "num_speculative_tokens, i.e. fixed K; see "
                            "docs/scheduling.md)")
        parser.add_argument("--spec-k-max", type=int, default=None,
                            help="upper bound of the SLO-adaptive "
                            "speculative draft length K; the boot warm-up "
                            "compiles one draft+teacher executable pair "
                            "per K in [spec-k-min, spec-k-max] (default: "
                            "num_speculative_tokens)")
        return parser

    @classmethod
    def from_cli_args(cls, args: argparse.Namespace) -> "EngineArgs":
        attrs = [f.name for f in dataclasses.fields(cls)]
        return cls(**{a: getattr(args, a) for a in attrs if hasattr(args, a)})

    def create_engine_configs(self):
        if self.slo_ttft_ms is not None or self.slo_tpot_ms is not None:
            from intellillm_tpu.obs import get_slo_tracker
            get_slo_tracker().configure(slo_ttft_ms=self.slo_ttft_ms,
                                        slo_tpot_ms=self.slo_tpot_ms)
        if self.hbm_headroom_warn is not None:
            from intellillm_tpu.obs import get_device_telemetry
            get_device_telemetry().configure(
                headroom_warn=self.hbm_headroom_warn)
        if self.peak_flops is not None:
            from intellillm_tpu.obs import get_efficiency_tracker
            get_efficiency_tracker().configure(peak_flops=self.peak_flops)
        if self.enable_numerics:
            # env-only enablement (INTELLILLM_NUMERICS) already landed
            # at tracker construction; the flag only ever turns it ON.
            from intellillm_tpu.obs import get_numerics_tracker
            get_numerics_tracker().configure(enabled=True)
        model_config = ModelConfig(
            model=self.model,
            tokenizer=self.tokenizer,
            tokenizer_mode=self.tokenizer_mode,
            trust_remote_code=self.trust_remote_code,
            dtype=self.dtype,
            load_format=self.load_format,
            seed=self.seed,
            revision=self.revision,
            max_model_len=self.max_model_len,
            quantization=self.quantization,
            enforce_eager=self.enforce_eager,
        )
        cache_config = CacheConfig(
            block_size=self.block_size,
            hbm_utilization=self.hbm_utilization,
            swap_space_gib=self.swap_space,
            cache_dtype=self.kv_cache_dtype,
            num_device_blocks_override=self.num_device_blocks_override,
            sliding_window=model_config.get_sliding_window(),
            window_group_size=model_config.get_window_group_size(),
        )
        parallel_config = ParallelConfig(
            tensor_parallel_size=self.tensor_parallel_size,
            data_parallel_size=self.data_parallel_size,
            pipeline_parallel_size=self.pipeline_parallel_size,
        )
        scheduler_config = SchedulerConfig(
            max_num_batched_tokens=self.max_num_batched_tokens,
            max_num_seqs=self.max_num_seqs,
            max_model_len=model_config.max_model_len,
            max_paddings=self.max_paddings,
            policy=self.scheduling_policy,
            num_decode_steps=self.num_decode_steps,
            min_block_table_width=self.min_block_table_width,
            enable_chunked_prefill=(self.enable_chunked_prefill
                                    and not self.disable_chunked_prefill),
            sjf_starvation_s=self.sjf_starvation_s,
            predictor_path=self.predictor_path,
            replica_role=self.replica_role,
            tenant_fairness=not self.disable_tenant_fairness,
        )
        lora_config = None
        if self.enable_lora:
            lora_config = LoRAConfig(
                max_lora_rank=self.max_lora_rank,
                max_loras=self.max_loras,
                max_cpu_loras=self.max_cpu_loras,
                lora_dtype=self.lora_dtype,
                lora_extra_vocab_size=self.lora_extra_vocab_size,
            )
            lora_config.verify_with_model_config(model_config)
            lora_config.verify_with_scheduler_config(scheduler_config)
        speculative_config = None
        if self.speculative_model is not None:
            import os

            from intellillm_tpu.config import SpeculativeConfig
            from intellillm_tpu.utils import parse_env_flag
            if parse_env_flag(os.environ.get("INTELLILLM_PIPELINE")) is True:
                # The draft+teacher round trip needs every substep's
                # sampled ids on host before the next dispatch, so there
                # is nothing to overlap — deferred-fetch pipelining and
                # speculative decoding are mutually exclusive (see
                # docs/scheduling.md). INTELLILLM_PIPELINE defaults on
                # and the engine quietly drops it under spec; an EXPLICIT
                # opt-in plus a draft model is a contradiction — fail at
                # config time instead of on the first decode step deep
                # inside the worker.
                raise ValueError(
                    "speculative decoding (--speculative-model) is "
                    "incompatible with pipelined/deferred dispatch: "
                    "INTELLILLM_PIPELINE=1 was set explicitly alongside "
                    "a draft model; unset it (the engine cannot overlap "
                    "fetches across the draft/verify round trip)")
            draft_mc = ModelConfig(
                model=self.speculative_model,
                tokenizer=self.speculative_model,
                dtype=self.dtype,
                load_format=self.load_format,
                seed=self.seed,
                max_model_len=model_config.max_model_len,
            )
            speculative_config = SpeculativeConfig(
                draft_mc, self.num_speculative_tokens,
                k_min=self.spec_k_min, k_max=self.spec_k_max)
            speculative_config.verify_with_model_config(model_config)
        return (model_config, cache_config, parallel_config, scheduler_config,
                lora_config, speculative_config)


@dataclass
class AsyncEngineArgs(EngineArgs):
    """Args for the async engine (reference arg_utils.py:303)."""
    engine_use_ray: bool = False  # accepted for CLI parity; no Ray on TPU
    disable_log_requests: bool = False
    max_log_len: Optional[int] = None

    @staticmethod
    def add_cli_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
        parser = EngineArgs.add_cli_args(parser)
        parser.add_argument("--disable-log-requests", action="store_true")
        parser.add_argument("--max-log-len", type=int, default=None)
        return parser
