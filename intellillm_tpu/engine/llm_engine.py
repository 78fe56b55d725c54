"""The central synchronous engine.

Role parity: reference `vllm/engine/llm_engine.py` (LLMEngine :34): owns
tokenizer, scheduler and the worker; `add_request` :372 / `step` :739 /
`abort_request` :430; beam-search fork/prune `_process_sequence_group_outputs`
:535; incremental detokenization `_decode_sequence` :878; stop checks
`_check_stop` :898; stats :815.

TPU redesign: `_run_workers` RPC fan-out (:946) is gone — a single Worker
owns the whole mesh; `_init_cache` keeps the same shape (profile → set
block counts → allocate pool → warm up).
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

import numpy as np
from typing import Dict, Iterable, List, Optional, Set, Tuple, Union

from intellillm_tpu.config import (CacheConfig, LoRAConfig, ModelConfig,
                                   ParallelConfig, SchedulerConfig)
from intellillm_tpu.core.scheduler import Scheduler, SchedulerOutputs
from intellillm_tpu.engine.arg_utils import EngineArgs
from intellillm_tpu.engine.metrics import StatLogger, Stats
from intellillm_tpu.logger import init_logger
from intellillm_tpu.obs import (get_alert_manager, get_boot_timeline,
                                get_compile_tracker, get_device_telemetry,
                                get_efficiency_tracker,
                                get_flight_recorder, get_heap,
                                get_metrics_history,
                                get_numerics_tracker, get_slo_tracker,
                                get_step_tracer, get_watchdog,
                                request_context)
from intellillm_tpu.outputs import RequestOutput
from intellillm_tpu.prediction import get_prediction_service
from intellillm_tpu.sampling_params import SamplingParams
from intellillm_tpu.sequence import (SamplerOutput, Sequence, SequenceGroup,
                                     SequenceGroupOutput, SequenceStatus)
from intellillm_tpu.transformers_utils.detokenizer import (
    detokenize_incrementally)
from intellillm_tpu.transformers_utils.tokenizer import TokenizerGroup
from intellillm_tpu.utils import Counter
from intellillm_tpu.worker.worker import Worker

logger = init_logger(__name__)

_LOG_STATS_INTERVAL = 5.0  # seconds

# step_pipelined: prefill-only steps that may be in flight per decode step
# in flight. One throttles admission: a ~410-token prompt step a 94 ms
# decode period admitted 4.3k prompt tokens/s where 128 closed-loop
# clients ask ~6k, 28 % of the seats stood empty and out_tok_s fell 6.5 %
# (PERF.md section 6, PR 29, chip run p29a); two kept every seat taken.
_PROMPT_STEPS_PER_DECODE_STEP = 2
# step_pipelined: continuations in a row whose rows would fit a smaller
# batch bucket before the loop drains to re-bucket them. A drain costs
# about one host pass with the device dry; a half-empty bucket costs a
# share of every step.
_REBUCKET_AFTER_CONTS = 4
# Why a prefill-only scheduling pass admitted nothing
# (SchedulerOutputs.blocked) -> the cause of the drain it asks for.
_ADMISSION_DRAINS = {"kv_watermark": "memory",
                     "needs_chunking": "chunked_prefill"}


class LLMEngine:

    def __init__(
        self,
        model_config: ModelConfig,
        cache_config: CacheConfig,
        parallel_config: ParallelConfig,
        scheduler_config: SchedulerConfig,
        lora_config: Optional[LoRAConfig] = None,
        speculative_config=None,
        log_stats: bool = True,
        length_predictor=None,
        skip_tokenizer_init: bool = False,
    ) -> None:
        # The collector's seconds count from here, the boot's included
        # (obs/heap.py).
        get_heap().watch()
        logger.info(
            "Initializing intellillm-tpu engine: model=%s dtype=%s tp=%d "
            "policy=%s max_model_len=%d", model_config.model,
            model_config.dtype, parallel_config.tensor_parallel_size,
            scheduler_config.policy, model_config.max_model_len)
        self.model_config = model_config
        self.cache_config = cache_config
        self.parallel_config = parallel_config
        self.scheduler_config = scheduler_config
        self.lora_config = lora_config
        self.log_stats = log_stats
        # IntelliLLM research hook: optional response-length predictor used
        # by SJF policies (reference `scheduler/predictor.py`; here wired
        # into add_request as a first-class component).
        self.length_predictor = length_predictor

        self.seq_counter = Counter()
        self.skip_tokenizer_init = skip_tokenizer_init
        if skip_tokenizer_init:
            self.tokenizer = None
        else:
            self._init_tokenizer()

        # A non-FCFS policy without an injected predictor auto-loads one
        # (checkpoint from --predictor-path, else the prompt-length
        # heuristic) so SJF never runs open-loop on absent predictions.
        if (self.length_predictor is None
                and scheduler_config.policy != "fcfs"):
            from intellillm_tpu.research.predictor import load_predictor
            self.length_predictor = load_predictor(
                scheduler_config.predictor_path,
                self.tokenizer.tokenizer if self.tokenizer else None)
        # Calibrated quantile predictions (prediction/): p50 orders the
        # SJF queue, p90 prices preemption victims; the finish hook below
        # feeds actual lengths back into the online calibrator.
        self._prediction = get_prediction_service().configure(
            self.length_predictor)

        self.speculative_config = speculative_config
        # A model with per-sequence state layers (a short convolution's
        # taps, a linear-attention layer's matrix a head:
        # `ModelConfig.get_layer_cache_kinds`) takes a state slot a
        # running sequence, and refuses what would need more of the state
        # than its newest value.
        self._stateful = model_config.get_num_state_layers() > 0
        if self._stateful:
            self._refuse_for_state_model(speculative_config)
            cache_config.num_state_slots = scheduler_config.max_num_seqs
        # A model with a sliding window, on every layer or on some
        # (`get_sliding_window` / `get_window_group_size`): its windowed
        # cache group keeps the last `window` tokens' blocks only, and
        # what would need the blocks it gave back is refused.
        self._window = (model_config.get_sliding_window()
                        or model_config.get_window_group_size())
        if self._window:
            self._refuse_for_window_model(speculative_config)
            # The most tokens a step writes ahead of its first query: a
            # prefill chunk or a K-step decode.
            cache_config.window_ahead_tokens = max(
                scheduler_config.max_num_batched_tokens,
                scheduler_config.num_decode_steps)
        if speculative_config is not None:
            # A family that cannot be speculated for says why itself.
            from intellillm_tpu.models import get_model_class
            architectures = getattr(model_config.hf_config,
                                    "architectures", None) or []
            refusal = getattr(get_model_class(architectures),
                              "speculation_refusal", None)
            if refusal:
                raise NotImplementedError(refusal)
            # One engine decode "step" = K draft proposals + the bonus
            # token; the scheduler must reserve K+1 KV slots per pass.
            scheduler_config.num_decode_steps = (
                speculative_config.num_speculative_tokens + 1)
            from intellillm_tpu.worker.spec_decode.spec_worker import (
                SpecDecodeWorker)
            self.worker = SpecDecodeWorker(
                model_config, parallel_config, scheduler_config,
                cache_config, lora_config,
                speculative_config=speculative_config)
        else:
            self.worker = Worker(model_config, parallel_config,
                                 scheduler_config, cache_config,
                                 lora_config)
        # Boot timeline (obs/boot.py): phase durations surface in
        # /health/detail — a persistent compile cache should show up as
        # a collapsed warm-up phase.
        self._boot = get_boot_timeline()
        # Before anything compiles: what jax traces and compiles from
        # here on is accounted by stage (obs/compile_tracker.py).
        get_compile_tracker().install_listeners()
        with self._boot.phase("init_model"):
            self.worker.init_model()
        with self._boot.phase("load_model"):
            self.worker.load_model()

        # Fused multi-step decode is incompatible with ALiBi (bias needs
        # the true query position per substep). Clamp K HERE so the
        # scheduler budgets lookahead slots consistently with what the
        # runner will actually execute — deciding only in the runner would
        # make the scheduler reserve blocks that are never consumed. (A
        # sliding window is no reason: the staged decode moves the
        # window's lower edge a token a substep.)
        from intellillm_tpu.layers.attention import (model_attention_windows,
                                                     model_uses_alibi)
        if self._window and not model_attention_windows(self.worker.model):
            raise NotImplementedError(
                f"The config gives a sliding window of {self._window} "
                f"tokens, but {type(self.worker.model).__name__} builds no "
                f"attention layer with a window: the windowed cache group "
                f"would give back blocks its layers still read.")
        if scheduler_config.num_decode_steps > 1 and model_uses_alibi(
                self.worker.model):
            if speculative_config is not None:
                raise ValueError(
                    "Speculative decoding needs the fused multi-step "
                    "decode program, which ALiBi models cannot use.")
            logger.info("Clamping num_decode_steps %d -> 1 (model uses "
                        "ALiBi).", scheduler_config.num_decode_steps)
            scheduler_config.num_decode_steps = 1

        # Chunked prefill + speculative decoding compose since the
        # per-row spec plan: chunk rows ride the target's mixed dispatch
        # (their KV mirrored into the draft pool each step) while
        # eligible decode rows run the draft+verify pass in the same
        # scheduler round — no force-disable needed.

        # Compute-efficiency ledger (obs/efficiency.py): derive the
        # analytic FLOPs model and this chip's peak FLOPs BEFORE warm-up
        # (inside _init_cache) so its dispatches hit a configured tracker
        # — warm-up wraps itself in warmup() to stay excluded.
        self._efficiency = get_efficiency_tracker()
        try:
            self._efficiency.configure_model(model_config)
        except Exception:
            logger.warning("Efficiency telemetry unavailable.",
                           exc_info=True)
        # Per-kernel cost ledger (obs/kernels.py): the runner's dispatch
        # hook feeds it; the engine only marks step boundaries for the
        # cost-model MFU window.
        from intellillm_tpu.obs import get_kernel_ledger
        self._kernel_ledger = get_kernel_ledger()

        self._init_cache()

        # Observability (docs/observability.md): step-phase tracer and the
        # per-request flight recorder. The last drained step breakdown is
        # kept on the engine so tests and benches can read it even with
        # log_stats off.
        self._tracer = get_step_tracer()
        self._flight = get_flight_recorder()
        self._numerics = get_numerics_tracker()
        # Serializes KV export/import against device stepping: the async
        # engine runs step() on an executor thread while /kv/* handlers
        # call export_kv/import_kv from the event loop (also via executor)
        # — both re-bind cache_engine.device_cache entries, so unguarded
        # concurrency loses one side's writes.
        self._kv_transfer_lock = threading.Lock()
        self._slo = get_slo_tracker()
        self.last_step_phases: dict = {}
        self.last_step_time: float = 0.0
        self.last_step_gap: Optional[float] = None

        # Device/HBM telemetry (obs/device_telemetry.py): install the
        # static memory ledger and start the HBM poller. Best-effort —
        # telemetry must never block engine startup.
        self._device_telemetry = get_device_telemetry()
        try:
            self._device_telemetry.set_ledger(self.worker.memory_ledger())
        except Exception:
            logger.warning("Memory ledger unavailable.", exc_info=True)
        self._device_telemetry.attach()

        self.scheduler = Scheduler(scheduler_config, cache_config, lora_config)
        # The block manager's per-sequence state moves (swap, fork) reach
        # the worker through this one list: the manager appends in the
        # order it decides them, the worker runs and clears them before
        # its next device step, whichever call dispatches it.
        self.worker.state_ops = self.scheduler.block_manager.state_ops
        # Per-row speculative scheduling: eligible decode rows reserve
        # K+1 slots and join SchedulerOutputs.spec_plan.
        self.scheduler.spec_decode_enabled = speculative_config is not None
        self.stat_logger = StatLogger(
            local_interval=_LOG_STATS_INTERVAL,
            labels=dict(model_name=model_config.model)) if log_stats else None

        # Pipelined stepping (step_pipelined): keep up to `depth` device
        # steps dispatched-but-unfetched so the device→host fetch and
        # host post-processing overlap with device compute.
        # INTELLILLM_PIPELINE=0 disables.
        import os as _os
        from intellillm_tpu.utils import pipeline_enabled_env
        # Speculative decoding owns its own dispatch pattern (draft +
        # teacher-forced verify per step) — no pipelined continuations.
        # Chunked mode pipelines too: steady-state decode runs the fused
        # continuation programs, and a sequence mid-prefill over several
        # chunks gets them as prompt steps beside the continuations.
        self.pipeline_enabled = (pipeline_enabled_env()
                                 and speculative_config is None)
        self._pipeline_depth = max(
            1, int(_os.environ.get("INTELLILLM_PIPELINE_DEPTH", "2")))
        self._inflight: deque = deque()
        self._pending_outputs: List[RequestOutput] = []
        # Sequences that have been given a row of a decode batch (fresh
        # or by continuation) and are not known to be finished: a running
        # sequence outside this set is a joiner (see _dispatch_cont).
        self._decode_members: Set[int] = set()
        # Why the pipeline is draining to a fresh schedule, if it is
        # (intellillm_pipeline_drains_total{cause}).
        self._drain_cause: Optional[str] = None
        # Consecutive continuations whose rows would have fitted a
        # smaller batch bucket than the one in flight.
        self._roomy_conts = 0

        # Stall watchdog (obs/watchdog.py): heartbeat at every step
        # boundary; the monitor thread uses these callbacks to decide
        # whether silence means "idle" or "wedged" and to enrich the
        # stall report.
        self._watchdog = get_watchdog()
        self._watchdog.attach(
            has_work=self._has_work,
            queue_depths=lambda: {
                "waiting": len(self.scheduler.waiting),
                "running": len(self.scheduler.running),
                "swapped": len(self.scheduler.swapped),
            },
            kv_usage=self.kv_cache_usage)

        # Metrics history + alert rules (obs/history.py, obs/alerts.py):
        # the sampler snapshots every intellillm_* gauge/counter on an
        # interval; the alert manager re-evaluates its rule set after
        # each tick. Attached last so the first sample sees a fully
        # initialized engine; boot is marked complete here.
        self._history = get_metrics_history()
        self._alerts = get_alert_manager()
        self._alerts.attach(self._history)
        self._history.attach()
        self._boot.mark_complete()
        # Everything alive now lives as long as the server: out of the
        # collector's sight with it, and an earlier engine's heap given
        # back (obs/heap.py).
        get_heap().engine_stands()

    def kv_cache_usage(self) -> dict:
        """KV-cache fill fractions (device HBM + CPU swap), 0..1."""
        num_total = self.cache_config.num_device_blocks
        num_free = self.scheduler.block_manager.get_num_free_device_blocks()
        num_total_cpu = self.cache_config.num_cpu_blocks
        free_cpu = self.scheduler.block_manager.get_num_free_cpu_blocks()
        return {
            "device": round(1.0 - num_free / max(num_total, 1), 4),
            "cpu": round(1.0 - free_cpu / num_total_cpu, 4)
            if num_total_cpu > 0 else 0.0,
        }

    # --- disaggregated KV transfer (docs/routing.md "Disaggregated
    # roles"): a prefill replica exports the paged KV blocks behind a
    # computed prompt prefix; a decode replica imports them into its own
    # pool as a pre-computed prefix, so requests carrying the matching
    # prefix_pos decode with zero prefill recompute. ---------------------

    def export_kv(self, token_ids: List[int], lora_int_id: int = 0) -> bytes:
        """Serialize the computed KV prefix for `token_ids` (truncated to
        a block multiple) into a content-addressed wire payload."""
        from intellillm_tpu.affinity import affinity_key, truncate_to_block
        from intellillm_tpu.obs.kv_transfer import get_kv_transfer_stats
        from intellillm_tpu.worker.kv_transfer import (make_handle,
                                                       serialize_handle)
        ids = truncate_to_block(token_ids, self.cache_config.block_size)
        if not ids:
            raise ValueError(
                "prompt is shorter than one KV block; nothing to export")
        key = affinity_key(ids, lora_int_id)
        prefix = self.scheduler.prefix_pool.prefixes.get(key)
        if prefix is None or not prefix.computed or not prefix.allocated:
            raise KeyError(
                f"prefix {key:#018x} is not computed on this replica")
        t0 = time.monotonic()
        ce = self.worker.cache_engine
        block_numbers = prefix.get_block_numbers()
        with self._kv_transfer_lock:
            layers = ce.export_blocks(block_numbers)
        handle = make_handle(list(ids), lora_int_id,
                             block_size=ce.block_size,
                             num_layers=ce.num_layers,
                             num_kv_heads=ce.num_kv_heads,
                             head_size=ce.head_size,
                             dtype=ce.dtype.name,
                             num_blocks=len(block_numbers))
        payload = serialize_handle(handle, layers)
        get_kv_transfer_stats().record("export", len(block_numbers),
                                       len(payload), time.monotonic() - t0)
        self._flight.record(f"kv:{key:#018x}", "kv_export",
                            detail=f"blocks={len(block_numbers)} "
                            f"bytes={len(payload)}")
        return payload

    def export_kv_for_prompt(self, prompt: str, lora_int_id: int = 0) -> bytes:
        """Export the KV prefix a prefill-role add_request() pinned for
        `prompt`. Uses the same ``((len - 1) // block_size) * block_size``
        alignment as the auto-pin: for prompts that are an exact block
        multiple, the last block holds the boundary token's KV from the
        handoff sample and is NOT part of the pinned prefix."""
        ids = self.tokenizer.encode(prompt, "kv-export", None)
        bs = self.cache_config.block_size
        aligned = ((len(ids) - 1) // bs) * bs
        if aligned <= 0:
            raise ValueError(
                "prompt is shorter than one KV block; nothing to export")
        return self.export_kv(ids[:aligned], lora_int_id)

    def import_kv(self, payload: bytes) -> dict:
        """Install an exported KV payload as a computed prefix in this
        replica's pool. Idempotent: re-importing a present prefix is a
        no-op (reported as imported=False)."""
        from intellillm_tpu.obs.kv_transfer import get_kv_transfer_stats
        from intellillm_tpu.worker.kv_transfer import deserialize_handle
        t0 = time.monotonic()
        handle, layers = deserialize_handle(payload)
        ce = self.worker.cache_engine
        mine = dict(block_size=ce.block_size, num_layers=ce.num_layers,
                    num_kv_heads=ce.num_kv_heads, head_size=ce.head_size,
                    dtype=ce.dtype.name)
        theirs = dict(block_size=handle.block_size,
                      num_layers=handle.num_layers,
                      num_kv_heads=handle.num_kv_heads,
                      head_size=handle.head_size, dtype=handle.dtype)
        if mine != theirs:
            raise ValueError(
                f"KV payload geometry {theirs} does not match this "
                f"replica's cache {mine}")
        prefix = self.scheduler.prefix_pool.add_or_get_prefix(
            handle.token_ids, handle.lora_int_id)
        assert prefix is not None and prefix.hash == handle.key
        if prefix.computed or prefix.allocated:
            # Already present (computed) or a local group is mid-prefill
            # on it (allocated): scattering imported blocks on top would
            # race the local prefill — skip, the KV is/will be there.
            return {"key": handle.key, "imported": False,
                    "num_blocks": prefix.get_num_blocks(),
                    "prefix_pos": len(handle.token_ids)}
        bm = self.scheduler.block_manager
        if not bm.can_allocate_prefix_blocks(handle.num_blocks):
            raise RuntimeError(
                f"cannot import prefix {handle.key:#018x}: "
                f"{handle.num_blocks} blocks would breach the allocation "
                "watermark")
        blocks = bm.allocate_prefix_blocks(handle.num_blocks)
        with self._kv_transfer_lock:
            ce.import_blocks(layers, [b.block_number for b in blocks])
        prefix.set_block_table(blocks)
        prefix.computed = True
        get_kv_transfer_stats().record("import", handle.num_blocks,
                                       len(payload), time.monotonic() - t0)
        self._flight.record(f"kv:{handle.key:#018x}", "kv_import",
                            detail=f"blocks={handle.num_blocks} "
                            f"bytes={len(payload)}")
        # prefix_pos is what a /generate request must carry to decode on
        # top of this prefix (replica token space, block-aligned).
        return {"key": handle.key, "imported": True,
                "num_blocks": handle.num_blocks,
                "prefix_pos": len(handle.token_ids)}

    # --- multi-tenant adapter lifecycle (docs/multitenancy.md): the API
    # servers' POST /tenants/{id}/adapter lands here. Error conventions
    # mirror the KV transfer handlers: ValueError -> 400, KeyError -> 404,
    # RuntimeError -> 409. ------------------------------------------------

    def load_lora_adapter(
        self,
        tenant_id: str,
        lora_name: str,
        lora_int_id: int,
        lora_local_path: str,
        weight: float = 1.0,
        token_share_cap: Optional[float] = None,
    ) -> dict:
        """Register `tenant_id` and hot-load its adapter: validate the
        checkpoint and warm the worker's host LRU so the tenant's first
        request doesn't pay the disk read mid-batch. Device slot
        activation stays per-step (set_active_loras). Re-posting the same
        tenant updates its fairness knobs in place."""
        from intellillm_tpu.lora.request import LoRARequest
        from intellillm_tpu.tenancy import TenantSpec, get_tenant_registry
        req = None
        if lora_int_id:
            if self.worker.lora_manager is None:
                raise RuntimeError(
                    "LoRA is not enabled on this engine (start with "
                    "--enable-lora)")
            req = LoRARequest(lora_name=lora_name, lora_int_id=lora_int_id,
                              lora_local_path=lora_local_path)
        spec = TenantSpec(tenant_id, lora_request=req, weight=weight,
                          token_share_cap=token_share_cap)
        # Register FIRST so the load/evict churn counters the hot-load
        # emits attribute to the tenant, not the adapter-<id> fallback.
        registry = get_tenant_registry()
        old = registry.get(tenant_id)
        registry.register(spec)
        info = {"lora_int_id": 0, "active": False}
        if req is not None:
            try:
                with self._kv_transfer_lock:
                    info = self.worker.lora_manager.load_adapter(req)
            except Exception:
                # Roll the registration back (or restore the previous
                # spec) so a bad checkpoint doesn't leave a
                # half-registered tenant.
                if old is not None:
                    registry.register(old)
                else:
                    registry.unregister(tenant_id)
                raise
        return {"tenant": tenant_id, "weight": weight,
                "token_share_cap": token_share_cap, **info}

    def unload_lora_adapter(self, tenant_id: str) -> dict:
        """Unregister `tenant_id` and drop its adapter from the device
        slot table and host cache. In-flight requests already holding the
        adapter's stacked weights finish on whatever slot data is
        resident; new requests naming the adapter re-load from disk."""
        from intellillm_tpu.tenancy import get_tenant_registry
        registry = get_tenant_registry()
        spec = registry.get(tenant_id)
        if spec is None:
            raise KeyError(f"unknown tenant {tenant_id!r}")
        # Unload BEFORE unregistering: the eviction event the unload
        # emits resolves through the registry for tenant attribution.
        if spec.lora_int_id and self.worker.lora_manager is not None:
            with self._kv_transfer_lock:
                self.worker.lora_manager.unload_adapter(spec.lora_int_id)
        registry.unregister(tenant_id)
        return {"tenant": tenant_id, "lora_int_id": spec.lora_int_id,
                "unloaded": True}

    # --- init ------------------------------------------------------------

    def _refuse_for_state_model(self, speculative_config) -> None:
        """Start-up refusals of a model with "state" layers, each naming
        the mechanism it lacks."""
        if speculative_config is not None:
            raise NotImplementedError(
                "Speculative decoding is not supported for a model with "
                "per-sequence recurrent state: a rejected draft token "
                "has already advanced the state, and the state pool keeps "
                "no earlier value to roll back to.")
        role = self.scheduler_config.replica_role
        if role != "mixed":
            raise NotImplementedError(
                f"replica_role={role!r} (KV export/import, "
                f"worker/kv_transfer.py) is not supported for a model with "
                f"per-sequence recurrent state: the IKV1 payload carries "
                f"K/V blocks of a prompt prefix and no state snapshot at "
                f"the prefix's end.")

    def _refuse_for_window_model(self, speculative_config) -> None:
        """Start-up refusals of a model with a sliding-window cache group,
        each naming the mechanism it lacks."""
        if speculative_config is not None:
            raise NotImplementedError(
                "Speculative decoding is not supported for a model with a "
                "sliding-window cache group: the teacher-forced decode "
                "program takes one block table a row and no window, and a "
                "draft model's pool has no window group.")
        role = self.scheduler_config.replica_role
        if role != "mixed":
            raise NotImplementedError(
                f"replica_role={role!r} (KV export/import, "
                f"worker/kv_transfer.py) is not supported for a model with "
                f"a sliding-window cache group: the IKV1 payload carries "
                f"one table of blocks a prefix, and the window group has "
                f"given back the blocks of a prefix longer than the window.")

    _WINDOW_PREFIX_REFUSAL = (
        "Prefix reuse (prefix_pos, prefix.py and the prefix pool) is not "
        "supported for a model with a sliding-window cache group: the "
        "window group gives back the blocks of a prefix longer than the "
        "window, so a later request could not attend to them (it would "
        "need the pool to pin a window's worth of blocks a prefix).")

    _PREFIX_REFUSAL = (
        "Prefix reuse (prefix_pos, prefix.py and the prefix pool) is not "
        "supported for a model with per-sequence recurrent state: a "
        "request that skips a cached prefix needs the state as it stood at "
        "the prefix's end, and the pool keeps K/V blocks only (it would "
        "need state snapshots).")

    def _init_tokenizer(self, **kwargs) -> None:
        self.tokenizer = TokenizerGroup(
            self.model_config.tokenizer,
            enable_lora=bool(self.lora_config),
            tokenizer_mode=self.model_config.tokenizer_mode,
            trust_remote_code=self.model_config.trust_remote_code,
            revision=self.model_config.revision,
            **kwargs)

    def _init_cache(self) -> None:
        """Profile → block counts → allocate pool (reference :283-342)."""
        with self._boot.phase("cache_init"):
            self._init_cache_pool()
        with self._boot.phase("warmup_compile"):
            self.worker.warm_up_model()
        # Structured warm-up outcome (executable count + wall seconds) in
        # the boot timeline: serve_bench reads it off /health/detail and
        # bench.py reads it in-process, so the "<30s, mixed family only"
        # boot criterion is machine-checkable rather than log-grepped.
        stats = getattr(self.worker, "warmup_stats", None)
        if stats is not None:
            self._boot.set_info("warmup", dict(stats))
        cc = self.cache_config
        from intellillm_tpu.worker.cache_engine import CacheEngine
        mc = self.model_config
        state_slot_bytes = CacheEngine.get_state_slot_size(mc)
        # One layer's share of a block (either group: the rows are alike).
        layer_block_bytes = CacheEngine.get_layer_block_size(
            cc.block_size, cc.cache_dtype, mc)
        self._boot.set_info("cache", {
            "num_device_blocks": cc.num_device_blocks,
            "num_cpu_blocks": cc.num_cpu_blocks,
            "profiled": cc.num_device_blocks_override is None,
            "block_size": cc.block_size,
            "cache_dtype": cc.cache_dtype,
            # "latent": one pool a layer holding [c_kv | k_rope] a token;
            # "kv": a K pool and a V pool of per-head vectors.
            "kind": "latent" if mc.get_latent_cache_width() else "kv",
            "pools_per_layer": mc.get_cache_row_shape()[0],
            # Device bytes one token takes in one layer's cache (lane
            # padding included): block bytes / block size / layers with
            # a pool.
            "bytes_per_token_per_layer": layer_block_bytes // cc.block_size,
            # Layers by what they cache, and the per-sequence state beside
            # the paged pools (0 slots for a model without state layers).
            "kv_layers": mc.get_num_kv_layers(),
            "state_layers": mc.get_num_state_layers(),
            "num_state_slots": cc.num_state_slots,
            "state_slot_bytes": state_slot_bytes,
            # intellillm_cache_bytes{kind}: the device pools' sizes.
            "bytes": self._efficiency.record_cache_bytes(
                kv=(layer_block_bytes * mc.get_num_kv_layers()
                    * cc.num_device_blocks),
                kv_window=(layer_block_bytes * mc.get_num_window_layers()
                           * cc.num_window_device_blocks),
                state=state_slot_bytes * cc.num_state_slots),
            # The cache groups: layers that hold a pool of it, layers
            # that read one (the holders, and layers that hold nothing
            # and walk another layer's pool), blocks and tokens. A
            # window group (layers with a sliding window beside layers
            # that keep the whole context) has pools of its own.
            "groups": {
                "kv": {"layers": mc.get_num_kv_layers(),
                       "readers": mc.get_cache_walkers()[0],
                       "window": mc.get_sliding_window(),
                       "num_device_blocks": cc.num_device_blocks,
                       "tokens": cc.num_device_blocks * cc.block_size},
                "kv_window": {
                    "layers": mc.get_num_window_layers(),
                    "readers": mc.get_cache_walkers()[1],
                    "window": mc.get_window_group_size(),
                    "num_device_blocks": cc.num_window_device_blocks,
                    "tokens": cc.num_window_device_blocks * cc.block_size},
            },
        })
        from intellillm_tpu.utils import runtime_facts
        self._boot.set_info("runtime", runtime_facts())

    def _init_cache_pool(self) -> None:
        cc = self.cache_config
        if cc.num_device_blocks_override is not None:
            num_device = cc.num_device_blocks_override
            # The host swap pool is plain numpy: size it by logical bytes
            # (get_cache_block_size reports lane-padded DEVICE bytes).
            from intellillm_tpu.worker.cache_engine import CacheEngine
            logical = CacheEngine.get_logical_cache_block_size(
                cc.block_size, cc.cache_dtype, self.model_config,
                cc.window_ahead_tokens)
            num_cpu = max(int(cc.swap_space_bytes // logical), 1)
        else:
            num_device, num_cpu = self.worker.profile_num_available_blocks(
                block_size=cc.block_size,
                hbm_utilization=cc.hbm_utilization,
                cpu_swap_space=cc.swap_space_bytes,
                cache_dtype=cc.cache_dtype,
            )
        if num_device <= 0:
            raise ValueError(
                "No available memory for the KV cache blocks. Try increasing "
                "hbm_utilization.")
        max_seq_len = cc.block_size * num_device
        if self.model_config.max_model_len > max_seq_len:
            raise ValueError(
                f"The model's max seq len ({self.model_config.max_model_len}) "
                f"is larger than the maximum tokens that can be stored in the "
                f"KV cache ({max_seq_len}). Increase hbm_utilization or "
                "decrease max_model_len.")
        cc.num_device_blocks = num_device
        cc.num_cpu_blocks = num_cpu
        cc.size_window_group(self.model_config)
        logger.info("KV cache: %d device blocks, %d CPU (swap) blocks",
                    num_device, num_cpu)
        if cc.num_window_device_blocks:
            logger.info("Window group: %d device blocks, %d CPU blocks",
                        cc.num_window_device_blocks,
                        cc.num_window_cpu_blocks)
        self.worker.init_cache_engine(cc)
        # Per-block byte sizes for the absolute used/total figures in
        # Stats (physical device bytes; unpadded host bytes for swap).
        from intellillm_tpu.worker.cache_engine import CacheEngine
        self._kv_block_bytes = CacheEngine.get_cache_block_size(
            cc.block_size, cc.cache_dtype, self.model_config,
            self.parallel_config, cc.window_ahead_tokens)
        self._cpu_block_bytes = CacheEngine.get_logical_cache_block_size(
            cc.block_size, cc.cache_dtype, self.model_config,
            cc.window_ahead_tokens)

    @classmethod
    def from_engine_args(cls, engine_args: EngineArgs,
                         **kwargs) -> "LLMEngine":
        # `engine_init` keeps what the phases inside the constructor
        # leave: the configs, the tokenizer, the scheduler.
        with get_boot_timeline().phase("engine_init"):
            configs = engine_args.create_engine_configs()
            return cls(*configs,
                       log_stats=not engine_args.disable_log_stats,
                       **kwargs)

    # --- requests ---------------------------------------------------------

    def add_request(
        self,
        request_id: str,
        prompt: Optional[str],
        sampling_params: SamplingParams,
        prompt_token_ids: Optional[List[int]] = None,
        arrival_time: Optional[float] = None,
        lora_request=None,
        prefix_pos: Optional[int] = None,
        predicted_len: Optional[int] = None,
    ) -> None:
        if arrival_time is None:
            arrival_time = time.monotonic()
        if lora_request is not None and not self.lora_config:
            raise ValueError(
                f"Got lora_request {lora_request} but LoRA is not enabled "
                "(set enable_lora=True / --enable-lora)")
        if lora_request is not None and self.worker.lora_manager is not None:
            # Fail a bad adapter at admission, not mid-step for the batch.
            self.worker.lora_manager.validate_request(lora_request)
        self._validate_sampling_params(sampling_params)
        if prompt_token_ids is None:
            with request_context(request_id):
                prompt_token_ids = self.tokenizer.encode(prompt, request_id,
                                                         lora_request)

        block_size = self.cache_config.block_size
        if self.scheduler_config.replica_role == "prefill":
            # Prefill role: pin the block-aligned prompt prefix so its
            # blocks survive past request completion for export. The
            # router ends the prefill leg at the first token by sending
            # max_tokens=1 — not enforced here, because on decode-replica
            # failover the router replays the FULL request on a prefill-
            # capable replica and needs the complete output.
            if (prefix_pos is None
                    and sampling_params.prompt_logprobs is None
                    and not self._window):
                aligned = ((len(prompt_token_ids) - 1) // block_size
                           ) * block_size
                if aligned > 0:
                    prefix_pos = aligned
        seq_id = next(self.seq_counter)
        seq = Sequence(seq_id, prompt, prompt_token_ids, block_size,
                       lora_request)

        prefix = None
        if prefix_pos is not None and self._stateful:
            raise NotImplementedError(self._PREFIX_REFUSAL)
        if (prefix_pos is not None
                and sampling_params.prompt_logprobs is not None):
            # Cached-prefix positions have no hidden states in the prefill.
            raise ValueError(
                "prompt_logprobs cannot be combined with prefix_pos.")
        if prefix_pos is not None:
            if self._window:
                raise NotImplementedError(self._WINDOW_PREFIX_REFUSAL)
            prefix = self.scheduler.prefix_pool.add_or_get_prefix(
                prompt_token_ids[:prefix_pos],
                lora_request.lora_int_id if lora_request else 0)

        # Oracle-supplied predicted_len wins (and is never calibrated);
        # otherwise the service returns calibrated quantiles and handles
        # predictor failures (log once per episode + failure counter).
        prediction = None
        if predicted_len is None and self._prediction.enabled:
            prediction = self._prediction.predict(request_id, prompt,
                                                  prompt_token_ids)
            if prediction is not None:
                predicted_len = prediction.p50

        seq_group = SequenceGroup(request_id, [seq], sampling_params,
                                  arrival_time, lora_request, prefix,
                                  predicted_len)
        if prediction is not None:
            seq_group.predicted_len_p90 = prediction.p90
            seq_group.predicted_len_raw = prediction.raw
        self._flight.record(request_id, "arrived",
                            detail=f"prompt_tokens={len(prompt_token_ids)}")
        self.scheduler.add_seq_group(seq_group)

    # Sampler shape-bucket limits (see layers/sampler.py LOGPROB_K_BUCKETS
    # and model_runner._SAMPLE_BUCKETS): enforced here so an unsupported
    # request fails at submission, not mid-step for the whole batch.
    _MAX_BEST_OF_RANDOM = 16
    _MAX_BEAM_WIDTH = 64

    def _validate_sampling_params(self, sp: SamplingParams) -> None:
        if sp.use_beam_search:
            if sp.best_of > self._MAX_BEAM_WIDTH:
                raise ValueError(
                    f"beam width {sp.best_of} exceeds the supported maximum "
                    f"of {self._MAX_BEAM_WIDTH}.")
        elif sp.best_of > self._MAX_BEST_OF_RANDOM:
            raise ValueError(
                f"best_of {sp.best_of} exceeds the supported maximum of "
                f"{self._MAX_BEST_OF_RANDOM}.")
        for proc in sp.logits_processors:
            if not callable(proc):
                raise ValueError(
                    "logits_processors must be callables taking "
                    "(output_token_ids, logits_row numpy array) and "
                    "returning a logits row.")
        if sp.logits_processors and sp.temperature >= 1e-5:
            # Known divergence for reference migrators (PARITY.md §2.2):
            # processor-bearing rows sample on the HOST from a numpy
            # Gumbel stream, so at temperature>0 the tokens differ from
            # the same request without processors (greedy is identical).
            logger.warning(
                "Request attaches logits_processors with temperature>0: "
                "sampling uses the host RNG stream for this request, so "
                "tokens will differ from an identical processor-free "
                "request (greedy output is unaffected).")
        from intellillm_tpu.layers.sampler import LOGPROB_K_BUCKETS
        if (sp.prompt_logprobs is not None
                and sp.prompt_logprobs > LOGPROB_K_BUCKETS[-1]):
            raise ValueError(
                f"prompt_logprobs must be <= {LOGPROB_K_BUCKETS[-1]} "
                "(sampler panel buckets).")

    def abort_request(self, request_id: Union[str, Iterable[str]]) -> None:
        self.scheduler.abort_seq_group(request_id)

    def get_model_config(self) -> ModelConfig:
        return self.model_config

    # --- profiling (SURVEY §5: jax.profiler trace hooks — an improvement
    # over the reference, which has no tracer) ----------------------------

    def start_profile(self,
                      trace_dir: str = "/tmp/intellillm-trace"
                      ) -> Optional[str]:
        """Begin a jax.profiler trace covering subsequent engine steps.
        View with TensorBoard or xprof. Returns the trace directory, or
        None if a trace is already running (jax allows only one) or the
        profiler refuses to start — never raises into the caller (the
        admin endpoint maps None to a 409, not a 500 that could take
        the engine thread down with it).

        Every trace carries a mandatory max-duration watchdog: a trace
        left running degrades serving and grows without bound on disk,
        so after INTELLILLM_PROFILER_MAX_S (default 120s) it is stopped
        automatically, as if stop_profile had been called."""
        import jax
        import threading
        if not hasattr(self, "_profile_lock"):
            self._profile_lock = threading.Lock()
        with self._profile_lock:
            if getattr(self, "_profiling", False):
                logger.warning("Profiling already running; ignoring start.")
                return None
            # jax's default also traces every Python call, under which
            # the host runs 1.5-3x slower and a trace shows device idle
            # the untraced program does not have. The host tracer stays
            # at its default level: it records the spans' annotations.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            try:
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
            except Exception:
                # e.g. a trace started outside the engine's bookkeeping,
                # or an unwritable dir — a busy/bad-request condition,
                # not an engine fault.
                logger.warning("jax.profiler.start_trace(%s) failed; "
                               "refusing the profile request.", trace_dir,
                               exc_info=True)
                return None
            self._profiling = True
            # Spans enter TraceAnnotations while the trace runs, so its
            # host plane carries the phases (obs/tracing.py).
            self._tracer.set_profiling(True)
            max_s = self._profiler_max_s()
            timer = threading.Timer(max_s, self._profile_expired, (max_s,))
            timer.daemon = True
            timer.start()
            self._profile_timer = timer
        logger.info("Profiling started; trace dir: %s (auto-stop after "
                    "%.0fs)", trace_dir, max_s)
        return trace_dir

    @staticmethod
    def _profiler_max_s() -> float:
        raw = os.environ.get("INTELLILLM_PROFILER_MAX_S")
        try:
            value = float(raw) if raw else 120.0
        except ValueError:
            logger.warning("Ignoring invalid INTELLILLM_PROFILER_MAX_S=%r "
                           "(want seconds).", raw)
            value = 120.0
        return value if value > 0 else 120.0

    def _profile_expired(self, max_s: float) -> None:
        logger.warning("Profiling exceeded INTELLILLM_PROFILER_MAX_S "
                       "(%.0fs); stopping the trace automatically.", max_s)
        self.stop_profile()

    def stop_profile(self) -> None:
        import jax
        import threading
        if not hasattr(self, "_profile_lock"):
            self._profile_lock = threading.Lock()
        # Serialize start/stop: stop_trace runs for seconds (it writes the
        # whole trace) and may be called from an executor thread, the
        # watchdog timer thread, or both racing — the _profiling flag
        # under the lock makes the stop exactly-once.
        with self._profile_lock:
            if not getattr(self, "_profiling", False):
                return
            self._profiling = False
            self._tracer.set_profiling(False)
            timer = getattr(self, "_profile_timer", None)
            if timer is not None:
                timer.cancel()
                self._profile_timer = None
            try:
                jax.profiler.stop_trace()
            except Exception:
                logger.warning("jax.profiler.stop_trace() failed.",
                               exc_info=True)
        logger.info("Profiling stopped.")

    def get_num_unfinished_requests(self) -> int:
        return self.scheduler.get_num_unfinished_seq_groups()

    def has_unfinished_requests(self) -> bool:
        return self.scheduler.has_unfinished_seqs()

    def _has_work(self) -> bool:
        """Unfinished requests, or steps dispatched and not yet fetched."""
        return self.scheduler.has_unfinished_seqs() or bool(self._inflight)

    # --- the hot loop -----------------------------------------------------

    def step(self) -> List[RequestOutput]:
        assert not self._inflight, (
            "serial step() called with pipelined steps in flight; use "
            "step_pipelined() or drain_pipeline() first")
        self._tracer.begin_step()
        if self.speculative_config is not None:
            # Adaptive draft length: the controller's current K becomes
            # this round's K+1 slot reservation BEFORE scheduling, so the
            # scheduler's plan and the worker's draft/teacher programs
            # agree (all K in [k_min, k_max] are warm — no compiles).
            self.scheduler.scheduler_config.num_decode_steps = (
                self.worker.adaptive_num_decode_steps())
        seq_group_metadata_list, scheduler_outputs = self.scheduler.schedule()

        if not scheduler_outputs.is_empty():
            if self.speculative_config is not None:
                outputs = self.worker.execute_model(
                    seq_group_metadata_list,
                    scheduler_outputs.blocks_to_swap_in,
                    scheduler_outputs.blocks_to_swap_out,
                    scheduler_outputs.blocks_to_copy,
                    scheduler_outputs.num_decode_steps,
                    spec_plan=scheduler_outputs.spec_plan,
                )
            else:
                outputs = self.worker.execute_model(
                    seq_group_metadata_list,
                    scheduler_outputs.blocks_to_swap_in,
                    scheduler_outputs.blocks_to_swap_out,
                    scheduler_outputs.blocks_to_copy,
                    scheduler_outputs.num_decode_steps,
                )
        else:
            outputs = []

        return self._process_model_outputs(outputs, scheduler_outputs)

    # --- pipelined stepping ----------------------------------------------
    #
    # step() is strictly serial: schedule → dispatch → fetch → process.
    # The fetch and host post-processing (detokenize, stop checks,
    # streaming) serialize with device compute, so the chip idles for
    # the host's share of every step.
    # step_pipelined() keeps up to `depth` DECODE steps dispatched but
    # unfetched, and finalizes the oldest step in flight:
    #   - decode→decode: a continuation program slices its input tokens
    #     from the previous step's ON-DEVICE packed output, so the host
    #     never needs step N's results to dispatch step N+1 (the host's
    #     view of sequence state intentionally trails the device);
    #   - prompt admission chains on the in-flight cache futures (XLA
    #     executes enqueued programs in order) as a prefill-only step,
    #     at most _PROMPT_STEPS_PER_DECODE_STEP of them per decode step
    #     in flight, so a new request never waits for the pipeline to
    #     drain before its prefill starts and a decode step is always
    #     queued behind a prompt step; a prompt that only waits for a
    #     seat does not stop decode;
    #   - an admitted sequence JOINS the running decode batch at the next
    #     continuation: each continuation is dispatched from a re-snapshot
    #     of the row set (_dispatch_cont) in which rows the host knows to
    #     be done (max_tokens / max_model_len covered by dispatched steps,
    #     or seen finished) give up their slot and admitted sequences take
    #     free slots of the compiled batch bucket. One whose final prompt
    #     step is still in flight takes its first token on the device,
    #     gathered from that step's un-fetched output, so it rides the
    #     first continuation dispatched behind its prompt step (every
    #     continuation behind a prompt step takes all of its sequences
    #     that the fused program takes, or is not dispatched);
    #   - what needs a coherent host view drains the pipeline to a full
    #     schedule(), counted by cause: no decode step in flight (idle),
    #     swap / preemption / a refused block growth (memory), a joiner
    #     the fused program excludes or a K=1 batch (params), more rows
    #     than the batch bucket in flight was compiled for, or few enough
    #     for a smaller one _REBUCKET_AFTER_CONTS times running (bucket);
    #   - a prompt longer than a step's token budget runs its chunks as
    #     prompt steps too (schedule(prefill_only=True) hands out the
    #     next chunk of every sequence part-way through its prompt), so a
    #     long prompt does not drain a pipeline that has a decode step in
    #     flight; with none in flight (an idle start, after a drain) the
    #     fresh schedule runs its chunks in mixed steps with decode rows
    #     at K=1 and counts a chunked_prefill drain, as ever.
    # KV pages referenced by in-flight steps are free-guarded in the
    # scheduler: a sequence finishing host-side mid-pipeline stays a
    # "zombie" row of the steps already dispatched (its outputs are
    # overshoot, discarded) and its pages are released only once the
    # last referencing step is fetched.

    def has_inflight(self) -> bool:
        return bool(self._inflight)

    def step_pipelined(self) -> List[RequestOutput]:
        """Pipelined equivalent of step(): dispatches as much device work
        as the pipeline depth allows, then fetches + processes the oldest
        in-flight step. Returns [] only when fully idle."""
        self._tracer.begin_step()
        while self._pipeline_dispatch_one():
            pass
        if not self._inflight:
            self._tracer.cancel_step()
            pending, self._pending_outputs = self._pending_outputs, []
            return pending
        return self._finalize_one()

    def drain_pipeline(self) -> List[RequestOutput]:
        outs: List[RequestOutput] = []
        while self._inflight:
            outs.extend(self._finalize_one())
        return outs

    def _drain(self, cause: str) -> bool:
        """Nothing more may be dispatched until the pipeline is empty and
        a full schedule() has run; the first cause of a drain names it."""
        if self._drain_cause is None:
            self._drain_cause = cause
        return False

    def _pipeline_dispatch_one(self) -> bool:
        """Dispatch one more step if the pipeline has room for it; False
        when the next thing to do is to finalize the oldest step."""
        sched = self.scheduler
        seed = self._newest_decode_inflight()
        decodes = sum(s.cont_state is not None for s in self._inflight)
        others = len(self._inflight) - decodes
        # New prompts admit immediately, chained behind in-flight steps,
        # a bounded number per decode step in flight, so that a burst of
        # prompts cannot hold decode back by more than that.
        # A prompt longer than the budget runs its chunks as prompt steps
        # only beside a decode step in flight. With none (an idle start, a
        # drained pipeline) no continuation can be dispatched either (it
        # needs a decode step to continue, and a fresh schedule an empty
        # pipeline), so chunk steps of their own would hold every running
        # sequence still until the last waiting prompt's last chunk: the
        # fresh schedule below puts the chunks in steps with their decode
        # rows instead, as it does under the serial loop.
        chunks = decodes > 0
        if ((sched.waiting or (chunks and sched.mid_prefill()))
                and not sched.swapped
                and others < (decodes or 1) * _PROMPT_STEPS_PER_DECODE_STEP):
            metas, so = sched.schedule(prefill_only=True,
                                       chunk_long_prompts=chunks)
            if so.ignored_seq_groups and not metas:
                # Rejected without device work (over-long prompts):
                # surface their outputs with the next batch returned.
                self._pending_outputs.extend(
                    self._process_model_outputs([], so,
                                                is_step_boundary=False))
                return True
            if metas:
                self._dispatch(metas, so)
                return True
            if self._inflight and so.blocked in _ADMISSION_DRAINS:
                # What only a full scheduling pass on an empty pipeline
                # resolves. Seats, adapter slots or a tenant's share
                # running out is not: decode goes on, and a later
                # prefill-only pass admits when a request has finished.
                return self._drain(_ADMISSION_DRAINS[so.blocked])
        if not self._inflight:
            return self._dispatch_fresh()
        if seed is None:
            # Only prompt steps (start, idle engine) in flight, or a step
            # of a program that cannot be continued (_dispatch named it).
            return self._drain("idle")
        if self._inflight[0].cont_state is None:
            # A prompt step is the oldest in flight and a decode step is
            # queued behind it: fetch it first. It is done or next to
            # run, its first tokens are due (TTFT), and a sequence of it
            # that a continuation has already taken must have its first
            # token on the host before that continuation is fetched.
            return False
        if decodes >= self._pipeline_depth:
            return False
        if sched.swapped:
            return self._drain("memory")
        # A sequence part-way through its prompt is no row of the decode
        # batch: its chunks run as prompt steps beside the continuations
        # (the pass above) and it joins behind its final chunk's step.
        return self._dispatch_cont(seed)

    def _dispatch_fresh(self) -> bool:
        """Pipeline empty: full scheduling pass (may swap/preempt)."""
        metas, so = self.scheduler.schedule()
        if so.is_empty() and not metas:
            if so.ignored_seq_groups:
                self._pending_outputs.extend(
                    self._process_model_outputs([], so,
                                                is_step_boundary=False))
                return True
            return False
        if not metas:
            # Swap-only plan (preemption emptied the running set): run
            # the block ops eagerly — there is no device step to track.
            self.worker.execute_model([], so.blocks_to_swap_in,
                                      so.blocks_to_swap_out,
                                      so.blocks_to_copy,
                                      so.num_decode_steps)
            self._pending_outputs.extend(
                self._process_model_outputs([], so,
                                            is_step_boundary=False))
            return True
        self._dispatch(metas, so)
        return True

    def _dispatch(self, metas, scheduler_outputs) -> None:
        step = self.worker.execute_model(
            metas,
            scheduler_outputs.blocks_to_swap_in,
            scheduler_outputs.blocks_to_swap_out,
            scheduler_outputs.blocks_to_copy,
            scheduler_outputs.num_decode_steps,
            defer_fetch=True,
        )
        seq_ids = [sid for m in metas for sid in m.seq_data]
        self.scheduler.guard_seqs(seq_ids)
        groups = scheduler_outputs.scheduled_seq_groups
        if not scheduler_outputs.prompt_run:
            # A fresh schedule merged every running sequence into this
            # step's decode rows, after a drain.
            rows = {sid for m in metas if m.token_chunk_size is None
                    for sid in m.seq_data}
            self._efficiency.record_decode_join(
                "fresh", len(rows - self._decode_members))
            self._decode_members = rows
            self._efficiency.record_pipeline_drain(
                self._drain_cause or "idle")
            self._drain_cause = None
            self._roomy_conts = 0
            if step.cont_state is None:
                # No program continues this step: the scheduler clamped
                # the batch to K=1, or a sequence is mid-prefill.
                self._drain("chunked_prefill" if scheduler_outputs.is_mixed
                            else "params")
        if step.cont_state is not None:
            owner = {seq.seq_id: (seq, meta, group)
                     for meta, group in zip(metas, groups)
                     for seq in group.get_seqs()}
            step.cont_state.owners = [owner[sid]
                                      for _, sid in step.cont_state.rows]
        step._pipeline_seq_ids = seq_ids
        step._pipeline_sched = scheduler_outputs
        self._inflight.append(step)

    def _newest_decode_inflight(self):
        """The newest in-flight entry that can seed a continuation — it
        need not be the pipeline tail: prompt admissions interleave, and
        a continuation chained PAST a prefill is legal (the prefill
        touches disjoint pages)."""
        for step in reversed(self._inflight):
            if step.cont_state is not None:
                return step
        return None

    def _prompt_steps_in_flight(self) -> Tuple[Set[int], Dict[int, tuple]]:
        """The sequences of the prompt steps in flight, and of those a
        step samples a token for (a final chunk's last row): seq_id ->
        (that step, the flat row of its output that holds the sample)."""
        prefilling: Set[int] = set()
        first_rows: Dict[int, tuple] = {}
        for step in self._inflight:
            if step.cont_state is not None:
                continue
            prefilling.update(step._pipeline_seq_ids)
            if step.first_tokens is not None:
                first_rows.update(
                    (sid, (step, int(row)))
                    for row, (_, sid) in zip(*step.emit))
        return prefilling, first_rows

    @staticmethod
    def _fused_excludes(sp: SamplingParams) -> bool:
        """Sampling the fused K>1 decode program does not take: such a
        sequence enters decode through a fresh schedule (which forks
        best_of groups copy-on-write, or clamps the batch to K=1)."""
        return bool(sp.best_of > 1 or sp.use_beam_search
                    or sp.presence_penalty or sp.frequency_penalty
                    or sp.repetition_penalty != 1.0
                    or sp.logits_processors)

    def _dispatch_cont(self, prev) -> bool:
        """Continue the newest decode step `prev` by one fused step over a
        re-snapshot of its row set."""
        with self._tracer.span("schedule"):
            plan = self._resnapshot(prev)
        if plan is None:
            return False
        cont, tables, first_token_rows, groups, joined = plan
        step = self.worker.execute_decode_cont(cont, tables,
                                               first_token_rows,
                                               prev.packed, prev.t1)
        self._decode_members.update(joined)
        self._efficiency.record_decode_join("device", len(joined))
        self._drain_cause = None
        seq_ids = [sid for _, sid in cont.rows]
        self.scheduler.guard_seqs(seq_ids)
        step._pipeline_seq_ids = seq_ids
        step._pipeline_sched = SchedulerOutputs(
            scheduled_seq_groups=groups, prompt_run=False,
            num_batched_tokens=len(seq_ids), blocks_to_swap_in={},
            blocks_to_swap_out={}, blocks_to_copy={},
            ignored_seq_groups=[], num_decode_steps=cont.num_steps)
        self._inflight.append(step)
        return True

    def _resnapshot(self, prev) -> Optional[tuple]:
        """The row set of the step that continues `prev`, computed
        numerically: the host has not seen the tokens of the steps in
        flight, but it knows every row's budget. None when no
        continuation may be dispatched now (a drain names why, if the
        reason is one)."""
        from intellillm_tpu.worker.model_runner import (PAD_PARAMS,
                                                         DecodeContState)
        sched = self.scheduler
        pc = prev.cont_state
        k = pc.num_steps
        mml = self.model_config.max_model_len
        b = pc.ctx0.shape[0]

        # Rows kept: those the dispatched steps do not finish for sure.
        # max_tokens and the model-length cap are known ahead of the
        # tokens; EOS / stop strings are not, and a row that may have
        # stopped stays (a zombie) until a finalize has shown it.
        ctx = np.zeros(b, np.int32)
        out_lens = np.zeros(b, np.int64)
        owners: List[Optional[tuple]] = [None] * b   # (seq, meta, group)
        params = [PAD_PARAMS] * b
        loras = None if pc.row_loras is None else [None] * b
        for slot, owner in zip(pc.slots, pc.owners):
            if owner[0].is_finished():
                continue
            mt = pc.row_params[slot].max_tokens
            if ((mt is not None and pc.out_lens0[slot] + k >= mt)
                    or pc.ctx0[slot] + k > mml):
                continue
            ctx[slot] = pc.ctx0[slot] + k
            out_lens[slot] = pc.out_lens0[slot] + k
            owners[slot] = owner
            params[slot] = pc.row_params[slot]
            if loras is not None:
                loras[slot] = pc.row_loras[slot]

        # Joiners: running sequences no decode batch has taken yet, whose
        # final prompt step is in flight. A joiner takes its first token
        # on the device, from that step's un-fetched output
        # (`first_rows`): the step was dispatched before this
        # continuation, so its KV, its state slot and its sample are
        # ready when the continuation runs, and its lengths are known
        # without the token. The host appends, streams and stop-checks
        # that token when the prompt step is fetched, which is before
        # this continuation is (`_inflight` is FIFO); a first token that
        # ends the request leaves a zombie row, as any stop the host has
        # not seen does.
        prefilling, first_rows = self._prompt_steps_in_flight()
        alive: Set[int] = set()
        joiners: List[Tuple[Sequence, SequenceGroup, tuple]] = []
        for group in sched.running:
            sp = group.sampling_params
            for seq in group.get_seqs(status=SequenceStatus.RUNNING):
                alive.add(seq.seq_id)
                if (seq.seq_id in self._decode_members
                        or not seq.data.prefill_complete):
                    continue
                if seq.seq_id not in prefilling:
                    # Fetched, and no continuation behind its prompt step
                    # took it: sampling the fused program excludes.
                    self._drain("params")
                    return None
                # Its first token is its last where the budget or the
                # model's length says so.
                source = first_rows.get(seq.seq_id)
                if (source is None or self._fused_excludes(sp)
                        or (sp.max_tokens is not None
                            and seq.get_output_len() + 1 >= sp.max_tokens)
                        or seq.get_len() + 1 > mml):
                    continue
                joiners.append((seq, group, source))
        free = np.flatnonzero(ctx == 0)
        if len(joiners) > len(free):
            self._drain("bucket")
            return None
        # By prompt step in flight: the flat row of its output a slot
        # reads its first token from, -1 for none.
        first_token_rows: Dict[object, np.ndarray] = {}
        for slot, (seq, group, (step, row)) in zip(free, joiners):
            # The token still on the device counts in both lengths.
            ctx[slot] = seq.get_len() + 1
            out_lens[slot] = seq.get_output_len() + 1
            owners[slot] = (seq, sched.group_metadata(group), group)
            params[slot] = group.sampling_params
            if loras is not None:
                loras[slot] = group.lora_request
            first_token_rows.setdefault(
                step, np.full(b, -1, np.int32))[slot] = row
        slots = np.flatnonzero(ctx)
        if not len(slots):
            # Every row's budget is covered by the steps in flight and
            # nobody waits to join: a continuation would be pure
            # overshoot (the offline shape max_tokens == K would waste a
            # whole fused call per batch).
            return None
        # A continuation inherits the batch bucket in flight. When the
        # rows have fitted a smaller one for a few snapshots running
        # (load fell), a fresh schedule re-buckets them.
        if self.worker.model_runner.decode_batch_bucket(len(slots)) < b:
            self._roomy_conts += 1
            if self._roomy_conts >= _REBUCKET_AFTER_CONTS:
                self._drain("bucket")
                return None
        else:
            self._roomy_conts = 0

        bm = sched.block_manager
        targets = [(owners[slot][0].seq_id, min(int(ctx[slot]) + k - 1, mml))
                   for slot in slots]
        # A row's first query of this step stands at its context's last
        # position: what a windowed cache group may give back first.
        firsts = [max(int(ctx[slot]) - 1, 0) for slot in slots]
        if not bm.can_grow_all(targets, firsts):
            self._drain("memory")
            return None
        tables = [bm.grow_to(sid, target, first)
                  for (sid, target), first in zip(targets, firsts)]
        state_slots = [bm.get_state_slot(sid) for sid, _ in targets]

        # One metadata entry per group holding a row, in slot order.
        cont = DecodeContState([], [], slots, ctx, out_lens, params, loras,
                               k)
        cont.owners = [owners[slot] for slot in slots]
        cont.state_slots = state_slots if self._stateful else None
        if bm.window_group is not None:
            cont.window_tables = [bm.window_group.table_numbers(sid)
                                  for sid, _ in targets]
        groups: List[SequenceGroup] = []
        listed: Set[str] = set()
        for seq, meta, group in cont.owners:
            cont.rows.append((group.request_id, seq.seq_id))
            if group.request_id not in listed:
                listed.add(group.request_id)
                cont.metas.append(meta)
                groups.append(group)
        self._decode_members &= alive
        return (cont, tables, first_token_rows, groups,
                [seq.seq_id for seq, _, _ in joiners])

    def _finalize_one(self) -> List[RequestOutput]:
        step = self._inflight.popleft()
        # Groups that finished at an EARLIER finalize still appear in this
        # step's (pre-dispatched) group snapshot; their rows are overshoot
        # zombies — don't re-emit their finished outputs.
        already_done = {
            g.request_id
            for g in step._pipeline_sched.scheduled_seq_groups
            if g.is_finished()}
        outputs = step.finalize()
        request_outputs = self._process_model_outputs(outputs,
                                                      step._pipeline_sched)
        self.scheduler.unguard_seqs(step._pipeline_seq_ids)
        request_outputs = [ro for ro in request_outputs
                           if ro.request_id not in already_done]
        if self._pending_outputs:
            pending, self._pending_outputs = self._pending_outputs, []
            return pending + request_outputs
        return request_outputs

    def _quarantine_seq_group(self, seq_group: SequenceGroup,
                              info: Dict) -> None:
        """Numerics quarantine (obs/numerics.py): the sentinel tripped
        on this request's logit row, so its sampled token is garbage —
        never append or stream it. Every live sequence finishes
        FINISHED_ABORTED, closing the request with a structured error
        (finish_reason "abort"); the `numerics_anomaly` flight event
        lands ahead of the terminal record so the sealed trace explains
        WHY the request aborted."""
        detail = ",".join(info.get("kinds", ())) or "anomaly"
        self._flight.record(seq_group.request_id, "numerics_anomaly",
                            detail=detail)
        logger.error("Quarantining request %s: numerics anomaly (%s)",
                     seq_group.request_id, detail)
        for seq in seq_group.get_seqs():
            if seq.is_finished():
                continue
            seq.status = SequenceStatus.FINISHED_ABORTED
            self.scheduler.free_seq(seq)

    def _process_model_outputs(
        self,
        outputs_per_substep: List[SamplerOutput],
        scheduler_outputs: SchedulerOutputs,
        is_step_boundary: bool = True,
    ) -> List[RequestOutput]:
        with self._tracer.span("process_outputs"):
            request_outputs = self._apply_model_outputs(
                outputs_per_substep, scheduler_outputs)

        # Drain the step-phase tracer even with stats logging off, so the
        # breakdown stays readable off the engine (tests, benches). Only
        # the once-per-logical-step call sites drain (is_step_boundary);
        # the pipelined dispatch path may process ignored/swap-only plans
        # mid-step, and an early drain there would consume the step timer
        # and split one step's breakdown across multiple StatLogger rows.
        phases: Dict[str, float] = {}
        step_time = 0.0
        step_gap = None
        if is_step_boundary:
            phases, step_time = self._tracer.end_step(
                work_pending=self._has_work())
            step_gap = self._tracer.last_gap
            if phases or step_time:
                self.last_step_phases = phases
                self.last_step_time = step_time
                self.last_step_gap = step_gap

        # The telemetry's own cost: after end_step(), so it carries into
        # the next drain like the other between-step phases.
        with self._tracer.span("stats"):
            if is_step_boundary:
                self._watchdog.heartbeat_step()
                # Fold this step's wall time into the rolling MFU (works
                # with stats logging off — benches read the gauge/ledger).
                self._efficiency.record_step(step_time)
                # Cost-model MFU cross-check + the capture endpoint's
                # step counter (obs/kernels.py).
                self._kernel_ledger.record_step(step_time)
            if self.stat_logger is not None:
                stats = self._get_stats(scheduler_outputs)
                stats.step_phase_times = phases
                stats.step_time = step_time
                stats.step_gap = step_gap
                if is_step_boundary:
                    stats.device_busy = self._tracer.last_device_busy
                    stats.device_idle = self._tracer.last_device_idle
                self.stat_logger.log(stats)
        return request_outputs

    def _apply_model_outputs(
        self,
        outputs_per_substep: List[SamplerOutput],
        scheduler_outputs: SchedulerOutputs,
    ) -> List[RequestOutput]:
        """Append the step's tokens to their sequences, check stops, run
        the finish hooks and build the RequestOutputs (the
        `process_outputs` phase; `detokenize` nests inside)."""
        now = time.monotonic()
        scheduled_seq_groups = scheduler_outputs.scheduled_seq_groups
        for idx, seq_group in enumerate(scheduled_seq_groups):
            if seq_group.is_finished():
                continue  # finished at an earlier (possibly pipelined) step
            if self._numerics.enabled:
                info = self._numerics.take_quarantine(seq_group.request_id)
                if info is not None:
                    # Sentinel tripped on this request's logit row
                    # (observed at the step fetch, before any token from
                    # that row reaches here): quarantine — finish with a
                    # structured abort, never stream the poisoned token.
                    self._quarantine_seq_group(seq_group, info)
                    continue
            sp = seq_group.sampling_params
            running = seq_group.get_seqs(status=SequenceStatus.RUNNING)
            if (len(running) == 1 and not sp.use_beam_search
                    and sp.best_of == 1):
                # Fast path for the dominant serving shape (one sequence,
                # no forking): append the K fused tokens directly instead
                # of re-deriving the fork bookkeeping per substep — the
                # generic path's per-substep dict/list churn is ~40% of
                # host post-processing at bs=96.
                seq = running[0]
                for output in outputs_per_substep:
                    go = output[idx]
                    if go.prompt_logprobs is not None:
                        seq_group.prompt_logprobs = go.prompt_logprobs
                    if not go.samples:
                        continue
                    if seq_group.first_token_time is None:
                        seq_group.first_token_time = now
                        self._flight.record(seq_group.request_id,
                                            "first_token")
                    s = go.samples[0]
                    seq.append_token_id(s.output_token, s.logprobs)
                    if self.tokenizer is not None:
                        with self._tracer.span("detokenize"):
                            self._decode_sequence(seq, sp)
                    self._check_stop(seq, sp)
                    if seq.is_finished():
                        self.scheduler.free_seq(seq)
                        break
                continue
            for output in outputs_per_substep:
                if seq_group.is_finished():
                    break  # finished at an earlier fused substep
                outputs = output[idx]
                if not outputs.samples and outputs.prompt_logprobs is None:
                    # Mid-prefill chunk: no token emitted yet. Skipping
                    # here matters for beam/best_of groups — the fork/
                    # prune bookkeeping would treat an empty sample list
                    # as "every continuation pruned" and kill the group.
                    continue
                if seq_group.first_token_time is None and outputs.samples:
                    seq_group.first_token_time = now
                    self._flight.record(seq_group.request_id, "first_token")
                self._process_sequence_group_outputs(seq_group, outputs)

        self.scheduler.free_finished_seq_groups()

        request_outputs: List[RequestOutput] = []
        for seq_group in (scheduled_seq_groups +
                          scheduler_outputs.ignored_seq_groups):
            if seq_group.is_finished():
                reasons = sorted({
                    r for r in (SequenceStatus.get_finished_reason(s.status)
                                for s in seq_group.get_seqs())
                    if r is not None})
                # record() returns False for sealed traces (zombie rows
                # re-reported by pipelined steps), so the SLO finish hook
                # fires exactly once per request.
                if self.speculative_config is not None:
                    # One spec event per request, BEFORE the terminal
                    # "finished" record seals the trace (pop() makes this
                    # exactly-once; per-pass records would evict the
                    # interesting scheduling history from the capped
                    # event buffer).
                    from intellillm_tpu.worker.spec_decode.metrics import (
                        get_spec_stats)
                    accepted = get_spec_stats().pop_request_accepted(
                        seq_group.request_id)
                    if accepted is not None:
                        self._flight.record(
                            seq_group.request_id, "spec_accepted",
                            detail=str(accepted))
                if self._flight.record(seq_group.request_id, "finished",
                                       detail=",".join(reasons) or None):
                    actual_len = sum(s.get_output_len()
                                     for s in seq_group.get_seqs())
                    self._slo.record_finish(seq_group.request_id,
                                            actual_len)
                    # Per-tenant SLO attribution rides the same
                    # exactly-once seal (docs/multitenancy.md). Lazy
                    # import: tenancy singletons shouldn't initialise
                    # for engines that never finish a request (tests
                    # poking step() internals).
                    from intellillm_tpu.tenancy import (get_tenant_registry,
                                                        get_tenant_stats)
                    tenant = get_tenant_registry().tenant_for_adapter(
                        seq_group.lora_int_id)
                    get_tenant_stats().record_finish(
                        tenant, seq_group.request_id, actual_len)
                    # Same exactly-once seal feeds the online length
                    # calibrator; it may restamp in-flight predictions.
                    self._prediction.observe_finish(
                        seq_group.request_id, actual_len,
                        scheduler=self.scheduler)
                    # ... and the workload log (obs/workload.py): one
                    # bounded append per request, replayable via
                    # serve_bench --scenario replay.
                    from intellillm_tpu.obs.workload import get_workload_log
                    get_workload_log().record_seq_group(
                        seq_group, emitted_tokens=actual_len,
                        reason=",".join(reasons) or "finished")
            request_outputs.append(RequestOutput.from_seq_group(seq_group))

        # Flip freshly computed prefixes once their FINAL chunk ran
        # (reference llm_engine.py:727-731; with chunked prefill the
        # prefix KV is only fully resident at the last chunk).
        chunks_ran = scheduler_outputs.chunked_prefills or {}
        for seq_group in scheduled_seq_groups:
            if seq_group.prefix is None or seq_group.prefix.computed:
                continue
            chunk = chunks_ran.get(seq_group.request_id)
            if chunk is not None and chunk[2]:
                seq_group.prefix.computed = True

        return request_outputs

    # --- per-group output processing (incl. beam search) ------------------

    def _process_sequence_group_outputs(
        self,
        seq_group: SequenceGroup,
        outputs: SequenceGroupOutput,
    ) -> None:
        sampling_params = seq_group.sampling_params
        if outputs.prompt_logprobs is not None:
            seq_group.prompt_logprobs = outputs.prompt_logprobs
        parent_seqs = seq_group.get_seqs(status=SequenceStatus.RUNNING)
        existing_finished = seq_group.get_finished_seqs()

        parent_child: dict = {p.seq_id: [] for p in parent_seqs}
        for sample in outputs.samples:
            # Samples for parents that finished at an earlier fused substep
            # are surplus lookahead tokens: drop them.
            if sample.parent_seq_id in parent_child:
                parent_child[sample.parent_seq_id].append(sample)

        # (child, parent) pairs; a parent continuing itself is (parent, parent)
        child_seqs: List[Tuple[Sequence, Sequence]] = []
        for parent in parent_seqs:
            samples = parent_child[parent.seq_id]
            if not samples:
                if not sampling_params.use_beam_search:
                    continue
                # Beam pruning dropped every continuation of this parent.
                parent.status = SequenceStatus.FINISHED_ABORTED
                seq_group.remove(parent.seq_id)
                self.scheduler.free_seq(parent)
                continue
            for sample in samples[:-1]:
                new_child_id = next(self.seq_counter)
                child = parent.fork(new_child_id)
                child.data.seed_stream = seq_group.take_seed_stream()
                child.append_token_id(sample.output_token, sample.logprobs)
                child_seqs.append((child, parent))
            last = samples[-1]
            parent.append_token_id(last.output_token, last.logprobs)
            child_seqs.append((parent, parent))

        for seq, _ in child_seqs:
            if self.tokenizer is not None:
                with self._tracer.span("detokenize"):
                    self._decode_sequence(seq, sampling_params)
            self._check_stop(seq, sampling_params)

        if not sampling_params.use_beam_search:
            # Fork children before freeing finished parents; a child that
            # finished immediately never gets blocks, so don't fork it.
            for seq, parent in child_seqs:
                if seq is not parent:
                    seq_group.add(seq)
                    if not seq.is_finished():
                        self.scheduler.fork_seq(parent, seq)
            for seq, parent in child_seqs:
                if seq is parent and seq.is_finished():
                    self.scheduler.free_seq(seq)
            return

        # ----- beam search bookkeeping (reference :575-705) -----
        beam_width = sampling_params.best_of
        length_penalty = sampling_params.length_penalty
        eos = self._get_eos_token_id()

        def beam_score(seq: Sequence) -> float:
            return seq.get_beam_search_score(length_penalty,
                                             eos_token_id=eos)

        # Finished pool: previously finished + newly finished children.
        new_finished = [(s, p) for s, p in child_seqs if s.is_finished()]
        all_finished = ([(s, None) for s in existing_finished] + new_finished)
        all_finished.sort(key=lambda sp: beam_score(sp[0]), reverse=True)

        selected: List[Tuple[Sequence, Optional[Sequence]]] = []
        unselected: List[Tuple[Sequence, Optional[Sequence]]] = []
        for i, (seq, parent) in enumerate(all_finished):
            if i < beam_width:
                if parent is not None:
                    selected.append((seq, parent))
                # existing finished stay in the group as-is
            else:
                if parent is not None:
                    unselected.append((seq, parent))
                else:
                    seq_group.remove(seq.seq_id)  # outcompeted old beam

        running_children = [(s, p) for s, p in child_seqs
                            if not s.is_finished()]
        running_children.sort(key=lambda sp: beam_score(sp[0]), reverse=True)

        stop_all = False
        if len(all_finished) >= beam_width and running_children:
            best_running = running_children[0][0]
            worst_kept = all_finished[beam_width - 1][0]
            stop_all = self._beam_search_early_stop(
                sampling_params, best_running, worst_kept)

        if stop_all:
            unselected.extend(running_children)
        else:
            selected.extend(running_children[:beam_width])
            unselected.extend(running_children[beam_width:])

        for seq, parent in selected:
            if seq is not parent:
                seq_group.add(seq)
                if not seq.is_finished():
                    self.scheduler.fork_seq(parent, seq)
        for seq, parent in selected:
            if seq is parent and seq.is_finished():
                self.scheduler.free_seq(seq)
        for seq, parent in unselected:
            if seq is parent:
                # Continuing parent lost its slot: remove it entirely.
                seq_group.remove(seq.seq_id)
                self.scheduler.free_seq(seq)
            # else: forked child never registered; nothing to free.

    def _beam_search_early_stop(
        self,
        sampling_params: SamplingParams,
        best_running_seq: Sequence,
        current_worst_seq: Sequence,
    ) -> bool:
        """Reference `_check_beam_search_early_stopping` (:490-533)."""
        length_penalty = sampling_params.length_penalty
        eos = self._get_eos_token_id()
        worst = current_worst_seq.get_beam_search_score(length_penalty,
                                                        eos_token_id=eos)
        if sampling_params.early_stopping is True:
            return True
        if sampling_params.early_stopping == "never":
            if length_penalty > 0.0:
                budget = (sampling_params.max_tokens
                          if sampling_params.max_tokens is not None
                          else self.scheduler_config.max_model_len)
                max_possible_len = max(
                    best_running_seq.get_prompt_len() + budget,
                    self.scheduler_config.max_model_len)
                best_possible = best_running_seq.get_beam_search_score(
                    length_penalty, seq_len=max_possible_len,
                    eos_token_id=eos)
            else:
                best_possible = best_running_seq.get_beam_search_score(
                    length_penalty, eos_token_id=eos)
        else:  # early_stopping is False: HF heuristic on current length
            best_possible = best_running_seq.get_beam_search_score(
                length_penalty, eos_token_id=eos)
        return worst >= best_possible

    def _get_eos_token_id(self) -> Optional[int]:
        if self.tokenizer is None:
            return None
        return getattr(self.tokenizer.tokenizer, "eos_token_id", None)

    # --- detokenization & stop checks ------------------------------------

    def _decode_sequence(self, seq: Sequence,
                         sampling_params: SamplingParams) -> None:
        tokenizer = self.tokenizer.get_lora_tokenizer(seq.lora_request)
        new_tokens, new_text, prefix_offset, read_offset = \
            detokenize_incrementally(
                tokenizer,
                all_input_ids=seq.get_token_ids(),
                prev_tokens=seq.tokens,
                prefix_offset=seq.prefix_offset,
                read_offset=seq.read_offset,
                skip_special_tokens=sampling_params.skip_special_tokens,
                spaces_between_special_tokens=(
                    sampling_params.spaces_between_special_tokens),
            )
        if seq.tokens is None:
            seq.tokens = new_tokens
        else:
            seq.tokens.extend(new_tokens)
        seq.prefix_offset = prefix_offset
        seq.read_offset = read_offset
        seq.output_text += new_text

    def _check_stop(self, seq: Sequence,
                    sampling_params: SamplingParams) -> None:
        for stop_str in sampling_params.stop:
            if seq.output_text.endswith(stop_str):
                if not sampling_params.include_stop_str_in_output:
                    seq.output_text = seq.output_text[:-len(stop_str)]
                seq.status = SequenceStatus.FINISHED_STOPPED
                return
        if seq.get_last_token_id() in sampling_params.stop_token_ids:
            seq.status = SequenceStatus.FINISHED_STOPPED
            return
        if seq.get_len() > self.scheduler_config.max_model_len:
            seq.status = SequenceStatus.FINISHED_LENGTH_CAPPED
            return
        if seq.get_output_len() == sampling_params.max_tokens:
            seq.status = SequenceStatus.FINISHED_LENGTH_CAPPED
            return
        if (not sampling_params.ignore_eos
                and seq.get_last_token_id() == self._get_eos_token_id()):
            seq.status = SequenceStatus.FINISHED_STOPPED
            return

    # --- stats ------------------------------------------------------------

    def _get_stats(self, scheduler_outputs: SchedulerOutputs) -> Stats:
        now = time.monotonic()
        num_total_blocks = self.cache_config.num_device_blocks or 0
        num_free = self.scheduler.block_manager.get_num_free_device_blocks()
        device_cache_usage = 1.0 - num_free / max(num_total_blocks, 1)
        num_total_cpu = self.cache_config.num_cpu_blocks or 0
        free_cpu = self.scheduler.block_manager.get_num_free_cpu_blocks()
        cpu_cache_usage = (1.0 - free_cpu / num_total_cpu
                           if num_total_cpu > 0 else 0.0)
        kv_block_bytes = getattr(self, "_kv_block_bytes", 0)
        cpu_block_bytes = getattr(self, "_cpu_block_bytes", 0)
        device_used = max(num_total_blocks - num_free, 0) * kv_block_bytes
        cpu_used = max(num_total_cpu - free_cpu, 0) * cpu_block_bytes

        # A decode pass generates num_decode_steps tokens PER ROW
        # (num_batched_tokens counts rows); without the multiplier the
        # throughput log and Prometheus counter under-report by K.
        # Speculative passes emit a VARIABLE count (accepted+1 per row) —
        # use the worker's actual emission, not K+1. Mixed
        # (chunked-prefill) steps split the batch by phase: chunk tokens
        # count as prompt tokens, decode rows as generation (K=1) — the
        # per-phase counts come from the scheduler, so nothing is double
        # counted or misattributed.
        k_eff = scheduler_outputs.num_decode_steps
        if (self.speculative_config is not None
                and not scheduler_outputs.prompt_run):
            # Spec decode pass (plain or mixed with prefill chunks): the
            # emission count is VARIABLE (accepted+1 per eligible row,
            # 1 per plain row, 0 per mid-prefill chunk) — the worker's
            # actual per-pass emission is authoritative; the scheduler's
            # row counts would under/over-report by the acceptance rate.
            rows = (scheduler_outputs.num_mixed_decode_tokens
                    if scheduler_outputs.is_mixed else
                    scheduler_outputs.num_batched_tokens)
            prompt_tokens = (scheduler_outputs.num_prefill_tokens
                             if scheduler_outputs.is_mixed else 0)
            generation_tokens = getattr(self.worker, "last_pass_emitted",
                                        rows)
            k_eff = max(generation_tokens / max(rows, 1), 1e-6)
        elif scheduler_outputs.is_mixed:
            prompt_tokens = scheduler_outputs.num_prefill_tokens
            generation_tokens = scheduler_outputs.num_mixed_decode_tokens
            k_eff = 1
        elif scheduler_outputs.prompt_run:
            prompt_tokens = scheduler_outputs.num_batched_tokens
            generation_tokens = 0
        else:
            prompt_tokens = 0
            generation_tokens = (scheduler_outputs.num_batched_tokens *
                                 scheduler_outputs.num_decode_steps)

        time_to_first: List[float] = []
        time_per_output: List[float] = []
        e2e: List[float] = []
        k = max(k_eff, 1e-6)
        chunks = scheduler_outputs.chunked_prefills or {}
        for sg in scheduler_outputs.scheduled_seq_groups:
            chunk = chunks.get(sg.request_id)
            if chunk is not None:
                # Mid-prefill groups emit no token: TTFT is recorded at
                # the FINAL chunk (when the first token actually samples)
                # and last_token_time starts there so the first TPOT
                # sample doesn't absorb prefill time.
                if chunk[2]:
                    time_to_first.append(now - sg.arrival_time)
                    sg.last_token_time = now
                if sg.is_finished():
                    e2e.append(now - sg.arrival_time)
                continue
            if scheduler_outputs.prompt_run and sg.first_scheduled_time:
                time_to_first.append(now - sg.arrival_time)
            elif not scheduler_outputs.prompt_run and sg.last_token_time:
                # One decode pass emits ~k tokens per row; the histogram
                # records PER-TOKEN time.
                time_per_output.append((now - sg.last_token_time) / k)
            sg.last_token_time = now
            if sg.is_finished():
                e2e.append(now - sg.arrival_time)

        spec_rate = None
        if (self.speculative_config is not None
                and getattr(self.worker, "num_draft_tokens", 0) > 0):
            spec_rate = self.worker.acceptance_rate()

        return Stats(
            now=now,
            num_running=len(self.scheduler.running),
            num_swapped=len(self.scheduler.swapped),
            num_waiting=len(self.scheduler.waiting),
            device_cache_usage=device_cache_usage,
            cpu_cache_usage=cpu_cache_usage,
            device_cache_bytes_used=device_used,
            device_cache_bytes_total=num_total_blocks * kv_block_bytes,
            cpu_cache_bytes_used=cpu_used,
            cpu_cache_bytes_total=num_total_cpu * cpu_block_bytes,
            num_prompt_tokens=prompt_tokens,
            num_generation_tokens=generation_tokens,
            time_to_first_tokens=time_to_first,
            time_per_output_tokens=time_per_output,
            time_e2e_requests=e2e,
            spec_acceptance_rate=spec_rate,
        )
