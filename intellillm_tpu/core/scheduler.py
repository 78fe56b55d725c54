"""Iteration-level (continuous-batching) scheduler.

Role parity: reference `vllm/core/scheduler.py` (Scheduler :73,
SchedulerOutputs :31, PreemptionMode :18, _schedule :160, schedule :363):
three queues WAITING/RUNNING/SWAPPED; prefill-first admission under token /
seq / padding budgets; decode with priority-ordered preemption (recompute
for single-sequence groups, swap for multi-sequence); swap-in when room.
Emits `SequenceGroupMetadata` plus block-op plans the worker executes
before the model step.

TPU-specific change: the padding budget is interpreted against the
prefill-shape *buckets* the runner will pad to (XLA static shapes), not
raw max-prompt-len padding; the policy is pluggable (FCFS / SJF — the
IntelliLLM fork's research scheduler made first-class, SURVEY §2.10).

Honesty note: the queue/admission control flow here is a deliberate
close port of the reference's host-side scheduler (pure-Python logic
with no hardware component — SURVEY §7.4 sanctions porting such layers
nearly verbatim). What is NOT ported: the bucketed padding budget, the
policy-driven admission order, the clamped K-slot lookahead for fused
multi-step decode, prefill-only scheduling for pipelined admission, and
the free-guard machinery for dispatched-but-unfetched device steps.
"""
from __future__ import annotations

import enum
import time
from collections import deque
from typing import Deque, Dict, Iterable, List, Optional, Set, Tuple, Union

from intellillm_tpu.config import CacheConfig, LoRAConfig, SchedulerConfig
from intellillm_tpu.core.block_manager import AllocStatus, BlockSpaceManager
from intellillm_tpu.core.policy import Policy, PolicyFactory
from intellillm_tpu.logger import init_logger
from intellillm_tpu.obs import (get_decision_log, get_flight_recorder,
                                get_slo_tracker, get_step_tracer)
from intellillm_tpu.prediction import get_prediction_service
from intellillm_tpu.prefix import PrefixPool
from intellillm_tpu.sequence import (Sequence, SequenceData, SequenceGroup,
                                     SequenceGroupMetadata, SequenceStatus)
from intellillm_tpu.tenancy import get_tenant_registry, get_tenant_stats
from intellillm_tpu.utils import default_len_buckets, pad_to_bucket
from intellillm_tpu.worker.spec_decode.eligibility import (
    seq_group_spec_eligible)

logger = init_logger(__name__)


class PreemptionMode(enum.Enum):
    """SWAP: move KV blocks to host memory and back later (used for groups
    with multiple live sequences, where recompute can't reproduce sampling
    state). RECOMPUTE: drop blocks and re-prefill later (cheaper for
    single-sequence groups)."""
    SWAP = enum.auto()
    RECOMPUTE = enum.auto()


class SchedulerOutputs:

    def __init__(
        self,
        scheduled_seq_groups: List[SequenceGroup],
        prompt_run: bool,
        num_batched_tokens: int,
        blocks_to_swap_in: Dict[int, int],
        blocks_to_swap_out: Dict[int, int],
        blocks_to_copy: Dict[int, List[int]],
        ignored_seq_groups: List[SequenceGroup],
        num_decode_steps: int = 1,
        chunked_prefills: Optional[Dict[str, Tuple[int, int, bool]]] = None,
        num_prefill_tokens: int = 0,
        num_mixed_decode_tokens: int = 0,
        spec_plan: Optional[Set[str]] = None,
        blocked: Optional[str] = None,
    ) -> None:
        self.scheduled_seq_groups = scheduled_seq_groups
        self.prompt_run = prompt_run
        self.num_batched_tokens = num_batched_tokens
        self.blocks_to_swap_in = blocks_to_swap_in
        self.blocks_to_swap_out = blocks_to_swap_out
        self.blocks_to_copy = blocks_to_copy
        self.ignored_seq_groups = ignored_seq_groups
        # Fused decode iterations this batch (slots already reserved).
        self.num_decode_steps = num_decode_steps
        # Mixed (chunked-prefill) step bookkeeping: request_id ->
        # (start, chunk_size, is_final_chunk) for every group running a
        # prefill chunk this step. None on homogeneous steps. The token
        # split feeds per-phase stats/telemetry (no double counting).
        self.chunked_prefills = chunked_prefills
        self.num_prefill_tokens = num_prefill_tokens
        self.num_mixed_decode_tokens = num_mixed_decode_tokens
        # Speculative step plan: request_ids whose decode rows reserved
        # num_decode_steps KV slots and may run the draft+teacher pass
        # this round (per-row eligibility — the rest of the batch decodes
        # one plain token). None on non-speculative engines.
        self.spec_plan = spec_plan
        # Why a prefill-only pass stopped admitting, when it did (the
        # reason given to the decision log): kv_watermark, needs_chunking,
        # max_seqs, token_budget, padding. None otherwise.
        self.blocked = blocked
        assert not (blocks_to_swap_in and blocks_to_swap_out)

    @property
    def is_mixed(self) -> bool:
        return self.chunked_prefills is not None

    def is_empty(self) -> bool:
        return (not self.scheduled_seq_groups and not self.blocks_to_swap_in
                and not self.blocks_to_swap_out and not self.blocks_to_copy)


class _TenantFairnessPass:
    """Per-scheduling-pass tenant fairness caps (docs/multitenancy.md).

    Weighted share: each present tenant is entitled to
    `weight / sum(present weights)` of the machine, optionally tightened
    by its `token_share_cap`. That share caps (a) the tenant's RUNNING
    seats — gating prompt admission and swap-in, never evicting already
    running work — and (b) in chunked mode, the tenant's prefill-chunk
    tokens per step, so a hog's prompt stream cannot monopolize the
    token budget while other tenants' decodes are resident.

    Work-conserving: inactive (every check a no-op) when fairness is
    disabled or fewer than two tenants are present, so a lone tenant
    may use the whole machine. Every tenant always keeps at least one
    seat / one chunk token, so caps never deadlock admission.
    """

    def __init__(self, scheduler: "Scheduler",
                 chunk_budget: Optional[int] = None) -> None:
        self.active = False
        cfg = scheduler.scheduler_config
        if not getattr(cfg, "tenant_fairness", True):
            return
        registry = get_tenant_registry()
        self._registry = registry
        present: Dict[str, float] = {}
        for queue in (scheduler.running, scheduler.swapped,
                      scheduler.waiting):
            for sg in queue:
                tenant = registry.tenant_for_adapter(sg.lora_int_id)
                if tenant not in present:
                    present[tenant] = registry.weight_for(tenant)
        if len(present) < 2:
            return
        self.active = True
        total_weight = sum(present.values())
        self.seat_limits: Dict[str, int] = {}
        self.chunk_limits: Optional[Dict[str, int]] = (
            {} if chunk_budget is not None else None)
        for tenant, weight in present.items():
            share = weight / total_weight
            cap = registry.share_cap_for(tenant)
            if cap is not None:
                share = min(share, cap)
            self.seat_limits[tenant] = max(
                1, int(cfg.max_num_seqs * share))
            if self.chunk_limits is not None:
                self.chunk_limits[tenant] = max(1, int(chunk_budget * share))
        self.seats: Dict[str, int] = {}
        for sg in scheduler.running:
            tenant = registry.tenant_for_adapter(sg.lora_int_id)
            self.seats[tenant] = (self.seats.get(tenant, 0)
                                  + sg.get_max_num_running_seqs())
        self.chunk_used: Dict[str, int] = {}

    def defer_admission(self, seq_group: SequenceGroup, pending_tokens: int,
                        check_chunk: bool = False) -> bool:
        """True when admitting would push the group's tenant past its
        seat cap this pass (or, for new prompts with `check_chunk`, its
        per-step chunk-token share is already spent) — the caller
        defers the group and `pending_tokens` is recorded as
        admission-deferred."""
        if not self.active:
            return False
        tenant = self._registry.tenant_for_adapter(seq_group.lora_int_id)
        seat_limit = self.seat_limits.get(tenant)
        if seat_limit is None:
            # Tenant appeared after this pass's caps were computed (e.g.
            # registered mid-step): no cap this pass, fair next pass.
            return False
        over_seats = (self.seats.get(tenant, 0)
                      + seq_group.get_max_num_running_seqs() > seat_limit)
        chunk_limit = ((self.chunk_limits or {}).get(tenant)
                       if check_chunk else None)
        chunk_spent = (chunk_limit is not None
                       and self.chunk_used.get(tenant, 0) >= chunk_limit)
        if not over_seats and not chunk_spent:
            return False
        get_tenant_stats().record_deferred(tenant,
                                           max(int(pending_tokens), 0))
        return True

    def note_admit(self, seq_group: SequenceGroup) -> None:
        if not self.active:
            return
        tenant = self._registry.tenant_for_adapter(seq_group.lora_int_id)
        self.seats[tenant] = (self.seats.get(tenant, 0)
                              + seq_group.get_max_num_running_seqs())

    def allowed_chunk(self, seq_group: SequenceGroup, want: int) -> int:
        """Clamp a prefill chunk to the tenant's remaining per-step
        token share; the granted amount is charged and the shortfall
        recorded as admission-deferred tokens."""
        if not self.active or self.chunk_limits is None or want <= 0:
            return want
        tenant = self._registry.tenant_for_adapter(seq_group.lora_int_id)
        limit = self.chunk_limits.get(tenant)
        if limit is None:
            return want
        used = self.chunk_used.get(tenant, 0)
        granted = max(0, min(want, limit - used))
        if granted:
            self.chunk_used[tenant] = used + granted
        if granted < want:
            get_tenant_stats().record_deferred(tenant, want - granted)
        return granted


class Scheduler:

    def __init__(
        self,
        scheduler_config: SchedulerConfig,
        cache_config: CacheConfig,
        lora_config: Optional[LoRAConfig] = None,
    ) -> None:
        self.scheduler_config = scheduler_config
        self.cache_config = cache_config
        self.lora_config = lora_config

        if scheduler_config.enable_chunked_prefill:
            # Chunked mode: the token budget caps per-step compute, not
            # prompt length — prompts longer than the budget are split.
            self.prompt_limit = scheduler_config.max_model_len
        else:
            # --disable-chunked-prefill escape hatch: prompts still run
            # as (single-chunk) mixed rows, so they must fit the step
            # budget whole.
            self.prompt_limit = min(scheduler_config.max_model_len,
                                    scheduler_config.max_num_batched_tokens)
        self._prefill_token_budget = scheduler_config.max_num_batched_tokens

        # Bucketed-shape mirror of the runner's mixed (token_budget,)
        # family (worker/model_runner.py builds its list from the same
        # helper with the same cap), so max_paddings and the starvation
        # guard's headroom are charged against the flat-row shape the
        # device actually runs.
        max_blocks = (scheduler_config.max_model_len +
                      cache_config.block_size - 1) // cache_config.block_size
        self._mixed_token_buckets = default_len_buckets(
            max(scheduler_config.max_num_batched_tokens,
                scheduler_config.max_num_seqs, max_blocks, 16),
            start=16)

        self.policy: Policy = PolicyFactory.get_policy(
            scheduler_config.policy,
            starvation_s=getattr(scheduler_config, "sjf_starvation_s", None))
        self.block_manager = BlockSpaceManager(
            block_size=cache_config.block_size,
            num_device_blocks=cache_config.num_device_blocks,
            num_cpu_blocks=cache_config.num_cpu_blocks,
            sliding_window=cache_config.sliding_window,
            num_state_slots=cache_config.num_state_slots,
            window_group=(
                (cache_config.window_group_size,
                 cache_config.num_window_device_blocks,
                 cache_config.num_window_cpu_blocks)
                if cache_config.num_window_device_blocks else None),
            window_ahead=cache_config.window_ahead_tokens,
        )
        self.prefix_pool = PrefixPool(cache_config.block_size)

        # Disaggregated role (docs/routing.md "Disaggregated roles"): the
        # behavioral split lives in the engine (prefill handoff) and the
        # router (KV orchestration); here the role drives admission
        # telemetry — a decode-role replica running a full local prefill
        # means the router's KV handoff missed.
        self.replica_role = getattr(scheduler_config, "replica_role",
                                    "mixed")
        self.prefill_recompute_count = 0

        self.waiting: Deque[SequenceGroup] = deque()
        self.running: Deque[SequenceGroup] = deque()
        self.swapped: Deque[SequenceGroup] = deque()

        # Pipelined-decode free guard: while a dispatched-but-unfetched
        # device step still references a sequence's KV pages, freeing
        # them would let a chained prefill reuse pages the in-flight
        # step's commit will scribble over. Guarded seqs' frees are
        # deferred until the engine unguards them (see LLMEngine pipeline).
        self._free_guard: Dict[int, int] = {}       # seq_id -> refcount
        self._deferred_free: Dict[int, Sequence] = {}

        # Speculative decoding (set by the engine when a draft model is
        # configured): decode scheduling turns per-row — spec-eligible
        # groups reserve scheduler_config.num_decode_steps (= K+1) slots
        # and join the step's spec_plan, everyone else reserves 1.
        self.spec_decode_enabled = False

        self._tracer = get_step_tracer()
        self._flight = get_flight_recorder()
        self._decisions = get_decision_log()

    @property
    def lora_enabled(self) -> bool:
        return self.lora_config is not None

    def _running_loras(self) -> Optional[Set[int]]:
        """Distinct adapter ids currently resident in the running batch
        (None when LoRA is disabled)."""
        if not self.lora_enabled:
            return None
        return set(sg.lora_int_id for sg in self.running
                   if sg.lora_int_id > 0)

    def _lora_cap_exceeded(self, curr_loras: Optional[Set[int]],
                           lora_id: int) -> bool:
        """Would admitting a group with this adapter exceed max_loras
        concurrent adapters (reference scheduler.py:218-227)?"""
        return (curr_loras is not None and lora_id > 0
                and lora_id not in curr_loras
                and len(curr_loras) >= self.lora_config.max_loras)

    def add_seq_group(self, seq_group: SequenceGroup) -> None:
        # `queued` marks scheduler admission (vs `arrived` at engine
        # entry, before tokenization) so SLO queue-wait = scheduled -
        # queued measures scheduler wait only.
        self._flight.record(seq_group.request_id, "queued")
        self._decisions.note_queued(seq_group.request_id)
        self.waiting.append(seq_group)

    def abort_seq_group(self, request_id: Union[str, Iterable[str]]) -> None:
        if isinstance(request_id, str):
            request_id = (request_id, )
        request_ids = set(request_id)
        for state_queue in (self.waiting, self.running, self.swapped):
            aborted: List[SequenceGroup] = []
            for seq_group in state_queue:
                if not request_ids:
                    break
                if seq_group.request_id in request_ids:
                    aborted.append(seq_group)
                    request_ids.remove(seq_group.request_id)
            for seq_group in aborted:
                state_queue.remove(seq_group)
                if self._flight.record(seq_group.request_id, "aborted"):
                    emitted = sum(s.get_output_len()
                                  for s in seq_group.get_seqs())
                    get_slo_tracker().record_finish(seq_group.request_id,
                                                    emitted)
                    # Aborted decodes must not calibrate the length
                    # predictor (their actual length is censored).
                    get_prediction_service().discard(seq_group.request_id)
                    # Aborts are workload too: a replayed stream must
                    # reproduce the cancelled tail, not just the wins.
                    from intellillm_tpu.obs.workload import get_workload_log
                    get_workload_log().record_seq_group(
                        seq_group, emitted_tokens=emitted,
                        reason="aborted")
                for seq in seq_group.get_seqs():
                    if seq.is_finished():
                        continue
                    seq.status = SequenceStatus.FINISHED_ABORTED
                    self.free_seq(seq)

    def has_unfinished_seqs(self) -> bool:
        return bool(self.waiting or self.running or self.swapped)

    def get_num_unfinished_seq_groups(self) -> int:
        return len(self.waiting) + len(self.running) + len(self.swapped)

    def iter_seq_groups(self) -> Iterable[SequenceGroup]:
        """Every in-flight group across the three state queues (the
        calibrator restamps their predictions through this)."""
        yield from self.waiting
        yield from self.running
        yield from self.swapped

    def _pop_preemption_victim(
            self, trigger: Optional[str] = None) -> SequenceGroup:
        """Remove and return the running group with the most predicted
        remaining work (p90 when available — evicting the priciest tail
        frees the most future block demand per preemption). Groups
        without any prediction fall back to the priority-order tail.
        `trigger` is the request that needed the blocks (decision-log
        attribution only)."""
        best_i = -1
        best_remaining = -1.0
        for i, sg in enumerate(self.running):
            plen = getattr(sg, "predicted_len_p90", None)
            if plen is None:
                plen = sg.predicted_len
            if plen is None:
                continue
            generated = max(
                (s.get_output_len() for s in sg.get_seqs()), default=0)
            remaining = max(float(plen) - generated, 0.0)
            if remaining > best_remaining:
                best_i, best_remaining = i, remaining
        if best_i < 0:
            victim = self.running.pop()  # lowest priority
            self._decisions.preempt_victim(
                victim.request_id, None, trigger, "priority_tail")
            return victim
        victim = self.running[best_i]
        del self.running[best_i]
        self._decisions.preempt_victim(
            victim.request_id, best_remaining, trigger, "p90_priced")
        return victim

    # --- the scheduling pass --------------------------------------------

    def _schedule(self, prefill_only: bool = False,
                  chunk_long_prompts: bool = False) -> SchedulerOutputs:
        blocks_to_swap_in: Dict[int, int] = {}
        blocks_to_swap_out: Dict[int, int] = {}
        blocks_to_copy: Dict[int, List[int]] = {}
        ignored_seq_groups: List[SequenceGroup] = []
        blocked: Optional[str] = None

        now = time.monotonic()

        # Chunked prefill (the default): decode-first mixed steps. Once
        # any admitted sequence is mid-prefill, every step MUST go through
        # the chunked pass until prefills drain — the decode pass below
        # would treat a partially-prefilled sequence as a decode row over
        # garbage KV. With nothing waiting and nothing mid-prefill the
        # pass falls through so steady-state decode runs the fused
        # multi-step program.
        if (self.scheduler_config.enable_chunked_prefill
                and not prefill_only
                and (self.waiting
                     or any(self._is_prefilling(sg)
                            for sg in list(self.running)
                            + list(self.swapped)))):
            return self._chunked_pass(now)
        if (self.scheduler_config.enable_chunked_prefill and prefill_only
                and chunk_long_prompts and not self.swapped
                and self._pipelined_chunking_due()):
            return self._chunked_pass(now, prefill_only=True)

        # Prompt admission: runs for --disable-chunked-prefill mode and
        # for pipelined prefill-only passes. Prompts still execute as
        # mixed token rows — each admission emits one whole-prompt chunk
        # (flat token accounting against the mixed bucket family), so
        # only the mixed program family ever runs.
        # Admit while nothing is swapped out (swapped groups have
        # priority — they were already admitted once).
        if self.swapped and self.waiting:
            self._decisions.pass_blocked("swap_backlog")
        if not self.swapped:
            scheduled: List[SequenceGroup] = []
            chunks: Dict[str, Tuple[int, int, bool]] = {}
            num_curr_seqs = sum(sg.get_max_num_running_seqs()
                                for sg in self.running)
            num_batched_tokens = 0
            curr_loras = self._running_loras()
            lora_deferred: List[SequenceGroup] = []
            fairness = _TenantFairnessPass(self)
            tenant_deferred: List[SequenceGroup] = []

            # SJF makes admission order policy-driven too: sort the waiting
            # queue by policy priority (FCFS degenerates to arrival order).
            if self.scheduler_config.policy != "fcfs":
                self.waiting = deque(
                    self.policy.sort_by_priority(now, self.waiting))

            while self.waiting:
                seq_group = self.waiting[0]
                waiting_seqs = seq_group.get_seqs(
                    status=SequenceStatus.WAITING)
                assert len(waiting_seqs) == 1, (
                    "Waiting sequence group should have only one prompt "
                    "sequence.")
                num_prompt_tokens = waiting_seqs[0].get_len()
                if num_prompt_tokens > self.prompt_limit:
                    logger.warning(
                        "Input prompt (%d tokens) is too long and exceeds "
                        "limit of %d", num_prompt_tokens, self.prompt_limit)
                    for seq in waiting_seqs:
                        seq.status = SequenceStatus.FINISHED_IGNORED
                    ignored_seq_groups.append(seq_group)
                    self.waiting.popleft()
                    continue

                can_allocate = self.block_manager.can_allocate(seq_group)
                if can_allocate == AllocStatus.LATER:
                    blocked = "kv_watermark"
                    self._decisions.pass_blocked(
                        blocked, self.block_manager.kv_pressure_detail())
                    break
                if (can_allocate == AllocStatus.OK and not
                        self.block_manager.can_allocate_state(seq_group)):
                    # Blocks but no state slot: like seats, a later pass
                    # admits when a sequence has ended (no drain).
                    blocked = "state_slots"
                    self._decisions.pass_blocked(blocked)
                    break
                if can_allocate == AllocStatus.NEVER:
                    logger.warning(
                        "Input prompt (%d tokens) cannot be allocated even "
                        "with an empty KV cache; ignoring.", num_prompt_tokens)
                    for seq in waiting_seqs:
                        seq.status = SequenceStatus.FINISHED_IGNORED
                    ignored_seq_groups.append(seq_group)
                    self.waiting.popleft()
                    continue

                lora_id = seq_group.lora_int_id
                if self._lora_cap_exceeded(curr_loras, lora_id):
                    # Defer: admitting would exceed the concurrent-adapter
                    # slots; later groups may still fit.
                    self._decisions.defer(seq_group.request_id, "lora_cap")
                    self.waiting.popleft()
                    lora_deferred.append(seq_group)
                    continue
                if fairness.defer_admission(
                        seq_group,
                        waiting_seqs[0].data.get_num_uncomputed_tokens(),
                        check_chunk=True):
                    self._decisions.defer(seq_group.request_id,
                                          "tenant_fairness")
                    self.waiting.popleft()
                    tenant_deferred.append(seq_group)
                    continue

                # Computed prefix-cache tokens are skipped: their KV is
                # already resident, so the chunk starts past them.
                start = 0
                prefix = seq_group.prefix
                if prefix is not None and prefix.computed:
                    start = min(prefix.get_length(), num_prompt_tokens - 1)
                new_tokens = num_prompt_tokens - start
                # Flat token accounting: the runner flattens prompt rows
                # into one (token_budget,)-bucketed batch, so the budget
                # caps the SUM of chunk tokens, not batch x max-len.
                if num_batched_tokens + new_tokens > self._prefill_token_budget:
                    # Alone over the budget, it too needs chunking.
                    blocked = ("token_budget" if num_batched_tokens
                               else "needs_chunking")
                    self._decisions.pass_blocked("token_budget")
                    break

                num_new_seqs = seq_group.get_max_num_running_seqs()
                if (num_curr_seqs + num_new_seqs
                        > self.scheduler_config.max_num_seqs):
                    blocked = "max_seqs"
                    self._decisions.pass_blocked(blocked)
                    break

                # Padding waste counted against the *bucketed* flat shape
                # the runner actually pads to. A lone prompt is always
                # admitted: its bucket padding is intrinsic — no admission
                # decision can shrink it.
                total = num_batched_tokens + new_tokens
                num_paddings = (
                    pad_to_bucket(total, self._mixed_token_buckets) - total)
                if scheduled and num_paddings > self.scheduler_config.max_paddings:
                    blocked = "padding"
                    self._decisions.pass_blocked(blocked)
                    break
                num_batched_tokens = total

                self.waiting.popleft()
                self._allocate(seq_group)
                chunks[seq_group.request_id] = (start, new_tokens, True)
                self.running.append(seq_group)
                num_curr_seqs += num_new_seqs
                if curr_loras is not None and lora_id > 0:
                    curr_loras.add(lora_id)
                fairness.note_admit(seq_group)
                scheduled.append(seq_group)
                self._decisions.scheduled(seq_group.request_id)
                if seq_group.first_scheduled_time is None:
                    seq_group.first_scheduled_time = now
                    self._flight.record(seq_group.request_id, "scheduled")
                self._flight.record(seq_group.request_id, "prefill_start",
                                    detail=f"tokens={num_prompt_tokens}")

            # Deferred-for-LoRA groups go back to the front (in order).
            for sg in reversed(lora_deferred):
                self.waiting.appendleft(sg)
            for sg in reversed(tenant_deferred):
                self.waiting.appendleft(sg)

            if scheduled or ignored_seq_groups:
                return SchedulerOutputs(
                    scheduled_seq_groups=scheduled,
                    prompt_run=True,
                    num_batched_tokens=num_batched_tokens,
                    blocks_to_swap_in=blocks_to_swap_in,
                    blocks_to_swap_out=blocks_to_swap_out,
                    blocks_to_copy=blocks_to_copy,
                    ignored_seq_groups=ignored_seq_groups,
                    chunked_prefills=chunks,
                    num_prefill_tokens=num_batched_tokens,
                    blocked=blocked,
                )

        if prefill_only:
            # Pipelined admission: the caller only wants prompts it can
            # chain behind in-flight decode steps. No decode side effects
            # (no re-sort, no preemption, no swap planning) may run with
            # device steps still unfetched.
            return SchedulerOutputs(
                scheduled_seq_groups=[], prompt_run=True,
                num_batched_tokens=0, blocks_to_swap_in={},
                blocks_to_swap_out={}, blocks_to_copy={},
                ignored_seq_groups=[], blocked=blocked)

        # Decode step. Highest-priority groups keep their blocks; the
        # lowest-priority running groups get preempted when memory runs out.
        self.running = deque(self.policy.sort_by_priority(now, self.running))

        # Fused decode-step count for this batch: beam-search groups need
        # host fork/prune after every token, penalty-bearing groups need
        # fresh token counts, and logits_processors run on host between
        # steps, so their presence forces K=1. Stop strings / stop tokens /
        # EOS do NOT: the engine checks stops per fused substep and
        # discards the overshoot tokens (the same mechanism as max_tokens
        # overshoot), so a chatty request no longer degrades the whole
        # batch. Swapped groups are included since they may join this very
        # batch via swap-in.
        num_steps = self.scheduler_config.num_decode_steps
        spec_requests: Optional[Set[str]] = None
        if self.spec_decode_enabled:
            # Per-row speculation replaces the batch-wide fused K: each
            # eligible group reserves K+1 slots (draft proposals + bonus)
            # and joins the spec plan as it is scheduled below; every
            # other group reserves 1 and decodes a single plain token in
            # the same round.
            spec_requests = set()
        else:
            for sg in list(self.running) + list(self.swapped):
                sp = sg.sampling_params
                if (sp.use_beam_search or sp.presence_penalty
                        or sp.frequency_penalty
                        or sp.repetition_penalty != 1.0
                        or sp.logits_processors):
                    num_steps = 1
                    break
        # K is deliberately NOT clamped to remaining max_tokens: a varying K
        # would compile a fresh decode executable per value. Overshoot
        # tokens are discarded by the engine's stop checks; only {1, K}
        # decode programs ever exist.

        running: Deque[SequenceGroup] = deque()
        preempted: List[SequenceGroup] = []
        while self.running:
            seq_group = self.running.popleft()
            steps = self._row_steps(seq_group, num_steps, spec_requests)
            while not self.block_manager.can_append_slots(
                    seq_group, self._clamped_steps(seq_group, steps)):
                if self.running:
                    victim = self._pop_preemption_victim(
                        trigger=seq_group.request_id)
                    self._preempt(victim, blocks_to_swap_out)
                    preempted.append(victim)
                else:
                    self._preempt(seq_group, blocks_to_swap_out)
                    preempted.append(seq_group)
                    break
            else:
                self._append_slots(seq_group, steps, blocks_to_copy)
                if spec_requests is not None and steps > 1:
                    spec_requests.add(seq_group.request_id)
                running.append(seq_group)
        self.running = running

        # Swap in previously swapped-out groups while there's room.
        if not prefill_only:
            self.swapped = deque(
                self.policy.sort_by_priority(now, self.swapped))
        if not preempted and not prefill_only:
            num_curr_seqs = sum(sg.get_max_num_running_seqs()
                                for sg in self.running)
            curr_loras = self._running_loras()
            lora_deferred_swap: List[SequenceGroup] = []
            fairness = _TenantFairnessPass(self)
            tenant_deferred_swap: List[SequenceGroup] = []
            while self.swapped:
                seq_group = self.swapped[0]
                steps = self._row_steps(seq_group, num_steps, spec_requests)
                if not self.block_manager.can_swap_in(
                        seq_group, self._clamped_steps(seq_group, steps)):
                    self._decisions.pass_blocked(
                        "kv_watermark",
                        self.block_manager.kv_pressure_detail())
                    break
                lora_id = seq_group.lora_int_id
                if self._lora_cap_exceeded(curr_loras, lora_id):
                    self._decisions.defer(seq_group.request_id, "lora_cap")
                    self.swapped.popleft()
                    lora_deferred_swap.append(seq_group)
                    continue
                if fairness.defer_admission(
                        seq_group, seq_group.get_max_num_running_seqs()):
                    self._decisions.defer(seq_group.request_id,
                                          "tenant_fairness")
                    self.swapped.popleft()
                    tenant_deferred_swap.append(seq_group)
                    continue
                num_new_seqs = seq_group.get_max_num_running_seqs()
                if (num_curr_seqs + num_new_seqs
                        > self.scheduler_config.max_num_seqs):
                    self._decisions.pass_blocked("max_seqs")
                    break
                self.swapped.popleft()
                self._swap_in(seq_group, blocks_to_swap_in)
                self._append_slots(seq_group, steps, blocks_to_copy)
                if spec_requests is not None and steps > 1:
                    spec_requests.add(seq_group.request_id)
                num_curr_seqs += num_new_seqs
                if curr_loras is not None and lora_id > 0:
                    curr_loras.add(lora_id)
                fairness.note_admit(seq_group)
                self.running.append(seq_group)
            for sg in reversed(lora_deferred_swap):
                self.swapped.appendleft(sg)
            for sg in reversed(tenant_deferred_swap):
                self.swapped.appendleft(sg)

        num_batched_tokens = sum(
            sg.num_seqs(status=SequenceStatus.RUNNING) for sg in self.running)
        if spec_requests is not None:
            # Multi-step only when at least one row actually speculates;
            # a fully ineligible batch is a plain single-step decode.
            num_steps = (self.scheduler_config.num_decode_steps
                         if spec_requests else 1)
        return SchedulerOutputs(
            scheduled_seq_groups=list(self.running),
            prompt_run=False,
            num_batched_tokens=num_batched_tokens,
            blocks_to_swap_in=blocks_to_swap_in,
            blocks_to_swap_out=blocks_to_swap_out,
            blocks_to_copy=blocks_to_copy,
            ignored_seq_groups=[],
            num_decode_steps=num_steps,
            spec_plan=spec_requests or None,
        )

    # --- chunked prefill (mixed decode+prefill steps) ---------------------

    @staticmethod
    def _is_prefilling(seq_group: SequenceGroup) -> bool:
        return any(not s.data.prefill_complete
                   for s in seq_group.get_unfinished_seqs())

    def _chunked_pass(self, now: float,
                      prefill_only: bool = False) -> SchedulerOutputs:
        """One mixed step: admit every runnable decode first (preempting
        as needed), then spend the remaining token-budget slack on prefill
        chunks — continuing in-flight chunked prefills before admitting
        new prompts (Sarathi-Serve style decode-maximal batching).

        `prefill_only` (a pipelined pass, device steps still unfetched):
        the chunks alone, with the whole budget and no decode row, no
        preemption and no swap planning: the decode batch goes on by
        continuation beside them, and a sequence joins it once its final
        chunk has been fetched."""
        blocks_to_swap_in: Dict[int, int] = {}
        blocks_to_swap_out: Dict[int, int] = {}
        blocks_to_copy: Dict[int, List[int]] = {}
        ignored_seq_groups: List[SequenceGroup] = []
        budget = self.scheduler_config.max_num_batched_tokens
        chunks: Dict[str, Tuple[int, int, bool]] = {}

        # Pass 1: decodes. Mid-prefill groups pass straight through — their
        # prompt blocks were fully allocated at admission, and they emit no
        # token this step, so no slot growth either.
        if not prefill_only:
            self.running = deque(
                self.policy.sort_by_priority(now, self.running))
        running: Deque[SequenceGroup] = deque()
        decode_groups: List[SequenceGroup] = []
        prefilling_groups: List[SequenceGroup] = []
        preempted: List[SequenceGroup] = []
        decode_rows = 0
        # Compute charged against the token budget by decode rows: a
        # plain row costs 1, a speculative row costs K+1 (the teacher
        # verifies K+1 positions for it) — prefill slack shrinks
        # accordingly so a spec-heavy batch doesn't overcommit the step.
        decode_charge = 0
        spec_rows = 0
        spec_requests: Optional[Set[str]] = None
        if self.spec_decode_enabled:
            spec_requests = set()
        if prefill_only:
            prefilling_groups = [sg for sg in self.running
                                 if self._is_prefilling(sg)]
            running, self.running = self.running, deque()
        while self.running:
            seq_group = self.running.popleft()
            if self._is_prefilling(seq_group):
                prefilling_groups.append(seq_group)
                running.append(seq_group)
                continue
            steps = self._row_steps(seq_group, 1, spec_requests)
            while not self.block_manager.can_append_slots(
                    seq_group, self._clamped_steps(seq_group, steps)):
                if self.running:
                    victim = self._pop_preemption_victim(
                        trigger=seq_group.request_id)
                    self._preempt(victim, blocks_to_swap_out)
                    preempted.append(victim)
                else:
                    self._preempt(seq_group, blocks_to_swap_out)
                    preempted.append(seq_group)
                    break
            else:
                self._append_slots(seq_group, steps, blocks_to_copy)
                if spec_requests is not None and steps > 1:
                    spec_requests.add(seq_group.request_id)
                running.append(seq_group)
                decode_groups.append(seq_group)
                n = seq_group.num_seqs(status=SequenceStatus.RUNNING)
                decode_rows += n
                decode_charge += n * steps
                if steps > 1:
                    spec_rows += n
        self.running = running
        # A preempted victim may have been mid-prefill; drop stale entries.
        prefilling_groups = [sg for sg in prefilling_groups
                             if sg in self.running]

        # Per-tenant fairness caps for this step (seat caps gate the
        # swap-in/admission passes below; chunk-token caps split the
        # prefill slack). Inactive unless >= 2 tenants are present.
        fairness = _TenantFairnessPass(self, chunk_budget=budget)

        # Pass 2: swap-in (decode-ready groups join the batch, mid-prefill
        # groups resume chunking where their KV left off).
        if not prefill_only:
            self.swapped = deque(
                self.policy.sort_by_priority(now, self.swapped))
        if not preempted and not prefill_only:
            num_curr_seqs = sum(sg.get_max_num_running_seqs()
                                for sg in self.running)
            curr_loras = self._running_loras()
            lora_deferred_swap: List[SequenceGroup] = []
            tenant_deferred_swap: List[SequenceGroup] = []
            while self.swapped:
                seq_group = self.swapped[0]
                steps = self._row_steps(seq_group, 1, spec_requests)
                if not self.block_manager.can_swap_in(
                        seq_group, self._clamped_steps(seq_group, steps)):
                    self._decisions.pass_blocked(
                        "kv_watermark",
                        self.block_manager.kv_pressure_detail())
                    break
                lora_id = seq_group.lora_int_id
                if self._lora_cap_exceeded(curr_loras, lora_id):
                    self._decisions.defer(seq_group.request_id, "lora_cap")
                    self.swapped.popleft()
                    lora_deferred_swap.append(seq_group)
                    continue
                if fairness.defer_admission(
                        seq_group, seq_group.get_max_num_running_seqs()):
                    self._decisions.defer(seq_group.request_id,
                                          "tenant_fairness")
                    self.swapped.popleft()
                    tenant_deferred_swap.append(seq_group)
                    continue
                num_new_seqs = seq_group.get_max_num_running_seqs()
                if (num_curr_seqs + num_new_seqs
                        > self.scheduler_config.max_num_seqs):
                    self._decisions.pass_blocked("max_seqs")
                    break
                self.swapped.popleft()
                self._swap_in(seq_group, blocks_to_swap_in)
                if self._is_prefilling(seq_group):
                    prefilling_groups.append(seq_group)
                else:
                    self._append_slots(seq_group, steps, blocks_to_copy)
                    if spec_requests is not None and steps > 1:
                        spec_requests.add(seq_group.request_id)
                    decode_groups.append(seq_group)
                    n = seq_group.num_seqs(status=SequenceStatus.RUNNING)
                    decode_rows += n
                    decode_charge += n * steps
                    if steps > 1:
                        spec_rows += n
                num_curr_seqs += num_new_seqs
                if curr_loras is not None and lora_id > 0:
                    curr_loras.add(lora_id)
                fairness.note_admit(seq_group)
                self.running.append(seq_group)
            for sg in reversed(lora_deferred_swap):
                self.swapped.appendleft(sg)
            for sg in reversed(tenant_deferred_swap):
                self.swapped.appendleft(sg)

        # Pass 3: spend the slack on prefill chunks — in-flight first.
        slack = budget - decode_charge
        if slack <= 0 and (prefilling_groups
                           or (self.waiting and not preempted
                               and not self.swapped)):
            # Starvation guard — prefills must advance every step even
            # when decode work alone fills the token budget. The padded
            # bucket usually has free rows (headroom is measured against
            # the MIXED flat batch only: spec rows ride the separate
            # teacher program, not these buckets), so chunk tokens ride
            # in the padding for free; if the resident rows land exactly
            # on a bucket edge, defer the lowest-priority decode group by
            # one step instead (it stays RUNNING and rejoins next step).
            mixed_rows = decode_rows - spec_rows
            slack = (pad_to_bucket(max(mixed_rows, 1),
                                   self._mixed_token_buckets) - mixed_rows)
            if slack <= 0 and decode_groups:
                deferred = decode_groups.pop()
                self._decisions.defer(
                    deferred.request_id, "token_budget",
                    "decode_deferred_one_step_for_prefill")
                n = deferred.num_seqs(status=SequenceStatus.RUNNING)
                decode_rows -= n
                if (spec_requests is not None
                        and deferred.request_id in spec_requests):
                    spec_requests.discard(deferred.request_id)
                    spec_rows -= n
                    decode_charge -= (
                        n * self.scheduler_config.num_decode_steps)
                else:
                    decode_charge -= n
                slack = budget - decode_charge
        chunk_groups: List[SequenceGroup] = []
        for seq_group in prefilling_groups:
            if slack <= 0:
                break
            seq = seq_group.get_seqs(status=SequenceStatus.RUNNING)[0]
            remaining = seq.data.get_num_uncomputed_tokens()
            want = min(remaining, slack)
            size = fairness.allowed_chunk(seq_group, want)
            if size <= 0:
                # Tenant's chunk share for this step is spent; the group
                # stays resident and resumes next step.
                self._decisions.chunk_split(
                    seq_group.request_id,
                    seq.data.get_num_computed_tokens(), 0, remaining,
                    "tenant_fairness")
                continue
            start = seq.data.get_num_computed_tokens()
            final = size == remaining
            if not final:
                self._decisions.chunk_split(
                    seq_group.request_id, start, size, remaining - size,
                    "tenant_fairness" if size < want else "token_budget")
            seq.data.update_num_computed_tokens(size)
            if final:
                seq.data.mark_prefill_complete()
            chunks[seq_group.request_id] = (start, size, final)
            chunk_groups.append(seq_group)
            slack -= size

        # Pass 4: admit new prompts into whatever slack is left (every
        # prompt is chunkable now — beam/best_of fan out through the
        # mixed dispatch's multi-sample rows, prompt_logprobs accumulate
        # across chunks, prefix hits start past the computed tokens).
        # Swapped groups keep priority; a preempting step admits nothing.
        if self.waiting and (preempted or self.swapped):
            self._decisions.pass_blocked(
                "preempted" if preempted else "swap_backlog")
        if not preempted and not self.swapped:
            num_curr_seqs = sum(sg.get_max_num_running_seqs()
                                for sg in self.running)
            curr_loras = self._running_loras()
            lora_deferred: List[SequenceGroup] = []
            tenant_deferred: List[SequenceGroup] = []
            if self.scheduler_config.policy != "fcfs":
                self.waiting = deque(
                    self.policy.sort_by_priority(now, self.waiting))
            while self.waiting and slack > 0:
                seq_group = self.waiting[0]
                waiting_seqs = seq_group.get_seqs(
                    status=SequenceStatus.WAITING)
                assert len(waiting_seqs) == 1, (
                    "Waiting sequence group should have only one prompt "
                    "sequence.")
                num_prompt_tokens = waiting_seqs[0].get_len()
                if num_prompt_tokens > self.prompt_limit:
                    logger.warning(
                        "Input prompt (%d tokens) is too long and exceeds "
                        "limit of %d", num_prompt_tokens, self.prompt_limit)
                    for seq in waiting_seqs:
                        seq.status = SequenceStatus.FINISHED_IGNORED
                    ignored_seq_groups.append(seq_group)
                    self.waiting.popleft()
                    continue
                can_allocate = self.block_manager.can_allocate(seq_group)
                if can_allocate == AllocStatus.LATER:
                    self._decisions.pass_blocked(
                        "kv_watermark",
                        self.block_manager.kv_pressure_detail())
                    break
                if (can_allocate == AllocStatus.OK and not
                        self.block_manager.can_allocate_state(seq_group)):
                    self._decisions.pass_blocked("state_slots")
                    break
                if can_allocate == AllocStatus.NEVER:
                    logger.warning(
                        "Input prompt (%d tokens) cannot be allocated even "
                        "with an empty KV cache; ignoring.",
                        num_prompt_tokens)
                    for seq in waiting_seqs:
                        seq.status = SequenceStatus.FINISHED_IGNORED
                    ignored_seq_groups.append(seq_group)
                    self.waiting.popleft()
                    continue
                lora_id = seq_group.lora_int_id
                if self._lora_cap_exceeded(curr_loras, lora_id):
                    self.waiting.popleft()
                    lora_deferred.append(seq_group)
                    continue
                if fairness.defer_admission(
                        seq_group,
                        waiting_seqs[0].data.get_num_uncomputed_tokens(),
                        check_chunk=True):
                    self._decisions.defer(seq_group.request_id,
                                          "tenant_fairness")
                    self.waiting.popleft()
                    tenant_deferred.append(seq_group)
                    continue
                num_new_seqs = seq_group.get_max_num_running_seqs()
                if (num_curr_seqs + num_new_seqs
                        > self.scheduler_config.max_num_seqs):
                    self._decisions.pass_blocked("max_seqs")
                    break
                self.waiting.popleft()
                self._allocate(seq_group, mark_prefilled=False)
                seq = seq_group.get_seqs(status=SequenceStatus.RUNNING)[0]
                # Computed prefix-cache tokens are skipped: their KV is
                # already resident, so the first chunk starts past them.
                start = 0
                prefix = seq_group.prefix
                if prefix is not None and prefix.computed:
                    start = min(prefix.get_length(), num_prompt_tokens - 1)
                    seq.data.update_num_computed_tokens(start)
                if (self.replica_role == "decode" and start == 0
                        and num_prompt_tokens
                        > self.cache_config.block_size):
                    # Tail chunks (< one block past an imported prefix)
                    # are expected on decode replicas; a whole multi-block
                    # prompt with no computed prefix is not.
                    self.prefill_recompute_count += 1
                    logger.warning(
                        "decode-role replica is running a full local "
                        "prefill (%d tokens, no imported prefix) — the "
                        "router's KV handoff missed for %s",
                        num_prompt_tokens, seq_group.request_id)
                remaining = num_prompt_tokens - start
                want = min(remaining, slack)
                size = fairness.allowed_chunk(seq_group, want)
                final = size == remaining
                if not final:
                    self._decisions.chunk_split(
                        seq_group.request_id, start, size, remaining - size,
                        "tenant_fairness" if size < want else "token_budget")
                seq.data.update_num_computed_tokens(size)
                if final:
                    seq.data.mark_prefill_complete()
                chunks[seq_group.request_id] = (start, size, final)
                chunk_groups.append(seq_group)
                slack -= size
                self.running.append(seq_group)
                num_curr_seqs += num_new_seqs
                if curr_loras is not None and lora_id > 0:
                    curr_loras.add(lora_id)
                fairness.note_admit(seq_group)
                self._decisions.scheduled(seq_group.request_id)
                if seq_group.first_scheduled_time is None:
                    seq_group.first_scheduled_time = now
                    self._flight.record(seq_group.request_id, "scheduled")
                self._flight.record(
                    seq_group.request_id, "prefill_start",
                    detail=f"tokens={num_prompt_tokens},chunked=1")
            if self.waiting and slack <= 0:
                # Loop exited with prompts still waiting: the step's
                # token budget is spent.
                self._decisions.pass_blocked("token_budget")
            for sg in reversed(lora_deferred):
                self.waiting.appendleft(sg)
            for sg in reversed(tenant_deferred):
                self.waiting.appendleft(sg)

        num_prefill_tokens = sum(size for _, size, _ in chunks.values())
        return SchedulerOutputs(
            scheduled_seq_groups=decode_groups + chunk_groups,
            prompt_run=prefill_only,
            num_batched_tokens=decode_rows + num_prefill_tokens,
            blocks_to_swap_in=blocks_to_swap_in,
            blocks_to_swap_out=blocks_to_swap_out,
            blocks_to_copy=blocks_to_copy,
            ignored_seq_groups=ignored_seq_groups,
            num_decode_steps=(self.scheduler_config.num_decode_steps
                              if spec_requests else 1),
            chunked_prefills=chunks,
            num_prefill_tokens=num_prefill_tokens,
            num_mixed_decode_tokens=decode_rows,
            spec_plan=spec_requests or None,
        )

    def schedule(
        self, prefill_only: bool = False, chunk_long_prompts: bool = False,
    ) -> Tuple[List[SequenceGroupMetadata], SchedulerOutputs]:
        """`prefill_only`: a pipelined pass, prompts alone.
        `chunk_long_prompts` (with it): a prompt longer than the token
        budget is admitted and continued a chunk a pass (the engine asks
        so while a decode step is in flight); without, such a prompt
        blocks the pass ("needs_chunking") and waits for a full one."""
        with self._tracer.span("schedule"):
            # Decision-log pass bracket: verdict sites inside _schedule
            # report what blocked admission; end_pass charges every
            # still-waiting request the elapsed wall time to the cause
            # observed this pass (see obs/decisions.py).
            self._decisions.begin_pass()
            scheduler_outputs = self._schedule(
                prefill_only=prefill_only,
                chunk_long_prompts=chunk_long_prompts)
            self._decisions.end_pass(
                [sg.request_id for sg in self.waiting],
                [sg.request_id for sg in self.swapped])

            seq_group_metadata_list: List[SequenceGroupMetadata] = []
            for seq_group in scheduler_outputs.scheduled_seq_groups:
                chunk = None
                if scheduler_outputs.chunked_prefills:
                    chunk = scheduler_outputs.chunked_prefills.get(
                        seq_group.request_id)
                seq_group_metadata_list.append(self.group_metadata(
                    seq_group, chunk, scheduler_outputs.prompt_run))
        return seq_group_metadata_list, scheduler_outputs

    def group_metadata(
        self, seq_group: SequenceGroup,
        chunk: Optional[Tuple[int, int, bool]] = None,
        prompt_run: bool = False,
    ) -> SequenceGroupMetadata:
        """The runner's payload for one group's running sequences: a
        decode entry, or (with `chunk` / `prompt_run`) a prefill one."""
        seq_data: Dict[int, SequenceData] = {}
        block_tables: Dict[int, List[int]] = {}
        window_tables: Optional[Dict[int, List[int]]] = (
            None if self.block_manager.window_group is None else {})
        for seq in seq_group.get_seqs(status=SequenceStatus.RUNNING):
            seq_data[seq.seq_id] = seq.data
            if chunk is not None:
                # A windowed cache group moves on with the prompt.
                self.block_manager.advance_for_chunk(seq, chunk[0], chunk[1])
            block_tables[seq.seq_id] = (
                self.block_manager.get_block_table(seq))
            if window_tables is not None:
                window_tables[seq.seq_id] = (
                    self.block_manager.get_window_block_table(seq))
        state_slots = None
        if self.block_manager.num_state_slots:
            state_slots = {sid: self.block_manager.get_state_slot(sid)
                           for sid in seq_data}
        return SequenceGroupMetadata(
            request_id=seq_group.request_id,
            is_prompt=True if chunk is not None else prompt_run,
            seq_data=seq_data,
            sampling_params=seq_group.sampling_params,
            block_tables=block_tables,
            lora_request=seq_group.lora_request,
            prefix=seq_group.prefix,
            token_chunk_size=chunk[1] if chunk is not None else None,
            num_computed_tokens=chunk[0] if chunk is not None else 0,
            state_slots=state_slots,
            window_block_tables=window_tables,
        )

    def fork_seq(self, parent_seq: Sequence, child_seq: Sequence) -> None:
        self.block_manager.fork(parent_seq, child_seq)

    def free_seq(self, seq: Sequence) -> None:
        if self._free_guard.get(seq.seq_id, 0) > 0:
            self._deferred_free[seq.seq_id] = seq
            # The state slot does not wait for the steps in flight
            # (core/block_manager.py says why that is safe).
            self.block_manager.release_state_slot(seq)
            return
        self.block_manager.free(seq)

    def free_finished_seq_groups(self) -> None:
        self.running = deque(sg for sg in self.running if not sg.is_finished())

    # --- pipelined-decode support ----------------------------------------

    def guard_seqs(self, seq_ids: Iterable[int]) -> None:
        for sid in seq_ids:
            self._free_guard[sid] = self._free_guard.get(sid, 0) + 1

    def unguard_seqs(self, seq_ids: Iterable[int]) -> None:
        for sid in seq_ids:
            n = self._free_guard.get(sid, 0) - 1
            if n > 0:
                self._free_guard[sid] = n
                continue
            self._free_guard.pop(sid, None)
            seq = self._deferred_free.pop(sid, None)
            if seq is not None:
                self.block_manager.free(seq)

    def _pipelined_chunking_due(self) -> bool:
        """Whether a pipelined (prefill-only) pass has chunks to run: a
        resident sequence is part-way through its prompt, or the next
        waiting prompt is longer than a step's token budget. (A prompt
        that fits a step whole is admitted whole, as ever.)"""
        if self.mid_prefill():
            return True
        if not self.waiting:
            return False
        seq = self.waiting[0].get_seqs(status=SequenceStatus.WAITING)[0]
        return (self.prompt_limit >= seq.get_len()
                > self._prefill_token_budget)

    def mid_prefill(self) -> bool:
        """Whether a resident sequence is part-way through its prompt:
        its next chunk needs a fresh mixed scheduling pass, so the
        running decode batch may not be continued past it."""
        return any(self._is_prefilling(sg) for sg in self.running)

    # --- internals -------------------------------------------------------

    def _allocate(self, seq_group: SequenceGroup,
                  mark_prefilled: bool = True) -> None:
        self.block_manager.allocate(seq_group)
        for seq in seq_group.get_seqs(status=SequenceStatus.WAITING):
            seq.status = SequenceStatus.RUNNING
            if mark_prefilled:
                # Homogeneous admission computes the whole history this
                # step; chunked admission advances per chunk instead.
                seq.data.mark_prefill_complete()

    def _row_steps(self, seq_group: SequenceGroup, num_steps: int,
                   spec_requests: Optional[Set[str]]) -> int:
        """Decode-slot lookahead for one group this round. Non-spec
        engines use the batch-wide fused K; spec engines reserve K+1 for
        eligible rows (the draft proposals + the bonus position all land
        before the next scheduling pass) and 1 for everyone else."""
        if spec_requests is None:
            return num_steps
        k = self.scheduler_config.num_decode_steps
        eligible = seq_group_spec_eligible(seq_group)
        # Decision-log verdict (recorded on eligibility change only).
        self._decisions.spec_plan(seq_group.request_id, eligible, k)
        return k if eligible else 1

    def _clamped_steps(self, seq_group: SequenceGroup,
                       num_steps: int) -> int:
        """K-slot lookahead clamped at max_model_len, conservatively over
        the group's running/swapped seqs (shortest seq needs the most).
        Admission checks (can_append_slots / can_swap_in) must use the
        SAME clamp as the actual reservation, or a near-cap sequence gets
        preempted for blocks it would never allocate — with a tight pool
        that preempt/re-prefill cycle never terminates."""
        mml = self.scheduler_config.max_model_len
        lens = [seq.get_len() for seq in seq_group.get_unfinished_seqs()]
        min_len = min(lens) if lens else mml
        return max(1, min(num_steps, mml - min_len + 1))

    def _append_slots(
        self,
        seq_group: SequenceGroup,
        num_steps: int,
        blocks_to_copy: Dict[int, List[int]],
    ) -> None:
        mml = self.scheduler_config.max_model_len
        for seq in seq_group.get_seqs(status=SequenceStatus.RUNNING):
            # Clamp the K-slot lookahead at max_model_len: decode positions
            # past it are never written (the device drops overshoot), and
            # reserving blocks beyond ceil(max_model_len/block_size) would
            # overflow the block-table width buckets for prompts near the
            # cap (len + K - 1 > max_model_len).
            eff = max(1, min(num_steps, mml - seq.get_len() + 1))
            for src, dst in self.block_manager.append_slots(seq, eff):
                blocks_to_copy.setdefault(src, []).append(dst)

    def _preempt(
        self,
        seq_group: SequenceGroup,
        blocks_to_swap_out: Dict[int, int],
        preemption_mode: Optional[PreemptionMode] = None,
    ) -> None:
        # Single live sequence → recompute (re-prefill later) is cheaper and
        # exact; multiple live sequences → must swap (fork state can't be
        # reproduced by recompute). Same heuristic as reference :420-447.
        if preemption_mode is None:
            if seq_group.get_max_num_running_seqs() == 1:
                preemption_mode = PreemptionMode.RECOMPUTE
            else:
                preemption_mode = PreemptionMode.SWAP
        self._flight.record(seq_group.request_id, "preempted",
                            detail=preemption_mode.name.lower())
        self._decisions.requeued(seq_group.request_id,
                                 preemption_mode.name.lower())
        if preemption_mode == PreemptionMode.RECOMPUTE:
            self._preempt_by_recompute(seq_group)
        else:
            self._preempt_by_swap(seq_group, blocks_to_swap_out)

    def _preempt_by_recompute(self, seq_group: SequenceGroup) -> None:
        seqs = seq_group.get_seqs(status=SequenceStatus.RUNNING)
        assert len(seqs) == 1
        for seq in seqs:
            # Recompute re-prefills from scratch, so the pages must really
            # free NOW — a deferred free would leave the re-prefill
            # double-allocated. The engine only runs a full (preempting)
            # scheduling pass with the pipeline drained, so no guard can
            # be active here.
            assert self._free_guard.get(seq.seq_id, 0) == 0, (
                "preempt-by-recompute hit a pipeline-guarded sequence")
            seq.status = SequenceStatus.WAITING
            # All KV pages are discarded — chunked-prefill progress resets
            # with them (re-prefill covers prompt + generated tail).
            seq.data.reset_num_computed_tokens()
            self.block_manager.free(seq)
        # Highest-priority among waiting: front of the queue.
        self.waiting.appendleft(seq_group)

    def _preempt_by_swap(
        self,
        seq_group: SequenceGroup,
        blocks_to_swap_out: Dict[int, int],
    ) -> None:
        self._swap_out(seq_group, blocks_to_swap_out)
        self.swapped.append(seq_group)

    def _swap_in(
        self,
        seq_group: SequenceGroup,
        blocks_to_swap_in: Dict[int, int],
    ) -> None:
        mapping = self.block_manager.swap_in(seq_group)
        blocks_to_swap_in.update(mapping)
        self._flight.record(seq_group.request_id, "swapped_in",
                            detail=f"blocks={len(mapping)}")
        self._decisions.swap(seq_group.request_id, "in", len(mapping))
        for seq in seq_group.get_seqs(status=SequenceStatus.SWAPPED):
            seq.status = SequenceStatus.RUNNING

    def _swap_out(
        self,
        seq_group: SequenceGroup,
        blocks_to_swap_out: Dict[int, int],
    ) -> None:
        if not self.block_manager.can_swap_out(seq_group):
            raise RuntimeError(
                "Aborted due to the lack of CPU swap space. Please increase "
                "the swap space to avoid this error.")
        mapping = self.block_manager.swap_out(seq_group)
        blocks_to_swap_out.update(mapping)
        self._flight.record(seq_group.request_id, "swapped_out",
                            detail=f"blocks={len(mapping)}")
        self._decisions.swap(seq_group.request_id, "out", len(mapping))
        for seq in seq_group.get_seqs(status=SequenceStatus.RUNNING):
            seq.status = SequenceStatus.SWAPPED
