"""Scheduler unit tests: admission budgets, preemption, policy ordering."""
import pytest

from intellillm_tpu.config import CacheConfig, SchedulerConfig
from intellillm_tpu.core.policy import PolicyFactory
from intellillm_tpu.core.scheduler import Scheduler
from intellillm_tpu.sampling_params import SamplingParams
from intellillm_tpu.sequence import Sequence, SequenceGroup, SequenceStatus


def make_scheduler(num_blocks=16, block_size=4, max_num_seqs=8,
                   policy="fcfs", num_decode_steps=1, max_model_len=64,
                   **config_kwargs):
    cache_config = CacheConfig(block_size=block_size, swap_space_gib=0.001)
    cache_config.num_device_blocks = num_blocks
    cache_config.num_cpu_blocks = 8
    scheduler_config = SchedulerConfig(
        max_num_batched_tokens=max(64, max_model_len),
        max_num_seqs=max_num_seqs,
        max_model_len=max_model_len,
        max_paddings=256,
        policy=policy,
        num_decode_steps=num_decode_steps,
        **config_kwargs)
    return Scheduler(scheduler_config, cache_config)


def add_request(scheduler, rid, prompt_len, block_size=4,
                predicted_len=None, **sp_kwargs):
    seq = Sequence(int(rid), "x", list(range(prompt_len)), block_size)
    sp = SamplingParams(**sp_kwargs) if sp_kwargs else SamplingParams(
        temperature=0.0, max_tokens=16)
    group = SequenceGroup(rid, [seq], sp, arrival_time=float(rid),
                          predicted_len=predicted_len)
    scheduler.add_seq_group(group)
    return group, seq


def append_token(group):
    for seq in group.get_seqs(SequenceStatus.RUNNING):
        seq.append_token_id(1, {1: 0.0})


def test_prefill_first_then_decode():
    s = make_scheduler()
    g1, _ = add_request(s, "0", 6)
    g2, _ = add_request(s, "1", 5)
    metas, out = s.schedule()
    assert out.prompt_run and len(metas) == 2
    append_token(g1)
    append_token(g2)
    metas, out = s.schedule()
    assert not out.prompt_run
    assert len(metas) == 2


def test_prompt_too_long_is_ignored():
    s = make_scheduler(max_model_len=8)
    g, seq = add_request(s, "0", 100)
    metas, out = s.schedule()
    assert not metas
    assert out.ignored_seq_groups == [g]
    assert seq.status == SequenceStatus.FINISHED_IGNORED


def test_admission_respects_max_num_seqs():
    s = make_scheduler(max_num_seqs=2, num_blocks=64)
    for i in range(4):
        add_request(s, str(i), 4)
    metas, out = s.schedule()
    assert len(metas) == 2
    assert len(s.waiting) == 2


def test_preemption_by_recompute_when_out_of_blocks():
    # 4 blocks of 4 tokens; two seqs with 8-token prompts fill everything.
    s = make_scheduler(num_blocks=4, block_size=4)
    g1, _ = add_request(s, "0", 8)
    g2, _ = add_request(s, "1", 8)
    metas, out = s.schedule()
    assert len(metas) == 2
    append_token(g1)
    append_token(g2)
    # Decode needs a new block per seq; none free → lowest-priority (g2,
    # arrived later) preempted by recompute back to waiting.
    metas, out = s.schedule()
    assert not out.prompt_run
    assert len(metas) == 1
    assert metas[0].request_id == "0"
    assert g2.get_seqs()[0].status == SequenceStatus.WAITING
    assert len(s.waiting) == 1


def test_sjf_policy_orders_waiting_by_predicted_len():
    s = make_scheduler(policy="sjf", max_num_seqs=1, num_blocks=64)
    add_request(s, "0", 4, predicted_len=500)
    g_short, _ = add_request(s, "1", 4, predicted_len=5)
    metas, out = s.schedule()
    assert [m.request_id for m in metas] == ["1"], (
        "SJF must admit the shortest predicted job first")


def test_fcfs_policy_priority():
    fcfs = PolicyFactory.get_policy("fcfs")
    g_old = SequenceGroup("a", [Sequence(0, "x", [1], 4)],
                          SamplingParams(), arrival_time=0.0)
    g_new = SequenceGroup("b", [Sequence(1, "x", [1], 4)],
                          SamplingParams(), arrival_time=10.0)
    order = fcfs.sort_by_priority(100.0, [g_new, g_old])
    assert [g.request_id for g in order] == ["a", "b"]


def test_multi_step_reserves_blocks():
    s = make_scheduler(num_blocks=16, block_size=4, num_decode_steps=8)
    g, seq = add_request(s, "0", 4)
    s.schedule()
    append_token(g)
    metas, out = s.schedule()
    assert out.num_decode_steps == 8
    # 4 prompt tokens + 1 output + 7 lookahead = 12 tokens → 3 blocks.
    assert len(s.block_manager.block_tables[seq.seq_id]) == 3


def test_beam_group_forces_single_step():
    s = make_scheduler(num_blocks=32, block_size=4, num_decode_steps=8)
    g, seq = add_request(s, "0", 4, use_beam_search=True, best_of=2,
                         temperature=0.0, max_tokens=8)
    s.schedule()
    append_token(g)
    metas, out = s.schedule()
    assert out.num_decode_steps == 1


def test_abort():
    s = make_scheduler()
    g, seq = add_request(s, "0", 4)
    s.schedule()
    s.abort_seq_group("0")
    assert not s.has_unfinished_seqs()
    assert seq.status == SequenceStatus.FINISHED_ABORTED
    assert s.block_manager.get_num_free_device_blocks() == 16


# --- length-predicted scheduling: calibration, aging, victim choice ----


def _group(rid, arrival, predicted_len):
    return SequenceGroup(rid, [Sequence(hash(rid) % 1000, "x", [1], 4)],
                         SamplingParams(), arrival_time=arrival,
                         predicted_len=predicted_len)


def test_sjf_remaining_unknown_lengths_sort_last_fcfs():
    """Unknown-length groups sort behind any predicted job and FCFS
    among themselves — the age term is a tiebreak, never dominant."""
    policy = PolicyFactory.get_policy("sjf_remaining")
    known = _group("k", arrival=90.0, predicted_len=10**6)
    unk_old = _group("a", arrival=0.0, predicted_len=None)
    unk_new = _group("b", arrival=80.0, predicted_len=None)
    order = policy.sort_by_priority(100.0, [unk_new, known, unk_old])
    assert [g.request_id for g in order] == ["k", "a", "b"]


def test_starvation_promotion_is_fcfs_above_sjf():
    policy = PolicyFactory.get_policy("sjf", starvation_s=5.0)
    long_oldest = _group("a", arrival=0.0, predicted_len=1000)
    long_older = _group("b", arrival=50.0, predicted_len=1000)
    short_fresh = _group("c", arrival=98.0, predicted_len=1)
    order = policy.sort_by_priority(
        100.0, [short_fresh, long_older, long_oldest])
    # Both long jobs waited past the deadline: promoted above the fresh
    # short job, ordered FCFS between themselves.
    assert [g.request_id for g in order] == ["a", "b", "c"]
    # Disabled (unset or 0) never promotes.
    for off in (PolicyFactory.get_policy("sjf"),
                PolicyFactory.get_policy("sjf", starvation_s=0)):
        assert off.starvation_s is None
        order = off.sort_by_priority(100.0, [long_oldest, short_fresh])
        assert [g.request_id for g in order] == ["c", "a"]


def test_starvation_deadline_bounds_queue_wait_in_scheduler():
    """An old long job must be admitted ahead of a stream of fresh
    short jobs once its wait exceeds --sjf-starvation-s."""
    import time
    s = make_scheduler(policy="sjf", max_num_seqs=1, num_blocks=64,
                       sjf_starvation_s=5.0)
    now = time.monotonic()
    g_long, _ = add_request(s, "0", 4, predicted_len=1000)
    g_long.arrival_time = now - 10.0  # waited past the deadline
    for rid in ("1", "2"):
        g, _ = add_request(s, rid, 4, predicted_len=1)
        g.arrival_time = now
    metas, _ = s.schedule()
    assert [m.request_id for m in metas] == ["0"], (
        "aged-out long job must be promoted over fresh short jobs")


def test_calibration_refresh_reorders_sjf_queue():
    """Golden ordering: a calibration update restamps a service-stamped
    in-flight prediction and flips the SJF admission order."""
    from intellillm_tpu.prediction import OnlineCalibrator

    s = make_scheduler(policy="sjf", max_num_seqs=1, num_blocks=64)
    g_stamped, _ = add_request(s, "0", 40, predicted_len=100)
    g_stamped.predicted_len_raw = 100         # stamped by the service
    g_oracle, _ = add_request(s, "1", 8, predicted_len=50)  # oracle len

    cal = OnlineCalibrator()
    cal.note_admission("warm", 40, 100)
    cal.observe("warm", 10)  # bucket 32-63 factor → 0.1, marked dirty
    assert cal.refresh_predictions(s.iter_seq_groups()) == 1
    assert g_stamped.predicted_len == 10
    assert g_oracle.predicted_len == 50  # oracle-supplied: never touched

    metas, _ = s.schedule()
    assert [m.request_id for m in metas] == ["0"], (
        "restamped prediction (10 < 50) must now win SJF admission")


def test_preemption_victim_is_most_predicted_remaining():
    """Under memory pressure the victim is the running group with the
    most predicted remaining work, not the priority-order tail."""
    # 7 blocks: three 8-token prompts use 6, and the conservative
    # can_append_slots check (2 free per appending seq) forces exactly
    # one preemption on the first decode step.
    s = make_scheduler(num_blocks=7, block_size=4)
    g1, _ = add_request(s, "0", 8, predicted_len=10)
    g2, _ = add_request(s, "1", 8, predicted_len=500)
    g3, _ = add_request(s, "2", 8, predicted_len=10)
    metas, _ = s.schedule()
    assert len(metas) == 3
    for g in (g1, g2, g3):
        append_token(g)
    metas, out = s.schedule()
    assert not out.prompt_run
    # Old behavior evicted the tail (g3); now the 500-token prediction
    # is evicted, freeing the most future block demand.
    assert [m.request_id for m in metas] == ["0", "2"]
    assert g2.get_seqs()[0].status == SequenceStatus.WAITING


def test_preemption_victim_prices_with_p90_when_available():
    s = make_scheduler(num_blocks=7, block_size=4)
    g1, _ = add_request(s, "0", 8, predicted_len=10)
    g2, _ = add_request(s, "1", 8, predicted_len=500)
    g3, _ = add_request(s, "2", 8, predicted_len=10)
    g3.predicted_len_p90 = 800  # calibrated tail dwarfs g2's p50
    s.schedule()
    for g in (g1, g2, g3):
        append_token(g)
    metas, _ = s.schedule()
    assert [m.request_id for m in metas] == ["0", "1"]
    assert g3.get_seqs()[0].status == SequenceStatus.WAITING


@pytest.mark.parametrize("case, expected", [
    ("seats", "max_seqs"),
    ("pool", "kv_watermark"),
    ("long_prompt", "needs_chunking"),
    ("long_prompt_beside_decode", None),
    ("admits", None),
])
def test_prefill_only_pass_says_why_it_stopped(case, expected):
    """A prefill-only pass (the pipelined loop's admission) reports why it
    stopped admitting in `SchedulerOutputs.blocked`: the loop drains for a
    full pool or a prompt that needs chunking, not for full seats; asked
    to chunk (the engine does while a decode step is in flight), the pass
    admits a prompt longer than the budget with its first chunk and goes
    on chunk by chunk."""
    cache_config = CacheConfig(block_size=4, swap_space_gib=0.001)
    cache_config.num_device_blocks = 7 if case == "pool" else 64
    cache_config.num_cpu_blocks = 8
    s = Scheduler(SchedulerConfig(
        max_num_batched_tokens=16, max_model_len=64, max_paddings=256,
        max_num_seqs=1 if case == "seats" else 8,
        enable_chunked_prefill=True), cache_config)
    first, _ = add_request(s, "0", 16 if case == "pool" else 6)
    metas, out = s.schedule(prefill_only=True)
    assert [m.request_id for m in metas] == ["0"] and out.blocked is None
    append_token(first)
    beside = case == "long_prompt_beside_decode"
    add_request(s, "1", 24 if case.startswith("long_prompt") else
                {"pool": 16}.get(case, 6))
    metas, out = s.schedule(prefill_only=True, chunk_long_prompts=beside)
    assert out.blocked == expected
    assert bool(metas) == (expected is None)
    if beside:
        assert out.prompt_run and out.chunked_prefills == {"1": (0, 16, False)}
        assert s.mid_prefill()
        metas, out = s.schedule(prefill_only=True, chunk_long_prompts=True)
        # the rest of it, alone: no decode row rides a prefill-only pass
        assert [m.request_id for m in metas] == ["1"]
        assert out.chunked_prefills == {"1": (16, 8, True)}
        assert not s.mid_prefill()
