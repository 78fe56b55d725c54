"""Per-sequence state slots in the block manager and the scheduler: a slot
is taken with the block table, moved with a swap, copied at a fork, given
back when the sequence ends, and an admission that finds blocks but no slot
waits. Synthetic sequences, no device."""
import pytest

from intellillm_tpu.config import CacheConfig, SchedulerConfig
from intellillm_tpu.core.block_manager import AllocStatus, BlockSpaceManager
from intellillm_tpu.core.scheduler import Scheduler
from intellillm_tpu.obs import get_efficiency_tracker
from intellillm_tpu.sampling_params import SamplingParams
from intellillm_tpu.sequence import Sequence, SequenceGroup, SequenceStatus


def make_group(seq_id, prompt_len, block_size=4, best_of=1):
    seq = Sequence(seq_id, "x", list(range(prompt_len)), block_size)
    sp = SamplingParams(temperature=1.0 if best_of > 1 else 0.0,
                        best_of=best_of, n=best_of)
    return SequenceGroup(f"req{seq_id}", [seq], sp, 0.0), seq


def manager(slots, blocks=32):
    return BlockSpaceManager(block_size=4, num_device_blocks=blocks,
                             num_cpu_blocks=blocks, num_state_slots=slots)


def admit(bm, group):
    bm.allocate(group)
    for seq in group.get_seqs():
        seq.status = SequenceStatus.RUNNING


def drain(bm):
    """What the worker does with the list before a device step."""
    ops = list(bm.state_ops)
    bm.state_ops.clear()
    return ops


def events():
    m = get_efficiency_tracker()._metrics
    return {e: m.counter_state_slot_events.labels(e)._value.get()
            for e in ("alloc", "free", "swap_out", "swap_in", "fork")}


def test_a_model_without_state_has_no_slots():
    bm = manager(0)
    group, seq = make_group(0, 10)
    assert bm.can_allocate_state(group)
    admit(bm, group)
    assert bm.get_state_slot(seq.seq_id) is None
    assert not bm.state_slots and not bm.state_ops
    bm.free(seq)


def test_slot_is_taken_with_the_table_and_given_back():
    before = events()
    bm = manager(2)
    (g0, s0), (g1, s1), (g2, _) = (make_group(i, 6) for i in range(3))
    admit(bm, g0)
    admit(bm, g1)
    assert {bm.get_state_slot(s0.seq_id),
            bm.get_state_slot(s1.seq_id)} == {0, 1}
    # blocks are plenty, slots are not: the third waits
    assert bm.can_allocate(g2) == AllocStatus.OK
    assert not bm.can_allocate_state(g2)
    bm.free(s0)
    assert bm.can_allocate_state(g2)
    bm.free(s0)                                   # a second free is nothing
    assert len(bm._free_state_slots) == 1
    admit(bm, g2)
    after = events()
    assert after["alloc"] - before["alloc"] == 3
    assert after["free"] - before["free"] == 1
    gauge = get_efficiency_tracker()._metrics.gauge_state_slots
    assert gauge.labels("used")._value.get() == 2
    assert gauge.labels("free")._value.get() == 0
    bm.reset()
    assert len(bm._free_state_slots) == 2 and not bm.state_slots


def test_fork_copies_and_swap_moves_the_slot():
    before = events()
    bm = manager(4)
    group, parent = make_group(0, 6, best_of=2)
    admit(bm, group)
    child = parent.fork(1)
    group.add(child)
    bm.fork(parent, child)
    p, c = bm.get_state_slot(parent.seq_id), bm.get_state_slot(child.seq_id)
    assert p != c and drain(bm) == [("fork", p, c)]
    assert bm.can_swap_out(group)
    bm.swap_out(group)
    for seq in group.get_seqs():
        seq.status = SequenceStatus.SWAPPED
    ops = drain(bm)
    assert sorted(op[:2] for op in ops) == sorted(
        [("swap_out", p), ("swap_out", c)])
    assert not bm.state_slots and len(bm.cpu_state_slots) == 2
    assert len(bm._free_state_slots) == 4
    assert bm.can_swap_in(group)
    bm.swap_in(group)
    ops = drain(bm)
    assert [op[0] for op in ops] == ["swap_in", "swap_in"]
    assert len({src for _, src, _ in ops}) == 2
    assert len(bm.state_slots) == 2 and not bm.cpu_state_slots
    assert len(bm._free_cpu_state_slots) == 4
    after = events()
    for event, n in (("fork", 1), ("swap_out", 2), ("swap_in", 2)):
        assert after[event] - before[event] == n
    # the list object the engine handed to the worker stays the same
    ops_list = bm.state_ops
    bm.reset()
    assert bm.state_ops is ops_list and not ops_list


def test_swap_in_waits_for_a_slot():
    bm = manager(1)
    g0, s0 = make_group(0, 6)
    admit(bm, g0)
    bm.swap_out(g0)
    s0.status = SequenceStatus.SWAPPED
    g1, _ = make_group(1, 6)
    admit(bm, g1)                      # takes the one device slot
    assert not bm.can_swap_in(g0)
    # and a second swap-out finds no host slot (as many as device slots)
    assert not bm.can_swap_out(g1)


def test_fork_without_a_free_slot_says_why():
    bm = manager(1)
    group, parent = make_group(0, 6, best_of=2)
    admit(bm, group)
    with pytest.raises(RuntimeError, match="state slot"):
        bm.fork(parent, parent.fork(1))


def _scheduler(slots, seats=4):
    cache = CacheConfig(block_size=4, swap_space_gib=0.0001,
                        num_device_blocks_override=64)
    cache.num_device_blocks, cache.num_cpu_blocks = 64, 64
    cache.num_state_slots = slots
    return Scheduler(SchedulerConfig(max_num_batched_tokens=64,
                                     max_num_seqs=seats, max_model_len=64,
                                     enable_chunked_prefill=True), cache,
                     None)


def test_admission_waits_for_a_slot_and_says_so():
    sched = _scheduler(slots=1)
    (g0, s0), (g1, s1) = make_group(0, 6), make_group(1, 6)
    sched.add_seq_group(g0)
    sched.add_seq_group(g1)
    metas, out = sched.schedule(prefill_only=True)
    assert [m.request_id for m in metas] == ["req0"]
    assert out.blocked == "state_slots"
    assert metas[0].state_slots == {s0.seq_id: 0}
    assert len(sched.waiting) == 1
    # the running sequence ends: its slot goes back at once, even while a
    # step in flight still guards its blocks
    sched.guard_seqs([s0.seq_id])
    s0.status = SequenceStatus.FINISHED_STOPPED
    sched.free_seq(s0)
    sched.free_finished_seq_groups()
    assert s0.seq_id in sched.block_manager.block_tables    # guarded
    assert not sched.block_manager.state_slots              # not the slot
    metas, out = sched.schedule(prefill_only=True)
    assert [m.request_id for m in metas] == ["req1"]
    assert metas[0].state_slots == {s1.seq_id: 0}
    sched.unguard_seqs([s0.seq_id])
    assert s0.seq_id not in sched.block_manager.block_tables


def test_abort_gives_the_slot_back():
    sched = _scheduler(slots=2)
    g0, s0 = make_group(0, 6)
    sched.add_seq_group(g0)
    sched.schedule(prefill_only=True)
    assert len(sched.block_manager.state_slots) == 1
    sched.abort_seq_group("req0")
    assert not sched.block_manager.state_slots
    assert len(sched.block_manager._free_state_slots) == 2
