"""The block manager's cache groups: a full group that grows with the
context and a window group that holds the window's blocks only, from one
manager; and the case of one windowed group (every layer has the window:
what the whole-model ring was)."""
import pytest

from intellillm_tpu.core.block_manager import AllocStatus, BlockSpaceManager
from intellillm_tpu.sampling_params import SamplingParams
from intellillm_tpu.sequence import Sequence, SequenceGroup, SequenceStatus

BS, WINDOW = 4, 16


def make_group(seq_id, prompt_len, best_of=1):
    seq = Sequence(seq_id, "x", list(range(prompt_len)), BS)
    sp = SamplingParams(temperature=1.0 if best_of > 1 else 0.0,
                        best_of=best_of, n=best_of)
    return SequenceGroup(f"req{seq_id}", [seq], sp, 0.0), seq


def two_groups(full=64, window=32, cpu=32, ahead=8):
    return BlockSpaceManager(block_size=BS, num_device_blocks=full,
                             num_cpu_blocks=cpu,
                             window_group=(WINDOW, window, cpu),
                             window_ahead=ahead)


def one_windowed_group(blocks=32, ahead=8):
    return BlockSpaceManager(block_size=BS, num_device_blocks=blocks,
                             num_cpu_blocks=blocks, sliding_window=WINDOW,
                             window_ahead=ahead)


def windowed(bm):
    return bm.window_group or bm.groups[0]


def live(bm, seq):
    return sum(b is not None for b in windowed(bm).tables[seq.seq_id])


def admit(bm, group, seq):
    assert bm.can_allocate(group) == AllocStatus.OK
    bm.allocate(group)
    for s in group.get_seqs():
        s.status = SequenceStatus.RUNNING


def decode(bm, seq, steps, k=1):
    for _ in range(steps):
        seq.append_token_id(1, {1: 0.0})
        bm.append_slots(seq, k)


MANAGERS = {"full+window": two_groups, "window only": one_windowed_group}


@pytest.mark.parametrize("make", sorted(MANAGERS))
@pytest.mark.parametrize("prompt_len,held", [
    (5, 2),                   # below the window: what the context needs
    (WINDOW, 4),              # at the window
    (100, 8),                 # beyond: the cap, (16 + 8) / 4 + 2
])
def test_window_blocks_held_at_admission(make, prompt_len, held):
    bm = MANAGERS[make]()
    group, seq = make_group(0, prompt_len)
    admit(bm, group, seq)
    assert live(bm, seq) == held
    if bm.window_group is not None:
        # The full group holds the whole prompt.
        assert len(bm.get_block_table(seq)) == -(-prompt_len // BS)


@pytest.mark.parametrize("make", sorted(MANAGERS))
def test_prefill_chunks_move_the_window_on(make):
    """A 100-token prompt in chunks of 8: the windowed group never holds
    more than its cap, gives back what fell out of the window, and ends
    with the blocks of positions (99 - 16, 99]."""
    bm = MANAGERS[make]()
    group, seq = make_group(0, 100)
    admit(bm, group, seq)
    g = windowed(bm)
    free0 = g.device.get_num_free_blocks()
    for start in range(0, 100, 8):
        bm.advance_for_chunk(seq, start, min(8, 100 - start))
        assert live(bm, seq) <= g.cap
        table = g.tables[seq.seq_id]
        first = max(start - WINDOW + 1, 0) // BS
        assert all(b is None for b in table[:first])
        assert all(b is not None
                   for b in table[first:-(-(start + 8) // BS)])
    assert g.recycled == (96 - WINDOW + 1) // BS
    assert bm.window_blocks_recycled == g.recycled
    # A stale entry reads as block 0; the kernels never walk it.
    numbers = (bm.get_window_block_table(seq) if bm.window_group
               else bm.get_block_table(seq))
    assert numbers[:g.recycled] == [0] * g.recycled
    assert len(numbers) == 25
    bm.free(seq)
    assert g.device.get_num_free_blocks() == free0 + 8
    assert not g.tables


@pytest.mark.parametrize("make", sorted(MANAGERS))
@pytest.mark.parametrize("k", [1, 8])
def test_decode_wraps_the_window_group(make, k):
    """Decoding from below the window to far beyond it, K slots ahead a
    step: the blocks held stay at the window's worth, every block that
    fell out came back to the free list."""
    bm = MANAGERS[make]()
    group, seq = make_group(0, 6)
    admit(bm, group, seq)
    g = windowed(bm)
    total = g.device.get_num_free_blocks() + live(bm, seq)
    seen = []
    for _ in range(120 // k):
        for _ in range(k):
            seq.append_token_id(1, {1: 0.0})
        bm.append_slots(seq, k)
        seen.append(live(bm, seq))
        assert g.device.get_num_free_blocks() + seen[-1] == total
    # window / block + what K writes ahead + the two partial blocks
    assert max(seen) <= WINDOW // BS + (k - 1) // BS + 2
    assert seen[-1] >= WINDOW // BS
    assert g.recycled == (seq.get_len() - 1 - WINDOW + 1) // BS
    if bm.window_group is not None:
        assert len(bm.get_block_table(seq)) == -(-(seq.get_len() + k - 1)
                                                 // BS)


def test_admission_waits_for_either_group():
    """Two groups, one verdict: LATER while either lacks the blocks, NEVER
    if either could never hold the prompt."""
    bm = two_groups(full=64, window=10)
    g1, s1 = make_group(0, 100)
    admit(bm, g1, s1)                              # 8 of 10 window blocks
    g2, _ = make_group(1, 100)
    assert bm.can_allocate(g2) == AllocStatus.LATER
    assert "window_free=2/10" in bm.kv_pressure_detail()
    bm.free(s1)
    assert bm.can_allocate(g2) == AllocStatus.OK
    assert two_groups(full=64, window=6).can_allocate(g2) == AllocStatus.NEVER
    assert two_groups(full=20, window=32).can_allocate(g2) == AllocStatus.NEVER


def test_can_append_and_grow_count_both_groups():
    bm = two_groups(full=64, window=10)
    group, seq = make_group(0, 30)
    admit(bm, group, seq)                          # 8 window blocks held
    other, oseq = make_group(1, 4)
    admit(bm, other, oseq)                         # 1 more: 1 free
    assert not bm.can_append_slots(group, 8)       # 1 + 2 blocks a seq
    # The continuation's check nets what the step gives back first:
    # from position 30 on, blocks below (30 - 16 + 1) // 4 = 3 are dead.
    assert bm.can_grow_all([(seq.seq_id, 38)], [30])
    assert not bm.can_grow_all([(seq.seq_id, 38)])
    bm.grow_to(seq.seq_id, 38, 30)
    assert live(bm, seq) == 10 - 3
    assert len(bm.get_block_table(seq)) == 10


@pytest.mark.parametrize("make", sorted(MANAGERS))
def test_swap_out_and_in_moves_live_blocks_of_every_group(make):
    bm = MANAGERS[make]()
    group, seq = make_group(0, 40)
    admit(bm, group, seq)
    decode(bm, seq, 30)
    held = live(bm, seq)
    g = windowed(bm)
    free = [x.device.get_num_free_blocks() for x in bm.groups]
    assert bm.can_swap_out(group)
    mapping = bm.swap_out(group)
    seq.status = SequenceStatus.SWAPPED
    moves = [op for op in bm.state_ops if op[0] == "w_swap_out"]
    if bm.window_group is not None:
        assert len(mapping) == 18 and len(moves) == held
    else:
        assert len(mapping) == held and not moves
    assert g.device.get_num_free_blocks() == g.num_device_blocks
    assert g.cpu.get_num_free_blocks() == 32 - held
    bm.state_ops.clear()
    assert bm.can_swap_in(group)
    back = bm.swap_in(group)
    seq.status = SequenceStatus.RUNNING
    assert len(back) == len(mapping)
    assert len([op for op in bm.state_ops if op[0] == "w_swap_in"]) == len(
        moves)
    assert [x.device.get_num_free_blocks() for x in bm.groups] == free
    assert live(bm, seq) == held
    # Stale entries stay stale through the swap.
    table = g.tables[seq.seq_id]
    assert table[0] is None and table[-1] is not None


@pytest.mark.parametrize("make", sorted(MANAGERS))
def test_fork_shares_and_copies_on_write_in_every_group(make):
    bm = MANAGERS[make]()
    group, seq = make_group(0, 6, best_of=2)       # a partial last block
    admit(bm, group, seq)
    child = seq.fork(1)
    group.add(child)
    bm.fork(seq, child)
    for g in bm.groups:
        assert all(b.ref_count == 2 for b in g.tables[seq.seq_id])
    seq.append_token_id(5, {5: 0.0})
    cows = bm.append_slots(seq, 1)
    assert len(cows) == 1
    copies = [op for op in bm.state_ops if op[0] == "w_copy"]
    assert len(copies) == (1 if bm.window_group is not None else 0)
    for g in bm.groups:
        assert g.tables[seq.seq_id][-1].ref_count == 1
        assert g.tables[child.seq_id][-1].ref_count == 1
    # A block two sequences share goes back when both have let go of it.
    g = windowed(bm)
    free = g.device.get_num_free_blocks()
    decode(bm, seq, 40)
    decode(bm, child, 40)
    assert g.recycled >= 2 * ((46 - WINDOW) // BS) - 1
    bm.free(seq)
    bm.free(child)
    assert g.device.get_num_free_blocks() == free + 3
    assert all(x.device.get_num_free_blocks() == x.num_device_blocks
               for x in bm.groups)


def test_recompute_preemption_frees_both_groups_and_readmits():
    bm = two_groups()
    group, seq = make_group(0, 40)
    admit(bm, group, seq)
    decode(bm, seq, 20)
    bm.free(seq)                                   # preempt by recompute
    assert all(g.device.get_num_free_blocks() == g.num_device_blocks
               for g in bm.groups)
    seq.status = SequenceStatus.WAITING
    admit(bm, group, seq)                          # prompt + generated tail
    assert live(bm, seq) == 8
    assert len(bm.get_block_table(seq)) == 15
    bm.reset()
    assert not bm.block_tables and not bm.window_group.tables


def test_no_window_no_second_group():
    """Every other model: one group, nothing recorded, tables as ever."""
    bm = BlockSpaceManager(block_size=BS, num_device_blocks=16,
                           num_cpu_blocks=4)
    group, seq = make_group(0, 40)
    admit(bm, group, seq)
    bm.advance_for_chunk(seq, 32, 8)
    decode(bm, seq, 20)
    assert bm.window_group is None and bm.get_window_block_table(seq) is None
    assert bm.window_blocks_recycled == 0 and not bm.state_ops
    assert all(b is not None for b in bm.block_tables[seq.seq_id])
    assert len(bm.get_block_table(seq)) == 15


@pytest.mark.parametrize("make", sorted(MANAGERS))
@pytest.mark.parametrize("sizes", [(1, 8, 2, 8, 8, 3, 8), (8, 1, 8, 1, 8)])
def test_uneven_chunks_never_find_the_pool_empty(make, sizes):
    """Chunks of any sizes (a short one, then a long one): the windowed
    group keeps what admission reserved of the prompt (its cap, counted
    from the first block it still sees) while a neighbour has taken every
    other block, so no chunk needs a block the pool does not have."""
    bm = MANAGERS[make]()
    group, seq = make_group(0, 120)
    admit(bm, group, seq)
    g = windowed(bm)
    assert live(bm, seq) == g.cap
    hog = [g.device.allocate() for _ in range(g.device.get_num_free_blocks())]
    start = 0
    while start < 120:
        for size in sizes:
            size = min(size, 120 - start)
            if not size:
                break
            bm.advance_for_chunk(seq, start, size)     # raises if it must take
            table = g.tables[seq.seq_id]
            assert all(b is not None for b in table[
                g.first_live_block(start):-(-(start + size) // BS)])
            assert live(bm, seq) <= g.cap
            start += size
    # At the prompt's end what lies past it was never taken.
    assert len(g.tables[seq.seq_id]) == 120 // BS
    for block in hog:
        g.device.free(block)
    bm.free(seq)
    assert g.device.get_num_free_blocks() == g.num_device_blocks
