"""Paged KV-cache block bookkeeping (host side).

Role parity: reference `vllm/core/block_manager.py` (BlockAllocator :10,
AllocStatus :54, BlockSpaceManager :68): logical→physical block maps,
refcounted free lists per device, copy-on-write forking, host↔HBM swap
planning, sliding-window groups, allocation watermark. The physical
block numbers index the HBM pool arrays held by the worker's CacheEngine;
this module never touches device memory itself — it emits block-op plans
(swap-in / swap-out / copy dicts) that the worker executes.

Cache groups (`ModelConfig.get_layer_cache_kinds`): a sequence holds one
block table a group. The FULL group ("kv" layers) grows with the context.
A WINDOW group ("kv_window" layers, a sliding window of `window` tokens)
has a table indexed by logical block like the full one, but holds only
the blocks a coming step can still see: before a step whose first query
stands at position p, every block wholly below p - window + 1 is given
back to the group's free list (`recycled` counts them) and its table
entry goes stale (reported as block 0; the attention kernels never walk
below the window's first page). So a sequence holds at most
window / block_size + (a chunk or a K-step ahead) / block_size + 2 window
blocks however long it grows, and a step in flight that still reads a
block given back reads it before its next owner's first write: the
device runs its programs in order. The two groups have pools (and block
numbers) of their own: the primary group's swap and copy plans are
returned to the scheduler as ever, the second group's are recorded in
`state_ops` ("w_swap_out" | "w_swap_in" | "w_copy", source block,
destination block) for the worker to run before the next device step. A
model whose every layer has the window (`sliding_window`: the old
whole-model ring's case) has the window group as its only group.

Per-sequence state slots (models with "state" layers,
`ModelConfig.get_layer_cache_kinds`): beside its block table a sequence
holds ONE slot of the worker's state pools, taken with the table at
admission (an admission that finds blocks but no slot waits), moved to a
host slot and back with a swap, copied at a fork, given back when the
sequence is freed. A slot is given back at once even while the blocks'
release waits for steps in flight (`release_state_slot`): the device runs
its programs in order, so whatever a step in flight still writes there
lands before the next owner's first step, and that step starts at
position 0, which reads no state. The moves are recorded in
`state_ops` for the worker to run before the next device step. With
`num_state_slots` 0 (every other model) none of this does anything.

Honesty note: the refcounted free-list / CoW / swap bookkeeping is a
deliberate close port of the reference's host-side block manager (pure
bookkeeping, SURVEY §7.4). Additions that have no reference analogue:
multi-slot (K-step) reservation for fused decode, and target-length
growth (`grow_to`) for pipelined continuations whose host lengths trail
the device.
"""
from __future__ import annotations

import enum
from typing import Dict, List, Optional, Set, Tuple

from intellillm_tpu.block import BlockTable, PhysicalTokenBlock
from intellillm_tpu.sequence import Sequence, SequenceGroup, SequenceStatus
from intellillm_tpu.utils import Device


class BlockAllocator:
    """Free-list allocator over a fixed pool of refcounted blocks."""

    def __init__(self, device: Device, block_size: int, num_blocks: int) -> None:
        self.device = device
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.free_blocks: List[PhysicalTokenBlock] = [
            PhysicalTokenBlock(device, i, block_size)
            for i in range(num_blocks)
        ]

    def allocate(self) -> PhysicalTokenBlock:
        if not self.free_blocks:
            raise ValueError("Out of memory! No free blocks are available.")
        block = self.free_blocks.pop()
        block.ref_count = 1
        return block

    def free(self, block: PhysicalTokenBlock) -> None:
        if block.ref_count == 0:
            raise ValueError(f"Double free! {block} is already freed.")
        block.ref_count -= 1
        if block.ref_count == 0:
            self.free_blocks.append(block)

    def get_num_free_blocks(self) -> int:
        return len(self.free_blocks)


class AllocStatus(enum.Enum):
    """Admission verdict for a waiting group (reference block_manager.py:54)."""
    OK = enum.auto()        # fits now
    LATER = enum.auto()     # could fit once memory frees up
    NEVER = enum.auto()     # can never fit; reject the request


class _Group:
    """One cache group's pools and tables: device and host allocators, a
    table a sequence (entries by logical block; None where a windowed
    group gave the block back)."""

    def __init__(self, block_size: int, num_device_blocks: int,
                 num_cpu_blocks: int, watermark: float,
                 window: Optional[int] = None, ahead: int = 0) -> None:
        self.block_size = block_size
        self.num_device_blocks = num_device_blocks
        self.watermark_blocks = int(watermark * num_device_blocks)
        self.device = BlockAllocator(Device.DEVICE, block_size,
                                     num_device_blocks)
        self.cpu = BlockAllocator(Device.CPU, block_size, num_cpu_blocks)
        self.tables: Dict[int, List[Optional[PhysicalTokenBlock]]] = {}
        self.window = window
        # The most blocks a sequence holds: the window, what a step
        # writes ahead of its first query, and the two partly used blocks
        # at the ends.
        self.cap: Optional[int] = None
        if window is not None:
            self.cap = -(-(window + ahead) // block_size) + 2
        # Blocks given back because they fell out of the window.
        self.recycled = 0

    def blocks_for(self, num_tokens: int) -> int:
        n = -(-num_tokens // self.block_size)
        return n if self.cap is None else min(n, self.cap)

    def first_live_block(self, first_pos: int) -> int:
        """Blocks below it hold nothing a query at `first_pos` or later
        sees."""
        if self.window is None:
            return 0
        return max(first_pos - self.window + 1, 0) // self.block_size

    def shortfall(self, seq_id: int, first_pos: int, num_tokens: int) -> int:
        """Blocks `advance` would take from the free list, net of those it
        gives back first."""
        table = self.tables[seq_id]
        need = -(-num_tokens // self.block_size) - len(table)
        dead = sum(b is not None and b.ref_count == 1
                   for b in table[:self.first_live_block(first_pos)])
        return max(need, 0) - dead

    def advance(self, seq_id: int, first_pos: int, num_tokens: int) -> int:
        """Give back what a step from `first_pos` on no longer sees, then
        cover `num_tokens` tokens. Returns the blocks given back."""
        table = self.tables[seq_id]
        gone = 0
        for i in range(min(self.first_live_block(first_pos), len(table))):
            if table[i] is not None:
                self.device.free(table[i])
                table[i] = None
                gone += 1
        self.recycled += gone
        while len(table) * self.block_size < num_tokens:
            table.append(self.device.allocate())
        return gone

    def live(self, seq_ids) -> List[PhysicalTokenBlock]:
        blocks: Set[PhysicalTokenBlock] = set()
        for sid in seq_ids:
            blocks.update(b for b in self.tables[sid] if b is not None)
        return list(blocks)

    def move(self, seq_ids, src: BlockAllocator,
             dst: BlockAllocator) -> Dict[int, int]:
        """Swap plan of the sequences' blocks from `src`'s pool to
        `dst`'s: {source block number: destination block number}."""
        mapping: Dict[PhysicalTokenBlock, PhysicalTokenBlock] = {}
        for sid in seq_ids:
            new_table: List[Optional[PhysicalTokenBlock]] = []
            for block in self.tables[sid]:
                if block is None:
                    new_table.append(None)
                    continue
                if block in mapping:
                    other = mapping[block]
                    other.ref_count += 1
                else:
                    other = dst.allocate()
                    mapping[block] = other
                new_table.append(other)
                src.free(block)
            self.tables[sid] = new_table
        return {a.block_number: b.block_number for a, b in mapping.items()}

    def free_table(self, seq_id: int) -> None:
        table = self.tables.pop(seq_id, None)
        for block in set(table or ()):
            if block is None:
                continue
            (self.device if block.device == Device.DEVICE
             else self.cpu).free(block)

    def table_numbers(self, seq_id: int) -> List[int]:
        return [0 if b is None else b.block_number
                for b in self.tables[seq_id]]


class BlockSpaceManager:
    """Maps sequences' logical blocks onto the physical HBM/CPU pools."""

    def __init__(
        self,
        block_size: int,
        num_device_blocks: int,
        num_cpu_blocks: int,
        watermark: float = 0.01,
        sliding_window: Optional[int] = None,
        num_state_slots: int = 0,
        window_group: Optional[Tuple[int, int, int]] = None,
        window_ahead: int = 0,
    ) -> None:
        """`sliding_window`: every layer has it (the primary group is
        windowed). `window_group` (window tokens, device blocks, host
        blocks): some layers have one, beside full ones: a second group.
        `window_ahead`: the most tokens a step writes past its first
        query (a prefill chunk, a K-step decode)."""
        self.block_size = block_size
        self.num_total_device_blocks = num_device_blocks
        self.num_total_cpu_blocks = num_cpu_blocks
        assert watermark >= 0.0
        self.watermark = watermark

        primary = _Group(block_size, num_device_blocks, num_cpu_blocks,
                         watermark, sliding_window, window_ahead)
        self.groups: List[_Group] = [primary]
        self.window_group: Optional[_Group] = None
        if window_group is not None:
            assert sliding_window is None, "one window a model"
            window, win_device, win_cpu = window_group
            self.window_group = _Group(block_size, win_device, win_cpu,
                                       watermark, window, window_ahead)
            self.groups.append(self.window_group)
        self.watermark_blocks = primary.watermark_blocks
        self.device_allocator = primary.device
        self.cpu_allocator = primary.cpu
        # seq_id -> physical block table of the primary group
        self.block_tables = primary.tables

        # seq_id -> state slot, on the device (running) or on the host
        # (swapped); as many host slots as device slots.
        self.num_state_slots = num_state_slots
        self.state_slots: Dict[int, int] = {}
        self.cpu_state_slots: Dict[int, int] = {}
        self._free_state_slots = list(range(num_state_slots))[::-1]
        self._free_cpu_state_slots = list(range(num_state_slots))[::-1]
        # Moves beside the primary group's plans, in the order made:
        # ("swap_out" | "swap_in" | "fork", source slot, destination slot)
        # of state slots, ("w_swap_out" | "w_swap_in" | "w_copy", source
        # block, destination block) of the second group's blocks. The
        # engine hands the worker this very list (never rebound, only
        # appended to and cleared): the worker runs and clears it before
        # its next device step.
        self.state_ops: List[Tuple[str, int, int]] = []
        from intellillm_tpu.obs import get_efficiency_tracker
        self._efficiency = get_efficiency_tracker()
        self._report_state_slots()

    # --- windowed groups ---------------------------------------------------

    def _windowed(self) -> Optional[_Group]:
        for g in self.groups:
            if g.window is not None:
                return g
        return None

    @property
    def window_blocks_recycled(self) -> int:
        g = self._windowed()
        return g.recycled if g else 0

    def _advance_group(self, g: _Group, seq_id: int, first_pos: int,
                       num_tokens: int) -> None:
        gone = g.advance(seq_id, first_pos, num_tokens)
        if gone:
            self._efficiency.record_window_blocks("recycled", gone)

    def _advance(self, seq_id: int, first_pos: int, num_tokens: int) -> None:
        for g in self.groups:
            self._advance_group(g, seq_id, first_pos, num_tokens)

    def advance_for_chunk(self, seq: Sequence, start: int, size: int) -> None:
        """Before a prefill chunk of positions [start, start + size):
        a windowed group gives back what the chunk cannot see and covers
        what admission reserved of the sequence from there on (`cap`
        blocks from the first it still sees, or up to the sequence's end):
        it takes no more than it has just given back, so a chunk never
        finds the pool empty, whatever the chunks' sizes. (A group that
        keeps everything holds the whole prompt since admission.)"""
        g = self._windowed()
        if g is None:
            return
        reserved = (g.first_live_block(start) + g.cap) * self.block_size
        assert start + size <= reserved, (start, size, reserved)
        self._advance_group(g, seq.seq_id, start,
                            min(seq.get_len(), reserved))

    def get_window_block_table(self, seq: Sequence) -> Optional[List[int]]:
        """The second group's table (None: the model has one group)."""
        if self.window_group is None:
            return None
        return self.window_group.table_numbers(seq.seq_id)

    # --- per-sequence state slots ------------------------------------------

    def _report_state_slots(self, event: Optional[str] = None) -> None:
        if self.num_state_slots:
            self._efficiency.record_state_slots(
                self.num_state_slots - len(self._free_state_slots),
                len(self._free_state_slots), event)

    def can_allocate_state(self, seq_group: SequenceGroup) -> bool:
        """Whether every waiting sequence of the group finds a slot."""
        if not self.num_state_slots:
            return True
        return (seq_group.num_seqs(status=SequenceStatus.WAITING)
                <= len(self._free_state_slots))

    def _take_state_slot(self, seq_id: int, event: str) -> int:
        if not self._free_state_slots:
            raise RuntimeError(
                "no free state slot (one a running sequence: the "
                "scheduler's max_num_seqs bounds them)")
        slot = self._free_state_slots.pop()
        self.state_slots[seq_id] = slot
        self._report_state_slots(event)
        return slot

    def release_state_slot(self, seq: Sequence) -> None:
        """Give the sequence's slot back (device or host); nothing if it
        holds none."""
        slot = self.state_slots.pop(seq.seq_id, None)
        if slot is not None:
            self._free_state_slots.append(slot)
            self._report_state_slots("free")
        slot = self.cpu_state_slots.pop(seq.seq_id, None)
        if slot is not None:
            self._free_cpu_state_slots.append(slot)
            self._report_state_slots("free")

    def get_state_slot(self, seq_id: int) -> Optional[int]:
        """The device slot of a running sequence (None: the model keeps
        no state)."""
        if not self.num_state_slots:
            return None
        return self.state_slots[seq_id]

    # --- admission -------------------------------------------------------

    def can_allocate(self, seq_group: SequenceGroup) -> AllocStatus:
        # All WAITING seqs in a group share the prompt, hence one table.
        seq = seq_group.get_seqs(status=SequenceStatus.WAITING)[0]
        verdict = AllocStatus.OK
        for g in self.groups:
            num_required = g.blocks_for(seq.get_len())
            if (g is self.groups[0] and seq_group.prefix is not None
                    and seq_group.prefix.allocated):
                num_required -= seq_group.prefix.get_num_blocks()
            if g.num_device_blocks - num_required < g.watermark_blocks:
                return AllocStatus.NEVER
            if (g.device.get_num_free_blocks() - num_required
                    < g.watermark_blocks):
                verdict = AllocStatus.LATER
        return verdict

    def allocate(self, seq_group: SequenceGroup) -> None:
        seq = seq_group.get_seqs(status=SequenceStatus.WAITING)[0]
        waiting = seq_group.get_seqs(status=SequenceStatus.WAITING)
        tables = []
        for g in self.groups:
            block_table: List[Optional[PhysicalTokenBlock]] = []
            num_prefix_blocks = 0
            prefix = seq_group.prefix if g is self.groups[0] else None
            if prefix is not None and prefix.allocated:
                # Reuse already-computed prefix blocks (+1 ref each).
                num_prefix_blocks = prefix.get_num_blocks()
                for block in prefix.block_table:
                    block.ref_count += seq_group.num_seqs()
                    block_table.append(block)
            # A windowed group holds the prompt's head only: the chunks
            # move it on (`advance_for_chunk`).
            for _ in range(num_prefix_blocks, g.blocks_for(seq.get_len())):
                block = g.device.allocate()
                # All seqs of the group share the full prompt.
                block.ref_count = seq_group.num_seqs()
                block_table.append(block)
            if prefix is not None and not prefix.allocated:
                # First group to bring this prefix in: pin its blocks.
                prefix_block_table = block_table[:prefix.get_num_blocks()]
                for block in prefix_block_table:
                    block.ref_count += 1
                prefix.set_block_table(prefix_block_table)
            tables.append(block_table)

        for seq in waiting:
            for g, block_table in zip(self.groups, tables):
                g.tables[seq.seq_id] = block_table.copy()
            if self.num_state_slots:
                self._take_state_slot(seq.seq_id, "alloc")

    # --- prefix import (disaggregated KV transfer) ------------------------

    def can_allocate_prefix_blocks(self, num_blocks: int) -> bool:
        return (self.device_allocator.get_num_free_blocks() - num_blocks
                >= self.watermark_blocks)

    def allocate_prefix_blocks(self, num_blocks: int) -> BlockTable:
        """Allocate device blocks for an imported (already-computed) prefix.
        Each block carries ref_count=1 — the prefix-pool pin, mirroring
        what `allocate()` does for the first group that computes a prefix
        locally — so the blocks survive until the pool drops them."""
        return [self.device_allocator.allocate() for _ in range(num_blocks)]

    # --- decode growth ---------------------------------------------------

    def _blocks_per_append(self, num_slots: int) -> int:
        """A CoW block plus the blocks covering `num_slots` lookahead
        tokens: what a sequence may take from a group for one step."""
        return 1 + (num_slots - 1) // self.block_size + 1

    def can_append_slots(self, seq_group: SequenceGroup,
                         num_slots: int = 1) -> bool:
        """Conservative check: every running seq may need a CoW block plus
        the blocks covering `num_slots` lookahead tokens (multi-step
        decode reserves K slots per scheduling pass), in every group."""
        num_seqs = seq_group.num_seqs(status=SequenceStatus.RUNNING)
        need = num_seqs * self._blocks_per_append(num_slots)
        return all(need <= g.device.get_num_free_blocks()
                   for g in self.groups)

    def append_slots(self, seq: Sequence,
                     num_slots: int = 1) -> List[Tuple[int, int]]:
        """Ensure physical slots exist for the next `num_slots` token
        positions (positions len-1 .. len+num_slots-2 get written by the
        fused decode steps).

        Returns [(src, dst)] copy-on-write pairs of the primary group
        (shared trailing block); the second group's go to `state_ops`.
        """
        first_pos = seq.get_len() - 1
        total_tokens = seq.get_len() + num_slots - 1
        cows: List[Tuple[int, int]] = []
        for g in self.groups:
            block_table = g.tables[seq.seq_id]
            # CoW the current last block only when shared AND actually
            # written this step (the first write position falls inside
            # it); writes to fresh blocks never need a copy.
            first_write_block = first_pos // self.block_size
            if block_table and first_write_block < len(block_table):
                last_block = block_table[-1]
                assert last_block.device == Device.DEVICE
                if last_block.ref_count > 1:
                    new_block = g.device.allocate()
                    block_table[-1] = new_block
                    g.device.free(last_block)
                    pair = (last_block.block_number, new_block.block_number)
                    if g is self.groups[0]:
                        cows.append(pair)
                    else:
                        self.state_ops.append(("w_copy", ) + pair)
        self._advance(seq.seq_id, first_pos, total_tokens)
        return cows

    def can_grow_all(self, targets: List[Tuple[int, int]],
                     first_positions: Optional[List[int]] = None) -> bool:
        """Whether `grow_to` would succeed for EVERY (seq_id, target_len)
        pair without dipping below the watermark — the shortfalls sum, so
        a per-row check would over-admit. Used by the pipelined decode
        continuation, whose host sequence lengths lag the device by the
        in-flight fused steps — targets are explicit token counts, not
        `seq.get_len()`. `first_positions`: the rows' first query
        positions (what a windowed group may give back first)."""
        if first_positions is None:
            first_positions = [0] * len(targets)
        for g in self.groups:
            total_short = 0
            for (seq_id, target_len), first in zip(targets, first_positions):
                if seq_id not in g.tables:
                    return False
                total_short += g.shortfall(seq_id, first, target_len)
            if total_short > (g.device.get_num_free_blocks()
                              - g.watermark_blocks):
                return False
        return True

    def grow_to(self, seq_id: int, target_len: int,
                first_pos: int = 0) -> List[int]:
        """Extend a sequence's block tables to cover `target_len` tokens
        (a windowed group first gives back what a step from `first_pos`
        on cannot see) and return the primary block-number table. No
        copy-on-write handling: the continuation path only runs for
        sequences whose trailing block is private (the first post-prefill
        decode step, which goes through `append_slots`, resolves any fork
        sharing)."""
        self._advance(seq_id, first_pos, target_len)
        return self.groups[0].table_numbers(seq_id)

    def fork(self, parent_seq: Sequence, child_seq: Sequence) -> None:
        for g in self.groups:
            src_block_table = g.tables[parent_seq.seq_id]
            g.tables[child_seq.seq_id] = src_block_table.copy()
            for block in src_block_table:
                if block is not None:
                    block.ref_count += 1
        if self.num_state_slots:
            self.state_ops.append(
                ("fork", self.state_slots[parent_seq.seq_id],
                 self._take_state_slot(child_seq.seq_id, "fork")))

    # --- swap ------------------------------------------------------------

    @staticmethod
    def _unfinished_ids(seq_group: SequenceGroup) -> List[int]:
        return [seq.seq_id for seq in seq_group.get_seqs()
                if not seq.is_finished()]

    def can_swap_in(self, seq_group: SequenceGroup,
                    num_slots: int = 1) -> bool:
        num_swapped = seq_group.num_seqs(status=SequenceStatus.SWAPPED)
        # Headroom per seq for the imminent append: with multi-step decode
        # the scheduler reserves `num_slots` lookahead slots right after the
        # swap-in, which may need a CoW block plus the blocks covering the
        # lookahead tokens (same budget as can_append_slots).
        headroom = num_swapped * self._blocks_per_append(num_slots)
        if self.num_state_slots and num_swapped > len(self._free_state_slots):
            return False
        ids = self._unfinished_ids(seq_group)
        return all(
            len(g.live(ids)) + headroom
            <= g.device.get_num_free_blocks() - g.watermark_blocks
            for g in self.groups)

    def _record_window_moves(self, op: str, mapping: Dict[int, int]) -> None:
        self.state_ops.extend((op, src, dst) for src, dst in mapping.items())

    def swap_in(self, seq_group: SequenceGroup) -> Dict[int, int]:
        """Plan CPU→HBM block moves; returns {cpu_block_no: device_block_no}
        of the primary group."""
        ids = [seq.seq_id
               for seq in seq_group.get_seqs(status=SequenceStatus.SWAPPED)]
        primary, *others = self.groups
        mapping = primary.move(ids, primary.cpu, primary.device)
        for g in others:
            self._record_window_moves("w_swap_in",
                                      g.move(ids, g.cpu, g.device))
        if self.num_state_slots:
            for sid in ids:
                host = self.cpu_state_slots.pop(sid)
                self._free_cpu_state_slots.append(host)
                self.state_ops.append(
                    ("swap_in", host, self._take_state_slot(sid, "swap_in")))
        return mapping

    def can_swap_out(self, seq_group: SequenceGroup) -> bool:
        if self.num_state_slots and (
                seq_group.num_seqs(status=SequenceStatus.RUNNING)
                > len(self._free_cpu_state_slots)):
            return False
        ids = self._unfinished_ids(seq_group)
        return all(len(g.live(ids)) <= g.cpu.get_num_free_blocks()
                   for g in self.groups)

    def swap_out(self, seq_group: SequenceGroup) -> Dict[int, int]:
        """Plan HBM→CPU block moves; returns {device_block_no: cpu_block_no}
        of the primary group."""
        ids = [seq.seq_id
               for seq in seq_group.get_seqs(status=SequenceStatus.RUNNING)]
        primary, *others = self.groups
        mapping = primary.move(ids, primary.device, primary.cpu)
        for g in others:
            self._record_window_moves("w_swap_out",
                                      g.move(ids, g.device, g.cpu))
        if self.num_state_slots:
            for sid in ids:
                slot = self.state_slots.pop(sid)
                host = self._free_cpu_state_slots.pop()
                self.cpu_state_slots[sid] = host
                self._free_state_slots.append(slot)
                self.state_ops.append(("swap_out", slot, host))
                self._report_state_slots("swap_out")
        return mapping

    # --- free ------------------------------------------------------------

    def free(self, seq: Sequence) -> None:
        self.release_state_slot(seq)
        for g in self.groups:
            g.free_table(seq.seq_id)  # nothing if freed or never allocated

    def reset(self) -> None:
        for g in self.groups:
            for seq_id in list(g.tables):
                g.free_table(seq_id)
        self.state_slots.clear()
        self.cpu_state_slots.clear()
        self._free_state_slots = list(range(self.num_state_slots))[::-1]
        self._free_cpu_state_slots = list(range(self.num_state_slots))[::-1]
        self.state_ops.clear()
        self._report_state_slots()

    def get_block_table(self, seq: Sequence) -> List[int]:
        return self.groups[0].table_numbers(seq.seq_id)

    def get_num_free_device_blocks(self) -> int:
        return self.device_allocator.get_num_free_blocks()

    def get_num_free_cpu_blocks(self) -> int:
        return self.cpu_allocator.get_num_free_blocks()

    def kv_pressure_detail(self) -> str:
        """Compact free-vs-watermark snapshot for scheduler decision
        events: what the watermark check saw when it said LATER."""
        detail = (f"free={self.device_allocator.get_num_free_blocks()}"
                  f"/{self.num_total_device_blocks}"
                  f",watermark={self.watermark_blocks}")
        if self.window_group is not None:
            g = self.window_group
            detail += (f",window_free={g.device.get_num_free_blocks()}"
                       f"/{g.num_device_blocks}")
        return detail
