"""Paged KV-cache block bookkeeping (host side).

Role parity: reference `vllm/core/block_manager.py` (BlockAllocator :10,
AllocStatus :54, BlockSpaceManager :68): logical→physical block maps,
refcounted free lists per device, copy-on-write forking, host↔HBM swap
planning, sliding-window block rings, allocation watermark. The physical
block numbers index the HBM pool arrays held by the worker's CacheEngine;
this module never touches device memory itself — it emits block-op plans
(swap-in / swap-out / copy dicts) that the worker executes.

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
    ) -> None:
        self.block_size = block_size
        self.num_total_device_blocks = num_device_blocks
        self.num_total_cpu_blocks = num_cpu_blocks

        self.block_sliding_window: Optional[int] = None
        if sliding_window is not None:
            assert sliding_window % block_size == 0, (sliding_window, block_size)
            self.block_sliding_window = sliding_window // block_size

        self.watermark = watermark
        assert watermark >= 0.0
        self.watermark_blocks = int(watermark * num_device_blocks)

        self.device_allocator = BlockAllocator(Device.DEVICE, block_size,
                                               num_device_blocks)
        self.cpu_allocator = BlockAllocator(Device.CPU, block_size,
                                            num_cpu_blocks)
        # seq_id -> physical block table
        self.block_tables: Dict[int, BlockTable] = {}

        # seq_id -> state slot, on the device (running) or on the host
        # (swapped); as many host slots as device slots.
        self.num_state_slots = num_state_slots
        self.state_slots: Dict[int, int] = {}
        self.cpu_state_slots: Dict[int, int] = {}
        self._free_state_slots = list(range(num_state_slots))[::-1]
        self._free_cpu_state_slots = list(range(num_state_slots))[::-1]
        # ("swap_out" | "swap_in" | "fork", source slot, destination slot)
        # in the order made. The engine hands the worker this very list
        # (never rebound, only appended to and cleared): the worker runs
        # and clears it before its next device step.
        self.state_ops: List[Tuple[str, int, int]] = []
        from intellillm_tpu.obs import get_efficiency_tracker
        self._efficiency = get_efficiency_tracker()
        self._report_state_slots()

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
        num_required = seq.num_logical_blocks()

        if seq_group.prefix is not None and seq_group.prefix.allocated:
            num_required -= seq_group.prefix.get_num_blocks()

        if self.block_sliding_window is not None:
            num_required = min(num_required, self.block_sliding_window)

        num_free = self.device_allocator.get_num_free_blocks()
        if self.num_total_device_blocks - num_required < self.watermark_blocks:
            return AllocStatus.NEVER
        if num_free - num_required >= self.watermark_blocks:
            return AllocStatus.OK
        return AllocStatus.LATER

    def allocate(self, seq_group: SequenceGroup) -> None:
        seq = seq_group.get_seqs(status=SequenceStatus.WAITING)[0]
        num_prompt_blocks = seq.num_logical_blocks()

        block_table: BlockTable = []
        prefix_block_table: BlockTable = []
        num_prefix_blocks = 0

        prefix = seq_group.prefix
        if prefix is not None and prefix.allocated:
            # Reuse already-computed prefix blocks (+1 ref each).
            num_prefix_blocks = prefix.get_num_blocks()
            for block in prefix.block_table:
                block.ref_count += seq_group.num_seqs()
                block_table.append(block)

        for logical_idx in range(num_prefix_blocks, num_prompt_blocks):
            if (self.block_sliding_window is not None
                    and logical_idx >= self.block_sliding_window):
                # Ring reuse: positions beyond the window wrap onto old blocks.
                block = block_table[logical_idx % self.block_sliding_window]
            else:
                block = self.device_allocator.allocate()
                # All seqs of the group share the full prompt.
                block.ref_count = seq_group.num_seqs()
            block_table.append(block)

        if prefix is not None and not prefix.allocated:
            # First group to bring this prefix in: pin its blocks.
            num_prefix_blocks = prefix.get_num_blocks()
            prefix_block_table = block_table[:num_prefix_blocks]
            for block in prefix_block_table:
                block.ref_count += 1
            prefix.set_block_table(prefix_block_table)

        for seq in seq_group.get_seqs(status=SequenceStatus.WAITING):
            self.block_tables[seq.seq_id] = block_table.copy()
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

    def can_append_slots(self, seq_group: SequenceGroup,
                         num_slots: int = 1) -> bool:
        """Conservative check: every running seq may need a CoW block plus
        the blocks covering `num_slots` lookahead tokens (multi-step
        decode reserves K slots per scheduling pass)."""
        num_free = self.device_allocator.get_num_free_blocks()
        num_seqs = seq_group.num_seqs(status=SequenceStatus.RUNNING)
        blocks_per_seq = 1 + (num_slots - 1) // self.block_size + 1
        return num_seqs * blocks_per_seq <= num_free

    def append_slots(self, seq: Sequence,
                     num_slots: int = 1) -> List[Tuple[int, int]]:
        """Ensure physical slots exist for the next `num_slots` token
        positions (positions len-1 .. len+num_slots-2 get written by the
        fused decode steps).

        Returns [(src, dst)] copy-on-write pairs (shared trailing block).
        """
        block_table = self.block_tables[seq.seq_id]
        total_tokens = seq.get_len() + num_slots - 1
        blocks_needed = (total_tokens + self.block_size - 1) // self.block_size

        cows: List[Tuple[int, int]] = []
        # CoW the current last block only when shared AND actually written
        # this step (the first write position falls inside it); writes to
        # fresh blocks never need a copy.
        first_write_block = (seq.get_len() - 1) // self.block_size
        if block_table and first_write_block < len(block_table):
            last_block = block_table[-1]
            assert last_block.device == Device.DEVICE
            if last_block.ref_count > 1:
                new_block = self.device_allocator.allocate()
                block_table[-1] = new_block
                self.device_allocator.free(last_block)
                cows.append((last_block.block_number, new_block.block_number))

        while len(block_table) < blocks_needed:
            if (self.block_sliding_window
                    and len(block_table) >= self.block_sliding_window):
                block_table.append(
                    block_table[len(block_table) % self.block_sliding_window])
            else:
                block_table.append(self.device_allocator.allocate())
        return cows

    def can_grow_all(self, targets: List[Tuple[int, int]]) -> bool:
        """Whether `grow_to` would succeed for EVERY (seq_id, target_len)
        pair without dipping below the watermark — the shortfalls sum, so
        a per-row check would over-admit. Used by the pipelined decode
        continuation, whose host sequence lengths lag the device by the
        in-flight fused steps — targets are explicit token counts, not
        `seq.get_len()`."""
        total_short = 0
        for seq_id, target_len in targets:
            block_table = self.block_tables.get(seq_id)
            if block_table is None:
                return False
            needed = (target_len + self.block_size - 1) // self.block_size
            if self.block_sliding_window is not None:
                needed = min(needed, self.block_sliding_window)
            total_short += max(0, needed - len(block_table))
        return total_short <= (self.device_allocator.get_num_free_blocks()
                               - self.watermark_blocks)

    def grow_to(self, seq_id: int, target_len: int) -> List[int]:
        """Extend a sequence's block table to cover `target_len` tokens and
        return the block-number table. No copy-on-write handling: the
        continuation path only runs for sequences whose trailing block is
        private (the first post-prefill decode step, which goes through
        `append_slots`, resolves any fork sharing)."""
        block_table = self.block_tables[seq_id]
        needed = (target_len + self.block_size - 1) // self.block_size
        while len(block_table) < needed:
            if (self.block_sliding_window
                    and len(block_table) >= self.block_sliding_window):
                block_table.append(
                    block_table[len(block_table) % self.block_sliding_window])
            else:
                block_table.append(self.device_allocator.allocate())
        return [b.block_number for b in block_table]

    def fork(self, parent_seq: Sequence, child_seq: Sequence) -> None:
        src_block_table = self.block_tables[parent_seq.seq_id]
        self.block_tables[child_seq.seq_id] = src_block_table.copy()
        for block in src_block_table:
            block.ref_count += 1
        if self.num_state_slots:
            self.state_ops.append(
                ("fork", self.state_slots[parent_seq.seq_id],
                 self._take_state_slot(child_seq.seq_id, "fork")))

    # --- swap ------------------------------------------------------------

    def _get_physical_blocks(
            self, seq_group: SequenceGroup) -> List[PhysicalTokenBlock]:
        blocks: Set[PhysicalTokenBlock] = set()
        for seq in seq_group.get_seqs():
            if seq.is_finished():
                continue
            blocks.update(self.block_tables[seq.seq_id])
        return list(blocks)

    def can_swap_in(self, seq_group: SequenceGroup,
                    num_slots: int = 1) -> bool:
        blocks = self._get_physical_blocks(seq_group)
        num_swapped = seq_group.num_seqs(status=SequenceStatus.SWAPPED)
        num_free = self.device_allocator.get_num_free_blocks()
        # Headroom per seq for the imminent append: with multi-step decode
        # the scheduler reserves `num_slots` lookahead slots right after the
        # swap-in, which may need a CoW block plus the blocks covering the
        # lookahead tokens (same budget as can_append_slots).
        blocks_per_seq = 1 + (num_slots - 1) // self.block_size + 1
        if self.num_state_slots and num_swapped > len(self._free_state_slots):
            return False
        return (len(blocks) + num_swapped * blocks_per_seq
                <= num_free - self.watermark_blocks)

    def swap_in(self, seq_group: SequenceGroup) -> Dict[int, int]:
        """Plan CPU→HBM block moves; returns {cpu_block_no: device_block_no}."""
        mapping: Dict[PhysicalTokenBlock, PhysicalTokenBlock] = {}
        for seq in seq_group.get_seqs(status=SequenceStatus.SWAPPED):
            new_block_table: BlockTable = []
            for cpu_block in self.block_tables[seq.seq_id]:
                if cpu_block in mapping:
                    device_block = mapping[cpu_block]
                    device_block.ref_count += 1
                else:
                    device_block = self.device_allocator.allocate()
                    mapping[cpu_block] = device_block
                new_block_table.append(device_block)
                self.cpu_allocator.free(cpu_block)
            self.block_tables[seq.seq_id] = new_block_table
            if self.num_state_slots:
                host = self.cpu_state_slots.pop(seq.seq_id)
                self._free_cpu_state_slots.append(host)
                self.state_ops.append(
                    ("swap_in", host,
                     self._take_state_slot(seq.seq_id, "swap_in")))
        return {
            cpu.block_number: dev.block_number
            for cpu, dev in mapping.items()
        }

    def can_swap_out(self, seq_group: SequenceGroup) -> bool:
        if self.num_state_slots and (
                seq_group.num_seqs(status=SequenceStatus.RUNNING)
                > len(self._free_cpu_state_slots)):
            return False
        return (len(self._get_physical_blocks(seq_group))
                <= self.cpu_allocator.get_num_free_blocks())

    def swap_out(self, seq_group: SequenceGroup) -> Dict[int, int]:
        """Plan HBM→CPU block moves; returns {device_block_no: cpu_block_no}."""
        mapping: Dict[PhysicalTokenBlock, PhysicalTokenBlock] = {}
        for seq in seq_group.get_seqs(status=SequenceStatus.RUNNING):
            new_block_table: BlockTable = []
            for device_block in self.block_tables[seq.seq_id]:
                if device_block in mapping:
                    cpu_block = mapping[device_block]
                    cpu_block.ref_count += 1
                else:
                    cpu_block = self.cpu_allocator.allocate()
                    mapping[device_block] = cpu_block
                new_block_table.append(cpu_block)
                self.device_allocator.free(device_block)
            self.block_tables[seq.seq_id] = new_block_table
            if self.num_state_slots:
                slot = self.state_slots.pop(seq.seq_id)
                host = self._free_cpu_state_slots.pop()
                self.cpu_state_slots[seq.seq_id] = host
                self._free_state_slots.append(slot)
                self.state_ops.append(("swap_out", slot, host))
                self._report_state_slots("swap_out")
        return {
            dev.block_number: cpu.block_number
            for dev, cpu in mapping.items()
        }

    # --- free ------------------------------------------------------------

    def _free_block_table(self, block_table: BlockTable) -> None:
        for block in set(block_table):
            if block.device == Device.DEVICE:
                self.device_allocator.free(block)
            else:
                self.cpu_allocator.free(block)

    def free(self, seq: Sequence) -> None:
        self.release_state_slot(seq)
        if seq.seq_id not in self.block_tables:
            return  # already freed or never allocated
        self._free_block_table(self.block_tables[seq.seq_id])
        del self.block_tables[seq.seq_id]

    def reset(self) -> None:
        for block_table in self.block_tables.values():
            self._free_block_table(block_table)
        self.block_tables.clear()
        self.state_slots.clear()
        self.cpu_state_slots.clear()
        self._free_state_slots = list(range(self.num_state_slots))[::-1]
        self._free_cpu_state_slots = list(range(self.num_state_slots))[::-1]
        self.state_ops.clear()
        self._report_state_slots()

    def get_block_table(self, seq: Sequence) -> List[int]:
        return [b.block_number for b in self.block_tables[seq.seq_id]]

    def get_num_free_device_blocks(self) -> int:
        return self.device_allocator.get_num_free_blocks()

    def get_num_free_cpu_blocks(self) -> int:
        return self.cpu_allocator.get_num_free_blocks()

    def kv_pressure_detail(self) -> str:
        """Compact free-vs-watermark snapshot for scheduler decision
        events: what the watermark check saw when it said LATER."""
        return (f"free={self.device_allocator.get_num_free_blocks()}"
                f"/{self.num_total_device_blocks}"
                f",watermark={self.watermark_blocks}")
