"""Host-side paged KV cache bookkeeping: block allocator + per-sequence block
tables + KV event emission hooks.

The device tensors live in the runner; this module owns WHICH blocks belong
to WHOM. Block ids are stable across the engine, the router events, and the
offload tiers — the same currency as the reference's block manager
(lib/llm/src/block_manager), though the multi-tier pools arrive separately.
The device-side page ENCODING is orthogonal to this bookkeeping: with
`DYN_KV_DTYPE=int8` the runner stores pages as int8 mantissas with
per-(layer, head, block) scales (ops/kv_quant.py) and nothing here changes —
a block id names the same page whether it is bf16 or quantized.

Block 0 is reserved as the null block: padded/inactive lanes write there.

A model whose paged layers are of two groups (`models.page_groups`: layers
that keep every position, and window layers that keep the last `window`
alone) has two pools of blocks, one a group, each as large as its own layers'
arrays, and a sequence has a table in each. The full group's is the one
every model has (`SequenceState.block_ids`); the window group's rides beside
it: a block of the full group has a companion in the window group from the
moment it is allocated until the window has wholly left it
(`BlockAllocator.window_of`), when the engine gives the companion back
(`detach_window`, `free_window`) and the sequence's window table names the
null block in its place.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from dynamo_tpu.tokens import TokenBlockSequence


class OutOfBlocks(RuntimeError):
    pass


class BlockAllocator:
    def __init__(self, num_blocks: int, window_blocks: int = 0) -> None:
        # block 0 reserved as null block
        self.num_blocks = num_blocks
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        # the window group's pool (none: `window_blocks` 0), its own null
        # block 0, and each full block's companion there (0: none, given
        # back or never had)
        self.window_blocks = window_blocks
        self._free_window: list[int] = list(range(window_blocks - 1, 0, -1))
        self.window_of = np.zeros(num_blocks if window_blocks else 0, np.int32)
        self.window_given_back = 0  # blocks, ever

    @property
    def free_count(self) -> int:
        """Blocks an `alloc` can still hand out: of the shorter pool, where
        there are two."""
        if self.window_blocks:
            return min(len(self._free), len(self._free_window))
        return len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > self.free_count:
            raise OutOfBlocks(f"need {n} blocks, have {self.free_count}")
        out = [self._free.pop() for _ in range(n)]
        if self.window_blocks:
            self.window_of[out] = [self._free_window.pop() for _ in range(n)]
        return out

    def free(self, blocks: list[int]) -> None:
        self._free.extend(blocks)
        if self.window_blocks and blocks:
            self._free_window.extend(self.detach_window(blocks))

    def detach_window(self, blocks: list[int]) -> list[int]:
        """Take the companions of `blocks` from them: from now on a table
        built of `window_of` names the null block there. Returns the window
        blocks, which are the caller's to hand to `free_window`, at once or
        when the dispatch that still names them has landed."""
        ids = self.window_of[blocks]
        self.window_of[blocks] = 0
        return [int(b) for b in ids if b]

    def free_window(self, window_blocks: list[int]) -> None:
        self._free_window.extend(window_blocks)

    def give_back(self, blocks: list[int]) -> list[int]:
        """`detach_window`, counted: blocks that have wholly left the
        window."""
        out = self.detach_window(blocks)
        self.window_given_back += len(out)
        return out

    @property
    def in_use(self) -> int:
        """Blocks of the pool every model has that some sequence holds."""
        return self.num_blocks - 1 - len(self._free)

    @property
    def window_in_use(self) -> int:
        return max(0, self.window_blocks - 1 - len(self._free_window))


@dataclass
class SequenceState:
    """Engine-side state of one running sequence."""

    seq_id: int
    token_ids: list[int]  # prompt + generated
    num_prompt: int
    block_ids: list[int] = field(default_factory=list)
    slot: Optional[int] = None  # decode batch lane
    hash_seq: Optional[TokenBlockSequence] = None  # block-hash chain
    emitted_hashes: int = 0  # how many block hashes already published
    # leading blocks whose window companions have been given back
    window_released: int = 0

    @property
    def pos(self) -> int:
        """Number of tokens whose KV is in cache."""
        return len(self.token_ids)

    def blocks_needed(self, block_size: int) -> int:
        return (len(self.token_ids) + block_size - 1) // block_size
