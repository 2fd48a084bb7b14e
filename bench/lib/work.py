"""Bytes a filter operation needs, from the configuration's layout alone.

Counted from what the operation must move whatever implements it, never
from what today's kernels move: each key is read once (8 bytes), each
distinct block the keys touch is read once, an add writes each touched
block back once, and a lookup writes one byte of answer per key. A plan
that fetches less than its block, or fuses hashing into the kernel, is
then read against the same work.
"""
from __future__ import annotations

KEY_BYTES = 8
ANSWER_BYTES = 1


def lookup_bytes(n_keys: int, distinct_blocks: int, block_bits: int) -> int:
    return n_keys * (KEY_BYTES + ANSWER_BYTES) + distinct_blocks * (
        block_bits // 8)


def add_bytes(n_keys: int, distinct_blocks: int, block_bits: int) -> int:
    return n_keys * KEY_BYTES + 2 * distinct_blocks * (block_bits // 8)
