"""What a call needs, from its shapes alone, and the chip's peaks.

The fleet step is integer gather/scatter over columnar segment arrays: no
matrix unit is involved, so its roofline is the memory one.  The bytes the
CALL NEEDS are not the bytes the program moves: each document that got an op
has its state read once and written once, and every op row is uploaded once.
A fleet-wide step that scans all 32 slots over every document's columns moves
far more; that is what the share shows.
"""

from __future__ import annotations

import json
import os

OP_FIELDS = 8         # mergetree_kernel.OP_FIELDS: int32 columns of one op row
MAX_INSERT_LEN = 8    # fleet_main --max-insert-len: int32 codepoints per row


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       "benchmark/peaks.json with its source")
    return table[device_kind]


def op_row_bytes() -> int:
    return 4 * (OP_FIELDS + MAX_INSERT_LEN)


def state_bytes_per_doc(resident_bytes_per_device: dict, n_docs: int) -> float:
    return sum(resident_bytes_per_device.values()) / n_docs


def step_bytes_needed(touched_docs: int, op_rows: int,
                      doc_state_bytes: float) -> float:
    return 2.0 * touched_docs * doc_state_bytes + op_rows * op_row_bytes()


def memory_roofline_share(bytes_needed: float, device_seconds: float,
                          device_kind: str, chips: int = 1) -> float:
    """Percent: the least time the chips could take over what they took."""
    least = bytes_needed / (peaks(device_kind)["hbm_bytes_per_s"] * chips)
    return 100.0 * least / device_seconds
