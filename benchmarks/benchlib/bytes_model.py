"""The least bytes one proposal has to move through device memory,
whatever implements it, from the configuration's file alone.

Each goal of the chain has to read the deployment's state once and write
the assignment once: a goal decides on the placement the goals before it
left, so the chain cannot be answered in fewer passes over the state. At
the unpadded shapes, 4 bytes an element (int32 ids, float32 loads):

- read: assignment ``[P, RF]``, leader slot ``[P]``, leader and follower
  load ``[P, 4]`` each, broker load and capacity ``[B, 4]`` each, replica
  and leader counts ``[B]`` each, racks ``[B]``, topic replica counts
  ``[topics, B]``;
- write: assignment ``[P, RF]`` and leader slot ``[P]``.

A search of many rounds moves far more; the share of the roofline says how
far the solver is from what the problem needs, and which bound it is
(bytes over bandwidth: goal evaluation does a few operations per byte).
"""

from __future__ import annotations

import json

from .deployment import path_of

_WORD = 4


def proposal_bytes(cfg: dict) -> int:
    p, b = int(cfg["partitions"]), int(cfg["brokers"])
    rf, topics = int(cfg["replication_factor"]), int(cfg["topics"])
    read = p * rf + p + 2 * p * 4 + 2 * b * 4 + 3 * b + topics * b
    write = p * rf + p
    return len(cfg["goals"]) * (read + write) * _WORD


def peak(device_kind: str) -> dict:
    """The peak table's row of a device; a kind that is not in the table
    is an error, not a default."""
    path = path_of("peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path}")
    return table[device_kind]
