"""The absorbed latent (MLA) decode attention, one call = one layer of
one decode step over every live position.

A query head's score against a cached row is a dot over ``r + rope``
numbers (the compressed ``c_kv`` and the one rotary key), and its value
a weighted sum over the row's first ``r``: 2 * H * ((r + rope) + r)
FLOPs a live position. The row is read ONCE (the values are a slice of
the keys): (r + rope) * 2 bytes a live position in bf16. For r = 512,
rope = 64, H = 32: 69,632 FLOPs and 1,152 bytes a position, 60 FLOPs a
byte, so memory bounds it on a v5e (240 FLOPs a byte at the peaks).
The queries and the output (H * (2r + rope) numbers a slot) are nothing
beside a slot's thousands of positions and are left out.
"""
from __future__ import annotations

from typing import Tuple


def ops_and_bytes(heads: int, rank: int, rope: int, live_tokens: float,
                  bytes_per: int = 2) -> Tuple[float, float]:
    flops = 2.0 * heads * ((rank + rope) + rank) * live_tokens
    return flops, float((rank + rope) * bytes_per) * live_tokens
