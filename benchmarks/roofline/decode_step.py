"""The decode step's roofline: one step must read this chip's share of
the weights once and the live K/V of every active sequence once, and
it must do 2*N FLOPs per active sequence plus attention's. Memory
bounds it at any batch this benchmark runs.

bytes = weight_bytes / chips + live_tokens * kv_bytes_per_token / chips
flops = (2*N*active + 4*L*Hq*D*live_tokens) / chips
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.roofline import model


def ops_and_bytes(cfg: Dict[str, Any], active: float, live_tokens: float,
                  chips: int = 1) -> Tuple[float, float]:
    _, L, hq, _, hd, _, _ = model.dims(cfg)
    nbytes = (model.weight_bytes(cfg)
              + live_tokens * model.kv_bytes_per_token(cfg)) / chips
    flops = (2.0 * model.matmul_params(cfg) * active
             + 4.0 * L * hq * hd * live_tokens) / chips
    return flops, nbytes
