"""Operations and bytes of the kernels the benchmark rates, as
functions of the configuration and the shapes — never of the
implementation — and the table of peaks they are held against."""
from __future__ import annotations

import json
import os
from typing import Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Peaks of ``device_kind`` from ``benchmarks/peaks.json``. A
    device that is not in the table is an error, not a default."""
    with open(os.path.join(os.path.dirname(_HERE), 'peaks.json')) as f:
        table = json.load(f)['devices']
    if device_kind not in table:
        raise KeyError(f'no peaks for device kind {device_kind!r}; '
                       f'known: {sorted(table)}')
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: Dict[str, float]) -> float:
    """Least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the time taken, in
    percent."""
    least = max(flops / peaks['bf16_flops'],
                nbytes / peaks['hbm_bytes_per_s'])
    return 100.0 * least / seconds
