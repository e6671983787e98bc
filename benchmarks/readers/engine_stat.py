"""One key of ``ContinuousEngine.stats()`` as it stands when the window
closes: a gauge or a constant of the deployment that the program counts
for itself. A program whose ``stats()`` has no such key gives nothing
to read."""
from __future__ import annotations

from typing import Optional


def read(ctx, key: str) -> Optional[float]:
    val = (ctx.stats1 or {}).get(key)
    return None if val is None else float(val)
