"""Operations and bytes of the served programs, from their shapes.

Each count is of the logical work, the same whatever implements it, so a
later change that reads fewer bytes than the logic needs shows as a share
over 100% (refused) rather than as a gain.  A roofline share is the least
time the chip could take (the larger of bytes over peak bandwidth and
operations over peak rate, ``peaks.json``) over the time the trace gives the
kernel.
"""

from __future__ import annotations

from typing import Dict, Optional

F32 = 4


def flat_scan(rows: int, dim: int, queries: float) -> Dict[str, float]:
    """One exact scan of ``queries`` against ``rows``: the corpus and the
    queries read once; a multiply-add per element pair."""
    return {"bytes": (rows + queries) * dim * F32,
            "flops": 2.0 * rows * dim * queries}


def beam_gather(gathered_rows: float, dim: int) -> Dict[str, float]:
    """One layer-0 gather: each named row read once at the vector's own
    width (not the lane-padded tile), one multiply-add per element."""
    return {"bytes": gathered_rows * dim * F32,
            "flops": 2.0 * gathered_rows * dim}


def share(work: Dict[str, float], seconds: float,
          peak: Dict[str, float]) -> Optional[float]:
    """Roofline share in %, or None where there is no time to divide by."""
    if seconds <= 0:
        return None
    least = max(work["bytes"] / peak["hbm_bytes_per_s"],
                work["flops"] / peak["bf16_flops_per_s"])
    return 100.0 * least / seconds
