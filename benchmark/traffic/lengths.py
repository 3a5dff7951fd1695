"""Length multisets: the quantile points of a stated distribution, so
every run of a cell offers the same lengths and only their order, the
token ids and the arrival gaps come from the seed. A tail then does
not move with the draw."""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import List


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """`n` lengths at the mid-quantiles (i + 0.5) / n of `spec`:
    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    {"dist": "uniform", "min", "max"}."""
    dist = spec["dist"]
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if dist == "lognormal":
            x = spec["median"] * math.exp(
                spec["sigma"] * NormalDist().inv_cdf(p)
            )
        elif dist == "uniform":
            x = spec["min"] + (spec["max"] - spec["min"]) * p
        else:
            raise ValueError(f"unknown length distribution {dist!r}")
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out
