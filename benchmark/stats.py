"""Small statistics the harness and the readers share."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) with linear interpolation between
    closest ranks, as numpy's default."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def histogram_delta(before: dict, after: dict) -> list:
    """Per-bucket counts that arrived between two snapshots of a labelled
    histogram, summed over its labels.  A snapshot maps each label tuple
    to its bucket counts (last entry the +Inf overflow)."""
    out = None
    for labels, counts in after.items():
        prev = before.get(labels, [0] * len(counts))
        d = [a - b for a, b in zip(counts, prev)]
        out = d if out is None else [x + y for x, y in zip(out, d)]
    return out or []


def histogram_quantile(edges, counts, q: float):
    """Bucket-interpolated quantile of a histogram (Prometheus
    ``histogram_quantile`` semantics): linear inside the bucket the rank
    falls in, the highest finite edge when it falls in +Inf.  None when
    the histogram is empty."""
    total = sum(counts)
    if total <= 0:
        return None
    rank = q * total
    cum = 0
    lo = 0.0
    for edge, c in zip(edges, counts):
        cum += c
        if c > 0 and cum >= rank:
            return lo + (edge - lo) * (rank - (cum - c)) / c
        lo = edge
    return edges[-1]
