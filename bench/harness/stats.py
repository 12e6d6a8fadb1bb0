"""Tail arithmetic over every sample of a window.

Percentiles are numpy's linear interpolation (the rule ``launch/metrics.py``
in the program uses). Latencies are measured from when a request was DUE,
not from when it was submitted: in an open loop a stall delays submission of
the requests behind it, and that wait is part of what their users feel.
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence

import numpy as np


def percentile(xs: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile of every sample, or None for no samples."""
    arr = np.asarray(list(xs), dtype=np.float64)
    if arr.size == 0:
        return None
    return float(np.percentile(arr, q))


def ttft_samples(due: Sequence[float], first: Sequence[Optional[float]],
                 w0: float, w1: float, censor_at: float) -> list:
    """Seconds from due time to first token of every request due in
    ``[w0, w1)``. A request that has no first token by ``censor_at`` (the
    harness waits past the window's close for late ones) counts with
    ``censor_at - due``, a lower bound on its wait; it is never dropped."""
    out = []
    for d, f in zip(due, first):
        if not w0 <= d < w1:
            continue
        out.append((f if f is not None else censor_at) - d)
    return out


def itl_samples(token_times: Iterable[Sequence[float]], w0: float, w1: float) -> list:
    """Every gap between consecutive output tokens of a request with both
    tokens in ``[w0, w1]``, pooled over requests."""
    out = []
    for times in token_times:
        t = np.asarray(times, dtype=np.float64)
        if t.size < 2:
            continue
        both = (t[:-1] >= w0) & (t[1:] <= w1)
        out.extend(np.diff(t)[both].tolist())
    return out


def tokens_in(token_times: Iterable[Sequence[float]], w0: float, w1: float) -> int:
    """Output tokens emitted in ``[w0, w1]``."""
    n = 0
    for times in token_times:
        t = np.asarray(times, dtype=np.float64)
        n += int(((t >= w0) & (t <= w1)).sum())
    return n
