"""The end-to-end metrics of a window, from the engines' stamps.

A window is a list of pools ``(submitted, returned, requests)``.  Every
request of every pool counts.  Rates are all the window's tokens over all of
its time; tails are percentiles over every sample (linear interpolation
between order statistics, as ``numpy.percentile`` takes them).
"""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    if len(values) == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_metrics(pools: list, log, t_open: float, t_close: float) -> dict:
    ttft, itl, tokens = [], [], 0
    for t_sub, _, reqs in pools:
        for r in reqs:
            ts = log.token_t.get(r.rid, [])
            tokens += len(ts)
            if ts:
                ttft.append(ts[0] - t_sub)
                itl.extend(np.diff(ts))
    return {
        "output_tokens_per_s": tokens / (t_close - t_open),
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "itl_p95_ms": 1e3 * percentile(itl, 95),
    }
