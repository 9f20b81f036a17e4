"""What the serving readers share: per-request values from the program's
spans (``generate``'s ``prefill_s`` and ``decode_s``), and step rooflines
from ``bench/counts/lm.py``."""
from __future__ import annotations

import numpy as np

from bench.counts import lm as counts


def per_request(readings: dict, key) -> list[float]:
    """``key(batch)`` once for each real request of each batch."""
    return [key(b) for b in readings.get("batches", []) for _ in
            range(b["rows"])]


def p95_ms(values: list[float]) -> float | None:
    return 1e3 * float(np.percentile(values, 95)) if values else None


def mfu(readings: dict, work, time_key: str) -> float | None:
    """Roofline share of a step: least time at peak over measured time,
    summed over every batch of the window, in %."""
    batches, peak = readings.get("batches"), readings.get("peak")
    if not batches or peak is None:
        return None
    least = sum(counts.roofline_s(*work(readings["config"], b), peak)
                for b in batches)
    return 100.0 * least / sum(b[time_key] for b in batches)
