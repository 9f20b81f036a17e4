"""Prefill's roofline share: least time at the chip's peaks for each
batch's prefill of its real requests (``counts.prefill``; padding rows
are not work) over ``generate``'s ``prefill_s``."""
from bench.counts import lm as counts
from bench.metrics._serve import mfu


def read(readings):
    return mfu(readings,
               lambda cfg, b: counts.prefill(cfg, b["rows"], b["prompt"]),
               "prefill_s")
