"""Decode's roofline share: least time at the chip's peaks for each
batch's decode steps over its real requests (``counts.decode``, live K/V
only; padding rows are not work) over ``generate``'s ``decode_s``."""
from bench.counts import lm as counts
from bench.metrics._serve import mfu


def read(readings):
    return mfu(readings,
               lambda cfg, b: counts.decode(cfg, b["rows"], b["prompt"],
                                            b["steps"]),
               "decode_s")
