"""95th percentile over requests of decode time per output token, as
``generate`` times its decode loop."""
from bench.metrics._serve import p95_ms, per_request


def read(readings):
    return p95_ms(per_request(readings,
                              lambda b: b["decode_s"] / b["steps"]))
