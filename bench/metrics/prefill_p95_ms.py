"""95th percentile over requests of their batch's prefill time, as
``generate`` times it (call to prefill logits ready)."""
from bench.metrics._serve import p95_ms, per_request


def read(readings):
    return p95_ms(per_request(readings, lambda b: b["prefill_s"]))
