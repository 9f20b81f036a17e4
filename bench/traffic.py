"""The one traffic generator: requests from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

- ``batch``: rows of every call into the server (a static batch);
- ``prompt_len``, ``output_len``: tokens in and greedy tokens out;
- ``arrival``: ``uniform``, one request every ``1 / rate_per_s`` seconds;
- ``rate_per_s``: the offered load, fixed in the cell.

The seed changes the prompts' token ids (uniform over the vocabulary),
never the sizes or the arrival times, so every seed asks for the same work.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np


@dataclass(frozen=True)
class Requests:
    arrivals: np.ndarray      # (n,) seconds from the window's start
    prompts: np.ndarray       # (n, prompt_len) int32 token ids
    output_len: int


def load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Arrival times of every request due in a window of ``seconds``."""
    if traffic["arrival"] != "uniform":
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}")
    rate = traffic["rate_per_s"]
    return np.arange(int(seconds * rate + 1e-9)) / rate


def requests(traffic: dict, seed: int, seconds: float, vocab: int
             ) -> Requests:
    t = arrivals(traffic, seconds)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, vocab, size=(len(t), traffic["prompt_len"]),
                           dtype=np.int32)
    return Requests(t, prompts, traffic["output_len"])
