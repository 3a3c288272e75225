"""The one traffic generator. A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it and a seed into requests.

Sizes are drawn by stratified blocks: each block of ``block`` requests
holds the same lengths (the distribution's quantiles at (i + 0.5) / block)
and, for an open loop, the same inter-arrival gaps, and the seed only
orders them and draws the token ids. So every seed offers the same work in
another order, and two seeds differ about as little as two runs of one.

Mix keys:

* ``arrival``: ``"poisson"`` (open loop at ``rate_per_s``) or
  ``"backlog"`` (a queue that never empties).
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` in tokens; ``output`` is the request's
  ``max_new_tokens``.
* ``block``: requests per stratified block.
* ``warm_in_s``: seconds the arrival process runs before the window opens
  (set-up). ``drain_cap_s``: how long after the window closes a request
  due in it may still get its first token before it counts as failed.
* ``ignore_eos``: serve every request to its ``max_new_tokens``.
* ``check_tokens``: served tokens the output check samples at least.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Arrival:
    due_s: float            # offset from the start of serving
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def quantiles(spec: Dict, n: int) -> List[int]:
    """The ``n`` stratified lengths of a length distribution."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = NormalDist()
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"] * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def gaps(rate: float, n: int) -> List[float]:
    """The ``n`` stratified gaps of a Poisson process at ``rate``/s."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def prompt_buckets(mix: Dict, multiple: int, max_len: int) -> List[int]:
    """Every padded prompt length the mix draws, for admission warm-up
    (the engine rounds a prompt up to ``multiple``, clamped to
    ``max_len``): those of the block's lengths, the only ones drawn."""
    return sorted({min(-(-n // multiple) * multiple, max_len)
                   for n in quantiles(mix["prompt"], int(mix["block"]))})


def arrivals(mix: Dict, seed: int, vocab: int) -> Iterator[Arrival]:
    """Endless arrivals of ``mix`` for ``seed``. Token ids are drawn over
    ``[2, vocab)`` (0 is the pad id, 1 the end-of-sequence id)."""
    rng = np.random.default_rng(seed)
    n = int(mix["block"])
    prompts, outputs = quantiles(mix["prompt"], n), quantiles(mix["output"], n)
    poisson = mix["arrival"] == "poisson"
    if not poisson and mix["arrival"] != "backlog":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    block_gaps = gaps(mix["rate_per_s"], n) if poisson else [0.0] * n
    t = 0.0
    while True:
        order_p, order_o, order_g = (rng.permutation(n) for _ in range(3))
        for i in range(n):
            t += block_gaps[order_g[i]]
            length = prompts[order_p[i]]
            yield Arrival(due_s=t,
                          prompt=rng.integers(2, vocab, size=length,
                                              dtype=np.int32),
                          max_new_tokens=outputs[order_o[i]])
