"""Draw request sizes.  A traffic file names a distribution for prompt and
output lengths; a cell's pool of sizes comes from the file's own
``pool_seed`` and not from the run's seed: every run offers the same requests
in the same order (PERF.md, PR 24: with the same multiset of sizes and gaps
in a shuffled or a rotated order, the tails followed the order, by 15%
and more).  The run's seed makes the text, the sampling seeds and the
weights."""

import math
import random


def draw(spec, rng):
    """One whole number from ``spec``: {"dist": "lognormal", "median",
    "sigma", "min", "max"} | {"dist": "uniform", "min", "max"} |
    {"dist": "fixed", "value"}."""
    kind = spec["dist"]
    if kind == "fixed":
        return int(spec["value"])
    if kind == "uniform":
        return rng.randint(int(spec["min"]), int(spec["max"]))
    if kind == "lognormal":
        x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
        return int(min(max(round(x), spec["min"]), spec["max"]))
    raise ValueError(f"unknown length distribution {kind!r}")


def pool(params, n, salt=0):
    """``n`` (prompt_tokens, max_tokens) pairs from the traffic file's
    ``pool_seed`` (``salt`` tells a second pool of the same file apart)."""
    rng = random.Random(int(params.get("pool_seed", 0)) * 1000 + salt)
    return [(draw(params["prompt_tokens"], rng),
             draw(params["max_tokens"], rng)) for _ in range(n)]
