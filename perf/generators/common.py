"""What every traffic generator shares.

A mix file fixes the *set* of sizes and arrival gaps of a window; the seed
chooses their order and the token contents. So every seed offers the same
work, and the spread between runs is the system's and the machine's, not
the draw's: lengths are the evenly spaced quantiles of the mix's
distribution, gaps the evenly spaced quantiles of the exponential (scaled
so that the last request is due inside the window), each shuffled by the
seed.
"""

from __future__ import annotations

import math
import random


def rng_for(seed: int, *purpose) -> random.Random:
    """An independent stream per purpose; any seed up to 2**32 and beyond."""
    return random.Random(f"{int(seed)}:" + ":".join(str(p) for p in purpose))


def quantile_lengths(spec, n: int, rng: random.Random) -> list:
    """``n`` lengths: the (i + 0.5)/n quantiles of ``spec``, shuffled.
    ``spec`` is a number (fixed) or ``{"dist": "loguniform" | "uniform",
    "lo": a, "hi": b}``."""
    if isinstance(spec, (int, float)):
        return [int(spec)] * n
    lo, hi = float(spec["lo"]), float(spec["hi"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if spec["dist"] == "loguniform":
            v = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
        elif spec["dist"] == "uniform":
            v = lo + u * (hi - lo)
        else:
            raise ValueError(f"unknown distribution {spec['dist']!r}")
        out.append(int(round(v)))
    rng.shuffle(out)
    return out


def poisson_due_times(n: int, seconds: float, rng: random.Random) -> list:
    """``n`` due times in [0, seconds): exponential-quantile gaps in a
    seeded order, scaled so the schedule fills the window."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    rng.shuffle(gaps)
    scale = seconds / sum(gaps)
    t, out = 0.0, []
    for g in gaps:
        out.append(t)  # first request due at the window's start
        t += g * scale
    return out


def tokens(n: int, vocab: int, rng: random.Random) -> list:
    """``n`` token ids in [3, vocab): 0..2 are left to pad/bos/eos."""
    return [rng.randrange(3, vocab) for _ in range(n)]
