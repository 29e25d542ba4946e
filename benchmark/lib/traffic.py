"""Request sizes and prompt tokens from parameters in a traffic file.

Every seed gets the SAME sizes and the SAME inter-arrival gaps in the SAME
order, drawn from the mix's `sizes_seed`; `--seed` picks the token ids
only.  A window holds about a hundred requests, so their order IS the
work: with the order permuted by the seed, four seeds spread ttft_p95 by
28 % where two runs of one seed differed by 0.2 % (PERF.md, PR 23).
(Row/length arithmetic after dynamo_tpu/loadgen/trace.py, which draws
sizes and order from one seed.)
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np


@dataclass
class Row:
    index: int
    prompt_len: int
    max_tokens: int
    due: float = 0.0      # seconds from window open (open loop only)
    seed: int = 0         # of the token ids


def draw_lengths(dist: Dict[str, Any], n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """`{"dist": "lognormal", "median", "sigma", "min", "max"}` or
    `{"dist": "uniform", "min", "max"}` -> n whole numbers, clipped."""
    if dist["dist"] == "lognormal":
        v = rng.lognormal(math.log(dist["median"]), dist["sigma"], n)
    elif dist["dist"] == "uniform":
        v = rng.uniform(dist["min"], dist["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(v), dist["min"], dist["max"]).astype(np.int64)


def size_pool(mix: Dict[str, Any], n: int, stream: int) -> np.ndarray:
    """[n, 2] (prompt_len, max_tokens), the same for every `--seed`."""
    rng = np.random.default_rng([int(mix["sizes_seed"]), stream, n])
    return np.stack([draw_lengths(mix["prompt_tokens"], n, rng),
                     draw_lengths(mix["output_tokens"], n, rng)], axis=1)


def gap_pool(rate: float, n: int, span_s: float, mix: Dict[str, Any],
             stream: int) -> np.ndarray:
    """n exponential inter-arrival gaps (a Poisson process at `rate`),
    scaled to fill `span_s` exactly: every seed offers the same load."""
    rng = np.random.default_rng([int(mix["sizes_seed"]), 7, stream, n])
    gaps = rng.exponential(1.0 / rate, n)
    return gaps * (span_s / gaps.sum())


def rows_from(pool: np.ndarray, seed: int, first_index: int = 0
              ) -> List[Row]:
    return [Row(index=first_index + i, prompt_len=int(p), max_tokens=int(m),
                seed=(seed * 1000003 + first_index + i) % (2 ** 63))
            for i, (p, m) in enumerate(pool)]


def prompt_tokens(row: Row, vocab_size: int) -> List[int]:
    """Uniform ids over the vocabulary (ids 0-2 left out, as the program's
    own generators do): nothing is shared between prompts."""
    rng = np.random.default_rng([row.seed, row.index])
    return rng.integers(3, vocab_size, row.prompt_len).tolist()
