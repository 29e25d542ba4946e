"""The comparison that decides `correct`.

Outside the window, on the device the cell runs on, at the cell's widths
and depth: one seeded prompt goes through the engine (prefill, then
decode through the cache), greedy.  The class's plain float32 reference
then runs ONE full forward over prompt + emitted tokens, and every
emitted token is held against the reference's logits at its position.

Tolerance.  With random weights the largest logit wins by a hair, and the
engine's bf16 arithmetic may pick a near-tie the float32 reference ranks
second: so the test is not "same argmax" but "the emitted token's
reference logit lies within TOL_RANGE_SHARE of the position's largest,
as a share of that position's logit range (max - min)".  A wrong cache
offset, table or position gives logits unrelated to the reference's: the
emitted token then sits near the middle of the range (share ~ 0.5), far
outside 0.04.  bf16 against float32 over 8-16 layers was measured at
0.013-0.018 of the range between the program's own impls (PERF.md, PR
21); PR 23's chip runs are in PERF.md beside the figure used here.
"""

from __future__ import annotations

import asyncio
from typing import Any, Dict, List

import numpy as np

from .client import Client
from .traffic import Row, prompt_tokens

TOL_RANGE_SHARE = 0.04
PROMPT_TOKENS = 256
OUTPUT_TOKENS = 8
CHECK_SEED = 20260927


def logit_gaps(logits: np.ndarray, prompt_len: int,
               emitted: List[int]) -> List[float]:
    """For each emitted token: (max - logit[token]) / (max - min) of the
    reference's logits at the position that predicts it.  `logits` is
    [prompt_len + len(emitted), vocab]; row i predicts token i + 1."""
    gaps = []
    for i, tok in enumerate(emitted):
        row = logits[prompt_len - 1 + i]
        hi, lo = float(row.max()), float(row.min())
        gaps.append((hi - float(row[tok])) / max(hi - lo, 1e-30))
    return gaps


async def check_engine(engine, cfg, reference_logits) -> Dict[str, Any]:
    client = Client(engine, cfg.vocab_size, "correct")
    row = Row(index=0, prompt_len=PROMPT_TOKENS, max_tokens=OUTPUT_TOKENS,
              seed=CHECK_SEED)
    rec = await client.request(row, 0.0)
    prompt = prompt_tokens(row, cfg.vocab_size)
    emitted = list(rec["tokens"])
    out: Dict[str, Any] = {"emitted": emitted, "error": rec["error"],
                           "tolerance": TOL_RANGE_SHARE}
    if rec["error"] or len(emitted) != OUTPUT_TOKENS:
        out.update(ok=False, gaps=[])
        return out
    logits = await asyncio.to_thread(
        lambda: np.asarray(reference_logits(engine.params, cfg,
                                            prompt + emitted[:-1])))
    gaps = logit_gaps(logits, PROMPT_TOKENS, emitted)
    agree = sum(int(np.argmax(logits[PROMPT_TOKENS - 1 + i]) == t)
                for i, t in enumerate(emitted))
    out.update(ok=bool(max(gaps) <= TOL_RANGE_SHARE), gaps=gaps,
               argmax_agree=agree)
    return out


def requests_well_formed(records) -> bool:
    """Every finished request returned exactly max_tokens tokens, all
    inside the vocabulary (the client marks the latter as an error)."""
    return all(r["error"] is None
               and len(r["tokens"]) == r["max_tokens"]
               for r in records if r["end_t"] is not None)
