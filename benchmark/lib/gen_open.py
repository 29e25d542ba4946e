"""Open loop: requests are due on a schedule fixed before the window,
whatever the system does.  Mix parameters: `rate_rps`, `preroll_s`,
`prompt_tokens`, `output_tokens`, `sizes_seed`."""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List

import numpy as np

from .traffic import Row, gap_pool, rows_from, size_pool


def build(mix: Dict[str, Any], seconds: float, seed: int) -> List[Row]:
    """Pre-roll rows (due < 0, not counted: the window opens on a system
    already in its steady state) then the window's rows (0 <= due <
    seconds)."""
    rate = float(mix["rate_rps"])
    out: List[Row] = []
    for stream, t0, span in ((1, -float(mix.get("preroll_s", 0.0)),
                              float(mix.get("preroll_s", 0.0))),
                             (2, 0.0, float(seconds))):
        n = int(round(rate * span))
        if n <= 0:
            continue
        rows = rows_from(size_pool(mix, n, stream), seed, len(out))
        gaps = gap_pool(rate, n, span, mix, stream)
        # a request falls in the middle of its gap, so the last one is
        # due before the span ends and the spans join without a seam
        due = t0 + np.cumsum(gaps) - gaps / 2.0
        for r, d in zip(rows, due):
            r.due = float(d)
        out.extend(rows)
    return out


async def drive(rows: List[Row], client, t_open: float, t_close: float,
                mix: Dict[str, Any]) -> List[asyncio.Task]:
    """Send each row when it is due; return at `t_close` with the tasks
    (finished or not).  Called at t_open - preroll_s."""
    tasks: List[asyncio.Task] = []
    for row in rows:
        due_t = t_open + row.due
        delay = due_t - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        if time.perf_counter() >= t_close:
            break
        tasks.append(asyncio.create_task(client.request(row, due_t)))
    delay = t_close - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    return tasks
