"""Closed loop: `clients` callers, each sends its next request when its
last one completes.  Mix parameters: `clients`, `preroll_s`,
`prompt_tokens`, `output_tokens`, `sizes_seed`, and `pool_per_s`: how
many rows to prepare per second of run (more than the system can
finish)."""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List

from .traffic import Row, rows_from, size_pool


def build(mix: Dict[str, Any], seconds: float, seed: int) -> List[Row]:
    span = float(seconds) + float(mix.get("preroll_s", 0.0))
    n = max(int(mix["clients"]),
            int(round(float(mix.get("pool_per_s", 4.0)) * span)))
    return rows_from(size_pool(mix, n, 1), seed)


async def drive(rows: List[Row], client, t_open: float, t_close: float,
                mix: Dict[str, Any]) -> List[asyncio.Task]:
    """Run the callers from now (t_open - preroll_s) to `t_close`.  A
    request is due when it is sent.  Returns the callers' tasks."""
    it = iter(rows)

    async def caller() -> None:
        while time.perf_counter() < t_close:
            row = next(it, None)
            if row is None:
                raise RuntimeError("closed loop ran out of rows: raise "
                                   "pool_per_s in the traffic file")
            await client.request(row, time.perf_counter())

    tasks = [asyncio.create_task(caller())
             for _ in range(int(mix["clients"]))]
    delay = t_close - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    return tasks
