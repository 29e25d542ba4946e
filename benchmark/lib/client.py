"""The one place the load generators touch the system under test:
`JaxEngine.generate()` driven in-process, one record per request (timing
idiom after bench.py's served stage: stamp every stream item on
arrival, one stamp per token it carries)."""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List

from .traffic import Row, prompt_tokens


class Client:
    def __init__(self, engine, vocab_size: int, tag: str):
        self.engine = engine
        self.vocab_size = vocab_size
        self.tag = tag
        self.records: List[Dict[str, Any]] = []

    async def request(self, row: Row, due_t: float) -> Dict[str, Any]:
        from dynamo_tpu.protocols import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )

        rec: Dict[str, Any] = {
            "index": row.index, "due_t": due_t, "sent_t": None,
            "end_t": None, "prompt_len": row.prompt_len,
            "max_tokens": row.max_tokens, "token_times": [],
            "tokens": [], "error": None,
        }
        self.records.append(rec)
        req = PreprocessedRequest(
            token_ids=prompt_tokens(row, self.vocab_size),
            request_id=f"{self.tag}-{row.index}",
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=row.max_tokens, ignore_eos=True),
        )
        rec["sent_t"] = time.perf_counter()
        async for out in self.engine.generate(req):
            now = time.perf_counter()
            rec["token_times"].extend([now] * len(out.token_ids))
            rec["tokens"].extend(out.token_ids)
            if out.finish_reason == "error" or out.error:
                rec["error"] = out.error or "error"
        if any(not (0 <= t < self.vocab_size) for t in rec["tokens"]):
            rec["error"] = rec["error"] or "token outside the vocabulary"
        rec["end_t"] = time.perf_counter()
        return rec


async def settle(tasks: List[asyncio.Task]) -> int:
    """Cancel what is still running (the engine frees a cancelled
    request's slot and blocks) and wait for all; returns how many were
    cancelled.  An exception in a finished task is raised."""
    pending = [t for t in tasks if not t.done()]
    for t in pending:
        t.cancel()
    for t in tasks:
        try:
            await t
        except asyncio.CancelledError:
            pass
    return len(pending)
