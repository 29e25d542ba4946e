"""Warm every program the cell's traffic reaches, before the window.

Decode programs: the program's own `warmup_decode()` covers the closed
ladder.  Prefill programs are keyed by (token bucket, rows, table width)
and only admission reaches them, so the cell's own size distribution is
replayed on fixed warm seeds (never the measured seed).  The mix's `warmup`
holds: `groups`, lists of prompt lengths sent together to an idle engine,
one group after another, worked out from the planner's pow2 triples so
that the common shapes are reached whatever the seeds (first round only);
and `stages`, each a closed loop of `clients` callers over `requests` rows
of the mix's own sizes with outputs clipped to `output_max`.  The stages
run again with the next warm seed until a round adds no compile, at most
`rounds_max` rounds.  With the compile cache filled, two rounds."""

from __future__ import annotations

import asyncio
from typing import Any, Dict, List

from .client import Client
from .traffic import Row, rows_from, size_pool

WARM_SEED = 911


async def _stage(client: Client, rows: List[Row], clients: int) -> None:
    it = iter(rows)

    async def caller() -> None:
        for row in it:
            rec = await client.request(row, 0.0)
            if rec["error"]:
                raise RuntimeError(f"warm-up request failed: {rec['error']}")

    await asyncio.gather(*[caller() for _ in range(clients)])


async def _groups(client: Client, groups, output_max: int) -> None:
    index = 0
    for lengths in groups:
        rows = []
        for n in lengths:
            rows.append(Row(index=index, prompt_len=int(n),
                            max_tokens=output_max, seed=WARM_SEED))
            index += 1
        await _stage(client, rows, len(rows))


def n_compiles(engine) -> int:
    return sum(engine.compile_watch.counts.values())


async def warm_prefill(engine, vocab_size: int, mix: Dict[str, Any],
                       log) -> Dict[str, Any]:
    plan = mix["warmup"]
    rounds = []
    for rnd in range(int(plan["rounds_max"])):
        before = n_compiles(engine)
        if rnd == 0 and plan.get("groups"):
            await _groups(Client(engine, vocab_size, "warmg"),
                          plan["groups"], int(plan.get("groups_output", 2)))
            log(f"warm-up groups: {n_compiles(engine) - before} compiles")
        for si, st in enumerate(plan["stages"]):
            # another seed every stage and round: equal token seeds would
            # share prefixes and reach prefix-hit shapes no window reaches
            rows = rows_from(size_pool(mix, int(st["requests"]),
                                       100 + 10 * rnd + si),
                             WARM_SEED + 1 + 10 * rnd + si)
            for r in rows:
                r.max_tokens = min(r.max_tokens, int(st["output_max"]))
            await _stage(Client(engine, vocab_size, f"warm{rnd}.{si}"),
                         rows, int(st["clients"]))
        added = n_compiles(engine) - before
        rounds.append(added)
        log(f"warm-up round {rnd}: {added} compiles")
        if added == 0:
            break
    await engine.clear_kv_blocks()
    return {"rounds": rounds}
