"""A request's life in the engine's stamps (`req_stage_s.wake` / `.lane`
/ `.turn` and `req_ahead_steps` over `req_stage_n`, `req_stage_s.join`
over `req_join_n`, `req_stage_s.decode` over `req_decode_tokens` of
`engine.metrics`: engine/core.py `_admit_waiting`, `_stamp_dispatch`,
`_emit_first`, `_push_token`) and the host's emit phase (`host_s.emit`
over `host_n.step`), each read as one counter's difference over the
window divided by another's.  A program without the counters (an older
commit, which may well have the count) gives None, not 0."""


def mean_of_deltas(ctx, total, count, scale=1.0):
    """scale * d(total) / d(count) over the window; None where `count`
    did not move or the program has either counter not at all."""
    opened, closed = ctx["counters_open"], ctx["counters_close"]
    if total not in closed or count not in closed:
        return None
    n = closed[count] - opened.get(count, 0)
    return scale * (closed[total] - opened.get(total, 0)) / n if n else None
