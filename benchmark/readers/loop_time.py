"""The scheduler loop's wall time by the engine's own counters
(`host_s.idle`, `host_s.hop` over `host_n.step`, `host_s.pause` of
`engine.metrics`: dynamo_tpu/obs PhaseClock, engine/core.py `_loop`), read
over the stretch from the window's opening to the END of the traced
stretch (`counters_open` -> `trace_counters[1]`, `trace_window[1]` -
`window[0]` seconds) and not to the window's close: a traced run stops
its profiler session inside the window, and writing the profile then
holds the interpreter, which the program would itself report as a pause
and a long hop.  None where the run was not traced, and where the
closing counters lack a key (an older program has none of them: nothing
to report, not 0)."""


def _stretch(ctx, *keys):
    """(counters at the stretch's ends, its seconds), or None."""
    ends = ctx.get("trace_counters")
    if not ends or ends[1] is None or any(k not in ends[1] for k in keys):
        return None
    return (ctx["counters_open"], ends[1],
            ctx["trace_window"][1] - ctx["window"][0])


def _grew(opened, closed, key):
    return closed[key] - opened.get(key, 0)


def share_of_stretch(ctx, counter):
    """100 * d(counter, seconds) / the stretch's seconds."""
    s = _stretch(ctx, counter)
    if s is None or s[2] <= 0:
        return None
    return 100.0 * _grew(s[0], s[1], counter) / s[2]


def mean_over_stretch(ctx, total, count, scale=1.0):
    """scale * d(total) / d(count) over the stretch; None where `count`
    did not move."""
    s = _stretch(ctx, total, count)
    n = _grew(s[0], s[1], count) if s else 0
    return scale * _grew(s[0], s[1], total) / n if n else None


def grown_over_stretch(ctx, counter, scale=1.0):
    """scale * d(counter) over the stretch."""
    s = _stretch(ctx, counter)
    return None if s is None else scale * _grew(s[0], s[1], counter)
