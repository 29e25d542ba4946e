"""Program counters (`engine.metrics`, `engine.compile_watch.events`)
read as differences over the window."""


def _delta(ctx, key):
    return (ctx["counters_close"].get(key, 0)
            - ctx["counters_open"].get(key, 0))


def delta(ctx, key):
    return float(_delta(ctx, key))


def share_of_deltas(ctx, part, whole):
    """100 * d(part) / d(whole) over the window."""
    w = _delta(ctx, whole)
    return 100.0 * _delta(ctx, part) / w if w else None


def compiles_in_window(ctx):
    """Compile events whose time falls inside the window.  The watch's
    own `serving` flag is the worker's and is not set in-process, so they
    are counted by time (its clock is time.monotonic)."""
    t0, t1 = (t + ctx["mono_offset"] for t in ctx["window"])
    return float(sum(1 for e in ctx["compile_events"] if t0 <= e["t"] < t1))
