"""Work completed over the whole window, per chip."""

from benchmark.lib.stats import tokens_in_window


def output_tokens_per_s(ctx):
    t0, t1 = ctx["window"]
    n = tokens_in_window(ctx["records"], t0, t1)
    return n / (t1 - t0) / ctx["chips"] if n else None


def setup_seconds(ctx):
    """Process start to the opening of the window (pre-roll included)."""
    return ctx["setup_s"]
