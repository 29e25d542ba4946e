"""What the engine's own phase and request-stage counters say (the
`host_s.*` / `host_n.*` / `req_stage_*` keys of `engine.metrics`,
dynamo_tpu/obs PhaseClock and engine/core.py `_emit_first`), read as
differences over the window, and device time by program NAME.  A program
without these counters or names (an older commit) gives None."""

from benchmark.readers.counters import delta as _delta


def stage_mean_ms(ctx, stage):
    """Mean milliseconds a request spent in one stage of its time to
    first token (queue | prefill | emit), over the requests whose first
    token was emitted inside the window."""
    n = _delta(ctx, "req_stage_n")
    return 1e3 * _delta(ctx, f"req_stage_s.{stage}") / n if n else None


def host_ms_per_step(ctx):
    """Milliseconds of a scheduler step the host did not spend blocked on
    a device fetch: (step wall - device_wait) / steps."""
    n = _delta(ctx, "host_n.step")
    if not n:
        return None
    return 1e3 * (_delta(ctx, "host_s.step")
                  - _delta(ctx, "host_s.device_wait")) / n


def device_wait_share(ctx):
    """100 * seconds blocked on device fetches / seconds inside scheduler
    steps."""
    whole = _delta(ctx, "host_s.step")
    return 100.0 * _delta(ctx, "host_s.device_wait") / whole \
        if whole else None


def named_module_share(ctx, word):
    """100 * device seconds of the programs whose name contains `word` /
    device seconds of all programs, in the traced stretch.  None where no
    program carries the word (the program does not name its jits)."""
    tr = ctx.get("trace")
    if not tr or not tr.get("module_s"):
        return None
    whole = sum(tr["module_s"].values())
    part = sum(s for name, s in tr["module_s"].items() if word in name)
    return 100.0 * part / whole if part and whole else None
