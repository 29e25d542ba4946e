"""benchmark/readers/spans.py on hand-made observations: the arithmetic,
and None wherever a program without the counters or the names (an older
commit) leaves nothing to read."""

import pytest

from benchmark.lib import spec
from benchmark.readers import spans

OPEN = {"req_stage_s.queue": 1.0, "req_stage_s.prefill": 2.0,
        "req_stage_s.emit": 0.5, "req_stage_n": 10,
        "host_s.step": 100.0, "host_s.device_wait": 90.0,
        "host_n.step": 1000, "steps": 1000}
CLOSE = {"req_stage_s.queue": 1.6, "req_stage_s.prefill": 5.0,
         "req_stage_s.emit": 0.52, "req_stage_n": 20,
         "host_s.step": 145.0, "host_s.device_wait": 130.5,
         "host_n.step": 1300, "steps": 1300}


def ctx(open_=OPEN, close=CLOSE, trace=None):
    return {"counters_open": open_, "counters_close": close, "trace": trace}


@pytest.mark.parametrize("stage,want", [("queue", 60.0), ("prefill", 300.0),
                                        ("emit", 2.0)])
def test_stage_mean_is_the_windows_seconds_over_its_requests(stage, want):
    assert spans.stage_mean_ms(ctx(), stage) == pytest.approx(want)


def test_stage_means_add_up_to_the_engines_mean_ttft():
    total = sum(spans.stage_mean_ms(ctx(), s)
                for s in ("queue", "prefill", "emit"))
    assert total == pytest.approx(362.0)


def test_host_ms_per_step_leaves_the_waits_out():
    # 45 s of steps, 40.5 s of them blocked, over 300 steps
    assert spans.host_ms_per_step(ctx()) == pytest.approx(15.0)
    assert spans.device_wait_share(ctx()) == pytest.approx(90.0)


def test_counters_that_did_not_move_give_none():
    same = ctx(close=OPEN)
    assert spans.stage_mean_ms(same, "queue") is None
    assert spans.host_ms_per_step(same) is None
    assert spans.device_wait_share(same) is None


def test_a_program_without_the_counters_gives_none():
    old = ctx(open_={"steps": 10}, close={"steps": 20})
    for stage in ("queue", "prefill", "emit"):
        assert spans.stage_mean_ms(old, stage) is None
    assert spans.host_ms_per_step(old) is None
    assert spans.device_wait_share(old) is None


def test_prefill_share_goes_by_the_programs_names():
    trace = {"module_s": {"jit_dyn_decode_multi": 3.0,
                          "jit_dyn_prefill_packed": 0.75,
                          "jit_dyn_prefill": 0.25, "jit_dyn_gather": 1.0}}
    assert spans.named_module_share(ctx(trace=trace), "prefill") \
        == pytest.approx(20.0)


@pytest.mark.parametrize("trace", [
    None, {}, {"module_s": {}},
    {"module_s": {"jit__unknown": 4.0}},      # the jits are not named
])
def test_prefill_share_is_none_without_named_programs(trace):
    assert spans.named_module_share(ctx(trace=trace), "prefill") is None


def test_every_new_metric_file_names_a_reader_that_runs():
    full = ctx(trace={"module_s": {"jit_dyn_prefill": 1.0,
                                   "jit_dyn_decode": 1.0}})
    for name in ("queue_wait_mean_ms", "prefill_flight_mean_ms",
                 "emit_delay_mean_ms", "sched_host_ms_per_step.chat",
                 "sched_host_ms_per_step.doc", "device_wait_share.chat",
                 "device_wait_share.doc", "prefill_dev_share"):
        value = spec.metric_reader("layer_metrics", name)(full)
        assert value is not None and value == value
