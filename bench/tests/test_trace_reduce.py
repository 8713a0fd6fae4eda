"""The trace reduction, on a small trace recorded on a TPU v5e (a tiny
bf16 model serving two waves of two requests) and on made-up events."""

from pathlib import Path
from types import SimpleNamespace

import pytest

import run
import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.summarize(trace_reduce.load(DATA))


def test_window_busy_and_gaps_add_up(summary):
    assert 0 < summary.busy_s < summary.window_s
    assert summary.busy_s + sum(summary.gaps.values()) == \
        pytest.approx(summary.window_s, rel=1e-6)
    assert sum(summary.op_s.values()) == pytest.approx(summary.busy_s,
                                                       rel=1e-3)


def test_program_runs_are_found_by_name(summary):
    assert len(summary.module_runs("_prefill")) == 2
    assert len(summary.module_runs("_decode")) == 20
    assert all(0 < s < summary.window_s for s in
               summary.module_runs("_decode"))


def test_gaps_are_named_by_host_events(summary):
    assert "serve" in summary.gaps
    assert "np.asarray(jax.Array)" in summary.gaps


def test_readers_on_the_recorded_trace(summary):
    ctx = SimpleNamespace(trace=summary, compiles_in_window=0,
                          window_s=summary.window_s, model_flops=1e9,
                          peak_flops=197e12, chips=1)
    decode = run.load_reader("decode_step_ms")(ctx)
    assert decode == pytest.approx(
        1e3 * sum(summary.module_runs("_decode")) / 20)
    idle = run.load_reader("device_idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - summary.busy_s /
                                        summary.window_s))
    assert 0 < run.load_reader("mfu")(ctx) < 100


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def test_self_time_leaves_out_nested_operations():
    got = dict(trace_reduce._self_times([
        _ev("loop", 0, 100), _ev("a", 10, 30), _ev("b", 50, 20),
        _ev("c", 200, 5)]))
    assert got == {"loop": 50e-9, "a": 30e-9, "b": 20e-9, "c": 5e-9}


def test_host_label_is_the_innermost_event():
    events = [_ev("serve", 0, 100), _ev("sync", 20, 10), _ev("client", 150, 10)]
    assert trace_reduce._host_labels(events, [5, 25, 50, 120, 155]) == [
        "serve", "sync", "serve", "(no host event)", "client"]


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 9), (0, 3), (2, 4), (9, 12)]) == [
        (0, 4), (5, 12)]


def test_op_names_drop_layouts():
    assert trace_reduce.op_name(
        "%f = bf16[4,9728]{1,0:T(4,128)(2,1)S(1)} fusion(x)") == \
        "%f = bf16[4,9728] fusion(x)"
