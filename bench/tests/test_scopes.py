"""The split of a traced window by layer scope and by host span
(``scopes.py``), its readers, and the profile they read: made-up events
and compiled text, and a small trace recorded on a TPU v5e (the tiny
bf16 model of ``tiny.py`` serving one wave of three requests, recorded
by ``record_scoped_trace.py``)."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import run
import scopes
import trace_reduce

RECORDED = Path(__file__).resolve().parent / "data_scoped"
SCOPES = ("embed", "norm", "attn", "mlp", "moe", "ssm", "lm_head")
SPANS = ("serve.prefill", "serve.rng", "serve.decode", "serve.sample",
         "serve.collect")


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _line(name, events):
    return SimpleNamespace(name=name, events=events)


def _profile(host, modules, ops):
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[_line("python3", host)]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            _line("XLA Modules", modules), _line("XLA Ops", ops)])])


DECODE = """HloModule jit__decode, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.9 = f32[4]{0} multiply(f32[4]{0} %param_0, f32[4]{0} %param_0), metadata={op_name="jit(_decode)/while/body/closed_call/attn/mul"}
}

ENTRY %main.5 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %while.0 = f32[4]{0} while(f32[4]{0} %p), condition=%cond, body=%body, metadata={op_name="jit(_decode)/while"}
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %while.0), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_decode)/while/body/closed_call/attn/mlp/mul" source_file="x.py"}
  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %fusion.1), kind=kLoop, metadata={op_name="jit(_decode)/lm_head/norm/mul"}
  ROOT %copy.3 = f32[4]{0} copy(f32[4]{0} %fusion.2)
}
"""


def _prefill(width, scope):
    return f"""HloModule jit__prefill, is_scheduled=true

ENTRY %main.2 (p: f32[2,{width}]) -> f32[2,{width}] {{
  %p = f32[2,{width}]{{1,0}} parameter(0)
  ROOT %fusion.1 = f32[2,{width}]{{1,0}} fusion(f32[2,{width}]{{1,0}} %p), kind=kLoop, metadata={{op_name="jit(_prefill)/{scope}/dot_general"}}
}}
"""


def test_the_outermost_known_scope_names_an_operation():
    assert scopes.scope_of("jit(_decode)/while/body/closed_call/attn/mlp/dot",
                           SCOPES) == "attn"
    assert scopes.scope_of("jit(_decode)/lm_head/norm/mul", SCOPES) == \
        "lm_head"
    assert scopes.scope_of("jit(_decode)/while/body/dynamic_slice",
                           SCOPES) == scopes.UNSCOPED
    assert scopes.scope_of("params['blocks']['pos0']['attn']['wq']",
                           SCOPES) == scopes.UNSCOPED


def test_instructions_of_every_computation_with_their_scopes():
    ins = scopes.instructions(DECODE, SCOPES)
    assert {k: v[1] for k, v in ins.items()} == {
        "%param_0": scopes.UNSCOPED, "%mul.9": "attn", "%p": scopes.UNSCOPED,
        "%while.0": scopes.UNSCOPED, "%fusion.1": "attn",
        "%fusion.2": "lm_head", "%copy.3": scopes.UNSCOPED}
    assert ins["%fusion.2"][0].startswith("%fusion.2 = f32[4]{0} fusion(")


def test_op_names_come_from_the_named_programs_only():
    import profile_layers
    got = profile_layers._op_names([_prefill(8, "mlp"), DECODE], "_decode")
    assert got == {
        "%mul.9": "jit(_decode)/while/body/closed_call/attn/mul",
        "%while.0": "jit(_decode)/while",
        "%fusion.1": "jit(_decode)/while/body/closed_call/attn/mlp/mul",
        "%fusion.2": "jit(_decode)/lm_head/norm/mul"}


@pytest.fixture
def made_up():
    """A window holding one decode run, one prefill run (of the second of
    two prefill shapes, whose instruction names clash with each other's
    and with decode's), a run outside the window, and host spans."""
    host = [_ev("window", 0, 1000),
            _ev("serve.prefill", 0, 150), _ev("DevicePut", 5, 5),
            _ev("serve.sample", 300, 100), _ev("np.asarray", 310, 80),
            _ev("serve.collect", 400, 60), _ev("serve.rng", 460, 40),
            _ev("serve.decode", 500, 120)]
    modules = [_ev("jit__prefill(7)", 10, 100),
               _ev("jit__decode(9)", 600, 100),
               _ev("jit__decode(9)", 1100, 50)]
    ops = [_ev("%fusion.1 = f32[2,16]{1,0} fusion(f32[2,16]{1,0} %p)", 20, 80),
           _ev("%while.0 = f32[4]{0} while(f32[4]{0} %p)", 600, 80),
           _ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %while.0)", 610, 30),
           _ev("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %fusion.1)", 650, 20),
           _ev("%copy.3 = f32[4]{0} copy(f32[4]{0} %fusion.2)", 680, 15),
           _ev("%gone.4 = f32[4]{0} negate(f32[4]{0} %copy.3)", 695, 5),
           _ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %while.0)", 1110, 30)]
    texts = [_prefill(8, "mlp"), DECODE, _prefill(16, "attn")]
    return _profile(host, modules, ops), texts


def test_self_times_go_to_scopes_within_each_program_run(made_up):
    prof, texts = made_up
    got = scopes.attribute(prof, texts, SCOPES)
    assert got.runs == {"jit__prefill": 1, "jit__decode": 1}
    assert got.module_s == pytest.approx({"jit__prefill": 100e-9,
                                          "jit__decode": 100e-9})
    # decode: the loop's own 30 ns and the copy are (unscoped); the op
    # its text lacks is (unscoped) and counted as unknown
    assert got.by_scope("_decode") == pytest.approx({
        "attn": 30e-9, "lm_head": 20e-9, scopes.UNSCOPED: 50e-9})
    assert got.unknown == {"jit__decode": 1}
    # prefill: the run's operations read as the 16-wide program's
    assert got.by_scope("_prefill") == pytest.approx({"attn": 80e-9})
    assert got.per_run_ms("_decode", "attn") == pytest.approx(30e-6)
    assert got.per_run_ms("_decode", "mlp") is None
    assert got.per_run_ms("_other", "attn") is None
    assert [op for op, _ in got.top_ops("_decode", scopes.UNSCOPED)] == [
        "%while.0 = f32[4] while(f32[4] %p)",
        "%copy.3 = f32[4] copy(f32[4] %fusion.2)",
        "%gone.4 = f32[4] negate(f32[4] %copy.3)"]


def test_a_program_without_its_text_is_unscoped(made_up):
    prof, _ = made_up
    got = scopes.attribute(prof, [], SCOPES)
    assert set(got.by_scope("_decode")) == {scopes.UNSCOPED}
    assert got.unknown == {}
    assert got.per_run_ms("_decode", "attn") is None


def test_idle_gaps_are_shared_out_among_the_program_spans(made_up):
    prof, _ = made_up
    gaps = scopes.host_gaps(prof, SPANS)
    # busy: [20, 100) and [600, 700). Idle [0, 20) lies in
    # serve.prefill; idle [100, 600) runs from serve.prefill through
    # nothing, serve.sample (not the runtime's np.asarray inside it),
    # serve.collect, serve.rng and serve.decode; idle [700, 1000) lies
    # outside every span
    assert gaps == pytest.approx({"serve.prefill": 70e-9,
                                  "serve.sample": 100e-9,
                                  "serve.collect": 60e-9,
                                  "serve.rng": 40e-9,
                                  "serve.decode": 100e-9,
                                  scopes.NO_SPAN: 450e-9})
    summary = trace_reduce.summarize(prof)
    assert sum(gaps.values()) == pytest.approx(sum(summary.gaps.values()))


def test_nested_spans_give_way_to_the_innermost():
    pieces = scopes._pieces([(10, 90, "outer"), (20, 30, "a"),
                             (30, 50, "b"), (95, 200, "late")], 0, 100)
    assert pieces == [(0, 10, scopes.NO_SPAN), (10, 20, "outer"),
                      (20, 30, "a"), (30, 50, "b"), (50, 90, "outer"),
                      (90, 95, scopes.NO_SPAN), (95, 100, "late")]


# ------------------------------------------------------------- readers

def _summary(decode_runs):
    return trace_reduce.Summary(window_s=1.0, busy_s=0.9,
                                modules={"jit__decode(9)": [0.01] *
                                         decode_runs}, op_s={})


def _times(op_s, runs):
    return scopes.ScopeTimes(op_s=op_s, runs=runs, module_s={}, unknown={})


def test_readers_on_a_made_up_window():
    ctx = SimpleNamespace(
        trace=_summary(4),
        scopes=_times({("jit__decode", "attn", "a"): 0.02,
                       ("jit__decode", "attn", "b"): 0.02,
                       ("jit__decode", "mlp", "c"): 0.008,
                       ("jit__prefill", "attn", "d"): 1.5,
                       ("jit__prefill", "mlp", "e"): 0.5},
                      {"jit__decode": 4, "jit__prefill": 3}),
        program_gaps={"serve.prefill": 0.5, "serve.rng": 0.1,
                      "serve.sample": 0.6, "serve.collect": 0.2,
                      "serve.decode": 0.3, "(no host event)": 0.2},
        counters={"prompt_tokens": 755, "prefill_positions": 1000,
                  "decode_steps": 400, "slots": 3264, "tokens_kept": 1206})
    read = {n: run.load_reader(n)(ctx) for n in (
        "decode_attn_ms", "decode_mlp_ms", "prefill_attn_ms.longprompt",
        "decode_host_gap_ms", "decode_slot_use", "prefill_token_use")}
    assert read == pytest.approx({
        "decode_attn_ms": 10.0, "decode_mlp_ms": 2.0,
        "prefill_attn_ms.longprompt": 500.0, "decode_host_gap_ms": 3.0,
        "decode_slot_use": 100 * 1206 / 3264, "prefill_token_use": 75.5})


@pytest.mark.parametrize("ctx", [
    SimpleNamespace(trace=None),
    SimpleNamespace(trace=_summary(4), scopes=_times({}, {}),
                    program_gaps={"(no host event)": 0.1}, counters=None),
    SimpleNamespace(trace=_summary(0),
                    scopes=_times({("jit__decode", "(unscoped)", "a"): 1.0},
                                  {"jit__decode": 4}),
                    program_gaps={"serve.rng": 0.1}, counters={})],
    ids=["nothing", "no-marks", "no-decode-runs"])
def test_readers_find_nothing_and_say_nothing(ctx):
    for n in ("decode_attn_ms", "decode_mlp_ms", "prefill_attn_ms.longprompt",
              "decode_host_gap_ms", "decode_slot_use", "prefill_token_use"):
        assert run.load_reader(n)(ctx) is None, n


# ------------------------------------------------- the recorded trace

@pytest.fixture(scope="module")
def recorded():
    prof = trace_reduce.load(RECORDED)
    with gzip.open(RECORDED / "hlo.json.gz", "rt") as f:
        texts = json.load(f)
    return prof, texts


def _busy_in_runs(prof, part):
    """Seconds in which some operation runs inside the window's runs of
    the programs whose name has ``part``, and those runs' seconds."""
    t0, t1, _ = trace_reduce._window(prof)
    plane, = [p for p in prof.planes
              if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
    lines = {line.name: line for line in plane.lines}
    runs = [(r.start_ns, r.start_ns + r.duration_ns)
            for r in lines[trace_reduce.MODULES_LINE].events
            if part in r.name and t0 <= r.start_ns
            and r.start_ns + r.duration_ns <= t1]
    busy = trace_reduce._union(
        [(e.start_ns, min(e.start_ns + e.duration_ns, b))
         for e in lines[trace_reduce.OPS_LINE].events
         for a, b in runs if a <= e.start_ns < b])
    return (sum(b - a for a, b in busy) / 1e9,
            sum(b - a for a, b in runs) / 1e9)


def test_recorded_decode_splits_by_scope_and_adds_up(recorded):
    prof, texts = recorded
    got = scopes.attribute(prof, texts, SCOPES)
    decode = [p for p in got.runs if "_decode" in p]
    assert decode and got.runs[decode[0]] >= 3
    assert not got.unknown.get(decode[0])      # every op is in its text
    by = got.by_scope("_decode")
    assert {"attn", "mlp", "norm", "embed", "lm_head"} <= set(by)
    assert set(by) <= set(SCOPES) | {scopes.UNSCOPED}
    assert "attn" in got.by_scope("_prefill")
    # the scopes share out the time some operation of the step runs,
    # all of it; the rest of a run of a tiny program is the device
    # idling between its operations
    for part in ("_decode", "_prefill"):
        busy, runs = _busy_in_runs(prof, part)
        assert sum(got.by_scope(part).values()) == pytest.approx(busy,
                                                                 rel=1e-6)
        assert runs == pytest.approx(sum(
            s for p, s in got.module_s.items() if part in p))
        assert 0.9 * runs < busy <= runs


def test_recorded_decode_loop_gaps_fall_in_serve_spans(recorded):
    prof, _ = recorded
    gaps = scopes.host_gaps(prof, SPANS)
    assert set(gaps) <= set(SPANS) | {"(no host event)"}
    assert {"serve.sample", "serve.decode"} <= set(gaps)
    summary = trace_reduce.summarize(prof)
    assert sum(gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
