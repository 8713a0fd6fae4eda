"""The harness's data, its refusals, and its reading of BENCHMARK.json."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import run
from run import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_benchmark_json_keeps_to_its_form():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    n = 24                                  # the most cells a check holds
    assert (2 + 14 * n) * (SPEC["run_seconds"] + 60) + n * 180 + 1200 \
        <= 43200
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + \
        [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= set(CELLS)
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_has_its_files_and_metrics(cell):
    c = run.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
    assert 0 < c.limits["max_logit_gap"] and c.limits["check_per_slot"] >= 1
    run.program_config(c.config)            # sizes agree with the program


def test_metric_selection():
    q = run.load_cell("qwen3-4b.longprompt")
    assert {x["name"] for x in q.end_to_end} == {
        "gen_tok_s", "req_latency_p90_s", "peak_hbm_gib", "setup_s"}
    assert {x["name"] for x in q.per_layer} == {
        "compiles_in_window", "prefill_ms.longprompt", "decode_step_ms",
        "mfu", "device_idle_share"}


def test_readers_find_nothing_and_say_nothing():
    from types import SimpleNamespace
    empty = SimpleNamespace(trace=None, compiles_in_window=0, window_s=0.0,
                            model_flops=0.0, peak_flops=1.0, chips=1)
    for m in SPEC["per_layer"]:
        v = run.load_reader(m["name"])(empty)
        assert v is None or m["name"] == "compiles_in_window"


def test_unknown_device_kind_is_refused():
    with pytest.raises(run.Failure):
        run.peaks_of("TPU v99")
    assert run.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_no_result():
    p = _bench(ROOT, "--workload", CELLS[0], "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path, "--workload", CELLS[0], "--seed", "1",
               "--seconds", "1")
    assert p.returncode != 0 and p.stdout == ""

