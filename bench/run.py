"""One run of one benchmark cell on the accelerator.

    python3 bench/run.py --workload qwen3-4b.longprompt --seed 7 \\
        --seconds 45 --trace 0

The cell, its configuration, its traffic mix and its metrics are looked
up by name in ``BENCHMARK.json``; each lives in a file of its own under
``bench/`` (``configs/``, ``traffic/``, ``cells/``, ``metrics/``,
``reference/``), so a new cell is new files and entries, not new code.

A run makes the weights on the device from ``--seed``, warms every
program shape its traffic uses, then serves closed-loop waves through
``BatchServer.serve`` for ``--seconds`` (the window closes when the wave
running at that moment returns), reads the device's peak memory, frees
the server and checks the served tokens against the float32 reference
(``check.py``). ``--trace 1`` profiles the window and reports the
per-layer metrics in place of the end-to-end ones. The last line of
standard output is the JSON result; without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints none.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# one fixed directory inside the checkout, for every run of every cell,
# holding every program however quick to compile, never evicted (the
# eviction pass fails every write on an entry it finds without its
# access-time file)
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import trace_reduce  # noqa: E402
from waves import Waves, padded_lengths  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GIB = 2 ** 30


class Failure(Exception):
    """The run cannot give a result; the message says why."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<mix>.json
    limits: dict            # bench/cells/<cell>.json
    end_to_end: list[dict]  # BENCHMARK.json metrics this cell reports
    per_layer: list[dict]


def _reports(metric: dict, cell: str, e2e: set[str] | None = None) -> bool:
    """Whether ``cell`` reports ``metric``: a listed cell, or (a per-layer
    metric with no list) a cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e is None or metric["moves"] in e2e


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Failure(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((BENCH / "cells" / f"{name}.json").read_text()),
        end_to_end=e2e,
        per_layer=[m for m in spec["per_layer"] if _reports(m, name, names)])


def family_of(config: dict):
    return importlib.import_module(f"reference.{config['family']}")


def program_config(config: dict):
    """The program's ArchConfig for ``config``, checked against it."""
    from repro.configs import get_config
    family = family_of(config)
    cfg = dataclasses.replace(get_config(config["arch"]),
                              **config.get("program", {}))
    for key, field_name in family.PROGRAM_FIELDS.items():
        want = config
        for part in key.split("."):
            want = want[part]
        if getattr(cfg, field_name) != want:
            raise Failure(f"program {field_name}={getattr(cfg, field_name)!r}"
                          f" but {config['arch']} states {key}={want!r}")
    return cfg


class CompileCounter:
    """Backend compilations (or persistent-cache loads) while active."""

    def __init__(self):
        self.count = 0

    def _on(self, event: str, secs: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def build_server(cell: Cell, seed: int, device):
    """The server with its weights drawn from ``seed``, every program
    shape of the cell's traffic compiled and run once."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import BatchServer, Request

    cfg = program_config(cell.config)
    mix = cell.traffic
    server = BatchServer(cfg, make_local_mesh(devices=[device]),
                         max_len=mix["max_len"], seed=seed % 2 ** 32)
    n = sum(x.size for x in jax.tree.leaves(server.params))
    if n != family_of(cell.config).param_count(cell.config):
        raise Failure(f"the program holds {n} parameters, the configuration "
                      f"{family_of(cell.config).param_count(cell.config)}")
    B = mix["clients"]
    widths = padded_lengths(mix)
    for w in widths:
        jax.block_until_ready(server.prefill_fn(
            server.params, jnp.zeros((B, w), jnp.int32)))
    server.serve([Request(i, np.ones(widths[0], np.int32), max_new=2)
                  for i in range(B)])
    return server


def serve_window(server, cell: Cell, seed: int, seconds: float,
                 trace_dir: str | None = None):
    """Closed-loop waves for ``seconds``; returns (records, window_s,
    compiles in the window)."""
    import jax
    from repro.launch.serve import Request

    waves = Waves(cell.traffic, cell.config["vocab_size"], seed)
    done: list[check.Served] = []
    with CompileCounter() as comp:
        if trace_dir:
            # host spans are the benchmark's own and the runtime's; the
            # Python tracer would slow every host call in the window
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_open = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            k = 0
            while True:
                t_send = time.perf_counter()
                with jax.profiler.TraceAnnotation("client"):
                    wave = waves.wave(k)
                    reqs = [Request(r.rid, r.prompt, max_new=r.max_new)
                            for r in wave]
                with jax.profiler.TraceAnnotation("serve"):
                    stats = server.serve(reqs)
                t_back = time.perf_counter()
                width = max(len(r.prompt) for r in wave)
                done += [check.Served(r.rid, r.prompt, r.max_new, width,
                                      stats["outputs"][r.rid],
                                      t_back - t_send) for r in wave]
                k += 1
                if t_back - t_open >= seconds:
                    break
        window_s = t_back - t_open
        if trace_dir:
            jax.profiler.stop_trace()
    return done, window_s, comp.count


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(cell: Cell, seed: int, seconds: float, trace: bool, device,
        peaks: dict, t_start: float = T_START) -> dict:
    """One run; returns the result object (``checks`` last)."""
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    server = build_server(cell, seed, device)
    setup_s = time.perf_counter() - t_start
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        done, window_s, compiles = serve_window(server, cell, seed, seconds,
                                                trace_dir)
        mem = device.memory_stats() or {}
        peak = mem.get("peak_bytes_in_use")
        del server
        gc.collect()

        family = family_of(cell.config)
        model_flops = sum(family.request_flops(cell.config, len(r.prompt),
                                               len(r.tokens)) for r in done)
        gen = sum(len(r.tokens) for r in done)
        lat = [r.latency for r in done]
        waves = lat[::cell.traffic["clients"]]
        print(f"window {window_s:.6f} s: {len(done)} requests, {gen} tokens "
              f"generated, {compiles} compilations; latency p50 "
              f"{np.percentile(lat, 50):.6f} s p90 "
              f"{np.percentile(lat, 90):.6f} s over {len(lat)} requests; "
              f"slowest wave {int(np.argmax(waves))} of {len(waves)}: "
              f"{max(waves):.6f} s; peak {peak} bytes", file=sys.stderr)

        summary = None
        if trace_dir:
            summary = trace_reduce.summarize(trace_reduce.load(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    t_check = time.perf_counter()
    picked = check.sample(done, seed, cell.traffic["clients"],
                          cell.limits["check_per_slot"])
    gaps = check.widest_gaps(family, cell.config, seed % 2 ** 32, picked)
    short = sum(len(r.tokens) != r.max_new for r in done)
    checks = {
        "max_logit_gap": {"value": max(gaps),
                          "limit": cell.limits["max_logit_gap"]},
        "short_replies": {"value": short, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(f"check: {len(picked)} requests, "
          f"{sum(len(r.tokens) for r in picked)} generated tokens against "
          f"the float32 reference in {time.perf_counter() - t_check:.3f} s",
          file=sys.stderr)

    if trace:
        ctx = SimpleNamespace(trace=summary, compiles_in_window=compiles,
                              window_s=window_s, model_flops=model_flops,
                              peak_flops=peaks["bf16_flops_per_s"],
                              chips=cell.chips)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {
            "gen_tok_s": gen / window_s,
            "req_latency_p90_s": float(np.percentile(lat, 90)),
            "peak_hbm_gib": peak / GIB if peak else None,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": cell.chips, "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(done), "failed": short,
           "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": trace_reduce.top(summary.op_s),
                            "idle_gaps": trace_reduce.top(summary.gaps)}
    out["checks"] = checks
    return out


def accelerator(chips: int):
    """The devices the cell runs on, or a Failure without a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Failure(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise Failure(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return devices[:chips]


def peaks_of(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Failure(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        devices = accelerator(cell.chips)
        peaks = peaks_of(devices[0].device_kind)
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  devices[0], peaks)
    except Failure as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
