"""Per-layer profile of one traced window of a benchmark cell.

    python3 bench/profile_layers.py --workload qwen3-4b.longprompt \\
        --seed 7 --seconds 40 [--keep DIR]

Builds and warms the server as ``bench/run.py`` does, compiles the text
of every program shape the traffic uses (before the window, so no
compilation falls inside it), serves one traced window through
``run.serve_window`` and reduces its trace three ways: by program
(``trace_reduce``), by the model's layer scopes within each program and
by ``BatchServer.serve``'s host spans (``scopes``). With the server's
work counters across the window, that is what the readers of the
per-layer metrics that need the program's own marks read
(``LAYER_METRICS``, besides the cell's own per-layer metrics).

The last line of standard output is a JSON object: the device, the
window, the generated tokens per second of the traced window, every
metric that has a reading, the counters, the device time of each step
by scope with the three largest operations of ``(unscoped)``, and the
idle time by host span. ``--keep DIR`` also leaves the trace and the
programs' compiled texts (``hlo.json.gz``) in ``DIR``. Without a TPU
it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import gzip
import json
import shutil
import sys
import tempfile
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import run
import scopes
import trace_reduce
from waves import padded_lengths

LAYER_METRICS = ("decode_attn_ms", "decode_mlp_ms",
                 "prefill_attn_ms.longprompt", "decode_host_gap_ms",
                 "decode_slot_use", "prefill_token_use")
HLO_FILE = "hlo.json.gz"
UNSCOPED_TOP = 12       # operations of ``(unscoped)`` itemised per step


def compiled_texts(server, cell) -> list[str]:
    """The compiled text of the prefill at every padded width of the
    cell's traffic and of the decode step, lowered from arguments like
    the ones ``serve`` passes (so the same executables)."""
    import jax.numpy as jnp
    B = cell.traffic["clients"]
    texts, cache = [], None
    for w in padded_lengths(cell.traffic):
        tokens = jnp.zeros((B, w), jnp.int32)
        texts.append(server.prefill_fn.lower(server.params, tokens)
                     .compile().as_text())
        if cache is None:
            _, cache = server.prefill_fn(server.params, tokens)
    texts.append(server.decode_fn.lower(
        server.params, cache, jnp.zeros((B, 1), jnp.int32),
        jnp.int32(0)).compile().as_text())
    return texts


def _op_names(texts: list[str], part: str) -> dict[str, str]:
    """Instruction name -> its ``op_name``, in the compiled texts of the
    programs whose name has ``part``."""
    out: dict[str, str] = {}
    for text in texts:
        if part in scopes._MODULE.search(text).group(1):
            for line in text.splitlines():
                m = scopes._INSTRUCTION.match(line)
                op = scopes._OP_NAME.search(line)
                if m and op:
                    out.setdefault(m.group(1), op.group(1))
    return out


def profile(cell, seed: int, seconds: float, device, peaks: dict,
            keep: str | None = None) -> dict:
    """One traced window of ``cell``; returns the result object."""
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import SPANS
    from repro.models.layers import SCOPES
    enable_compile_cache()

    server = run.build_server(cell, seed, device)
    texts = compiled_texts(server, cell)
    before = dataclasses.asdict(server.counters)
    trace_dir = keep or tempfile.mkdtemp(prefix="bench-trace-")
    try:
        done, window_s, compiles = run.serve_window(server, cell, seed,
                                                    seconds, trace_dir)
        counters = {k: v - before[k]
                    for k, v in dataclasses.asdict(server.counters).items()}
        del server
        gc.collect()
        prof = trace_reduce.load(trace_dir)
        summary = trace_reduce.summarize(prof)
        by_scope = scopes.attribute(prof, texts, SCOPES)
        gaps = scopes.host_gaps(prof, SPANS)
    finally:
        if keep is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if keep:
        with gzip.open(Path(keep) / HLO_FILE, "wt") as f:
            json.dump(texts, f)

    family = run.family_of(cell.config)
    gen = sum(len(r.tokens) for r in done)
    ctx = SimpleNamespace(
        trace=summary, compiles_in_window=compiles, window_s=window_s,
        model_flops=sum(family.request_flops(cell.config, len(r.prompt),
                                             len(r.tokens)) for r in done),
        peak_flops=peaks["bf16_flops_per_s"], chips=cell.chips,
        scopes=by_scope, program_gaps=gaps, counters=counters)
    names = [m["name"] for m in cell.per_layer]
    names += [n for n in LAYER_METRICS if n not in names]
    metrics = {n: v for n in names
               if (v := run.load_reader(n)(ctx)) is not None}
    steps = {}
    for part in ("_prefill", "_decode"):
        progs = [p for p in by_scope.runs if part in p]
        op_names = _op_names(texts, part)
        unscoped = by_scope.top_ops(part, scopes.UNSCOPED, n=None)
        by_name: dict[str, float] = defaultdict(float)
        for op, secs in unscoped:
            by_name[op_names.get(op.split(" ", 1)[0], "")] += secs
        steps[part] = {
            "runs": sum(by_scope.runs[p] for p in progs),
            "module_s": sum(by_scope.module_s[p] for p in progs),
            "scope_s": by_scope.by_scope(part),
            "unscoped_by_op_name": trace_reduce.top(by_name, None),
            "unscoped_top": [[op, secs, op_names.get(op.split(" ", 1)[0], "")]
                             for op, secs in unscoped[:UNSCOPED_TOP]],
            "unknown_ops": sum(by_scope.unknown.get(p, 0) for p in progs)}
    return {"device": {"platform": device.platform,
                       "kind": device.device_kind, "count": cell.chips,
                       "busy_s": summary.busy_s,
                       "window_s": summary.window_s},
            "window_s": window_s, "gen_tok_s": gen / window_s,
            "metrics": metrics, "counters": counters, "steps": steps,
            "host_spans": gaps}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="leave the trace and compiled texts here")
    args = ap.parse_args(argv)
    try:
        cell = run.load_cell(args.workload)
        devices = run.accelerator(cell.chips)
        out = profile(cell, args.seed, args.seconds, devices[0],
                      run.peaks_of(devices[0].device_kind), args.keep)
    except run.Failure as e:
        print(f"profile: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
