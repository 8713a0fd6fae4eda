"""Readings that a cell's ``max_logit_gap`` limit is set from.

    python3 bench/calibrate.py --workload qwen3-4b.longprompt \\
        --seeds 1,2,3 --seconds 15 --control 3

For every seed, in one process: the cell's server with that seed's
weights serves its traffic for ``--seconds`` exactly as a run does, and
the widest logit gap of its served tokens is read against the float32
reference (the lower reading). For the first ``--control`` seeds, the
control is read on the same prompts and tokens: the reference computed
with float8 (e4m3) matrix products in the program's place, the step
below the configuration's bf16 (the upper reading). One JSON line per
seed goes to standard output. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run  # sets the compile cache and the import path first
import check


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="read the control on this many of the seeds")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    device = run.accelerator(cell.chips)[0]
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    family = run.family_of(cell.config)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        server = run.build_server(cell, seed, device)
        done, window_s, compiles = run.serve_window(server, cell, seed,
                                                    args.seconds)
        del server
        gc.collect()
        picked = check.sample(done, seed, cell.traffic["clients"],
                              cell.limits["check_per_slot"])
        t = time.perf_counter()
        prog = check.widest_gaps(family, cell.config, seed % 2 ** 32, picked)
        line = {"workload": cell.name, "seed": seed, "window_s": window_s,
                "requests": len(done), "compiles": compiles,
                "checked_requests": len(picked),
                "checked_tokens": sum(len(r.tokens) for r in picked),
                "program_gap": max(prog), "program_gaps": prog,
                "reference_s": time.perf_counter() - t}
        if i < args.control:
            ctrl = check.widest_gaps(family, cell.config, seed % 2 ** 32,
                                     picked, control="fp8")
            line |= {"control_gap": max(ctrl), "control_gaps": ctrl}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
