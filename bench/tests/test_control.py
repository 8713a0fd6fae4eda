"""The control comes out not correct: the reference computed with
float8 matrix products, in place of the bf16 program, at a size a test
run holds. On the chip, ``bench/calibrate.py`` reads the same two
numbers at each cell's own size."""

import jax
import pytest

import check
import run
import tiny

SEEDS = (1, 2, 2 ** 31 + 7)


@pytest.mark.parametrize("config", [tiny.DENSE], ids=["dense"])
def test_control_reads_far_above_the_program(config):
    cell = tiny.cell(config)
    family = run.family_of(cell.config)
    prog, ctrl = [], []
    for seed in SEEDS:
        server = run.build_server(cell, seed, jax.devices()[0])
        # every request of a one-second window, so that each seed reads
        # enough tokens however few waves a slow machine finishes
        done, _, _ = run.serve_window(server, cell, seed, 1.0)
        picked = check.sample(done, seed, cell.traffic["clients"], 10 ** 6)
        prog.append(max(check.widest_gaps(family, cell.config, seed,
                                          picked)))
        ctrl.append(max(check.widest_gaps(family, cell.config, seed, picked,
                                          control="fp8")))
    print(f"program {prog} control {ctrl}")
    assert min(ctrl) >= 3 * max(prog)
    assert max(prog) < tiny.LIMIT < min(ctrl)
