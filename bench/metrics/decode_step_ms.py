"""Mean device time of one run of the jitted decode step (``_decode``)
in the traced window."""

from statistics import fmean


def read(run):
    runs = run.trace.module_runs("_decode") if run.trace else []
    return 1e3 * fmean(runs) if runs else None
