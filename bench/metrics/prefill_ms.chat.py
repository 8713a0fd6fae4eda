"""Mean device time of one run of the jitted prefill (``_prefill``) in
the traced window, in the chat cell, where it is the SSD prefill: the
SSM layers' projections and their token-by-token recurrence."""

from statistics import fmean


def read(run):
    runs = run.trace.module_runs("_prefill") if run.trace else []
    return 1e3 * fmean(runs) if runs else None
