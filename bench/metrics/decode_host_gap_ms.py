"""Idle device time per decode step that falls inside the decode loop's
host spans of ``BatchServer.serve`` (every ``serve.*`` span but
``serve.prefill``: keys, dispatch, the sampling sync, collecting), over
the decode steps ``BatchServer.counters`` counted in the window."""


def read(run):
    try:
        from repro.launch.serve import SPAN_PREFILL, SPANS
    except ImportError:             # a program without the spans
        return None
    gaps = getattr(run, "program_gaps", None)
    steps = (getattr(run, "counters", None) or {}).get("decode_steps")
    loop = [s for s in SPANS if s != SPAN_PREFILL]
    if not gaps or not steps or not any(s in gaps for s in loop):
        return None
    return 1e3 * sum(gaps.get(s, 0.0) for s in loop) / steps
