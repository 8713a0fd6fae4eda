"""Share of the decoded batch slots whose token a request keeps:
``tokens_kept`` over ``slots`` (rows × the wave's longest ``max_new``)
of ``BatchServer.counters`` across the window, in %. A static batch
decodes every row to the wave's longest reply."""


def read(run):
    c = getattr(run, "counters", None)
    if not c or not c.get("slots"):
        return None
    return 100.0 * c["tokens_kept"] / c["slots"]
