"""Mean device time one run of the jitted decode step (``_decode``)
spends in the model's ``mlp`` scope (``layers.mlp_fwd``), from the
operations' self times in the traced window."""


def read(run):
    scopes = getattr(run, "scopes", None)
    return scopes.per_run_ms("_decode", "mlp") if scopes else None
