"""Mean device time one run of the jitted decode step (``_decode``)
spends in the model's ``attn`` scope (``layers.attention_decode``: the
projections, the KV-cache write and repeat, attention over the cache),
from the operations' self times in the traced window."""


def read(run):
    scopes = getattr(run, "scopes", None)
    return scopes.per_run_ms("_decode", "attn") if scopes else None
