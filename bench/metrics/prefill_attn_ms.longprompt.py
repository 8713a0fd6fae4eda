"""Mean device time one run of the jitted prefill (``_prefill``) spends
in the model's ``attn`` scope (``layers.attention_fwd`` and the cache
write), from the operations' self times in the traced window, in the
long-prompt cell, where prefill sets latency."""


def read(run):
    scopes = getattr(run, "scopes", None)
    return scopes.per_run_ms("_prefill", "attn") if scopes else None
