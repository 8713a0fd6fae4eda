"""Mean device time one run of the jitted prefill (``_prefill``) spends
in the model's ``ssm_scan`` scope (``ssm.ssm_fwd_with_cache``'s
token-by-token recurrence, with its repeats of B and C, its final state
and the skip term), from the operations' self times in the traced
window, in the chat cell."""


def read(run):
    scopes = getattr(run, "scopes", None)
    return scopes.per_run_ms("_prefill", "ssm_scan") if scopes else None
