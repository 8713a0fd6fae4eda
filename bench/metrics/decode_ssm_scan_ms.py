"""Mean device time one run of the jitted decode step (``_decode``)
spends in the model's ``ssm_scan`` scope (``ssm.ssm_decode``'s
recurrence: the SSD state read, update and readout, and the skip term),
from the operations' self times in the traced window."""


def read(run):
    scopes = getattr(run, "scopes", None)
    return scopes.per_run_ms("_decode", "ssm_scan") if scopes else None
