"""Device time of the monitoring forward (scope ``monitor_forward``: the
loss the step reports, a forward pass outside the per-shard loop), per
step and chip, by ``perf/scopes.py``.  Moves ``tokens_per_s``."""
from perf import scopes


def read(rec):
    return scopes.per_step_ms(rec, "monitor_forward")
