"""Device time of the rollout per interval: the self time of the ops
under the program's ``hts.rollout`` scope (the actors' forward and
sampling, the device env's step), over the window's intervals, the
largest over devices (``bench.spans``)."""
from bench.spans import scope_ms_per_interval


def read(record):
    return scope_ms_per_interval(record, "hts.rollout")
