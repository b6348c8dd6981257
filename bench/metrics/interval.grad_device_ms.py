"""Device time of the per-env gradient per interval: the self time of
the ops under the program's ``hts.learner/per_env_grad`` scope in the
fused segment program, over the window's intervals, the largest over
devices (``bench.spans``)."""
from bench.spans import scope_ms_per_interval


def read(record):
    return scope_ms_per_interval(record, "hts.learner/per_env_grad")
