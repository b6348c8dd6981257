"""Device time of the gradient's reduction per interval: the self time
of the ops under the program's ``hts.learner/grad_reduce`` scope and
every scope of the program's inside it (the per-env gradients' tree
sum, the all-gather across replicas), over the window's intervals, the
largest over devices (``bench.spans``)."""
from bench.spans import scope_ms_per_interval


def read(record):
    return scope_ms_per_interval(record, "hts.learner/grad_reduce")
