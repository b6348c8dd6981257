"""Device time of the device env's frame rendering per interval: the
self time of the ops under the program's ``hts.rollout/env_step/render``
scope (the frames of each step and of each auto-reset), over the
window's intervals, the largest over devices (``bench.spans``). A
program without that scope reads nothing."""
from bench.spans import scope_ms_per_interval


def read(record):
    return scope_ms_per_interval(record, "hts.rollout/env_step/render")
