"""The whole training step's share of the chips' peak: model FLOPs per
env step (``bench.flops.train_step``, from shapes; nothing recomputed
counts) times env steps per second, over chips times the peak bf16
FLOP/s of the device kind (``bench/peaks.json``)."""
from bench import device


def read(record):
    if not record.get("flops_per_step") or not record.get("env_steps_per_s"):
        return None
    peak = device.peaks(record["device_kind"])["bf16_flops_per_s"]
    return 100.0 * (record["flops_per_step"] * record["env_steps_per_s"]
                    / (record["chips"] * peak))
