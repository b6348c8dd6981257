"""Share of a training window spent outside the runtime's segment
calls: the fit loop's state capture, metric stream, checkpoint save and
restore. 100 * (1 - sum of the segments' ``wall_time`` / window)."""


def read(record):
    if record.get("program_s") is None or not record.get("window_s"):
        return None
    return 100.0 * (1.0 - record["program_s"] / record["window_s"])
