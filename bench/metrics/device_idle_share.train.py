"""1 - (union of device op intervals / traced window), mean over the
cell's chips, in a training window."""


def read(record):
    tr = record.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
