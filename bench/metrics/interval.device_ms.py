"""Device time of the fused segment program per interval, from the
trace: on each device, the program (XLA module) that ran longest in the
window is the segment program; its time over the window's intervals,
the largest over devices."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("intervals"):
        return None
    per = [max(d["modules_s"].values()) for d in tr["devices"].values()
           if d["modules_s"]]
    if not per:
        return None
    return 1e3 * max(per) / record["intervals"]
