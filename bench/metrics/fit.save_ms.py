"""Milliseconds per checkpoint save in a training window: the seconds
of the program's ``fit.save`` span (``Trainer._save``: the checkpoint's
write and prune) over its count (``bench.spans``)."""


def read(record):
    save = ((record.get("trace") or {}).get("spans") or {}).get("fit.save")
    if not save or not save["n"]:
        return None
    return 1e3 * save["s"] / save["n"]
