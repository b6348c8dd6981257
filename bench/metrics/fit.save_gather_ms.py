"""Milliseconds per checkpoint gather in a training window: the seconds
of the program's ``fit.save_gather`` span (``checkpoint/io.save``: the
leaves' move to the host, inside ``fit.save``) over its count
(``bench.spans``). A program without the span reads nothing."""


def read(record):
    gather = ((record.get("trace") or {}).get("spans")
              or {}).get("fit.save_gather")
    if not gather or not gather["n"]:
        return None
    return 1e3 * gather["s"] / gather["n"]
