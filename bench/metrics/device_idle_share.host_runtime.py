"""The device's idle share, read as ``device_idle_share.train``
reads it, in the threaded host runtime's cell, whose rate is an
end-to-end metric of its own."""
from bench.harness import load_reader

read = load_reader("device_idle_share.train")
