"""Milliseconds per checkpoint save, read as ``fit.save_ms`` reads it,
in the threaded host runtime's cell, whose rate is an end-to-end metric
of its own."""
from bench.harness import load_reader

read = load_reader("fit.save_ms")
