"""The whole step's share of the chip's peak, read as ``step_mfu``
reads it, in the threaded host runtime's cell, whose rate is an
end-to-end metric of its own."""
from bench.harness import load_reader

read = load_reader("step_mfu")
