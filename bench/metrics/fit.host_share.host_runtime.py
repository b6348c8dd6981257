"""The fit loop's share of the window, read as ``fit.host_share``
reads it, in the threaded host runtime's cell, whose rate is an
end-to-end metric of its own."""
from bench.harness import load_reader

read = load_reader("fit.host_share")
