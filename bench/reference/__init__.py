"""Plain references for the benchmark's correctness check.

Everything here is written from the published descriptions (the HTS-RL
paper's policy trunk and update rule, the environments' rules) in
straightforward ``jax.numpy``. It imports nothing of the system under
test and takes nothing it made: weights come from ``nets.init`` with the
benchmark's seed, trajectories are compared, never trusted.
"""
