"""FLOPs from shapes against XLA's own count of the same functions,
compiled for the CPU at a small size. XLA also counts elementwise work
(bias adds, activations, the loss), which the model count leaves out, so
the model count must come within a few percent from below."""
import jax
import jax.numpy as jnp
import pytest

from bench import flops
from bench.reference import nets

CNN = {"name": "cnn", "arch": {"conv_filters": [32, 64, 64],
                               "conv_sizes": [3, 3, 3],
                               "conv_strides": [1, 1, 1], "hidden": 512}}
# the source's own trunk on 84x84x4 frames (8x8/4, 4x4/2, 3x3/1)
PAPER = {"name": "cnn", "arch": {"conv_filters": [32, 64, 64],
                                 "conv_sizes": [8, 4, 3],
                                 "conv_strides": [4, 2, 1], "hidden": 512}}
CASES = [(CNN, (9, 9, 3), 4), (PAPER, (84, 84, 4), 6)]


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return cost["flops"]


@pytest.mark.parametrize("policy,obs_shape,n_actions", CASES)
def test_forward_flops_match_xla(policy, obs_shape, n_actions):
    init, apply = nets.make(policy, obs_shape, n_actions)
    params = init(jax.random.key(0))
    batch = 16
    obs = jnp.zeros((batch,) + obs_shape)
    xla = _xla_flops(lambda p, o: apply(p, o), params, obs)
    ours = batch * flops.forward(policy, obs_shape, n_actions)
    assert 0.9 * xla <= ours <= xla


@pytest.mark.parametrize("policy,obs_shape,n_actions", CASES)
def test_backward_flops_match_xla(policy, obs_shape, n_actions):
    init, apply = nets.make(policy, obs_shape, n_actions)
    params = init(jax.random.key(0))
    batch = 16
    obs = jnp.zeros((batch,) + obs_shape)

    def loss(p, o):
        logits, value = apply(p, o)
        return logits.sum() + value.sum()
    xla_fb = _xla_flops(jax.grad(loss), params, obs)
    ours = batch * (flops.forward(policy, obs_shape, n_actions)
                    + flops.backward(policy, obs_shape, n_actions))
    assert 0.85 * xla_fb <= ours <= 1.02 * xla_fb


def test_train_step_counts_actor_learner_and_bootstrap():
    fwd = flops.forward(CNN, (9, 9, 3), 4)
    step = flops.train_step(CNN, (9, 9, 3), 4, alpha=5)
    assert step == pytest.approx(2 * fwd + flops.backward(CNN, (9, 9, 3), 4)
                                 + fwd / 5)
    # the paper's trunk on the 9x9 board: about 2.26 MFLOP a forward
    assert fwd == pytest.approx(2.26e6, rel=0.01)
