"""The program's trace names (``repro.core.spans``): the scopes in the
compiled programs' op metadata, the fit loop's spans in order, and the
host runtime's per-phase totals."""
import queue
import re
import sys
import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import engine, spans
from repro.core.engine import HTSConfig
from repro.core.host_runtime import HostConfig
from repro.core.trainer import Trainer
from repro import models
from repro.envs import catch
from repro.optim import rmsprop

ALPHA, N_ENVS = 5, 4


def _make(name, **kwargs):
    env1 = catch.make()
    cfg = HTSConfig(alpha=ALPHA, n_envs=N_ENVS, seed=3)
    policy = models.get_policy("mlp", env1)
    params = policy.init(jax.random.key(0))
    return engine.make_runtime(name, env1, policy.apply, params,
                               rmsprop(7e-4, eps=1e-5), cfg, **kwargs)


def _op_paths(lowered) -> list:
    """The ``op_name`` metadata of a compiled program's ops: the path of
    scopes and primitives that a profiler trace shows as ``tf_op``."""
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


def _under(paths, outer, inner) -> bool:
    """Whether some op's path has scope ``inner`` inside ``outer``."""
    for p in paths:
        parts = p.split("/")
        if outer in parts and inner in parts[parts.index(outer) + 1:]:
            return True
    return False


def test_segment_and_drain_programs_carry_every_scope():
    rt = _make("sharded", mesh=Mesh(np.array(jax.devices()[:1]), ("data",)))
    rt.run(1)                           # builds the drain program too
    paths = _op_paths(rt._program(2).lower(rt.carry))
    for outer, inner in ((spans.ROLLOUT, spans.ACTOR_FORWARD),
                         (spans.ROLLOUT, spans.ENV_STEP),
                         (spans.LEARNER, spans.PER_ENV_GRAD),
                         (spans.LEARNER, spans.GRAD_REDUCE),
                         (spans.LEARNER, spans.OPTIMIZER)):
        assert _under(paths, outer, inner), (outer, inner)
    assert not any(spans.DRAIN in p.split("/") for p in paths)
    dg, _, _, buf, _ = rt.carry
    drain = _op_paths(rt._final_prog.one_pass.lower(dg, buf,
                                                     jnp.asarray(False)))
    for inner in (spans.PER_ENV_GRAD, spans.GRAD_REDUCE, spans.OPTIMIZER):
        assert _under(drain, spans.DRAIN, inner), inner


def test_fit_opens_its_spans_in_order(tmp_path, monkeypatch):
    opened = []
    real = spans.span

    def recording(name, **args):
        opened.append(name)
        return real(name, **args)

    monkeypatch.setattr(spans, "span", recording)
    rt = _make("mesh")
    trainer = Trainer(rt, checkpoint_dir=str(tmp_path), ckpt_every=1)
    trainer.fit(2)
    seg = [spans.FIT_SEGMENT, spans.FIT_STREAM, spans.FIT_CAPTURE,
           spans.FIT_SAVE, spans.FIT_SAVE_GATHER]
    assert opened == seg * 2
    opened.clear()
    trainer.fit(3, resume=True)
    assert opened == [spans.FIT_RESUME] + seg
    assert set(opened) <= set(spans.SPANS)


def test_host_profile_holds_the_waits_and_dispatch_counts():
    intervals = 3
    rt = _make("host", host=HostConfig(profile=True, n_actors=2))
    rt.run(intervals)
    prof = rt.profile
    assert set(prof) == {"env_step_wait", "learner_drain", "actor_wait",
                         "actor_dispatches", "step_dispatches"}
    # a dispatch takes at most one request per env, and each env's next
    # request waits on the last: alpha to alpha * n_envs per interval
    for key in ("actor_dispatches", "step_dispatches"):
        assert ALPHA * intervals <= prof[key] <= ALPHA * N_ENVS * intervals
        assert prof[key] == int(prof[key])
    per = (prof["actor_dispatches"] + prof["step_dispatches"]) / intervals
    assert 2 * ALPHA <= per <= 2 * ALPHA * N_ENVS
    assert all(prof[k] >= 0 for k in ("env_step_wait", "learner_drain",
                                      "actor_wait"))
    off = _make("host")
    off.run(intervals)
    assert off.profile == {}


def test_phase_counts_are_exact_across_threads():
    phases = spans.Phases(True)
    n_threads, n_each = 16, 2000
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(
            target=lambda: [phases.count("x") for _ in range(n_each)])
            for _ in range(n_threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(before)
    assert phases.totals == {"x": n_threads * n_each}
    off = spans.Phases(False)
    off.count("x")
    with off.timed("y"), off.span(spans.HOST_ACTOR_DISPATCH):
        pass
    q = queue.Queue()
    q.put("item")
    assert off.get("z", q) == "item"
    assert off.totals == {}
