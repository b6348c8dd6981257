"""The program's spans and scopes out of a trace (``bench.spans``), on
hand-made events and on two traces recorded on a TPU v5e:

* ``fit_n64.xplane.pb.gz`` -- the fused training cell at 64 envs, one
  checkpointed segment of two intervals resumed from a checkpoint,
  recorded before the program had spans or scopes;
* ``fit_n64_scoped.xplane.pb.gz`` -- the same run of the program with
  its spans and scopes: ``sharded`` at 64 envs, one ``Session.fit``
  resumed from a checkpoint, one segment of two intervals and its save.

The reduction the benchmark already had (``bench.trace.reduce``) must
read the first exactly as it did before the program was instrumented
(``fit_n64.reduce.json``)."""
import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench import harness, spans, trace

DATA = Path(__file__).parent / "data"


def _unpack(name, tmp_path):
    path = tmp_path / name.replace(".gz", "")
    with gzip.open(DATA / name) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.mark.parametrize("tf_op,chain", [
    ("jit(body)/while/body/closed_call/hts.learner/per_env_grad/"
     "vmap(transpose(jvp()))/dot_general:", "hts.learner/per_env_grad"),
    ("jit(body)/while/body/closed_call/hts.rollout/actor_forward/tanh:",
     "hts.rollout/actor_forward"),
    ("jit(scoped)/hts.drain/optimizer/sqrt:", "hts.drain/optimizer"),
    ("hts.learner/grad_reduce/add;hts.learner/div", "hts.learner/grad_reduce"),
    ("jit(body)/while/body/closed_call/hts.learner/per_env_grad/vmap(jvp())"
     "/hts.learner/per_env_grad/add:", "hts.learner/per_env_grad"),
    ("jit(body)/while/body/closed_call/iota:", spans.UNSCOPED),
    ("", spans.UNSCOPED),
])
def test_scope_chain_reads_the_scopes_along_the_path(tf_op, chain):
    assert spans.scope_chain(tf_op) == chain


def test_idle_split_takes_the_innermost_span():
    idle = [(0, 10), (20, 40), (50, 60)]
    program = [(0, 100, "fit.segment"), (25, 35, "fit.save"),
               (30, 32, "host.grad_dispatch")]
    got = trace.idle_split(idle, program)
    assert got == [{"fit.segment": 10},
                   {"fit.segment": 5 + 5, "fit.save": 8,
                    "host.grad_dispatch": 2},
                   {"fit.segment": 10}]
    assert trace.idle_split(idle, []) == [{trace.NO_SPAN: 10},
                                          {trace.NO_SPAN: 20},
                                          {trace.NO_SPAN: 10}]
    assert trace.idle_split([(0, 10)], [(5, 20, "fit.save")]) == [{
        trace.NO_SPAN: 5, "fit.save": 5}]


def _ops(*events):
    """Device ops as ``trace.load`` gives them: (start, end, HLO text,
    tf_op path)."""
    return {"ops": [(s, e, "%op = f32[] fusion()", tf) for s, e, tf in events],
            "modules": []}


def test_reduce_on_hand_made_planes():
    planes = {
        "devices": {
            "/device:TPU:0": _ops(
                (100, 200, "jit(body)/while"),
                (110, 150, "jit(body)/while/body/hts.learner/per_env_grad/a"),
                (150, 170, "jit(body)/while/body/hts.learner/optimizer/b"),
                (170, 190, "jit(body)/while/body/hts.rollout/env_step/c"),
                (600, 650, "jit(one)/hts.drain/per_env_grad/d"),
            ),
            "/device:TPU:1": _ops((100, 300, "x/hts.rollout/actor_forward/y"))},
        "host": [(0, 1000, "bench.window"), (50, 500, "fit.segment"),
                 (500, 700, "fit.save"), (700, 720, "fit.capture"),
                 (520, 540, "PjitFunction(body)"), (990, 1100, "fit.stream"),
                 (300, 310, "host.learner_drain")],
    }
    r = spans.reduce(planes)
    assert r["spans"]["fit.segment"] == {"s": pytest.approx(450e-9), "n": 1}
    assert r["spans"]["fit.stream"] == {"s": pytest.approx(10e-9), "n": 1}
    assert "bench.window" not in r["spans"]
    assert "PjitFunction(body)" not in r["spans"]
    dev0 = r["scopes"]["/device:TPU:0"]
    assert dev0 == pytest.approx({
        spans.UNSCOPED: 20e-9, "hts.learner/per_env_grad": 40e-9,
        "hts.learner/optimizer": 20e-9, "hts.rollout/env_step": 20e-9,
        "hts.drain/per_env_grad": 50e-9})
    assert r["scopes"]["/device:TPU:1"] == pytest.approx(
        {"hts.rollout/actor_forward": 200e-9})
    # device 0 idle: 0-100 (no span 0-50, segment 50-100), 200-600
    # (segment 200-500 less the drain at 300-310, save 500-600), 650-1000
    t = trace.reduce(planes)
    assert t["idle_by_span"] == pytest.approx({
        trace.NO_SPAN: (50 + 270) * 1e-9, "fit.segment": 340e-9,
        "host.learner_drain": 10e-9, "fit.save": 150e-9,
        "fit.capture": 20e-9, "fit.stream": 10e-9})
    # each gap is named by the span that holds most of it
    assert [n for n, _ in t["idle_gaps"]] == [
        "fit.segment", trace.NO_SPAN, trace.NO_SPAN]


def test_reduce_falls_back_to_the_trace_extent_without_a_window():
    planes = {"devices": {"/device:TPU:0": _ops((10, 20, "a"))},
              "host": [(0, 30, "fit.save")]}
    r = spans.reduce(planes)
    assert r["spans"] == {"fit.save": {"s": pytest.approx(30e-9), "n": 1}}
    assert trace.reduce(planes)["idle_by_span"] == pytest.approx(
        {"fit.save": 20e-9})


def test_trace_reduce_reads_the_recorded_trace_as_before(tmp_path):
    """The reduction and the per-layer readers the benchmark already had
    give, on the first recorded trace, the values they gave before the
    program had spans, but that its idle time is named by program spans
    only, of which it has none; the spans reader finds no span and no
    scope there, and its ops' self time is the device's busy time."""
    path = _unpack("fit_n64.xplane.pb.gz", tmp_path)
    want = json.loads((DATA / "fit_n64.reduce.json").read_text())
    planes = trace.load(path)
    got = json.loads(json.dumps(trace.reduce(planes)))
    assert got == want["reduce"]
    record = {"trace": trace.reduce(planes), "intervals": 2}
    for name, value in want["readers"].items():
        assert harness.load_reader(name)(record) == value, name
    r = spans.reduce(planes)
    assert r["spans"] == {}
    assert list(r["scopes"]["/device:TPU:0"]) == [spans.UNSCOPED]
    assert r["scopes"]["/device:TPU:0"][spans.UNSCOPED] == pytest.approx(
        got["busy_s"], rel=1e-12)
    assert list(got["idle_by_span"]) == [trace.NO_SPAN]
    assert got["idle_by_span"][trace.NO_SPAN] == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-9)


def test_read_the_scoped_trace(tmp_path):
    """Each scope's time is at most the device's busy time, and scopes
    and idle time split the window between them."""
    path = _unpack("fit_n64_scoped.xplane.pb.gz", tmp_path)
    planes = trace.load(path)
    lo, hi = trace.window(planes)
    r, t = spans.read(path), trace.reduce(planes)
    assert r == spans.reduce(planes)
    busy = t["busy_s"]
    scopes = r["scopes"]["/device:TPU:0"]
    assert all(0 < v <= busy for v in scopes.values())
    assert sum(scopes.values()) == pytest.approx(busy, rel=1e-9)
    for chain in ("hts.rollout/actor_forward", "hts.rollout/env_step",
                  "hts.learner/per_env_grad", "hts.learner/grad_reduce",
                  "hts.learner/optimizer", "hts.drain/per_env_grad"):
        assert scopes.get(chain, 0) > 0, chain
    for name in ("fit.resume", "fit.segment", "fit.stream", "fit.capture",
                 "fit.save"):
        assert r["spans"][name]["n"] == 1, name
    assert 0 < r["spans"]["fit.save"]["s"] < (hi - lo) * 1e-9
    idle = t["idle_by_span"]
    assert sum(idle.values()) == pytest.approx((hi - lo) * 1e-9 - busy,
                                               rel=1e-9)
    assert idle["fit.save"] > 0


def test_the_reader_knows_the_programs_names():
    """The reader keeps no copy of the program's names: its scopes are
    the program's ``SCOPES``, and a scope the program leaves out of them
    (the tree sum's paths, ``tree_halves`` and ``tree_strided``) is read
    under the scope around it."""
    from repro.core import spans as program
    assert spans.SCOPES == tuple(program.SCOPES)
    assert not set(spans.SCOPES) & set(program.SPANS)
    assert len(set(spans.SCOPES)) == len(spans.SCOPES)
    assert spans.scope_chain(
        "jit(body)/while/body/hts.learner/grad_reduce/add:"
    ) == "hts.learner/grad_reduce"
    want = ("hts.learner/grad_reduce/tree_halves"
            if program.TREE_HALVES in program.SCOPES
            else "hts.learner/grad_reduce")
    assert spans.scope_chain(
        "jit(body)/while/body/hts.learner/grad_reduce/tree_halves/add:"
    ) == want
    assert all(n.startswith(trace.PROGRAM_SPANS) for n in program.SPANS)
