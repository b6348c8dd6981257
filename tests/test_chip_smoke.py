"""CPU rehearsal of chip_smoke.py: its phase functions run the main path
(fit + bit-identical rerun, host-vs-fused finding, serving) at a tiny
size, and ``main()`` refuses to run anywhere but on a TPU."""
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_phases_run_small_on_cpu(tmp_path, capsys):
    trained = chip_smoke.train_phase(n_envs=8, intervals=2, ckpt_every=1,
                                     runs=tmp_path / "runs")
    assert trained["finite"]
    assert trained["first_s"] > 0 and trained["steady_sps"] > 0
    # the phase-2 checkpoints are where serving loads its params from
    assert sorted(p.name for p in (tmp_path / "runs" / "train").iterdir()
                  if p.suffix == ".npz") == ["step_00000001.npz",
                                             "step_00000002.npz"]

    host = chip_smoke.host_phase(n_envs=8, intervals=2)
    assert set(host) == {"equal", "max_abs_diff"}
    assert host["max_abs_diff"] < 1e-3

    served = chip_smoke.serve_phase(trained["session"], pairs=8)
    assert served == {"answered": 16, "identical": True}

    out = capsys.readouterr().out
    assert "bit-identical rerun: True" in out
    assert "every param finite: True" in out


def test_spec_is_the_papers_conv_policy_at_full_width():
    spec = chip_smoke.train_spec()
    assert spec.runtime.name == "sharded" and spec.batch.n_replicas == 1
    assert spec.hts == {"alpha": 5, "n_envs": 512, "staleness": 1,
                        "env_backend": "device", "seed": 0}
    from repro import api
    session = api.build(spec.replace(hts={**spec.hts, "n_envs": 8}))
    ccfg = session.policy.config
    assert (ccfg.conv_filters, ccfg.hidden) == ((32, 64, 64), 512)
    n = sum(x.size for x in jax.tree.leaves(session.params))
    assert n == 354_309


def test_main_refuses_cpu_and_names_the_platform(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""      # no result line


def test_script_exits_nonzero_without_a_tpu():
    """The script as a user runs it: no result line, the platform
    named, and nothing imported that forces host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, runpy; "
         "sys.argv = ['chip_smoke.py']; "
         "runpy.run_path('chip_smoke.py', run_name='__main__')"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "platform 'cpu'" in r.stderr


def test_chip_path_does_not_import_the_dry_run():
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "print('repro.launch.dryrun' in sys.modules)"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "False"


def test_four_chip_phase_on_four_virtual_devices():
    """The --four-chips path on 4 forced host devices (a fresh process:
    the device count is fixed when JAX starts). Rollouts must agree
    across replica counts; the params comparison is reported."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c",
         "import json, chip_smoke; "
         "out = chip_smoke.four_chip_phase(n_envs=16, intervals=2); "
         "print(json.dumps(out))"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "4-replica mesh devices: [(0, 'cpu'), (1, 'cpu'), (2, 'cpu'), " \
           "(3, 'cpu')]" in r.stdout
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["streams_equal"] == [True, True]
    assert out["max_abs_diff"] < 1e-3
