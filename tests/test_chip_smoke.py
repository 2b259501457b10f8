"""chip_smoke.py rehearsed on the CPU (ISSUE 21 satellite).

The smoke proves on the chip that cli.train and cli.serve run end to end;
these tests keep the script itself from rotting between chip runs: the same
phases (data -> cli.train -> cli.serve -> score check -> drain, and the
four-device mesh comparison) at a tiny --rows on the CPU backend, and the
refusals the chip contract asks for — no accelerator, no repo beside it.
Every run is a child process, as on the chip: the script never imports JAX.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _start(argv, script=_SMOKE, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_overrides)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_ENABLE_X64", None)
    return subprocess.Popen([sys.executable, script] + argv, env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, cwd=os.path.dirname(script))


def _finish(child):
    try:
        out, err = child.communicate(timeout=600)
    except subprocess.TimeoutExpired:
        child.kill()
        child.communicate()
        raise
    return subprocess.CompletedProcess(child.args, child.returncode, out, err)


def _run(argv, script=_SMOKE, **env_overrides):
    return _finish(_start(argv, script, **env_overrides))


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    """The one-device and the four-device rehearsal, each its own chain of
    child processes in its own work directory, started together: {multichip:
    (work directory, completed process)}."""
    started = {}
    for multichip in (False, True):
        work = tmp_path_factory.mktemp("rehearsal") / "work"
        argv = ["--rehearse-cpu", "--rows", "40000", "--work-dir", str(work)]
        started[multichip] = (
            work, _start(argv + (["--multichip"] if multichip else [])))
    return {multichip: (work, _finish(child))
            for multichip, (work, child) in started.items()}


@pytest.mark.parametrize("multichip", [False, True],
                         ids=["one-device", "four-devices"])
def test_rehearsal_runs_every_phase_on_cpu(rehearsals, multichip):
    """--rehearse-cpu drives every phase through the real entry points at
    40,000 rows and ends with the result line — naming the CPU, never the
    chip."""
    work, r = rehearsals[multichip]
    assert r.returncode == 0, r.stderr[-3000:]
    assert '"platform": "tpu"' not in r.stdout
    assert "rows cut to 40000 of 1000209" in r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4 if multichip else 1}}
    if multichip:
        # the comparison and nothing else: no serving phase
        assert "serve:" not in r.stdout
        assert "objective histories agree" in r.stdout
        assert "static transfers precede the second outer" in r.stdout
        assert (work / "model-mesh" / "best").is_dir()
        assert (work / "model-single" / "best").is_dir()
    else:
        assert "batch of 1324 (two buckets): 1324 rows" in r.stdout
        assert "avro decoder files {'native': 9}" in r.stdout
        assert (work / "model" / "best" / "model-metadata.json").exists()


def test_plain_run_without_accelerator_fails_before_data():
    """As the driver runs it — no arguments — on a machine where JAX finds
    only the CPU: non-zero, quickly, no result line, no data written."""
    t0 = time.monotonic()
    r = _run([])
    assert r.returncode not in (0, 2), (r.returncode, r.stderr[-2000:])
    assert time.monotonic() - t0 < 120
    assert "no accelerator" in r.stderr
    assert '"ok"' not in r.stdout and "data:" not in r.stdout


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    there is no program to prove: exit 2, no result line."""
    alone = shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    r = _run([], script=str(alone))
    assert r.returncode == 2
    assert "not beside this script" in r.stderr and r.stdout == ""


def test_smoke_config_is_the_benchmark_glmix_config():
    """The --config JSON the smoke hands cli.train IS the
    GameTrainingConfig the benchmark trains `glmix-ml20m` with, field for
    field: the builder's own object (built at the configuration's rehearsal
    size, which changes the data and no training field)."""
    from benchmark.builders import game_fit
    from photon_ml_tpu.game import GameTrainingConfig
    smoke = _load("chip_smoke_under_test", _SMOKE)
    with open(os.path.join(_REPO, "benchmark", "configs",
                           "glmix-ml20m.json")) as f:
        config = json.load(f)
    config.update(config["rehearsal"])
    want = game_fit.build(config, 11, 1).cfg
    got = GameTrainingConfig.from_json(json.dumps(smoke._glmix_config(11)))
    assert got == want
