"""Without a TPU the command exits non-zero and prints no result."""

import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]


def test_command_refuses_to_run_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    p = subprocess.run(
        [sys.executable, "-m", "tcqbench", "--workload",
         "mathoverflow.adhoc", "--seed", "5", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
