"""Off a TPU the runner exits non-zero and prints no result line."""
import os
import subprocess
import sys

import tiny
import harness

RUN = str(harness.BENCH / "run.py")


def test_runner_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, RUN, "--workload",
                        "xlstm-350m.fl.c1", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, timeout=300, cwd=str(harness.ROOT))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_cell_is_refused():
    import pytest
    with pytest.raises(harness.BenchError):
        harness.resolve_cell("no-such-cell")
