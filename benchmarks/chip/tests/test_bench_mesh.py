"""The four-chip cell on four virtual CPU devices, in a child process
(``mesh_child.py``; the device count is fixed when JAX starts): a whole
run at the tiny size is correct and each planted fault is caught; the
reference reads the same bits wherever its peers lie; the readers lower
the mesh step that ran and split its ops."""
import json
import os
import subprocess
import sys

import pytest

import tiny  # noqa: F401  (puts the benchmark's modules on the path)
import harness
from mesh_child import FAULTS

CHILD = harness.BENCH / "tests" / "mesh_child.py"


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(CHILD)], capture_output=True,
                       text=True, env=env, timeout=900, cwd=str(harness.ROOT))
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_sound_run_is_correct(child):
    r = child["runs"]["sound"]
    assert r["correct"], r["checks"]
    assert r["count"] == 4 and r["attempted"] >= 1


@pytest.mark.parametrize("fault", FAULTS)
def test_mesh_fault_is_caught(child, fault):
    r = child["runs"][fault]
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("placement", ["host_parked", "two_devices",
                                       "one_per_device"])
def test_reference_reads_the_same_bits_wherever_peers_lie(child, placement):
    assert child["placements"][placement]


def test_the_lowering_is_the_mesh_step_that_ran(child):
    low = child["lowering"]
    assert low["same_paths"]
    # MAR's rounds cross between devices
    assert low["all_reduces"] > 0


def test_readers_split_each_call_of_the_mesh_step(child):
    got = child["lowering"]["readers"]
    assert all(v is not None for v in got.values()), got
    # each device ran every op once, 1 ms each, over two calls
    per_call = child["lowering"]["busy_ms"] / 2
    phases = sum(got[f"{p}_ms.train"]
                 for p in ("fwd", "bwd", "remat", "opt", "aggregate"))
    assert phases == pytest.approx(
        per_call * (1 - got["unscoped_share.train"] / 100))
    assert got["aggregate_ms.train"] > 0
    assert 0 < got["mlstm_ms.train"] + got["slstm_ms.train"] + \
        got["head_ms.train"] <= phases
    assert got["unscoped_share.train"] < 50
    assert 50.0 < got["slstm_fused_share.train"] <= 100.0
    assert got["idle_share.train"] == pytest.approx(0.0, abs=1e-9)
    assert got["train_mfu"] > 0
