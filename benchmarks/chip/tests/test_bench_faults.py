"""A whole run at a tiny size on the CPU, the chip check skipped: the
sound timed path comes out correct, and each fault planted underneath it
comes out not correct."""
import pytest

import tiny


def test_train_sound_run_is_correct():
    r = tiny.run("xlstm-350m.fl.c1")
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "no_mar"])
def test_train_fault_is_caught(fault):
    r = tiny.run("xlstm-350m.fl.c1", fault=fault)
    assert not r["correct"], r["checks"]
