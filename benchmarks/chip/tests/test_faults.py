"""A whole run on the CPU with the timed path broken underneath: ``correct``
must come out false for each fault a training cell can have, and true with none.

The run skips the harness's look for a chip and is otherwise the benchmark's
own: set-up, the first steps through the window's call and feed, a short
window, the data check and the reference.
"""

import time

import pytest

import harness
from tiny import tiny_cell


@pytest.fixture(autouse=True)
def cpu_devices(monkeypatch):
    import jax
    monkeypatch.setattr(harness, "chips", lambda n: jax.devices()[:n])


def _run(fault, mesh=False):
    return harness.run_cell(tiny_cell(mesh), 2**31 + 12345, 0.3, False,
                            t_process=time.perf_counter(), fault=fault, log=lambda s: None)


@pytest.mark.parametrize("mesh", [False, True], ids=["one-device", "mesh"])
def test_sound_run_is_correct(mesh):
    r = _run(None, mesh)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault,caught_by,mesh", [
    ("frozen_state", "grad_gap", False),    # a step that returns its state unchanged
    ("half_batch", "grad_gap", False),      # half of the batch left out, mean over the rest
    ("half_batch", "grad_gap", True),
    ("no_exchange", "grad_gap", True),      # each chip's shard alone, no gradient exchange
    ("token_altered", "rows_wrong", False),  # one token altered where the store makes it
    ("token_altered", "rows_wrong", True),
])
def test_fault_is_not_correct(fault, caught_by, mesh):
    r = _run(fault, mesh)
    assert not r["correct"]
    c = r["checks"][caught_by]
    assert c["value"] > c["limit"], r["checks"]
