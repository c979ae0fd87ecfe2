"""The scheduler's host metrics on a traced toy-width run on the CPU: each
reads a number, and within its range."""

import time

import jax
import pytest

import harness
import tiny


@pytest.fixture(autouse=True)
def cpu_chips(monkeypatch):
    """The CPU stands in for the chips the cell asks for."""
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])


def test_traced_run_reads_the_host_metrics(tmp_path):
    root = tiny.make_root(str(tmp_path), limit=0.05)
    res = harness.run_cell(root, "tiny.mix", 2**31 + 7, 0.5, True,
                           time.perf_counter())
    assert res["correct"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["queue_wait_p95_ms"] >= 0.0
    assert 0.0 < m["host_busy_share"] <= 100.0
    assert m["host_stall_ms"] > 0.0
