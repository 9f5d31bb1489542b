"""The benchmark's own tests run on the CPU, with four virtual devices for
the sharded rehearsal; both variables must be set before JAX is imported."""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def short_runs(monkeypatch):
    """``tpot_p50_ms`` over runs of 8 tokens, not 64: a tiny mix's
    requests are 16 to 24 tokens, and the CPU's steps too slow for
    longer ones to end in a window of two seconds."""
    from perfbench import serve_cell

    monkeypatch.setattr(serve_cell, "TPOT_RUN", 8)
