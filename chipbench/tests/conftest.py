"""The benchmark's own tests run on the host CPU at reduced sizes:

    python -m pytest chipbench/tests
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

# Reduced widths and sizes with the full structure: every stage, a stride-2
# stage for each encoder tap, the DetNet heads and all five decoder stages.
SMALL = dict(
    stem_channels=8, head_channels=64,
    stages=[[1, 8, 1, 1], [2, 8, 2, 2], [2, 16, 2, 2], [2, 16, 2, 2],
            [2, 24, 1, 1], [2, 24, 2, 2], [2, 32, 1, 1]],
    decoder_channels=[16, 16, 8, 8, 8])


def shrink(cfg, traffic):
    """A configuration and traffic small enough for the CPU."""
    cfg = dict(cfg, **SMALL)
    cfg["input_hw"] = [32, 32] if cfg["task"] == "detection" else [32, 64]
    return cfg, dict(traffic, batch=min(traffic["batch"], 8))


@pytest.fixture
def small():
    return shrink
