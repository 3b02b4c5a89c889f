"""Test harness configuration.

Runs the whole suite on CPU with 8 virtual devices so the distributed
(mesh/shard_map) paths are unit-testable on a single host — the gap the
reference leaves open (its unit binary is single-process; multi-rank
coverage only via MPI example programs, SURVEY.md §4).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _cpu_backend import force_cpu  # noqa: E402

force_cpu(8)

from amgx_tpu import compile_cache  # noqa: E402

# persistent compilation cache (eager setup ops compile one XLA
# executable per shape bucket): placed by the one rule of
# amgx_tpu/compile_cache.py, never here
compile_cache.enable(every_program=True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tests excluded from the tier-1 budgeted run "
        "(`-m 'not slow'`); run them with `-m slow` on a capable rig")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)
