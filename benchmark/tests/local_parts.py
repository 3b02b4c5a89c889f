"""Parts that arrive as a file: an entry, an operator generator, a
traffic kind and a control that no table of the harness knows, named by
`local/config.json` and `local/traffic.json` through their modules.
`test_by_module.py` runs a cell made of them; `USED` says which ran.
"""
from __future__ import annotations

import numpy as np

from benchmark import entries, reference, traffic
from benchmark.operator_host import poisson_csr

USED = []


class LocalEntry(entries.PythonEntry):
    def upload(self, ro, ci, vals, rhs):
        USED.append("entry")
        super().upload(ro, ci, vals, rhs)


def scaled_poisson(operator: dict, seed: int):
    """D A D of the 7-point operator on `cells`, D a positive diagonal
    drawn from the seed: symmetric, definite, no constant stencil."""
    USED.append("generator")
    ro, ci, vals = poisson_csr("7pt", operator["cells"],
                               np.dtype(operator["dtype"]))
    d = 1.0 + np.random.default_rng([seed, 7]).random(ro.shape[0] - 1)
    rows = np.repeat(np.arange(ro.shape[0] - 1), np.diff(ro))
    return ro, ci, (vals * d[rows] * d[ci]).astype(vals.dtype)


def _solve_twice(entry, spec, inputs, spans, window, base_vals=None,
                 first_op=0):
    """One operation is the same right-hand side solved twice."""
    USED.append("traffic")
    log = []
    sample = traffic.Sample(inputs.sample_rng, int(spec["checked_ops"]),
                            window.expected)
    op = first_op
    while window.open(len(log)):
        i = op % len(inputs.rhs)
        with spans.span("bench.pair"):
            entry.solve(i)
            entry.solve(i)
        traffic._note(entry, log, sample, op, i, 1.0)
        op += 1
    return log, sample


solve_twice = (_solve_twice, "bench.pair")


class LocalControl(reference.ReferenceCG):
    def upload(self, ro, ci, vals, rhs):
        USED.append("control")
        super().upload(ro, ci, vals, rhs)
