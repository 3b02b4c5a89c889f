"""The lower-precision control of a cell: it has to come out NOT correct.

    python3 -m benchmark.control --workload <name> --seed <n> --seconds <s>

Runs the cell through the same harness (benchmark.run) with the
configuration's `control` entry in the program's place: the nearest
precision below the one the configuration states. `control.entry` is a
name of the table below or, with `control.module` beside it, a class of
that module of the benchmark package. The benchmark's own
runs never call this; a builder runs it on the chip at the cell's own
size when a limit is set or changed, and tests/ keeps it at a small
size. Exit code 0 when the control failed the check (as it must), 1
when it passed: the check is then too weak to tell the precisions apart.
"""
from __future__ import annotations

import argparse

from . import run as harness
from .entries import ENTRIES
from .reference import ReferenceCG

CONTROL_ENTRIES = dict(ENTRIES, reference_cg=ReferenceCG)


def control_class(config: dict):
    ctl = config["control"]
    return harness.named("control entry", ctl["entry"], ctl.get("module"),
                         CONTROL_ENTRIES)


def control_entry(config: dict):
    ctl = config["control"]
    operator = dict(config["operator"], **ctl.get("operator", {}))
    return control_class(config)(ctl["solver"], operator)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    _cell, config, _traffic, _bench = harness.find_cell(a.workload)
    control_class(config)           # or exit, before any device call
    print(f"CONTROL: {config['control']['what']}")
    result = harness.run(a.workload, a.seed, a.seconds, False,
                         make_entry=control_entry)
    harness.say(result)
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
