"""The lower-precision control of a cell: it has to come out NOT correct.

    python3 -m benchmark.control --workload <name> --seed <n> --seconds <s>

Runs the cell through the same harness (benchmark.run) with the
configuration's `control` entry in the program's place: the nearest
precision below the one the configuration states. The benchmark's own
runs never call this; a builder runs it on the chip at the cell's own
size when a limit is set or changed, and tests/ keeps it at a small
size. Exit code 0 when the control failed the check (as it must), 1
when it passed: the check is then too weak to tell the precisions apart.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import run as harness
from .entries import ENTRIES
from .reference import ReferenceCG

CONTROL_ENTRIES = dict(ENTRIES, reference_cg=ReferenceCG)


def control_entry(config: dict):
    ctl = config["control"]
    operator = dict(config["operator"], **ctl.get("operator", {}))
    return CONTROL_ENTRIES[ctl["entry"]](ctl["solver"], operator)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    _cell, config, _traffic, _bench = harness.find_cell(a.workload)
    print(f"CONTROL: {config['control']['what']}")
    result = harness.run(a.workload, a.seed, a.seconds, False,
                         make_entry=control_entry)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
