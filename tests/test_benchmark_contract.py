"""What the benchmark and the documents name must exist in the tree.

Three contracts, all read-only over their subjects:

- every per-layer metric of `benchmark/layer_metrics/*.json` that reads
  the program's counters or span timers names ones the program
  declares. A renamed counter then fails here and not as a `null`
  under `per_layer` in the ledger, which nobody is asked to look at;
- every module that a configuration, a traffic file or a reader file
  of the benchmark names (`module`, `entry_module`, `control.module`)
  imports, lies in the benchmark package and has the attribute the
  file names beside it: an operator's generator, an entry, a control,
  a traffic kind, a reduction. `python3 -m benchmark.selfcheck` holds
  the files of the cells to that on the chip's side; this holds every
  file, in tier-1;
- every repo-relative file or directory that `README.md` and
  `.claude/skills/verify/SKILL.md` name in code spans exists, but for
  the few a run of the program writes.
"""
import fnmatch
import glob
import json
import os
import re

import pytest

from amgx_tpu.telemetry import metrics, spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _readers(field):
    out = []
    for path in sorted(glob.glob(
            os.path.join(REPO, "benchmark", "layer_metrics", "*.json"))):
        with open(path) as f:
            patterns = json.load(f).get(field)
        if patterns:
            out.append(pytest.param(patterns,
                                    id=os.path.basename(path)[:-5]))
    return out


@pytest.mark.parametrize("patterns", _readers("counters"))
def test_layer_metric_counters_are_declared(patterns):
    undeclared = [p for p in patterns
                  if not fnmatch.filter(metrics.COUNTERS, p)]
    assert not undeclared, (
        f"no counter of telemetry.metrics.COUNTERS matches {undeclared}")


@pytest.mark.parametrize("patterns", _readers("timers"))
def test_layer_metric_timers_are_declared(patterns):
    # `amg.L*.rap` is read from the timers of levels 0, 1, ...
    undeclared = [p for p in patterns
                  if not spans.is_declared(p.replace("*", "0"))]
    assert not undeclared, (
        f"telemetry.spans declares no span for {undeclared}")


def _named_by_module():
    """(file, what, module, attribute) of every name a benchmark data
    file gives with a module beside it."""
    out = []

    def add(path, what, module, attribute):
        if module is not None:
            out.append(pytest.param(
                module, attribute,
                id=f"{os.path.basename(path)[:-5]}:{what}"))

    def files(*parts):
        return sorted(glob.glob(os.path.join(REPO, "benchmark", *parts)))

    for path in files("configs", "*.json") \
            + files("tests", "local", "config.json"):
        with open(path) as f:
            cfg = json.load(f)
        op, ctl = cfg["operator"], cfg["control"]
        add(path, "generator", op.get("module"), op.get("generator"))
        add(path, "entry", cfg.get("entry_module"), cfg["entry"])
        add(path, "control", ctl.get("module"), ctl["entry"])
    for path in files("traffic", "*.json") \
            + files("tests", "local", "traffic.json"):
        with open(path) as f:
            spec = json.load(f)
        add(path, "kind", spec.get("module"), spec["kind"])
    for path in files("layer_metrics", "*.json"):
        with open(path) as f:
            spec = json.load(f)
        add(path, "reduction", spec.get("module"), spec["reduction"])
    return out


@pytest.mark.parametrize("module,attribute", _named_by_module())
def test_named_module_has_the_attribute(module, attribute):
    import importlib
    assert module.startswith("benchmark."), (
        f"{module} lies outside the benchmark package")
    mod = importlib.import_module(module)
    assert hasattr(mod, attribute), (
        f"module {module} has no {attribute!r}; it has "
        f"{sorted(k for k in vars(mod) if not k.startswith('_'))}")


# Named in the documents and absent from a fresh checkout, each because
# running the program is what makes it.
WRITTEN_AT_RUN_TIME = {
    "MULTICHIP.json": "__graft_entry__.dryrun_multichip writes it",
    "amgx_tpu/native/_build/": "the native library builds itself there",
}

_CODE_BLOCK = re.compile(r"```.*?```", re.S)
_CODE_SPAN = re.compile(r"`([^`\n]+)`")
_PATHLIKE = re.compile(r"[A-Za-z0-9_.*/-]+")


def _named_paths(text):
    """The words of a markdown text's code (fenced blocks and inline
    spans) that read as a repo-relative file or directory."""
    code = _CODE_BLOCK.findall(text)
    code += _CODE_SPAN.findall(_CODE_BLOCK.sub("", text))
    for chunk in code:
        for word in _PATHLIKE.findall(chunk):
            word = word.rstrip(".")
            if word.startswith("/") or word.strip("./*") == "":
                continue     # absolute, or the tail of `<placeholder>/x`
            if word.endswith((".py", ".json", ".md", "/")):
                yield word


def _exists(word):
    # a run-time product is excused under either base: the skill file
    # writes `native/_build/`, which a tree whose native library has
    # not been built yet (the driver's fresh checkout, this file's
    # turn coming before the first native call of the run) lacks
    if word in WRITTEN_AT_RUN_TIME \
            or os.path.join("amgx_tpu", word) in WRITTEN_AT_RUN_TIME:
        return True
    return any(glob.glob(os.path.join(base, word))
               for base in (REPO, os.path.join(REPO, "amgx_tpu")))


@pytest.mark.parametrize("doc", ["README.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_documents_name_only_paths_that_exist(doc):
    if not os.path.exists(os.path.join(REPO, doc)):
        # a tree handed over without its .claude/ directory: nothing
        # of this document to hold; README.md has to be there
        assert doc.startswith(".claude/"), f"{doc} is missing"
        return
    with open(os.path.join(REPO, doc)) as f:
        named = sorted(set(_named_paths(f.read())))
    assert len(named) >= 10, f"the extraction found {named} in {doc}"
    missing = [w for w in named if not _exists(w)]
    assert not missing, (
        f"{doc} names {missing}: absent from the repo root and from "
        "amgx_tpu/ (a file the program writes when run belongs in "
        "WRITTEN_AT_RUN_TIME, with the reason)")
