"""The repo's account of itself: every command and path the operator
documents name exists in the tree, the root carries no record of a
benchmark the tree cannot run, and `BENCHMARK.json` points at a module
that imports. `PERF.md` and `ROADMAP.md` tell history and name files
that are gone on purpose; a reviewer reads those, not this test."""
import importlib.util
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: `python X.py` / `python3 -m pkg.mod`, as a shell line or inline code
_COMMAND = re.compile(r"python3? +(?:-m +([\w.]+)|([\w/.\-]+\.py))")
#: `pkg/mod.py`-shaped paths, anything under `benchmarks/`, and the
#: upper-case records of the root (`PERF.md`, `BENCHMARK.json`)
_PATH = re.compile(
    r"(?<![\w/.\-])((?:[\w.\-]+/)+[\w.\-]+\.(?:py|cc|json|md|sh)\b"
    r"|benchmarks(?:/[\w.\-]+)*/?)"
    r"|`([A-Z][\w.\-]*\.(?:jsonl|json|md))`")
#: paths into the reference tree (/root/reference), not into this one
_REFERENCE = ("src/", "qa/tasks/", "qa/workunits/", "doc/")


@pytest.mark.parametrize("doc", ["README.md", "COVERAGE.md"])
def test_documented_commands_and_paths_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = []
    for module, script in _COMMAND.findall(text):
        if script:
            if not os.path.isfile(os.path.join(REPO, script)):
                missing.append(f"python {script}")
        elif importlib.util.find_spec(module) is None:
            missing.append(f"python -m {module}")
    paths = {a or b for a, b in _PATH.findall(text)}
    assert len(paths) > 20, "the path pattern no longer reads this file"
    for path in sorted(paths):
        if path.startswith(_REFERENCE):
            continue
        # module docs shorten `ceph_tpu/osd/scrub.py` to `osd/scrub.py`
        if not any(os.path.exists(os.path.join(REPO, root, path))
                   for root in ("", "ceph_tpu")):
            missing.append(path)
    assert missing == [], f"{doc} names what the tree does not have"


def test_root_holds_no_record_of_a_benchmark_that_is_gone():
    stale = [p for p in os.listdir(REPO)
             if re.fullmatch(r"(BENCH|MULTICHIP)_r\d+\.json", p)]
    assert stale == []


def test_benchmark_command_resolves_to_a_module_of_the_tree():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    assert command[1] == "-m"
    spec = importlib.util.find_spec(command[2])
    assert spec is not None
    assert spec.origin == os.path.join(REPO, *command[2].split(".")) + ".py"
