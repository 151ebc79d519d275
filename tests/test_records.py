"""The records' one yardstick (ISSUE 28): the leg schema the driver's dry
run prints, no tracked file that still names the deleted harness or its
record files, and CI workflows that run only what the tree holds."""

import glob
import importlib.util
import os
import re
import shutil
import subprocess

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the deleted harness, its record files and its environment names
GONE = re.compile(r"bench\.py|BENCH_(r|LOCAL)|MULTICHIP_r[0-9]|IOTML_BENCH_")
#: history, the driver's files, and this file
MAY_NAME_IT = {"CHANGES.md", "VERDICT.md", "SURVEY.md", "PERF_LEDGER.jsonl",
               "ISSUE.md", "ROADMAP.md", "tests/test_records.py"}


def test_leg_record_schema():
    from __graft_entry__ import leg_record

    shared = ["leg", "devices", "records", "seconds", "records_per_sec",
              "loss_first", "loss_last"]
    rec = leg_record("dp", 4.0, 1001.0, 2.123456, 0.12345678, 0.01234567,
                     mesh={"data": 4}, hosts=2)
    assert list(rec) == shared + ["mesh", "hosts"]
    assert rec == {"leg": "dp", "devices": 4, "records": 1001,
                   "seconds": 2.1235, "records_per_sec": 471.4,
                   "loss_first": 0.123457, "loss_last": 0.012346,
                   "mesh": {"data": 4}, "hosts": 2}
    assert isinstance(rec["devices"], int) and isinstance(rec["records"], int)
    idle = leg_record("x", 1, 10, 0.0, None, None)
    assert list(idle) == shared
    assert idle["records_per_sec"] == 0.0
    assert idle["loss_first"] is None and idle["loss_last"] is None


def test_no_tracked_file_names_the_deleted_harness():
    if shutil.which("git") is None:
        pytest.skip("no git")
    ls = subprocess.run(["git", "ls-files", "-z"], cwd=ROOT,
                        capture_output=True, text=True)
    if ls.returncode != 0:
        pytest.skip("not a git checkout")
    found = []
    for rel in filter(None, ls.stdout.split("\0")):
        path = os.path.join(ROOT, rel)
        if GONE.search(rel) and os.path.lexists(path):
            found.append(rel)
        if rel in MAY_NAME_IT or not os.path.isfile(path):
            continue    # a symlink to a directory, or deleted and unstaged
        with open(path, errors="replace") as f:
            for n, line in enumerate(f, 1):
                if GONE.search(line):
                    found.append(f"{rel}:{n}: {line.strip()[:80]}")
    assert not found, "\n".join(found)


def _ours(top: str) -> bool:
    return os.path.exists(os.path.join(ROOT, top)) or \
        os.path.isfile(os.path.join(ROOT, top + ".py"))


def _runs_as_module(module: str) -> bool:
    """Whether `python -m module` finds a file of this tree to run."""
    base = os.path.join(ROOT, *module.split("."))
    return os.path.isfile(base + ".py") or \
        os.path.isfile(os.path.join(base, "__main__.py"))


def _run_blocks():
    for path in sorted(glob.glob(os.path.join(ROOT, ".github", "workflows",
                                              "*.yml"))):
        with open(path) as f:
            doc = yaml.safe_load(f)
        for job, spec in doc["jobs"].items():
            for step in spec.get("steps", []):
                if "run" in step:
                    yield f"{os.path.basename(path)}:{job}", step["run"]


def test_workflows_run_only_what_the_tree_holds():
    blocks = list(_run_blocks())
    assert blocks
    missing = []
    for where, run in blocks:
        for script in re.findall(r"\bpython3?\s+([\w./-]+\.py)\b", run):
            if not os.path.isfile(os.path.join(ROOT, script)):
                missing.append(f"{where}: python {script}")
        for module in re.findall(r"\bpython3?\s+-m\s+([\w.]+)", run):
            top = module.split(".")[0]
            if not (_runs_as_module(module) if _ours(top)
                    else importlib.util.find_spec(top)):
                missing.append(f"{where}: python -m {module}")
        # inline programs: heredocs and `python -c '...'`
        for kw, rest in re.findall(r"^\s*(import|from)\s+([\w., ]+)", run,
                                   re.M):
            names = rest.split(",") if kw == "import" else [rest]
            for top in (n.split()[0].split(".")[0] for n in names
                        if n.strip()):
                if importlib.util.find_spec(top) is None:
                    missing.append(f"{where}: import {top}")
    assert not missing, "\n".join(missing)
