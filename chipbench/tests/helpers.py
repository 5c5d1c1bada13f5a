"""Shared by the tests and the CPU rehearsal: a temporary root that holds a
copy of the benchmark's data files, to which a tiny stand-in configuration
and cell are ADDED as files and entries, the way a later PR adds real ones."""

from __future__ import annotations

import json
import os
import shutil

TESTS = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(TESTS)
REPO = os.path.dirname(CHIPBENCH)
DATA_DIRS = ("configs", "workloads", "traffic", "layer_metrics")
TINY_CELL = "tiny-cpu.train-b4-t64"


def copy_root(tmp: str) -> str:
    """BENCHMARK.json and the data files, copied under ``tmp``."""
    os.makedirs(os.path.join(tmp, "chipbench"), exist_ok=True)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    for d in DATA_DIRS:
        shutil.copytree(os.path.join(CHIPBENCH, d),
                        os.path.join(tmp, "chipbench", d), dirs_exist_ok=True)
    return tmp


def add_tiny(root: str) -> str:
    """Add the tiny configuration, mix and cell: three files, two entries."""
    tiny = os.path.join(TESTS, "tiny")
    for f in os.listdir(tiny):
        kind, name = f.split(".", 1)
        sub = {"config": "configs", "traffic": "traffic",
               "workload": "workloads"}[kind]
        shutil.copy(os.path.join(tiny, f),
                    os.path.join(root, "chipbench", sub, name))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "tiny-cpu", "source": "none", "reduced": [], "why": "tests",
        "file": "chipbench/configs/tiny-cpu.json"})
    bench["workloads"].append({
        "name": TINY_CELL, "config": "tiny-cpu", "traffic": "train-b4-t64",
        "chips": 1, "why": "tests"})
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh)
    return TINY_CELL
