"""Finds the benchmark's pieces by name.

`BENCHMARK.json` at the root of the checkout names the cells; each
piece a cell needs is a file of its own, found by its name:

  benchmark/configs/<config>.json    one deployment (table, holders, node)
  benchmark/traffic/<traffic>.json   one traffic mix
  benchmark/metrics/<metric>.py      one reader per per-layer metric; a
                                     metric "x.open" is read by x.py
  benchmark/kernels/<kernel>.py      operations and bytes of one kernel
  benchmark/peaks.json               the chip's peaks, by device_kind

So a later change adds a configuration, a mix, a metric or a kernel by
adding files and entries, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import table as tbl  # noqa: E402

ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    pass


def check_name(name: str, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise SpecError(f"bad {what} {name!r}")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT.match(unit):
        raise SpecError(f"bad unit {unit!r}")
    return unit


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_name(m["name"], "metric")
        check_unit(m["unit"])
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"metric {m['name']}: better is {m['better']!r}")
    for c in bench["configs"]:
        check_name(c["name"], "config")
    for w in bench["workloads"]:
        check_name(w["name"], "workload")
        check_name(w["config"], "config")
        check_name(w["traffic"], "traffic")
    return bench


def load_config(name: str, bench_dir: str = BENCH_DIR) -> dict:
    check_name(name, "config")
    conf = _json(os.path.join(bench_dir, "configs", name + ".json"))
    conf.setdefault("name", name)
    return conf


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    check_name(name, "traffic")
    traffic = _json(os.path.join(bench_dir, "traffic", name + ".json"))
    if traffic.get("loop") not in ("open", "closed"):
        raise SpecError(f"traffic {name}: loop must be open or closed")
    try:
        tbl.check_draw(traffic.get("topic_draw"))
    except ValueError as e:
        raise SpecError(f"traffic {name}: {e}")
    return traffic


def _module(path: str, modname: str) -> ModuleType:
    if not os.path.exists(path):
        raise SpecError(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: str = BENCH_DIR) -> ModuleType:
    """The reader of per-layer metric `name`: metrics/<base>.py, where
    base is the name up to its first dot. It defines `read(ctx)`, which
    returns the number, or None when the run gave it nothing to read."""
    check_name(name, "metric")
    base = name.split(".")[0]
    mod = _module(
        os.path.join(bench_dir, "metrics", base + ".py"), f"bench_metric_{base}"
    )
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metrics/{base}.py has no read(ctx)")
    return mod


def kernel(name: str, bench_dir: str = BENCH_DIR) -> ModuleType:
    """kernels/<name>.py: TRACE_NAMES (the names the kernel's device
    events carry) and cost(shape) -> (operations, bytes)."""
    check_name(name, "kernel")
    mod = _module(
        os.path.join(bench_dir, "kernels", name + ".py"), f"bench_kernel_{name}"
    )
    if not callable(getattr(mod, "cost", None)):
        raise SpecError(f"kernels/{name}.py has no cost(shape)")
    return mod


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = _json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


class Cell:
    """One workload of BENCHMARK.json with its pieces loaded."""

    def __init__(self, bench: dict, name: str, bench_dir: str = BENCH_DIR):
        found = [w for w in bench["workloads"] if w["name"] == name]
        if not found:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json")
        self.entry = found[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.conf = load_config(self.entry["config"], bench_dir)
        self.traffic = load_traffic(self.entry["traffic"], bench_dir)
        self.end_to_end = self._mine(bench["end_to_end"])
        self.per_layer = self._mine(bench["per_layer"])

    def _mine(self, metrics: List[dict]) -> List[dict]:
        return [
            m for m in metrics
            if "workloads" not in m or self.name in m["workloads"]
        ]

    def metrics(self, trace: bool) -> List[dict]:
        return self.per_layer if trace else self.end_to_end


def find_cell(name: str, root: Optional[str] = None) -> Cell:
    root = root or ROOT
    return Cell(load_benchmark(root), name, os.path.join(root, "benchmark"))
