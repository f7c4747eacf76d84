"""What the benchmark's pieces share: where the files are, how a cell's
configuration, traffic and metrics are found by name, and how a
configuration file maps onto the program's ``ModelConfig``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives it:

  bench/configs/<config>.json     sizes, guarantees and comparison limits
  bench/traffic/<traffic>.json    parameters for ``bench/traffic.py``
  bench/metrics/<metric>.py       ``read(ctx) -> float | None``
  bench/reference/<kind>.py       plain float32 reference of an architecture
  bench/arch/<kind>.py            the program's side of an architecture: its
                                  ``ModelConfig`` and the cost of its calls
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def config_path(name: str) -> str:
    return os.path.join(BENCH, "configs", f"{name}.json")


def load_config(path: str) -> dict:
    return _read_json(path)


def load_traffic(name: str) -> dict:
    return _read_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def end_to_end_for(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_for(bench: dict, cell: str) -> list[dict]:
    """Per-layer metrics this cell reports: those that list it, and those
    without a list whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_module(path: str, name: str):
    """Import a file by path (metric readers carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                       f"bench_metric_{name.replace('.', '_')}")


def reference_module(kind: str):
    return load_module(os.path.join(BENCH, "reference", f"{kind}.py"),
                       f"bench_reference_{kind}")


@functools.cache
def arch_module(kind: str):
    """``bench/arch/<kind>.py``: the program's ``ModelConfig`` for a
    configuration of that kind (``program_config``) and the operations and
    bytes its calls need (``cost``, ``parts``; see ``bench/flops.py``)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    return load_module(os.path.join(BENCH, "arch", f"{kind}.py"),
                       f"bench_arch_{kind}")


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``: the
    registered architecture with the file's sizes laid over it."""
    return arch_module(c["reference"]).program_config(c)


def send(stream, obj: dict) -> None:
    """One JSON object per line on a pipe between the run's processes."""
    stream.write(json.dumps(obj) + "\n")
    stream.flush()


def recv(stream) -> dict:
    line = stream.readline()
    if not line:
        raise EOFError("peer process closed its pipe")
    return json.loads(line)
