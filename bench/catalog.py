"""Find a cell's parts by name, and build ``BENCHMARK.json`` from them.

* ``configs/<config>.json``  — one deployment: its source, shape, ``reduced``
  and ``assumed`` keys, the generator that makes its data.
* ``traffic/<cell>.json``    — one cell: its config, task (``path`` or
  ``cv``), plan, responses solved in set-up and made for the window,
  chips, the limit of its correctness check, the length of its traced
  slice and ``why``.
* ``metrics/<metric>.py``    — one per-layer metric reader.  It declares
  ``LAYER``, ``UNIT``, ``BETTER``, ``SOURCE``, ``MOVES`` and ``TASK`` (and
  may narrow to ``PENALTY``), and ``read(run)`` returns its value or None.
* ``generators/<name>.py``   — one data generator, named by a
  configuration's ``generator`` (``bench.data``).
* ``penalties/<name>.py``    — one penalty, named by a configuration's
  ``penalty``: the group structure, the program's ``Problem`` and the f64
  reference gap.
* ``suite.json``             — command, paths, run length and the
  end-to-end metrics; a metric with ``task`` exists only in cells of it.

A later change adds files here; ``python -m bench.catalog`` rewrites
``BENCHMARK.json`` from them, and a test keeps the two in step.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TASKS = ("path", "cv")


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise KeyError(f"no such file: {path.relative_to(ROOT)}") from None


def config(name: str) -> dict:
    return _load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _load_json(HERE / "traffic" / f"{name}.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    traffic: dict
    config: dict

    @property
    def task(self) -> str:
        return self.traffic["task"]

    @property
    def penalty(self) -> str:
        return self.config["penalty"]


def cell(name: str) -> Cell:
    tr = traffic(name)
    if tr["task"] not in TASKS:
        raise ValueError(f"{name}: unknown task {tr['task']!r}")
    return Cell(name=name, traffic=tr, config=config(tr["config"]))


def cell_names() -> list[str]:
    return sorted(p.stem for p in (HERE / "traffic").glob("*.json"))


def _module(kind: str, name: str):
    """The module ``<kind>/<name>.py``, loaded from its file."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no such file: bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.NAME = name
    return mod


def _names(kind: str) -> list[str]:
    return sorted(p.name[:-3] for p in (HERE / kind).glob("*.py")
                  if not p.name.startswith("_"))


def metric(name: str):
    """The reader module of one per-layer metric."""
    return _module("metrics", name)


def metric_names() -> list[str]:
    return _names("metrics")


def generator(name: str):
    """The module that makes a configuration's data."""
    return _module("generators", name)


def generator_names() -> list[str]:
    return _names("generators")


def penalty(name: str):
    """The module that poses and checks a configuration's problem."""
    return _module("penalties", name)


def applies(mod, c: Cell) -> bool:
    """Whether the reader ``mod`` finds something to read in cell ``c``.
    A cell whose traced slice cannot hold a whole call (its traffic sets
    ``device_trace_metrics`` false) has no device-trace readers."""
    return (mod.TASK == c.task
            and getattr(mod, "PENALTY", None) in (None, c.penalty)
            and (mod.SOURCE != "device_trace"
                 or c.traffic.get("device_trace_metrics", True)))


def suite() -> dict:
    return _load_json(HERE / "suite.json")


def benchmark() -> dict:
    """The content of ``BENCHMARK.json``."""
    s = suite()
    cells = [cell(n) for n in cell_names()]
    used = sorted({c.traffic["config"] for c in cells})
    configs = [{"name": n, "source": config(n)["source"],
                "file": f"bench/configs/{n}.json",
                "reduced": config(n)["reduced"], "why": config(n)["why"]}
               for n in used]
    workloads = [{"name": c.name, "config": c.traffic["config"],
                  "traffic": c.name, "chips": c.traffic["chips"],
                  "why": c.traffic["why"]} for c in cells]
    # a metric that no cell reports (a task no cell runs) is left out
    end_to_end = []
    for m in s["end_to_end"]:
        entry = {k: m[k] for k in ("name", "unit", "better", "bound",
                                   "source")}
        if "task" in m:
            entry["workloads"] = [c.name for c in cells
                                  if c.task == m["task"]]
            if not entry["workloads"]:
                continue
        end_to_end.append(entry)
    per_layer = []
    for n in metric_names():
        mod = metric(n)
        where = [c.name for c in cells if applies(mod, c)]
        if where:
            per_layer.append({"name": n, "unit": mod.UNIT,
                              "better": mod.BETTER, "source": mod.SOURCE,
                              "layer": mod.LAYER, "moves": mod.MOVES,
                              "workloads": where})
    return {"command": s["command"], "paths": s["paths"],
            "run_seconds": s["run_seconds"], "configs": configs,
            "workloads": workloads, "end_to_end": end_to_end,
            "per_layer": per_layer}


def render() -> str:
    return json.dumps(benchmark(), indent=2) + "\n"


if __name__ == "__main__":
    (ROOT / "BENCHMARK.json").write_text(render())
    sys.exit(0)
