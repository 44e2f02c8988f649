"""Everything a run needs, found by name from ``BENCHMARK.json``.

    configs/<config>.json     the configuration as it is run
    references/<ref>.py       its plain reference (``reference`` in the file)
    traffic/<traffic>.json    the traffic mix
    limits/<workload>.json    the limits of the numbers ``correct`` compares
    metrics/<metric>.py       one reader per metric, ``read(run) -> float``
                              or None when it finds nothing to read
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]


def _load_py(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path.relative_to(ROOT)}")
    mod_name = "chipbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list        # metric entries this cell reports with --trace 0
    per_layer: list         # ... and with --trace 1

    def reference(self):
        return _load_py(BENCH / "references" / f"{self.config['reference']}.py")

    def reader(self, metric: str):
        return _load_py(BENCH / "metrics" / f"{metric}.py").read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, bench_file: pathlib.Path | None = None) -> Cell:
    bench = _json(bench_file or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=_json(BENCH / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])
