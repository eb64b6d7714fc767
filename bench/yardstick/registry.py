"""Find every part of a cell by the name ``BENCHMARK.json`` gives it.

A configuration is the JSON file its ``configs`` entry names.  A traffic
mix is ``bench/traffic/<name>.json``; the mix names its DAG family, which
is the module ``bench/dags/<family>.py``.  A per-layer metric is the
reader ``bench/metrics/<metric>.py``.  Adding one of each is adding files
and entries: nothing here lists them.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[2]


class Registry:
    def __init__(self, root: Path | str = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict[Path, ModuleType] = {}

    @staticmethod
    def _by_name(entries: list[dict], name: str, what: str) -> dict:
        for e in entries:
            if e["name"] == name:
                return e
        raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        return self._by_name(self.spec["workloads"], name, "workload")

    def config(self, name: str) -> dict:
        entry = self._by_name(self.spec["configs"], name, "configuration")
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def family(self, name: str) -> ModuleType:
        return self._load(self.root / "bench" / "dags" / f"{name}.py")

    def reader(self, metric: str) -> ModuleType:
        return self._load(self.root / "bench" / "metrics" / f"{metric}.py")

    def metrics(self, workload: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports:
        those without a ``workloads`` key, and those that list it."""
        return [
            m
            for m in self.spec[kind]
            if "workloads" not in m or workload in m["workloads"]
        ]

    def _load(self, path: Path) -> ModuleType:
        if path not in self._modules:
            if not path.is_file():
                raise KeyError(f"no file {path.relative_to(self.root)}")
            name = "bench_" + "_".join(path.relative_to(self.root).with_suffix("").parts)
            spec = importlib.util.spec_from_file_location(
                name.replace("-", "_").replace(".", "_"), path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]
