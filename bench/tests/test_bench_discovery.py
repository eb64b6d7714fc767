"""Every part of a cell is found by name; a new configuration, mix, DAG
family and metric are new files and entries, with no existing file
edited."""

import json
import re
import shutil

from helpers import run_cell

from yardstick.registry import ROOT, Registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_benchmark_json_names_resolve():
    reg = Registry()
    spec = reg.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    for kind, keys in KEYS.items():
        for e in spec[kind]:
            assert set(e) - {"workloads"} == keys, e
            assert NAME.match(e["name"]), e["name"]
            for text in ("why", "source", "layer"):
                assert len(e.get(text, "x")) <= 200 and "\n" not in e.get(text, "")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            reg.workload(w)
    names = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in names
        assert callable(reg.reader(m["name"]).read)
    used = set()
    for w in spec["workloads"]:
        cfg = reg.config(w["config"])
        used.add(w["config"])
        assert cfg["chips"] == w["chips"]
        traffic = reg.traffic(w["traffic"])
        assert reg.family(traffic["dag"]).Family
        assert reg.metrics(w["name"], "per_layer")
    assert used == {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        assert c["file"].startswith("bench/configs/")


CHAIN = '''
"""A chain of matadds on one seeded block."""
from yardstick.dag import Spec

KERNELS = {"matadd": "matadd"}
OPS = {"matadd": lambda xs, ar: ar.add(xs[0], xs[1] if len(xs) > 1 else xs[0])}


class Family:
    kernels, ops = KERNELS, OPS

    def __init__(self, config, traffic, platform, seed):
        from repro.core.arena import ArenaStep
        from repro.core.executor import attach_matrix_kernels
        from repro.core.graph import TaskGraph

        self.attach, self.scale = attach_matrix_kernels, 1.0
        names = [f"c{i}" for i in range(traffic["length"])]
        self.spec = Spec({n: "matadd" for n in names},
                         {n: [p] for p, n in zip(["c0/in"] + names, names)})
        g = TaskGraph()
        for i, n in enumerate(names):
            g.add(n, op="matadd", costs={c: 1.0 for c in platform.classes})
            if i:
                g.add_edge(names[i - 1], n)
        self.step = ArenaStep(graph=g, tag="chain")

    def __getitem__(self, i):
        return self.spec, self.step
'''


def test_new_parts_are_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "paper-task-2048.json").read_text())
    cfg["name"] = "chain-cfg"
    (bench / "configs" / "chain-cfg.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "chain5.json").write_text(json.dumps({
        "dag": "chain", "length": 5, "warmup_graphs": 1, "check_sample": 2}))
    (bench / "dags" / "chain.py").write_text(CHAIN)
    (bench / "metrics" / "kernels_per_graph.py").write_text(
        "def read(run):\n    return sum(r.n_kernels for r in run.reports) / run.graphs\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "chain-cfg", "source": "x", "reduced": [],
                            "file": "bench/configs/chain-cfg.json", "why": "x"})
    spec["workloads"].append({"name": "chain-cfg.chain5", "config": "chain-cfg",
                              "traffic": "chain5", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "kernels_per_graph", "unit": "kernels",
                              "better": "lower", "source": "program_counter",
                              "layer": "serving executor and session",
                              "moves": "graphs_per_s", "workloads": ["chain-cfg.chain5"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    r = run_cell("chain-cfg.chain5", root=tmp_path, seconds=0.3)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"graphs_per_s", "graph_p90_ms", "setup_s"}
    r = run_cell("chain-cfg.chain5", root=tmp_path, seconds=0.3, trace=True)
    assert r["metrics"]["kernels_per_graph"]["value"] == 5
    # the CPU trace has no chip: the device readers find nothing to read
    assert "device_idle_share" not in r["metrics"]
    assert "matmul_roofline" not in r["metrics"]
