"""The executed path agrees with the benchmark's reference at a small side,
and the benchmark's copies of the program's generators make the same DAGs."""

import jax.numpy as jnp
import numpy as np
import pytest
from helpers import run_cell

from yardstick import reference
from yardstick.dag import Spec
from yardstick.registry import Registry
from yardstick.seeding import BlockDrawer, key_data


@pytest.mark.parametrize("workload", ["serve-flat.churn", "paper-task.mm"])
def test_executed_path_matches_reference(workload):
    r = run_cell(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["checks"]["max_rel_err"]["value"] <= 1e-5
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"graphs_per_s", "graph_p90_ms", "setup_s"}


@pytest.mark.parametrize("workload,control", [
    pytest.param("serve-flat.churn", "fp8_dot", id="serve-flat.churn"),
    pytest.param("paper-task.mm", "fp8_dot", id="paper-task.mm"),
    pytest.param("serve-flat.churn", "bf16_blocks", id="serve-flat.churn-bf16_blocks"),
])
def test_control_precision_fails(workload, control):
    """The reference put in the program's place one precision step below
    what the configuration states: fp8 dot operands with a bfloat16 add, or
    every block stored in bfloat16.  The paper task's matmul chain cannot
    show the second: its one-pass dot rounds every operand to bfloat16."""
    r = run_cell(workload, side=128, arithmetic=reference.CONTROLS[control])
    c = r["checks"]["max_rel_err"]
    assert not r["correct"] and r["failed"] >= 1
    assert c["value"] > c["limit"]


def test_draws_depend_on_seed_index_and_name():
    a = key_data(2**31 + 5, 3, ["x/in", "y/in"])
    assert not np.array_equal(a, key_data(2**31 + 6, 3, ["x/in", "y/in"]))
    assert not np.array_equal(a, key_data(2**31 + 5, 4, ["x/in", "y/in"]))
    assert not np.array_equal(a[0], a[1])
    d = BlockDrawer(16, scale=0.5)
    one, two = d(9, 1, ["b/in", "a/in"]), d(9, 1, ["a/in"])
    assert jnp.array_equal(one["a/in"], two["a/in"])
    assert float(jnp.std(d(2**40, 0, ["a/in"])["a/in"])) == pytest.approx(0.5, rel=0.3)


def test_reference_evaluates_in_dependency_order():
    spec = Spec(
        {"c": "add", "a": "mm", "b": "mm"},
        {"a": ["a/in"], "b": ["a", "b/in"], "c": ["b", "a"]},
    )
    assert spec.order().index("a") < spec.order().index("b") < spec.order().index("c")
    assert spec.exits == ["c"] and spec.inputs == ["a/in", "b/in"]
    ops = {"mm": lambda xs, ar: ar.matmul(xs[0], xs[-1]), "add": lambda xs, ar: ar.add(*xs)}
    x, y = jnp.eye(4) * 2, jnp.ones((4, 4))
    out = reference.evaluate(spec, {"a/in": x, "b/in": y}, ops)
    a = x @ x
    assert jnp.allclose(out["c"], a @ y + a)


def test_compare_flags_missing_and_nonfinite():
    ref = {"a": jnp.ones((4, 4)), "b": jnp.ones((4, 4))}
    assert reference.compare(ref, ref) == {"max_rel_err": 0.0, "missing_blocks": 0}
    assert reference.compare({"a": ref["a"]}, ref)["missing_blocks"] == 1
    bad = {"a": ref["a"], "b": ref["b"].at[0, 0].set(jnp.nan)}
    err = reference.compare(bad, ref)["max_rel_err"]
    assert err != err


def test_request_stream_copy_matches_program():
    from repro.core.arena import make_request_stream
    from repro.launch.serve import heterogeneous_platform

    reg = Registry()
    traffic = reg.traffic("churn")
    cfg = reg.config("serve-flat-2048")
    seed = 2**31 + 99
    fam = reg.family("request_stream").Family(cfg, traffic, heterogeneous_platform(), seed)
    want = make_request_stream(
        5, base_requests=traffic["base_requests"], decode_chunks=traffic["decode_chunks"],
        churn=traffic["churn"], kv_bytes=int(traffic["kv_mb"] * 2**20), seed=seed,
        arrival_spread_ms=traffic["arrival_spread_ms"],
    )
    for i, w in enumerate(want):
        _, got = fam[i]
        assert got.tag == w.tag and got.arrivals == w.arrivals
        assert got.graph.fingerprint() == w.graph.fingerprint()
        assert got.graph.topo_order() == w.graph.topo_order()
        for n in w.graph.nodes:
            assert got.graph.predecessors(n) == w.graph.predecessors(n)
            assert got.graph.nodes[n].meta == w.graph.nodes[n].meta


def test_paper_task_copy_matches_program():
    from repro.core.cost import paper_calibrated_model
    from repro.core.graph import generate_paper_dag
    from repro.core.simulate import make_cpu_gpu_platform

    reg = Registry()
    cfg = reg.config("paper-task-2048")
    fam = reg.family("paper_task").Family(cfg, reg.traffic("mm"), make_cpu_gpu_platform(), 1)
    want = paper_calibrated_model().weight_graph(generate_paper_dag("matmul"), {"matmul": 2048})
    got = fam[0][1].graph
    assert got.num_nodes() == 39 and got.num_edges() == 75
    assert got.fingerprint() == want.fingerprint()
    for n in want.nodes:
        assert got.predecessors(n) == want.predecessors(n)
    # the reference reads each kernel's arguments in the executor's order
    for n, args in fam.spec.args.items():
        preds = want.predecessors(n)
        assert args == [n + "/in" if want.nodes[p].op == "source" else p for p in preds]
