"""The tile Cholesky deployment: its DAG generator, kernels, attach, cost
entries and reference, the executed path against the reference, and the
per-op counters ``run_step`` records.  CPU-only (Pallas in interpret mode)."""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost import (AnalyticCostModel, CPU_I7_4770, GPU_GTX_TITAN,
                             kernel_flops_bytes, paper_calibrated_model)
from repro.core.arena import ArenaStep
from repro.core.executor import attach_tile_kernels
from repro.core.graph import SOURCE, TaskGraph, tile_cholesky_dag
from repro.core.reference import cholesky_tiles, max_rel_error, tile_values
from repro.core.schedulers import as_executed, make_policy
from repro.core.serving import (ServeReport, ServingExecutor,
                                groups_for_platform, merge_serve_reports)
from repro.core.simulate import make_cpu_gpu_platform
from repro.kernels import ref
from repro.kernels.matmul import gemm_nt as pl_gemm_nt

SHIFT = 3.0
OPS = ("diag_shift", "potrf", "trsm", "syrk", "gemm")


def _counts(t):
    """Tasks per op and edges (source edges included) for t x t tiles."""
    tri, tet = t * (t - 1) // 2, t * (t - 1) * (t - 2) // 6
    ops = {"diag_shift": t, "potrf": t, "trsm": tri, "syrk": tri, "gemm": tet}
    # diag_shift, potrf: one input each; trsm, syrk: two; gemm: three
    return ops, 2 * t + 2 * tri + 2 * tri + 3 * tet


@pytest.mark.parametrize("tiles, tasks", [(4, 24), (12, 376)])
def test_generator_counts(tiles, tasks):
    g = tile_cholesky_dag(tiles, shift=SHIFT)
    ops, edges = _counts(tiles)
    got = Counter(k.op for k in g.nodes.values() if k.op != "source")
    assert got == ops and sum(got.values()) == tasks
    assert g.num_edges() == edges
    assert g.exit_nodes() == [f"potrf_{tiles - 1}"]
    # the one exit depends on every task
    order = g.topo_order()
    reach = {f"potrf_{tiles - 1}"}
    for n in reversed(order):
        if any(s in reach for s in g.successors(n)):
            reach.add(n)
    assert reach == set(g.nodes)
    # seeded tiles: the lower triangle, diagonal included, read once each
    seeded = [n for n in g.successors(SOURCE)]
    assert len(seeded) == tiles * (tiles + 1) // 2
    assert len({g.nodes[n].meta["tile"] for n in seeded}) == len(seeded)


def test_generator_argument_order():
    """gemm reads (C, A, B) for C - A B^T; trsm reads (A, L); syrk (C, A)."""
    g = tile_cholesky_dag(4, shift=SHIFT)
    assert g.predecessors("gemm_3_2_1") == ["gemm_3_2_0", "trsm_3_1", "trsm_2_1"]
    assert g.predecessors("gemm_2_1_0") == [SOURCE, "trsm_2_0", "trsm_1_0"]
    assert g.predecessors("trsm_2_1") == ["gemm_2_1_0", "potrf_1"]
    assert g.predecessors("syrk_2_1") == ["syrk_2_0", "trsm_2_1"]
    assert g.predecessors("potrf_0") == ["diag_shift_0"]
    assert g.nodes["diag_shift_2"].meta == {"tile": (2, 2), "step": -1, "shift": SHIFT}


def _factored(tiles=4, side=64):
    g = tile_cholesky_dag(tiles, shift=SHIFT)
    inputs = attach_tile_kernels(g, side)
    return g, inputs, tile_values(g, inputs)


def test_reference_tiles_assemble_the_whole_factor():
    g, inputs, vals = _factored()
    A, L = cholesky_tiles(g, inputs, vals)
    assert A.shape == (256, 256)
    np.testing.assert_array_equal(np.asarray(A), np.asarray(A.T))
    eig = np.linalg.eigvalsh(np.asarray(A, np.float64))
    assert 0.5 < eig.min() and eig.max() < 6.0  # sym(R) + 3 I
    whole = jnp.linalg.cholesky(A)
    assert float(jnp.max(jnp.abs(L - whole)) / jnp.max(jnp.abs(whole))) < 1e-5
    np.testing.assert_allclose(np.asarray(L @ L.T), np.asarray(A), atol=1e-5)


def test_every_update_moves_the_last_tile():
    """With the diagonal raised by 3 (not by N), dropping the updates of
    the last tile changes it well beyond rounding."""
    g, inputs, vals = _factored()
    last = vals["potrf_3"]
    skipped = jnp.linalg.cholesky(vals["diag_shift_3"])
    assert float(jnp.max(jnp.abs(last - skipped)) / jnp.max(jnp.abs(last))) > 0.05


def test_attached_kernels_match_the_reference():
    g, inputs, vals = _factored()
    out: dict = {}
    for n in g.topo_order():
        k = g.nodes[n]
        if k.op == "source":
            continue
        args = [inputs[f"{n}/in"] if g.nodes[p].op == "source" else out[p]
                for p in g.predecessors(n)]
        out[n] = k.fn(*args)
        assert float(jnp.max(jnp.abs(out[n] - vals[n]))) < 1e-4, n
    assert float(jnp.max(jnp.abs(jnp.triu(out["potrf_3"], 1)))) == 0.0


def test_seeded_inputs_scale_with_the_matrix_order():
    g = tile_cholesky_dag(4, shift=SHIFT)
    inputs = attach_tile_kernels(g, 128)
    assert len(inputs) == 10
    std = float(jnp.std(jnp.stack(list(inputs.values()))))
    assert std == pytest.approx((4 * 128) ** -0.5, rel=0.05)


def test_attach_raises_on_an_unknown_op():
    g = tile_cholesky_dag(2, shift=SHIFT)
    g.add("lu_0", op="getrf")
    with pytest.raises(KeyError, match="getrf"):
        attach_tile_kernels(g, 16)


def _executed(tiles, side, n_steps=2):
    plat = make_cpu_gpu_platform()
    g = paper_calibrated_model().weight_graph(
        tile_cholesky_dag(tiles, shift=SHIFT), {op: side for op in OPS})
    step = ArenaStep(graph=g, tag="tile")
    got = []
    sx = ServingExecutor(groups_for_platform(plat), plat, side=side,
                         attach=attach_tile_kernels,
                         check=lambda st, rep, outs: got.append(outs))
    pol = as_executed(make_policy("incremental-gp", scale_by_workers=True))
    reps = [sx.run_step(step, pol, i) for i in range(n_steps)]
    return g, reps, got


def test_executed_path_matches_the_reference():
    g, reps, got = _executed(4, 64)
    ref_g = g.copy()
    expect = tile_values(ref_g, attach_tile_kernels(ref_g, 64))
    for outs in got:
        assert set(outs) == {"potrf_3"}
        assert max_rel_error(outs, {"potrf_3": expect["potrf_3"]}) < 1e-5
    assert all(r.n_kernels == 24 and r.reexecuted == 0 for r in reps)


def test_run_step_counts_kernels_and_waits_per_op():
    g, reps, _ = _executed(4, 32)
    ops, _ = _counts(4)
    for r in reps:
        assert r.op_calls == ops
        assert set(r.op_wait_ms) == set(OPS)
        assert all(ms >= 0.0 for ms in r.op_wait_ms.values())
        # the waits of the kernels' steps are a part of all the waits
        assert sum(r.op_wait_ms.values()) <= r.span_ms["exec.wait"] + 1e-9
    d = ServeReport("igp", list(reps)).to_dict()
    assert d["op_calls"] == {op: 2 * n for op, n in ops.items()}
    assert d["op_wait_ms"] == pytest.approx(
        {op: sum(r.op_wait_ms[op] for r in reps) for op in OPS})
    m = merge_serve_reports([ServeReport("a", reps[:1]), ServeReport("b", reps[1:])])
    assert m.steps[0].op_calls == {op: 2 * n for op, n in ops.items()}


def test_launch_trace_event_names_the_op(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        _executed(2, 16, n_steps=1)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (path,) = tmp_path.rglob("*.xplane.pb")
    ops = {dict(e.stats).get("op") for p in ProfileData.from_file(str(path)).planes
           for line in p.lines for e in line.events if e.name == "exec.launch"}
    assert ops == {"diag_shift", "potrf", "trsm", "syrk"}


# (M, K, N) of C (M, N) - A (M, K) @ B (N, K)^T; the last runs the chosen
# blocks on a (2, 2, 2) grid
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (128, 256, 512), (2048, 1024, 2048)])
def test_gemm_nt_matches_oracle(shape):
    M, K, N = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    c = jax.random.normal(ks[0], (M, N))
    a = jax.random.normal(ks[1], (M, K))
    b = jax.random.normal(ks[2], (N, K))
    out = pl_gemm_nt(c, a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.gemm_nt(c, a, b)),
                               rtol=2e-4, atol=2e-4 * np.sqrt(K))


def test_gemm_nt_with_b_equal_a_is_syrk():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    c = jax.random.normal(ks[0], (256, 256))
    a = jax.random.normal(ks[1], (256, 128))
    out = pl_gemm_nt(c, a, a, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(c - a @ a.T),
                               rtol=2e-4, atol=2e-3)


def test_flops_and_bytes_of_the_tile_ops():
    n = 2048
    assert kernel_flops_bytes("potrf", n) == (n**3 / 3, 2 * n * n * 4)
    assert kernel_flops_bytes("trsm", n) == (n**3, 3 * n * n * 4)
    assert kernel_flops_bytes("syrk", n) == (n * n * (n + 1), 3 * n * n * 4)
    assert kernel_flops_bytes("gemm", n) == (2 * n**3, 4 * n * n * 4)
    # the paper's ops keep their counts
    assert kernel_flops_bytes("matmul", n) == (2 * n**3, 3 * n * n * 4)
    assert kernel_flops_bytes("matadd", n) == (n * n, 3 * n * n * 4)


def test_analytic_model_prices_the_tile_ops():
    model = paper_calibrated_model()
    n = 2048
    ms = {(op, c): model.kernel_ms(op, n, c) for op in OPS for c in ("cpu", "gpu")}
    assert all(v > 0 for v in ms.values())
    # the accelerator wins the BLAS-3 updates by far more than the panel
    gain = {op: ms[(op, "cpu")] / ms[(op, "gpu")] for op in OPS}
    assert gain["gemm"] > gain["syrk"] > gain["trsm"] > gain["potrf"]
    # the paper's kernels are priced as before
    plain = AnalyticCostModel({"cpu": CPU_I7_4770, "gpu": GPU_GTX_TITAN})
    assert model.kernel_ms("matmul", n, "gpu") == pytest.approx(
        2 * n**3 / (GPU_GTX_TITAN.peak_flops * 0.6) * 1e3 + GPU_GTX_TITAN.overhead_ms)
    assert plain.kernel_ms("matmul", n, "gpu") == model.kernel_ms("matmul", n, "gpu")
    g = model.weight_graph(tile_cholesky_dag(3, shift=SHIFT), {op: n for op in OPS})
    assert g.nodes["gemm_2_1_0"].costs == {
        c: model.kernel_ms("gemm", n, c) for c in ("cpu", "gpu")}
    assert all(e.nbytes == n * n * 4 for e in g.edges)


def test_weight_graph_raises_on_an_op_it_cannot_price():
    model = paper_calibrated_model()
    g = TaskGraph()
    g.add("x", op="getrf")
    with pytest.raises(KeyError):
        model.weight_graph(g, {"getrf": 8})
