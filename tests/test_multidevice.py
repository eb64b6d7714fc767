"""Multi-device semantics checks run in subprocesses with 8 forced host
devices (jax locks the device count at first init, so the main pytest
session must stay at 1 device for the smoke tests).

Covers: MoE expert-parallel all_to_all vs the dense reference, flash-decode
(seq-sharded cache) vs the dense decode path, and shape-aware sharding
trees."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str):
    r = subprocess.run(
        [sys.executable, "-c",
         'import os\nos.environ["XLA_FLAGS"] = '
         '"--xla_force_host_platform_device_count=8"\n'
         'import sys\nsys.path.insert(0, "src")\n'
         'from repro.launch.mesh import make_mesh\n' + textwrap.dedent(code)],
        capture_output=True, text=True, cwd=ROOT, timeout=420)
    assert "PASS" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])


def test_moe_ep_matches_reference():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, LayerSpec
    from repro.models import moe as M
    from repro.models.layers import Ctx
    from repro.models.params import init_params
    from repro.parallel.sharding import TRAIN_RULES
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="t", family="m", d_model=32, n_layers=1,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                      unit=(LayerSpec("attn", "moe"),), n_experts=8,
                      top_k=2, moe_d_ff=16, n_shared_experts=1)
    ctx1 = Ctx(rules=TRAIN_RULES, dtype=jnp.float32, mesh=None)
    ctx8 = Ctx(rules=TRAIN_RULES, dtype=jnp.float32, mesh=mesh)
    p = init_params(M.moe_params(cfg, tp=4), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    ref_out, ref_aux = M.moe_ref(p, x, cfg, ctx1)
    with jax.sharding.set_mesh(mesh):
        ep_out, ep_aux = jax.jit(
            lambda p, x: M.moe_ep(p, x, cfg, ctx8,
                                  capacity_factor=8.0))(p, x)
    np.testing.assert_allclose(np.asarray(ep_out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)
    # aux is computed per shard over LOCAL tokens (GShard/Switch convention)
    # then averaged — only approximately the global load-balance loss
    np.testing.assert_allclose(float(ep_aux), float(ref_aux), rtol=0.1)
    print("PASS")
    """)


def test_moe_ep_expert_perm_preserves_output():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, LayerSpec
    from repro.models import moe as M
    from repro.models.layers import Ctx
    from repro.models.params import init_params
    from repro.parallel.sharding import TRAIN_RULES
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="t", family="m", d_model=32, n_layers=1,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                      unit=(LayerSpec("attn", "moe"),), n_experts=8,
                      top_k=2, moe_d_ff=16)
    ctx = Ctx(rules=TRAIN_RULES, dtype=jnp.float32, mesh=mesh)
    p = init_params(M.moe_params(cfg, tp=4), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    perm = jnp.array([3, 2, 1, 0, 7, 6, 5, 4])   # physical slot per expert
    # permute the expert weights accordingly: slot perm[e] holds expert e
    inv = jnp.argsort(perm)
    p2 = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        p2[k] = p[k][inv]
    with jax.sharding.set_mesh(mesh):
        base, _ = jax.jit(lambda p, x: M.moe_ep(p, x, cfg, ctx,
                                                capacity_factor=8.0))(p, x)
        permed, _ = jax.jit(lambda p, x: M.moe_ep(
            p, x, cfg, ctx, capacity_factor=8.0,
            expert_perm=perm))(p2, x)
    np.testing.assert_allclose(np.asarray(permed), np.asarray(base),
                               rtol=2e-4, atol=2e-4)
    print("PASS")
    """)


def test_flash_decode_seqpar_matches_dense():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import layers as L
    from repro.models.layers import Ctx
    from repro.parallel.sharding import DECODE_RULES
    mesh = make_mesh((2, 4), ("data", "model"))
    B, S, K, G, hd = 4, 64, 2, 2, 16
    H = K * G
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, H, hd))
    ck = jax.random.normal(ks[1], (B, S, K, hd))
    cv = jax.random.normal(ks[2], (B, S, K, hd))
    kn = jax.random.normal(ks[3], (B, K, hd))
    vn = jax.random.normal(ks[4], (B, K, hd))
    pos = jnp.int32(37)
    ctx = Ctx(rules=DECODE_RULES, dtype=jnp.float32, mesh=mesh,
              decode_seqpar=True)
    dense_o, (dk, dv) = L.decode_attn_dense(q, ck, cv, kn, vn, pos)
    with jax.sharding.set_mesh(mesh):
        sp_o, (sk, sv) = jax.jit(lambda *a: L.decode_attn_seqpar(
            *a, ctx=ctx))(q, ck, cv, kn, vn, pos)
    np.testing.assert_allclose(np.asarray(sp_o), np.asarray(dense_o),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(dk), rtol=1e-5)
    print("PASS")
    """)


def test_sharding_trees_drop_nondivisible_axes():
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS
    from repro.parallel.sharding import spec_for, rules_for
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = rules_for(type("C", (), {"fsdp": False})(), "train")
    # batch=1 cannot shard: dp axes dropped
    assert spec_for(("batch", "seq"), rules, mesh, (1, 64)) == PS()
    # heads=6 not divisible by model=4: dropped
    assert spec_for(("embed", "heads", "head_dim"), rules, mesh,
                    (8, 6, 4)) == PS()
    # heads=8 divisible: sharded
    assert spec_for(("embed", "heads", "head_dim"), rules, mesh,
                    (8, 8, 4)) == PS(None, "model")
    print("PASS")
    """)


def test_train_step_runs_on_8_devices():
    """A real (tiny) sharded train step executes end-to-end on 8 devices —
    data x model parallel with real collectives."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs.registry import get_config, make_batch
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import (DistConfig, make_train_step,
                                    param_shardings, shardings_for_batch,
                                    replicated)
    from repro.models.params import init_params
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(get_config("granite_3_2b").smoke(),
                              activation_dtype="float32")
    step, p_specs, o_specs, ctx = make_train_step(cfg, mesh, DistConfig())
    p_sh = param_shardings(p_specs, mesh, ctx.rules)
    o_sh = param_shardings(o_specs, mesh, ctx.rules)
    batch = make_batch(cfg, 32, 4, train=True)
    b_sh = shardings_for_batch(batch, mesh, ctx.rules)
    batch = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
    params = jax.device_put(init_params(p_specs, jax.random.PRNGKey(0)), p_sh)
    opt = jax.device_put(init_params(o_specs, jax.random.PRNGKey(1)), o_sh)
    fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                 out_shardings=(p_sh, o_sh, replicated(mesh)),
                 donate_argnums=(0, 1))
    with jax.sharding.set_mesh(mesh):
        params, opt, m = fn(params, opt, batch)
        params, opt, m = fn(params, opt, batch)
    assert jnp.isfinite(m["loss"]), m
    print("PASS", float(m["loss"]))
    """)


def test_moe_ep_dedup_matches_reference():
    """Dedup-dispatch EP == dense reference at ample capacity; also with a
    placement permutation applied."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, LayerSpec
    from repro.models import moe as M
    from repro.models.layers import Ctx
    from repro.models.params import init_params
    from repro.parallel.sharding import TRAIN_RULES
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="t", family="m", d_model=32, n_layers=1,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                      unit=(LayerSpec("attn", "moe"),), n_experts=8,
                      top_k=3, moe_d_ff=16, n_shared_experts=1)
    ctx1 = Ctx(rules=TRAIN_RULES, dtype=jnp.float32, mesh=None)
    ctx8 = Ctx(rules=TRAIN_RULES, dtype=jnp.float32, mesh=mesh)
    p = init_params(M.moe_params(cfg, tp=4), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
    ref_out, _ = M.moe_ref(p, x, cfg, ctx1)
    with jax.sharding.set_mesh(mesh):
        dd_out, _ = jax.jit(lambda p, x: M.moe_ep_dedup(
            p, x, cfg, ctx8, dest_k=3.0, capacity_factor=8.0))(p, x)
        perm = jnp.array([0, 4, 1, 5, 2, 6, 3, 7])
        inv = jnp.argsort(perm)
        p2 = dict(p)
        for kk in ("w_gate", "w_up", "w_down"):
            p2[kk] = p[kk][inv]
        pd_out, _ = jax.jit(lambda p, x: M.moe_ep_dedup(
            p, x, cfg, ctx8, dest_k=3.0, capacity_factor=8.0,
            expert_perm=perm))(p2, x)
    np.testing.assert_allclose(np.asarray(dd_out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pd_out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)
    print("PASS")
    """)


@pytest.mark.parametrize("mode", ["{}", "{'fused': True, 'async_groups': True}"],
                         ids=["unfused", "fused+waves"])
def test_executed_hier_stream_places_outputs_per_group(mode):
    """The rack/pod stream with one device per class: outputs equal the
    reference, each lives on the device of the group that produced it, and
    blocks really cross devices."""
    _run(f"""
    import jax
    from repro.core.reference import interval_error
    from repro.core.serving import groups_for_platform
    from repro.launch.serve import hierarchical_platform, run_arena_executed
    groups = groups_for_platform(hierarchical_platform())
    assert len(set(groups.values())) == 4, groups
    held, errs = set(), []
    def check(step, report, outputs):
        for name, arr in outputs.items():
            assert arr.devices() == {{groups[report.ran_on[name]]}}, name
            held.add(groups[report.ran_on[name]])
        errs.append(interval_error(step, outputs, 16))
    _, arena = run_arena_executed(6, 3, steps=3, drop_step=1, seed=0,
                                  hier=True, side=16, policies=("dmda",),
                                  check=check, **{mode})
    assert len(errs) == 3 and max(errs) < 1e-6
    assert len(held) > 1 and arena.reports["dmda"].to_dict()["transfers"] > 0
    print("PASS")
    """)
