"""The roofline analyzers: jaxpr FLOP walker (scan-aware) and HLO
collective/memory walker (loop-multiplied)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import flops as F
from repro.launch import hlo as H


S64 = jax.ShapeDtypeStruct((64, 64), jnp.float32)


def test_dot_general_flops_exact():
    assert F.count_step_flops(lambda a, b: a @ b, S64, S64) == 2 * 64 ** 3


def test_grad_counts_backward():
    n = F.count_step_flops(jax.grad(lambda a, b: (a @ b).sum(),
                                    argnums=(0, 1)), S64, S64)
    assert n == pytest.approx(3 * 2 * 64 ** 3, rel=0.05)


def test_scan_multiplies_body():
    def f(a, x):
        body = lambda c, _: (jnp.tanh(c @ a), None)
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y.sum()
    n1 = F.count_step_flops(f, S64, S64)
    assert n1 == pytest.approx(10 * 2 * 64 ** 3, rel=0.05)


def test_remat_scan_counts_recompute():
    def f(a, x):
        body = lambda c, _: (jnp.tanh(c @ a), None)
        y, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=10)
        return y.sum()
    n = F.count_step_flops(jax.grad(f), S64, S64)
    # fwd (1x) + recompute (1x) + bwd (2x) = 4 matmuls per layer
    assert n == pytest.approx(4 * 10 * 2 * 64 ** 3, rel=0.1)


def test_peak_live_bytes_orders_sanely():
    def f(a, b):
        return (a @ b).sum()
    peak = F.step_peak_bytes(f, S64, S64)
    assert 2 * 64 * 64 * 4 <= peak <= 16 * 64 * 64 * 4


def test_memory_model_counts_dots_not_elementwise():
    def f(a, b):
        c = a @ b                 # counted: 3 x 16 KiB
        d = jnp.tanh(c) + 1.0     # fused: free
        return d
    jx = jax.make_jaxpr(f)(S64, S64)
    m = F.jaxpr_memory_bytes(jx.jaxpr)
    assert m == 3 * 64 * 64 * 4


def test_memory_model_fusedkernel_region_is_io_only():
    from repro.models.layers import fusedkernel_flash_fwd
    import math
    B, Sq, K, G, hd = 1, 256, 2, 2, 32
    q = jax.ShapeDtypeStruct((B, Sq, K, G, hd), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, Sq, K, hd), jnp.float32)

    def f(q, k, v):
        out, lse = fusedkernel_flash_fwd(q, k, v, 0, causal=True,
                                         scale=1.0 / math.sqrt(hd), Cq=64,
                                         Ck=64, logit_cap=0.0)
        return out
    jx = jax.make_jaxpr(f)(q, kv, kv)
    m = F.jaxpr_memory_bytes(jx.jaxpr)
    io = (B * Sq * K * G * hd * 2 + 2 * B * Sq * K * hd) * 4 \
        + B * K * G * Sq * 4 + 4   # q,out + k,v + lse + q_offset
    assert m <= io * 1.05
    # flops still counted fully (scores + pv per block)
    fl = F.jaxpr_flops(jx.jaxpr)
    assert fl >= 2 * 2 * B * K * G * Sq * Sq * hd * 0.9


# -- HLO walker ----------------------------------------------------------------

def test_shape_bytes_parsing():
    assert H.shape_bytes("bf16[8,4096,2048]{2,1,0}") == 8 * 4096 * 2048 * 2
    assert H.shape_bytes("f32[]") == 4
    assert H.shape_bytes("(s32[], f32[4,16]{1,0})") == 4 + 4 * 16 * 4


def test_hlo_walker_multiplies_while_loops():
    from jax.sharding import PartitionSpec as PS, NamedSharding
    # needs >1 device for a collective; skip on this 1-device session —
    # the multidevice subprocess test covers it
    if len(jax.devices()) > 1:
        pytest.skip("covered elsewhere")
    def f(x):
        body = lambda c, _: (jnp.tanh(c @ c), None)
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y.sum()
    comp = jax.jit(f).lower(S64).compile()
    stats = H.analyze(comp.as_text())
    # memory bytes must reflect ~7 x the dot traffic
    assert stats["mem_bytes"] >= 7 * 2 * 64 * 64 * 4
    assert stats["collectives"]["total"] == 0


def test_hlo_collectives_on_forced_multidevice():
    """Spawn a subprocess with 8 host devices; count in-loop all-reduces."""
    import subprocess, sys, os, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as PS, NamedSharding
        from repro.launch import hlo as H
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        w_sh = NamedSharding(mesh, PS(None, "model"))
        x_sh = NamedSharding(mesh, PS("data", None))
        def f(w, x):
            def body(c, _):
                y = jnp.tanh(c @ w)   # contract sharded dim -> all-reduce?
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, PS("data", None)))
                return y @ w.T, None
            y, _ = jax.lax.scan(body, x, None, length=5)
            return y.sum()
        s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        xs = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        with mesh:
            comp = jax.jit(f, in_shardings=(w_sh, x_sh)).lower(s, xs).compile()
        st = H.analyze(comp.as_text())
        c = st["collectives"]
        assert c["total"] > 0, c
        # in-loop collectives are multiplied by the trip count (5)
        assert c["count"] >= 5, c
        print("OK", c["count"], c["total"])
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.join(os.path.dirname(__file__),
                                                   ".."))
    assert "OK" in r.stdout, (r.stdout, r.stderr[-2000:])
