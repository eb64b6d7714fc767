"""Compile rehearsals: the Pallas kernels of the executed path, compiled for a
described TPU v5e at the widths the chip smoke run uses.  Nothing runs —
the TPU compiler only has to accept each program (Mosaic refuses unaligned
blocks and oversized VMEM use that interpret mode never sees) and the
program must really contain the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
the worker that runs this file may load the TPU library."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.arena import _request_chain
from repro.core.executor import attach_request_kernels
from repro.core.graph import TaskGraph
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matadd import matadd
from repro.kernels.matmul import gemm_nt, matmul
from repro.kernels.wkv6 import wkv6

SIDE = 2048  # chip_smoke.py's kernel side: one 16 MiB f32 block


@pytest.fixture(scope="module")
def one_chip():
    # only a missing TPU library skips; any failure of an installed one fails
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the persistent
    # cache without that chip: keep these programs out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def pallas_ops(monkeypatch):
    """Steer ``ops`` onto its TPU branch (Pallas, not interpreted)."""
    monkeypatch.setattr(ops, "KERNEL_MODE", "auto")
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _compile(fn, shapes, sharding):
    specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*specs).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_compiles_for_v5e(one_chip, dtype):
    sq = ((SIDE, SIDE), dtype)
    _assert_kernel(_compile(matmul, [sq, sq], one_chip))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_is_one_kernel_named_matmul(one_chip, dtype):
    """With the blocks it chooses at 2048, the program is the Pallas call
    alone (no pad, cast or copy beside it), and the op keeps the name
    ``matmul.<n>`` that the benchmark's roofline reader matches."""
    sq = ((SIDE, SIDE), dtype)
    text = _compile(matmul, [sq, sq], one_chip).as_text()
    entry = text[text.index("\nENTRY "):]
    ops_ = re.findall(r"^\s+(?:ROOT )?%(\S+) = (.*)$", entry, re.M)
    work = [name for name, rest in ops_ if "parameter(" not in rest]
    assert len(work) == 1 and re.fullmatch(r"matmul\.\d+", work[0])
    assert 'custom_call_target="tpu_custom_call"' in dict(ops_)[work[0]]


def test_gemm_nt_is_one_kernel_named_gemm_nt(one_chip):
    """The tile Cholesky's update C - A @ B^T at 2048 f32: the Pallas call
    alone (no transpose of B beside it), named ``gemm_nt.<n>`` as the
    benchmark's roofline reader matches it."""
    sq = ((SIDE, SIDE), jnp.float32)
    text = _compile(gemm_nt, [sq, sq, sq], one_chip).as_text()
    entry = text[text.index("\nENTRY "):]
    ops_ = re.findall(r"^\s+(?:ROOT )?%(\S+) = (.*)$", entry, re.M)
    work = [name for name, rest in ops_ if "parameter(" not in rest]
    assert len(work) == 1 and re.fullmatch(r"gemm_nt\.\d+", work[0])
    assert 'custom_call_target="tpu_custom_call"' in dict(ops_)[work[0]]


@pytest.mark.parametrize("op", ["potrf", "trsm", "diag_shift"])
def test_tile_panel_ops_compile_for_v5e(one_chip, op):
    """XLA's Cholesky and triangular solve of a 2048 f32 tile, and the
    diagonal tile's symmetrization, for the described chip."""
    sq = ((SIDE, SIDE), jnp.float32)
    fn = {"potrf": ops.potrf, "trsm": ops.trsm,
          "diag_shift": lambda x: ops.diag_shift(x, shift=3.0)}[op]
    n_args = 2 if op == "trsm" else 1
    compiled = _compile(fn, [sq] * n_args, one_chip)
    assert compiled.out_info.shape == (SIDE, SIDE)


def test_matadd_compiles_for_v5e(one_chip):
    sq = ((SIDE, SIDE), jnp.float32)
    _assert_kernel(_compile(matadd, [sq, sq], one_chip))


@pytest.mark.parametrize("side", [48, 1000])
def test_ops_matadd_pads_unaligned_side(one_chip, pallas_ops, side):
    sq = ((side, side), jnp.float32)
    compiled = _compile(ops.matadd, [sq, sq], one_chip)
    _assert_kernel(compiled)
    assert compiled.out_info.shape == (side, side)


def test_fused_request_chain_compiles_for_v5e(one_chip, pallas_ops):
    """One request's prefill -> 8 decodes as ``build_chain`` fuses it for a
    super-step, with the kernels the executed path attaches."""
    g = TaskGraph()
    _request_chain(g, 0, 8, costs_prefill={"big": 1.0},
                   costs_decode={"big": 1.0}, kv_bytes=0)
    attach_request_kernels(g, 8)  # attaches fns; the seed size is irrelevant
    order = g.topo_order()
    steps = [(g.nodes[order[0]].fn, [("ext", 0)])]
    steps += [(g.nodes[n].fn, [("mem", i)]) for i, n in enumerate(order[1:])]
    chain = ops.build_chain(steps, keep=[len(steps) - 1])
    compiled = _compile(chain, [((SIDE, SIDE), jnp.float32)], one_chip)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2  # matmul and matadd kernels


def test_flash_attention_compiles_for_v5e(one_chip):
    """granite-3-2b width: 32 heads of 128 (bf16), 2048 tokens."""
    qkv = ((1, 32, 2048, 128), jnp.bfloat16)
    _assert_kernel(_compile(flash_attention, [qkv, qkv, qkv], one_chip))


def test_wkv6_compiles_for_v5e_multihead(one_chip):
    """rwkv6-3b width: 40 heads of 64; H > 1 exercises the ``u`` block."""
    B, H, S, N = 1, 40, 512, 64
    seq = ((B, H, S, N), jnp.float32)
    _assert_kernel(
        _compile(wkv6, [seq, seq, seq, seq, ((H, N), jnp.float32)], one_chip))
