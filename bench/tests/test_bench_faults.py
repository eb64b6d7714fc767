"""A run whose timed path is broken underneath comes out not correct: once
for each fault the cells can have.  The harness's look for a chip is
skipped; everything else of a run is driven as on the chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest
from helpers import run_cell

from repro.core import executor as ex
from repro.kernels import ops

# the kernel each cell's DAGs run most: decode chains' matadd, the MM task's matmul
KERNEL = {"serve-flat.churn": "matadd", "paper-task.mm": "matmul"}


def _assert_caught(r):
    assert not r["correct"], r["checks"]
    assert r["failed"] >= 1


@pytest.mark.parametrize("workload", sorted(KERNEL))
def test_step_returning_its_state_unchanged(workload, monkeypatch):
    monkeypatch.setattr(ops, KERNEL[workload], lambda a, b: a)
    _assert_caught(run_cell(workload))


@pytest.mark.parametrize("workload", sorted(KERNEL))
def test_answer_altered_where_produced(workload, monkeypatch):
    real = getattr(ops, KERNEL[workload])

    def altered(a, b):
        out = real(a, b)
        return out.at[0, 0].add(jnp.max(jnp.abs(out)))

    monkeypatch.setattr(ops, KERNEL[workload], altered)
    _assert_caught(run_cell(workload))


@pytest.mark.parametrize("workload", sorted(KERNEL))
def test_half_the_outputs_left_out(workload, monkeypatch):
    real = ex.ExecSession.result

    def half(self):
        res = real(self)
        for n in sorted(res.outputs)[::2]:
            del res.outputs[n]
        return res

    monkeypatch.setattr(ex.ExecSession, "result", half)
    r = run_cell(workload)
    _assert_caught(r)
    assert r["checks"]["missing_blocks"]["value"] > 0


_FOUR_DEVICES = r"""
import json, sys
sys.path[:0] = [sys.argv[1]]
import conftest  # noqa: F401  (puts the harness and the program on the path)
import jax.numpy as jnp
from helpers import run_cell
from repro.core import executor as ex

out = [run_cell("serve-rackpod.churn")]
real = ex.ExecSession._pull

def no_exchange(self, key, nbytes, grp, dev, kind, now=None):
    moved = real(self, key, nbytes, grp, dev, kind, now)
    if moved:  # the copy is booked but its data never crosses
        copy = self.valid[key][grp]
        self.valid[key][grp] = jnp.zeros_like(copy, device=dev)
    return moved

ex.ExecSession._pull = no_exchange
out.append(run_cell("serve-rackpod.churn"))
print(json.dumps([{k: r[k] for k in ("correct", "failed", "device", "checks")} for r in out]))
"""


def test_exchange_between_chips_left_out():
    """The four-chip cell on four virtual CPU devices: sound, then with every
    pull between groups booked but its data left behind."""
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _FOUR_DEVICES, str(here)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, broken = json.loads(proc.stdout.strip().splitlines()[-1])
    assert sound["device"]["count"] == 4
    assert sound["correct"], sound["checks"]
    _assert_caught(broken)
