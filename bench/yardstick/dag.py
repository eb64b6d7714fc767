"""A DAG as the reference reads it: plain names, ops and argument lists.

Each kernel's arguments are listed in order.  An argument is either another
kernel's name or an input key ``<kernel>/in``, a seeded host block.  A DAG
family builds this first and the program's graph from it, so the reference
never reads the program's objects.
"""

from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class Spec:
    ops: dict[str, str]  # kernel -> op, in insertion order
    args: dict[str, list[str]]  # kernel -> its arguments, in order

    @property
    def inputs(self) -> list[str]:
        return sorted({a for v in self.args.values() for a in v if a.endswith("/in")})

    @property
    def exits(self) -> list[str]:
        used = {a for v in self.args.values() for a in v}
        return [n for n in self.ops if n not in used]

    def order(self) -> list[str]:
        """Kernels in an order that puts every argument first."""
        done: set[str] = set()
        out: list[str] = []

        def visit(n: str) -> None:
            stack = [(n, False)]
            while stack:
                k, expanded = stack.pop()
                if k in done:
                    continue
                if expanded:
                    done.add(k)
                    out.append(k)
                    continue
                stack.append((k, True))
                stack.extend((a, False) for a in self.args[k] if a in self.ops)

        for n in self.ops:
            visit(n)
        return out


def lcg(seed: int):
    """The program's generators' 64-bit LCG: ``rnd(n)`` draws from [0, n)."""
    state = [(seed * 6364136223846793005 + 1442695040888963407) % 2**64 or 1]

    def rnd(n: int) -> int:
        state[0] = (state[0] * 6364136223846793005 + 1442695040888963407) % 2**64
        return (state[0] >> 33) % n

    return rnd


def resolve(path: str):
    """``"package.module:name"`` -> that object (a configuration's builders)."""
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)
