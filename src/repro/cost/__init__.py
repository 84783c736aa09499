"""Instruction-level memory-traffic accounting over optimized HLO.

Static byte accounting of compiled programs: walks
``Compiled.as_text()`` with loop trip counts multiplied through and
charges each executed instruction for the bytes it actually moves (see
``accounting`` for the rule table and ``README.md`` for the mapping to
the paper's cost model).

Public API:

  * ``analyze_text(hlo) -> Cost``      -- flops / bytes / coll / by_op
  * ``analyze_compiled(compiled)``     -- same, from a jax Compiled
  * ``attribute(hlo, top=20)``         -- per-(opcode, shape) byte tally
  * ``xla_cost_analysis(compiled)``    -- version-normalized raw XLA dict
  * ``Cost``, ``HloCostModel``, ``shape_bytes`` -- building blocks
"""

from __future__ import annotations

from repro.cost.accounting import (COLLECTIVE_OPS, Cost,  # noqa: F401
                                   HloCostModel)
from repro.cost.parser import (Instr, Module, parse_module,  # noqa: F401
                               shape_bytes, shape_dims)
from repro.cost.xla import (xla_bytes_accessed, xla_cost_analysis,  # noqa: F401
                            xla_flops)

__all__ = [
    "COLLECTIVE_OPS", "Cost", "HloCostModel", "Instr", "Module",
    "analyze_text", "analyze_compiled", "attribute", "parse_module",
    "shape_bytes", "shape_dims", "xla_bytes_accessed", "xla_cost_analysis",
    "xla_flops",
]


def analyze_text(hlo_text: str) -> Cost:
    return HloCostModel(hlo_text).entry_cost()


def analyze_compiled(compiled) -> Cost:
    return analyze_text(compiled.as_text())


def attribute(hlo_text: str, top: int = 20, min_bytes: float = 1e11):
    return HloCostModel(hlo_text).attribute(top=top, min_bytes=min_bytes)
