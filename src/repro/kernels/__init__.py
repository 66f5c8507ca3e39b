"""Pallas TPU kernels for the perf-critical hot-spots.

Each kernel ships as <name>.py (pl.pallas_call + explicit BlockSpec VMEM
tiling) with its jnp oracle in ref.py; ops.py dispatches the aggregation
kernel, the one on the FL path.  The CPU tests run them in interpret mode;
``tests/test_chip_compile.py`` compiles ``fl_aggregate`` for a described
TPU v5e, and ``chip_smoke.py`` runs it on the chip against ref.py.
"""
from . import ops, ref
from .fl_aggregate import fl_aggregate
from .flash_attention import flash_attention
from .selective_scan import selective_scan

__all__ = ["ops", "ref", "fl_aggregate", "flash_attention", "selective_scan"]
