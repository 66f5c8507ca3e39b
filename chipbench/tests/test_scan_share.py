"""The ``scan_share.lm_sim`` reader on hand-made trace reductions: the Mamba
scan kernels' share of busy device time where the trace holds them, and
nothing where the program ran the associative scan."""
from __future__ import annotations

import pytest

from chipbench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from chipbench import bench, trace


def _scan_trace(with_kernels: bool):
    call = ' custom_call_target="tpu_custom_call"'
    ops = {"%fusion.7 = f32[10,1024,5120]{2,1,0} fusion(f32[] %p)": 3.0,
           # a consumer of the kernel's output names it among its operands
           "%fusion.9 = f32[10,1024,5120]{2,1,0} fusion(f32[10,128,320,128]"
           "{3,2,1,0} %jvp_vmap_jit_mamba_scan_fwd___.2)": 0.5,
           "%fl_aggregate.5 = f32[1,8192]{1,0} custom-call(f32[10,1]{1,0} "
           "%a)," + call: 0.25}
    if with_kernels:
        ops.update({
            "%jvp_vmap_jit_mamba_scan_fwd___.2 = (f32[10,1,128,320,128]) "
            "custom-call(%bitcast.2)," + call: 1.25,
            "%vmap_jit_mamba_scan_fwd__.1 = (f32[4,1,128,320,128]) "
            "custom-call(%bitcast.3)," + call: 0.5,
            "%transpose_jvp_vmap_jit_mamba_scan_bwd____.1 = (f32[10,1,128,"
            "320,128]) custom-call(%bitcast.9)," + call: 2.5})
    counts = {k: 2 for k in ops}
    return trace.Reduced(10.0, 8.0, ops, counts, [])


def test_scan_share_reads_the_scan_kernels_share_of_busy_time():
    share = bench.load_reader("scan_share.lm_sim")
    red = _scan_trace(True)
    assert share.scan_seconds(red) == (pytest.approx(4.25), 6)
    assert share.read({"trace": red}) == pytest.approx(100 * 4.25 / 8.0)


def test_scan_share_reads_nothing_without_the_kernels():
    share = bench.load_reader("scan_share.lm_sim")
    assert share.read({"trace": _scan_trace(False)}) is None
    assert share.read({"trace": None}) is None
    idle = trace.Reduced(10.0, 0.0, {}, {}, [])
    assert share.read({"trace": idle}) is None
