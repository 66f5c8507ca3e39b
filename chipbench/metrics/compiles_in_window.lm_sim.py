"""Programs JAX traced, compiled or loaded from its cache inside the
window, read as ``compiles_in_window.sim`` reads them.  The cell's runner
program takes minutes to compile, so one retrace would take the window."""
from chipbench import bench

read = bench.load_reader("compiles_in_window.sim").read
