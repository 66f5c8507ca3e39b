"""Device→host copies per runner call, read as ``d2h_per_call.sim`` reads
them (counters ``engine.d2h_transfers`` over ``engine.calls``; the calls
run again for the check count as any other)."""
from chipbench import bench

read = bench.load_reader("d2h_per_call.sim").read
