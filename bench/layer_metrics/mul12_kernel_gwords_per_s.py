"""CGP kernel rate of the width-12 cell: ``cgp_kernel_gwords_per_s`` read
where every candidate walks 1,024 blocks of the 2^24-row cube and the
kernel carries its exact sums at n_o 24."""
from bench import host_heap
from bench.layer_metrics.cgp_kernel_gwords_per_s import read  # noqa: F401

# the cell's check runs the reference over the 2^24-row cube
# (bench/host_heap.py); the harness loads this reader before JAX
host_heap.keep()
