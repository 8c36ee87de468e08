"""Final measurement of the width-12 cell: ``characterize_ms_per_chunk``
read where the chunk's 32 genomes go through the kernel over the 2^24-row
cube."""
from bench import host_heap
from bench.layer_metrics.characterize_ms_per_chunk import read  # noqa: F401

# the cell's check runs the reference over the 2^24-row cube
# (bench/host_heap.py); the harness loads this reader before JAX
host_heap.keep()
