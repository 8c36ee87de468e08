"""Compile the main path for a described TPU v5e — no chip needed.

The TPU compiler ships with jaxlib and compiles for a topology that is
described, not attached.  These tests catch what interpret mode cannot: a
Pallas block shape or memory access Mosaic refuses.  Nothing runs, so they
say nothing about results or times (``chip_smoke.py`` and the benchmark
cells run the same programs on a chip).

Only one process may load the TPU library at a time, so the topology is
described inside a module fixture (never at import) and every compile stays
in this process; all such compiles live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sweep
from repro.core.evolve import EvolveConfig
from repro.core.genome import CGPSpec, Genome
from repro.kernels import cgp_sim, ops
from repro.models import quant

# the paper's problem: 8x8 multiplier, 400 nodes, 2^16 cube = 2048 words;
# one sweep chunk of 32 runs x lambda 8 = 256 genomes per dispatch
WIDTH, N_N, R, CHUNK, LAM = 8, 400, 256, 32, 8
N_I = N_O = 2 * WIDTH
W = (1 << N_I) // 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernel wrappers pick interpret mode from the CPU backend this
    process runs on; a described-chip compile needs the compiled kernel."""
    monkeypatch.setattr(ops, "_INTERPRET_DEFAULT", False)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_cgp_kernel_compiles_at_paper_shape(one_chip):
    i32 = lambda *s: _shape(s, jnp.int32, one_chip)
    fn = jax.jit(lambda n, o, p, g: cgp_sim.cgp_sim_metrics_batched(
        n, o, p, g, n_i=N_I, n_n=N_N, n_o=N_O, block_words=512,
        interpret=False))
    compiled = fn.lower(i32(R, N_N, 3), i32(R, N_O), i32(N_I, W),
                        i32(W * 32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("moments", [False, True])
def test_cgp_kernel_compiles_at_width_12(one_chip, moments):
    """The exhaustive 12x12 multiplier: 900 nodes, the 2^24-row cube in
    1,024 blocks of 512 words, 256 genomes a dispatch, the exact carried
    digits (and with ``moments`` those of the sum of squares)."""
    n_i = n_o = 24
    w12, n_n = (1 << n_i) // 32, 900
    i32 = lambda *s: _shape(s, jnp.int32, one_chip)
    fn = jax.jit(lambda n, o, p, g: cgp_sim.cgp_sim_metrics_batched(
        n, o, p, g, n_i=n_i, n_n=n_n, n_o=n_o, block_words=512,
        moments=moments, interpret=False))
    compiled = fn.lower(i32(R, n_n, 3), i32(R, n_o), i32(n_i, w12),
                        i32(w12 * 32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width,n_n,moments,block", [
    (WIDTH, N_N, False, 2048),  # the whole cube a block, (16, 128)
    (WIDTH, N_N, True, 512),    # the moments carry: (4, 128)
    (12, 900, False, 1024),     # the kernel carries: (8, 128)
    (12, 900, True, 512),
])
def test_cgp_kernel_compiles_dense_default_blocks(one_chip, width, n_n,
                                                  moments, block):
    """The blocks the kernel takes by default, each wire sublane-dense."""
    n_i = n_o = 2 * width
    w = (1 << n_i) // 32
    assert cgp_sim.default_block_words(w, n_o, moments) == block
    i32 = lambda *s: _shape(s, jnp.int32, one_chip)
    fn = jax.jit(lambda n, o, p, g: cgp_sim.cgp_sim_metrics_batched(
        n, o, p, g, n_i=n_i, n_n=n_n, n_o=n_o, moments=moments,
        interpret=False))
    compiled = fn.lower(i32(R, n_n, 3), i32(R, n_o), i32(n_i, w),
                        i32(w * 32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_characterize_chunk_compiles_at_width_12(one_chip,
                                                 compiled_kernels):
    """The sweep's final measurement of a width-12 chunk goes through the
    kernel: no (genomes, wires, words) array of its own."""
    n_i = n_o = 24
    w12, n_n = (1 << n_i) // 32, 900
    spec = CGPSpec(n_i=n_i, n_o=n_o, n_n=n_n)
    i32 = lambda *s: _shape(s, jnp.int32, one_chip)
    compiled = sweep.characterize_chunk.lower(
        spec, 256.0, i32(CHUNK, n_n, 3), i32(CHUNK, n_o),
        _shape((CHUNK, 7), jnp.float32, one_chip), i32(n_i, w12),
        i32(w12 * 32), _shape((), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the cube's inputs and golden values, and its transpose: well under
    # the chip's 16 GB, where a simulation of 32 genomes held 62 GB
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1 << 30


@pytest.mark.parametrize("n_i,n_o,n_n", [
    pytest.param(16, 16, 400, id="mul8"),
    pytest.param(16, 9, 100, id="add8"),
    pytest.param(24, 24, 900, id="mul12"),
])
def test_sweep_chunk_compiles_at_cell_shape(one_chip, compiled_kernels, n_i,
                                            n_o, n_n):
    """The chunk program each benchmark cell runs: a chunk of 32 runs x
    lambda 8 over the whole cube on one chip, the kernel compiled."""
    spec = CGPSpec(n_i=n_i, n_o=n_o, n_n=n_n)
    cfg = EvolveConfig(generations=2, lam=LAM, backend="pallas")
    w = (1 << n_i) // 32
    i32 = lambda *s: _shape(s, jnp.int32, one_chip)
    compiled = sweep.evolve_chunk.lower(
        spec, cfg, Genome(i32(n_n, 3), i32(n_o)),
        _shape((CHUNK, 7), jnp.float32, one_chip), i32(n_i, w),
        i32(w * 32), _shape((), jnp.float32, one_chip),
        _shape((CHUNK, 2), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_approx_matmul_compiles(one_chip):
    """The LUT serving matmul (XLA gather) compiles for the chip."""
    f32 = lambda *s: _shape(s, jnp.float32, one_chip)
    compiled = jax.jit(quant.approx_matmul).lower(
        f32(64, 256), f32(256, 512),
        _shape((256, 256), jnp.int32, one_chip)).compile()
    assert compiled.as_text()
