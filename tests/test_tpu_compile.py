"""Compile the main path for a described TPU v5e — no chip needed.

The TPU compiler ships with jaxlib and compiles for a topology that is
described, not attached.  These tests catch what interpret mode cannot: a
Pallas block shape or memory access Mosaic refuses, or a sharded program the
partitioner rejects.  Nothing runs, so they say nothing about results or
times (``chip_smoke.py`` runs the same programs on a chip).

Only one process may load the TPU library at a time, so the topology is
described inside a module fixture (never at import) and every compile stays
in this process; all such compiles live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import sweep
from repro.core.evolve import EvolveConfig
from repro.core.genome import CGPSpec
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


@pytest.mark.parametrize("layout", ["genome_major", "cube_major"])
def test_cgp_kernel_compiles_at_paper_shape(one_chip, layout):
    i32 = lambda *s: _shape(s, jnp.int32, one_chip)
    fn = jax.jit(lambda n, o, p, g: cgp_sim.cgp_sim_metrics_batched(
        n, o, p, g, n_i=N_I, n_n=N_N, n_o=N_O, block_words=512,
        layout=layout, interpret=False))
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
        layout="genome_major", moments=moments, interpret=False))
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


def test_cube_sharded_sweep_chunk_compiles_on_four_chips(topo,
                                                         compiled_kernels):
    """The ``SweepConfig(model_axis="model")`` chunk program over a 2x2 host:
    the cube splits four ways, every chip runs the fused kernel on its slice
    and the partials all-reduce."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4),
                ("pod", "data", "model"))
    spec = CGPSpec(n_i=N_I, n_o=N_O, n_n=N_N)
    cfg = EvolveConfig(generations=2, lam=LAM, backend="pallas",
                       layout="genome_major")
    fn = sweep._sharded_chunk_fn(mesh, "model", spec, cfg)
    rep = NamedSharding(mesh, P())
    args = (_shape((N_N, 3), jnp.int32, rep),            # golden nodes
            _shape((N_O,), jnp.int32, rep),              # golden outs
            _shape((CHUNK, 7), jnp.float32, rep),        # thresholds
            _shape((N_I, W), jnp.int32,
                   NamedSharding(mesh, P(None, "model"))),  # input planes
            _shape((W * 32,), jnp.int32,
                   NamedSharding(mesh, P("model"))),     # golden values
            _shape((), jnp.float32, rep),                # golden power
            _shape((CHUNK, 2), jnp.uint32, rep))         # PRNG keys
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


def test_approx_matmul_compiles(one_chip):
    """The LUT serving matmul (XLA gather) compiles for the chip."""
    f32 = lambda *s: _shape(s, jnp.float32, one_chip)
    compiled = jax.jit(quant.approx_matmul).lower(
        f32(64, 256), f32(256, 512),
        _shape((256, 256), jnp.int32, one_chip)).compile()
    assert compiled.as_text()
