"""Both cells' chunk program compiles for a described TPU v5e, at the
cell's own shapes, with the kernel compiled by Mosaic -- no chip needed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.  Nothing runs, so this
says nothing about results or times.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import cells


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def described_chip(monkeypatch):
    """Compile the kernel (not its interpreter) and keep the compile out of
    the persistent cache, which cannot read it back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_INTERPRET_DEFAULT", False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("name", ["mul8_paper.fig_grid",
                                  "add8_evoapprox.fig_grid"])
def test_chunk_program_compiles(name, one_chip, described_chip):
    from repro.core import sweep
    from repro.core.evolve import EvolveConfig
    from repro.core.genome import CGPSpec, Genome

    c = cells.resolve(name).config
    chunk = sweep.SweepConfig().chunk_size
    spec = CGPSpec(n_i=c["n_i"], n_o=c["n_o"], n_n=c["n_n"])
    ecfg = EvolveConfig(generations=c["generations"], lam=c["lam"],
                        mutation_rate=c["mutation_rate"], backend="pallas")
    # the layout "auto" resolves to on a TPU (no table entry: genome-major)
    ecfg = dataclasses.replace(ecfg, layout="genome_major")
    words = (1 << c["n_i"]) // 32
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    golden = Genome(s((c["n_n"], 3), jnp.int32), s((c["n_o"],), jnp.int32))
    compiled = sweep.evolve_chunk.lower(
        spec, ecfg, golden, s((chunk, 7), jnp.float32),
        s((c["n_i"], words), jnp.int32), s((words * 32,), jnp.int32),
        s((), jnp.float32), s((chunk, 2), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
