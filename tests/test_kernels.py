"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import golden as G, simulate as S
from repro.core.genome import CGPSpec, random_genome
from repro.kernels import ops, ref


# ----------------------------- cgp_sim --------------------------------------

def _assert_partials_close(pk, pr, rtol=1e-5):
    for name in pk._fields:
        if getattr(pk, name) is None is getattr(pr, name):
            continue  # ``moments``: only the kernel's moments call has them
        a, b = np.asarray(getattr(pk, name)), np.asarray(getattr(pr, name))
        np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-6,
                                   err_msg=f"partial {name}")


@pytest.mark.parametrize("width,n_n,block", [(3, 80, 2), (4, 120, 8),
                                             (4, 120, 4), (5, 200, 32)])
def test_cgp_kernel_matches_ref_random(width, n_n, block):
    spec = CGPSpec(n_i=2 * width, n_o=2 * width, n_n=n_n)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(width, "mul"))
    for seed in range(3):
        g = random_genome(jax.random.PRNGKey(seed), spec)
        pk, popk = ops.cgp_eval(g, spec, planes, gvals, gauss_sigma=32.0,
                                block_words=block)
        pr, popr = ref.cgp_eval_ref(g, spec, planes, gvals, 32.0)
        _assert_partials_close(pk, pr)
        np.testing.assert_allclose(np.asarray(popk), np.asarray(popr))


def test_cgp_kernel_exact_multiplier_8bit():
    g, spec = G.array_multiplier(8, n_n=400)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(8, "mul"))
    pk, _ = ops.cgp_eval(g, spec, planes, gvals)
    assert float(pk.abs_sum) == 0 and int(pk.wce_max) == 0
    assert int(pk.err_count) == 0 and int(pk.acc0_bad) == 0
    assert int(pk.count) == 65536


def test_cgp_kernel_vmaps_over_population():
    spec = CGPSpec(n_i=8, n_o=8, n_n=60)
    planes = S.input_planes(spec.n_i)
    gvals = jnp.asarray(G.golden_values(4, "mul"))
    genomes = jax.vmap(lambda k: random_genome(k, spec))(
        jax.random.split(jax.random.PRNGKey(0), 4))
    pk, popk = jax.vmap(
        lambda g: ops.cgp_eval(g, spec, planes, gvals))(genomes)
    for i in range(4):
        gi = jax.tree.map(lambda x: x[i], genomes)
        pr, popr = ref.cgp_eval_ref(gi, spec, planes, gvals, 256.0)
        np.testing.assert_allclose(np.asarray(pk.abs_sum[i]),
                                   np.asarray(pr.abs_sum), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(popk[i]), np.asarray(popr))


# ----------------------------- lut_matmul -----------------------------------

EXACT_LUT = (np.arange(256)[:, None] * np.arange(256)[None, :]).astype(
    np.int32)


@pytest.mark.parametrize("shape", [(8, 16, 8), (128, 128, 128), (7, 130, 5),
                                   (1, 8, 1), (33, 64, 96)])
def test_lut_matmul_exact_lut_equals_int_matmul(shape):
    Mx, K, N = shape
    key = jax.random.PRNGKey(Mx * 1000 + K)
    a = jax.random.randint(key, (Mx, K), 0, 256, dtype=jnp.int32)
    b = jax.random.randint(jax.random.fold_in(key, 1), (K, N), 0, 256,
                           dtype=jnp.int32)
    got = np.asarray(ops.lut_matmul(a, b, jnp.asarray(EXACT_LUT)))
    want = np.asarray(a) @ np.asarray(b)
    assert (got == want).all()


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint8, jnp.int8])
def test_lut_matmul_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    hi = 127 if dtype == jnp.int8 else 255
    a = jax.random.randint(key, (16, 32), 0, hi + 1, jnp.int32).astype(dtype)
    b = jax.random.randint(jax.random.fold_in(key, 1), (32, 8), 0, hi + 1,
                           jnp.int32).astype(dtype)
    got = np.asarray(ops.lut_matmul(a, b, jnp.asarray(EXACT_LUT)))
    want = np.asarray(ref.lut_matmul_ref(a, b, jnp.asarray(EXACT_LUT)))
    assert (got == want).all()


def test_lut_matmul_approximate_lut_matches_ref():
    rng = np.random.default_rng(0)
    lut = EXACT_LUT + rng.integers(-8, 8, EXACT_LUT.shape)  # noisy circuit
    key = jax.random.PRNGKey(3)
    a = jax.random.randint(key, (24, 48), 0, 256, dtype=jnp.int32)
    b = jax.random.randint(jax.random.fold_in(key, 1), (48, 16), 0, 256,
                           dtype=jnp.int32)
    got = np.asarray(ops.lut_matmul(a, b, jnp.asarray(lut)))
    want = np.asarray(ref.lut_matmul_ref(a, b, jnp.asarray(lut)))
    assert (got == want).all()


# ----------------------------- flash attention ------------------------------

@pytest.mark.parametrize("shape", [
    (2, 4, 2, 128, 32), (1, 8, 8, 256, 64), (1, 4, 1, 64, 16),
    (2, 2, 2, 96, 8)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_naive(shape, causal):
    B, Hq, Hkv, Ssz, D = shape
    key = jax.random.PRNGKey(B * 100 + Ssz)
    q = jax.random.normal(key, (B, Hq, Ssz, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, Ssz, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, Ssz, D))
    got = ops.flash_attention(q, k, v, causal=causal, bq=32, bkv=32)
    group = Hq // Hkv
    kf = jnp.repeat(k, group, axis=1).reshape(B * Hq, Ssz, D)
    vf = jnp.repeat(v, group, axis=1).reshape(B * Hq, Ssz, D)
    want = ref.attention_ref(q.reshape(B * Hq, Ssz, D), kf, vf,
                             causal=causal).reshape(B, Hq, Ssz, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 2, 64, 16)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 16)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 16)).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=True, bq=32, bkv=32)
    want = ref.attention_ref(q.reshape(2, 64, 16), k.reshape(2, 64, 16),
                             v.reshape(2, 64, 16), causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(2, 64, 16),
        np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 2e-5, atol=2e-2)


# --------------------------------------------------------------------------
# The kernel's one execution point: genome-major grid, the shape rule's
# block, a pad of 8 compiled and 1 interpreted
# --------------------------------------------------------------------------

@pytest.mark.parametrize("moments", [False, True])
@pytest.mark.parametrize("interpret", [True, False])
def test_kernel_execution_point(interpret, moments, monkeypatch):
    from repro.core.genome import Genome
    from repro.kernels import cgp_sim
    seen = []
    real = cgp_sim.cgp_sim_metrics_batched

    def spy(*args, **kw):
        seen.append(kw)
        return real(*args, **kw)

    monkeypatch.setattr(cgp_sim, "cgp_sim_metrics_batched", spy)
    width, R = 2, 8
    spec = CGPSpec(n_i=2 * width, n_o=2 * width, n_n=40)
    W = max(1, (1 << spec.n_i) // 32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    jax.eval_shape(
        lambda n, o, p, g: ops.cgp_eval_batched(
            Genome(n, o), spec, p, g, interpret=interpret, moments=moments),
        i32(R, spec.n_n, 3), i32(R, spec.n_o), i32(spec.n_i, W),
        i32(W * 32))
    (kw,) = seen
    assert kw["block_words"] is None  # the kernel's own shape rule
    assert kw["r_tile"] == (1 if interpret else 8)
    assert kw["moments"] is moments and kw["interpret"] is interpret
    assert set(kw) == {"n_i", "n_n", "n_o", "gauss_sigma", "block_words",
                       "r_tile", "moments", "interpret"}
