"""Distributed correctness on fake multi-device meshes (subprocesses so the
main test process keeps its single real device, per the task brief)."""
import pytest

from conftest import run_subprocess


@pytest.mark.slow
def test_distributed_lm_matches_single_device():
    out = run_subprocess("""
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, LayerSpec, MoEConfig, SSMConfig
from repro.models import model as M
from repro.parallel import ctx
mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'))
cfg = ModelConfig(name='hyb', n_layers=4, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=256,
                  period=(LayerSpec(kind='ssm'), LayerSpec(kind='attn', moe=True)),
                  ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
                  moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                                capacity_factor=2.0), remat=True)
key = jax.random.PRNGKey(0)
params = M.init_params(key, cfg)
toks = jax.random.randint(key, (8, 32), 0, cfg.vocab)
ref, _ = M.forward_train(params, toks, cfg)
with ctx.use_mesh(mesh):
    shardings = ctx.map_specs(lambda s: ctx.named_sharding(tuple(s)),
                              M.param_specs(cfg))
    p_sh = jax.device_put(params, shardings)
    t_sh = jax.device_put(toks, ctx.named_sharding(('dp', None)))
    got, _ = jax.jit(lambda p, t: M.forward_train(p, t, cfg))(p_sh, t_sh)
err = np.abs(np.asarray(got) - np.asarray(ref)).max()
assert err < 1e-3, err
print('DIST-LM-OK', err)
""")
    assert "DIST-LM-OK" in out


@pytest.mark.slow
def test_evolve_sharded_runs_and_improves():
    out = run_subprocess("""
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from repro.core import golden as G, simulate as S, metrics as MM
from repro.core.evolve import EvolveConfig, evolve_sharded, make_island_keys
from repro.core.fitness import ConstraintSpec
from repro.core.power import circuit_cost_from_probs
from repro.parallel import ctx
mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'))
gold, spec = G.array_multiplier(4, n_n=120)
planes = S.input_planes(spec.n_i)
gvals = jnp.asarray(G.golden_values(4, 'mul'))
wires = S.simulate_planes(gold, spec, planes)
probs = S.signal_probabilities(wires[spec.n_i:], spec.n_inputs_total)
gpower = circuit_cost_from_probs(gold, spec, probs).power
cfg = EvolveConfig(generations=150, lam=4, migrate_every=32)
# two pods = two different constraint configurations (the paper's sweep)
thr = jnp.stack([jnp.asarray(ConstraintSpec(mae=2.0).thresholds()),
                 jnp.asarray(ConstraintSpec(mae=0.5, er=60.0).thresholds())])
keys = make_island_keys(0, 4)  # data axis: 2 pods x 2 islands... 4 islands total? no: data=2 -> 2 per pod
keys = make_island_keys(0, 2)
with ctx.use_mesh(mesh):
    fn = evolve_sharded(mesh, spec, cfg, gold, thr, gpower, pod_axis='pod')
    parent, best, best_fit, hp, hm, hf = jax.jit(fn)(thr, keys, planes, gvals)
hp = np.asarray(hp)
assert hp.shape == (2, cfg.generations)
assert np.isfinite(hp).all()
assert (hp[:, -1] <= 1.0 + 1e-6).all()
print('DIST-EVOLVE-OK', hp[:, -1])
""")
    assert "DIST-EVOLVE-OK" in out


@pytest.mark.slow
def test_pod_mesh_sweep_matches_single_host():
    """ISSUE 4 acceptance: the pod-sharded sweep on a forced 2-pod CPU mesh
    produces bit-identical per-run results AND shard bytes vs the
    single-host path, including a resume from a partial per-pod shard set
    (pod 1's chunks committed, pod 0 interrupted mid-slice)."""
    out = run_subprocess("""
import sys, os, tempfile; sys.path.insert(0, 'src')
import numpy as np
from repro.core.evolve import EvolveConfig
from repro.core.fitness import ConstraintSpec
from repro.core.results import SweepResultReader
from repro.core.search import SearchConfig
from repro.core.sweep import SweepConfig, run_sweep_batched
from repro.launch.mesh import make_sweep_mesh
from repro.parallel import ctx

CFG = SearchConfig(width=2, kind='add', n_n=40,
                   evolve=EvolveConfig(generations=40, lam=3))
CONS = [ConstraintSpec(mae=1.0), ConstraintSpec(mae=2.0),
        ConstraintSpec(er=50.0)]
sd, pd = tempfile.mkdtemp(), tempfile.mkdtemp()
single = run_sweep_batched(CFG, CONS, (0, 1), SweepConfig(
    chunk_size=2, keep_history='summary', results_dir=sd))
assert single.completed == 6
mesh = make_sweep_mesh(pods=2)
with ctx.use_mesh(mesh):
    pods = ctx.pod_count()
    assert pods == 2, pods
    # one process drives both pod slices in turn (multi-host runs one of
    # these per host); pod 0 is interrupted first to leave a partial
    # per-pod shard set with a global gap, then both slices drain
    for pod, kw in ((0, dict(max_chunks=1)), (1, {}), (0, {})):
        res = run_sweep_batched(CFG, CONS, (0, 1), SweepConfig(
            chunk_size=2, keep_history='summary', results_dir=pd,
            n_pods=pods, pod_index=pod, **kw))
assert res.completed == 6 and res.done_mask.all()
shards = sorted(f for f in os.listdir(sd) if f.startswith('shard_'))
assert shards == sorted(f for f in os.listdir(pd)
                        if f.startswith('shard_'))
for f in shards:
    a = open(os.path.join(sd, f), 'rb').read()
    b = open(os.path.join(pd, f), 'rb').read()
    assert a == b, f'shard bytes differ: {f}'
ra, rb = SweepResultReader(sd), SweepResultReader(pd)
sa, sb = ra.summary(), rb.summary()
for key in sa:
    np.testing.assert_array_equal(sa[key], sb[key])
print('POD-SWEEP-OK', len(shards))
""", devices=2)
    assert "POD-SWEEP-OK" in out


@pytest.mark.slow
def test_pod_mesh_migration_pod_order_independent():
    """ISSUE 9 acceptance: island migration on a forced 2-pod CPU mesh is
    deterministic and pod-start-order independent — both interleavings of
    the two pod slices converge to byte-identical shard AND migrant files
    (the import schedule is pinned by the chunk plan, the merge rule is
    content-based)."""
    out = run_subprocess("""
import sys, os, tempfile; sys.path.insert(0, 'src')
from repro.core.evolve import EvolveConfig
from repro.core.fitness import ConstraintSpec
from repro.core.search import SearchConfig
from repro.core.sweep import SweepConfig, run_sweep_batched
from repro.launch.mesh import make_sweep_mesh
from repro.parallel import ctx

CFG = SearchConfig(width=2, kind='add', n_n=40,
                   evolve=EvolveConfig(generations=40, lam=3))
CONS = [ConstraintSpec(mae=1.0), ConstraintSpec(mae=2.0),
        ConstraintSpec(er=50.0)]
N_RUNS = 6  # chunk_size 2 -> 3 chunks, pod slices [2, 1]

def drive(d, order):
    # one epoch (max_chunks == migrate_every) per leg, pods alternating —
    # the single-process stand-in for concurrently progressing pods
    done = {}
    for _ in range(4):
        for pod in order:
            done[pod] = run_sweep_batched(CFG, CONS, (0, 1), SweepConfig(
                chunk_size=2, keep_history='summary', results_dir=d,
                n_pods=2, pod_index=pod, max_chunks=1, migrate_every=1,
                migrate_timeout=30.0))
            if len(done) == 2 and all(
                    r.completed == N_RUNS for r in done.values()):
                return done[pod]
    raise AssertionError('pods never drained: %r' %
                         {p: r.completed for p, r in done.items()})

da, db = tempfile.mkdtemp(), tempfile.mkdtemp()
mesh = make_sweep_mesh(pods=2)
with ctx.use_mesh(mesh):
    last = drive(da, (0, 1))
    drive(db, (1, 0))
assert last.migrate_stats is not None
files = sorted(f for f in os.listdir(da)
               if f.startswith(('shard_', 'migrants_')))
assert files == sorted(f for f in os.listdir(db)
                       if f.startswith(('shard_', 'migrants_')))
assert any(f.startswith('migrants_pod0_') for f in files)
assert any(f.startswith('migrants_pod1_') for f in files)
for f in files:
    a = open(os.path.join(da, f), 'rb').read()
    b = open(os.path.join(db, f), 'rb').read()
    assert a == b, f'bytes differ across pod orders: {f}'
print('MIGRATE-MESH-OK', len(files))
""", devices=2)
    assert "MIGRATE-MESH-OK" in out




@pytest.mark.slow
def test_debug_mesh_dryrun_cell():
    """A miniature dry-run on an in-test mesh proves the dryrun plumbing
    (shardings + lowering + collective parsing) without 512 devices."""
    out = run_subprocess("""
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp
from repro.configs import base as B
from repro.launch import steps as ST
from repro.launch.dryrun import parse_collective_bytes
from repro.models import model as M
from repro.optim import OptConfig, opt_state_specs
from repro.parallel import ctx
mesh = jax.make_mesh((2, 4), ('data', 'model'))
mod = B.get_arch('llama3_2_1b')
cfg = mod.reduced()
import dataclasses
cfg = dataclasses.replace(cfg, scan_layers=True)
shape = B.ShapeConfig('t', 64, 4, 'train')
opt_cfg = OptConfig()
with ctx.use_mesh(mesh):
    params_sds = ST.abstract_params(cfg)
    opt_sds = ST.abstract_opt_state(cfg, opt_cfg)
    pspecs = ST.resolve_tree(M.param_specs(cfg))
    ospecs = ST.resolve_tree(opt_state_specs(M.param_specs(cfg), opt_cfg))
    bspecs = ST.resolve_tree(ST.batch_specs(cfg, shape))
    batch = B.input_specs(cfg, shape)
    step = ST.make_train_step(cfg, opt_cfg)
    jitted = jax.jit(step, in_shardings=(pspecs, ospecs, bspecs, None),
                     out_shardings=(pspecs, ospecs, None),
                     donate_argnums=(0, 1))
    lowered = jitted.lower(params_sds, opt_sds, batch,
                           jax.ShapeDtypeStruct((), jnp.int32))
    compiled = lowered.compile()
    colls = parse_collective_bytes(compiled.as_text(), {'default': 1})
    assert colls['total_bytes'] > 0, 'expected collectives on a 2x4 mesh'
    print('DRYRUN-MINI-OK', sorted(colls['per_op']))
""")
    assert "DRYRUN-MINI-OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_reshard():
    """Save on mesh A (2x4), restore on mesh B (4x2) — elastic rescale."""
    out = run_subprocess("""
import sys, tempfile; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import store
mesh_a = jax.make_mesh((2, 4), ('data', 'model'))
mesh_b = jax.make_mesh((4, 2), ('data', 'model'))
x = jnp.arange(64.0).reshape(8, 8)
tree = {'w': jax.device_put(x, NamedSharding(mesh_a, P('data', 'model')))}
d = tempfile.mkdtemp()
store.save_checkpoint(d, 1, tree)
tmpl = {'w': jax.ShapeDtypeStruct((8, 8), jnp.float32)}
shard_b = {'w': NamedSharding(mesh_b, P('model', 'data'))}
out, _ = store.load_checkpoint(d, 1, tmpl, shard_b)
assert (np.asarray(out['w']) == np.asarray(x)).all()
assert out['w'].sharding.mesh.shape['data'] == 4
print('ELASTIC-OK')
""")
    assert "ELASTIC-OK" in out


@pytest.mark.slow
def test_compressed_psum_collective():
    out = run_subprocess("""
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import compress
mesh = jax.make_mesh((8,), ('data',))
g = jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) / 100.0
e = jnp.zeros((8, 64), jnp.float32)

def local(g_l, e_l):
    red, err = compress.compressed_psum({'w': g_l[0]}, {'w': e_l[0]}, 'data')
    return red['w'][None], err['w'][None]

fn = jax.shard_map(local, mesh=mesh, in_specs=(P('data'), P('data')),
                   out_specs=(P('data'), P('data')), check_vma=False)
red, err = fn(g, e)
want = g.mean(axis=0)
got = np.asarray(red[0])
np.testing.assert_allclose(got, np.asarray(want), rtol=0.02, atol=0.01)
print('COMPRESS-PSUM-OK')
""", devices=8)
    assert "COMPRESS-PSUM-OK" in out
