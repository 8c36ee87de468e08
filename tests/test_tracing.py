"""The spans and scopes a sweep leaves in a profiler trace, read as the
benchmark reads them (``bench/trace.py``).

On the CPU: the power model's scan carries the scope ``active_mask`` into
the compiled HLO, a tiny sweep records its host phases as ``sweep.*``
spans, and ``launch.evolve --profile-dir`` writes them.  On a trace
recorded on a TPU v5e (``bench/tests/data``), the benchmark's four first
per-layer readers give the values they gave before the program had spans.
How the benchmark reads scopes is tested with the benchmark
(``bench/tests/test_scopes.py``).
"""
import functools
import gzip
import os
import re
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.evolve import EvolveConfig
from repro.core.fitness import ConstraintSpec
from repro.core.search import SearchConfig, problem_arrays
from repro.core.sweep import SweepConfig, evolve_chunk, run_sweep_batched

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import cells, run, trace, work, xspace  # noqa: E402

DATA = os.path.join(REPO, "bench", "tests", "data")
CFG = SearchConfig(width=2, kind="add", n_n=40,
                   evolve=EvolveConfig(generations=8, lam=4))
CONSTRAINTS = [ConstraintSpec(mae=1.0), ConstraintSpec(er=50.0)]
SEEDS = (0, 1)
SPANS = ("sweep.prepare", "sweep.dispatch", "sweep.fetch", "sweep.commit",
         "sweep.finish")


def _unzip(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / name.removesuffix(".gz")
    with gzip.open(os.path.join(DATA, name), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def _read(red, name, counters):
    window = red.host_span("bench.window")
    r = run.Run(red, window, counters, trace.busy_seconds(red, window))
    return cells.load_reader(cells.ROOT, name)(r)


# --------------------------------------------------------------------------
# What the program records (CPU)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _evolve_chunk_hlo() -> str:
    """The compiled HLO text of one tiny sweep chunk."""
    gold, spec, in_planes, gvals, gpower = problem_arrays(CFG)
    thr = jnp.asarray(np.stack([c.thresholds() for c in CONSTRAINTS]))
    keys = jnp.stack([jax.random.PRNGKey(s) for s in SEEDS])
    return evolve_chunk.lower(spec, CFG.evolve, gold, thr, in_planes, gvals,
                              gpower, keys).compile().as_text()


def test_active_mask_scope_in_compiled_hlo():
    text = _evolve_chunk_hlo()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    # the golden parent's scan, and the generation step's vmapped one
    assert "jit(evolve_chunk)/active_mask/while" in names
    assert any(n.startswith("jit(evolve_chunk)/while/body/")
               and "/vmap(active_mask)/while/body/" in n for n in names)


def test_no_scatter_under_active_mask():
    """The active-mask scan updates its mask with no scatter: under ``vmap``
    a scatter writes one candidate's row after another on the TPU."""
    ops = re.findall(r' = .*?\s([a-z][\w-]*)\(.*op_name="([^"]*)"',
                     _evolve_chunk_hlo())
    scoped = [op for op, name in ops if "active_mask" in name.split("/")
              or "vmap(active_mask)" in name.split("/")]
    assert "while" in scoped
    assert "scatter" not in scoped


def _traced_sweep(tmp_path, **kw):
    """The ``sweep.*`` spans of one traced sweep, and the xplane file."""
    log_dir = str(tmp_path / "trace")
    with jax.profiler.trace(log_dir):
        run_sweep_batched(CFG, CONSTRAINTS, SEEDS, SweepConfig(
            chunk_size=2, keep_history="summary",
            results_dir=str(tmp_path / "shards"), **kw))
    xplane = trace.find_xplane(log_dir)
    red = trace.reduce(xplane)
    return {name: [e for e in red.host if e.name == name]
            for name in SPANS}, xplane


def _threads(xplane, name):
    """Ids of the host threads that recorded a span ``name``."""
    (host,) = [p for p in xspace.read(xplane) if p.name == trace.HOST_PLANE]
    ids = {i for i, (n, _) in host.event_metadata.items() if n == name}
    return {line_id for line_id, _, events in host.lines()
            if any(e.metadata_id in ids for e in events)}


def test_sweep_records_its_phases(tmp_path):
    spans, _ = _traced_sweep(tmp_path)
    # one sweep of two chunks
    assert [len(spans[n]) for n in SPANS] == [1, 2, 2, 2, 1]
    (prepare,), (finish,) = spans["sweep.prepare"], spans["sweep.finish"]
    first = min(e.start for e in spans["sweep.dispatch"])
    assert prepare.end <= first
    assert finish.start >= max(e.end for e in spans["sweep.fetch"])
    for d, f in zip(spans["sweep.dispatch"], spans["sweep.fetch"]):
        assert d.end <= f.start


def test_async_commit_span_on_the_worker_thread(tmp_path):
    spans, xplane = _traced_sweep(tmp_path, async_commit=True)
    assert len(spans["sweep.commit"]) == 2
    caller = _threads(xplane, "sweep.dispatch")
    assert len(caller) == 1
    workers = _threads(xplane, "sweep.commit")
    assert workers and workers.isdisjoint(caller)


def test_launch_evolve_profile_dir(tmp_path, capsys):
    from jax.profiler import ProfileData
    from repro.launch import evolve

    out = tmp_path / "profile"
    evolve.main(["--width", "2", "--kind", "add", "--nodes", "40",
                 "--constraint", "er=50", "--generations", "6", "--lam", "4",
                 "--seeds", "2", "--profile-dir", str(out)])
    assert "runs/s" in capsys.readouterr().out
    pd = ProfileData.from_file(trace.find_xplane(str(out)))
    names = {e.name for plane in pd.planes for line in plane.lines
             for e in line.events}
    assert "sweep.prepare" in names


# --------------------------------------------------------------------------
# The benchmark's readers on the stored chip trace
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def add8_3gen(tmp_path_factory):
    return trace.reduce(_unzip("add8_3gen.xplane.pb.gz", tmp_path_factory))


#: the counts of ``add8_3gen.xplane.pb.gz`` (bench/tests/test_chip_trace.py)
ADD8_3GEN = {"chunks": 2, "generations": 3, "runs": 64, "lam": 8,
             "n_n": 100, "n_inputs": 1 << 16,
             "evaluations": work.candidate_evaluations(64, 8, 3)}


@pytest.mark.parametrize("name,value", [
    ("cgp_kernel_gwords_per_s", 19.905217408636737),
    ("evolve_other_ms_per_gen", 4.0500266666666676),
    ("characterize_ms_per_chunk", 90.500017),
    ("device_idle_pct", 76.55435416298447),
])
def test_existing_readers_unchanged(add8_3gen, name, value):
    """The values the four readers gave on this trace before the program
    had spans and scopes, to the bit."""
    assert _read(add8_3gen, name, ADD8_3GEN) == value
