"""The trace reduction on a trace recorded on a TPU v5e chip.

``data/add8_3gen.xplane.pb.gz``: one traced sweep of the adder cell cut to
3 generations, two chunks of 32 runs (lambda 8, 100 nodes, the 2^16
cube), inside a ``bench.window`` span, as ``bench/run.py`` traces a
window.
"""
import gzip
import os
import shutil

import pytest

from bench import cells, run, trace, work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "add8_3gen.xplane.pb.gz")
COUNTERS = {"chunks": 2, "generations": 3, "runs": 64, "lam": 8, "n_n": 100,
            "n_inputs": 1 << 16,
            "evaluations": work.candidate_evaluations(64, 8, 3)}


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "add8_3gen.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(str(path))


def test_planes_and_spans(reduced):
    assert reduced.n_devices == 1
    window = reduced.host_span("bench.window")
    assert window is not None and window.seconds > 0
    assert reduced.host_span("bench.sweep") is not None
    names = {e.name.split("(")[0] for e in reduced.modules}
    assert {"jit_evolve_chunk", "jit_characterize_chunk"} <= names


def test_kernel_found_by_name(reduced):
    window = reduced.host_span("bench.window")
    seconds, n = trace.seconds_named(reduced.within(reduced.ops, window),
                                     work.KERNEL_OP)
    # a dispatch per generation of each chunk and one of the golden parent
    # per chunk; not the fusions that read the kernel's result
    c = COUNTERS
    assert n == c["chunks"] * (c["generations"] + 1) and seconds > 0


def test_busy_and_idle(reduced):
    window = reduced.host_span("bench.window")
    busy = trace.busy_seconds(reduced, window)
    assert 0 < busy < window.seconds
    gaps = trace.idle_gaps(reduced, window)
    idle = sum(e - s for s, e in gaps) * 1e-9
    assert idle == pytest.approx(window.seconds - busy, rel=1e-6)
    assert trace.top_ops(reduced, window)[0][1] > 0


def test_layer_metrics(reduced):
    window = reduced.host_span("bench.window")
    r = run.Run(reduced, window, COUNTERS,
                trace.busy_seconds(reduced, window))
    for name in ("cgp_kernel_gwords_per_s", "evolve_other_ms_per_gen",
                 "characterize_ms_per_chunk", "device_idle_pct"):
        value = cells.load_reader(cells.ROOT, name)(r)
        assert value is not None and value > 0, name
    assert cells.load_reader(cells.ROOT, "device_idle_pct")(r) < 100
