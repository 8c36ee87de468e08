"""Device-op scopes read from a profiler trace (``bench/scopes.py``,
``bench/xspace.py``) and the readers of the program's spans and scopes.

The rules on made-up events; the wire reader against
``jax.profiler.ProfileData`` on both stored chip traces; the new readers
on ``data/add8_3gen_scoped.xplane.pb.gz``, recorded on a TPU v5e by
``record_trace.py`` from a program with the spans and the scope.
"""
import gzip
import os
import shutil

import pytest

import record_trace
from bench import cells, run, scopes, trace, xspace
from bench.trace import Event

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("active_mask_ms_per_gen", "sweep_prepare_ms_per_sweep",
       "commit_ms_per_chunk")


def _unzip(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / name.removesuffix(".gz")
    with gzip.open(os.path.join(DATA, name), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def _read(path, red, name, counters):
    window = red.host_span("bench.window")
    r = run.Run(red, window, counters, trace.busy_seconds(red, window))
    r.xplane = path
    return cells.load_reader(cells.ROOT, name)(r)


@pytest.fixture(scope="module")
def counters():
    return record_trace.counters()


@pytest.fixture(scope="module")
def unscoped(tmp_path_factory):
    """``add8_3gen.xplane.pb.gz``: the same shapes, from a program without
    spans or scopes."""
    path = _unzip("add8_3gen.xplane.pb.gz", tmp_path_factory)
    return path, trace.reduce(path)


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    path = _unzip("add8_3gen_scoped.xplane.pb.gz", tmp_path_factory)
    return path, trace.reduce(path)


def test_scope_of_drops_the_op_and_its_control_flow():
    p = "jit(evolve_chunk)/while/body/closed_call/vmap(active_mask)"
    assert scopes.scope_of(p + "/while/body/closed_call/or:") == p
    assert scopes.scope_of(p + "/while:") == p
    assert scopes.scope_of(p + "/while/cond/lt:") == p
    assert scopes.scope_of(p + "/while/body/add;while/body/closed_call:") \
        == p
    assert scopes.scope_of("jit(characterize_chunk)/vmap()/reduce_sum:") \
        == "jit(characterize_chunk)/vmap()"


@pytest.mark.parametrize("scope,hit", [
    ("jit(evolve_chunk)/active_mask", True),
    ("jit(evolve_chunk)/while/body/closed_call/vmap(active_mask)", True),
    ("jit(f)/vmap(vmap(active_mask))/x", True),
    ("jit(evolve_chunk)/active_mask_count", False),
    ("jit(evolve_chunk)/while/body/closed_call/vmap()", False),
])
def test_in_scope(scope, hit):
    assert scopes.in_scope(scope, "active_mask") is hit


def test_nest():
    ops = {
        "loop": (Event("loop", 0, 100), ""),        # holds a and b: mixed
        "scan": (Event("scan", 10, 50), ""),        # holds only "a"
        "body1": (Event("body1", 11, 20), "a"),
        "copy": (Event("copy", 21, 22), ""),        # inside the scan
        "body2": (Event("body2", 30, 49), "a"),
        "kernel": (Event("kernel", 60, 90), "b"),
        "fusion": (Event("fusion", 61, 70), ""),    # inside the kernel
        "after": (Event("after", 120, 130), ""),    # holds nothing
    }
    # given out of time order
    names = sorted(ops, reverse=True)
    got = scopes.nest([ops[n][0] for n in names], [ops[n][1] for n in names])
    assert dict(zip(names, got)) == {
        "loop": "", "scan": "a", "body1": "a", "copy": "a", "body2": "a",
        "kernel": "b", "fusion": "b", "after": ""}


@pytest.mark.parametrize("name", ["add8_3gen.xplane.pb.gz",
                                  "add8_3gen_scoped.xplane.pb.gz"])
def test_wire_reader_agrees_with_profile_data(name, tmp_path_factory):
    """Every device event: the same name (its metadata's), start and
    duration (picoseconds floored to nanoseconds) in both readers."""
    from jax.profiler import ProfileData

    path = _unzip(name, tmp_path_factory)
    planes = {p.name: p for p in xspace.read(path)}
    n = 0
    for plane in ProfileData.from_file(path).planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        mine = planes[plane.name]
        lines = {line: events for _, line, events in mine.lines()}
        for line in plane.lines:
            theirs = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events]
            assert theirs == [
                (mine.event_metadata[e.metadata_id][0], e.start_ps // 1000,
                 e.duration_ps // 1000) for e in lines[line.name]]
            n += len(theirs)
    assert n > 10_000


def test_unscoped_trace_reads_no_new_metric(unscoped, counters):
    """A trace of a program without the spans and the scope gives the new
    readers nothing to read, as on the parent of the program's spans."""
    path, red = unscoped
    for name in NEW:
        assert _read(path, red, name, counters) is None


def test_no_trace_file_reads_nothing(unscoped, counters):
    _, red = unscoped
    window = red.host_span("bench.window")
    r = run.Run(red, window, counters, trace.busy_seconds(red, window))
    assert scopes.xplane(r) is None
    assert cells.load_reader(cells.ROOT, NEW[0])(r) is None


def test_new_readers_on_the_chip_trace(scoped, counters):
    path, red = scoped
    active = _read(path, red, "active_mask_ms_per_gen", counters)
    other = _read(path, red, "evolve_other_ms_per_gen", counters)
    # the scan is nearly all of the generation step outside the kernel
    assert 0.85 * other <= active <= other
    assert 3.0 < active < 4.5
    # four eager compilations in the set-up (the test below); a shard
    # commit of 32 runs is milliseconds
    assert 400 < _read(path, red, "sweep_prepare_ms_per_sweep",
                       counters) < 1500
    assert 1 < _read(path, red, "commit_ms_per_chunk", counters) < 15


def test_active_mask_matches_the_scan_by_name(scoped, counters):
    """The scope's union of op intervals against the ``while`` ops that
    ``core/genome.py`` emits in the generation program (the vmapped scan
    and the golden parent's), found by their metadata."""
    path, red = scoped
    window = red.host_span("bench.window")
    evolve_ids = {m.name.split("(")[1].rstrip(")") for m in red.modules
                  if m.name.startswith("jit_evolve_chunk(")}
    names = {name for p in xspace.read(path)
             if trace.DEVICE_PLANE.match(p.name)
             for name, st in p.event_metadata.values()
             if st.get("hlo_category") == "while"
             and "genome.py" in str(st.get("source"))
             and str(st.get("program_id")) in evolve_ids}
    assert len(names) == 2
    ops, ambiguous = scopes.scoped_ops(red.within(red.ops, window), path)
    assert ambiguous == 0
    scans = [(o, s) for o, s in ops if o.name in names]
    # the TPU compiler keeps no scope on a while: it comes from its body
    assert scans and all(s.endswith("active_mask)")
                         or s == "jit(evolve_chunk)/active_mask"
                         for _, s in scans)
    per_gen = sum(o.seconds for o, _ in scans) \
        / (counters["chunks"] * counters["generations"]) * 1e3
    active = _read(path, red, "active_mask_ms_per_gen", counters)
    assert active == pytest.approx(per_gen, rel=0.1)


def test_generation_scan_stays_unscoped(scoped):
    """The generation program's outer ``while`` holds the kernel,
    mutation and the active-mask scan: no one scope."""
    path, red = scoped
    evolve = [m for m in red.modules if m.name.startswith("jit_evolve_chunk(")]
    ops, _ = scopes.scoped_ops(red.ops, path)
    loops = [(o, s) for o, s in ops if o.name.startswith("%while")
             and any(m.start <= o.start and o.end <= m.end for m in evolve)]
    outer = max(loops, key=lambda p: p[0].seconds)
    assert outer[1] == ""
    assert outer[0].seconds > 0.5 * sum(m.seconds for m in evolve) \
        / len(evolve)


def test_prepare_holds_the_eager_scan_compiles(scoped):
    """The set-up of a sweep compiles four eager ``lax.scan`` programs
    (``search.problem_arrays``) every call: the four long idle gaps."""
    _, red = scoped
    window = red.host_span("bench.window")
    (prepare,) = [e for e in red.within(red.host, window)
                  if e.name == "sweep.prepare"]
    inside = [e for e in red.host
              if prepare.start <= e.start and e.end <= prepare.end]
    assert sum(e.name == "backend_compile_and_load" for e in inside) == 4
    compiled = [m for m in red.modules if m.name.startswith("jit_scan(")
                and prepare.start <= m.start and m.end <= prepare.end]
    assert len(compiled) == 4
    gaps = sorted((e - s for s, e in trace.idle_gaps(red, window)),
                  reverse=True)[:4]
    assert all(g > 100e6 for g in gaps)
    for s, e in trace.idle_gaps(red, window):
        if e - s > 100e6:
            assert trace.host_doing(red, s, e, ("bench.window",)) \
                == "sweep.prepare"
