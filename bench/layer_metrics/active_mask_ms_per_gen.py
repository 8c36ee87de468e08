"""Power model's active-gate scan (``core/genome.active_mask``, reached
from ``core/power``): device time of the ops under the scope
``active_mask`` in the ``evolve_chunk`` programs (``bench/scopes.py``),
nested ops counted once, per generation of a chunk."""
from bench import scopes, trace


def read(run):
    path = scopes.xplane(run)
    if path is None:
        return None
    ops, _ = scopes.scoped_ops(run.in_window(run.trace.ops), path)
    hit = [o for o, s in ops if s.startswith("jit(evolve_chunk)")
           and scopes.in_scope(s, "active_mask")]
    if not hit:
        return None
    busy = trace.busy_seconds(
        trace.Reduced(hit, [], [], run.trace.n_devices), run.window)
    c = run.counters
    return busy / (c["chunks"] * c["generations"]) * 1e3
