"""The benchmark's tests import the bench package and the program under
test from the repository root, and run on the CPU (kernels interpreted)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


import pytest  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny(cell_name: str, root: str = ROOT, **over):
    """A cell of the benchmark cut to a size the CPU interprets in
    seconds: 3-bit operands (64 inputs), 40 nodes, lambda 4, 8
    generations.  Everything else is the cell's own."""
    from bench import cells
    cell = cells.resolve(cell_name, root)
    width = 3 if cell.config["kind"] == "mul" else 4
    cell.config = dict(cell.config, width=width, n_i=2 * width, n_n=40,
                       lam=4, generations=8, **over)
    return cell


@pytest.fixture
def fresh_jit():
    """Clear JAX's in-process caches around a test that patches the
    program, so that no trace of the unpatched program is reused."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()
