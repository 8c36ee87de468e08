"""The check must refuse what is wrong.

The control (the reference computed in bfloat16 in the program's place)
and three faults planted in the timed path under the harness -- a step that
returns its state unchanged, half of the cube left out of every evaluation
(the means taken over the rest), and an answer altered where the kernel
produces it -- each make ``correct`` come out false.  The exchange between
chips does not exist on one chip and has no fault here.  Tiny sizes on the
CPU; the chip readings of the control are in PERF.md.
"""
import os

import pytest

from bench import check, run, traffic
from bench import reference as R
from bench.tests.conftest import CPU, tiny

SEED = 3 * 2 ** 31 + 7


@pytest.mark.parametrize("name", ["mul8_paper.fig_grid",
                                  "add8_evoapprox.fig_grid"])
def test_control_is_refused(name, tmp_path):
    cell = tiny(name)
    c = cell.config
    prog = run.Program(c, cell.mix.get("sweep", {}))
    plan = traffic.sweeps(cell.mix, SEED, 1, prog.chunk_size)
    d = os.path.join(tmp_path, "sweep")
    prog.sweep(plan[0], d)
    prob = R.Problem(c["kind"], c["width"], c["n_n"])
    judged = {ctl: check.compare(prob, c["generations"], c["lam"],
                                 c["mutation_rate"], plan, [d], SEED,
                                 control=ctl)[0]
              for ctl in (False, True)}
    assert check.verdict(judged[False], c["limits"]), judged[False]
    assert not check.verdict(judged[True], c["limits"]), judged[True]


def _unchanged_state(monkeypatch):
    from repro.core import sweep
    monkeypatch.setattr(sweep, "make_batched_generation_step",
                        lambda spec, cfg, axis_name=None:
                        lambda state, *args: state)


def _half_cube(monkeypatch):
    from repro.kernels import ops
    real = ops.cgp_eval_batched

    def half(genomes, spec, in_planes, golden_vals, *a, **kw):
        w = in_planes.shape[1] // 2
        return real(genomes, spec, in_planes[:, :w], golden_vals[:32 * w],
                    *a, **kw)
    monkeypatch.setattr(ops, "cgp_eval_batched", half)


def _altered_answer(monkeypatch):
    from repro.kernels import ops
    real = ops.cgp_eval_batched

    def altered(*a, **kw):
        partials, pops = real(*a, **kw)
        return partials._replace(abs_sum=partials.abs_sum + 1.0), pops
    monkeypatch.setattr(ops, "cgp_eval_batched", altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_cube,
                                   _altered_answer])
def test_fault_is_refused(fault, monkeypatch, fresh_jit, tmp_path):
    fault(monkeypatch)
    result, _ = run.measure(tiny("mul8_paper.fig_grid"), SEED, 0.01, False,
                            CPU, str(tmp_path))
    assert not result["correct"], result["compared"]
