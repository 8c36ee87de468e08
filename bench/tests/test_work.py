"""The kernel's work function against counts made by hand."""
import pytest

from bench import work


def test_paper_chunk_generation():
    # one generation of a 32-run chunk at lambda 8: 256 candidates, each
    # walking 400 gates over the 2^16 cube = 2048 words of 32 inputs
    evals = work.candidate_evaluations(runs=32, lam=8, generations=1)
    assert evals == 256
    assert work.gate_words(evals, n_n=400, n_inputs=1 << 16) \
        == 256 * 400 * 2048 == 209_715_200


def test_adder_chunk():
    # a 300-generation chunk of the 100-node adder: 32 x 8 x 300 = 76,800
    # candidates x 100 gates x 2048 words
    evals = work.candidate_evaluations(runs=32, lam=8, generations=300)
    assert evals == 76_800
    assert work.gate_words(evals, n_n=100, n_inputs=1 << 16) \
        == 15_728_640_000


def test_cube_must_fill_words():
    with pytest.raises(ValueError):
        work.gate_words(1, n_n=10, n_inputs=16)
