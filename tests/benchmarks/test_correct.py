"""The verdict's arithmetic."""
import math

import pytest

from benchmarks import correct
from benchmarks.timeline import Record


def test_verdict_holds_every_number_to_its_own_limit():
    ok, table = correct.verdict({'a': 0.5, 'b': 0.0, 'c': 9},
                                {'a': 1.0, 'b': 0})
    assert ok and table == {'a': [0.5, 1.0], 'b': [0.0, 0]}
    ok, _ = correct.verdict({'a': 1.5, 'b': 0.0}, {'a': 1.0, 'b': 0})
    assert not ok


@pytest.mark.parametrize('bad', [float('inf'), float('nan'), None])
def test_a_number_that_is_not_there_or_not_finite_fails(bad):
    ok, table = correct.verdict({'a': bad}, {'a': 1.0})
    assert not ok and 'a' in table


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = {'big': 10.0, 'mid': 1.0, 'tiny': 1e-6}
    prog = {'big': 10.5, 'mid': 1.0, 'tiny': 3e-6}
    gap, leaf = correct.worst_leaf_gap(prog, ref)
    # tiny's gap is held against the median leaf (1.0), not its own size
    assert leaf == 'big' and gap == pytest.approx(0.05)
    gap, leaf = correct.worst_leaf_gap({'big': 10, 'mid': 0.0, 'tiny': 0},
                                       ref)
    assert leaf == 'mid' and gap == pytest.approx(1.0)   # a leaf unmoved


def test_leaves_with_no_gradient_are_left_out_by_rule_not_by_name():
    grad = {'a': 1.0, 'b': 2.0, 'c': 3.0, 'dead': 1e-5}
    assert correct.moving_leaves(grad) == ['a', 'b', 'c']


def test_train_numbers():
    ref = {'losses': [10.0, 9.0], 'grad': {'w': 2.0, 'v': 1.0},
           'grad_global': 2.5, 'change': {'w': 0.1, 'v': 0.2}}
    prog = {'losses': [10.01, 9.0], 'grad': {'w': 2.0, 'v': 1.1},
            'grad_global': 2.5, 'change': {'w': 0.1, 'v': 0.0}}
    n = correct.train_numbers(prog, ref)
    assert n['loss_gap'] == pytest.approx(0.001)
    assert n['grad_leaf_gap'] == pytest.approx(0.1 / 1.5)
    assert n['change_leaf_gap'] == pytest.approx(1.0)
    assert n['grad_global_gap'] == 0


def test_missing_answers_counts_what_never_came_or_came_short():
    full = Record(rid=0, prompt_len=1, max_new=2, arrivals=[(0, 2)],
                  tokens=[1, 2])
    short = Record(rid=1, prompt_len=1, max_new=3, arrivals=[(0, 2)],
                   tokens=[1, 2])
    failed = Record(rid=2, prompt_len=1, max_new=2, failed=True)
    ramp = Record(rid=3, prompt_len=1, max_new=2, counted=False)
    assert correct.missing_answers([full, short, failed, ramp]) == 2


def test_compared_numbers_are_printed_last_on_stderr(capsys):
    correct.print_table({'gap_max': [0.25, 1.0]}, True)
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-1] == '[compared] correct = true'
    assert 'gap_max = 0.25 (limit 1)' in err[-2]
    assert math.isfinite(0.25)
