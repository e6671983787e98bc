"""The ``setup.*`` metrics: what the program counts of its own set-up,
read in a traced tiny run, and nothing to read where the program keeps
no such ledger (the parent commit's, which the driver runs this file's
readers against too)."""
from test_run_cpu import tiny_run

from benchmarks import run
from benchmarks.readers import setup_ledger
from skypilot_tpu.observability import profiler

SETUP = ['setup.' + s for s in setup_ledger.STATS]


def test_a_traced_run_reports_the_set_up_ledger_within_setup_s():
    parts = {}
    res = tiny_run('sessions', trace=True,
                   hook=lambda r, stage: parts.update(run=r))
    assert res['correct'] is True
    got = {n: res['metrics'][n]['value'] for n in SETUP}
    assert all(isinstance(v, float) and v >= 0 for v in got.values()), got
    # A pytest worker may already hold the tiny cell's programs: builds
    # may be 0 here; each chip run is a new process, where they are not.
    stages = got['setup.trace_s'] + got['setup.lower_s'] + \
        got['setup.build_s']
    assert stages <= parts['run'].parts['setup_s']


def test_a_program_without_the_ledger_gives_nothing_to_read(monkeypatch):
    ctx = run.Context(cell={}, cfg={}, mix={}, chips=1, parts={}, t0=1.0)
    assert setup_ledger.read(ctx, 'builds') is not None
    monkeypatch.delattr(profiler, 'setup_totals')
    for stat in setup_ledger.STATS:
        assert setup_ledger.read(ctx, stat) is None
