"""Runtime profiler (observability/profiler.py): ledger bounds,
compile detection + recompile-storm firing at budget+1, device-memory
reconciliation against the engine's block accounting, cold-start
phase-ledger monotonicity, the SKYTPU_PROFILE=0 no-op, and the
snapshot-in-bundle contract with the black-box recorder, and the
set-up ledger: what the process traces, lowers and builds, always on.

The compile legs jit tiny programs (milliseconds each on the CPU); the
engine and the device-trace tests are marked slow.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import llama
from skypilot_tpu.observability import blackbox, profiler

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def profiling(monkeypatch, tmp_path):
    monkeypatch.setenv('SKYTPU_PROFILE', '1')
    monkeypatch.setenv('SKYTPU_BLACKBOX_DIR', str(tmp_path / 'bb'))
    profiler.reset()
    blackbox.reset()
    yield
    profiler.reset()
    blackbox.reset()


# -- registry bounds ---------------------------------------------------------


def test_programs_registry_bounded_and_unique():
    assert len(profiler.PROGRAM_NAMES) == len(profiler.PROGRAMS)
    for p in profiler.PROGRAMS:
        assert p.budget >= 1, p.name
        assert p.doc, p.name


def test_unknown_program_name_rejected_with_hint():
    with pytest.raises(ValueError, match='engine.paged_chunk'):
        # skylint: allow-jit(the typo is the thing under test)
        profiler.profiled_jit('engine.paged_chnk', lambda x: x)


def test_budget_overrides_parse(monkeypatch):
    monkeypatch.setenv('SKYTPU_PROFILE_BUDGETS',
                       'engine.paged_chunk=2, generate.prefill=1,junk,x=')
    assert profiler.budget_for('engine.paged_chunk') == 2
    assert profiler.budget_for('generate.prefill') == 1
    # Undeclared overrides are inert; unset programs keep registry
    # budgets.
    assert profiler.budget_for('engine.rewind') == 4


# -- compile ledger ----------------------------------------------------------


def test_compile_counted_once_per_shape(profiling):
    f = profiler.profiled_jit('engine.rewind', lambda x: x * 2)
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))  # cached: no new compile
    snap = profiler.snapshot()['compile']['engine.rewind']
    assert snap['compiles'] == 1
    assert snap['compile_ms'] > 0
    assert snap['shapes'] and 'float32[4]' in snap['shapes'][0]
    f(jnp.ones((8,)))  # new shape: one more compile
    snap = profiler.snapshot()['compile']['engine.rewind']
    assert snap['compiles'] == 2
    # Shape samples are bounded.
    assert len(snap['shapes']) <= profiler._SHAPES_KEPT


def test_storm_fires_at_budget_plus_one(profiling, monkeypatch):
    monkeypatch.setenv('SKYTPU_PROFILE_BUDGETS', 'engine.paged_chunk=2')
    f = profiler.profiled_jit('engine.paged_chunk', lambda x: x + 1)
    for n in (2, 3):  # within budget: no storm
        f(jnp.ones((n,)))
    assert profiler.snapshot()['storms_total'] == 0
    f(jnp.ones((4,)))  # budget+1: storm
    snap = profiler.snapshot()
    assert snap['compile']['engine.paged_chunk']['storms'] == 1
    assert snap['storms_total'] == 1
    storms = [e for e in blackbox.events()
              if e['name'] == 'profiler.storm']
    assert storms and storms[-1]['attrs']['program'] == 'engine.paged_chunk'
    assert storms[-1]['attrs']['budget'] == 2


def test_disabled_is_a_noop(monkeypatch):
    monkeypatch.delenv('SKYTPU_PROFILE', raising=False)
    profiler.reset()
    f = profiler.profiled_jit('engine.sample', lambda x: x - 1)
    out = f(jnp.ones((3,)))
    assert out.shape == (3,)
    assert profiler.snapshot() == {'enabled': False}
    monkeypatch.setenv('SKYTPU_PROFILE', '1')
    # Nothing was counted while disabled.
    assert profiler.snapshot()['compile']['engine.sample']['compiles'] \
        == 0
    profiler.reset()


def test_a_declared_programs_compile_ms_is_the_sum_of_its_stages(profiling):
    f = profiler.profiled_jit('engine.rewind', lambda x: x * 2 + 1)
    f(jnp.ones((6,)))
    snap = profiler.snapshot()
    st = snap['compile']['engine.rewind']
    assert st['compiles'] == 1
    assert st['trace_ms'] > 0 and st['lower_ms'] > 0 and st['build_ms'] > 0
    assert st['compile_ms'] == pytest.approx(
        st['trace_ms'] + st['lower_ms'] + st['build_ms'], abs=0.01)
    assert snap['setup']['builds'] >= 1   # /debug/profile's block


# -- set-up ledger -----------------------------------------------------------

_COUNTED = ('trace_s', 'lower_s', 'build_s', 'builds', 'cache_hits',
            'cache_misses')


def _delta(before, after):
    return {k: after[k] - before[k] for k in _COUNTED}


def test_one_jit_is_one_build_and_a_cached_call_adds_nothing():
    profiler.listen()
    # skylint: allow-jit(the compile is the thing under test)
    f = jax.jit(lambda x: x * 5 + 1)
    x = jnp.ones((3, 5, 7))
    a = profiler.setup_totals()
    f(x)
    b = profiler.setup_totals()
    f(x)
    c = profiler.setup_totals()
    d = _delta(a, b)
    assert d['builds'] == 1
    assert d['trace_s'] > 0 and d['lower_s'] > 0 and d['build_s'] > 0
    assert _delta(b, c) == dict.fromkeys(_COUNTED, 0)
    # a cut before the call leaves it out
    assert profiler.setup_totals(until=0.0)['builds'] == 0


def _inner_for_test(x):
    return jnp.sin(x) * 2


def _outer_for_test(x):
    # skylint: allow-jit(a jit traced inside another's trace is the case)
    inner = jax.jit(_inner_for_test)
    return inner(x) + inner(x * 3)


def test_a_jit_traced_inside_anothers_trace_counts_once(monkeypatch):
    profiler.listen()
    monkeypatch.setattr(profiler, '_NAMES', {})
    # skylint: allow-jit(the nested trace is the thing under test)
    outer = jax.jit(_outer_for_test)
    x = jnp.ones((9, 2))
    a = profiler.setup_totals()
    t0 = time.time()
    outer(x)
    wall = time.time() - t0
    b = profiler.setup_totals(top=None)
    d = _delta(a, b)
    rows = b['by_name']
    assert 0 < d['trace_s'] <= wall
    # the rows sum each name's own intervals; the union counts the
    # inner trace once, inside the outer's
    assert rows['_inner_for_test']['trace_s'] > 0
    assert d['trace_s'] < (rows['_outer_for_test']['trace_s']
                           + rows['_inner_for_test']['trace_s'])
    assert rows['_outer_for_test']['builds'] == 1
    assert rows['_inner_for_test']['builds'] == 0


def test_counting_goes_on_with_profiling_off(monkeypatch):
    monkeypatch.delenv('SKYTPU_PROFILE', raising=False)
    profiler.reset()
    profiler.listen()
    f = profiler.profiled_jit('engine.sample', lambda x: x * 7 - 1)
    a = profiler.setup_totals()
    f(jnp.ones((5, 3)))
    b = profiler.setup_totals()
    assert _delta(a, b)['builds'] >= 1
    assert profiler.snapshot() == {'enabled': False}
    monkeypatch.setenv('SKYTPU_PROFILE', '1')
    assert profiler.snapshot()['compile']['engine.sample']['compiles'] \
        == 0
    profiler.reset()


def test_an_eager_operation_is_counted_by_its_name(monkeypatch):
    profiler.listen()
    monkeypatch.setattr(profiler, '_NAMES', {})
    x = jnp.zeros((5, 13, 3))
    a = profiler.setup_totals()
    x.at[1].set(2.)
    b = profiler.setup_totals(top=None)
    assert _delta(a, b)['builds'] >= 1
    assert b['by_name']['scatter']['builds'] == 1
    assert b['by_name']['scatter']['build_s'] > 0


def test_the_by_name_table_is_bounded(monkeypatch):
    profiler.listen()
    monkeypatch.setattr(profiler, '_NAMES', {})
    monkeypatch.setattr(profiler, '_NAMES_KEPT', 2)
    monkeypatch.setattr(profiler, '_UNIONS', tuple(
        profiler._Union() for _ in profiler.SETUP_STAGES))
    for i in range(4):
        profiler._on_span(profiler.SETUP_STAGES[0][1], 1.0, 1.5,
                          fun_name=f'jit(f{i})')
    rows = profiler.setup_totals(top=None)['by_name']
    assert set(rows) == {'f0', 'f1', profiler._OTHER_NAMES}
    assert rows[profiler._OTHER_NAMES]['trace_s'] == 1.0


def test_the_union_merges_what_overlaps():
    u = profiler._Union()
    for start, end in ((2.0, 3.0), (2.5, 2.6), (1.0, 4.0), (6.0, 7.0),
                       (5.0, 6.5)):
        u.add(start, end)
    assert u.starts == [1.0, 5.0] and u.ends == [4.0, 7.0]
    assert u.seconds() == pytest.approx(5.0)
    assert u.seconds(until=5.5) == pytest.approx(3.5)


_CACHE_SCRIPT = '''
import json
import jax
import jax.numpy as jnp
from skypilot_tpu.observability import profiler
from skypilot_tpu.utils import jax_env
jax_env.enable_compile_cache()
f = jax.jit(lambda x: jnp.tanh(x) * 3)
x = jnp.ones((4, 4))
f(x).block_until_ready()
a = profiler.setup_totals(top=0)
jax.clear_caches()
f(x).block_until_ready()
b = profiler.setup_totals(top=0)
print(json.dumps([a, b]))
'''


def test_a_program_built_again_after_clear_caches_is_a_cache_hit(tmp_path):
    """``enable_compile_cache`` decides once per process: a child."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=str(REPO),
               SKYTPU_COMPILE_CACHE=str(tmp_path / 'cache'))
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    out = subprocess.run([sys.executable, '-c', _CACHE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=str(tmp_path), check=False)
    assert out.returncode == 0, out.stderr[-2000:]
    a, b = json.loads(out.stdout.strip().splitlines()[-1])
    assert a['cache_misses'] >= 1 and a['cache_hits'] == 0
    assert b['cache_hits'] >= 1
    assert b['cache_misses'] == a['cache_misses']
    assert b['builds'] > a['builds'] and b['trace_s'] > a['trace_s']


# -- device-memory accounting ------------------------------------------------


class _FakeDev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_memory_reconciliation_math(profiling):
    profiler.register_logical('weights', 600)
    profiler.register_logical('kv_cache', 300)
    dev = _FakeDev({'bytes_in_use': 1000, 'peak_bytes_in_use': 1200,
                    'bytes_limit': 4000})
    snap = profiler.sample_device_memory(devices=[dev])
    assert snap['bytes_in_use'] == 1000
    assert snap['headroom_bytes'] == 3000
    assert snap['headroom_frac'] == 0.75
    assert snap['logical_bytes'] == 900
    assert snap['unattributed_bytes'] == 100
    assert snap['unattributed_frac'] == 0.1
    # The snapshot rides subsequent full snapshots.
    assert profiler.snapshot()['device_memory']['bytes_in_use'] == 1000


def test_memory_cpu_degrades_to_logical(profiling):
    profiler.register_logical('weights', 64)
    snap = profiler.sample_device_memory(devices=[_FakeDev(None)])
    assert snap['devices_reporting'] == 0
    assert snap['logical_bytes'] == 64
    assert 'headroom_frac' not in snap  # no observation, never a breach


@pytest.mark.slow
def test_engine_registers_logical_kv_vs_block_accounting(profiling):
    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=64,
                                      kv_block=16)
    try:
        logical = profiler.logical_bytes()
        stats = eng.stats()['kv_blocks']
        # Reconciliation: the registered kv_cache footprint equals the
        # pool's block accounting (k + v planes, bf16 = 2 bytes):
        # total blocks x block x layers x kv_heads x head_dim x 2 x 2.
        expect = (stats['total'] * stats['block'] * cfg.n_layers
                  * cfg.n_kv_heads * cfg.head_dim * 2 * 2)
        # tables/lengths ride along (int32, tiny) — allow them as the
        # delta above the plane bytes.
        assert logical['kv_cache'] >= expect
        assert logical['kv_cache'] - expect < 16 * 1024
    finally:
        eng.stop()


# -- cold-start phase ledger -------------------------------------------------


def test_phase_ledger_monotonic_and_telescoping(profiling):
    profiler.mark('imports')
    profiler.mark('weights_load')
    profiler.mark('ready')
    # Out-of-order (late) mark of an earlier phase: first-crossing
    # semantics keep durations non-negative.
    profiler.mark('backend_init.device_enumeration')
    ledger = profiler.cold_start_ledger()
    assert all(v >= 0 for v in ledger['phases'].values())
    assert ledger['complete'] is True
    assert sum(ledger['phases'].values()) == pytest.approx(
        ledger['total_s'], abs=1e-3)
    # Idempotent: re-marking moves nothing.
    before = profiler.cold_start_ledger()
    profiler.mark('imports')
    assert profiler.cold_start_ledger() == before


def test_phase_ledger_rejects_undeclared_phase(profiling):
    with pytest.raises(ValueError, match='unknown cold-start phase'):
        profiler.mark('made_up_phase')


# -- surfaces ----------------------------------------------------------------


def test_snapshot_lands_in_blackbox_bundle(profiling):
    f = profiler.profiled_jit('engine.insert_cache', lambda x: x * 3)
    f(jnp.ones((2,)))
    bundle = blackbox.build_bundle('manual')
    prof = bundle['profile']
    assert prof is not None and prof['enabled'] is True
    assert prof['compile']['engine.insert_cache']['compiles'] == 1


def test_bundle_omits_profile_when_disabled(monkeypatch):
    monkeypatch.delenv('SKYTPU_PROFILE', raising=False)
    assert blackbox.build_bundle('manual')['profile'] is None


def test_debug_payload_catalog(profiling):
    out = profiler.debug_payload({'programs': '1'})
    assert out['enabled'] is True
    assert {p['name'] for p in out['programs']} == set(
        profiler.PROGRAM_NAMES)


@pytest.mark.slow
def test_a_device_trace_holds_the_programs_spans():
    """``/debug/profile?device_trace=<s>`` on a replica: a profiler trace
    of this process, with the spans the program opened meanwhile in its
    host plane."""
    import glob
    import os
    import shutil
    import threading

    stop = threading.Event()

    def work():
        while not stop.is_set():
            with profiler.span('engine.test_work'):
                stop.wait(0.01)

    t = threading.Thread(target=work, daemon=True)
    t.start()
    try:
        got = profiler.device_trace('0.3')
    finally:
        stop.set()
        t.join(timeout=10)
    try:
        assert got['seconds'] == pytest.approx(0.3)
        paths = glob.glob(os.path.join(got['dir'], 'plugins', 'profile',
                                       '*', '*.xplane.pb'))
        assert len(paths) == 1
        data = jax.profiler.ProfileData.from_file(paths[0])
        names = {ev.name for plane in data.planes
                 if plane.name.startswith('/host:')
                 for line in plane.lines for ev in line.events}
        assert 'engine.test_work' in names
    finally:
        shutil.rmtree(got['dir'], ignore_errors=True)
    assert 'error' in profiler.device_trace('soon')
