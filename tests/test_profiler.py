"""Runtime profiler (observability/profiler.py): ledger bounds,
compile detection + recompile-storm firing at budget+1, device-memory
reconciliation against the engine's block accounting, cold-start
phase-ledger monotonicity, the SKYTPU_PROFILE=0 no-op, and the
snapshot-in-bundle contract with the black-box recorder.

Marked slow: the compile-detection legs genuinely jit (that is the
thing under test).
"""
import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import llama
from skypilot_tpu.observability import blackbox, profiler

pytestmark = pytest.mark.slow


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
