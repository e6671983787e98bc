"""skylint: one seeded violation + one annotated suppression per rule,
the env-flag typo case, and the PR 7 regression re-introduction proof.

jax-free (pure AST analysis) so the whole suite stays in the fast tier.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / 'tools'))

import skylint  # noqa: E402
from skylint.checkers import alert_rules as alert_mod  # noqa: E402
from skylint.checkers import base as base_mod  # noqa: E402
from skylint.checkers import engine_thread  # noqa: E402
from skylint.checkers import env_flags as env_mod  # noqa: E402
from skylint.checkers import event_names as event_mod  # noqa: E402
from skylint.checkers import host_sync  # noqa: E402
from skylint.checkers import lock_discipline  # noqa: E402
from skylint.checkers import metric_names  # noqa: E402
from skylint.checkers import pycache as pycache_mod  # noqa: E402


def _sf(tmp_path, code, name='fixture.py', rel_root=None):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code), encoding='utf-8')
    return skylint.SourceFile(p, rel_root or tmp_path)


def _rules(findings):
    return [f.rule for f in findings]


# -- (1) lock discipline -----------------------------------------------------


def test_guarded_by_flags_unlocked_access(tmp_path):
    sf = _sf(tmp_path, '''
        class Engine:
            _GUARDED_BY = {'_requests': '_lock'}

            def bad(self):
                self._requests.append(1)

            def good(self):
                with self._lock:
                    self._requests.append(1)
        ''')
    findings = lock_discipline.LockDiscipline().check_file(sf)
    assert len(findings) == 1
    assert findings[0].rule == 'guarded-by'
    assert '_requests' in findings[0].message
    # the finding is in bad(), not good()
    assert sf.lines[findings[0].line - 1].strip() == \
        'self._requests.append(1)'
    assert findings[0].line < sf.text.index('def good')


def test_guarded_by_locked_suppression_and_reason_required(tmp_path):
    sf = _sf(tmp_path, '''
        class Engine:
            _GUARDED_BY = {'_n': '_lock'}

            # skylint: locked(callers hold _lock per the docstring)
            def bump_locked(self):
                self._n += 1

            def peek(self):
                return self._n  # skylint: locked(single-writer read)
        ''')
    assert lock_discipline.LockDiscipline().check_file(sf) == []
    # A reasonless suppression is itself a finding (base checker).
    sf2 = _sf(tmp_path, '''
        class Engine:
            _GUARDED_BY = {'_n': '_lock'}

            # skylint: locked()
            def bump_locked(self):
                self._n += 1
        ''', name='reasonless.py')
    ann = base_mod.Annotations().check_file(sf2)
    assert any(f.rule == 'annotation' and 'reason' in f.message
               for f in ann)


def test_guarded_by_per_assignment_comment_form(tmp_path):
    sf = _sf(tmp_path, '''
        class Engine:
            def __init__(self):
                self._q = []  # skylint: guarded-by=_lock

            def bad(self):
                self._q.pop()
        ''')
    findings = lock_discipline.LockDiscipline().check_file(sf)
    assert _rules(findings) == ['guarded-by']


def test_guarded_by_nested_def_does_not_inherit_lock(tmp_path):
    # A closure may run after the with-block releases the lock.
    sf = _sf(tmp_path, '''
        class Engine:
            _GUARDED_BY = {'_q': '_lock'}

            def sched(self):
                with self._lock:
                    def cb():
                        self._q.pop()
                    return cb
        ''')
    findings = lock_discipline.LockDiscipline().check_file(sf)
    assert _rules(findings) == ['guarded-by']


def test_guarded_by_module_level(tmp_path):
    sf = _sf(tmp_path, '''
        import threading
        _lock = threading.Lock()
        _samples = []
        _GUARDED_BY = {'_samples': '_lock'}

        def bad():
            _samples.append(1)

        def good():
            with _lock:
                _samples.append(1)
        ''')
    findings = lock_discipline.LockDiscipline().check_file(sf)
    assert _rules(findings) == ['guarded-by']


# -- (2) engine-thread raise safety ------------------------------------------


ENGINE_FIXTURE = '''
    class Engine:
        # skylint: engine-thread
        def _retire(self, req):
            if req is None:
                raise ValueError('no request')   # escapes -> finding

        # skylint: engine-thread
        def _retire_contained(self, req):
            try:
                if req is None:
                    raise ValueError('no request')
            except Exception:
                self._fail_one(req)

        # skylint: engine-thread
        def _invariant(self, req):
            # skylint: allow-raise(corrupt slot table: every stream is
            # already poisoned, nuking them IS the correct blast radius)
            raise RuntimeError('slot table corrupt')

        def _http_surface(self, req):
            raise ValueError('fine: not an engine-thread function')
    '''


def test_engine_raise_seeded_violation_and_suppressions(tmp_path):
    sf = _sf(tmp_path, ENGINE_FIXTURE)
    findings = engine_thread.EngineThreadRaise().check_file(sf)
    assert len(findings) == 1
    assert findings[0].rule == 'engine-raise'
    assert '_retire' in findings[0].message
    assert '_fail_everything' in findings[0].message


def test_engine_raise_handler_body_not_protected(tmp_path):
    sf = _sf(tmp_path, '''
        # skylint: engine-thread
        def _step():
            try:
                pass
            except Exception:
                raise RuntimeError('re-raise escapes the engine loop')
        ''')
    findings = engine_thread.EngineThreadRaise().check_file(sf)
    assert _rules(findings) == ['engine-raise']


def test_pr7_regression_reintroduced_is_caught(tmp_path):
    """Re-introduce the PR 7 bug — a shape-skew raise on the
    engine-thread install path of the REAL engine.py — and prove the
    unmodified rule set catches it (acceptance criterion)."""
    src = (REPO / 'skypilot_tpu/models/engine.py').read_text(
        encoding='utf-8')
    marker = '    def _install_import_paged(self, entry: _ImportEntry,'
    assert marker in src, 'engine.py install surface moved'
    # Clean copy: no engine-raise findings today.
    clean = _sf(tmp_path, src, name='engine_clean.py')
    checker = engine_thread.EngineThreadRaise()
    assert [f for f in checker.check_file(clean)
            if f.rule == 'engine-raise'] == []
    # Put the synchronous validation back where PR 7 removed it from:
    # inside the engine-thread install, raising instead of 400-ing.
    lines = src.splitlines(keepends=True)
    at = next(i for i, ln in enumerate(lines) if marker in ln)
    body = next(i for i in range(at + 1, len(lines))
                if lines[i].strip() == 'req = entry.req')
    lines.insert(body + 1, (
        '        if entry.k is not None and entry.k.shape[0] != '
        'self.cfg.n_layers:\n'
        "            raise ValueError('shape-skewed import payload')\n"))
    bugged = _sf(tmp_path, ''.join(lines), name='engine_bugged.py')
    findings = [f for f in checker.check_file(bugged)
                if f.rule == 'engine-raise']
    assert len(findings) == 1
    assert '_install_import_paged' in findings[0].message


# -- (3) host-sync in hot path -----------------------------------------------


def test_host_sync_seeded_violation_and_suppression(tmp_path):
    sf = _sf(tmp_path, '''
        class Engine:
            # skylint: hot-path
            def _loop(self):
                self._step()

            def _step(self):
                n = self._count.item()        # sync inside the closure
                # skylint: allow-host-sync(designed fetch point)
                toks = jax.device_get(self._toks)
                return n, toks
        ''')
    findings = host_sync.HostSync().check_file(sf)
    assert len(findings) == 1
    assert findings[0].rule == 'host-sync'
    assert '.item()' in findings[0].message
    assert '_step' in findings[0].message  # reached transitively


def test_host_sync_jit_scope_and_host_locals_exempt(tmp_path):
    sf = _sf(tmp_path, '''
        import jax
        import numpy as np

        @jax.jit
        def _kernel(x):
            return jax.device_get(x)    # sync under trace -> finding

        def _cold(x):
            buf = np.zeros((4,))
            a = np.asarray(buf)         # host local: exempt
            b = np.asarray([1, 2, 3])   # literal: exempt
            return a, b, x.item()       # not hot, not jit: no finding
        ''')
    findings = host_sync.HostSync().check_file(sf)
    assert len(findings) == 1
    assert '_kernel' in findings[0].message
    assert 'jit' in findings[0].message


def test_host_sync_function_level_allow(tmp_path):
    sf = _sf(tmp_path, '''
        class Engine:
            # skylint: hot-path
            def _loop(self):
                self._export()

            # skylint: allow-host-sync(whole function is the designed
            # serialization surface)
            def _export(self):
                return jax.device_get(self._cache)
        ''')
    assert host_sync.HostSync().check_file(sf) == []


# -- (4) env-flag registry ---------------------------------------------------


def test_env_flag_typo_is_caught_with_hint(tmp_path):
    sf = _sf(tmp_path, '''
        import os
        v = os.environ.get('SKYTPU_LLM_PIPLINE', '1')
        ''')
    findings = env_mod.EnvFlags().check_file(sf)
    assert len(findings) == 1
    assert findings[0].rule == 'env-flag'
    # skylint: allow-env(the deliberate typo this test seeds)
    assert 'SKYTPU_LLM_PIPLINE' in findings[0].message
    assert 'SKYTPU_LLM_PIPELINE' in findings[0].message  # typo hint


def test_env_flag_declared_ok_and_allow_env(tmp_path):
    sf = _sf(tmp_path, '''
        import os
        a = os.environ.get('SKYTPU_LLM_PIPELINE', '1')
        # skylint: allow-env(fixture flag for this very test)
        b = os.environ.get('SKYTPU_NOT_A_REAL_FLAG')
        ''')
    assert env_mod.EnvFlags().check_file(sf) == []


def test_env_flag_registry_has_no_dead_flags():
    """Every declared flag is read somewhere in the real tree (the
    tree-wide direction of the checker, against the live registry)."""
    files = skylint.load_files()
    findings = env_mod.EnvFlags().check_tree(files, skylint.ROOT)
    assert findings == [], '\n'.join(str(f) for f in findings)


# -- (5) metric-name cross-check ---------------------------------------------


def test_metric_defined_outside_registry_flagged(tmp_path):
    sf = _sf(tmp_path, '''
        from prometheus_client import Gauge
        G = Gauge('skytpu_rogue_series', 'defined outside metrics.py')
        ''')
    findings = metric_names.MetricNames().check_file(sf)
    assert _rules(findings) == ['metric-name']
    assert 'skytpu_rogue_series' in findings[0].message


def test_metric_unknown_reference_in_serve_scope(tmp_path):
    sf = _sf(tmp_path / 'skypilot_tpu' / 'serve', '''
        NAME = 'skytpu_series_nobody_defined'
        ''', name='fake.py', rel_root=tmp_path)
    findings = metric_names.MetricNames().check_tree([sf], REPO)
    mine = [f for f in findings if f.path == sf.rel]
    assert len(mine) == 1
    assert 'skytpu_series_nobody_defined' in mine[0].message


def test_metric_cross_check_clean_on_real_tree():
    files = skylint.load_files()
    findings = metric_names.MetricNames().check_tree(files, skylint.ROOT)
    assert findings == [], '\n'.join(str(f) for f in findings)


# -- event-name (black-box flight-recorder registry) -------------------------


def test_event_undeclared_record_flagged_with_hint(tmp_path):
    sf = _sf(tmp_path, '''
        from skypilot_tpu.observability import blackbox
        blackbox.record('engine.admitx', n=1)
        ''')
    findings = event_mod.EventNames().check_file(sf)
    assert _rules(findings) == ['event-name']
    assert 'engine.admitx' in findings[0].message
    assert "'engine.admit'" in findings[0].message  # did-you-mean


def test_event_dynamic_name_flagged_and_suppressible(tmp_path):
    sf = _sf(tmp_path, '''
        from skypilot_tpu.observability import blackbox as bb
        name = 'engine.admit'
        bb.record(name)
        bb.record(name)  # skylint: allow-event(fixture: dynamic name)
        ''')
    findings = event_mod.EventNames().check_file(sf)
    assert len(findings) == 1
    assert 'string literal' in findings[0].message


def test_event_unrelated_record_methods_ignored(tmp_path):
    # trace.py's ring, heartbeat recorders etc. also have .record
    # methods — only callees resolving to the blackbox module count.
    sf = _sf(tmp_path, '''
        class Ring:
            def record(self, item):
                return item
        Ring().record('not.an.event')
        ''')
    assert event_mod.EventNames().check_file(sf) == []


def test_event_declared_ok_via_function_import(tmp_path):
    sf = _sf(tmp_path, '''
        from skypilot_tpu.observability.blackbox import record
        record('engine.admit', n=1)
        ''')
    assert event_mod.EventNames().check_file(sf) == []


def test_event_dead_declaration_detected(tmp_path):
    reg = tmp_path / 'skypilot_tpu' / 'observability' / 'blackbox.py'
    reg.parent.mkdir(parents=True)
    reg.write_text(textwrap.dedent('''
        def Event(name, doc):
            return (name, doc)
        EVENTS = (Event('ghost.event', 'declared, never recorded'),)
        '''), encoding='utf-8')
    findings = event_mod.EventNames().check_tree([], tmp_path)
    assert _rules(findings) == ['event-name']
    assert 'ghost.event' in findings[0].message
    assert 'dead event' in findings[0].message


def test_event_cross_check_clean_on_real_tree():
    files = skylint.load_files()
    findings = event_mod.EventNames().check_tree(files, skylint.ROOT)
    assert findings == [], '\n'.join(str(f) for f in findings)


# -- alert-rule (SLO registry cross-check) -----------------------------------


_ALERT_METRICS_SRC = '''
    G = Gauge('skytpu_serve_qos_queue_depth', 'doc', ['qos_class'])
    '''


def _alert_tree(tmp_path, slo_src):
    slo_py = tmp_path / 'skypilot_tpu' / 'observability' / 'slo.py'
    slo_py.parent.mkdir(parents=True)
    slo_py.write_text(textwrap.dedent(slo_src), encoding='utf-8')
    metrics_py = tmp_path / 'skypilot_tpu' / 'server' / 'metrics.py'
    metrics_py.parent.mkdir(parents=True)
    metrics_py.write_text(textwrap.dedent(_ALERT_METRICS_SRC),
                          encoding='utf-8')
    (tmp_path / 'docs').mkdir()
    (tmp_path / 'docs' / 'operations.md').write_text(
        '| `serve.queue_depth` | page |\n', encoding='utf-8')
    return tmp_path


def test_alert_rule_typo_source_gets_hint(tmp_path):
    root = _alert_tree(tmp_path, '''
        HEALTH_FIELDS = (HealthField('replica.queue_depth', 'doc'),)
        RULES = (
            Rule('serve.queue_depth', 'doc', severity='page',
                 signal='queue_depth',
                 sources=('replica.queue_depht',
                          'skytpu_serve_qos_queue_depth'),
                 op='>', threshold=1.0),
        )
        SIGNALS = {'queue_depth': None}
        ''')
    findings = alert_mod.AlertRules().check_tree([], root)
    msgs = [f.message for f in findings]
    # The typo'd health field is flagged with a did-you-mean, and the
    # now-unreferenced declared field is the matching dead entry.
    assert any("'replica.queue_depht'" in m
               and "did you mean 'replica.queue_depth'" in m
               for m in msgs), msgs
    assert any('dead vocabulary entry' in m for m in msgs), msgs
    assert all(f.rule == 'alert-rule' for f in findings)


def test_alert_rule_dead_rule_dead_signal_and_unknown_metric(tmp_path):
    root = _alert_tree(tmp_path, '''
        HEALTH_FIELDS = (HealthField('replica.queue_depth', 'doc'),)
        RULES = (
            Rule('serve.queue_depth', 'doc', severity='page',
                 signal='queue_dpth',
                 sources=('replica.queue_depth',
                          'skytpu_no_such_series'),
                 op='>', threshold=1.0),
        )
        SIGNALS = {'queue_depth': None, 'unused_signal': None}
        ''')
    findings = alert_mod.AlertRules().check_tree([], root)
    msgs = [f.message for f in findings]
    assert any('declared but never evaluated' in m
               and "did you mean 'queue_depth'" in m for m in msgs), msgs
    assert any("'unused_signal'" in m and 'dead signal' in m
               for m in msgs), msgs
    assert any("'skytpu_no_such_series'" in m and 'not defined' in m
               for m in msgs), msgs


def test_alert_rule_undocumented_and_bad_severity(tmp_path):
    root = _alert_tree(tmp_path, '''
        HEALTH_FIELDS = (HealthField('replica.queue_depth', 'doc'),)
        RULES = (
            Rule('serve.mystery', 'doc', severity='critical',
                 signal='queue_depth',
                 sources=('replica.queue_depth',),
                 op='>', threshold=1.0),
        )
        SIGNALS = {'queue_depth': None}
        ''')
    findings = alert_mod.AlertRules().check_tree([], root)
    msgs = [f.message for f in findings]
    assert any("severity 'critical'" in m for m in msgs), msgs
    assert any('not documented' in m for m in msgs), msgs


def test_alert_rule_clean_on_real_tree():
    findings = alert_mod.AlertRules().check_tree([], skylint.ROOT)
    assert findings == [], '\n'.join(str(f) for f in findings)


# -- tracked-pycache ---------------------------------------------------------


def test_pycache_gitignore_patterns_required(tmp_path):
    # Bare dir (no .gitignore): both required patterns are findings.
    findings = pycache_mod.TrackedPycache().check_tree([], tmp_path)
    msgs = ' '.join(f.message for f in findings)
    assert '__pycache__/' in msgs and '*.pyc' in msgs
    # Covering .gitignore: clean.
    (tmp_path / '.gitignore').write_text('__pycache__/\n*.pyc\n')
    assert pycache_mod.TrackedPycache().check_tree([], tmp_path) == []


def test_no_tracked_bytecode_in_repo():
    findings = pycache_mod.TrackedPycache().check_tree([], REPO)
    assert findings == [], '\n'.join(str(f) for f in findings)


# -- annotations are part of the contract ------------------------------------


def test_unknown_directive_is_a_finding(tmp_path):
    sf = _sf(tmp_path, 'x = 1  # skylint: gaurded-by=_lock\n')
    findings = base_mod.Annotations().check_file(sf)
    assert _rules(findings) == ['annotation']
    assert 'gaurded-by' in findings[0].message


def test_multiline_comment_block_reason_parses(tmp_path):
    sf = _sf(tmp_path, '''
        class Engine:
            _GUARDED_BY = {'_n': '_lock'}

            # skylint: locked(a reason long enough that it wraps across
            # two comment lines and must still parse as one directive)
            def bump_locked(self):
                self._n += 1
        ''')
    assert base_mod.Annotations().check_file(sf) == []
    assert lock_discipline.LockDiscipline().check_file(sf) == []


# -- driver / CI gate --------------------------------------------------------


# -- jit-program (compile-ledger registry cross-check) -----------------------


def test_bare_jax_jit_flagged_and_hatch_suppresses(tmp_path):
    from skylint.checkers import jit_programs as jit_mod
    sf = _sf(tmp_path, '''
        import jax

        def _impl(x):
            return x

        _f = jax.jit(_impl)
        ''')
    findings = jit_mod.JitPrograms().check_file(sf)
    assert _rules(findings) == ['jit-program']
    assert 'profiled_jit' in findings[0].message
    ok = _sf(tmp_path, '''
        import jax

        def _impl(x):
            return x

        # skylint: allow-jit(startup-time init, not a serving program)
        _f = jax.jit(_impl)
        ''', name='hatched.py')
    assert jit_mod.JitPrograms().check_file(ok) == []


def test_serve_tree_allow_jit_must_name_declared_exception(tmp_path):
    """Inside skypilot_tpu/serve/ the allow-jit hatch is narrower: the
    reason must name a declared exception category (the AOT warm-up
    driver) — an arbitrary reasoned hatch there would let serving
    programs dodge the zero-post-READY-compiles gate."""
    from skylint.checkers import jit_programs as jit_mod
    code = '''
        import jax

        def _impl(x):
            return x

        # skylint: allow-jit({reason})
        _f = jax.jit(_impl)
        '''
    bad = _sf(tmp_path, code.format(reason='faster this way'),
              name='skypilot_tpu/serve/thing.py')
    findings = jit_mod.JitPrograms().check_file(bad)
    assert _rules(findings) == ['jit-program']
    assert 'declared exception' in findings[0].message
    ok = _sf(tmp_path,
             code.format(reason='AOT warm-up driver cache canary'),
             name='skypilot_tpu/serve/warm.py')
    assert jit_mod.JitPrograms().check_file(ok) == []
    # Outside the serve tree any reasoned hatch still suppresses.
    elsewhere = _sf(tmp_path, code.format(reason='faster this way'),
                    name='skypilot_tpu/train/thing.py')
    assert jit_mod.JitPrograms().check_file(elsewhere) == []


def test_profiled_jit_typo_gets_did_you_mean(tmp_path):
    from skylint.checkers import jit_programs as jit_mod
    sf = _sf(tmp_path, '''
        from skypilot_tpu.observability.profiler import profiled_jit

        def _impl(x):
            return x

        _f = profiled_jit('engine.paged_chunks', _impl)
        ''')
    findings = jit_mod.JitPrograms().check_file(sf)
    assert _rules(findings) == ['jit-program']
    assert "'engine.paged_chunk'" in findings[0].message  # did-you-mean
    ok = _sf(tmp_path, '''
        from skypilot_tpu.observability.profiler import profiled_jit

        def _impl(x):
            return x

        _f = profiled_jit('engine.paged_chunk', _impl)
        ''', name='ok.py')
    assert jit_mod.JitPrograms().check_file(ok) == []


def test_profiled_jit_dynamic_name_flagged(tmp_path):
    from skylint.checkers import jit_programs as jit_mod
    sf = _sf(tmp_path, '''
        from skypilot_tpu.observability.profiler import profiled_jit

        NAME = 'engine.paged_chunk'

        def _impl(x):
            return x

        _f = profiled_jit(NAME, _impl)
        ''')
    findings = jit_mod.JitPrograms().check_file(sf)
    assert _rules(findings) == ['jit-program']
    assert 'string literal' in findings[0].message


def test_jit_dead_program_detected(tmp_path):
    from skylint.checkers import jit_programs as jit_mod
    reg = tmp_path / 'skypilot_tpu' / 'observability' / 'profiler.py'
    reg.parent.mkdir(parents=True)
    reg.write_text(textwrap.dedent('''
        def Program(name, doc, budget):
            return (name, doc, budget)
        PROGRAMS = (
            Program('live.prog', 'wrapped below', budget=2),
            Program('ghost.prog', 'declared, never wrapped', budget=2),
        )
        '''), encoding='utf-8')
    user = _sf(tmp_path, '''
        from skypilot_tpu.observability.profiler import profiled_jit

        def _impl(x):
            return x

        _f = profiled_jit('live.prog', _impl)
        ''', name='user.py')
    checker = jit_mod.JitPrograms()
    checker._load_registry(tmp_path)  # anchor at the fixture tree
    findings = checker.check_tree([user], tmp_path)
    assert _rules(findings) == ['jit-program']
    assert 'ghost.prog' in findings[0].message
    assert 'dead program' in findings[0].message


def test_jit_program_clean_on_real_tree():
    from skylint.checkers import jit_programs as jit_mod
    files = skylint.load_files()
    checker = jit_mod.JitPrograms()
    findings = [f for sf in files for f in checker.check_file(sf)]
    findings += checker.check_tree(files, skylint.ROOT)
    assert findings == [], '\n'.join(str(f) for f in findings)


def test_cli_exit_codes(tmp_path):
    from skylint import cli
    bad = tmp_path / 'bad.py'
    bad.write_text(textwrap.dedent('''
        class Engine:
            _GUARDED_BY = {'_n': '_lock'}

            def bump(self):
                self._n += 1
        '''), encoding='utf-8')
    assert cli.main([str(bad)]) == 1
    good = tmp_path / 'good.py'
    good.write_text('x = 1\n', encoding='utf-8')
    assert cli.main([str(good)]) == 0


@pytest.mark.slow
def test_full_suite_zero_findings():
    """`make lint` parity: the committed tree is finding-free."""
    findings, nfiles = skylint.run()
    assert nfiles > 100
    assert findings == [], '\n'.join(str(f) for f in findings)


def test_changed_mode_runs(tmp_path):
    """--changed never crashes outside a work tree and lints nothing."""
    proc = subprocess.run(
        [sys.executable, str(REPO / 'tools' / 'lint.py'), '--changed'],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    assert '0 finding(s)' in proc.stdout


# ===========================================================================
# Interprocedural concurrency rules (callgraph.py + concurrency.py)
# ===========================================================================

from skylint import callgraph  # noqa: E402
from skylint import cli as cli_mod  # noqa: E402
from skylint.checkers import concurrency  # noqa: E402


def _tree(tmp_path, **files):
    """A fixture skypilot_tpu/ tree; returns its root. Keys are file
    names inside the package ('a' -> skypilot_tpu/a.py, 'serve/b' ->
    skypilot_tpu/serve/b.py)."""
    pkg = tmp_path / 'skypilot_tpu'
    for name, code in files.items():
        p = pkg / (name + '.py')
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(code), encoding='utf-8')
        init = p.parent / '__init__.py'
        while not init.exists() and tmp_path in init.parents:
            init.write_text('')
            init = init.parent.parent / '__init__.py'
    return tmp_path


_CYCLE_A = '''
    import threading
    from skypilot_tpu import beta

    class Alpha:
        _GUARDED_BY = {'_n': '_lock'}

        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0
            self._peer = beta.Beta(self)

        def poke(self):
            with self._lock:
                self._peer.bump()

        def count(self):
            with self._lock:
                return self._n
    '''

_CYCLE_B = '''
    import threading
    from skypilot_tpu import alpha

    class Beta:
        def __init__(self, a):
            self._lock = threading.Lock()
            self._m = 0
            self._owner = alpha.Alpha()

        def bump(self):
            with self._lock:
                self._m += 1

        def snap(self):
            with self._lock:
                return self._owner.count()
    '''


def test_lock_order_cycle_detected_with_both_chains(tmp_path):
    root = _tree(tmp_path, alpha=_CYCLE_A, beta=_CYCLE_B)
    findings = concurrency.LockOrder().check_tree([], root)
    assert [f.rule for f in findings] == ['lock-order']
    msg = findings[0].message
    # Both acquisition chains, file:line by file:line.
    assert 'chain' in msg
    assert 'skypilot_tpu/alpha.py:' in msg
    assert 'skypilot_tpu/beta.py:' in msg
    assert 'Alpha._lock' in msg and 'Beta._lock' in msg
    # Both files implicated, so --changed keeps the finding when
    # either side is the dirty one.
    assert set(findings[0].involved) >= {'skypilot_tpu/alpha.py',
                                         'skypilot_tpu/beta.py'}


def test_lock_order_allow_order_suppresses(tmp_path):
    root = _tree(tmp_path, alpha=_CYCLE_A, beta=_CYCLE_B.replace(
        'with self._lock:\n                return self._owner.count()',
        'with self._lock:  '
        '# skylint: allow-order(fixture: order is by design)\n'
        '                return self._owner.count()'))
    assert concurrency.LockOrder().check_tree([], root) == []


def test_lock_order_self_deadlock_and_rlock_exempt(tmp_path):
    root = _tree(tmp_path, gamma='''
        import threading

        class Gamma:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    return 1
        ''')
    findings = concurrency.LockOrder().check_tree([], root)
    assert len(findings) == 1
    assert 'self-deadlock' in findings[0].message
    # The same shape over an RLock is reentrant and legal.
    root2 = _tree(tmp_path / 'r', gamma='''
        import threading

        class Gamma:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    return 1
        ''')
    assert concurrency.LockOrder().check_tree([], root2) == []


def test_blocking_under_lock_direct_transitive_and_hatch(tmp_path):
    root = _tree(tmp_path, srv='''
        import threading
        import time

        class Srv:
            def __init__(self):
                self._lock = threading.Lock()

            def bad_direct(self):
                with self._lock:
                    time.sleep(1.0)

            def bad_transitive(self):
                with self._lock:
                    self._helper()

            def _helper(self):
                time.sleep(0.5)

            def ok(self):
                with self._lock:
                    # skylint: allow-block(fixture: designed wait)
                    time.sleep(0.1)
        ''')
    findings = concurrency.BlockingUnderLock().check_tree([], root)
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2
    assert any('bad_direct' in m for m in msgs)
    # The transitive finding prints the call chain to the sleep.
    trans = next(m for m in msgs if 'bad_transitive' in m)
    assert '_helper' in trans and 'time.sleep' in trans


def test_blocking_under_lock_locked_entry_annotation(tmp_path):
    # A locked(...) def that NAMES the lock runs with it held: its
    # blocking calls count even with no local `with`.
    root = _tree(tmp_path, srv='''
        import threading
        import time

        class Srv:
            _GUARDED_BY = {'_n': '_lock'}

            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            # skylint: locked(every caller holds _lock)
            def _flush_locked(self):
                time.sleep(1.0)
        ''')
    findings = concurrency.BlockingUnderLock().check_tree([], root)
    assert len(findings) == 1
    assert '_flush_locked' in findings[0].message


def test_event_loop_block_closure_and_executor_clean(tmp_path):
    root = _tree(tmp_path, web='''
        import asyncio
        import time

        class Handler:
            async def handle(self, request):
                return self._load()

            def _load(self):
                time.sleep(0.2)
                return 1

            async def handle_ok(self, request):
                return await asyncio.get_event_loop().run_in_executor(
                    None, self._load_ok)

            def _load_ok(self):
                time.sleep(0.2)
                return 1
        ''')
    findings = concurrency.EventLoopBlock().check_tree([], root)
    # _load is reachable by direct call from an async def; _load_ok is
    # only ever a reference passed to the executor — clean by
    # construction. (One finding, not two.)
    assert len(findings) == 1
    msg = findings[0].message
    assert 'async def Handler.handle' in msg and '_load' in msg
    assert 'time.sleep' in msg


def test_event_loop_block_allow_block_hatch(tmp_path):
    root = _tree(tmp_path, web='''
        import time

        class Handler:
            async def handle(self, request):
                # skylint: allow-block(fixture: sub-ms local read)
                time.sleep(0.001)
                return 1
        ''')
    assert concurrency.EventLoopBlock().check_tree([], root) == []


def test_resource_pair_leak_paths_and_finally(tmp_path):
    root = _tree(tmp_path, pool='''
        class Pool:
            # skylint: resource-pair=blocks.acquire
            def alloc(self):
                return [1]

            # skylint: resource-pair=blocks.release
            def release(self, blocks):
                del blocks

            def leak_on_exception(self):
                got = self.alloc()
                self.fallible()
                self.release(got)

            def leak_on_return(self):
                got = self.alloc()
                if len(got) > 3:
                    return None  # early exit skips the release
                self.release(got)

            def ok_finally(self):
                got = self.alloc()
                try:
                    self.fallible()
                finally:
                    self.release(got)

            def ok_escape(self):
                self.slots = self.alloc()

            def fallible(self):
                raise ValueError('boom')
        ''')
    findings = concurrency.ResourcePair().check_tree([], root)
    msgs = [f.message for f in findings]
    assert len(findings) == 2, msgs
    assert any('leak_on_exception' in m and 'fallible' in m
               for m in msgs)
    assert any('leak_on_return' in m for m in msgs)


def test_resource_pair_acquire_inside_try_is_clean(tmp_path):
    # If the acquire ITSELF raises, nothing was acquired: handlers are
    # analyzed from the try-entry state, so this idiom is leak-free —
    # while a mid-body leak reaching a non-releasing handler is still
    # an exception-edge finding.
    root = _tree(tmp_path, pool='''
        class Pool:
            # skylint: resource-pair=blocks.acquire
            def alloc(self):
                return [1]

            # skylint: resource-pair=blocks.release
            def release(self, blocks):
                del blocks

            def ok_acquire_in_try(self):
                try:
                    got = self.alloc()
                except ValueError:
                    return None
                self.release(got)

            def bad_mid_body(self):
                try:
                    got = self.alloc()
                    self.fallible()
                except ValueError:
                    return None
                self.release(got)

            def fallible(self):
                raise ValueError('boom')
        ''')
    findings = concurrency.ResourcePair().check_tree([], root)
    msgs = [f.message for f in findings]
    assert all('ok_acquire_in_try' not in m for m in msgs), msgs
    assert any('bad_mid_body' in m for m in msgs), msgs


def test_resource_pair_tmpfile_builtin_and_cleanup(tmp_path):
    root = _tree(tmp_path, spool='''
        import json
        import os

        def bad_write(path, payload):
            tmp = path + '.tmp'
            with open(tmp, 'w') as f:
                json.dump(payload, f)
            os.replace(tmp, path)

        def good_write(path, payload):
            tmp = path + '.tmp'
            try:
                with open(tmp, 'w') as f:
                    json.dump(payload, f)
                os.replace(tmp, path)
            except OSError:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        ''')
    findings = concurrency.ResourcePair().check_tree([], root)
    assert len(findings) == 1
    assert 'bad_write' in findings[0].message
    assert "'tmpfile'" in findings[0].message


def test_resource_pair_kv_tier_leaked_host_entry(tmp_path):
    """The hierarchical-KV demote/promote lifecycle (ISSUE 20) is a
    declared resource pair: a host-pool entry acquired (insert) but
    neither released (pop), transferred (spill), nor hatch-annotated
    on an exception edge is a lint finding — while the transfer def
    itself and a reasoned allow-leak are clean."""
    root = _tree(tmp_path, tiers='''
        class Tiers:
            # skylint: resource-pair=kv_tier.acquire
            def insert_entry(self, entry):
                return entry

            # skylint: resource-pair=kv_tier.release
            def pop_entry(self, entry):
                del entry

            # skylint: resource-pair=kv_tier.transfer
            def spill_entries(self, batch):
                del batch

            def leaky_demote(self, entry):
                self.insert_entry(entry)
                self.fallible()  # exception edge: the entry leaks

            def ok_released(self, entry):
                self.insert_entry(entry)
                try:
                    self.fallible()
                finally:
                    self.pop_entry(entry)

            def ok_hatched(self, entry):
                # skylint: allow-leak(fixture: ownership parks in the
                # pool's own LRU)
                self.insert_entry(entry)
                self.fallible()

            def fallible(self):
                raise ValueError('boom')
        ''')
    findings = concurrency.ResourcePair().check_tree([], root)
    msgs = [f.message for f in findings]
    assert any("'kv_tier'" in m and 'leaky_demote' in m
               for m in msgs), msgs
    assert all('ok_released' not in m for m in msgs), msgs
    assert all('ok_hatched' not in m for m in msgs), msgs
    assert all('spill_entries' not in m for m in msgs), msgs


def test_resource_pair_kv_tier_acquire_without_release_anywhere(
        tmp_path):
    """A kv_tier acquire with no release/transfer in the whole tree is
    a pair-declaration finding (a leak by construction)."""
    root = _tree(tmp_path, tiers='''
        class Tiers:
            # skylint: resource-pair=kv_tier.acquire
            def insert_entry(self, entry):
                return entry
        ''')
    findings = concurrency.ResourcePair().check_tree([], root)
    assert any("'kv_tier'" in f.message
               and 'no release/transfer' in f.message
               for f in findings), [f.message for f in findings]


def test_hatches_audit_ledger_and_reasonless_failure(tmp_path, capsys):
    """``skylint --hatches`` enumerates every allow-* suppression with
    its reason (the reviewable ledger) and exits nonzero when any
    hatch lacks one."""
    root = _tree(tmp_path, mod='''
        import time

        def documented():
            time.sleep(1)  # skylint: allow-block(fixture: documented)

        def silent():
            time.sleep(1)  # skylint: allow-block()
        ''')
    rc = cli_mod._audit_hatches(root, 'text')
    out = capsys.readouterr().out
    assert rc == 1, out
    assert 'fixture: documented' in out
    assert '1 without a reason' in out
    # JSON surface carries the same ledger for CI annotation.
    rc = cli_mod._audit_hatches(root, 'json')
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1 and payload['reasonless'] == 1
    assert len(payload['hatches']) == 2
    # A fully reasoned tree passes.
    root2 = _tree(tmp_path / 'ok', mod='''
        import time

        def documented():
            time.sleep(1)  # skylint: allow-block(fixture: documented)
        ''')
    assert cli_mod._audit_hatches(root2, 'text') == 0
    assert 'without a reason' in capsys.readouterr().out


def test_resource_pair_name_typo_did_you_mean(tmp_path):
    root = _tree(tmp_path, pool='''
        class Pool:
            # skylint: resource-pair=kv_blockz.acquire
            def alloc(self):
                return [1]

            # skylint: resource-pair=kv_blocks.release
            def release(self, blocks):
                del blocks

            # skylint: resource-pair=kv_blocks.acquire
            def alloc2(self):
                return [2]
        ''')
    findings = concurrency.ResourcePair().check_tree([], root)
    assert any("'kv_blockz'" in f.message
               and "did you mean 'kv_blocks'" in f.message
               for f in findings), [f.message for f in findings]


def test_resource_pair_role_typo_is_annotation_finding(tmp_path):
    sf = _sf(tmp_path, '''
        class Pool:
            # skylint: resource-pair=kv_blocks.aquire
            def alloc(self):
                return [1]
        ''')
    findings = base_mod.Annotations().check_file(sf)
    assert _rules(findings) == ['annotation']
    assert "'kv_blocks.acquire'" in findings[0].message  # did-you-mean


def test_unknown_directive_gets_did_you_mean(tmp_path):
    sf = _sf(tmp_path, 'x = 1  # skylint: allow-blok(reason here)\n')
    findings = base_mod.Annotations().check_file(sf)
    assert _rules(findings) == ['annotation']
    assert "'allow-block'" in findings[0].message


# -- the LB/controller regression injection ---------------------------------


def test_injected_lb_controller_lock_cycle_is_caught(tmp_path):
    """Deliberately introduce a two-lock cycle between the REAL
    load_balancer.py and controller.py and prove the unmodified rule
    set catches it (acceptance criterion): controller side takes a new
    module lock then pushes into the LB (which takes _stats_lock); LB
    side takes _stats_lock then calls back into the controller module
    (which takes the module lock)."""
    lb_src = (REPO / 'skypilot_tpu/serve/load_balancer.py').read_text(
        encoding='utf-8')
    ctl_src = (REPO / 'skypilot_tpu/serve/controller.py').read_text(
        encoding='utf-8')
    root = _tree(tmp_path)
    serve = tmp_path / 'skypilot_tpu' / 'serve'
    serve.mkdir(parents=True)
    (tmp_path / 'skypilot_tpu' / '__init__.py').write_text('')
    (serve / '__init__.py').write_text('')
    # Clean copies first: the unmodified pair has no ordering cycle.
    (serve / 'load_balancer.py').write_text(lb_src, encoding='utf-8')
    (serve / 'controller.py').write_text(ctl_src, encoding='utf-8')
    checker = concurrency.LockOrder()
    before = [f for f in checker.check_tree([], root)
              if 'load_balancer' in str(f.involved)
              or 'load_balancer' in f.path]
    assert before == [], '\n'.join(str(f) for f in before)
    # Inject: controller grows a module lock + a push that holds it
    # across lb.set_prefix_summaries() (which takes _stats_lock)...
    marker = '    def _sync_affinity_active(self) -> None:'
    assert marker in ctl_src, 'controller.py shape moved'
    ctl_bugged = ctl_src.replace(marker, (
        '    def _injected_push(self) -> None:\n'
        '        with _INJECTED_LOCK:\n'
        '            self.lb.set_prefix_summaries({})\n'
        '\n' + marker)) + (
        '\n\n_INJECTED_LOCK = threading.Lock()\n'
        '\n\ndef _injected_sweep() -> int:\n'
        '    with _INJECTED_LOCK:\n'
        '        return 1\n')
    # ...and the LB grows a drain that calls back into the controller
    # module while holding _stats_lock.
    lb_marker = '    def set_prefix_summaries(self'
    assert lb_marker in lb_src, 'load_balancer.py shape moved'
    lb_bugged = lb_src.replace(lb_marker, (
        '    def _injected_drain(self) -> int:\n'
        '        with self._stats_lock:\n'
        '            return controller_mod._injected_sweep()\n'
        '\n' + lb_marker)).replace(
        'from skypilot_tpu.utils import prefix_affinity',
        'from skypilot_tpu.utils import prefix_affinity\n'
        'from skypilot_tpu.serve import controller as controller_mod')
    (serve / 'load_balancer.py').write_text(lb_bugged, encoding='utf-8')
    (serve / 'controller.py').write_text(ctl_bugged, encoding='utf-8')
    findings = checker.check_tree([], root)
    assert findings, 'injected LB<->controller cycle was NOT caught'
    msg = findings[0].message
    assert '_stats_lock' in msg and '_INJECTED_LOCK' in msg
    assert 'skypilot_tpu/serve/load_balancer.py:' in msg
    assert 'skypilot_tpu/serve/controller.py:' in msg


# -- call-graph cache ---------------------------------------------------------


def test_cache_invalidates_on_upstream_callee_change(tmp_path):
    """--changed correctness: with only a.py in the dirty set, an edit
    to its UPSTREAM callee b.py must still be seen (the cache keys
    per-file local summaries by mtime; resolution always recomputes)."""
    root = _tree(tmp_path, a='''
        import threading
        from skypilot_tpu import b

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def tick(self):
                with self._lock:
                    b.helper()
        ''', b='''
        def helper():
            return 1
        ''')
    a_path = root / 'skypilot_tpu' / 'a.py'
    findings, _ = skylint.run([a_path], root, tree_wide=False)
    assert [f for f in findings
            if f.rule == 'blocking-under-lock'] == []
    # Upstream callee starts blocking; a.py itself is untouched.
    b_path = root / 'skypilot_tpu' / 'b.py'
    b_path.write_text(textwrap.dedent('''
        import time

        def helper():
            time.sleep(1.0)
        '''), encoding='utf-8')
    os.utime(b_path, (os.path.getmtime(b_path) + 10,) * 2)
    findings, _ = skylint.run([a_path], root, tree_wide=False)
    hits = [f for f in findings if f.rule == 'blocking-under-lock']
    assert len(hits) == 1, findings
    assert hits[0].path == 'skypilot_tpu/a.py'
    assert 'time.sleep' in hits[0].message


def test_cache_save_failure_leaves_no_tmp(tmp_path, monkeypatch):
    # The cache writer follows the tree's own resource-pair rule.
    root = _tree(tmp_path, a='def f():\n    return 1\n')
    callgraph._MEMO.clear()

    def boom(src, dst):
        raise OSError('injected')
    monkeypatch.setattr(callgraph.os, 'replace', boom)
    callgraph.get_graph([], root)  # best-effort: no raise
    monkeypatch.undo()
    cache_dir = root / callgraph.CACHE_DIR
    leftovers = [p.name for p in cache_dir.iterdir()] \
        if cache_dir.is_dir() else []
    assert [n for n in leftovers if n.endswith('.tmp')] == []


def test_cache_warm_hits_and_is_best_effort(tmp_path):
    root = _tree(tmp_path, a='def f():\n    return 1\n')
    callgraph._MEMO.clear()
    g1 = callgraph.get_graph([], root)
    assert g1.from_cache == 0
    callgraph._MEMO.clear()
    g2 = callgraph.get_graph([], root)
    assert g2.from_cache == g2.n_files  # warm: everything from cache
    # A corrupt cache file is ignored, not fatal.
    (root / callgraph.CACHE_DIR / callgraph.CACHE_NAME).write_text(
        '{torn', encoding='utf-8')
    callgraph._MEMO.clear()
    g3 = callgraph.get_graph([], root)
    assert g3.n_files == g2.n_files and g3.from_cache == 0


# -- driver robustness (deleted/renamed dirty files) --------------------------


def test_changed_files_skip_deleted_and_renamed(tmp_path, monkeypatch):
    (tmp_path / 'kept.py').write_text('x = 1\n')
    (tmp_path / 'new_name.py').write_text('y = 2\n')
    porcelain = (
        ' M kept.py\n'
        ' D deleted_worktree.py\n'
        'D  deleted_index.py\n'
        'R  old_name.py -> new_name.py\n'
        'R  other.py -> gone_after_rename.py\n'
        '?? brand_new_but_already_gone.py\n')

    class _Proc:
        stdout = porcelain

    monkeypatch.setattr(cli_mod.subprocess, 'run',
                        lambda *a, **k: _Proc())
    got = cli_mod._changed_files(tmp_path)
    assert [p.name for p in got] == ['kept.py', 'new_name.py']


def test_explicit_missing_path_is_skipped_not_crash(tmp_path, capsys):
    ok = tmp_path / 'ok.py'
    ok.write_text('x = 1\n')
    rc = cli_mod.main([str(ok), str(tmp_path / 'vanished.py')])
    captured = capsys.readouterr()
    assert rc == 0
    # The note goes to stderr: stdout is the machine-readable surface
    # under --format json and must stay parseable.
    assert 'skipping missing file' in captured.err
    assert '1 file(s)' in captured.out
    rc = cli_mod.main(['--format', 'json', str(ok),
                       str(tmp_path / 'vanished.py')])
    captured = capsys.readouterr()
    assert rc == 0
    assert json.loads(captured.out)['files'] == 1


def test_tree_wide_run_does_not_swallow_unreadable_file(tmp_path):
    # The CI gate must fail loudly on an unreadable committed file —
    # silently skipping it would exempt it from every rule.
    bad = tmp_path / 'skypilot_tpu'
    bad.mkdir()
    (bad / 'latin.py').write_bytes(b'# caf\xe9\nx = 1\n')  # not UTF-8
    with pytest.raises(UnicodeDecodeError):
        skylint.run(None, tmp_path, tree_wide=True)
    # ...but the --changed/explicit path is tolerant (deleted/renamed
    # races), which is the missing_ok split.
    findings, n = skylint.run([bad / 'latin.py'], tmp_path,
                              tree_wide=False)
    assert n == 0
    # (tracked-pycache always runs and flags the bare fixture dir's
    # missing .gitignore — irrelevant here.)
    assert [f for f in findings if f.rule != 'tracked-pycache'] == []


def test_noarg_condition_is_reentrant_for_lock_order(tmp_path):
    # threading.Condition() builds its own RLock: re-entry through a
    # call chain is legal Python, not a self-deadlock.
    root = _tree(tmp_path, w='''
        import threading

        class W:
            def __init__(self):
                self._cond = threading.Condition()

            def outer(self):
                with self._cond:
                    self.inner()

            def inner(self):
                with self._cond:
                    return 1
        ''')
    assert concurrency.LockOrder().check_tree([], root) == []


# -- machine-readable output --------------------------------------------------


def test_json_format_stable_ids(tmp_path, capsys):
    code = ('class E:\n'
            "    _GUARDED_BY = {'_n': '_lock'}\n"
            '    def bump(self):\n'
            '        self._n += 1\n')
    f1 = tmp_path / 'v1.py'
    f1.write_text(code)
    rc = cli_mod.main(['--format', 'json', str(f1)])
    out1 = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out1['findings'] and out1['findings'][0]['rule'] == \
        'guarded-by'
    fid = out1['findings'][0]['id']
    # Same violation shifted two lines down: the id is line-stable.
    f1.write_text('\n\n' + code)
    cli_mod.main(['--format', 'json', str(f1)])
    out2 = json.loads(capsys.readouterr().out)
    assert out2['findings'][0]['id'] == fid
    assert out2['findings'][0]['line'] == out1['findings'][0]['line'] + 2
    # Same-shaped finding in a DIFFERENT file gets a different id (the
    # path is hashed verbatim): fixing one file must never churn the
    # other file's id.
    f2 = tmp_path / 'v2.py'
    f2.write_text(code)
    cli_mod.main(['--format', 'json', str(f1), str(f2)])
    out3 = json.loads(capsys.readouterr().out)
    ids = [x['id'] for x in out3['findings']]
    assert len(ids) == 2 and len(set(ids)) == 2 and fid in ids


# -- clean-on-real-tree parity + runtime budgets ------------------------------


def test_concurrency_rules_clean_on_real_tree():
    files = skylint.load_files()
    findings = []
    for checker in (concurrency.LockOrder(),
                    concurrency.BlockingUnderLock(),
                    concurrency.EventLoopBlock(),
                    concurrency.ResourcePair()):
        findings += checker.check_tree(files, skylint.ROOT)
    assert findings == [], '\n'.join(str(f) for f in findings)


def test_graph_stats_surface_unresolved_category():
    g = callgraph.get_graph(skylint.load_files(), skylint.ROOT)
    stats = g.stats()
    # The soundness gap is explicit, never silently dropped: every
    # unplaceable call lands in a named category.
    assert stats['call_sites'] == stats['resolved'] + \
        sum(stats['unresolved'].values())
    assert stats['functions'] > 1000


@pytest.mark.slow
def test_full_cold_run_stays_in_lint_budget(tmp_path):
    """A full cold run (summary cache wiped) stays under the ~30 s
    `make lint` budget; a warm --changed run stays under 3 s."""
    import shutil
    import time as time_lib
    cache = REPO / callgraph.CACHE_DIR
    if cache.exists():
        shutil.rmtree(cache)
    t0 = time_lib.perf_counter()
    proc = subprocess.run(
        [sys.executable, str(REPO / 'tools' / 'lint.py')],
        capture_output=True, text=True, timeout=120)
    cold = time_lib.perf_counter() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert cold < 30.0, f'cold full suite took {cold:.1f}s'
    t0 = time_lib.perf_counter()
    proc = subprocess.run(
        [sys.executable, str(REPO / 'tools' / 'lint.py'), '--changed'],
        capture_output=True, text=True, timeout=60)
    warm = time_lib.perf_counter() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert warm < 3.0, f'warm --changed took {warm:.1f}s'


# -- verdict-name (tail-retention verdict registry) ---------------------------

from skylint.checkers import verdict_names as verdict_mod  # noqa: E402


def test_verdict_undeclared_literal_flagged_with_hint(tmp_path):
    sf = _sf(tmp_path, '''
        from skypilot_tpu.observability import trace
        trace.retain('abc123', 'resumedx')
        ''')
    findings = verdict_mod.VerdictNames().check_file(sf)
    assert _rules(findings) == ['verdict-name']
    assert 'resumedx' in findings[0].message
    assert "'resumed'" in findings[0].message  # did-you-mean


def test_verdict_declared_dynamic_and_suppressed_ok(tmp_path):
    sf = _sf(tmp_path, '''
        from skypilot_tpu.observability import trace as trace_lib
        trace_lib.retain('abc123', 'propagated')      # declared
        trace_lib.retain('abc123', verdict='slow')    # kwarg form
        v = compute()
        trace_lib.retain('abc123', v)                 # dynamic: clamped
        trace_lib.retain('abc123')                    # defaulted
        trace_lib.retain('abc123', 'wat')  # skylint: allow-verdict(fixture)
        ''')
    assert verdict_mod.VerdictNames().check_file(sf) == []


def test_verdict_unrelated_retain_methods_ignored(tmp_path):
    sf = _sf(tmp_path, '''
        class Cache:
            def retain(self, key, verdict):
                return key
        Cache().retain('k', 'not-a-verdict')
        ''')
    assert verdict_mod.VerdictNames().check_file(sf) == []


def test_verdict_undocumented_declaration_flagged(tmp_path):
    reg = tmp_path / 'skypilot_tpu' / 'observability' / 'trace.py'
    reg.parent.mkdir(parents=True)
    reg.write_text(textwrap.dedent('''
        def Verdict(name, doc):
            return (name, doc)
        VERDICTS = (Verdict('slow', 'kept when slow'),
                    Verdict('ghost_verdict', 'never documented'),)
        '''), encoding='utf-8')
    docs = tmp_path / 'docs' / 'operations.md'
    docs.parent.mkdir(parents=True)
    docs.write_text('| `slow` | kept because slow |\n', encoding='utf-8')
    findings = verdict_mod.VerdictNames().check_tree([], tmp_path)
    assert _rules(findings) == ['verdict-name']
    assert 'ghost_verdict' in findings[0].message
    # Duplicate declarations are findings too.
    reg.write_text(textwrap.dedent('''
        def Verdict(name, doc):
            return (name, doc)
        VERDICTS = (Verdict('slow', 'a'), Verdict('slow', 'b'),)
        '''), encoding='utf-8')
    findings = verdict_mod.VerdictNames().check_tree([], tmp_path)
    assert any('duplicate' in f.message for f in findings)


def test_verdict_cross_check_clean_on_real_tree():
    files = skylint.load_files()
    checker = verdict_mod.VerdictNames()
    findings = checker.check_tree(files, skylint.ROOT)
    findings += [f for sf in files for f in checker.check_file(sf)]
    assert findings == [], '\n'.join(str(f) for f in findings)


def test_metric_openmetrics_created_suffix_not_flagged(tmp_path):
    """Docs quoting an exemplar-bearing OpenMetrics scrape verbatim —
    bucket lines with `# {trace_id=...}` suffixes and the exposition's
    `_created` series — must not false-positive the metric-name scan."""
    doc = tmp_path / 'docs' / 'operations.md'
    doc.parent.mkdir(parents=True)
    doc.write_text(textwrap.dedent('''
        ```
        skytpu_serve_ttft_seconds_bucket{le="5.0"} 3 # {trace_id="4bf9"} 4.2 1726000000.0
        skytpu_serve_ttft_seconds_created 1726000000.0
        ```
        '''), encoding='utf-8')
    metrics_py = tmp_path / 'skypilot_tpu' / 'server' / 'metrics.py'
    metrics_py.parent.mkdir(parents=True)
    metrics_py.write_text(textwrap.dedent('''
        from prometheus_client import Histogram
        H = Histogram('skytpu_serve_ttft_seconds', 'ttft')
        '''), encoding='utf-8')
    findings = metric_names.MetricNames().check_tree([], tmp_path)
    assert findings == [], '\n'.join(str(f) for f in findings)
