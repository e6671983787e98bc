"""Assert the process table holds zero framework daemons.

`make audit-clean` — the leak gate (r3 verdict Next #1): a chip belongs
to one process, so one surviving agent/gangd/replica from a test run
that touched jax holds it against every later process. CI runs this
after the test tiers; builders should run it at session end.

Exit 0 = clean. Exit 1 = leaks found (each printed with pid, age,
ownership fingerprint, cmdline). Pass --reap to SIGTERM fingerprinted
(session-owned) leaks and re-check; unfingerprinted processes are never
killed automatically — they may be a real deployment (r3 advisor
medium). Use `stpu doctor --reap-all` for an explicit full sweep.
"""
import sys
import time

sys.path.insert(0, '.')

from skypilot_tpu.utils import tpu_doctor  # noqa: E402


def main() -> int:
    reap = '--reap' in sys.argv
    procs = tpu_doctor.framework_processes()
    if procs and reap:
        res = tpu_doctor.reap_stray_processes()
        if res['reaped']:
            print(f"audit-clean: reaped {len(res['reaped'])} "
                  'session-owned leak(s)', file=sys.stderr)
        time.sleep(1.0)
        procs = tpu_doctor.framework_processes()
    if not procs:
        print('audit-clean: OK — no framework processes alive')
        return 0
    print(f'audit-clean: FAIL — {len(procs)} framework process(es) '
          'alive:', file=sys.stderr)
    for p in procs:
        fp = p['fingerprint'] or 'UNFINGERPRINTED'
        print(f"  pid={p['pid']} age={p['age_s']}s [{fp}] "
              f"{p['cmdline'][:140]}", file=sys.stderr)
    print('Fix: `stpu doctor --reap` (session-owned) or '
          '`stpu doctor --reap-all` (everything).', file=sys.stderr)
    return 1


if __name__ == '__main__':
    sys.exit(main())
