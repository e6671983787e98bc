"""Where the JAX programs run: device identity, the refusal to pretend,
and the one compile-cache rule.

Every entry point that owns a JAX program (``train/run.py``,
``serve/llm_server.py``, ``serve/spmd.py``, ``bench.py``, the
``chip_smoke.py`` children) calls :func:`init_backend` once, before its
first lowering. JAX reads ``JAX_PLATFORMS`` itself; nothing here
overrides a platform. A chip belongs to one process at a time, so only
the process that will run the program calls this — launchers, agents
and supervisors never import jax.

``python -m skypilot_tpu.utils.jax_env`` prints the device line and
exits: the backend probe ``stpu doctor`` and ``chip_smoke.py`` run as
an ordinary child.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Optional

DEVICE_LINE_PREFIX = '[device] '
CACHE_LINE_PREFIX = '[compile-cache] '

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# the cache key's reach — a cache that moves (tempfile, pid, clock)
# never hits.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, '.jax_cache')

_cache_state: Optional[Dict[str, Any]] = None


def enable_compile_cache() -> Dict[str, Any]:
    """Place JAX's persistent compilation cache, once per process,
    before the first lowering. The rule, in order:

    1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it — touch
       nothing in ``jax.config`` and report that directory.
    2. ``SKYTPU_COMPILE_CACHE`` set (the fleet's per-model-version
       leaf, provisioned by ``provision/instance_setup.py``): use it.
    3. Otherwise ``<checkout>/.jax_cache`` — unless the caller asked
       for the CPU (tests, rehearsals): nobody pays for CPU compile
       time, and XLA:CPU's loader logs kilobytes of machine-feature
       warnings for every program it reads back.

    Returns the block ``/health`` carries as ``compile_cache``:
    ``{'enabled', 'dir', 'source', 'entries_at_start', 'warm'}`` —
    ``warm`` (the directory already held entries) is how boots are
    classified for the autoscaler's lead-time model. Cache trouble
    never fails a boot: running without the cache is only slower."""
    global _cache_state
    if _cache_state is not None:
        return _cache_state
    env_dir = (os.environ.get('JAX_COMPILATION_CACHE_DIR') or '').strip()
    fleet_dir = (os.environ.get('SKYTPU_COMPILE_CACHE') or '').strip()
    try:
        if env_dir:
            cache_dir, source = env_dir, 'JAX_COMPILATION_CACHE_DIR'
        elif not fleet_dir and _asked_for_cpu():
            _cache_state = {'enabled': False}
            return _cache_state
        else:
            import jax
            if fleet_dir:
                cache_dir = os.path.abspath(os.path.expanduser(fleet_dir))
                source = 'SKYTPU_COMPILE_CACHE'
            else:
                cache_dir, source = DEFAULT_CACHE_DIR, 'checkout'
            os.makedirs(cache_dir, exist_ok=True)
            jax.config.update('jax_compilation_cache_dir', cache_dir)
            # JAX's default floor (1 s) would skip most of a small
            # model's programs; 0 persists everything.
            jax.config.update(
                'jax_persistent_cache_min_compile_time_secs',
                float(os.environ.get('SKYTPU_COMPILE_CACHE_MIN_S') or 0))
            jax.config.update('jax_persistent_cache_min_entry_size_bytes',
                              -1)
        entries = _cache_entries(cache_dir)
        _cache_state = {'enabled': True, 'dir': cache_dir,
                        'source': source, 'entries_at_start': entries,
                        'warm': entries > 0}
    except (OSError, ValueError) as e:
        _cache_state = {'enabled': False, 'error': str(e)[:200]}
    return _cache_state


def compile_cache_state() -> Dict[str, Any]:
    """What :func:`enable_compile_cache` decided in this process, or
    ``{'enabled': False}`` when no entry point has called it (embedded
    engines, tests) — reading ``/health`` never configures JAX."""
    return _cache_state or {'enabled': False}


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if not n.endswith('-atime'))
    except OSError:
        return 0


def _asked_for_cpu() -> bool:
    return (os.environ.get('JAX_PLATFORMS') or '').strip().lower() == 'cpu'


def require_accelerator() -> None:
    """Raise when the backend came up as the CPU and the caller did not
    ask for it with ``JAX_PLATFORMS=cpu``. JAX falls back to the CPU
    with one warning when libtpu cannot initialise; a trainer or a
    replica that carried on would report CPU work under a TPU's name."""
    import jax
    if jax.default_backend() == 'cpu' and not _asked_for_cpu():
        raise RuntimeError(
            'JAX found no accelerator and fell back to the CPU '
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', 'unset')}). "
            'Set JAX_PLATFORMS=cpu to run on the CPU on purpose.')


def describe_devices() -> Dict[str, Any]:
    """Platform, device kind and count as JAX reports them, plus each
    local device's ``bytes_in_use`` where the backend keeps allocator
    statistics (the CPU backend does not)."""
    import jax
    devices = jax.devices()
    out: Dict[str, Any] = {'platform': devices[0].platform,
                           'device_kind': devices[0].device_kind,
                           'device_count': len(devices)}
    in_use = [(d.memory_stats() or {}).get('bytes_in_use')
              for d in jax.local_devices()]
    if any(b is not None for b in in_use):
        out['bytes_in_use'] = in_use
    return out


def print_device_line() -> Dict[str, Any]:
    """One parseable line saying where this process runs; entry points
    print it at start (and the trainer again at exit, when
    ``bytes_in_use`` shows where the state landed)."""
    info = describe_devices()
    print(DEVICE_LINE_PREFIX + json.dumps(info), flush=True)
    return info


def parse_device_lines(text: str) -> list:
    """Every device line in a captured log, oldest first."""
    return [json.loads(line[len(DEVICE_LINE_PREFIX):])
            for line in text.splitlines()
            if line.startswith(DEVICE_LINE_PREFIX)]


def init_backend() -> Dict[str, Any]:
    """The entry points' backend bring-up: compile cache placed, backend
    constructed (the cold-start ledger's two ``backend_init`` crossings),
    an un-asked-for CPU refused, the cache and device lines printed."""
    from skypilot_tpu.observability import profiler
    print(CACHE_LINE_PREFIX + json.dumps(enable_compile_cache()),
          flush=True)
    import jax
    from jax.extend import backend as jax_backend
    jax_backend.get_backend()
    profiler.mark('backend_init.plugin_discovery')
    jax.devices()
    profiler.mark('backend_init.device_enumeration')
    require_accelerator()
    return print_device_line()


if __name__ == '__main__':
    try:
        init_backend()
    except RuntimeError as e:
        print(f'jax_env: {e}', file=sys.stderr)
        sys.exit(1)
