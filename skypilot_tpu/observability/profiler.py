"""Runtime profiler: compile ledger, set-up ledger, device-memory
accounting, and a cold-start phase ledger.

The engine's whole TPU design rests on a compile-once-per-shape
contract ("everything compiles exactly once per shape",
``models/engine.py``) and ROADMAP open item 2 makes
provision→first-token a first-class budget — yet until this module the
tree had zero visibility into compiles, HBM occupancy, or warm-up
phases: a recompile storm, a leaked device buffer, or a minutes-long
jit warm-up was invisible until it surfaced as tail latency. Four
coupled ledgers close that gap:

* **Compile ledger** — every ``jax.jit`` program in the serving stack
  registers through :func:`profiled_jit` against the bounded
  :data:`PROGRAMS` registry (the ``EVENTS`` / ``RULES`` convention,
  cross-checked both ways by skylint's ``jit-program`` rule). Each
  entry declares its SHAPE BUDGET — the number of distinct compiled
  shapes the program is designed to cost (e.g. ~log2(max_len) prompt
  buckets for prefill, a couple of filter-pytree variants for the
  decode chunk). Compiles are detected by the set-up ledger's listener
  (below), attributed to the dispatching program through a
  thread-local (zero per-dispatch cost beyond a few attribute writes;
  the shape signature is computed only when a compile actually
  happened — compiles are rare by contract). A count past the budget
  is a **recompile storm**: storm counter + ``profiler.storm``
  black-box event + the ``serve.recompile_storm`` SLO warn rule
  (observability/slo.py).
* **Set-up ledger** — ONE ``jax.monitoring`` listener per process
  (:func:`listen`, registered first thing by
  ``utils/jax_env.enable_compile_cache``), always on: every program
  JAX traces, lowers and builds (compiles, or loads from the
  persistent cache), eager operations included, as wall-clock
  intervals per stage, a table by function name, and the persistent
  cache's hits and misses. :func:`setup_totals` reads it; the
  benchmark's ``setup.*`` metrics and ``/debug/profile``'s ``setup``
  block are its readers. The events fire only while JAX traces,
  lowers or compiles, never on a cached dispatch.
* **Device-memory accounting** — :func:`sample_device_memory` snapshots
  ``device.memory_stats()`` (bytes_in_use / peak / limit → headroom)
  and reconciles it against the engine's LOGICAL accounting
  (:func:`register_logical`: weights, KV pool, draft cache, prefix
  pool) into an ``unattributed_bytes`` residue — the leaked-buffer /
  fragmentation signal. Sampled on the ``server/daemons.py`` cadence
  on the API server and rate-limited per /health probe on replicas
  (``SKYTPU_PROFILE_MEM_S``); gated fleet-side by the
  ``serve.hbm_headroom`` SLO rule. CPU devices report no memory_stats
  and degrade to the logical view (the SLO signal then yields no
  observation — a CPU fleet never pages on HBM).
* **Cold-start phase ledger** — monotonic first-crossing marks from
  process start → imports → backend init (sub-phases: plugin
  discovery, device enumeration — the exact legs the r02
  ``tpu_unreachable`` hang sits in) → weights load → jit warm-up →
  ready → first token. Durations telescope, so the phases of one
  process SUM to its observed wall-clock (the ``perf_probe --profile``
  5% gate); ``replica_managers.py`` rolls the dark→READY transition up
  into ``skytpu_provision_to_first_token_s`` — the budget metric
  ROADMAP item 2's cache/AOT work gates on.

Surfaced everywhere the tree already looks: the ``/health`` ``profile``
block, token-gated ``/debug/profile`` on both servers,
``skytpu_compile_total{program}`` / ``skytpu_compile_seconds`` /
``skytpu_recompile_storm_total`` / ``skytpu_device_mem_bytes{kind}`` /
``skytpu_replica_warmup_seconds{phase}`` gauges (server/metrics.py), a
dashboard profile column, and the latest snapshot frozen into every
black-box incident bundle (observability/blackbox.py).

OFF by default behind ``SKYTPU_PROFILE`` (byte-parity pinned by
``tools/perf_probe.py --profile``); ``record()``-style hot-path
discipline — no I/O, no host sync, no allocation beyond the ledger
slot on the engine thread (skylint ``host-sync`` stays clean). Module
imports are stdlib-only by the observability package charter; jax is
imported lazily inside the functions that need it (their callers
already hold it).

See docs/operations.md §Profiling for ledger anatomy, storm semantics,
and the warm-up budget workflow.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import os
import threading
import time
from typing import Any, Dict, Iterable, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Program:
    """One declared jit program: the unit the compile ledger accounts.
    ``budget`` is the number of DISTINCT compiled shapes the program is
    designed to cost over a process lifetime; compiling past it is a
    recompile storm. Budgets are sized for the default serving config
    (e.g. log2(max_len / 16) + 1 prompt buckets x dtype/filter pytree
    variants) and overridable per process via SKYTPU_PROFILE_BUDGETS
    (the probe's storm-injection lever)."""
    name: str
    doc: str
    budget: int


#: Every profiled jit program in the tree, declared once. skylint's
#: ``jit-program`` rule fails on any ``profiled_jit('...')`` of an
#: undeclared name (did-you-mean on typos) AND on any declared name no
#: code wraps (dead-program detection) AND on any bare ``jax.jit``
#: call site outside this module (``# skylint: allow-jit(reason)`` is
#: the hatch for startup-time / training programs).
PROGRAMS: Tuple[Program, ...] = (
    # -- models/generate.py -------------------------------------------
    Program('generate.prefill',
            'Prompt prefill (forward_cached over a padded prompt '
            'block): one shape per power-of-two prompt bucket x '
            'admission-group batch x uniform/mixed-length variant.',
            budget=24),
    Program('generate.decode_scan',
            'Window-path decode lax.scan: one shape per (batch, '
            'max_new, filters-on/off) combination.', budget=16),
    # -- models/engine.py ---------------------------------------------
    Program('engine.sample',
            'Per-slot first-token sampling over prefill logits: one '
            'shape per admission-group size x filter variant.',
            budget=16),
    Program('engine.paged_chunk',
            'The K-step decode chunk over the paged pool — THE '
            'steady-state program: one shape per filters-None/array '
            'pytree variant.', budget=4),
    Program('engine.insert_cache',
            'Draft-cache-only insert (speculative mode).', budget=24),
    Program('engine.rewind',
            'Per-row lengths rollback after a speculative round.',
            budget=4),
    Program('engine.spec_round',
            'One draft-propose / target-verify round over all slots.',
            budget=4),
    # -- models/paged.py ----------------------------------------------
    Program('paged.insert',
            'Dense prefill rows → pool-block scatter: one shape per '
            'prompt bucket x admission-group size.', budget=24),
    Program('paged.fork_block',
            'Copy-on-write fork of one partially shared block.',
            budget=4),
    Program('paged.gather_blocks',
            'Shared-chain blocks → dense scratch row (chunked long '
            'prefill seed); compiles once (fixed MB*P width).',
            budget=4),
    Program('paged.export_blocks',
            'Pool-layout block gather for a KV-handoff export: one '
            'shape per power-of-two block count.', budget=12),
    Program('paged.import_blocks',
            'Handoff install: block scatter + table/length write in '
            'one dispatch; one shape per power-of-two block count.',
            budget=12),
    Program('paged.prefill_shared',
            'Suffix prefill directly over the pool (the block-share '
            'hit path): one shape per tail bucket.', budget=12),
    # -- models/mla_moe.py --------------------------------------------
    Program('mla_moe.prefill',
            'Latent-attention prefill into a dense one-plane cache: one '
            'shape per prompt bucket x admission-group size.', budget=24),
    Program('mla_moe.prefill_shared',
            'Suffix prefill directly over the latent pool (the '
            'block-share hit path): one shape per tail bucket.',
            budget=12),
    Program('mla_moe.insert',
            'paged.insert for a pool with a recurrent state beside it '
            '(KDA layers): the rows\' latent blocks and each row\'s final '
            'state into its slot; one shape per prompt bucket x '
            'admission-group size.', budget=24),
    Program('mla_moe.paged_chunk',
            'The K-step decode chunk over the latent pool (absorbed '
            'attention, drop-free experts); also returns the experts\' '
            'token counts.', budget=4),
    Program('mla_moe.paged_chunk_n',
            'mla_moe.paged_chunk that stops after n <= K steps (n a '
            'device scalar, so one shape): where a long prompt goes in '
            'pieces between the chunks, a chunk ends with its first '
            'row to finish.', budget=4),
    # -- models/speculative.py ----------------------------------------
    Program('spec.propose',
            'k+1 greedy draft proposal steps (solo speculative '
            'path).', budget=4),
    Program('spec.verify',
            'One k+1-token target verify forward (solo speculative '
            'path).', budget=4),
)

PROGRAM_NAMES = frozenset(p.name for p in PROGRAMS)
assert len(PROGRAM_NAMES) == len(PROGRAMS), 'duplicate program declaration'
_BY_NAME: Dict[str, Program] = {p.name: p for p in PROGRAMS}

#: Cold-start phases in their designed order. Each :func:`mark` records
#: the phase's first COMPLETION crossing; durations telescope between
#: consecutive crossings, so the ledger sums to the observed wall-clock
#: by construction. The two ``backend_init.*`` sub-phases are PJRT
#: client construction and device enumeration (utils/jax_env.py).
COLD_START_PHASES: Tuple[str, ...] = (
    'imports',
    'backend_init.plugin_discovery',
    'backend_init.device_enumeration',
    'weights_load',
    'jit_warmup',
    'ready',
    'first_token',
)

#: How many triggering-shape signatures the ledger keeps per program
#: (newest-first; bounded so a storm cannot grow the ledger).
_SHAPES_KEPT = 8


def enabled() -> bool:
    """Master switch, read live (the byte-parity probe and tests flip
    it mid-process). OFF by default — profiling is an opt-in
    measurement substrate, byte-parity-gated like SKYTPU_SLO."""
    return os.environ.get('SKYTPU_PROFILE', '0') not in ('0', '', 'off')


def mem_sample_interval_s() -> float:
    try:
        return max(float(os.environ.get('SKYTPU_PROFILE_MEM_S', '15')),
                   0.25)
    except ValueError:
        return 15.0


# (raw env string, parsed map): the budget check runs on the compile
# slow path only, but health snapshots read it per scrape — cache on
# the raw string like blackbox's ring-size cache.
_BUDGET_CACHE: Tuple[str, Dict[str, int]] = ('', {})


def _budget_overrides() -> Dict[str, int]:
    global _BUDGET_CACHE
    raw = os.environ.get('SKYTPU_PROFILE_BUDGETS', '')
    if raw != _BUDGET_CACHE[0]:
        out: Dict[str, int] = {}
        for part in raw.split(','):
            name, _, val = part.strip().partition('=')
            if not name or not val:
                continue
            try:
                out[name] = max(int(val), 1)
            except ValueError:
                continue
        _BUDGET_CACHE = (raw, out)
    return _BUDGET_CACHE[1]


def budget_for(name: str) -> int:
    return _budget_overrides().get(name, _BY_NAME[name].budget)


# -- ledger state ------------------------------------------------------------

_LOCK = threading.Lock()
# program name -> mutable ledger entry; entries exist only for WRAPPED
# programs, so the dict is bounded by the PROGRAMS registry.
_LEDGER: Dict[str, Dict[str, Any]] = {}
# logical device-memory accounting: kind -> bytes (weights, kv_cache,
# draft_cache, prefix_pool, ...), registered by the owning layer.
_LOGICAL: Dict[str, int] = {}
_LAST_MEM: Optional[Dict[str, Any]] = None
_LAST_MEM_MONO: float = 0.0

# Thread-local compile attribution: the profiled_jit wrapper names the
# dispatching program; the set-up listener accumulates each stage's
# milliseconds onto it. A few attribute reads and writes per dispatch
# are the whole hot-path cost.
_TLS = threading.local()


def _process_birth_mono() -> float:
    """This process's birth on the monotonic clock (via
    /proc/self/stat start ticks), so the cold-start ledger covers
    interpreter + import time the first profiler import cannot
    observe directly. Falls back to import time off-Linux."""
    try:
        with open('/proc/self/stat', encoding='utf-8') as f:
            ticks = int(f.read().rsplit(')', 1)[1].split()[19])
        hertz = os.sysconf('SC_CLK_TCK')
        with open('/proc/uptime', encoding='utf-8') as f:
            uptime = float(f.read().split()[0])
        return time.monotonic() - max(uptime - ticks / hertz, 0.0)
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic()


_BIRTH_MONO = _process_birth_mono()
_BIRTH_WALL = time.time() - (time.monotonic() - _BIRTH_MONO)
# phase -> monotonic first-crossing ts (insertion order is crossing
# order; cold_start_ledger() re-sorts by ts so a late out-of-order mark
# can never produce a negative duration).
_PHASE_TS: 'collections.OrderedDict[str, float]' = collections.OrderedDict()


def _entry(name: str) -> Dict[str, Any]:
    st = _LEDGER.get(name)
    if st is None:
        st = {'compiles': 0, 'compile_ms': 0.0, 'storms': 0,
              'last_compile_ts': None,
              'shapes': collections.deque(maxlen=_SHAPES_KEPT)}
        st.update(dict.fromkeys(_STAGE_MS, 0.0))
        _LEDGER[name] = st
    return st


# -- set-up ledger -----------------------------------------------------------

#: The stages of a program's set-up, each timed by one ``jax.monitoring``
#: time-span event (JAX 0.9.0, ``jax/_src/dispatch.py``). A ``build`` is
#: an XLA compile on a persistent-cache miss, or a retrieve-and-load on
#: a hit; the cache is keyed on the lowered module, so a warm cache
#: saves builds and never a trace or a lowering.
SETUP_STAGES: Tuple[Tuple[str, str], ...] = (
    ('trace', '/jax/core/compile/jaxpr_trace_duration'),
    ('lower', '/jax/core/compile/jaxpr_to_mlir_module_duration'),
    ('build', '/jax/core/compile/backend_compile_duration'),
)
_STAGE_OF = {event: i for i, (_, event) in enumerate(SETUP_STAGES)}
_STAGE_MS = tuple(f'{stage}_ms' for stage, _ in SETUP_STAGES)
_BUILD = 2      # SETUP_STAGES' index of 'build'
# a by-name row: each stage's seconds, then each stage's count
_ROW_KEYS = tuple(f'{stage}_s' for stage, _ in SETUP_STAGES) + (
    'traces', 'lowerings', 'builds')
_CACHE_EVENTS = {'/jax/compilation_cache/cache_hits': 'cache_hits',
                 '/jax/compilation_cache/cache_misses': 'cache_misses'}
#: Function names the by-name table keeps; the rest add up under
#: ``_OTHER_NAMES``.
_NAMES_KEPT = 256
_OTHER_NAMES = '(other)'
#: Intervals (or stamps) a stage (or counter) keeps for cuts at an
#: instant; past it the oldest fold into a sum that every cut counts.
_KEPT = 1 << 16


class _Union:
    """The union of one stage's wall-clock intervals: sorted, disjoint,
    merged as they come, so a jit traced inside another's trace, or two
    threads compiling at once, count once."""
    __slots__ = ('starts', 'ends', 'total', 'folded')

    def __init__(self) -> None:
        self.starts: list = []
        self.ends: list = []
        self.total = 0.0        # seconds of the intervals kept
        self.folded = 0.0       # seconds of those folded away

    def add(self, start: float, end: float) -> None:
        starts, ends = self.starts, self.ends
        lo = bisect.bisect_left(ends, start)
        hi = bisect.bisect_right(starts, end)
        if lo < hi:     # [lo, hi) overlap the new interval: merge them
            self.total -= sum(ends[k] - starts[k] for k in range(lo, hi))
            start, end = min(start, starts[lo]), max(end, ends[hi - 1])
            del starts[lo:hi], ends[lo:hi]
        starts.insert(lo, start)
        ends.insert(lo, end)
        self.total += end - start
        if len(starts) > _KEPT:
            oldest = ends.pop(0) - starts.pop(0)
            self.total -= oldest
            self.folded += oldest

    def seconds(self, until: Optional[float] = None) -> float:
        if until is None:
            return self.folded + self.total
        return self.folded + sum(min(e, until) - s for s, e in
                                 zip(self.starts, self.ends) if s < until)


class _Stamps:
    """When a counted event happened, for counts cut at an instant."""
    __slots__ = ('at', 'folded')

    def __init__(self) -> None:
        self.at: list = []
        self.folded = 0

    def add(self, t: float) -> None:
        bisect.insort(self.at, t)
        if len(self.at) > _KEPT:
            self.at.pop(0)
            self.folded += 1

    def count(self, until: Optional[float] = None) -> int:
        return self.folded + (len(self.at) if until is None else
                              bisect.bisect_right(self.at, until))


_SETUP_LOCK = threading.Lock()
_UNIONS = tuple(_Union() for _ in SETUP_STAGES)
# builds (ends of build intervals), cache hits, cache misses
_COUNTS = {'builds': _Stamps(), 'cache_hits': _Stamps(),
           'cache_misses': _Stamps()}
# function name -> its row, _ROW_KEYS (bounded)
_NAMES: Dict[str, list] = {}
# the listener's own cost: events taken, seconds spent taking them
_SELF = {'events': 0, 'listener_s': 0.0}
_LISTENING = False


def _base_name(fun_name: str) -> str:
    """``jit(f)`` (the lowering's and the build's name) -> ``f`` (the
    trace's), so one program's three stages share a row."""
    head, sep, rest = fun_name.partition('(')
    if sep and rest.endswith(')') and head.isidentifier():
        return rest[:-1]
    return fun_name


def _on_span(event: str, start_time: float, end_time: float,
             **kwargs: Any) -> None:
    """The time-span listener: one trace, lowering or build, on the
    wall clock, to the stage's union, the name's row and the program
    dispatching on this thread."""
    i = _STAGE_OF.get(event)
    if i is None:
        return
    t = time.perf_counter()
    name = _base_name(str(kwargs.get('fun_name', '?')))
    dur = end_time - start_time
    with _SETUP_LOCK:
        _UNIONS[i].add(start_time, end_time)
        if i == _BUILD:
            _COUNTS['builds'].add(end_time)
        row = _NAMES.get(name)
        if row is None:
            if len(_NAMES) >= _NAMES_KEPT:
                name = _OTHER_NAMES
            row = _NAMES.setdefault(name, [0.0, 0.0, 0.0, 0, 0, 0])
        row[i] += dur
        row[len(SETUP_STAGES) + i] += 1
        _SELF['events'] += 1
        _SELF['listener_s'] += time.perf_counter() - t
    if getattr(_TLS, 'program', None) is not None:
        acc = getattr(_TLS, 'stage_ms', None)
        if acc is None:
            acc = _TLS.stage_ms = [0.0] * len(SETUP_STAGES)
        acc[i] += dur * 1000.0


def _on_event(event: str, **_kw: Any) -> None:
    """The event listener: the persistent cache's hits and misses."""
    key = _CACHE_EVENTS.get(event)
    if key is None:
        return
    t = time.perf_counter()
    with _SETUP_LOCK:
        _COUNTS[key].add(time.time())
        _SELF['events'] += 1
        _SELF['listener_s'] += time.perf_counter() - t


def listen() -> None:
    """Register the set-up ledger's listener, once per process. Entry
    points reach it through ``jax_env.enable_compile_cache()`` before
    their first lowering; what JAX built before is not counted."""
    global _LISTENING
    if _LISTENING:
        return
    from jax import monitoring as jax_monitoring
    with _SETUP_LOCK:
        if _LISTENING:
            return
        jax_monitoring.register_event_time_span_listener(_on_span)
        jax_monitoring.register_event_listener(_on_event)
        _LISTENING = True


def setup_totals(until: Optional[float] = None,
                 top: Optional[int] = 10) -> Dict[str, Any]:
    """What this process traced, lowered and built since :func:`listen`:
    each stage's seconds (the union of its intervals), the builds, the
    persistent cache's hits and misses, cut at ``until`` (a
    ``time.time()`` instant) when given. ``by_name``: the ``top`` names
    (all with ``None``) by trace + lower + build seconds, each stage's
    seconds and count, since the start whatever ``until`` is.
    ``events`` and ``listener_s`` are the listener's own cost."""
    stages = len(SETUP_STAGES)
    with _SETUP_LOCK:
        out: Dict[str, Any] = {
            f'{stage}_s': u.seconds(until)
            for (stage, _), u in zip(SETUP_STAGES, _UNIONS)}
        out.update({k: s.count(until) for k, s in _COUNTS.items()})
        out.update(_SELF)
        rows = sorted(_NAMES.items(), key=lambda kv: -sum(kv[1][:stages]))
    out['by_name'] = {name: dict(zip(_ROW_KEYS, row))
                      for name, row in rows[:top]}
    return out


def _shape_sig(args: tuple, kwargs: dict) -> str:
    """Bounded abstract-shape signature of a dispatch's inputs —
    computed ONLY when the dispatch actually compiled (rare by
    contract), so walking the pytree here is off the steady-state
    path."""
    import jax
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    parts = []
    for leaf in leaves[:48]:
        shape = getattr(leaf, 'shape', None)
        if shape is not None:
            dtype = getattr(leaf, 'dtype', None)
            parts.append(f'{getattr(dtype, "name", dtype)}{list(shape)}')
        else:
            parts.append(type(leaf).__name__)
    if len(leaves) > 48:
        parts.append(f'+{len(leaves) - 48} leaves')
    return ','.join(parts)[:240]


def _note_compile(name: str, stage_ms: list, args: tuple,
                  kwargs: dict) -> None:
    """Record one compile on the ledger (slow path — a compile just
    happened, so the device is paying seconds; the host paying a
    signature walk and a locked update is free by comparison).
    ``compile_ms`` is the sum of the stages' milliseconds. Storm =
    distinct-compile count past the program's declared budget."""
    sig = _shape_sig(args, kwargs)
    budget = budget_for(name)
    storm = False
    ms = sum(stage_ms)
    with _LOCK:
        st = _entry(name)
        st['compiles'] += 1
        st['compile_ms'] += ms
        for key, val in zip(_STAGE_MS, stage_ms):
            st[key] += val
        st['last_compile_ts'] = round(time.time(), 3)
        st['shapes'].appendleft(sig)
        if st['compiles'] > budget:
            st['storms'] += 1
            storm = True
            compiles = st['compiles']
    if storm:
        # The flight recorder is the cheap always-on witness; the SLO
        # rule (serve.recompile_storm) pages the humans.
        try:
            from skypilot_tpu.observability import blackbox
            blackbox.record('profiler.storm', program=name,
                            compiles=compiles, budget=budget,
                            compile_ms=round(ms, 1))
        except Exception:  # noqa: BLE001 — observability must not
            pass           # fail the dispatch it observes


def profiled_jit(name: str, fn, **jit_kwargs):
    """``jax.jit`` with a compile ledger: the one sanctioned way to jit
    a program in this tree (skylint's ``jit-program`` rule). ``name``
    must be declared in :data:`PROGRAMS`. With SKYTPU_PROFILE off the
    wrapper is a passthrough to the jitted callable (one env read per
    dispatch — the same live-read cost blackbox.record already pays);
    with it on, the added steady-state cost is a few thread-local
    attribute reads and writes. Shape signatures and ledger updates
    happen only when a compile actually fired."""
    if name not in PROGRAM_NAMES:
        hint = _closest(name)
        raise ValueError(
            f'profiled_jit program {name!r} is not declared in '
            'observability/profiler.py PROGRAMS'
            + (f' — did you mean {hint!r}?' if hint else ''))
    import jax
    jitted = jax.jit(fn, **jit_kwargs)

    # skylint: hot-path
    def wrapper(*args, **kwargs):
        if not enabled():
            return jitted(*args, **kwargs)
        listen()
        prev = getattr(_TLS, 'program', None)
        prev_ms = getattr(_TLS, 'stage_ms', None)
        _TLS.program = name
        _TLS.stage_ms = None
        try:
            out = jitted(*args, **kwargs)
        finally:
            stage_ms = _TLS.stage_ms
            _TLS.program = prev
            _TLS.stage_ms = prev_ms
        if stage_ms is not None:
            _note_compile(name, stage_ms, args, kwargs)
        return out

    wrapper.program_name = name
    wrapper.jitted = jitted  # tests / AOT warm-up (serve/warmup.py)
    # Forward jit introspection so compile-count assertions and the
    # AOT warm-up driver keep working against the wrapped callable.
    for attr in ('_cache_size', 'lower', 'trace', 'clear_cache'):
        if hasattr(jitted, attr):
            setattr(wrapper, attr, getattr(jitted, attr))
    with _LOCK:
        _entry(name)  # the ledger lists every WRAPPED program
        _WRAPPERS[name] = wrapper
    return wrapper


def span(name: str):
    """A span on the device trace's clock: the ONE place the program
    opens ``jax.profiler.TraceAnnotation``. Always on, no switch: with
    no profile being taken it costs the tracer's flag test, and while
    one is (``jax.profiler.start_trace``, or the profiler server's
    capture) the span lands in the host plane beside the device's
    operations, so an idle gap on the chip can be laid to what the host
    was doing. Not the request waterfall of ``observability/trace.py``,
    which is on the wall clock and sampled per request."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def spanned(name: str):
    """Decorator: the whole call under :func:`span` (looked up at call
    time, so a test can swap ``span`` for a recorder)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


# program name -> last wrapper built for it (bounded by the registry).
# The warm-up driver's coverage fallback: with SKYTPU_PROFILE off the
# compile ledger stays empty, but a compile still grows the jitted
# callable's cache — so cache-size deltas stand in for ledger deltas.
_WRAPPERS: Dict[str, Any] = {}


def jit_cache_sizes() -> Dict[str, int]:
    """Per-program jit-cache entry counts across every wrapper built so
    far (programs whose jit lacks the cache-size API are omitted)."""
    with _LOCK:
        wrappers = dict(_WRAPPERS)
    out: Dict[str, int] = {}
    for name, w in wrappers.items():
        size = _safe_cache_size(w)
        if size is not None:
            out[name] = size
    return out


def _safe_cache_size(jitted) -> Optional[int]:
    try:
        return jitted._cache_size()  # noqa: SLF001 — no public API
    except Exception:  # noqa: BLE001 — no cache API: give up counting
        return None


def _closest(name: str) -> Optional[str]:
    """Cheap did-you-mean over the program registry (the env-flag
    checker's prefix/suffix-overlap recipe)."""
    best = None
    for cand in PROGRAM_NAMES:
        if abs(len(cand) - len(name)) > 2:
            continue
        pre = 0
        for x, y in zip(name, cand):
            if x != y:
                break
            pre += 1
        suf = 0
        for x, y in zip(reversed(name[pre:]), reversed(cand[pre:])):
            if x != y:
                break
            suf += 1
        if pre + suf >= max(len(name), len(cand)) - 2 and pre + suf > 4:
            best = cand
            break
    return best


# -- cold-start phase ledger -------------------------------------------------


def mark(phase: str) -> None:
    """Record ``phase``'s first completion crossing (idempotent; later
    marks of the same phase are ignored — the ledger is a cold-start
    record, not a recurring timer). Always recorded regardless of
    SKYTPU_PROFILE (a timestamp dict write is free; flipping the flag
    on mid-process must not lose the start), but SURFACED only with
    profiling on."""
    if phase not in COLD_START_PHASES:
        raise ValueError(f'unknown cold-start phase {phase!r}; declared: '
                         f'{", ".join(COLD_START_PHASES)}')
    with _LOCK:
        _PHASE_TS.setdefault(phase, time.monotonic())


def cold_start_ledger() -> Dict[str, Any]:
    """The phase ledger: per-phase durations in CROSSING order (each
    phase's duration runs from the previous crossing — or process
    birth — to its own), so durations are non-negative and telescope:
    they SUM to ``total_s`` exactly, and total_s tracks the observed
    process wall-clock (the perf_probe 5% gate). ``complete`` flips
    once the replica crossed 'ready'."""
    with _LOCK:
        items = sorted(_PHASE_TS.items(), key=lambda kv: kv[1])
    phases: Dict[str, float] = {}
    prev = _BIRTH_MONO
    for name, ts in items:
        phases[name] = round(max(ts - prev, 0.0), 4)
        prev = max(ts, prev)
    return {'started_at': round(_BIRTH_WALL, 3),
            'phases': phases,
            'total_s': round(prev - _BIRTH_MONO, 4),
            'complete': 'ready' in phases}


# -- device-memory accounting ------------------------------------------------


def tree_nbytes(tree) -> int:
    """Host-side byte count of a pytree's array leaves (attribute
    reads only — no device sync). The ONE definition the weight/KV
    registrations share, so a future sharded-array fix (global vs
    addressable nbytes) lands once."""
    import jax
    return sum(int(getattr(leaf, 'nbytes', 0) or 0)
               for leaf in jax.tree_util.tree_leaves(tree))


def register_logical(kind: str, nbytes: int) -> None:
    """Declare a logical device-memory consumer (weights, kv_cache,
    draft_cache, prefix_pool, ...). Re-registering a kind replaces its
    figure (an engine rebuild re-registers); the reconciliation residue
    ``unattributed_bytes`` = device bytes_in_use - sum(logical) is the
    leak/fragmentation signal."""
    with _LOCK:
        _LOGICAL[str(kind)] = int(nbytes)


def logical_bytes() -> Dict[str, int]:
    with _LOCK:
        return dict(_LOGICAL)


def sample_device_memory(devices: Optional[Iterable] = None
                         ) -> Optional[Dict[str, Any]]:
    """One device-memory snapshot, reconciled against the logical
    registrations. Returns None while profiling is off. ``devices``
    overrides ``jax.devices()`` for tests. Host-side allocator
    queries only — no device sync, legal anywhere off the engine
    thread."""
    global _LAST_MEM, _LAST_MEM_MONO
    if not enabled():
        return None
    if devices is None:
        try:
            import jax
            devices = jax.devices()
        except Exception:  # noqa: BLE001 — no backend: logical only
            devices = []
    devices = list(devices)
    in_use = peak = limit = 0
    reporting = 0
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:  # noqa: BLE001 — CPU/older runtimes
            ms = None
        if not ms:
            continue
        reporting += 1
        used = int(ms.get('bytes_in_use') or 0)
        in_use += used
        peak += int(ms.get('peak_bytes_in_use') or used)
        limit += int(ms.get('bytes_limit')
                     or ms.get('bytes_reservable_limit') or 0)
    with _LOCK:
        logical = dict(_LOGICAL)
    logical_total = sum(logical.values())
    out: Dict[str, Any] = {
        'ts': round(time.time(), 3),
        'devices': len(devices),
        'devices_reporting': reporting,
        'logical': logical,
        'logical_bytes': logical_total,
    }
    if reporting:
        headroom = max(limit - in_use, 0)
        out.update({
            'bytes_in_use': in_use,
            'peak_bytes': peak,
            'bytes_limit': limit,
            'headroom_bytes': headroom,
            'headroom_frac': (round(headroom / limit, 4) if limit
                              else None),
            # Allocator bytes the logical accounting cannot name:
            # leaks, allocator overhead, fragmentation. A creeping
            # fraction on a steady workload is the leak alarm.
            'unattributed_bytes': max(in_use - logical_total, 0),
            'unattributed_frac': (round(
                max(in_use - logical_total, 0) / in_use, 4)
                if in_use else 0.0),
        })
    with _LOCK:
        _LAST_MEM = out
        _LAST_MEM_MONO = time.monotonic()
    return out


def maybe_sample_device_memory() -> Optional[Dict[str, Any]]:
    """Rate-limited :func:`sample_device_memory` (SKYTPU_PROFILE_MEM_S)
    — the replica calls this from its /health handler so probing at
    the controller cadence yields a fresh-enough series without a
    dedicated thread."""
    if not enabled():
        return None
    with _LOCK:
        last, last_mono = _LAST_MEM, _LAST_MEM_MONO
    if last is not None and \
            time.monotonic() - last_mono < mem_sample_interval_s():
        return last
    return sample_device_memory()


# -- read side ---------------------------------------------------------------


def compile_totals() -> Tuple[int, float, int]:
    """(compiles, compile_ms, storms) across all programs."""
    with _LOCK:
        compiles = sum(st['compiles'] for st in _LEDGER.values())
        ms = sum(st['compile_ms'] for st in _LEDGER.values())
        storms = sum(st['storms'] for st in _LEDGER.values())
    return compiles, ms, storms


def snapshot() -> Dict[str, Any]:
    """The full profiler state: the /health ``profile`` block, the
    /debug/profile body, and what black-box bundles freeze. Bounded:
    programs are the registry, shapes per program cap at
    ``_SHAPES_KEPT``, memory is the last sample."""
    out: Dict[str, Any] = {'enabled': enabled()}
    if not out['enabled']:
        return out
    programs: Dict[str, Any] = {}
    with _LOCK:
        for name in sorted(_LEDGER):
            st = _LEDGER[name]
            programs[name] = {
                'compiles': st['compiles'],
                'compile_ms': round(st['compile_ms'], 3),
                **{k: round(st[k], 3) for k in _STAGE_MS},
                'budget': budget_for(name),
                'storms': st['storms'],
                'last_compile_ts': st['last_compile_ts'],
                'shapes': list(st['shapes']),
            }
        mem = _LAST_MEM
    compiles, ms, storms = compile_totals()
    out.update({
        'compile': programs,
        'compiles_total': compiles,
        'compile_ms_total': round(ms, 3),
        'storms_total': storms,
        'cold_start': cold_start_ledger(),
        'device_memory': mem,
        'setup': setup_totals(),
    })
    return out


def try_snapshot() -> Optional[Dict[str, Any]]:
    """Best-effort snapshot for the black-box dump path: never raises,
    None while disabled (a disabled profiler must not bloat bundles)."""
    try:
        if not enabled():
            return None
        return snapshot()
    except Exception:  # noqa: BLE001 — bundles must never fail to dump
        return None


_DEVICE_TRACE_MAX_S = 30.0


def device_trace(seconds: Any) -> Dict[str, Any]:
    """Take a ``jax.profiler`` trace of THIS process for ``seconds``
    (0.1 to 30) into a new directory, and say where it is: the device's
    operations with, in the host plane, every :func:`span` the program
    opened meanwhile. Only the process that holds the chip can trace
    it, and one trace at a time: a second call meanwhile is refused,
    as is a ``seconds`` that is no number. The Python tracer stays off
    (it slows the host it is measuring)."""
    import tempfile

    import jax
    try:
        seconds = min(max(float(seconds), 0.1), _DEVICE_TRACE_MAX_S)
    except ValueError:
        return {'error': 'device_trace takes a number of seconds'}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = tempfile.mkdtemp(prefix='skytpu-device-trace-')
    try:
        # skylint: allow-leak(jax's profiler, not observability/trace's
        # start_trace: the stop_trace in the finally below ends it)
        jax.profiler.start_trace(out, profiler_options=opts)
    except RuntimeError as e:  # a trace is already being taken
        os.rmdir(out)
        return {'error': str(e)}
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    return {'dir': out, 'seconds': seconds}


def debug_payload(query: Any) -> Dict[str, Any]:
    """The ``/debug/profile`` response body, shared by the API server
    and the serving replica (the debug_payload convention from
    blackbox/trace). ``?programs=1`` appends the PROGRAMS catalog;
    ``?mem=1`` forces a fresh device-memory sample first."""
    if str(query.get('mem', '')) in ('1', 'true'):
        sample_device_memory()
    out = snapshot()
    if str(query.get('programs', '')) in ('1', 'true'):
        out['programs'] = [dataclasses.asdict(p) for p in PROGRAMS]
    return out


def reset() -> None:
    """Drop ledger state (tests / probes). Wrapped-program entries are
    re-created empty so the ledger keeps listing every wrapped
    program; phase crossings and memory samples clear. The set-up
    ledger is not reset: it is this process's record since
    :func:`listen`."""
    with _LOCK:
        for st in _LEDGER.values():
            st['compiles'] = 0
            st['compile_ms'] = 0.0
            st.update(dict.fromkeys(_STAGE_MS, 0.0))
            st['storms'] = 0
            st['last_compile_ts'] = None
            st['shapes'].clear()
        _LOGICAL.clear()
        _PHASE_TS.clear()
        global _LAST_MEM, _LAST_MEM_MONO
        _LAST_MEM = None
        _LAST_MEM_MONO = 0.0
