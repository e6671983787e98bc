"""AOT warm-up before traffic (cold-start collapse, ROADMAP item 2).

A replica that flips READY with an empty jit cache pays its compiles on
the FIRST user requests — exactly the latency the dark-launch window
exists to hide. This driver runs inside that window (``llm_server``
calls it after weights load and BEFORE the HTTP listener binds, so the
controller's probes cannot see a 200 until warm-up finished): it drives
the steady-state shape set through every jit program the configuration
actually uses, then REPLAYS the same mix until a full round compiles
nothing new. That replay is the coverage confirmation the READY gate
demands — zero post-READY compiles stops being a hope and becomes the
thing warm-up measured.

Shape buckets are the engine's power-of-two prompt buckets
(``engine.prompt_bucket``) up to ``max_len``; the bucket COUNT is
bounded by the wrapped programs' declared compile budgets
(``observability/profiler.py``), so warm-up itself can never trip the
recompile-storm detector it feeds. Coverage is read off the compile
ledger when SKYTPU_PROFILE is on, and off the wrappers' jit-cache
sizes otherwise (``profiler.jit_cache_sizes``) — a compile grows the
cache whether or not the ledger recorded it.

Budget discipline: with the persistent compilation cache populated
(``utils/jax_env.enable_compile_cache``) the same warm-up mix
deserializes its programs instead of compiling them, which is why the
``perf_probe --coldstart`` gate can demand the second boot be strictly
faster on the compile-phase ledger.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

from skypilot_tpu.observability import profiler

_PROMPT_LO = 16  # engine.prompt_bucket's floor
_WARMUP_MAX_NEW = 4  # enough decode to run (and compile) a chunk


def skipped(reason: str) -> Dict[str, Any]:
    """The report for a boot that did NOT warm up — the
    ``warmup_skipped`` note /health surfaces so the phase ledger's
    missing ``jit_warmup`` crossing is explainable, not mysterious."""
    return {'ran': False, 'covered': False, 'warmup_skipped': reason}


def enabled() -> bool:
    return os.environ.get('SKYTPU_WARMUP', '0') == '1'


def _int_env(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)) or str(default))
    except ValueError:
        return default


def prompt_buckets(max_len: int) -> List[int]:
    """The steady-state prompt shape set: every power-of-two bucket
    that still fits a decode tail inside ``max_len``, smallest first,
    capped by SKYTPU_WARMUP_BUCKETS and — so warming cannot itself
    storm — by the smallest declared compile budget among the wrapped
    programs."""
    buckets = []
    b = _PROMPT_LO
    while b + _WARMUP_MAX_NEW <= max_len:
        buckets.append(b)
        b *= 2
    sizes = profiler.jit_cache_sizes()
    if sizes:
        budget_cap = min(profiler.budget_for(n) for n in sizes)
        buckets = buckets[:max(budget_cap, 1)]
    cap = _int_env('SKYTPU_WARMUP_BUCKETS', 0)
    if cap > 0:
        buckets = buckets[:cap]
    return buckets or [_PROMPT_LO]


def _compile_marker() -> tuple:
    """Monotone compile witness: (ledger compiles, total jit-cache
    entries). Unchanged across a replay round == that round compiled
    nothing — the coverage confirmation."""
    compiles, _ms, _storms = profiler.compile_totals()
    return compiles, sum(profiler.jit_cache_sizes().values())


def _cache_canary() -> Optional[Dict[str, int]]:
    """Round-trip the persistent compilation cache with one throwaway
    program: a mispointed or read-only SKYTPU_COMPILE_CACHE surfaces
    HERE, inside the dark window, instead of as a silently-cold next
    boot. Returns {'entries_before', 'entries_after'} (None with the
    cache off); after a successful round trip the canary's entry
    exists whether this boot wrote it or a predecessor did."""
    from skypilot_tpu.utils import jax_env
    state = jax_env.compile_cache_state()
    if not state.get('enabled'):
        return None
    import jax
    import jax.numpy as jnp

    def _canary(x):
        return x * 2.0 + 1.0

    def _entries() -> int:
        try:
            return sum(1 for n in os.listdir(state['dir'])
                       if not n.endswith('-atime'))
        except OSError:
            return 0

    before = _entries()
    # skylint: allow-jit(AOT warm-up driver cache canary — a throwaway
    # non-serving program that probes the persistent compile cache
    # round trip; never dispatched after READY, nothing to ledger)
    jax.jit(_canary)(jnp.float32(1.0)).block_until_ready()
    return {'entries_before': before, 'entries_after': _entries()}


def _row(bucket: int, rnd: int, idx: int) -> List[int]:
    """A prompt that pads to exactly ``bucket`` and shares NO prefix
    with any other bucket's or round's row (first token differs).
    Prefix distinctness matters: rows sharing a head would hit the
    block-share trie and prefill only the REMAINDER — a smaller
    bucket's shape — leaving the full-size prefill uncompiled while
    the coverage replay (same rows, now fully prefix-cached) happily
    compiles nothing and reports covered."""
    return [((7 * i + 13 * rnd + 29 * (idx + 1)) % 240) + 1
            for i in range(bucket)]


def _drive_engine(server, buckets: List[int], rnd: int) -> None:
    """One round of the steady-state mix through the continuous
    engine, three arrival patterns per prompt bucket because each
    compiles a DIFFERENT program set (rows are fresh every round, see
    :func:`_row` — replaying prompts the prefix trie already holds
    would validate only the cached path):

    * **solo** (submit, wait) — a group-of-one prefill at the bucket's
      padded shape plus its KV insert: the shape sequential
      steady-state arrivals hit;
    * **concurrent duplicate pair** — the grouped-prefill shape AND
      the second-sighting full-match path (the block-share trie
      serving a repeated prompt);
    * **prefix truncation** (a shorter prefix of the solo row) — a
      PARTIAL trie hit: block fork + remainder prefill, the path a
      shared-prompt-plus-divergence workload compiles."""
    for idx, bucket in enumerate(buckets):
        solo = _row(bucket, rnd, idx)
        server.engine.submit(
            solo, _WARMUP_MAX_NEW, 0.0).result(timeout=600)
        pair_row = _row(bucket, rnd, idx + len(buckets))
        pair = [server.engine.submit(pair_row, _WARMUP_MAX_NEW, 0.0)
                for _ in range(2)]
        for f in pair:
            f.result(timeout=600)
        if bucket > 4:
            server.engine.submit(solo[:bucket - 3], _WARMUP_MAX_NEW,
                                 0.0).result(timeout=600)


def _drive_window(server, buckets: List[int], rnd: int) -> None:
    """Window-batched path (engine off): greedy ``generate`` at each
    bucketed prompt length — the same shapes ``_run_group`` pads
    steady-state requests to when they arrive bucket-aligned."""
    import jax
    from skypilot_tpu.models import generate as gen_lib
    for idx, bucket in enumerate(buckets):
        padded, lens = gen_lib.pad_prompts([_row(bucket, rnd, idx)])
        out = gen_lib.generate(
            server.params, server.cfg, padded, _WARMUP_MAX_NEW,
            temperature=0.0, max_len=server.max_len,
            prompt_lengths=lens,
            kv_quantize=server.kv_cache == 'int8')
        jax.device_get(out)


def run(server) -> Dict[str, Any]:
    """Warm the replica and confirm coverage. Returns the report
    /health surfaces under ``profile.warmup``; never raises — a
    warm-up failure degrades to a slower (but correct) first request,
    and the report says so."""
    t0 = time.monotonic()
    buckets = prompt_buckets(server.max_len)
    rounds_max = max(_int_env('SKYTPU_WARMUP_ROUNDS', 4), 1)
    start = _compile_marker()
    report: Dict[str, Any] = {'ran': True, 'buckets': buckets,
                              'rounds': 0, 'covered': False}
    error: Optional[str] = None
    try:
        canary = _cache_canary()
        if canary is not None:
            report['cache_canary'] = canary
        for rnd in range(rounds_max):
            before = _compile_marker()
            if server.engine is not None:
                _drive_engine(server, buckets, rnd)
            else:
                _drive_window(server, buckets, rnd)
            report['rounds'] += 1
            if report['rounds'] > 1 and _compile_marker() == before:
                # A full steady-state replay compiled nothing: the
                # shape set is covered, post-READY compiles are zero
                # by construction for this mix.
                report['covered'] = True
                break
    except Exception as e:  # noqa: BLE001 — warm-up must never kill
        error = f'{type(e).__name__}: {e}'  # the boot it accelerates
    end = _compile_marker()
    report['compiles'] = max(end[0] - start[0], 0)
    report['cache_entries'] = max(end[1] - start[1], 0)
    report['wall_s'] = round(time.monotonic() - t0, 3)
    if error:
        report['error'] = error[:200]
    # The phase-ledger crossing happens ONLY here — on an actual
    # warm-up — so a skipped/failed-to-start warm-up never widens
    # ``jit_warmup`` with time that belongs to ``ready``.
    profiler.mark('jit_warmup')
    return report
