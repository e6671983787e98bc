"""Flagship benchmark: Llama train-step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline derivation (BASELINE.md / reference
``examples/tpu/v6e/README.md:33-44``): the reference's flagship recipe
(HF Llama-3-8B, PyTorch/XLA, FSDP, adafactor, seq 8192) reached
0.476 samples/s on v6e-8 = 487.4 tokens/s/chip; in HF's own 6*N*T
``total_flos`` convention that is 6 * 8.03e9 * 487.4 = **23.48 model
TFLOP/s per chip** (≈2.6% of v6e peak — the recipe is badly tuned, which
is exactly the headroom a TPU-native stack should reclaim).

We measure the same quantity — achieved model FLOP/s per chip, 6*N*T over
wall-clock — for our pjit train step (bf16, pallas flash attention fwd+bwd,
adafactor, full remat) at seq 4096 on whatever chip is attached (here: one
v5e, peak 197 TFLOP/s bf16, so vs_baseline > 1 means beating the
reference's per-chip utilization despite a 4.7x slower chip than its v6e).

``detail`` also reports:
  * seq-2048 throughput (round-1 comparable number), and
  * provision -> first-step seconds: a real ``execution.launch`` of a task
    on the in-sandbox local provider, timed from the launch call to the
    job's run phase emitting its first line (the reference names this the
    north-star latency; its hook is ``sky/utils/timeline.py``).
"""
from __future__ import annotations

import json
import os
import sys
import time


def _measure_step_throughput(cfg, warmup: int, iters: int):
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.train import Trainer
    from skypilot_tpu.train import data as data_lib
    from skypilot_tpu.train import trainer as trainer_mod

    trainer = Trainer(cfg)
    state = trainer.init_state(seed=0)
    step = trainer.compiled_step()
    batches = [jnp.asarray(b) for b in data_lib.synthetic_batches(
        cfg.global_batch_size, cfg.seq_len, cfg.model.vocab_size, seed=0,
        num_batches=warmup + iters)]

    # Sync via host transfer of the metrics: device_get forces the whole
    # state-dependency chain to finish.
    for b in batches[:warmup]:
        state, metrics = step(state, b)
    float(jax.device_get(metrics['loss']))

    t0 = time.perf_counter()
    for b in batches[warmup:]:
        state, metrics = step(state, b)
    final_loss = float(jax.device_get(metrics['loss']))
    dt = time.perf_counter() - t0

    steps_per_s = iters / dt
    n_chips = jax.device_count()
    tflops_per_chip = (trainer_mod.model_flops_per_step(cfg) * steps_per_s
                       / n_chips / 1e12)
    tokens_per_s_chip = (trainer_mod.tokens_per_step(cfg) * steps_per_s
                         / n_chips)
    return tflops_per_chip, tokens_per_s_chip, steps_per_s, final_loss


def _measure_decode_throughput(cfg):
    """Serving-side decode tokens/s (KV-cache generate path; the JetStream
    analog metric — reference baseline: 2500 tok/s input throughput on
    v6e, ``examples/tpu/v6e/README.md:118``).

    Decode is HBM-bound, so throughput scales with batch until the KV
    cache fills HBM (measured on v5e: 1.8k tok/s @ b8 -> 4.0k @ b32);
    sweep upward at capture time and report the best batch that fits."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import generate as gen_lib
    from skypilot_tpu.models import llama

    from skypilot_tpu.models import quantization as quant_lib

    prompt_len, new_tokens = 128, 128
    params = llama.init_params(jax.random.PRNGKey(0), cfg.model)
    per_variant: dict = {}

    def sweep(label, p, kv=False, batches=(32, 64, 128)):
        best = 0.0
        for batch in batches:
            try:
                prompt = jnp.ones((batch, prompt_len), jnp.int32)
                out = gen_lib.generate(p, cfg.model, prompt,
                                       new_tokens,
                                       kv_quantize=kv)  # compile
                jax.device_get(out[0, 0])
                t0 = _time.perf_counter()
                out = gen_lib.generate(p, cfg.model, prompt, new_tokens,
                                       kv_quantize=kv)
                jax.device_get(out[0, 0])
                dt = _time.perf_counter() - t0
                tps = batch * new_tokens / dt
            except Exception as exc:  # noqa: BLE001 — KV-cache OOM: keep best
                if best == 0.0 and not per_variant:
                    raise  # nothing measured: surface the REAL error type
                print(f'[bench] decode {label} b{batch} failed '
                      f'({type(exc).__name__}); keeping earlier results',
                      file=sys.stderr)
                break
            print(f'[bench] decode {label} b{batch}: {tps:.0f} tok/s',
                  file=sys.stderr)
            best = max(best, tps)
        per_variant[label] = round(best, 1)
        return best

    # bf16 first, then REPLACE the weight tree with the int8 one before
    # its sweep — holding both resident would shrink KV-cache headroom
    # and under-report the batches a real deployment (one tree) fits.
    # Peaks measured on v5e: bf16/int8 top out at b64 (b128 dips); the
    # int8 KV cache halves per-slot bytes so its peak moves to b192.
    best = sweep('bf16', params, batches=(32, 64))
    q = quant_lib.quantize_params(params)
    del params
    best = max(best, sweep('int8', q, batches=(32, 64)))
    # int8 weights + int8 KV: decode streams weights AND cache from HBM;
    # quantizing both is the lean serving configuration (measured 9.5k
    # tok/s vs 5.8k int8-weights-only on one v5e chip).
    best = max(best, sweep('int8+kv8', q, kv=True,
                           batches=(64, 128, 192)))
    # Continuous-engine A/B: pipelined dispatch (one chunk in flight,
    # host bookkeeping overlapped) vs the serial engine on the same
    # weights and load. Reported alongside the generate()-path variants
    # but kept OUT of `best` — the engine number includes admission/
    # prefill, a different quantity than the pure decode sweeps above.
    try:
        per_variant.update(_measure_engine_decode(cfg.model, q))
    except Exception as exc:  # noqa: BLE001 — A/B must not kill capture
        print(f'[bench] engine decode A/B failed '
              f'({type(exc).__name__}: {str(exc)[:160]})',
              file=sys.stderr)
    return best, per_variant


def engine_ab_rates(engines: dict, rows_lens: list, rounds: int,
                    timeout: float) -> dict:
    """The ONE engine A/B measurement protocol, shared with
    ``tools/perf_probe.py --smoke``: one full concurrent warmup round
    per engine (sequential submits would leave the grouped-prefill
    shapes uncompiled and bill them to a measured round), then
    back-to-back rounds with order alternating — each pair shares one
    machine state, so per-round comparisons are drift-immune where raw
    tok/s is not. Returns {label: [tok/s per round]}."""
    import time as _time

    rates: dict = {label: [] for label in engines}
    for eng in engines.values():
        for f in [eng.submit(r, n) for r, n in rows_lens]:
            f.result(timeout=timeout)
    for i in range(rounds):
        order = list(engines.items())
        if i % 2:
            order.reverse()
        for label, eng in order:
            t0 = _time.perf_counter()
            futs = [eng.submit(r, n) for r, n in rows_lens]
            toks = sum(len(f.result(timeout=timeout)) for f in futs)
            rates[label].append(toks / (_time.perf_counter() - t0))
    return rates


def _measure_engine_decode(model_cfg, params) -> dict:
    """Continuous-engine decode tokens/s, ``pipelined`` vs ``serial``
    dispatch (models/engine.py): the pipelined engine dispatches chunk
    N+1 before fetching chunk N, hiding per-chunk host bookkeeping
    (device_get, EOS truncation, admission) behind device compute —
    the per-chunk bubble that caps the serial engine on a
    remote-attached chip. int8 KV (the lean serving config); per-variant
    MEDIAN over paired rounds so one scheduler hiccup or thermal phase
    decides neither side."""
    import statistics

    from skypilot_tpu.models.engine import ContinuousEngine

    prompt_len, new_tokens, n_req = 128, 128, 64
    rows = [[(37 * i + j) % 1000 + 1 for j in range(prompt_len)]
            for i in range(n_req)]
    engines = {
        label: ContinuousEngine(params, model_cfg, slots=32, max_len=512,
                                kv_quantize=True, pipeline=pipe)
        for label, pipe in (('serial', False), ('pipelined', True))}
    try:
        rates = engine_ab_rates(engines, [(r, new_tokens) for r in rows],
                                rounds=3, timeout=600)
    finally:
        for eng in engines.values():
            eng.stop()
    out = {}
    for label, rs in rates.items():
        out[label] = round(statistics.median(rs), 1)
        print(f"[bench] engine decode {label}: {out[label]} tok/s "
              f"(rounds: {[round(r, 1) for r in rs]})", file=sys.stderr)
    return out


def prefix_share_probe(assert_gates: bool = False) -> dict:
    """Copy-on-write block-prefix-sharing gate (models/paged.py
    BlockTrie + the paged engine's pool-direct tail prefill) — shared
    by ``bench.py`` (the ``prefix_share`` detail entry) and
    ``tools/perf_probe.py --prefix`` (the CI gate, assert_gates=True).

    Three legs, all CPU, tiny model:
    (a) an 80%-shared mix (16/20 requests open with one 24-token head
        — one full block plus a partial, so copy-on-write forks fire)
        run share ON vs OFF on identical engines: greedy outputs must
        be byte-identical, hit rate > 0, and the ON engine must
        prefill-compute >= 40% fewer prompt tokens;
    (b) a 0%-shared mix (fresh unique prompts EVERY round, so the ON
        engine's commits never pay back): decode tok/s ON vs OFF as a
        median of back-to-back paired rounds — the trie's bookkeeping
        must not tax unshared traffic (>= 0.9x, 3 attempts, same drift
        discipline as the decode-overlap smoke);
    (c) an HTTP replica driven by ``loadgen --shared-prefix 0.8``
        (2 tenants x shared head + unique tails, streamed): the
        per-mix TTFT report fills and the engine's /health hit rate is
        nonzero — the CLI-reproducible form of the win.
    After every drain the free/owned/shared/cached block states must
    reconcile exactly (no leaked blocks)."""
    import asyncio
    import statistics
    import threading

    import jax
    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.models import llama
    from skypilot_tpu.models.engine import ContinuousEngine
    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.serve import loadgen
    from skypilot_tpu.utils import common_utils

    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    head = [((11 * j) % 250) + 1 for j in range(24)]
    rows80 = []
    for i in range(20):
        if i % 5 != 4:  # 16/20 = 80% shared
            rows80.append(head + [((7 * i + j) % 250) + 1
                                  for j in range(8)])
        else:
            rows80.append([((13 * i + j) % 250) + 1 for j in range(32)])

    def _drained(kb):
        return (kb['owned'] == 0 and kb['shared'] == 0
                and kb['free'] + kb['cached'] == kb['usable'])

    def _engine(share):
        return ContinuousEngine(params, cfg, slots=4, max_len=64,
                                chunk_steps=2, prefix_share=share)

    # (a) parity + savings on the 80% mix. The first request runs alone
    # so its blocks are committed before the sharers arrive (concurrent
    # first sightings all miss, like any cache).
    outs, stats = {}, {}
    for label, share in (('on', True), ('off', False)):
        eng = _engine(share)
        try:
            out = [eng.submit(rows80[0], 6).result(timeout=600)]
            futs = [eng.submit(r, 6) for r in rows80[1:]]
            out += [f.result(timeout=600) for f in futs]
            outs[label] = out
            stats[label] = eng.stats()
        finally:
            eng.stop()
    on, off = stats['on'], stats['off']
    saved_frac = 1.0 - (on['prefill_tokens']
                        / max(off['prefill_tokens'], 1))
    summary = {
        'parity_ok': outs['on'] == outs['off'],
        'hits': on['prefix_share']['hits'],
        'hit_rate': on['prefix_share']['hit_rate'],
        'cow_forks': on['prefix_share']['cow_forks'],
        'prefill_tokens_on': on['prefill_tokens'],
        'prefill_tokens_off': off['prefill_tokens'],
        'prefill_saved_frac': round(saved_frac, 4),
        'drain_reconciled': (_drained(on['kv_blocks'])
                            and _drained(off['kv_blocks'])),
        'blocks_after_drain': {
            k: on['kv_blocks'][k]
            for k in ('free', 'owned', 'shared', 'cached', 'usable')},
    }

    # (b) decode parity on a genuinely 0%-shared mix: fresh prompts
    # every round (same shapes — one compile), paired back-to-back.
    attempts = []
    for attempt in range(3):
        engines = {lbl: _engine(lbl == 'on') for lbl in ('on', 'off')}
        try:
            warm = [[((41 * attempt + 5 * i + j) % 250) + 1
                     for j in range(24)] for i in range(12)]
            for eng in engines.values():
                for f in [eng.submit(r, 8) for r in warm]:
                    f.result(timeout=600)
            rates = {lbl: [] for lbl in engines}
            for rnd in range(3):
                order = list(engines.items())
                if rnd % 2:
                    order.reverse()
                rows0 = [[((59 * attempt + 13 * rnd + 7 * i + j) % 250)
                          + 1 for j in range(24)] for i in range(12)]
                for lbl, eng in order:
                    t0 = time.perf_counter()
                    futs = [eng.submit(r, 8) for r in rows0]
                    toks = sum(len(f.result(timeout=600)) for f in futs)
                    rates[lbl].append(toks / (time.perf_counter() - t0))
        finally:
            for eng in engines.values():
                eng.stop()
        ratio = statistics.median(o / s for o, s in zip(rates['on'],
                                                        rates['off']))
        attempts.append(round(ratio, 3))
        if ratio >= 0.9:
            break
    summary['decode_ratio_unshared'] = attempts[-1]
    summary['decode_ratio_attempts'] = attempts

    # (c) the CLI-reproducible form: loadgen --shared-prefix against a
    # paged replica, per-mix TTFT + engine hit rate in one report.
    server = llm_mod.LlmServer('tiny', max_len=64, engine='continuous')
    port = common_utils.find_free_port(23600)
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, '127.0.0.1', port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    if not started.wait(30):
        raise RuntimeError('prefix probe replica failed to start')
    url = f'http://127.0.0.1:{port}'
    try:
        requests_lib.post(f'{url}/generate',
                          json={'tokens': [[1, 2, 3, 4, 5, 6, 7, 8]],
                                'max_new_tokens': 4},
                          timeout=600).raise_for_status()
        load = asyncio.run(loadgen.run_load(
            url, requests_total=12, concurrency=3, prompt_len='6:10',
            max_new='8', vocab=256, stream=True, tenants=2,
            shared_prefix=0.8, shared_prefix_len=24))
    finally:
        if server.engine is not None:  # built lazily on first request
            server.engine.stop()
    sp = load.get('shared_prefix') or {}
    eng_side = (sp.get('engine') or {})
    summary['loadgen'] = {
        'ok': load.get('ok'),
        'shared_p50_ttft_s': (sp.get('shared') or {}).get('p50_ttft_s'),
        'unique_p50_ttft_s': (sp.get('unique') or {}).get('p50_ttft_s'),
        'engine_hits': ((eng_side.get('prefix_share') or {})
                        .get('hits')),
        'engine_hit_rate': ((eng_side.get('prefix_share') or {})
                            .get('hit_rate')),
    }

    if assert_gates:
        assert summary['parity_ok'], 'sharing changed greedy output'
        assert summary['hits'] > 0 and summary['hit_rate'] > 0, summary
        assert summary['cow_forks'] >= 1, summary
        assert summary['prefill_saved_frac'] >= 0.4, summary
        assert summary['drain_reconciled'], summary
        assert summary['decode_ratio_unshared'] >= 0.9, summary
        lg = summary['loadgen']
        assert lg['ok'] == 12, summary
        assert lg['engine_hits'] and lg['engine_hits'] > 0, summary
        assert lg['shared_p50_ttft_s'] is not None, summary
    return summary


def kvtier_probe(assert_gates: bool = False) -> dict:
    """Hierarchical KV memory gate (serve/kv_tiers.py: HBM -> host
    DRAM -> spill segments, re-import instead of recompute) — shared
    by ``bench.py`` (the ``kv_tiers`` detail entry) and
    ``tools/perf_probe.py --kvtier`` (the CI gate, assert_gates=True).

    Three legs, all CPU, tiny model, 4-usable-block pool so three
    24-token heads cannot coexist in HBM (every revisit finds its
    chain evicted):
    (a) tiers ON vs OFF on identical revisit traffic: greedy outputs
        byte-identical, promotes happened, the ON engine
        prefill-computes strictly fewer prompt tokens, and mean
        revisit TTFT is lower (re-import beats recompute; median of
        3 attempts, same drift discipline as the decode smoke);
    (b) injected corruption: with a 1-byte host pool everything
        spills; every segment file gets a payload byte flipped, then
        the revisits must STILL match the solo oracle byte-for-byte
        with zero failed requests — corrupt chains quarantine and
        recompute, never a 500;
    (c) after a full drain the device block states reconcile exactly
        and the off-device host/spilled counts match the tier
        stats."""
    import shutil
    import statistics
    import tempfile

    import jax
    import numpy as np

    from skypilot_tpu.models import generate as gen_lib
    from skypilot_tpu.models import llama
    from skypilot_tpu.models.engine import ContinuousEngine

    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    heads = [[((17 * h + j) % 250) + 1 for j in range(24)]
             for h in range(3)]
    _ENV = ('SKYTPU_KV_TIERS', 'SKYTPU_KV_HOST_BYTES',
            'SKYTPU_KV_SPILL_DIR')

    def _engine(**env):
        saved = {k: os.environ.get(k) for k in _ENV}
        for k in _ENV:
            os.environ.pop(k, None)
        os.environ.update(env)
        try:
            return ContinuousEngine(params, cfg, slots=4, max_len=64,
                                    chunk_steps=2, kv_blocks=5)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def _timed(eng, row, n):
        t0 = time.perf_counter()
        ttft = []

        def cb(_tokens):
            if not ttft:
                ttft.append(time.perf_counter() - t0)

        out = eng.submit(row, n, on_tokens=cb).result(timeout=600)
        return out, (ttft[0] if ttft else None)

    def _leg(tiers_on, attempt):
        eng = _engine(SKYTPU_KV_TIERS='1' if tiers_on else '0')
        outs, ttfts = [], []
        try:
            # Pressure + one untimed revisit round: commits, evicts,
            # demotes, and compiles the promote/import path so the
            # timed rounds measure steady state.
            for rnd in ('p', 'w'):
                for i, h in enumerate(heads):
                    tail = [((3 if rnd == 'p' else 29) * (attempt + 1)
                             + 7 * i + j) % 250 + 1 for j in range(4)]
                    outs.append(eng.submit(h + tail, 6)
                                .result(timeout=600))
            for rnd in range(3):
                for i, h in enumerate(heads):
                    tail = [(53 * attempt + 11 * rnd + 5 * i + j) % 250
                            + 1 for j in range(4)]
                    out, tt = _timed(eng, h + tail, 6)
                    outs.append(out)
                    if tt is not None:
                        ttfts.append(tt)
            if tiers_on:
                assert eng._kv_tiers.quiesce(20)
            stats = eng.stats()
        finally:
            eng.stop()
        return outs, ttfts, stats

    def _drained(stats):
        kb = stats['kv_blocks']
        tiers = stats.get('kv_tiers') or {}
        return (kb['owned'] == 0 and kb['shared'] == 0
                and kb['free'] + kb['cached'] == kb['usable']
                and kb.get('host', 0) == (tiers.get('host_blocks') or 0)
                and kb.get('spilled', 0)
                == (tiers.get('spilled_blocks') or 0))

    # (a) tiered vs untiered A/B, with TTFT drift retries.
    attempts = []
    for attempt in range(3):
        on_outs, on_ttfts, on_stats = _leg(True, attempt)
        off_outs, off_ttfts, off_stats = _leg(False, attempt)
        attempts.append(round(statistics.mean(on_ttfts)
                              / statistics.mean(off_ttfts), 3))
        if on_outs == off_outs and attempts[-1] < 1.0:
            break
    tiers = on_stats['kv_tiers']
    summary = {
        'parity_ok': on_outs == off_outs,
        'demotes': tiers['demotes'],
        'promotes': tiers['promotes'],
        'host_hits': tiers['host_hits'],
        'prefill_tokens_on': on_stats['prefill_tokens'],
        'prefill_tokens_off': off_stats['prefill_tokens'],
        'ttft_ratio': attempts[-1],
        'ttft_ratio_attempts': attempts,
        'drain_reconciled': (_drained(on_stats)
                            and _drained(off_stats)),
    }

    # (b) corruption -> quarantine + recompute, zero failed requests.
    spill_dir = tempfile.mkdtemp(prefix='kvtier-probe-')
    corrupt_parity = True
    try:
        eng = _engine(SKYTPU_KV_TIERS='1', SKYTPU_KV_HOST_BYTES='1',
                      SKYTPU_KV_SPILL_DIR=spill_dir)
        try:
            for i, h in enumerate(heads):
                row = h + [5 + i, 6, 7, 8]
                ok = eng.submit(row, 6).result(timeout=600) == \
                    gen_lib.generate(
                        params, cfg, np.asarray([row], np.int32),
                        max_new_tokens=6, max_len=64)[0].tolist()
                corrupt_parity = corrupt_parity and ok
            assert eng._kv_tiers.quiesce(20)
            segs = [os.path.join(spill_dir, n)
                    for n in os.listdir(spill_dir)
                    if n.endswith('.seg')]
            for path in segs:
                with open(path, 'r+b') as f:
                    f.seek(-1, os.SEEK_END)
                    last = f.read(1)
                    f.seek(-1, os.SEEK_END)
                    f.write(bytes([last[0] ^ 0xFF]))
            for i, h in enumerate(heads):
                row = h + [9, 9, 9 + i]
                ok = eng.submit(row, 6).result(timeout=600) == \
                    gen_lib.generate(
                        params, cfg, np.asarray([row], np.int32),
                        max_new_tokens=6, max_len=64)[0].tolist()
                corrupt_parity = corrupt_parity and ok
            assert eng._kv_tiers.quiesce(20)
            cstats = eng.stats()['kv_tiers']
        finally:
            eng.stop()
    finally:
        shutil.rmtree(spill_dir, ignore_errors=True)
    summary['corruption'] = {
        'segments_flipped': len(segs),
        'parity_ok': corrupt_parity,
        'spills': cstats['spills'],
        'corrupt': cstats['corrupt'],
        'quarantined': cstats['quarantined'],
    }

    if assert_gates:
        assert summary['parity_ok'], 'tiering changed greedy output'
        assert summary['promotes'] > 0 and summary['host_hits'] > 0, \
            summary
        assert summary['prefill_tokens_on'] \
            < summary['prefill_tokens_off'], summary
        assert summary['ttft_ratio'] < 1.0, summary
        assert summary['drain_reconciled'], summary
        c = summary['corruption']
        assert c['segments_flipped'] > 0 and c['spills'] > 0, summary
        assert c['parity_ok'], 'corrupt spill broke byte parity'
        assert c['corrupt'] >= 1 and c['quarantined'] >= 1, summary
    return summary


def qos_overload_probe(assert_gates: bool = False) -> dict:
    """Deterministic 2x-overload probe for the QoS admission layer
    (serve/qos.py) — shared by ``bench.py`` (the ``qos_overload``
    detail entry) and ``tools/perf_probe.py --qos`` (the CI gate,
    ``assert_gates=True``).

    A real tiny-model replica runs with QoS on and a 2-slot dispatch
    gate; after one warmup request (compile time must not count as
    queue wait), a deterministic 1:1 interactive/batch mix of 24
    requests lands at concurrency 20 against a hold capacity of 14
    (2 in flight + 12 queued) — ~2x what the server can hold, so the
    queue saturates and sheds. Parameters are chosen so batch MUST
    absorb 100% of sheds: the mix offers only 12 interactive in total,
    so the 12-deep queue can never be all-interactive when an
    interactive request arrives — a full queue always contains a batch
    victim. Gates: sheds happened, every shed was batch-class, and
    every interactive request was served with bounded queue wait."""
    import asyncio
    import threading

    import requests as requests_lib
    from aiohttp import web

    from skypilot_tpu.serve import llm_server as llm_mod
    from skypilot_tpu.serve import loadgen
    from skypilot_tpu.utils import common_utils

    server = llm_mod.LlmServer(
        'tiny', max_len=64, engine='continuous', qos='on',
        qos_opts=dict(max_inflight=2, max_queue=12,
                      ttl_s={'interactive': 300.0, 'standard': 300.0,
                             'batch': 300.0},
                      tenant_rps=0, tenant_tps=0))
    port = common_utils.find_free_port(23400)
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.make_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, '127.0.0.1', port)
        loop.run_until_complete(site.start())
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    if not started.wait(30):
        raise RuntimeError('qos probe replica failed to start')
    url = f'http://127.0.0.1:{port}'
    try:
        # Warmup: one request compiles prefill/decode so engine compile
        # time never counts as queue wait in the measured run.
        r = requests_lib.post(f'{url}/generate',
                              json={'tokens': [[1, 2, 3, 4, 5, 6, 7, 8]],
                                    'max_new_tokens': 8}, timeout=600)
        r.raise_for_status()
        out = asyncio.run(loadgen.run_load(
            url, requests_total=24, concurrency=20, prompt_len='8',
            max_new='16', vocab=256, mix='interactive:1,batch:1'))
        health = requests_lib.get(f'{url}/health', timeout=10).json()
    finally:
        server.engine.stop()
    qos = health.get('qos') or {}
    classes = qos.get('classes') or {}
    inter = classes.get('interactive') or {}
    per_class = out.get('per_class') or {}
    summary = {
        'offered_concurrency': 20,
        'max_inflight': 2,
        'max_queue': 12,
        'shed_total': qos.get('shed_total', 0),
        'evicted_total': qos.get('evicted_total', 0),
        'batch_shed': (classes.get('batch') or {}).get('shed', 0),
        'interactive_shed': inter.get('shed', 0),
        'interactive_p95_wait_ms':
            (inter.get('queue_wait_ms') or {}).get('p95'),
        'per_class': per_class,
    }
    if assert_gates:
        pci = per_class.get('interactive') or {}
        assert summary['shed_total'] > 0, summary
        assert summary['interactive_shed'] == 0, summary
        assert summary['batch_shed'] == summary['shed_total'], summary
        assert pci.get('ok') == pci.get('requests'), summary
        p95 = summary['interactive_p95_wait_ms']
        assert p95 is not None and p95 < 30000, summary
    return summary


def ckpt_stall_probe(assert_gates: bool = False) -> dict:
    """Checkpoint-stall A/B (skypilot_tpu/ckpt/): per-save step-loop
    stall, synchronous persist vs async snapshot+background commit, on
    a tiny real param tree. The async stall should be the device->host
    copy alone — an order of magnitude under the sync write+fsync on
    any backend; the ratio is the BENCH artifact's 'checkpoint_stall'
    entry and (with ``assert_gates``) the perf_probe --ckpt bound of
    50% is enforced by the probe's subprocess variant instead (this
    in-process probe drains between saves, so it isolates the snapshot
    cost from back-pressure)."""
    import shutil
    import statistics
    import tempfile

    import jax
    import jax.numpy as jnp

    from skypilot_tpu.ckpt.manager import AsyncCheckpointManager
    from skypilot_tpu.models import llama

    params = llama.init_params(jax.random.PRNGKey(0), llama.TINY)
    state = {'step': jnp.zeros((), jnp.int32), 'params': params}
    stalls = {}
    dirs = []
    try:
        for mode in ('sync', 'async'):
            d = tempfile.mkdtemp(prefix=f'skytpu-bench-ck-{mode}-')
            dirs.append(d)
            mgr = AsyncCheckpointManager(
                d, save_interval_steps=1, async_save=(mode == 'async'),
                telemetry=None)
            samples = []
            for step in range(1, 9):
                t0 = time.perf_counter()
                mgr.save(step, state, force=True)
                samples.append(time.perf_counter() - t0)
                # Drain between saves: measure the snapshot cost, not
                # back-pressure (the probe's trainer-subprocess variant
                # covers the loaded case).
                mgr.wait_until_finished()
            mgr.close()
            stalls[mode] = statistics.median(samples[1:])
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)
    out = {'sync_save_ms_p50': round(stalls['sync'] * 1e3, 3),
           'async_stall_ms_p50': round(stalls['async'] * 1e3, 3),
           'stall_ratio': round(stalls['async'] / stalls['sync'], 4)}
    if assert_gates:
        assert out['stall_ratio'] < 0.5, out
    return out


def _measure_provision_to_first_step() -> float:
    """Launch a task on the local provider; time launch-call -> first run
    output. Exercises provision + runtime bootstrap + gang exec for real."""
    import tempfile

    os.environ.setdefault('SKYTPU_STATE_DIR',
                          tempfile.mkdtemp(prefix='skytpu-bench-'))
    from skypilot_tpu import core, execution
    from skypilot_tpu.backends.tpu_gang_backend import runtime_dir
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.task import Task

    task = Task('bench-first-step', run='echo FIRST_STEP')
    task.set_resources(Resources(cloud='local'))
    t0 = time.perf_counter()
    job_id, _ = execution.launch(task, cluster_name='bench-latency',
                                 detach_run=True)
    log = os.path.join(runtime_dir('bench-latency'), 'jobs', str(job_id),
                       'run.log')
    deadline = time.time() + 60
    seen = False
    while time.time() < deadline:
        try:
            with open(log, encoding='utf-8') as f:
                if 'FIRST_STEP' in f.read():
                    seen = True
                    break
        except OSError:
            pass
        time.sleep(0.05)
    dt = time.perf_counter() - t0
    try:
        core.down('bench-latency')
    except Exception:
        pass
    if not seen:
        raise TimeoutError('job never emitted FIRST_STEP within 60s')
    return dt


def _sweep_best_config(candidates, warmup: int = 1, iters: int = 3):
    """Short-run each candidate config; return (winner, results). A
    candidate that fails (HBM OOM on the bigger batches) is recorded and
    skipped — the sweep must never kill the capture. Falls back to the
    first candidate if everything failed (the final measurement will
    then surface the real error). Wall-clock-budgeted: producing SOME
    artifact beats finishing the sweep (SKYTPU_BENCH_SWEEP_BUDGET_S)."""
    try:
        budget_s = float(
            os.environ.get('SKYTPU_BENCH_SWEEP_BUDGET_S', '600'))
    except ValueError:
        budget_s = 600.0  # malformed env must not kill the capture
    t0 = time.monotonic()
    results = []
    best = None
    for cand in candidates:
        label = f'{cand.remat_policy}/b{cand.global_batch_size}'
        if best is not None and time.monotonic() - t0 > budget_s:
            results.append({'config': label, 'skipped': 'sweep budget'})
            continue
        try:
            tf, _, _, _ = _measure_step_throughput(cand, warmup, iters)
        except Exception as exc:  # noqa: BLE001 — OOM/compile failure
            results.append({'config': label,
                            'error': f'{type(exc).__name__}: '
                                     f'{str(exc)[:200]}'})
            continue
        results.append({'config': label, 'tflops_per_chip': round(tf, 2)})
        if best is None or tf > best[0]:
            best = (tf, cand)
        print(f'[bench] sweep {label}: {tf:.1f} TF/s/chip',
              file=sys.stderr)
    return (best[1] if best else candidates[0]), results


def _bench_tpu() -> dict:
    from skypilot_tpu.utils import jax_env
    try:
        backend = jax_env.init_backend()['platform']
    except RuntimeError as exc:  # no backend, or an un-asked-for CPU
        backend = f'none: {exc}'
    if backend != 'tpu':
        print(f'[bench] no TPU (backend {backend}): nothing measured',
              file=sys.stderr)
        sys.exit(2)

    import jax

    from skypilot_tpu.models import llama
    from skypilot_tpu.train import TrainerConfig

    # CAPTURE-TIME AUTOTUNE (r4): the bench itself runs a short sweep
    # over the configs that bracketed past winners (r2: 'dots' b2 beat
    # full remat 96 -> 108 TF/s) and measures the final number on the
    # winner. Candidates that OOM are skipped and recorded.
    candidates = [
        TrainerConfig(model=llama.BENCH_1B, global_batch_size=b,
                      seq_len=4096, optimizer='adafactor', remat=True,
                      remat_policy=p)
        for p, b in (('dots', 2), ('dots', 3), ('heavy', 4),
                     ('attn', 4), ('attn', 6), ('heavy', 6))
    ]
    cfg, sweep = _sweep_best_config(candidates)
    cfg2k = TrainerConfig(model=llama.BENCH_1B, global_batch_size=4,
                          seq_len=2048, optimizer='adafactor', remat=True,
                          remat_policy=cfg.remat_policy)
    tf4k, tok4k, steps4k, loss = _measure_step_throughput(cfg, 2, 8)
    tf2k, _, _, _ = _measure_step_throughput(cfg2k, 2, 8)

    # A failed measurement raises: a bench line with a hole in it is
    # not a result.
    provision_s = round(_measure_provision_to_first_step(), 3)
    best, decode_variants = _measure_decode_throughput(cfg)
    decode_tps = round(best, 1)
    # QoS admission under 2x overload (tiny model): interactive
    # bounded, batch absorbs the sheds.
    qos_overload = qos_overload_probe()
    # Block-prefix sharing A/B: parity, prefill-token savings on an
    # 80%-shared mix, decode parity unshared, loadgen TTFT per mix.
    prefix_share = prefix_share_probe()
    # Hierarchical KV tiers A/B: re-import vs recompute on evicted
    # prefix chains, plus the corruption->quarantine contract.
    kv_tiers = kvtier_probe()
    # Checkpoint-stall A/B: what the step loop pays per save, sync
    # persist vs async snapshot (skypilot_tpu/ckpt/).
    checkpoint_stall = ckpt_stall_probe()

    baseline_tflops_per_chip = 23.48  # reference recipe, see module docstring
    n_chips = jax.device_count()
    return {
        'metric': 'llama_train_model_tflops_per_chip',
        'value': round(tf4k, 3),
        'unit': 'TFLOP/s/chip (6ND)',
        'vs_baseline': round(tf4k / baseline_tflops_per_chip, 3),
        'detail': {
            'backend': backend,
            'device_kind': jax.devices()[0].device_kind,
            'chips': n_chips,
            'model_params': cfg.model.param_count,
            'seq_len': cfg.seq_len,
            'global_batch': cfg.global_batch_size,
            'tokens_per_sec_per_chip': round(tok4k, 1),
            'steps_per_sec': round(steps4k, 4),
            'loss': round(loss, 4),
            'tflops_per_chip_seq2048': round(tf2k, 3),
            'remat_policy': cfg.remat_policy,
            'sweep': sweep,
            # Honest label: this times the local provider's
            # launch->first-output path (provision + bootstrap + gang
            # exec), not provision on real cloud infra.
            'local_provider_first_step_s': provision_s,
            # Best across weight formats; the per-format breakdown
            # (bf16 vs int8 weight-only) is decode_variants.
            'decode_tokens_per_sec': decode_tps,
            'decode_variants': decode_variants,
            'qos_overload': qos_overload,
            'prefix_share': prefix_share,
            'kv_tiers': kv_tiers,
            'checkpoint_stall': checkpoint_stall,
        },
    }


def main() -> None:
    print(json.dumps(_bench_tpu(), separators=(',', ':')))


if __name__ == '__main__':
    main()
