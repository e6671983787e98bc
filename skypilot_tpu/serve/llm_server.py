"""In-framework LLM inference server (JetStream analog).

Reference analog: the reference serves LLMs by pointing ``sky serve`` at
JetStream/vLLM containers (``examples/tpu/v6e/README.md:112-118``); this is
the TPU-native replica process: the KV-cache generate path
(``models/generate.py``) behind a minimal HTTP API.

Two execution paths:

* CONTINUOUS BATCHING (default — ``models/engine.py``): JetStream-style
  slot server; requests prefill into free slots of a persistent decode
  batch, so short requests drain mid-stream instead of waiting for the
  batch's slowest member. ``SKYTPU_LLM_ENGINE=off`` disables.
* WINDOW BATCHING (legacy, and always used for seeded sampling — whose
  determinism contract is incompatible with continuous batching):
  concurrent requests landing within the batch window are right-padded
  into one prefill/decode (decode is HBM-bound, so throughput scales
  nearly linearly with batch; measured on v5e: 1.8k tok/s single ->
  4k+ batched -> 5k+ continuous).

QOS ADMISSION (``--qos on`` / ``SKYTPU_QOS=1``; default off —
``serve/qos.py``): requests carry an optional ``priority``
(``interactive``/``standard``/``batch``) and tenant identity; a
weighted-fair scheduler orders admission, per-tenant token buckets cap
request and generated-token rates, and overload sheds batch-first with
429 + Retry-After while queue TTLs evict stale waiters (504).

TRACING (``observability/trace.py``; on by default, ``SKYTPU_TRACE=0``
disables): every request gets a ``serve.generate`` root span — joined
to the caller's trace when an ``X-SkyTPU-Trace`` header arrives — with
``qos.queue_wait`` / ``serve.prefill`` / ``serve.decode`` (per-chunk
children annotated with the engine's pipeline-overlap deltas) /
``serve.stream`` phases built retroactively from engine-callback
timestamps, so the decode loop never touches the tracer. The same
timestamps feed the Prometheus latency histograms
(``server/metrics.py``: TTFT, queue wait, per-phase, decode tok/s, per
QoS class). Tracing is observational only: greedy output is
byte-identical with it on or off.

API (token-level; tokenization is the client's concern — no tokenizer
assets ship in-image):
  GET  /health               -> {"status": "ok", "model": ...,
                                 "batches_served": N, "max_batch_seen": M}
  GET  /metrics              -> Prometheus scrape (latency histograms +
                                engine/queue gauges)
  GET  /debug/traces         -> recent/slowest completed + RETAINED
                                traces (?slowest=1, ?trace_id=,
                                ?qos_class=, ?tenant=, ?limit=,
                                ?retained=1, ?autopsy=1; the LB's
                                trailing ?retain=<id>&verdict=<v>
                                promotes pending tail fragments)
  GET  /debug/exemplars      -> newest trace id per serving-histogram
                                bucket (the metric -> retained-trace
                                jump; also in the OpenMetrics /metrics
                                exposition)
  POST /generate             {"tokens": [[...]], "max_new_tokens": N,
                              "temperature": t?, "seed": s?}
                             -> {"tokens": [[...]]}

Run: ``python -m skypilot_tpu.serve.llm_server --model tiny``
(port from --port or SKYTPU_REPLICA_PORT — the serve plane's contract).
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import contextlib
import functools
import os
import time
from typing import Any, Deque, Dict, List, Optional

import jax
from aiohttp import web

from skypilot_tpu.models import generate as gen_lib
from skypilot_tpu.models import llama
# Runtime profiler (observability/profiler.py): cold-start phase marks
# here (weights_load / jit_warmup / ready / first_token), the /health
# `profile` block, and /debug/profile. mark() is a first-crossing
# timestamp write; every SURFACE is SKYTPU_PROFILE-gated.
from skypilot_tpu.observability import profiler
from skypilot_tpu.observability import trace as trace_lib
from skypilot_tpu.serve import qos as qos_lib
# AOT warm-up driver (serve/warmup.py): main() runs it in the dark
# window with SKYTPU_WARMUP=1; __init__ seeds the warmup_skipped note.
from skypilot_tpu.serve import warmup as warmup_lib
from skypilot_tpu.utils import jax_env

MAX_BATCH = int(os.environ.get('SKYTPU_LLM_MAX_BATCH', '32'))
BATCH_WINDOW_S = float(os.environ.get('SKYTPU_LLM_BATCH_WINDOW_MS',
                                      '8')) / 1000.0


class _Pending:

    def __init__(self, rows: List[List[int]], max_new: int,
                 temperature: float, seed: Optional[int],
                 top_k: int = 0, top_p: float = 1.0, eos=None):
        self.rows = rows
        self.max_new = max_new
        self.temperature = temperature
        self.seed = seed
        self.top_k = top_k
        self.top_p = top_p
        self.eos = eos  # frozenset of stop ids, or None
        self.future: asyncio.Future = asyncio.get_event_loop().create_future()

    @property
    def group_key(self):
        # Seeded sampling must stay deterministic for ITS caller — and
        # sampling noise depends on batch composition, so a seeded request
        # is NEVER batched with anything else (unique key per request).
        if self.temperature > 0 and self.seed is not None:
            return ('seeded', id(self))
        # Sampling params are per-generate()-call scalars on the window
        # path, so only like-configured requests share a batch.
        return (self.temperature, self.top_k, self.top_p, None)


_METRICS = None


def _metrics():
    """``server/metrics.py``, or a no-op stand-in when prometheus_client
    is absent (minimal replica images): observability must never fail a
    request whose tokens were already generated."""
    global _METRICS
    if _METRICS is None:
        try:
            from skypilot_tpu.server import metrics as metrics_lib
            _METRICS = metrics_lib
        except ImportError:
            class _NoopMetric:
                def labels(self, **_kw):
                    return self

                def observe(self, _value):
                    pass

            class _Shim:
                SERVE_TTFT = SERVE_QUEUE_WAIT = SERVE_PHASE = \
                    SERVE_DECODE_RATE = _NoopMetric()

                @staticmethod
                def render_serving(engine=None, qos=None, disagg=None,
                                   openmetrics=False):
                    del engine, qos, disagg, openmetrics
                    return b'# prometheus_client not installed\n'

                @staticmethod
                def observe_serving(name, value, trace_id=None,
                                    **labels):
                    del name, value, trace_id, labels

                @staticmethod
                def exemplars_payload(query=None):
                    del query
                    return {'count': 0, 'exemplars': []}

            _METRICS = _Shim()
    return _METRICS


class _ChunkRecorder:
    """Per-request emission timestamps: the engine-thread callback cost
    is one ``time.time()`` plus a tuple append — spans and histogram
    observations are built AFTER the request completes, so the decode
    loop never blocks on observability."""
    __slots__ = ('t0', 'events', 'timelines')

    def __init__(self):
        self.t0 = time.time()
        self.events: List = []  # (t, row_index, n_tokens)
        self.timelines: Dict[int, Any] = {}  # row_index -> RequestTimeline

    def submitted(self, ri: int, fut):
        """Keep the engine's own timeline of row ``ri`` (it rides on
        the future; a stub engine's future has none). Returns ``fut``."""
        self.timelines[ri] = getattr(fut, 'timeline', None)
        return fut

    def cb(self, ri: int):
        events = self.events

        def _cb(toks):
            events.append((time.time(), ri, len(toks)))
        return _cb



# Handoff payloads span ~100 KB (short prompts) to hundreds of MB (long
# prompts on big models). The crc32/serialize/parse work is real CPU
# time that must not stall in-flight streams on the event loop — but an
# executor hop has fixed cost that loses on small payloads, so only
# off-load past this size.
_DISAGG_OFFLOAD_MIN_BYTES = int(os.environ.get(
    'SKYTPU_DISAGG_OFFLOAD_MIN_BYTES', str(4 * 1024 * 1024)))


async def _run_sized(nbytes: int, fn, *args, **kw):
    """Run CPU-bound handoff work inline when small, in the default
    executor when large (see _DISAGG_OFFLOAD_MIN_BYTES)."""
    if nbytes < _DISAGG_OFFLOAD_MIN_BYTES:
        return fn(*args, **kw)
    return await asyncio.get_event_loop().run_in_executor(
        None, functools.partial(fn, *args, **kw))


def _handoff_nbytes(handoff) -> int:
    """Rough plane-bytes size of an un-serialized handoff."""
    total = 0
    for arr in (handoff.k, handoff.v, handoff.k_s, handoff.v_s):
        if arr is not None:
            total += int(arr.nbytes)
    return total


class LlmServer:

    def __init__(self, model: str, max_len: int = 1024, seed: int = 0,
                 quantize: Optional[str] = None,
                 engine: Optional[str] = None, tp: Optional[int] = None,
                 kv_cache: Optional[str] = None,
                 draft_model: Optional[str] = None,
                 kv_blocks: Optional[int] = None,
                 pipeline: Optional[str] = None,
                 qos: Optional[str] = None,
                 qos_opts: Optional[Dict[str, Any]] = None,
                 prefix_share: Optional[str] = None,
                 role: Optional[str] = None):
        self.model_name = model
        self.cfg = llama.PRESETS[model]
        self.max_len = min(max_len, self.cfg.max_seq_len)
        # Disaggregated serving role (serve/disagg.py): 'prefill'
        # replicas are routed /v1/kv/export (compute prompt KV, hand
        # off), 'decode' replicas /v1/kv/import (install + stream).
        # Every role still serves /generate — the LB's colocated
        # fallback must be able to land anywhere that survives.
        self.role = role or os.environ.get('SKYTPU_LLM_ROLE',
                                           'colocated')
        if self.role not in ('colocated', 'prefill', 'decode'):
            raise ValueError(f'Unknown role {self.role!r}; '
                             "'colocated', 'prefill' or 'decode'")
        # Validate ALL the cheap knobs BEFORE weight init: on a real
        # slice the sharded init+quantize pass takes minutes, and a
        # typo'd flag or env var must not cost the operator that
        # startup.
        self.kv_cache = (kv_cache
                         or os.environ.get('SKYTPU_LLM_KV_CACHE', 'bf16'))
        if self.kv_cache not in ('bf16', 'int8'):
            raise ValueError(f'Unknown kv_cache {self.kv_cache!r}; '
                             "'bf16' or 'int8'")
        # Pool size is THE KV knob (a full-capacity pool saves no
        # HBM); 0/None = engine default (full capacity, always safe).
        self.kv_blocks = kv_blocks or int(
            os.environ.get('SKYTPU_LLM_KV_BLOCKS', '0')) or None
        # Copy-on-write block-level prefix sharing (models/paged.py
        # BlockTrie). Default ON for dense engines — 'off' is the A/B
        # and escape hatch (also via SKYTPU_LLM_PREFIX_SHARE=0).
        if prefix_share not in (None, 'on', 'off'):
            raise ValueError(f'Unknown prefix_share {prefix_share!r}; '
                             "'on' or 'off'")
        self.prefix_share = prefix_share
        # Pipelined decode dispatch (models/engine.py): 'on' keeps one
        # chunk in flight so host bookkeeping overlaps device compute;
        # 'off' = the serial engine (A/B and debugging). None defers to
        # SKYTPU_LLM_PIPELINE inside the engine (default on).
        if pipeline not in (None, 'on', 'off'):
            raise ValueError(f'Unknown pipeline {pipeline!r}; '
                             "'on' or 'off'")
        self.pipeline = pipeline
        # QoS admission control (serve/qos.py): priority classes,
        # per-tenant quotas, overload shedding. OFF by default — with
        # SKYTPU_QOS=0 no scheduler is constructed and the serving path
        # is byte-identical to the pre-QoS server.
        if qos not in (None, 'on', 'off'):
            raise ValueError(f"Unknown qos {qos!r}; 'on' or 'off'")
        self.qos_enabled = qos_lib.enabled(qos)
        self._qos_opts = dict(qos_opts or {})
        if self.qos_enabled and not self._qos_opts:
            qos_lib.validate_env()  # typo'd env must fail pre-init
        self.quantize = quantize or os.environ.get('SKYTPU_LLM_QUANTIZE')
        if self.quantize and self.quantize != 'int8':
            raise ValueError(f'Unknown quantization {self.quantize!r}; '
                             "only 'int8' (weight-only) is supported")
        # Speculative decoding: with the continuous engine the draft
        # rides INSIDE it (per-slot propose/verify rounds,
        # models/engine.py); with --engine off it rides the
        # window-batched path (models/speculative.py). Greedy requests
        # get the acceleration either way; sampled requests advance one
        # verified token per round on the engine path.
        self.draft_model = (draft_model
                            or os.environ.get('SKYTPU_LLM_DRAFT') or None)
        engine = engine or os.environ.get('SKYTPU_LLM_ENGINE',
                                          'continuous')
        if engine not in ('continuous', 'off'):
            raise ValueError(f"Unknown engine {engine!r}; 'continuous' "
                             "or 'off'")
        self.spec_k = int(os.environ.get('SKYTPU_LLM_SPEC_K', '4'))
        if self.spec_k < 1:
            raise ValueError(f'SKYTPU_LLM_SPEC_K must be >= 1, got '
                             f'{self.spec_k}')
        if self.draft_model is not None:
            if self.draft_model not in llama.PRESETS:
                raise ValueError(f'Unknown draft model '
                                 f'{self.draft_model!r}')
            if self.cfg.num_experts > 0:
                # MoE expert capacity is per forward CALL: the k+1-token
                # verify routes (and drops) differently than sequential
                # decode, so the documented byte-identical greedy
                # contract would silently break (r4 advisor medium).
                raise ValueError(
                    '--draft-model requires a dense target model; '
                    f'{model!r} is MoE (expert capacity is per forward '
                    'call, so a multi-token verify breaks greedy '
                    'exactness)')
            draft_cfg = llama.PRESETS[self.draft_model]
            if draft_cfg.vocab_size != self.cfg.vocab_size:
                raise ValueError(
                    'draft and target must share a vocabulary '
                    f'({draft_cfg.vocab_size} vs {self.cfg.vocab_size})')
            if draft_cfg.max_seq_len < self.max_len:
                # Otherwise every spec-eligible request would 500 at
                # generate_speculative's own context check.
                raise ValueError(
                    f'draft model {self.draft_model!r} max_seq_len '
                    f'{draft_cfg.max_seq_len} < server max_len '
                    f'{self.max_len}')
        # Tensor-parallel serving over the replica's slice: a mesh whose
        # `tensor` axis spans tp chips; weights/KV shard by the training
        # stack's logical rules and every decode step runs SPMD (the way
        # JetStream serves sharded 8B+ models). Weights are initialized
        # (and quantized) SHARDED — a model that only fits spread over
        # the slice must never transit one chip whole.
        self.tp = tp or int(os.environ.get('SKYTPU_LLM_TP', '1'))
        self.mesh = None
        key = jax.random.PRNGKey(seed)
        if self.tp > 1:
            from skypilot_tpu.parallel import mesh as mesh_lib
            self.mesh = mesh_lib.build_mesh(
                mesh_lib.MeshSpec(fsdp=1, tensor=self.tp),
                devices=jax.devices()[:self.tp])
            self.params = llama.init_params_sharded(key, self.cfg,
                                                    self.mesh)
        else:
            self.params = llama.init_params(key, self.cfg)
        if self.quantize:
            # Deployment-time int8 weight-only quantization: halves the
            # per-decode-step weight stream (models/quantization.py).
            from skypilot_tpu.models import quantization as quant_lib
            if self.mesh is not None:
                self.params = quant_lib.quantize_params_sharded(
                    self.params, self.cfg, self.mesh)
            else:
                self.params = quant_lib.quantize_params(self.params)
        self.draft_cfg = None
        self.draft_params = None
        self._spec_stats = {'requests': 0, 'verifies': 0,
                            'proposals': 0, 'accepted': 0}
        if self.draft_model is not None:
            self.draft_cfg = llama.PRESETS[self.draft_model]
            self.draft_params = llama.init_params(
                jax.random.PRNGKey(seed + 1), self.draft_cfg)
        # Cold-start ledger: target (+draft) weights are resident now;
        # logical footprint registered for the memory reconciliation.
        profiler.mark('weights_load')
        profiler.register_logical('weights',
                                  profiler.tree_nbytes(self.params))
        if self.draft_params is not None:
            profiler.register_logical(
                'draft_weights', profiler.tree_nbytes(self.draft_params))
        # Multi-host SPMD replica (serve/spmd.py): every worker process
        # runs the same engine in lockstep; HTTP lives on rank 0 only.
        self.world = jax.process_count()
        if self.world > 1 and engine != 'continuous':
            raise ValueError('multi-host serving requires the '
                             'continuous engine (the window path is '
                             'head-local and would deadlock the '
                             'collective over sharded weights)')
        self.engine = None
        if engine == 'continuous':
            if self.world > 1:
                from skypilot_tpu.serve.spmd import SpmdEngine \
                    as ContinuousEngine
            else:
                from skypilot_tpu.models.engine import ContinuousEngine
            # params are already mesh-placed when tp > 1, so the engine's
            # own shard_params is a no-op placement — both paths serve
            # the SAME resident weights. The draft (if any) rides inside
            # the engine: per-slot propose/verify rounds.
            self.engine = ContinuousEngine(
                self.params, self.cfg, max_len=self.max_len,
                mesh=self.mesh, kv_quantize=self.kv_cache == 'int8',
                draft_params=self.draft_params, draft_cfg=self.draft_cfg,
                spec_k=self.spec_k, kv_blocks=self.kv_blocks,
                pipeline=(None if self.pipeline is None
                          else self.pipeline == 'on'),
                prefix_share=(None if self.prefix_share is None
                              else self.prefix_share == 'on'),
                role=self.role)
            self.params = self.engine.params
            if self.draft_params is not None:
                self.draft_params = self.engine.draft_params
        self.qos: Optional[qos_lib.QosScheduler] = None
        if self.qos_enabled:
            opts = self._qos_opts
            if not opts.get('max_inflight'):
                # The gate lives where the device's concurrency bound
                # lives: engine slots, or the window path's batch cap.
                opts['max_inflight'] = (
                    int(os.environ.get('SKYTPU_QOS_MAX_INFLIGHT', '0'))
                    or (self.engine.slots if self.engine is not None
                        else MAX_BATCH))
            self.qos = qos_lib.QosScheduler(**opts)
        self._queue: asyncio.Queue = asyncio.Queue()
        # deque: overflow spills pop from the FRONT every batch — the
        # old list's pop(0) was O(n) per pop under sustained overflow.
        self._overflow: Deque[_Pending] = collections.deque()
        self._worker: Optional[asyncio.Task] = None
        self.batches_served = 0
        self.draining = False
        self._inflight = 0
        self.max_batch_seen = 0
        # KV-handoff plumbing (serve/disagg.py): parked exports await
        # their fetch under a TTL; a configured staging dir enables the
        # same-host zero-copy-over-HTTP path. Server-level byte/second
        # accounting feeds /health and the skytpu_disagg_* gauges.
        from skypilot_tpu.serve import disagg as disagg_lib
        self._disagg_lib = disagg_lib
        self._handoffs = disagg_lib.HandoffRegistry()
        self.staging_dir = os.environ.get(disagg_lib.STAGING_ENV) or None
        self.disagg_stats: Dict[str, Any] = {
            'exports': 0, 'export_bytes': 0, 'export_seconds': 0.0,
            'imports': 0, 'import_bytes': 0, 'import_seconds': 0.0,
            'import_rejects': 0, 'fallbacks_served': 0}
        # Recent-request TTFT window (seconds): feeds the /health
        # ttft_ms percentiles the SLO engine's serve.ttft_p99 rule
        # samples (observability/slo.py). Appended from the handler
        # coroutines and read by /health — both on the event loop, and
        # deque appends are atomic besides.
        self._ttft_window: Deque[float] = collections.deque(maxlen=512)
        # Black-box flight recorder: incident bundles from this process
        # embed the replica's live /health snapshot.
        from skypilot_tpu.observability import blackbox
        blackbox.set_process_label(f'llm_server:{self.role}')
        blackbox.register_health_provider(self.health_snapshot)
        # AOT warm-up (serve/warmup.py) runs AFTER construction, from
        # main(), inside the dark window — and marks the 'jit_warmup'
        # phase crossing only when it actually ran. Marking it here
        # unconditionally (the old behavior) misattributed the
        # engine-build→ready gap to 'jit_warmup' on every boot that
        # never warmed anything; a skipped warm-up now leaves the
        # crossing absent and says why via the warmup_skipped note.
        self.warmup_report: Dict[str, Any] = warmup_lib.skipped(
            'SKYTPU_WARMUP disabled')
        self._warming = False

    async def health(self, request: web.Request) -> web.Response:
        del request
        if self.draining:
            # Readiness probes see 503: the LB stops routing here while
            # in-flight requests finish (graceful drain, see drain()).
            return web.json_response(
                {'status': 'draining', 'model': self.model_name},
                status=503)
        if self._warming:
            # READY contract: the probe must not see a 200 until the
            # compile ledger confirmed warm-up coverage. main() runs
            # warm-up before the listener binds, so this branch is
            # unreachable there — it guards any future async warm-up
            # (and documents the contract structurally).
            return web.json_response(
                {'status': 'warming', 'model': self.model_name},
                status=503)
        if profiler.enabled():
            # 'ready' = the first successful readiness probe — HERE,
            # not in health_snapshot(): the black-box health provider
            # also builds snapshots (e.g. an engine_failure bundle
            # during a failed start), and that must never fake the
            # dark→READY crossing.
            profiler.mark('ready')
            # Device-memory sampling rides the probe cadence but runs
            # OFF-LOOP and fire-and-forget: allocator queries on a
            # wedged PJRT runtime must not freeze the event loop every
            # other surface (streaming, /debug) shares. The body below
            # carries whatever the last completed sample was.
            asyncio.get_event_loop().run_in_executor(
                None, profiler.maybe_sample_device_memory)
        return web.json_response(self.health_snapshot())

    def health_snapshot(self) -> Dict[str, Any]:
        """The /health body, factored sync so the black-box recorder's
        incident bundles carry the exact snapshot operators already
        read (blackbox.register_health_provider in __init__). Reports
        'draining' once SIGTERM landed — the drain-triggered bundle
        must not describe the replica as healthy (the async handler
        503s before reaching here, so /health is unchanged)."""
        body = {'status': 'draining' if self.draining else 'ok',
                'model': self.model_name,
                'quantize': self.quantize, 'tp': self.tp,
                'kv_cache': self.kv_cache,
                'max_len': self.max_len,
                # Where this replica runs, as JAX reports it, with each
                # device's bytes_in_use (utils/jax_env.py).
                'device': jax_env.describe_devices(),
                'draft_model': self.draft_model,
                'batches_served': self.batches_served,
                'max_batch_seen': self.max_batch_seen,
                # Disaggregated serving (serve/disagg.py): the pool
                # role plus server-level handoff accounting — the
                # controller mirrors these into the skytpu_disagg_*
                # gauges and the dashboard pool column.
                'role': self.role,
                'disagg': {**self.disagg_stats,
                           'parked': len(self._handoffs),
                           'staging': bool(self.staging_dir)}}
        # Queue/backpressure snapshot: the controller reads depth_total
        # as the routing/scaling pressure signal (satellite: overflow
        # and queue depth surfaced in the health body).
        queue = {'pending': self._queue.qsize(),
                 'overflow': len(self._overflow)}
        queue['depth_total'] = queue['pending'] + queue['overflow']
        if self.qos is not None:
            qos_stats = self.qos.stats()
            body['qos'] = qos_stats
            queue['depth_total'] += qos_stats['queue_depth_total']
        body['queue'] = queue
        # Cold-start collapse surfaces (both independent of the
        # SKYTPU_PROFILE gate): the persistent-compile-cache state —
        # 'warm' is how the controller labels this boot for the
        # autoscaler's spin-up lead-time model — and the AOT warm-up
        # report (coverage, rounds, or the warmup_skipped note).
        body['compile_cache'] = jax_env.compile_cache_state()
        body['warmup'] = self.warmup_report
        # Tail-retention accounting (observability/trace.py): pending/
        # retained depth + per-verdict keep counts — how loadgen and
        # the autopsy probe see that interesting journeys survived and
        # boring ones were dropped.
        body['trace'] = trace_lib.tail_stats()
        if self._ttft_window:
            from skypilot_tpu.serve.qos import nearest_rank
            waits = sorted(round(t * 1000.0, 1)
                           for t in self._ttft_window)
            body['ttft_ms'] = {'count': len(waits),
                               'p50': nearest_rank(waits, 50),
                               'p95': nearest_rank(waits, 95),
                               'p99': nearest_rank(waits, 99)}
        if profiler.enabled():
            # Runtime profiler block: compile ledger + cold-start
            # phases + the last completed device-memory sample (the
            # async /health handler refreshes it off-loop at the probe
            # cadence — this sync builder must stay allocator-free for
            # the black-box provider path). The SLO extractors
            # (slo.replica_signal_fields) and the metrics-history
            # sampler read exactly this shape.
            body['profile'] = profiler.snapshot()
        if self.engine is not None:
            body['engine'] = self.engine.stats()
            # Fleet prefix-affinity advert (utils/prefix_affinity.py):
            # a bounded set of resident trie-chain hashes the
            # controller pushes into the LB's affinity policy. Top
            # level, not inside engine stats: the routing contract is
            # the SUMMARY schema, and consumers (controller, dashboard)
            # must not couple to the engine-stats shape to find it.
            if hasattr(self.engine, 'prefix_summary'):
                summary = self.engine.prefix_summary()
                if summary is not None:
                    body['prefix_summary'] = summary
        if self.draft_params is not None:
            s = dict(self._spec_stats)
            s['acceptance_rate'] = (
                round(s['accepted'] / s['proposals'], 4)
                if s['proposals'] else None)
            body['speculative'] = s
        return body

    # -- batching worker ---------------------------------------------------

    async def _collect(self) -> List[_Pending]:
        """One batch: the first waiter plus whatever lands inside the
        window, capped at MAX_BATCH total rows. A request that would push
        the batch past the cap spills into the NEXT batch rather than
        blowing the operator's HBM bound."""
        if self._overflow:
            batch = [self._overflow.popleft()]
        else:
            batch = [await self._queue.get()]
        rows = len(batch[0].rows)
        deadline = asyncio.get_event_loop().time() + BATCH_WINDOW_S
        while rows < MAX_BATCH:
            if self._overflow:
                nxt = self._overflow.popleft()
            else:
                timeout = deadline - asyncio.get_event_loop().time()
                if timeout <= 0:
                    break
                try:
                    nxt = await asyncio.wait_for(self._queue.get(),
                                                 timeout=timeout)
                except asyncio.TimeoutError:
                    break
            if rows + len(nxt.rows) > MAX_BATCH:
                self._overflow.append(nxt)
                break
            batch.append(nxt)
            rows += len(nxt.rows)
        return batch

    def _split_fitting(self, group: List[_Pending]) -> List[List[_Pending]]:
        """Partition a group so each sub-batch satisfies
        longest_prompt + max(max_new) <= max_len — requests are validated
        individually, but a batch combines one request's long prompt with
        ANOTHER's large max_new."""
        out: List[List[_Pending]] = []
        cur: List[_Pending] = []
        cur_longest = 0
        cur_max_new = 0
        for p in group:
            longest = max(len(r) for r in p.rows)
            if cur and (max(cur_longest, longest)
                        + max(cur_max_new, p.max_new)) > self.max_len:
                out.append(cur)
                cur, cur_longest, cur_max_new = [], 0, 0
            cur.append(p)
            cur_longest = max(cur_longest, longest)
            cur_max_new = max(cur_max_new, p.max_new)
        if cur:
            out.append(cur)
        return out

    @staticmethod
    def _deliver(p: _Pending, result) -> None:
        def _set():
            if not p.future.done():  # client may have disconnected
                p.future.set_result(result)
        p.future.get_loop().call_soon_threadsafe(_set)

    def _run_group(self, group: List[_Pending]) -> None:
        """Execute one compatible group as padded generate() calls."""
        for sub in self._split_fitting(group):
            rows: List[List[int]] = []
            for p in sub:
                rows.extend(p.rows)
            padded, lens = gen_lib.pad_prompts(rows)
            max_new = max(p.max_new for p in sub)
            temperature = sub[0].temperature
            seed = sub[0].seed
            lens_host = [len(r) for r in rows]
            # Speculative path (--draft-model): greedy, uniform-length
            # batches only (generate_speculative owns both caches and
            # takes no per-row prompt lengths); everything else keeps
            # the plain path.
            use_spec = (
                self.draft_params is not None and temperature == 0
                and min(lens_host) == max(lens_host)
                and max(lens_host) + max_new + self.spec_k + 1
                <= self.max_len)
            if use_spec:
                from skypilot_tpu.models import speculative
                out_arr, spec = speculative.generate_speculative(
                    self.params, self.cfg, self.draft_params,
                    self.draft_cfg, padded, max_new, k=self.spec_k,
                    max_len=self.max_len,
                    kv_quantize=self.kv_cache == 'int8')
                self._spec_stats['requests'] += len(sub)
                for key_ in ('verifies', 'proposals', 'accepted'):
                    self._spec_stats[key_] += spec[key_]
                out = jax.device_get(out_arr)
                i = 0
                for p in sub:
                    n = len(p.rows)
                    result = [gen_lib.truncate_at_stop(r, p.eos)[0]
                              for r in out[i:i + n, :p.max_new].tolist()]
                    self._deliver(p, result)
                    i += n
                continue
            key = None
            if temperature > 0:
                import secrets
                key = jax.random.PRNGKey(
                    seed if seed is not None else secrets.randbits(31))
            out = jax.device_get(gen_lib.generate(
                self.params, self.cfg, padded, max_new,
                temperature=temperature, key=key, max_len=self.max_len,
                prompt_lengths=lens,
                kv_quantize=self.kv_cache == 'int8',
                top_k=sub[0].top_k, top_p=sub[0].top_p))
            i = 0
            for p in sub:
                n = len(p.rows)
                # Each request gets only the tokens it asked for,
                # truncated at its first stop id (inclusive). The batch
                # still decodes to the group max (no per-row early exit
                # on this path — the continuous engine has that).
                result = [gen_lib.truncate_at_stop(r, p.eos)[0]
                          for r in out[i:i + n, :p.max_new].tolist()]
                self._deliver(p, result)
                i += n

    async def _worker_loop(self) -> None:
        while True:
            batch = await self._collect()
            groups: Dict[Any, List[_Pending]] = {}
            for p in batch:
                groups.setdefault(p.group_key, []).append(p)
            self.batches_served += 1
            self.max_batch_seen = max(
                self.max_batch_seen, sum(len(p.rows) for p in batch))
            for group in groups.values():
                try:
                    await asyncio.get_event_loop().run_in_executor(
                        None, self._run_group, group)
                except Exception as e:  # noqa: BLE001 — fail the waiters
                    for p in group:
                        if not p.future.done():
                            p.future.set_exception(e)

    def _ensure_worker(self) -> None:
        if self._worker is None or self._worker.done():
            self._worker = asyncio.get_event_loop().create_task(
                self._worker_loop())

    # -- per-request observability (trace spans + latency histograms) ------

    def _pipeline_stats(self) -> Optional[Dict[str, Any]]:
        """Lock-free snapshot of the engine's pipeline-overlap counters
        (plain float attrs; GIL-consistent, and these are trace
        annotations, not accounting). The full ``stats()`` takes the
        engine lock — a sampled-by-default hot path must not contend
        for it twice per request."""
        eng = self.engine
        if eng is None or not hasattr(eng, 'host_overlap_ms'):
            return None  # stub/foreign engine: no pipeline counters
        try:
            return {
                'pipeline_depth': getattr(eng, 'pipeline_depth', 0),
                'dispatch_gap_ms': round(
                    getattr(eng, '_gap_ms_total', 0.0)
                    / max(getattr(eng, '_gap_count', 0), 1), 3),
                'host_overlap_ms': eng.host_overlap_ms,
                'bubble_ms': eng.bubble_ms,
                # Block-share counters ride the same lock-free snapshot
                # so the serve.prefill span can annotate the delta.
                'share_hits': getattr(eng, 'share_hits', 0),
                'cow_forks': getattr(eng, 'cow_forks', 0),
                'prefill_tokens_saved': getattr(eng,
                                                'prefill_tokens_saved', 0),
            }
        except Exception:  # noqa: BLE001 — observability must never 500
            return None

    def _observe_serving(self, rec: _ChunkRecorder, qos_class: str,
                         pipe0: Optional[Dict[str, Any]],
                         parent: Optional[trace_lib.Span] = None) -> None:
        """Turn the recorder's timestamps into histogram observations
        and (when this request is sampled) prefill/decode spans. Purely
        after-the-fact: the tokens are already delivered."""
        metrics_lib = _metrics()
        events = sorted(rec.events)
        if not events:
            return
        anchor = parent if parent is not None else trace_lib.current()
        # The exemplar: the observation's trace id, whether head-sampled
        # or tail-pending — a retained tail outlier is exactly what a
        # hot bucket's exemplar should resolve to.
        tid = anchor.trace_id if anchor is not None else None
        ttft = max(events[0][0] - rec.t0, 0.0)
        profiler.mark('first_token')  # cold-start ledger: idempotent
        self._ttft_window.append(ttft)
        metrics_lib.observe_serving('skytpu_serve_ttft_seconds', ttft,
                                    trace_id=tid, qos_class=qos_class)
        metrics_lib.observe_serving('skytpu_serve_phase_seconds', ttft,
                                    trace_id=tid, phase='prefill',
                                    qos_class=qos_class)
        first_t, last_t = events[0][0], events[-1][0]
        toks = sum(n for _, _, n in events)
        decode_s = max(last_t - first_t, 0.0)
        metrics_lib.observe_serving('skytpu_serve_phase_seconds',
                                    decode_s, trace_id=tid,
                                    phase='decode', qos_class=qos_class)
        # Rate over the decode window only: the first emission's tokens
        # were produced during the prefill window the denominator
        # excludes — counting them would inflate short generations ~2x.
        decode_toks = toks - events[0][2]
        if decode_s > 0 and decode_toks > 0:
            metrics_lib.observe_serving(
                'skytpu_serve_decode_tok_s', decode_toks / decode_s,
                trace_id=tid, qos_class=qos_class)
        if anchor is None:
            return
        if anchor.end is not None:
            # Already-closed parent (the retroactive stream span after a
            # client disconnect): the engine thread keeps emitting, and
            # events past the parent's end would make the decode span
            # outgrow it — clamp to keep the nesting invariant.
            events = [e for e in events if e[0] <= anchor.end]
            if not events:
                return
            first_t, last_t = events[0][0], events[-1][0]
            toks = sum(n for _, _, n in events)
        trace_lib.set_attr(qos_class=qos_class,
                           ttft_ms=round(ttft * 1000.0, 3), tokens=toks)
        # "prefill" here is submit -> first emission: engine queue time
        # plus the actual prefill plus the first decode chunk — the TTFT
        # phase a serving operator tunes. Its children (below) say which
        # of the three it was.
        pipe1 = self._pipeline_stats()
        pattrs: Dict[str, Any] = {'tokens': events[0][2]}
        if pipe0 and pipe1 and 'share_hits' in pipe1:
            # Engine-wide deltas while this request was in flight
            # (co-resident requests share them — context, not
            # attribution; same convention as the decode-span overlap
            # deltas below).
            for k in ('share_hits', 'cow_forks', 'prefill_tokens_saved'):
                d = (pipe1.get(k) or 0) - (pipe0.get(k) or 0)
                if d:
                    pattrs[k] = d
        prefill_span = trace_lib.add_span('serve.prefill', rec.t0, first_t,
                                          parent=anchor, **pattrs)
        line = rec.timelines.get(events[0][1])
        if line is not None:
            # The engine stamps on perf_counter, the waterfall is on the
            # wall clock: one offset for the request, and each child cut
            # to its parent so the nesting invariant survives the two
            # clocks' jitter.
            to_wall = time.time() - time.perf_counter()
            for name, t0, t1 in line.phases():
                if name == 'engine.decode':
                    break  # serve.decode, on the stream's own stamps
                trace_lib.add_span(
                    name, min(max(t0 + to_wall, rec.t0), first_t),
                    min(max(t1 + to_wall, rec.t0), first_t),
                    parent=prefill_span, path=line.path, group=line.group,
                    saved_tokens=line.saved_tokens)
        dattrs: Dict[str, Any] = {'tokens': toks}
        if pipe0 and pipe1:
            # The engine's overlap counters are cumulative across ALL
            # requests; the before/after delta is what the engine did
            # while this request was in flight (co-resident requests
            # share it — it contextualizes, it does not attribute).
            for k in ('host_overlap_ms', 'bubble_ms'):
                dattrs[k] = round(
                    (pipe1.get(k) or 0.0) - (pipe0.get(k) or 0.0), 3)
            dattrs['dispatch_gap_ms'] = pipe1.get('dispatch_gap_ms')
            dattrs['pipeline_depth'] = pipe1.get('pipeline_depth')
        decode_span = trace_lib.add_span('serve.decode', first_t, last_t,
                                         parent=anchor, **dattrs)
        # Per-chunk children (capped: a 4k-token stream must not mint
        # thousands of spans — the tail aggregates into one).
        prev_t = first_t
        for t, ri, n in events[1:65]:
            trace_lib.add_span('serve.decode.chunk', prev_t, t,
                               parent=decode_span, row=ri, tokens=n)
            prev_t = t
        if len(events) > 65:
            trace_lib.add_span('serve.decode.chunk', prev_t, last_t,
                               parent=decode_span, aggregated=True,
                               tokens=sum(n for _, _, n in events[65:]))

    def _observe_window(self, t_start: float, out, qos_class: str) -> None:
        """Window-batch path: no per-chunk signal exists — the batch is
        one opaque phase (first tokens become visible at completion, so
        TTFT degenerates to the full duration here)."""
        metrics_lib = _metrics()
        now = time.time()
        dur = max(now - t_start, 0.0)
        toks = sum(len(r) for r in out)
        cur = trace_lib.current()
        tid = cur.trace_id if cur is not None else None
        profiler.mark('first_token')  # cold-start ledger: idempotent
        self._ttft_window.append(dur)
        metrics_lib.observe_serving('skytpu_serve_ttft_seconds', dur,
                                    trace_id=tid, qos_class=qos_class)
        metrics_lib.observe_serving('skytpu_serve_phase_seconds', dur,
                                    trace_id=tid, phase='window',
                                    qos_class=qos_class)
        if dur > 0 and toks:
            metrics_lib.observe_serving('skytpu_serve_decode_tok_s',
                                        toks / dur, trace_id=tid,
                                        qos_class=qos_class)
        trace_lib.set_attr(qos_class=qos_class, tokens=toks)
        trace_lib.add_span('serve.window', t_start, now, tokens=toks)

    async def _run_engine(self, rows, max_new: int, temperature: float,
                          top_k: int, top_p: float, eos,
                          qos_class: str = 'standard') -> List[List[int]]:
        """Continuous-engine path shared by the plain and QoS handlers:
        one slot per row, with emission timestamps feeding the latency
        histograms and the request's trace."""
        rec = _ChunkRecorder()
        # Engine stats take the engine lock — only worth it when this
        # request is sampled (the spans are the only consumer of pipe0).
        pipe0 = (self._pipeline_stats()
                 if trace_lib.current() is not None else None)
        futs = [asyncio.wrap_future(rec.submitted(
            i, self.engine.submit(r, max_new, temperature, top_k=top_k,
                                  top_p=top_p, eos=eos,
                                  on_tokens=rec.cb(i))))
                for i, r in enumerate(rows)]
        out = await asyncio.gather(*futs)
        self._observe_serving(rec, qos_class, pipe0)
        return [list(o) for o in out]

    # -- handlers ----------------------------------------------------------

    async def generate(self, request: web.Request) -> web.Response:
        # Draining still ACCEPTS work: the LB keeps routing here until
        # the controller's next probe cycle sees the 503 readiness, and
        # refusing during that lag would drop requests the LB already
        # committed — the exact loss drain exists to prevent. Admission
        # ends naturally once the LB's ready set refreshes.
        self._inflight += 1
        if request.headers.get('X-SkyTPU-Disagg-Fallback'):
            # The LB re-served this request whole after a handoff
            # failure — count it so the fallback rate is observable
            # (skytpu_disagg_fallback_total).
            self.disagg_stats['fallbacks_served'] += 1
        try:
            tctx = trace_lib.start_trace('serve.generate',
                                         headers=request.headers)
            if not tctx:  # untraced: zero further tracing cost
                return await self._generate_inner(request)
            with tctx:
                if request.headers.get(trace_lib.RESUME_HEADER):
                    # The LB is re-serving a died-mid-stream request on
                    # this replica: tag the leg so both legs stitch into
                    # one journey (and retention keeps it as 'resumed').
                    trace_lib.set_attr(resume=True)
                resp = await self._generate_inner(request)
                trace_lib.set_attr(status=resp.status)
            # Replica-side verdict propagation: the retention verdict
            # is final only at root finalize (slow/slow_ttft need the
            # completed duration), which ran at the block's exit —
            # surface it so the LB can keep ITS fragment of the journey
            # without a second round trip. Prepared stream responses
            # already shipped their headers; their verdicts travel via
            # the LB's own judgment of the stream outcome instead.
            verdict = (tctx.record or {}).get('retained')
            if verdict and not getattr(resp, 'prepared', True):
                resp.headers[trace_lib.VERDICT_HEADER] = verdict
            return resp
        finally:
            self._inflight -= 1

    async def _generate_inner(self,
                              request: web.Request) -> web.Response:
        body = await request.json()
        tokens = body.get('tokens')
        if not tokens:
            return web.json_response({'error': 'tokens required'},
                                     status=400)
        try:
            max_new = int(body.get('max_new_tokens', 32))
            temperature = float(body.get('temperature', 0.0))
            top_k = int(body.get('top_k', 0))
            top_p = float(body.get('top_p', 1.0))
        except (TypeError, ValueError):
            return web.json_response(
                {'error': 'max_new_tokens/temperature/top_k/top_p must '
                          'be numeric'}, status=400)
        if max_new < 1:
            return web.json_response(
                {'error': 'max_new_tokens must be >= 1'}, status=400)
        if top_k < 0 or not 0.0 < top_p <= 1.0:
            return web.json_response(
                {'error': 'top_k must be >= 0 and top_p in (0, 1]'},
                status=400)
        eos = body.get('eos_token')
        if eos is not None:
            def _id(x):
                # JSON true/false pass isinstance(x, int) — a silent
                # stop-id 0/1 instead of a 400.
                if isinstance(x, bool):
                    raise ValueError(x)
                return int(x)
            try:
                eos = frozenset([_id(eos)] if isinstance(eos, int)
                                else (_id(t) for t in eos))
            except (TypeError, ValueError):
                return web.json_response(
                    {'error': 'eos_token must be an int or list of '
                              'ints'}, status=400)
        try:
            if isinstance(tokens[0], int):
                tokens = [tokens]
            rows = [[int(t) for t in row] for row in tokens]
        except (TypeError, ValueError, KeyError, IndexError):
            return web.json_response(
                {'error': 'tokens must be rows of ints'}, status=400)
        if not all(rows):
            return web.json_response(
                {'error': 'empty token rows not allowed'}, status=400)
        longest = max(len(r) for r in rows)
        if longest + max_new > self.max_len:
            return web.json_response(
                {'error': f'prompt+max_new_tokens exceeds max_len '
                          f'{self.max_len}'}, status=400)
        seed = body.get('seed')
        seeded = temperature > 0 and seed is not None
        if seeded and self.world > 1:
            # The seeded window path is head-local; a head-only forward
            # over globally sharded weights would deadlock the other
            # ranks' collectives (serve/spmd.py caveats).
            return web.json_response(
                {'error': 'seeded sampling is not available on a '
                          'multi-host replica'}, status=400)
        stream = bool(body.get('stream'))
        if stream and (self.engine is None or seeded):
            return web.json_response(
                {'error': 'stream requires the continuous engine '
                          '(unseeded requests, SKYTPU_LLM_ENGINE!=off)'},
                status=400)
        trace_lib.set_attr(rows=len(rows), max_new=max_new, stream=stream)
        if self.qos is not None:
            return await self._generate_qos(request, body, rows, max_new,
                                            temperature, seed, top_k,
                                            top_p, eos, seeded, stream)
        # Histogram/trace label only: admission (QoS on) uses its own
        # classify with a 400 on unknown values; with QoS off the
        # priority field is advisory and must never reject.
        try:
            qos_class = qos_lib.classify(body, request.headers)
        except ValueError:
            qos_class = 'standard'
        if stream:
            return await self._generate_stream(request, rows, max_new,
                                               temperature, top_k, top_p,
                                               eos, qos_class=qos_class)
        if self.engine is not None and not seeded:
            # Continuous-batching path: one engine slot per row.
            out = await self._run_engine(rows, max_new, temperature,
                                         top_k, top_p, eos,
                                         qos_class=qos_class)
            return web.json_response({'tokens': out})
        pending = _Pending(rows, max_new, temperature, seed,
                           top_k=top_k, top_p=top_p, eos=eos)
        self._ensure_worker()
        t_queued = time.time()
        await self._queue.put(pending)
        out = await pending.future
        self._observe_window(t_queued, out, qos_class)
        return web.json_response({'tokens': out})

    # -- QoS-gated dispatch (serve/qos.py; SKYTPU_QOS=1 / --qos on) --------

    def _dispatch_window(self, pending: _Pending) -> None:
        """Dispatch grant for a window-path request: only now does it
        enter the batching FIFO — until the grant, waiting (and TTL
        expiry, and shed victimhood) happens in the weighted-fair
        queue, which replaces the old unbounded FIFO as the place
        requests queue."""
        self._ensure_worker()
        self._queue.put_nowait(pending)

    @staticmethod
    def _shed_response(e: qos_lib.ShedError,
                       qos_class: str) -> web.Response:
        return web.json_response(
            {'error': str(e), 'qos_class': qos_class, 'shed': True},
            status=429, headers={'Retry-After': str(e.retry_after_s)})

    async def _generate_qos(self, request: web.Request, body, rows,
                            max_new: int, temperature: float, seed,
                            top_k: int, top_p: float, eos,
                            seeded: bool, stream: bool) -> web.Response:
        """The QoS-enabled request path: classify -> admit (quota +
        overload) -> wait for the weighted-fair dispatch grant -> run
        on the normal engine/window path -> release. Output for any
        admitted request is identical to the ungated path; QoS only
        changes WHEN work starts and which requests are refused."""
        try:
            qos_class = qos_lib.classify(body, request.headers)
        except ValueError as e:
            return web.json_response({'error': str(e)}, status=400)
        if request.headers.get('Authorization', '').startswith('Bearer '):
            # Token resolution can hit the users sqlite DB (cold cache;
            # 10 s lock timeout) — never block the serving event loop
            # on it, or every in-flight stream on the replica stalls.
            tenant = await asyncio.get_event_loop().run_in_executor(
                None, qos_lib.resolve_tenant, request.headers, body)
        else:  # header/field/anonymous: pure dict reads
            tenant = qos_lib.resolve_tenant(request.headers, body)
        use_window = self.engine is None or seeded
        pending = None
        on_dispatch = None
        if use_window and not stream:
            pending = _Pending(rows, max_new, temperature, seed,
                               top_k=top_k, top_p=top_p, eos=eos)
            on_dispatch = (lambda p=pending: self._dispatch_window(p))
        trace_lib.set_attr(qos_class=qos_class, tenant=tenant)
        t_submit = time.time()
        try:
            ticket = self.qos.submit(
                qos_class, tenant, cost=float(len(rows)),
                est_tokens=float(len(rows) * max_new),
                on_dispatch=on_dispatch)
        except qos_lib.ShedError as e:
            return self._shed_response(e, qos_class)
        try:
            await ticket.granted
        except qos_lib.ShedError as e:
            return self._shed_response(e, qos_class)
        except qos_lib.QueueTimeout as e:
            return web.json_response(
                {'error': str(e), 'qos_class': qos_class}, status=504)
        except asyncio.CancelledError:
            self.qos.abandon(ticket)  # client disconnected while queued
            raise
        t_granted = time.time()
        cur = trace_lib.current()
        _metrics().observe_serving(
            'skytpu_serve_queue_wait_seconds',
            max(t_granted - t_submit, 0.0),
            trace_id=cur.trace_id if cur is not None else None,
            qos_class=qos_class)
        trace_lib.add_span('qos.queue_wait', t_submit, t_granted,
                           tenant=tenant)
        # generated drives the quota refund at release: the actual
        # count on success (unused ask refunded), 0 on server-side
        # failure (full refund — the work was not done), None on client
        # disconnect (full CHARGE — the engine completes the work
        # anyway, and disconnects must not become a quota bypass).
        generated: Optional[int] = 0
        try:
            if stream:
                # Streamed tokens are counted as emitted, so completion
                # still refunds the unused ask and feeds the throughput
                # estimator exactly like the buffered path.
                counter = [0]
                resp = await self._generate_stream(
                    request, rows, max_new, temperature, top_k, top_p,
                    eos, token_count=counter, qos_class=qos_class)
                generated = counter[0]
                return resp
            if pending is None:  # continuous engine
                out = await self._run_engine(rows, max_new, temperature,
                                             top_k, top_p, eos,
                                             qos_class=qos_class)
            else:
                out = await pending.future
                self._observe_window(t_granted, out, qos_class)
            generated = sum(len(o) for o in out)
            return web.json_response({'tokens': out})
        except asyncio.CancelledError:
            generated = None
            raise
        finally:
            self.qos.release(ticket, generated_tokens=generated)

    async def _generate_stream(self, request: web.Request,
                               rows, max_new: int, temperature: float,
                               top_k: int = 0, top_p: float = 1.0,
                               eos=None,
                               token_count: Optional[List[int]] = None,
                               qos_class: str = 'standard'
                               ) -> web.StreamResponse:
        """NDJSON streaming (the JetStream-style serving contract):
        tokens are written as the engine emits them, one
        ``{"row": i, "tokens": [...]}`` object per line, at decode-chunk
        granularity (``SKYTPU_LLM_CHUNK_STEPS`` trades stream latency
        against dispatch amortization); terminated by ``{"done": true}``."""
        import json as json_lib

        loop = asyncio.get_event_loop()
        q: asyncio.Queue = asyncio.Queue()
        rec = _ChunkRecorder()
        pipe0 = (self._pipeline_stats()
                 if trace_lib.current() is not None else None)
        futs = []
        for ri, row in enumerate(rows):
            def cb(toks, ri=ri):
                # Timestamp on the engine thread (true emission time,
                # not loop-drain time), then hand off to the writer.
                rec.events.append((time.time(), ri, len(toks)))
                loop.call_soon_threadsafe(q.put_nowait, (ri, toks))
            futs.append(asyncio.wrap_future(rec.submitted(
                ri, self.engine.submit(row, max_new, temperature,
                                       on_tokens=cb, top_k=top_k,
                                       top_p=top_p, eos=eos))))
        resp = web.StreamResponse()
        resp.content_type = 'application/x-ndjson'
        await resp.prepare(request)
        remaining = {i: max_new for i in range(len(rows))}
        done_task = asyncio.ensure_future(asyncio.gather(*futs))

        async def _emit(item):
            ri, toks = item
            if token_count is not None:  # QoS quota/throughput feed
                token_count[0] += len(toks)
            remaining[ri] -= len(toks)
            if remaining[ri] <= 0:
                del remaining[ri]
            await resp.write(json_lib.dumps(
                {'row': ri, 'tokens': toks}).encode() + b'\n')

        get_task = None
        try:
            while remaining:
                get_task = asyncio.ensure_future(q.get())
                await asyncio.wait({get_task, done_task},
                                   return_when=asyncio.FIRST_COMPLETED)
                if get_task.done():
                    task, get_task = get_task, None
                    await _emit(task.result())
                    continue
                get_task.cancel()
                get_task = None
                # Futures resolved first: either the engine failed (no
                # more callbacks will ever come — raise instead of
                # waiting forever) or every request completed. Engine
                # emissions are scheduled (call_soon_threadsafe, FIFO)
                # BEFORE future resolution, so on success everything is
                # already in the queue — drain it and stop; `remaining`
                # may legitimately stay nonzero when stop tokens ended
                # rows before max_new.
                done_task.result()
                while not q.empty():
                    await _emit(q.get_nowait())
                break
            await done_task
            await resp.write(json_lib.dumps({'done': True}).encode()
                             + b'\n')
        except Exception as e:  # noqa: BLE001 — mid-stream: report in-band
            # The failure may BE the transport (client disconnected):
            # the in-band error line is best-effort.
            with contextlib.suppress(Exception):
                await resp.write(json_lib.dumps(
                    {'error': str(e)}).encode() + b'\n')
        finally:
            # Runs on CancelledError too (aiohttp cancels the handler
            # when the client disconnects): the gather and any in-flight
            # queue get must not outlive the response as orphans whose
            # eventual exception is never retrieved.
            if get_task is not None:
                get_task.cancel()
            if not done_task.done():
                done_task.cancel()
            done_task.add_done_callback(
                lambda t: None if t.cancelled() else t.exception())
            with contextlib.suppress(Exception):
                await resp.write_eof()
            # The stream span runs submit -> eof ("stream-complete" in
            # the trace); prefill/decode nest inside it — it must open
            # at submit, since the first chunk can emit while prepare()
            # is still in flight.
            stream_span = trace_lib.add_span('serve.stream', rec.t0,
                                             time.time())
            self._observe_serving(rec, qos_class, pipe0,
                                  parent=stream_span)
        return resp

    # -- KV handoff endpoints (disaggregated serving, serve/disagg.py) -----

    def _parse_handoff_request(self, body):
        """Shared request validation for /v1/kv/export: one row + the
        generation ask that will ride the handoff. Returns (row,
        max_new, temperature, top_k, top_p, eos) or raises ValueError
        with a client-facing message."""
        tokens = body.get('tokens')
        if not tokens:
            raise ValueError('tokens required')
        if tokens and isinstance(tokens[0], list):
            if len(tokens) != 1:
                raise ValueError('KV handoff carries ONE prompt per '
                                 'request (the handoff unit is a row)')
            tokens = tokens[0]
        row = [int(t) for t in tokens]
        if not row:
            raise ValueError('empty token rows not allowed')
        max_new = int(body.get('max_new_tokens', 32))
        if max_new < 1:
            raise ValueError('max_new_tokens must be >= 1')
        temperature = float(body.get('temperature', 0.0))
        top_k = int(body.get('top_k', 0))
        top_p = float(body.get('top_p', 1.0))
        if top_k < 0 or not 0.0 < top_p <= 1.0:
            raise ValueError('top_k must be >= 0 and top_p in (0, 1]')
        eos = body.get('eos_token')
        if eos is not None:
            eos = frozenset([int(eos)] if isinstance(eos, int)
                            else (int(t) for t in eos))
        if len(row) + max_new > self.max_len:
            raise ValueError(f'prompt+max_new_tokens exceeds max_len '
                             f'{self.max_len}')
        return row, max_new, temperature, top_k, top_p, eos

    async def kv_export(self, request: web.Request) -> web.Response:
        """Prefill-role admission over HTTP: compute the prompt's KV,
        sample the first token, and PARK the handoff — the response
        carries the negotiation header (sizes, shareable chain) and a
        claim id for /v1/kv/fetch, or a staging ref when the same-host
        fast path is configured (payload already durable in the shared
        dir, zero bytes over HTTP)."""
        if self.engine is None:
            return web.json_response(
                {'error': 'KV export requires the continuous engine'},
                status=400)
        self._inflight += 1
        tctx = trace_lib.start_trace('serve.kv_export',
                                     headers=request.headers)
        try:
            with tctx if tctx else contextlib.nullcontext():
                return await self._kv_export_inner(request)
        finally:
            self._inflight -= 1

    async def _kv_export_inner(self,
                               request: web.Request) -> web.Response:
        disagg_lib = self._disagg_lib
        try:
            body = await request.json()
            row, max_new, temperature, top_k, top_p, eos = \
                self._parse_handoff_request(body)
        except (ValueError, TypeError) as e:
            return web.json_response({'error': str(e)}, status=400)
        # QoS admission gates the EXPORT — on a disaggregated fleet the
        # queue forms here, and skipping the gate would turn every
        # handoff into a per-tenant quota bypass. The full generation
        # budget is charged on this side (the decode pool does the
        # emitting but never re-meters); early EOS overcharges, which
        # is the conservative direction for a quota.
        ticket = None
        if self.qos is not None:
            try:
                qos_class = qos_lib.classify(body, request.headers)
            except ValueError as e:
                return web.json_response({'error': str(e)}, status=400)
            if request.headers.get('Authorization',
                                   '').startswith('Bearer '):
                tenant = await asyncio.get_event_loop().run_in_executor(
                    None, qos_lib.resolve_tenant, request.headers, body)
            else:
                tenant = qos_lib.resolve_tenant(request.headers, body)
            try:
                ticket = self.qos.submit(
                    qos_class, tenant, cost=float(len(row)),
                    est_tokens=float(len(row) * max_new))
            except qos_lib.ShedError as e:
                return self._shed_response(e, qos_class)
            try:
                await ticket.granted
            except qos_lib.ShedError as e:
                return self._shed_response(e, qos_class)
            except qos_lib.QueueTimeout as e:
                return web.json_response(
                    {'error': str(e), 'qos_class': qos_class},
                    status=504)
            except asyncio.CancelledError:
                self.qos.abandon(ticket)  # client gone while queued
                raise
        try:
            resp = await self._kv_export_admitted(
                disagg_lib, row, max_new, temperature, top_k, top_p,
                eos)
        except BaseException:  # incl. client-disconnect cancellation
            if ticket is not None:
                self.qos.abandon(ticket)  # no in-flight slot leaks
            raise
        if ticket is not None:
            # Success charges the full budget; any refusal refunds it
            # whole — the work was not done.
            self.qos.release(ticket, generated_tokens=(
                max_new if resp.status == 200 else 0))
        return resp

    async def _kv_export_admitted(self, disagg_lib, row, max_new,
                                  temperature, top_k, top_p,
                                  eos) -> web.Response:
        t0 = time.time()
        try:
            fut = self.engine.submit_prefill(
                row, max_new, temperature, top_k=top_k, top_p=top_p,
                eos=eos)
        except ValueError as e:  # MoE/spec/footprint refusals
            return web.json_response({'error': str(e)}, status=400)
        try:
            handoff = await asyncio.wrap_future(fut)
        except Exception as e:  # noqa: BLE001 — engine-side failure
            return web.json_response(
                {'error': f'prefill export failed: {e}'}, status=500)
        header = await _run_sized(
            _handoff_nbytes(handoff), disagg_lib.build_header, handoff,
            model=self.model_name, kv_cache=self.kv_cache)
        nbytes = disagg_lib.payload_nbytes(header)
        resp = {'layout': 'paged', 'nbytes': nbytes,
                'prompt_len': handoff.prompt_len,
                'full_blocks': handoff.full_blocks,
                'block': handoff.block}
        if self.staging_dir:
            # Same-host fast path: payload written once into the shared
            # dir; the decode replica reads it directly (off-loop: the
            # fsync'd write must not stall in-flight streams).
            ref, nbytes = await asyncio.get_event_loop().run_in_executor(
                None, disagg_lib.write_staging, self.staging_dir,
                handoff, header)
            resp['staging_ref'] = ref
            resp['nbytes'] = nbytes
        else:
            resp['handoff'] = self._handoffs.put(handoff)
        dt = time.time() - t0
        st = self.disagg_stats
        st['exports'] += 1
        st['export_bytes'] += nbytes
        st['export_seconds'] += dt
        trace_lib.add_span('serve.prefill', t0, time.time(),
                           tokens=len(row))
        trace_lib.set_attr(nbytes=nbytes, prompt_len=len(row),
                           staged=bool(self.staging_dir))
        return web.json_response(resp)

    async def kv_fetch(self, request: web.Request) -> web.Response:
        """Claim a parked export's bytes. ``?skip_blocks=N`` (from the
        decode side's /v1/kv/prepare answer) drops the first N full
        blocks' plane records — they transfer as trie references.
        One-shot: the handoff is consumed whether serialization
        succeeds or not (the LB retries by re-exporting)."""
        hid = request.query.get('handoff', '')
        handoff = self._handoffs.pop(hid)
        if handoff is None:
            return web.json_response(
                {'error': f'unknown or expired handoff {hid!r}'},
                status=404)
        try:
            skip = int(request.query.get('skip_blocks', 0))
            header = await _run_sized(
                _handoff_nbytes(handoff), self._disagg_lib.build_header,
                handoff, model=self.model_name, kv_cache=self.kv_cache,
                skip_blocks=skip)
        except ValueError as e:
            return web.json_response({'error': str(e)}, status=400)
        payload = await _run_sized(
            _handoff_nbytes(handoff), self._disagg_lib.serialize_bytes,
            handoff, header)
        return web.Response(body=payload,
                            content_type='application/octet-stream')

    async def kv_prepare(self, request: web.Request) -> web.Response:
        """Handoff negotiation: how many leading FULL prompt blocks this
        replica already holds in its share trie — the prefix the
        transfer can skip."""
        if self.engine is None or not hasattr(self.engine, 'probe_chain'):
            return web.json_response({'skip_blocks': 0})
        try:
            body = await request.json()
            tokens = body.get('tokens') or []
            if tokens and isinstance(tokens[0], list):
                tokens = tokens[0]
            row = [int(t) for t in tokens]
        except (ValueError, TypeError):
            return web.json_response({'error': 'tokens must be ints'},
                                     status=400)
        return web.json_response(
            {'skip_blocks': self.engine.probe_chain(row)})

    async def kv_chains(self, request: web.Request) -> web.Response:
        """Resolve affinity-advert chain digests back to the token rows
        this replica's trie still holds (engine.resolve_chains) — the
        remediation pre-warm handshake: the controller reads the
        victim's last advert (hex digests only), asks the victim for
        the concrete prompts here, then replays them victim→successor
        through the ordinary export/fetch/import path."""
        if self.engine is None \
                or not hasattr(self.engine, 'resolve_chains'):
            return web.json_response({'chains': []})
        try:
            body = await request.json()
            digests = [bytes.fromhex(str(h))
                       for h in (body.get('digests') or [])]
        except (ValueError, TypeError):
            return web.json_response(
                {'error': 'digests must be hex strings'}, status=400)
        rows = self.engine.resolve_chains(digests)
        return web.json_response({'chains': rows})

    async def kv_import(self, request: web.Request) -> web.Response:
        """Decode-role admission over HTTP: validate the payload
        (checksums first — corrupt bytes never reach the device),
        install it, and serve the generation. Buffered by default;
        ``?stream=1`` streams NDJSON exactly like /generate. Error
        contract the LB's fallback depends on: 400 = unusable bytes,
        409 = well-formed but not installable here, both mean
        're-serve colocated'."""
        if self.engine is None \
                or not hasattr(self.engine, 'submit_import'):
            return web.json_response(
                {'error': 'KV import requires the continuous engine'},
                status=400)
        self._inflight += 1
        tctx = trace_lib.start_trace('serve.kv_import',
                                     headers=request.headers)
        try:
            with tctx if tctx else contextlib.nullcontext():
                return await self._kv_import_inner(request)
        finally:
            self._inflight -= 1

    async def _kv_import_inner(self,
                               request: web.Request) -> web.Response:
        disagg_lib = self._disagg_lib
        t0 = time.time()
        try:
            if request.content_type == 'application/json':
                # Same-host fast path: the body is a staging REF, the
                # bytes are read from the shared dir.
                body = await request.json()
                data = await asyncio.get_event_loop().run_in_executor(
                    None, disagg_lib.read_staging, self.staging_dir,
                    str(body.get('staging_ref') or ''))
            else:
                data = await request.read()
            header, arrays = await _run_sized(
                len(data), disagg_lib.parse, data)
            disagg_lib.check_compat(
                header, model=self.model_name, kv_cache=self.kv_cache,
                kv_block=getattr(self.engine, 'kv_block', 0),
                max_len=self.max_len)
            # Inside the try: a header whose JSON parses but whose
            # request-state fields are missing/garbage (crc32 covers
            # plane bytes only) must 400, not 500.
            kwargs = disagg_lib.import_kwargs(header, arrays)
        except disagg_lib.DisaggCompatError as e:
            self.disagg_stats['import_rejects'] += 1
            return web.json_response({'error': str(e)}, status=409)
        except (disagg_lib.DisaggError, ValueError, TypeError,
                KeyError) as e:
            self.disagg_stats['import_rejects'] += 1
            return web.json_response({'error': str(e)}, status=400)
        stream = request.query.get('stream') in ('1', 'true')
        rec = _ChunkRecorder()
        try:
            if stream:
                return await self._kv_import_stream(request, kwargs,
                                                    data, rec, t0)
            fut = rec.submitted(0, self.engine.submit_import(
                on_tokens=rec.cb(0), **kwargs))
            tokens = await asyncio.wrap_future(fut)
        except ValueError as e:
            self.disagg_stats['import_rejects'] += 1
            return web.json_response({'error': str(e)}, status=400)
        except Exception as e:  # noqa: BLE001 — install failure: 409 so
            # the LB re-serves colocated (KVImportError's contract).
            self.disagg_stats['import_rejects'] += 1
            return web.json_response(
                {'error': f'import install failed: {e}'}, status=409)
        self._note_import(len(data), t0, rec)
        return web.json_response({'tokens': [list(tokens)]})

    def _note_import(self, nbytes: int, t0: float,
                     rec: _ChunkRecorder) -> None:
        st = self.disagg_stats
        st['imports'] += 1
        st['import_bytes'] += nbytes
        st['import_seconds'] += time.time() - t0
        self._observe_serving(rec, 'standard', None)

    async def _kv_import_stream(self, request: web.Request, kwargs,
                                data: bytes, rec: _ChunkRecorder,
                                t0: float) -> web.StreamResponse:
        """NDJSON streaming for an imported request — same wire shape
        as /generate?stream, so the LB pipes it straight through to the
        client."""
        import json as json_lib
        loop = asyncio.get_event_loop()
        q: asyncio.Queue = asyncio.Queue()

        def cb(toks):
            rec.events.append((time.time(), 0, len(toks)))
            loop.call_soon_threadsafe(q.put_nowait, toks)

        fut = asyncio.wrap_future(rec.submitted(
            0, self.engine.submit_import(on_tokens=cb, **kwargs)))
        # The first failure mode (evicted negotiated blocks) surfaces at
        # admission — wait for either the first emission or the future,
        # so a doomed import still gets its 409 instead of a broken
        # stream.
        first_get = asyncio.ensure_future(q.get())
        await asyncio.wait({first_get, fut},
                           return_when=asyncio.FIRST_COMPLETED)
        if fut.done() and not first_get.done():
            first_get.cancel()
            try:
                fut.result()
            except Exception as e:  # noqa: BLE001
                self.disagg_stats['import_rejects'] += 1
                return web.json_response(
                    {'error': f'import install failed: {e}'}, status=409)
        resp = web.StreamResponse()
        resp.content_type = 'application/x-ndjson'
        await resp.prepare(request)
        try:
            if first_get.done():
                await resp.write(json_lib.dumps(
                    {'row': 0, 'tokens': first_get.result()}).encode()
                    + b'\n')
            else:
                first_get.cancel()
            while not fut.done() or not q.empty():
                if fut.done() and q.empty():
                    break
                get_task = asyncio.ensure_future(q.get())
                await asyncio.wait({get_task, fut},
                                   return_when=asyncio.FIRST_COMPLETED)
                if get_task.done():
                    await resp.write(json_lib.dumps(
                        {'row': 0, 'tokens': get_task.result()}).encode()
                        + b'\n')
                else:
                    get_task.cancel()
            await fut
            await resp.write(json_lib.dumps({'done': True}).encode()
                             + b'\n')
            self._note_import(len(data), t0, rec)
        except Exception as e:  # noqa: BLE001 — mid-stream: in-band
            with contextlib.suppress(Exception):
                await resp.write(json_lib.dumps(
                    {'error': str(e)}).encode() + b'\n')
        finally:
            if not fut.done():
                fut.cancel()
            with contextlib.suppress(Exception):
                await resp.write_eof()
        return resp

    @staticmethod
    def _scrape_authorized(request: web.Request) -> bool:
        """Replica /metrics + /debug/traces honor the same optional
        scrape token as the API server (SKYTPU_METRICS_TOKEN, one
        shared implementation in users/): unset = open (single-operator
        default; the LB additionally refuses to proxy /debug/*), set =
        require the bearer — the knob for multi-tenant deployments
        where trace attrs name tenants."""
        from skypilot_tpu import users as users_lib
        return users_lib.metrics_scrape_allowed(request.headers)

    async def metrics(self, request: web.Request) -> web.Response:
        """Native Prometheus scrape: replicas are scrapeable directly
        (latency histograms + engine/queue gauges) instead of only via
        controller probes of /health."""
        if not self._scrape_authorized(request):
            return web.json_response({'error': 'unauthorized'},
                                     status=401)
        try:
            engine = (self.engine.stats()
                      if self.engine is not None else None)
            qos_stats = self.qos.stats() if self.qos is not None else None
        except Exception:  # noqa: BLE001 — a stopping engine must not
            engine, qos_stats = None, None  # fail the whole scrape
        # Content negotiation: an OpenMetrics-speaking scraper gets the
        # exposition that carries histogram exemplars (trace ids on the
        # bucket lines — the metric→retained-trace jump).
        metrics_lib = _metrics()
        openmetrics = ('openmetrics-text'
                       in request.headers.get('Accept', '')
                       and getattr(metrics_lib, 'openmetrics_available',
                                   lambda: False)())
        body = metrics_lib.render_serving(engine=engine, qos=qos_stats,
                                          disagg=self.disagg_stats,
                                          openmetrics=openmetrics)
        if openmetrics:
            return web.Response(
                body=body,
                headers={'Content-Type':
                         metrics_lib.OPENMETRICS_CONTENT_TYPE})
        return web.Response(body=body, content_type='text/plain',
                            charset='utf-8')

    async def debug_traces(self, request: web.Request) -> web.Response:
        """Recent + slowest completed traces (?slowest=1, ?trace_id=,
        ?qos_class=, ?tenant=, ?limit=). Off-loop: the export-spool read
        must never stall in-flight token streams."""
        if not self._scrape_authorized(request):
            return web.json_response({'error': 'unauthorized'},
                                     status=401)
        payload = await asyncio.get_event_loop().run_in_executor(
            None, trace_lib.debug_payload, dict(request.query))
        return web.json_response(payload)

    async def debug_blackbox(self, request: web.Request) -> web.Response:
        """Incident-bundle spool: ``?dump=1`` freezes this replica's
        event ring into a bundle NOW (and inlines it), ``?file=``
        fetches one, plain GET lists. Same scrape-token gate as
        /metrics (bundles carry engine state and trace attrs); the LB
        refuses to proxy /debug/*, so operators hit replicas directly.
        Off-loop: dumping reads engine stats and writes a file."""
        if not self._scrape_authorized(request):
            return web.json_response({'error': 'unauthorized'},
                                     status=401)
        from skypilot_tpu.observability import blackbox
        payload = await asyncio.get_event_loop().run_in_executor(
            None, blackbox.debug_payload, dict(request.query))
        return web.json_response(payload)

    async def debug_profile(self, request: web.Request) -> web.Response:
        """Runtime-profiler state (observability/profiler.py): compile
        ledger, device-memory accounting, cold-start phases.
        ``?programs=1`` appends the PROGRAMS catalog, ``?mem=1`` forces
        a fresh memory sample, ``?device_trace=<seconds>`` first takes
        a ``jax.profiler`` trace of this replica (the engine's spans
        beside the device's operations; docs/operations.md) and says
        where it is. Same scrape-token gate as /metrics; off-loop — a
        forced memory sample queries every device allocator, a trace
        lasts its seconds."""
        if not self._scrape_authorized(request):
            return web.json_response({'error': 'unauthorized'},
                                     status=401)
        loop = asyncio.get_event_loop()
        traced = None
        if request.query.get('device_trace'):
            traced = await loop.run_in_executor(
                None, profiler.device_trace,
                request.query['device_trace'])
        payload = await loop.run_in_executor(
            None, profiler.debug_payload, dict(request.query))
        if traced is not None:
            payload['device_trace'] = traced
        return web.json_response(payload)

    async def debug_exemplars(self, request: web.Request) -> web.Response:
        """The in-process metric exemplar store (server/metrics.py):
        newest trace id per histogram bucket — the jump from a tail
        latency bucket to a retained trace (?metric= filters one
        family). Same scrape-token gate as /metrics."""
        if not self._scrape_authorized(request):
            return web.json_response({'error': 'unauthorized'},
                                     status=401)
        return web.json_response(
            _metrics().exemplars_payload(dict(request.query)))

    async def debug_alerts(self, request: web.Request) -> web.Response:
        """SLO alert state visible from THIS process (observability/
        slo.py): the evaluator runs on the API server, so a replica
        normally reports enabled/empty — the endpoint exists on both
        servers so operators (and loadgen) can ask either side with the
        same path. Same scrape-token gate as /metrics."""
        if not self._scrape_authorized(request):
            return web.json_response({'error': 'unauthorized'},
                                     status=401)
        from skypilot_tpu.observability import slo
        query = {'history': '1', **dict(request.query)}
        payload = await asyncio.get_event_loop().run_in_executor(
            None, slo.alerts_payload, query)
        return web.json_response(payload)

    def make_app(self) -> web.Application:
        app = web.Application()
        app.router.add_get('/health', self.health)
        app.router.add_get('/metrics', self.metrics)
        app.router.add_get('/debug/traces', self.debug_traces)
        app.router.add_get('/debug/blackbox', self.debug_blackbox)
        app.router.add_get('/debug/profile', self.debug_profile)
        app.router.add_get('/debug/exemplars', self.debug_exemplars)
        app.router.add_get('/debug/alerts', self.debug_alerts)
        app.router.add_post('/generate', self.generate)
        # KV handoff (disaggregated prefill/decode, serve/disagg.py).
        app.router.add_post('/v1/kv/export', self.kv_export)
        app.router.add_get('/v1/kv/fetch', self.kv_fetch)
        app.router.add_post('/v1/kv/prepare', self.kv_prepare)
        app.router.add_post('/v1/kv/chains', self.kv_chains)
        app.router.add_post('/v1/kv/import', self.kv_import)
        return app


def build_parser() -> argparse.ArgumentParser:
    """The replica's full flag set — shared with serve/spmd.py's
    follower ranks, which must construct an IDENTICAL server (every
    serving knob changes the compiled programs all ranks must agree
    on)."""
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='tiny')
    parser.add_argument('--max-len', type=int, default=1024)
    parser.add_argument('--port', type=int,
                        default=int(os.environ.get('SKYTPU_REPLICA_PORT',
                                                   '8080')))
    parser.add_argument('--host', default='0.0.0.0')
    parser.add_argument('--quantize', default=None,
                        help="'int8' = weight-only quantized decode "
                             '(also via SKYTPU_LLM_QUANTIZE)')
    parser.add_argument('--engine', default=None,
                        help="'continuous' (default: JetStream-style slot "
                             "server) or 'off' (window batching only; "
                             'also via SKYTPU_LLM_ENGINE)')
    parser.add_argument('--tp', type=int, default=None,
                        help='tensor-parallel degree: shard weights/KV '
                             'over the first N local devices (also via '
                             'SKYTPU_LLM_TP)')
    parser.add_argument('--kv-cache', default=None,
                        choices=('bf16', 'int8'),
                        help='int8 = quantized KV cache, halves the '
                             'decode HBM stream (also via '
                             'SKYTPU_LLM_KV_CACHE)')
    parser.add_argument('--kv-blocks', type=int, default=None,
                        help='KV pool size in blocks incl. the junk '
                             'sink (also via SKYTPU_LLM_KV_BLOCKS; '
                             'default = full capacity — size it BELOW '
                             'slots*max_len/block for the HBM saving; '
                             'exhaustion queues admissions)')
    parser.add_argument('--prefix-share', default=None,
                        choices=('on', 'off'),
                        help='copy-on-write block-level prefix sharing '
                             'on the KV pool: committed prompt blocks '
                             'are refcount-shared via a trie, so a hit '
                             'is a table write and only the unshared '
                             'tail prefills (default on; also via '
                             'SKYTPU_LLM_PREFIX_SHARE; dense models '
                             'only, and not with --draft-model)')
    parser.add_argument('--draft-model', default=None,
                        help='preset name of a small draft model for '
                             'speculative decoding (rides inside the '
                             'continuous engine, or the window path '
                             "with --engine off; dense targets only; "
                             'also via SKYTPU_LLM_DRAFT)')
    parser.add_argument('--pipeline', default=None,
                        choices=('on', 'off'),
                        help='pipelined decode dispatch: keep one chunk '
                             'in flight so host bookkeeping overlaps '
                             'device compute (default on; off = serial '
                             'engine; also via SKYTPU_LLM_PIPELINE)')
    parser.add_argument('--role', default=None,
                        choices=('colocated', 'prefill', 'decode'),
                        help='disaggregated-serving pool role (also via '
                             'SKYTPU_LLM_ROLE): prefill replicas retire '
                             'prompts at the first token and export the '
                             'KV (/v1/kv/export), decode replicas '
                             'import it and stream (/v1/kv/import); '
                             'every role still serves /generate for '
                             'the colocated fallback (default '
                             'colocated)')
    parser.add_argument('--qos', default=None, choices=('on', 'off'),
                        help='QoS admission control: priority classes '
                             '(interactive/standard/batch), per-tenant '
                             'token-bucket quotas, and overload '
                             'shedding with 429+Retry-After (default '
                             'off; also via SKYTPU_QOS; knobs: '
                             'SKYTPU_QOS_WEIGHTS/_MAX_QUEUE/_TTL_S/'
                             '_TENANT_RPS/_TENANT_TPS/_TENANT_LIMITS/'
                             '_MAX_INFLIGHT)')
    return parser


def server_from_args(args) -> 'LlmServer':
    return LlmServer(args.model, max_len=args.max_len,
                     quantize=args.quantize, engine=args.engine,
                     tp=args.tp, kv_cache=args.kv_cache,
                     draft_model=args.draft_model,
                     kv_blocks=args.kv_blocks,
                     pipeline=args.pipeline,
                     qos=args.qos,
                     prefix_share=args.prefix_share,
                     role=args.role)


def main() -> None:
    # Cold-start ledger: python + package imports are done; what
    # follows is backend init (sub-phases marked inside
    # jax_env.init_backend), weight init, and engine construction.
    profiler.mark('imports')
    parser = build_parser()
    args = parser.parse_args()
    # SIGQUIT interrogation BEFORE backend init: a replica hung inside
    # PJRT construction is exactly the process an operator most needs
    # to `kill -QUIT` — registering only at app startup would leave
    # the hung-in-init case with SIGQUIT's default kill disposition.
    from skypilot_tpu.observability import blackbox
    blackbox.set_process_label(
        f'llm_server:{args.role or os.environ.get("SKYTPU_LLM_ROLE") or "colocated"}')
    blackbox.install_sigquit()
    # Backend init AFTER argparse, so --help/usage never touches the
    # chip: the persistent compile cache is placed before the first
    # lowering (a replacement replica deserializes its predecessor's
    # programs instead of recompiling them), an un-asked-for CPU is
    # refused, and the device line says where this replica runs.
    jax_env.init_backend()
    server = server_from_args(args)
    # AOT warm-up before traffic (serve/warmup.py): runs in the dark
    # window — the listener is not bound yet, so the controller's
    # readiness probes CANNOT flip READY until the compile ledger
    # confirmed steady-state coverage. Opt-in (SKYTPU_WARMUP=1);
    # head-local, so multi-host replicas skip it (the lockstep loop
    # owns the follower ranks' dispatch order).
    if warmup_lib.enabled():
        if server.world > 1:
            server.warmup_report = warmup_lib.skipped(
                'multi-host replica (warm-up is head-local)')
        else:
            server._warming = True
            try:
                server.warmup_report = warmup_lib.run(server)
            finally:
                server._warming = False
    if server.world > 1:
        # Multi-host: the head's lockstep loop must run from startup —
        # follower ranks are already blocked in the arrival collective,
        # and a drain signal arriving before the first request must
        # still reach them via the stop broadcast (serve/spmd.py).
        server.engine.start()
    app = server.make_app()

    async def _install_drain(app_):
        # GRACEFUL DRAIN (rolling updates / scale-down): on SIGTERM the
        # replica flips to draining — /health returns 503 so the LB
        # stops routing here. New /generate requests are still ACCEPTED
        # until the LB's ready set refreshes off that 503 probe (the
        # generate handler deliberately keeps serving; refusing would
        # drop requests routed in the probe-interval window) — then the
        # process exits once in-flight requests finish (bounded by
        # SKYTPU_LLM_DRAIN_S). A raw kill mid-generation would drop
        # requests the LB already routed.
        import signal

        from skypilot_tpu.observability import blackbox

        loop = asyncio.get_event_loop()

        def _graceful(*_):
            if server.draining:
                # Second signal escalates: exit now (conventional
                # Ctrl+C-twice semantics; kill -9 would skip even the
                # engine stop).
                if server.engine is not None:
                    server.engine.stop()
                raise web.GracefulExit()
            server.draining = True
            blackbox.record('server.drain',
                            inflight=int(server._inflight))
            # Preemption forensics: snapshot the ring before the drain
            # window runs out (off-loop; dump is best-effort file I/O).
            loop.run_in_executor(
                None, lambda: blackbox.dump('sigterm',
                                            reason='replica drain'))

            async def _finish():
                deadline = loop.time() + float(
                    os.environ.get('SKYTPU_LLM_DRAIN_S', '30'))
                while server._inflight > 0 and loop.time() < deadline:
                    await asyncio.sleep(0.2)
                if server.engine is not None:
                    server.engine.stop()

                def _exit():
                    raise web.GracefulExit()
                loop.call_soon(_exit)

            loop.create_task(_finish())

        for sig in (signal.SIGTERM, signal.SIGINT):
            loop.add_signal_handler(sig, _graceful)

    app.on_startup.append(_install_drain)
    web.run_app(app, host=args.host, port=args.port,
                handle_signals=False, print=lambda *a: None)


if __name__ == '__main__':
    main()
