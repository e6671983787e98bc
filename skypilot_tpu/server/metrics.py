"""Prometheus metrics for the API server and the serving replicas.

Reference analog: ``sky/server/metrics.py`` (API-server prometheus
metrics). Request counters update on every scheduled request; fleet-state
gauges (clusters/jobs/services by status) are computed at scrape time from
the state tables, so the endpoint is always consistent with reality.

Two registries:

* ``REGISTRY`` — the API server's fleet view (``/metrics`` there).
* ``SERVING_REGISTRY`` — request-latency **histograms** fed by the
  serving path (``serve/llm_server.py``): TTFT, QoS queue wait,
  per-phase durations, and per-request decode throughput, all labeled
  by QoS class. Histograms, not gauges: the p95-style gauges mirrored
  from replica /health bodies (below) are probe-sampled summaries; the
  histograms are the raw distribution Prometheus/Grafana can aggregate
  across replicas and window arbitrarily. Replicas serve this registry
  natively on their own ``/metrics``; the API server appends it to its
  scrape too (zero-valued there — serving happens in replicas).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

from prometheus_client import (CollectorRegistry, Counter, Gauge,
                               Histogram, generate_latest)

# text/plain exposition never carries exemplars; the OpenMetrics
# exposition does (the `# {trace_id="..."} value ts` suffix on bucket
# lines). Optional import: absent on older client libs, in which case
# the in-process exemplar store below is the only surface.
try:
    from prometheus_client.openmetrics.exposition import (
        generate_latest as _om_generate_latest)
except ImportError:  # pragma: no cover - baked-in lib has it
    _om_generate_latest = None

OPENMETRICS_CONTENT_TYPE = \
    'application/openmetrics-text; version=1.0.0; charset=utf-8'

REGISTRY = CollectorRegistry()
SERVING_REGISTRY = CollectorRegistry()

# Latency buckets spanning sub-ms CPU-fake replies through minutes-long
# queue waits (shared by every duration histogram so dashboards can
# overlay phases).
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

SERVE_TTFT = Histogram(
    'skytpu_serve_ttft_seconds',
    'Time to first generated token AFTER admission (engine submit -> '
    'first emission; QoS queue wait is excluded — add '
    'skytpu_serve_queue_wait_seconds for the client-experienced '
    'total), by QoS class.',
    ['qos_class'], buckets=LATENCY_BUCKETS_S, registry=SERVING_REGISTRY)
SERVE_QUEUE_WAIT = Histogram(
    'skytpu_serve_queue_wait_seconds',
    'QoS admission queue wait (submit -> dispatch grant), by QoS class.',
    ['qos_class'], buckets=LATENCY_BUCKETS_S, registry=SERVING_REGISTRY)
SERVE_PHASE = Histogram(
    'skytpu_serve_phase_seconds',
    'Per-phase serving durations (phase = prefill | decode | window).',
    ['phase', 'qos_class'], buckets=LATENCY_BUCKETS_S,
    registry=SERVING_REGISTRY)
DECODE_RATE_BUCKETS = (1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500,
                       5000, 10000, 25000)
SERVE_DECODE_RATE = Histogram(
    'skytpu_serve_decode_tok_s',
    'Per-request decode throughput (tokens / decode seconds).',
    ['qos_class'],
    buckets=DECODE_RATE_BUCKETS, registry=SERVING_REGISTRY)

# -- metric exemplars (tail-retention bridge) --------------------------------
# Each serving histogram observation that happened inside a trace
# records the trace id against the bucket it landed in: the operator
# jumps from "the p99.9 TTFT bucket moved" straight to a retained
# trace. Two surfaces: the OpenMetrics exposition (native exemplar
# syntax, negotiated via the Accept header) and the in-process store on
# /debug/exemplars (newest observation per (metric, labels, bucket),
# bounded).
_SERVE_HISTOGRAMS: Dict[str, Tuple[Histogram, tuple]] = {
    'skytpu_serve_ttft_seconds': (SERVE_TTFT, LATENCY_BUCKETS_S),
    'skytpu_serve_queue_wait_seconds': (SERVE_QUEUE_WAIT,
                                        LATENCY_BUCKETS_S),
    'skytpu_serve_phase_seconds': (SERVE_PHASE, LATENCY_BUCKETS_S),
    'skytpu_serve_decode_tok_s': (SERVE_DECODE_RATE,
                                  DECODE_RATE_BUCKETS),
}
_EXEMPLAR_CAP = 512
_EXEMPLARS_LOCK = threading.Lock()
# (metric, sorted-labels-tuple, le) -> {trace_id, value, ts}; dict
# insertion order doubles as recency for the cap eviction.
_EXEMPLARS: Dict[Tuple[str, tuple, float], Dict[str, Any]] = {}

_GUARDED_BY = {'_EXEMPLARS': '_EXEMPLARS_LOCK'}


def observe_serving(name: str, value: float,
                    trace_id: Optional[str] = None,
                    **labels: str) -> None:
    """Observe one serving histogram sample, recording ``trace_id`` as
    the bucket's exemplar when the request was traced (head-sampled OR
    tail-pending — a tail-kept outlier is exactly what the exemplar
    should point at). Falls back to a plain observe on client libs
    without exemplar support."""
    hist, buckets = _SERVE_HISTOGRAMS[name]
    child = hist.labels(**labels)
    exemplar = ({'trace_id': str(trace_id)[:64]} if trace_id else None)
    try:
        child.observe(value, exemplar=exemplar)
    except (TypeError, ValueError):  # no exemplar kwarg / invalid runes
        child.observe(value)
    if not trace_id:
        return
    le = next((float(b) for b in buckets if value <= b), float('inf'))
    key = (name, tuple(sorted(labels.items())), le)
    entry = {'trace_id': str(trace_id), 'value': round(float(value), 6),
             'ts': round(time.time(), 3)}
    with _EXEMPLARS_LOCK:
        _EXEMPLARS.pop(key, None)  # re-insert at the recency tail
        _EXEMPLARS[key] = entry
        while len(_EXEMPLARS) > _EXEMPLAR_CAP:
            _EXEMPLARS.pop(next(iter(_EXEMPLARS)))


def exemplars_payload(query: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
    """The ``/debug/exemplars`` body: the in-process exemplar store,
    newest-first, optionally filtered to one ``?metric=``. Each entry
    links a histogram bucket to the trace id of its most recent
    observation — resolve it via /debug/traces?trace_id=."""
    query = query or {}
    want = str(query.get('metric') or '') or None
    with _EXEMPLARS_LOCK:
        items = list(_EXEMPLARS.items())
    out = []
    for (name, labels, le), entry in reversed(items):
        if want and name != want:
            continue
        out.append({'metric': name, 'labels': dict(labels),
                    'le': (le if le != float('inf') else '+Inf'),
                    **entry})
    return {'count': len(out), 'exemplars': out}


def reset_exemplars_for_testing() -> None:
    with _EXEMPLARS_LOCK:
        _EXEMPLARS.clear()


# Tail-based trace retention (observability/trace.py): keep/drop
# accounting mirrored at scrape time from the in-process tail store.
# Gauges mirroring cumulative counters (restart legitimately resets),
# in the serving registry so replicas expose them natively.
_TRACE_RETAINED = Gauge(
    'skytpu_trace_retained_total',
    'Traces kept by tail-based retention on this process, by verdict '
    '(the bounded trace.VERDICTS vocabulary: slow | slow_ttft | error '
    '| shed | evicted | resumed | slo_breach | recompile_storm | '
    'baseline | propagated).',
    ['verdict'], registry=SERVING_REGISTRY)
_TRACE_PENDING = Gauge(
    'skytpu_trace_pending',
    'Tail-pending trace fragments currently parked awaiting a '
    'retention verdict (TTL-bounded).', registry=SERVING_REGISTRY)


def _refresh_trace_gauges() -> None:
    from skypilot_tpu.observability import trace as trace_lib
    _TRACE_RETAINED.clear()
    stats = trace_lib.tail_stats()
    for verdict, n in (stats.get('verdicts') or {}).items():
        _TRACE_RETAINED.labels(verdict=verdict).set(n)
    _TRACE_PENDING.set(stats.get('pending') or 0)

# Replica-local engine/queue gauges, set at scrape time by the replica's
# own /metrics handler (satellite: replicas scrapeable directly instead
# of only via controller probes of /health).
_REPLICA_TOKENS = Gauge(
    'skytpu_replica_tokens_emitted',
    'Cumulative tokens emitted by this replica engine.',
    registry=SERVING_REGISTRY)
_REPLICA_SLOTS = Gauge(
    'skytpu_replica_slots', 'Engine decode slots on this replica.',
    registry=SERVING_REGISTRY)
_REPLICA_ACTIVE = Gauge(
    'skytpu_replica_active_slots', 'Engine slots currently decoding.',
    registry=SERVING_REGISTRY)
_REPLICA_QUEUE_DEPTH = Gauge(
    'skytpu_replica_qos_queue_depth',
    'QoS admission queue depth on this replica, by class.',
    ['qos_class'], registry=SERVING_REGISTRY)
# Copy-on-write block-prefix sharing on the paged KV pool
# (models/paged.py BlockTrie; stats()['prefix_share'] / ['kv_blocks']).
_REPLICA_PREFIX_HITS = Gauge(
    'skytpu_replica_prefix_hits',
    'Cumulative block-share prefix-cache hits on this replica.',
    registry=SERVING_REGISTRY)
_REPLICA_PREFIX_HIT_RATE = Gauge(
    'skytpu_replica_prefix_hit_rate',
    'Block-share hit rate (hits / (hits + misses)) over the replica '
    'lifetime.', registry=SERVING_REGISTRY)
_REPLICA_COW_FORKS = Gauge(
    'skytpu_replica_prefix_cow_forks',
    'Cumulative copy-on-write forks of partially shared KV blocks.',
    registry=SERVING_REGISTRY)
_REPLICA_PREFILL_TOKENS = Gauge(
    'skytpu_replica_prefill_tokens',
    'Cumulative prompt tokens the prefill actually computed.',
    registry=SERVING_REGISTRY)
_REPLICA_PREFILL_SAVED = Gauge(
    'skytpu_replica_prefill_tokens_saved',
    'Cumulative prompt tokens skipped via shared/cached prefix KV.',
    registry=SERVING_REGISTRY)
_REPLICA_PREFILL_BUBBLE = Gauge(
    'skytpu_replica_prefill_bubble_ms',
    'Cumulative prefill host time decode provably waited on (ms).',
    registry=SERVING_REGISTRY)
_REPLICA_KV_BLOCKS = Gauge(
    'skytpu_replica_kv_blocks',
    'Paged KV pool block accounting by state (free | owned | shared | '
    'cached partition the usable device pool exactly; host and '
    'spilled count hierarchical-tier blocks living OFF-device in the '
    'host-DRAM pool and the spill segment store).',
    ['state'], registry=SERVING_REGISTRY)
# Hierarchical KV memory (serve/kv_tiers.py): demoted prefix chains
# living in host DRAM or spilled to range-readable segment files, and
# the promote path that re-imports them instead of recomputing.
_KV_TIER_HITS = Gauge(
    'skytpu_kv_tier_hits',
    'Cumulative admissions served from a KV tier instead of recompute '
    '(host = promoted straight from the host-DRAM pool; spilled = '
    'fetched from a spill segment first).',
    ['tier'], registry=SERVING_REGISTRY)
_KV_TIER_BYTES = Gauge(
    'skytpu_kv_tier_bytes',
    'Serialized KV bytes currently resident per tier (host-DRAM pool '
    'vs on-disk spill segments).',
    ['tier'], registry=SERVING_REGISTRY)
_KV_TIER_PROMOTE_SECONDS = Gauge(
    'skytpu_kv_tier_promote_seconds',
    'Cumulative wall-clock spent promoting demoted chains back into '
    'the device pool (validate + jit_import_blocks scatter).',
    registry=SERVING_REGISTRY)
# Disaggregated prefill/decode KV handoff (serve/disagg.py): cumulative
# per-replica handoff accounting by direction. Gauges mirroring the
# replica's own counters (restart legitimately resets them).
_DISAGG_HANDOFFS = Gauge(
    'skytpu_disagg_handoffs',
    'Cumulative KV handoffs on this replica by direction (export = '
    'prefill-role retirements, import = decode-role installs).',
    ['direction'], registry=SERVING_REGISTRY)
_DISAGG_BYTES = Gauge(
    'skytpu_disagg_handoff_bytes',
    'Cumulative KV-handoff payload bytes by direction (export planes '
    'serialized / import planes installed; skipped shared-prefix '
    'blocks transfer as references and cost nothing here).',
    ['direction'], registry=SERVING_REGISTRY)
_DISAGG_SECONDS = Gauge(
    'skytpu_disagg_handoff_seconds',
    'Cumulative wall-clock spent in KV handoffs by direction '
    '(export: prefill + serialize + park; import: parse + validate + '
    'install + decode-admission wait).',
    ['direction'], registry=SERVING_REGISTRY)
_DISAGG_FALLBACK = Gauge(
    'skytpu_disagg_fallback_total',
    'Requests this replica served whole after the LB abandoned a KV '
    'handoff (export/transfer/import failure or a decode replica '
    'dying mid-stream).', registry=SERVING_REGISTRY)
# Black-box flight recorder (observability/blackbox.py): incident
# bundles THIS PROCESS has written, by trigger — a nonzero
# engine_failure/watchdog count is the alert that forensics exist to
# fetch (`stpu debug bundles`, /debug/blackbox). A gauge mirroring the
# recorder's own cumulative counters (restart legitimately resets), in
# the serving registry so replicas and the API server both expose it.
# The label set is the recorder's bounded TRIGGERS vocabulary.
_INCIDENT_BUNDLES = Gauge(
    'skytpu_incident_bundles_total',
    'Incident bundles written by this process since start, by trigger '
    '(engine_failure | sigterm | watchdog | slo_breach | manual).',
    ['trigger'], registry=SERVING_REGISTRY)
# Runtime profiler (observability/profiler.py): compile ledger, device
# memory, cold-start phases. Gauges mirroring the profiler's own
# cumulative ledgers (restart legitimately resets them), refreshed at
# scrape time from the in-process profiler state; absent/cleared while
# SKYTPU_PROFILE is off.
_COMPILE_TOTAL = Gauge(
    'skytpu_compile_total',
    'Cumulative XLA compiles per profiled jit program (compile '
    'ledger). Nonzero AFTER warm-up under a fixed-shape mix means the '
    'compile-once-per-shape contract is being violated.',
    ['program'], registry=SERVING_REGISTRY)
_COMPILE_SECONDS = Gauge(
    'skytpu_compile_seconds',
    'Cumulative trace+lower+compile wall seconds per profiled jit '
    'program.', ['program'], registry=SERVING_REGISTRY)
_RECOMPILE_STORMS = Gauge(
    'skytpu_recompile_storm_total',
    'Cumulative compiles past a program\'s declared shape budget '
    '(recompile storms), by program; feeds the serve.recompile_storm '
    'SLO rule.', ['program'], registry=SERVING_REGISTRY)
_DEVICE_MEM = Gauge(
    'skytpu_device_mem_bytes',
    'Device-memory accounting by kind: allocator in_use/peak/limit/'
    'headroom plus the engine\'s logical registrations '
    '(logical_weights, logical_kv_cache, ...) and the unattributed '
    'residue (leak/fragmentation signal).',
    ['kind'], registry=SERVING_REGISTRY)
_WARMUP_SECONDS = Gauge(
    'skytpu_replica_warmup_seconds',
    'Cold-start phase-ledger durations on this replica by phase '
    '(imports | backend_init.* | weights_load | jit_warmup | ready | '
    'first_token); phases telescope and sum to the observed process '
    'wall-clock.', ['phase'], registry=SERVING_REGISTRY)


def _refresh_incident_gauge() -> None:
    from skypilot_tpu.observability import blackbox
    _INCIDENT_BUNDLES.clear()
    for trigger, n in blackbox.dump_counts().items():
        _INCIDENT_BUNDLES.labels(trigger=trigger).set(n)


def _refresh_profiler_gauges() -> None:
    """Mirror the in-process runtime profiler (observability/
    profiler.py) into the compile/memory/warm-up gauges at scrape
    time. Cleared (series absent) while SKYTPU_PROFILE is off, so the
    scrape stays byte-stable across the flag."""
    from skypilot_tpu.observability import profiler
    for gauge in (_COMPILE_TOTAL, _COMPILE_SECONDS, _RECOMPILE_STORMS,
                  _DEVICE_MEM, _WARMUP_SECONDS):
        gauge.clear()
    if not profiler.enabled():
        return
    snap = profiler.snapshot()
    for name, st in (snap.get('compile') or {}).items():
        _COMPILE_TOTAL.labels(program=name).set(st['compiles'])
        _COMPILE_SECONDS.labels(program=name).set(
            st['compile_ms'] / 1000.0)
        _RECOMPILE_STORMS.labels(program=name).set(st['storms'])
    mem = snap.get('device_memory') or {}
    for kind, key in (('in_use', 'bytes_in_use'),
                      ('peak', 'peak_bytes'),
                      ('limit', 'bytes_limit'),
                      ('headroom', 'headroom_bytes'),
                      ('unattributed', 'unattributed_bytes')):
        if isinstance(mem.get(key), (int, float)):
            _DEVICE_MEM.labels(kind=kind).set(mem[key])
    for kind, nbytes in (mem.get('logical') or {}).items():
        _DEVICE_MEM.labels(kind=f'logical_{kind}').set(nbytes)
    for phase, secs in ((snap.get('cold_start') or {}).get('phases')
                        or {}).items():
        _WARMUP_SECONDS.labels(phase=phase).set(secs)


# SLO engine (observability/slo.py): alerts currently FIRING, by rule
# and severity — the scrape-side mirror of `stpu alerts`. Recomputed
# from the engine's live state every scrape and cleared first, so the
# series is nonzero only while an alert is genuinely firing (pending
# and resolved states never surface here).
_ALERTS_FIRING = Gauge(
    'skytpu_alerts_firing',
    'SLO alerts currently firing, by rule and severity '
    '(observability/slo.py RULES registry; 0/absent when nothing '
    'fires or SKYTPU_SLO is off).',
    ['rule', 'severity'], registry=REGISTRY)


def _refresh_alert_gauge() -> None:
    from collections import Counter as C

    from skypilot_tpu.observability import slo
    _ALERTS_FIRING.clear()
    counts = C((a['rule'], a['severity']) for a in slo.firing())
    for (rule, severity), n in counts.items():
        _ALERTS_FIRING.labels(rule=rule, severity=severity).set(n)

API_REQUEST = Histogram(
    'skytpu_api_request_seconds',
    'API-server HTTP handler duration by operation.',
    ['op'], buckets=LATENCY_BUCKETS_S, registry=REGISTRY)

REQUESTS_TOTAL = Counter(
    'skytpu_api_requests_total', 'API requests scheduled, by operation.',
    ['op'], registry=REGISTRY)

_CLUSTERS = Gauge('skytpu_clusters', 'Clusters by status.', ['status'],
                  registry=REGISTRY)

# Training/fleet telemetry (computed at scrape time from the goodput
# ledger and the clusters' heartbeat payloads — the same
# read-state-at-scrape discipline as the fleet gauges below).
_JOB_GOODPUT = Gauge(
    'skytpu_job_goodput_ratio',
    'Managed-job goodput: fraction of wall-clock spent RUNNING (vs '
    'provisioning, queueing, and recovery), from the phase ledger.',
    ['job_id'], registry=REGISTRY)
_JOB_PHASE_SECONDS = Gauge(
    'skytpu_job_phase_seconds',
    'Managed-job wall-clock seconds per ledger phase (pending | '
    'launching | running | recovering | cancelling); the phases of one '
    'job sum to its wall-clock. A gauge, not a counter: series are '
    'recomputed each scrape and retire with the job — no _total suffix.',
    ['job_id', 'phase'], registry=REGISTRY)
_TRAIN_STEP_SECONDS = Gauge(
    'skytpu_train_step_seconds',
    'Latest trainer step time per cluster (heartbeat-shipped telemetry '
    'window).', ['cluster'], registry=REGISTRY)
_TRAIN_TOKENS_PER_S = Gauge(
    'skytpu_train_tokens_per_s',
    'Latest trainer throughput per cluster (heartbeat-shipped).',
    ['cluster'], registry=REGISTRY)
_TRAIN_MFU = Gauge(
    'skytpu_train_mfu',
    'Latest achieved MFU per cluster (needs SKYTPU_PEAK_FLOPS on the '
    'trainer host; absent otherwise).', ['cluster'], registry=REGISTRY)
_CLUSTER_HEARTBEAT_AGE = Gauge(
    'skytpu_cluster_heartbeat_age_seconds',
    'Seconds since each cluster daemon last heartbeated.',
    ['cluster'], registry=REGISTRY)
# Checkpoint pipeline accounting (heartbeat-shipped ckpt manager
# telemetry; see skypilot_tpu/ckpt/). save vs stall is the async win:
# stall is what the step loop actually paid; save is the background
# persist cost the loop overlapped.
_CKPT_SAVE_S = Gauge(
    'skytpu_ckpt_save_seconds',
    'Cumulative seconds spent persisting checkpoints on this cluster '
    '(commit + mirror, background under async saves).',
    ['cluster'], registry=REGISTRY)
_CKPT_STALL_S = Gauge(
    'skytpu_ckpt_stall_seconds',
    'Cumulative seconds the train step loop stalled for checkpointing '
    '(device->host snapshot + back-pressure).',
    ['cluster'], registry=REGISTRY)
_CKPT_LAST_STEP = Gauge(
    'skytpu_ckpt_last_step',
    'Newest durably checkpointed train step on this cluster.',
    ['cluster'], registry=REGISTRY)
_CKPT_STALENESS = Gauge(
    'skytpu_ckpt_staleness_seconds',
    'Seconds since the last successful checkpoint save — the work at '
    'risk if the slice is preempted right now.',
    ['cluster'], registry=REGISTRY)
_MANAGED_JOBS = Gauge('skytpu_managed_jobs', 'Managed jobs by status.',
                      ['status'], registry=REGISTRY)
_SERVICES = Gauge('skytpu_services', 'Services by status.', ['status'],
                  registry=REGISTRY)
_API_REQUESTS = Gauge('skytpu_api_request_table', 'Request table by status.',
                      ['status'], registry=REGISTRY)

# Serve-plane QoS backpressure, re-read at scrape time from the replicas'
# probe-recorded /health bodies (serve/qos.py). Gauges, not Counters:
# the shed/evict totals are the REPLICA's cumulative counters mirrored
# here — a replica restart legitimately resets them.
_SERVE_QOS_DEPTH = Gauge(
    'skytpu_serve_qos_queue_depth',
    'Replica QoS queue depth by priority class.',
    ['service', 'replica', 'qos_class'], registry=REGISTRY)
_SERVE_QOS_SHED = Gauge(
    'skytpu_serve_qos_shed_total',
    'Replica cumulative shed (429) count by priority class.',
    ['service', 'replica', 'qos_class'], registry=REGISTRY)
_SERVE_QOS_EVICTED = Gauge(
    'skytpu_serve_qos_evicted_total',
    'Replica cumulative queue-TTL eviction count by priority class.',
    ['service', 'replica', 'qos_class'], registry=REGISTRY)
_SERVE_QOS_WAIT_P95 = Gauge(
    'skytpu_serve_qos_queue_wait_p95_ms',
    'Replica p95 queue wait (ms, recent window) by priority class.',
    ['service', 'replica', 'qos_class'], registry=REGISTRY)

# Fleet-wide prefix-affinity routing (utils/prefix_affinity.py). The
# hit rate is recomputed at scrape time from the replicas' probe-
# recorded /health bodies (like the QoS gauges above); the LB routing
# counters are pushed by the serve controller each tick
# (ServeController._mirror_affinity_gauges) — gauges mirroring the
# LB's cumulative counters, so a controller restart legitimately
# resets them.
_LB_AFFINITY_ROUTED = Gauge(
    'skytpu_lb_affinity_routed_total',
    'Cumulative /generate requests the LB routed to the replica whose '
    'advertised trie summary matched the prompt head, by service.',
    ['service'], registry=REGISTRY)
_LB_AFFINITY_FALLBACK = Gauge(
    'skytpu_lb_affinity_fallback_total',
    'Cumulative affinity-eligible requests that matched a replica but '
    'fell back to least-load because the match sat past its detour '
    'credit (the hot-prefix saturation spill), by service.',
    ['service'], registry=REGISTRY)
# Cold-start budget (ROADMAP item 2): provision→first-token seconds
# per replica, rolled up by replica_managers.py at each replica's
# FIRST dark→READY transition (launch issued → readiness probe
# succeeded; the replica-local skytpu_replica_warmup_seconds ledger
# breaks the in-process share of it down by phase). Pushed like the
# LB affinity counters and rebuilt at scrape for live services only.
_PROVISION_TO_FIRST_TOKEN = Gauge(
    'skytpu_provision_to_first_token_s',
    'Seconds from replica launch to its first successful readiness '
    'probe (provision→first-token cold-start budget), per replica; '
    'set once at the dark→READY transition.',
    ['service', 'replica'], registry=REGISTRY)

# Self-healing actions (serve/remediation.py), controller-pushed like
# the affinity counters: cumulative decisions by (action, trigger,
# outcome) — outcome 'executed'/'failed'/'observed' (dry run) or
# 'suppressed_*' (budget/hysteresis/cooldown/concurrency downgraded
# the decision to noop_observe).
_REMEDIATION_TOTAL = Gauge(
    'skytpu_remediation_total',
    'Cumulative remediation-engine decisions by action, trigger and '
    'outcome, per service (serve/remediation.py).',
    ['service', 'action', 'trigger', 'outcome'], registry=REGISTRY)

_FLEET_PREFIX_HIT_RATE = Gauge(
    'skytpu_fleet_prefix_hit_rate',
    'Fleet-wide block-share prefix hit rate: sum(hits) / sum(hits + '
    'misses) aggregated across all of a service\'s replica /health '
    'bodies — the number per-replica hit rates overstate once the LB '
    'spreads a tenant\'s traffic.', ['service'], registry=REGISTRY)


# Last pushed values per service: the scrape-time refresh rebuilds the
# gauges from this cache for LIVE services only, so a torn-down
# service's series vanish instead of exporting its final counts
# forever (every other serve gauge is clear-and-rebuilt the same way).
_LB_AFFINITY_LAST: Dict[str, Any] = {}
# (service, replica) -> seconds; same live-services-only rebuild.
_P2FT_LAST: Dict[Any, float] = {}
# service -> {(action, trigger, outcome): count}; same rebuild.
_REMEDIATION_LAST: Dict[str, Dict[Any, int]] = {}


def set_lb_affinity(service: str, routed: float,
                    fallbacks: float) -> None:
    """Controller-pushed mirror of the LB's affinity routing counters
    (LoadBalancer.affinity_snapshot)."""
    _LB_AFFINITY_LAST[service] = (float(routed), float(fallbacks))
    _LB_AFFINITY_ROUTED.labels(service=service).set(routed)
    _LB_AFFINITY_FALLBACK.labels(service=service).set(fallbacks)


def set_remediation(service: str, counts: Dict[Any, int]) -> None:
    """Controller-pushed mirror of the remediation engine's decision
    counts ({(action, trigger, outcome): n},
    RemediationEngine.counts)."""
    _REMEDIATION_LAST[service] = dict(counts)
    for (action, trigger, outcome), n in counts.items():
        _REMEDIATION_TOTAL.labels(service=service, action=action,
                                  trigger=trigger, outcome=outcome).set(n)


def set_provision_to_first_token(service: str, replica: Any,
                                 seconds: float) -> None:
    """Replica-manager-pushed cold-start rollup: one observation per
    replica lifetime, at its first dark→READY transition."""
    _P2FT_LAST[(service, str(replica))] = float(seconds)
    _PROVISION_TO_FIRST_TOKEN.labels(
        service=service, replica=str(replica)).set(seconds)


def _refresh_goodput_gauges(clusters, jobs) -> None:
    """Goodput/phase gauges from the ledger (one grouped query) and
    train/heartbeat gauges from the cluster heartbeats."""
    import time as time_lib

    from skypilot_tpu.jobs import state as jobs_state

    for gauge in (_JOB_GOODPUT, _JOB_PHASE_SECONDS, _TRAIN_STEP_SECONDS,
                  _TRAIN_TOKENS_PER_S, _TRAIN_MFU, _CLUSTER_HEARTBEAT_AGE,
                  _CKPT_SAVE_S, _CKPT_STALL_S, _CKPT_LAST_STEP,
                  _CKPT_STALENESS):
        gauge.clear()
    totals = jobs_state.phase_totals()
    listed = {r['job_id'] for r in jobs}
    for job_id, phases in totals.items():
        if job_id not in listed:
            continue  # past the list_jobs window: keep label sets bounded
        for phase, secs in phases.items():
            _JOB_PHASE_SECONDS.labels(job_id=str(job_id),
                                      phase=phase).set(secs)
        ratio = jobs_state.goodput_ratio_from_phases(phases)
        if ratio is not None:
            _JOB_GOODPUT.labels(job_id=str(job_id)).set(ratio)
    now = time_lib.time()
    for rec in clusters:
        if rec.get('last_heartbeat'):
            _CLUSTER_HEARTBEAT_AGE.labels(cluster=rec['name']).set(
                max(now - rec['last_heartbeat'], 0.0))
        heartbeat = rec.get('heartbeat') or {}
        labels = {'cluster': rec['name']}
        ckpt = heartbeat.get('ckpt')
        if isinstance(ckpt, dict):
            if isinstance(ckpt.get('save_s'), (int, float)):
                _CKPT_SAVE_S.labels(**labels).set(ckpt['save_s'])
            if isinstance(ckpt.get('stall_s'), (int, float)):
                _CKPT_STALL_S.labels(**labels).set(ckpt['stall_s'])
            if isinstance(ckpt.get('last_step'), (int, float)):
                _CKPT_LAST_STEP.labels(**labels).set(ckpt['last_step'])
            if isinstance(ckpt.get('last_save_ts'), (int, float)) \
                    and ckpt['last_save_ts'] > 0:
                _CKPT_STALENESS.labels(**labels).set(
                    max(now - ckpt['last_save_ts'], 0.0))
        train = heartbeat.get('train')
        if not isinstance(train, dict):
            continue
        if isinstance(train.get('step_time_s'), (int, float)):
            _TRAIN_STEP_SECONDS.labels(**labels).set(train['step_time_s'])
        if isinstance(train.get('tokens_per_s'), (int, float)):
            _TRAIN_TOKENS_PER_S.labels(**labels).set(train['tokens_per_s'])
        if isinstance(train.get('mfu'), (int, float)):
            _TRAIN_MFU.labels(**labels).set(train['mfu'])


def _refresh_gauges() -> None:
    from collections import Counter as C

    from skypilot_tpu import global_user_state
    from skypilot_tpu.jobs import state as jobs_state
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.server import requests_db

    clusters = global_user_state.get_clusters()
    jobs = jobs_state.list_jobs()
    services = [s for s in serve_state.list_services() if s is not None]
    _refresh_goodput_gauges(clusters, jobs)
    for gauge, counts in (
        (_CLUSTERS, C(r['status'].value for r in clusters)),
        (_MANAGED_JOBS, C(r['status'].value for r in jobs)),
        (_SERVICES, C(s['status'].value for s in services)),
        (_API_REQUESTS, C(r['status'] for r in requests_db.list_requests())),
    ):
        gauge.clear()
        for status, n in counts.items():
            gauge.labels(status=status).set(n)

    for gauge in (_SERVE_QOS_DEPTH, _SERVE_QOS_SHED, _SERVE_QOS_EVICTED,
                  _SERVE_QOS_WAIT_P95, _FLEET_PREFIX_HIT_RATE,
                  _LB_AFFINITY_ROUTED, _LB_AFFINITY_FALLBACK,
                  _REMEDIATION_TOTAL, _PROVISION_TO_FIRST_TOKEN):
        gauge.clear()
    live_services = {s['name'] for s in services
                     if s['status'].value not in ('SHUTDOWN', 'FAILED')}
    for name in list(_LB_AFFINITY_LAST):
        if name not in live_services:
            del _LB_AFFINITY_LAST[name]
        else:
            routed, fallbacks = _LB_AFFINITY_LAST[name]
            _LB_AFFINITY_ROUTED.labels(service=name).set(routed)
            _LB_AFFINITY_FALLBACK.labels(service=name).set(fallbacks)
    for name in list(_REMEDIATION_LAST):
        if name not in live_services:
            del _REMEDIATION_LAST[name]
        else:
            for (action, trigger, outcome), n in \
                    _REMEDIATION_LAST[name].items():
                _REMEDIATION_TOTAL.labels(
                    service=name, action=action, trigger=trigger,
                    outcome=outcome).set(n)
    live_replicas = set()  # (service, replica_id) seen this scrape
    for svc in services:
        # Fleet prefix hit rate: aggregate the replicas' block-share
        # counters BEFORE dividing — averaging per-replica rates would
        # weight an idle replica's stale 100% the same as the replica
        # actually serving the tenant.
        fleet_hits = fleet_misses = 0.0
        fleet_reported = False
        for rep in serve_state.list_replicas(svc['name']):
            live_replicas.add((svc['name'], str(rep['replica_id'])))
            health = serve_state.parse_health(rep.get('health')) or {}
            share = (health.get('engine') or {}).get('prefix_share') \
                if isinstance(health.get('engine'), dict) else None
            if isinstance(share, dict) and isinstance(
                    share.get('hits'), (int, float)):
                fleet_reported = True
                fleet_hits += float(share['hits'])
                fleet_misses += float(share.get('misses') or 0)
            qos = health.get('qos')
            if not isinstance(qos, dict):
                continue
            labels = {'service': svc['name'],
                      'replica': str(rep['replica_id'])}
            for cls, c in (qos.get('classes') or {}).items():
                if not isinstance(c, dict):
                    continue
                _SERVE_QOS_DEPTH.labels(qos_class=cls, **labels).set(
                    c.get('depth') or 0)
                _SERVE_QOS_SHED.labels(qos_class=cls, **labels).set(
                    c.get('shed') or 0)
                _SERVE_QOS_EVICTED.labels(qos_class=cls, **labels).set(
                    c.get('evicted') or 0)
                p95 = (c.get('queue_wait_ms') or {}).get('p95')
                if isinstance(p95, (int, float)):
                    _SERVE_QOS_WAIT_P95.labels(qos_class=cls,
                                               **labels).set(p95)
        if fleet_reported:
            _FLEET_PREFIX_HIT_RATE.labels(service=svc['name']).set(
                fleet_hits / max(fleet_hits + fleet_misses, 1.0))
    # Cold-start rollups survive only as long as their replica: a
    # replaced/retired replica's series vanishes with it (per-replica,
    # not merely per-service — an autoscaled service churning spot
    # replicas for weeks must not accumulate unbounded label
    # cardinality; same stale-stats discipline as replica_managers).
    for key in list(_P2FT_LAST):
        if key not in live_replicas:
            del _P2FT_LAST[key]
        else:
            _PROVISION_TO_FIRST_TOKEN.labels(
                service=key[0], replica=key[1]).set(_P2FT_LAST[key])


def openmetrics_available() -> bool:
    return _om_generate_latest is not None


def render() -> bytes:
    _refresh_gauges()
    _refresh_incident_gauge()
    _refresh_alert_gauge()
    _refresh_profiler_gauges()
    _refresh_trace_gauges()
    return generate_latest(REGISTRY) + generate_latest(SERVING_REGISTRY)


def render_serving(engine: Optional[Dict[str, Any]] = None,
                   qos: Optional[Dict[str, Any]] = None,
                   disagg: Optional[Dict[str, Any]] = None,
                   openmetrics: bool = False) -> bytes:
    """The serving replica's scrape body: the latency histograms plus
    point-in-time engine/queue gauges from the stats dicts the replica
    already maintains for /health. ``disagg`` is the server-level
    KV-handoff accounting (serve/llm_server.py disagg_stats).
    ``openmetrics=True`` renders the OpenMetrics exposition instead —
    the one that carries histogram exemplars (trace ids on bucket
    lines) — when the client negotiated it via Accept."""
    _refresh_incident_gauge()
    _refresh_profiler_gauges()
    _refresh_trace_gauges()
    if disagg:
        for direction, prefix in (('export', 'export'),
                                  ('import', 'import')):
            _DISAGG_HANDOFFS.labels(direction=direction).set(
                disagg.get(f'{prefix}s') or 0)
            _DISAGG_BYTES.labels(direction=direction).set(
                disagg.get(f'{prefix}_bytes') or 0)
            _DISAGG_SECONDS.labels(direction=direction).set(
                disagg.get(f'{prefix}_seconds') or 0)
        _DISAGG_FALLBACK.set(disagg.get('fallbacks_served') or 0)
    else:
        _DISAGG_HANDOFFS.clear()
        _DISAGG_BYTES.clear()
        _DISAGG_SECONDS.clear()
        _DISAGG_FALLBACK.set(0)
    if engine:
        _REPLICA_TOKENS.set(engine.get('tokens_emitted') or 0)
        _REPLICA_SLOTS.set(engine.get('slots') or 0)
        _REPLICA_ACTIVE.set(engine.get('active_slots') or 0)
        share = engine.get('prefix_share') or {}
        _REPLICA_PREFIX_HITS.set(share.get('hits') or 0)
        _REPLICA_PREFIX_HIT_RATE.set(share.get('hit_rate') or 0)
        _REPLICA_COW_FORKS.set(share.get('cow_forks') or 0)
        _REPLICA_PREFILL_TOKENS.set(engine.get('prefill_tokens') or 0)
        _REPLICA_PREFILL_SAVED.set(
            engine.get('prefill_tokens_saved') or 0)
        _REPLICA_PREFILL_BUBBLE.set(engine.get('prefill_bubble_ms') or 0)
        kb = engine.get('kv_blocks')
        if isinstance(kb, dict):
            for state in ('free', 'owned', 'shared', 'cached',
                          'host', 'spilled'):
                _REPLICA_KV_BLOCKS.labels(state=state).set(
                    kb.get(state) or 0)
        else:
            _REPLICA_KV_BLOCKS.clear()
        tiers = engine.get('kv_tiers')
        if isinstance(tiers, dict) and tiers.get('enabled'):
            _KV_TIER_HITS.labels(tier='host').set(
                tiers.get('host_hits') or 0)
            _KV_TIER_HITS.labels(tier='spilled').set(
                tiers.get('spill_hits') or 0)
            _KV_TIER_BYTES.labels(tier='host').set(
                tiers.get('host_bytes') or 0)
            _KV_TIER_BYTES.labels(tier='spilled').set(
                tiers.get('spilled_bytes') or 0)
            _KV_TIER_PROMOTE_SECONDS.set(
                (tiers.get('promote_ms') or 0) / 1e3)
        else:
            _KV_TIER_HITS.clear()
            _KV_TIER_BYTES.clear()
            _KV_TIER_PROMOTE_SECONDS.set(0)
    else:
        # Stats unavailable (engine stopping/absent): zero rather than
        # re-render the last live values forever — stale "3 active
        # slots" would mislead alerting exactly when the replica wedged.
        _REPLICA_TOKENS.set(0)
        _REPLICA_SLOTS.set(0)
        _REPLICA_ACTIVE.set(0)
        for g in (_REPLICA_PREFIX_HITS, _REPLICA_PREFIX_HIT_RATE,
                  _REPLICA_COW_FORKS, _REPLICA_PREFILL_TOKENS,
                  _REPLICA_PREFILL_SAVED, _REPLICA_PREFILL_BUBBLE):
            g.set(0)
        _REPLICA_KV_BLOCKS.clear()
        _KV_TIER_HITS.clear()
        _KV_TIER_BYTES.clear()
        _KV_TIER_PROMOTE_SECONDS.set(0)
    if qos:
        for cls, c in (qos.get('classes') or {}).items():
            if isinstance(c, dict):
                _REPLICA_QUEUE_DEPTH.labels(qos_class=cls).set(
                    c.get('depth') or 0)
    else:
        _REPLICA_QUEUE_DEPTH.clear()
    if openmetrics and _om_generate_latest is not None:
        return _om_generate_latest(SERVING_REGISTRY)
    return generate_latest(SERVING_REGISTRY)
