"""The SKYTPU_* environment-flag registry.

Every environment flag the tree reads is declared here — name, type,
default, one-line doc — and `make lint` (skylint's env-flag checker)
fails on any ``SKYTPU_*`` string literal that is not a declared name
(typo-proofing: ``os.environ.get('SKYTPU_LLM_PIPLINE')`` would
otherwise silently read the default forever) and on any declared flag
no code reads (dead-flag detection). ``tools/gen_flag_docs.py``
generates ``docs/env_flags.md`` from this module; its ``--check`` mode
runs under `make lint`, so the docs cannot drift either.

This module is import-light ON PURPOSE (stdlib dataclasses only): the
lint tooling and the docs generator load it standalone, without paying
for (or requiring) the package's jax-adjacent imports.

Conventions: booleans are env-string booleans — unset/''/'0'/'off' is
false, anything else true — unless the doc says otherwise. ``default``
is the code-side fallback as a string, or None when the flag is simply
unset (feature off / auto-detect)."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

TYPES = ('bool', 'int', 'float', 'str', 'path', 'url', 'csv', 'map')


@dataclasses.dataclass(frozen=True)
class Flag:
    name: str
    type: str  # one of TYPES
    default: Optional[str]  # code-side fallback (None = unset)
    doc: str


FLAGS: Tuple[Flag, ...] = (
    # -- state, config, workspaces ------------------------------------
    Flag('SKYTPU_STATE_DIR', 'path', '~/.skypilot_tpu',
         'Root of all local state: the sqlite DBs, cluster YAMLs, SSH '
         'leases, trace exports, benchmark artifacts.'),
    Flag('SKYTPU_CONFIG', 'path', None,
         'Path to the user config YAML (overrides the default '
         '~/.skypilot_tpu/config.yaml lookup).'),
    Flag('SKYTPU_WORKSPACE', 'str', None,
         'Active workspace name; set by the request runner for every '
         'server-executed request.'),
    Flag('SKYTPU_DB_URL', 'url', None,
         'External database URL for server state; unset = per-user '
         'sqlite under SKYTPU_STATE_DIR.'),
    # -- API server / client ------------------------------------------
    Flag('SKYTPU_API_SERVER_URL', 'url', 'http://127.0.0.1:46580',
         'API server endpoint the SDK/CLI talks to.'),
    Flag('SKYTPU_API_TOKEN', 'str', None,
         'Bearer token the SDK/CLI sends to the API server.'),
    Flag('SKYTPU_API_TOKEN_FILE', 'path', '~/.skypilot_tpu/token',
         'File the client reads the bearer token from when '
         'SKYTPU_API_TOKEN is unset.'),
    Flag('SKYTPU_METRICS_TOKEN', 'str', None,
         'Separate scrape token granting /metrics-only access (so '
         'Prometheus need not hold an admin bearer).'),
    Flag('SKYTPU_SERVER_REFRESH_S', 'float', '120',
         'API-server background fleet-state refresh interval.'),
    Flag('SKYTPU_REQUEST_GC_AGE_S', 'float', '86400',
         'Age after which finished request-table rows are garbage-'
         'collected by the server daemons.'),
    Flag('SKYTPU_MAX_CONTROLLERS', 'int', '16',
         'Cap on concurrently running in-process service controllers.'),
    Flag('SKYTPU_ADVERTISE_IP', 'str', None,
         'Routable IP advertised for endpoints on multi-homed hosts '
         '(default: auto-detected local IP).'),
    # -- auth (OAuth / users) -----------------------------------------
    Flag('SKYTPU_OAUTH_ISSUER', 'url', None,
         'OIDC issuer URL; setting it enables the OAuth login flow.'),
    Flag('SKYTPU_OAUTH_CLIENT_ID', 'str', None,
         'OAuth client id registered with the issuer.'),
    Flag('SKYTPU_OAUTH_CLIENT_SECRET', 'str', None,
         'OAuth client secret (confidential clients only).'),
    Flag('SKYTPU_OAUTH_ADMIN_EMAILS', 'csv', None,
         'Emails auto-granted the admin role at first OAuth login.'),
    Flag('SKYTPU_OAUTH_DEFAULT_ROLE', 'str', 'user',
         'Role granted to OAuth logins not in the admin list.'),
    # -- telemetry / usage collection ---------------------------------
    Flag('SKYTPU_DISABLE_USAGE_COLLECTION', 'bool', '0',
         'Disable anonymous usage reporting entirely.'),
    Flag('SKYTPU_USAGE_ENDPOINT', 'url', None,
         'Usage-report POST endpoint; unset spools locally only.'),
    Flag('SKYTPU_USAGE_SPOOL_MAX_FILES', 'int', '32',
         'Max spooled usage-report files before oldest-first pruning.'),
    Flag('SKYTPU_USAGE_SPOOL_MAX_MB', 'float', '16',
         'Max total MB of spooled usage reports.'),
    Flag('SKYTPU_SESSION_FINGERPRINT', 'str', None,
         'Session id stamped into child processes so tpu_doctor can '
         'attribute strays to the test/bench session that leaked them.'),
    Flag('SKYTPU_TIMELINE_FILE_PATH', 'path', None,
         'When set, timeline-decorated control-plane calls append '
         'Chrome-trace events to this file.'),
    # -- black-box flight recorder (observability/blackbox.py) --------
    Flag('SKYTPU_BLACKBOX', 'bool', '1',
         'Master switch for the black-box flight recorder (event ring '
         '+ incident bundles).'),
    Flag('SKYTPU_BLACKBOX_RING', 'int', '512',
         'Per-process bounded event-ring size (events kept for '
         'incident bundles).'),
    Flag('SKYTPU_BLACKBOX_DIR', 'path',
         '$SKYTPU_STATE_DIR/blackbox',
         'Incident-bundle spool directory.'),
    Flag('SKYTPU_BLACKBOX_KEEP', 'int', '32',
         'Max committed incident bundles kept (oldest pruned).'),
    # -- tracing (observability/trace.py) -----------------------------
    Flag('SKYTPU_TRACE', 'bool', '1',
         'Master switch for request tracing.'),
    Flag('SKYTPU_TRACE_SAMPLE', 'float', '1',
         'Trace sampling rate in [0, 1] for LB-minted trace ids.'),
    Flag('SKYTPU_TRACE_RING', 'int', '256',
         'Per-process in-memory ring of finished traces '
         '(/debug/traces).'),
    Flag('SKYTPU_TRACE_EXPORT', 'bool', '0',
         'Also persist finished traces to the export spool dir.'),
    Flag('SKYTPU_TRACE_EXPORT_DIR', 'path',
         '$SKYTPU_STATE_DIR/traces',
         'Trace export spool directory.'),
    Flag('SKYTPU_TRACE_EXPORT_KEEP', 'int', '512',
         'Max exported trace files kept (oldest pruned).'),
    Flag('SKYTPU_TRACE_PARENT', 'str', None,
         'Inherited trace-context header value for server-spawned '
         'request runners (keeps child spans in the parent trace).'),
    Flag('SKYTPU_TRACE_TAIL', 'bool', '1',
         'Tail-based trace retention: trace every request into a '
         'short-lived pending buffer and keep-vs-drop on a retention '
         'verdict at completion (slow/error/shed/evicted/resumed/'
         'slo_breach/recompile_storm/baseline).'),
    Flag('SKYTPU_TRACE_TAIL_RING', 'int', '128',
         'Per-process bounded ring of RETAINED (verdict-kept) '
         'traces.'),
    Flag('SKYTPU_TRACE_TAIL_KEEP', 'int', '256',
         'Max retained keep-* spool files kept (their own rotation '
         'budget — ring-overflow rotation never evicts kept traces).'),
    Flag('SKYTPU_TRACE_TAIL_PENDING', 'int', '256',
         'Max trace ids parked in the tail-pending buffer awaiting a '
         'late (LB-propagated) retention verdict.'),
    Flag('SKYTPU_TRACE_TAIL_PENDING_S', 'float', '120',
         'Tail-pending fragment lifetime before it is dropped '
         'unkept.'),
    Flag('SKYTPU_TRACE_TAIL_LATENCY_MS', 'map', None,
         "Per-QoS-class keep thresholds for end-to-end latency, e.g. "
         "'interactive:500,batch:30000' (or one bare number for every "
         'class); unset = auto-derive 2x the recent window p95.'),
    Flag('SKYTPU_TRACE_TAIL_TTFT_MS', 'map', None,
         'Per-QoS-class keep thresholds for TTFT (same syntax as '
         'SKYTPU_TRACE_TAIL_LATENCY_MS); unset = auto-derived.'),
    Flag('SKYTPU_TRACE_TAIL_BASELINE_PER_MIN', 'float', '2',
         'Budget of boring traces kept per minute as a comparison '
         'baseline (0 disables the baseline verdict).'),
    # -- serving: replica / LLM server --------------------------------
    Flag('SKYTPU_REPLICA_PORT', 'int', '8001',
         'Port a serving replica binds.'),
    Flag('SKYTPU_LLM_ENGINE', 'str', 'continuous',
         "Serving engine: 'continuous' (batching engine) or 'off' "
         '(window batching only).'),
    Flag('SKYTPU_LLM_ROLE', 'str', 'colocated',
         "Disaggregated-serving role: 'prefill', 'decode', or "
         "'colocated'."),
    Flag('SKYTPU_LLM_SLOTS', 'int', '16',
         'Engine decode slots (continuous-batch width).'),
    Flag('SKYTPU_LLM_MAX_BATCH', 'int', '32',
         'Max rows per window-path batch (engine off).'),
    Flag('SKYTPU_LLM_BATCH_WINDOW_MS', 'float', '0',
         'Window-path arrival-batching window (engine off).'),
    Flag('SKYTPU_LLM_CHUNK_STEPS', 'int', '8',
         'Decode steps fused per dispatched chunk.'),
    Flag('SKYTPU_LLM_PIPELINE', 'bool', '1',
         'Depth-1 decode dispatch pipeline (host bookkeeping overlaps '
         'device compute); 0 = serial dispatch.'),
    Flag('SKYTPU_LLM_TP', 'int', '1',
         'Tensor-parallel ways for the serving engine.'),
    Flag('SKYTPU_LLM_PREFILL_BATCH', 'int', '8',
         'Max prompts prefilled per admission group.'),
    Flag('SKYTPU_LLM_PREFILL_CHUNK', 'int', '0',
         'Chunked-prefill chunk length (0 = whole prompt). Unset, a '
         'model family that names a piece of its own takes that '
         '(models/model_ops.py: 512 for a model that carries a '
         'recurrent state between the pieces).'),
    Flag('SKYTPU_LLM_PREFIX_SHARE', 'bool', '1',
         'Copy-on-write block-level prefix sharing in the paged KV '
         'pool.'),
    Flag('SKYTPU_LLM_KV_CACHE', 'str', 'bf16',
         "KV cache dtype: 'bf16' or 'int8'."),
    Flag('SKYTPU_LLM_KV_BLOCK', 'int', '16',
         'Paged-KV block length (tokens).'),
    Flag('SKYTPU_LLM_KV_BLOCKS', 'int', '0',
         'Paged-KV pool size in blocks (0 = full capacity).'),
    Flag('SKYTPU_LLM_QUANTIZE', 'str', None,
         "Weight quantization mode for serving (e.g. 'int8')."),
    Flag('SKYTPU_LLM_DRAFT', 'path', None,
         'Draft-model checkpoint enabling speculative decoding.'),
    Flag('SKYTPU_LLM_SPEC_K', 'int', '4',
         'Speculative-decoding proposal length.'),
    Flag('SKYTPU_LLM_DRAIN_S', 'float', '30',
         'Graceful drain window before a replica exits.'),
    # -- serving: QoS gate --------------------------------------------
    Flag('SKYTPU_QOS', 'bool', '0',
         'Enable the QoS admission gate on serving replicas.'),
    Flag('SKYTPU_QOS_WEIGHTS', 'map', None,
         "Per-class weighted-fair shares, e.g. 'interactive:8,batch:2'."),
    Flag('SKYTPU_QOS_TTL_S', 'map', None,
         'Per-class queue-wait TTLs before eviction (429).'),
    Flag('SKYTPU_QOS_MAX_QUEUE', 'int', '256',
         'Aggregate admission-queue depth before shedding.'),
    Flag('SKYTPU_QOS_MAX_INFLIGHT', 'int', '0',
         'Dispatch-gate in-flight cost cap (0 = engine slot budget).'),
    Flag('SKYTPU_QOS_TENANT_RPS', 'float', '0',
         'Default per-tenant request/s quota (0 = unlimited).'),
    Flag('SKYTPU_QOS_TENANT_TPS', 'float', '0',
         'Default per-tenant generated-tokens/s quota (0 = unlimited).'),
    Flag('SKYTPU_QOS_TENANT_LIMITS', 'map', None,
         "Per-tenant quota overrides, e.g. 'alice=5/1000,bob=1/50'."),
    Flag('SKYTPU_QOS_SWEEP_S', 'float', '0.25',
         'TTL-eviction sweeper period.'),
    Flag('SKYTPU_QOS_FALLBACK_TOK_S', 'float', '100',
         'Assumed decode tok/s for Retry-After before any throughput '
         'is observed.'),
    # -- serving: fleet prefix-affinity routing -----------------------
    Flag('SKYTPU_PREFIX_AFFINITY', 'bool', '0',
         'Route /generate requests to the replica whose advertised '
         'BlockTrie summary matches the prompt head (LB + '
         'autoscalers); 0 = plain least-load routing.'),
    Flag('SKYTPU_PREFIX_SUMMARY_MAX', 'int', '64',
         'Hard cap on trie-summary entries a replica adverts in '
         '/health (deepest/hottest chains kept first).'),
    Flag('SKYTPU_PREFIX_AFFINITY_WEIGHT', 'float', '1',
         'Load-unit credit per matched chain block when scoring an '
         'affinity pick against the least-loaded replica.'),
    Flag('SKYTPU_PREFIX_AFFINITY_MAX_DETOUR', 'float', '4',
         'Max load units an affinity pick may exceed the fleet '
         'minimum by before the request spills to least-load (the '
         'hot-prefix saturation budget; also discounted from the '
         'autoscalers\' queue signal).'),
    Flag('SKYTPU_PREFIX_AFFINITY_MAX_BLOCKS', 'int', '32',
         'Leading full prompt blocks hashed per request for affinity '
         'matching.'),
    # -- serving: hierarchical KV memory (HBM -> host -> bucket) ------
    Flag('SKYTPU_KV_TIERS', 'bool', '1',
         'Tiered KV memory on the paged engine (serve/kv_tiers.py): '
         'trie eviction demotes refcount-zero chains to a host-DRAM '
         'pool and re-imports them on a later match instead of '
         'recomputing; requires prefix sharing, 0 = evictions '
         'discard as before.'),
    Flag('SKYTPU_KV_HOST_BYTES', 'int', '268435456',
         'Host-DRAM pool capacity for demoted KV chains (serialized '
         'bytes); past it the decayed-hotness LRU spills cold entries '
         'to the spill dir, or drops them when none is set.'),
    Flag('SKYTPU_KV_SPILL_DIR', 'path', None,
         'Bucket/mirror directory for spilled KV segment files '
         '(range-readable, crc32 per block, tmp-write+rename); unset '
         '= host-pool overflow is dropped, not spilled.'),
    Flag('SKYTPU_KV_FETCH_MAX', 'int', '2',
         'Max concurrent background spill-segment fetch jobs; at the '
         'bound a cold-chain admission degrades to recompute instead '
         'of parking.'),
    # -- serving: disaggregated prefill/decode ------------------------
    Flag('SKYTPU_DISAGG_STAGING', 'path', None,
         'Shared staging dir for same-host KV handoffs (payload moves '
         'as a file ref instead of HTTP bytes).'),
    Flag('SKYTPU_DISAGG_TTL_S', 'float', '60',
         'Parked-export lifetime before the prefill replica reclaims '
         'its blocks.'),
    Flag('SKYTPU_DISAGG_OFFLOAD_MIN_BYTES', 'int', '4194304',
         'Payloads below this serialize inline in /v1/kv/export; '
         'above it they park for a separate /v1/kv/fetch.'),
    # -- training / checkpointing -------------------------------------
    Flag('SKYTPU_PEAK_FLOPS', 'float', '0',
         'Per-chip peak FLOP/s for MFU in trainer telemetry (0 = MFU '
         'not reported).'),
    Flag('SKYTPU_TRAIN_TELEMETRY_DIR', 'path', None,
         'Directory the trainer drops per-step telemetry JSON into '
         '(the agent heartbeat ships it).'),
    Flag('SKYTPU_TRAIN_TELEMETRY_MAX_KB', 'int', '64',
         'Size cap for one telemetry window file.'),
    Flag('SKYTPU_CKPT_HOLD_FILE', 'path', None,
         'Crash-probe hook: while this file exists, commit_step parks '
         'mid-commit so a prober can kill -9 the process.'),
    Flag('SKYTPU_CKPT_HOLD_STEP', 'int', None,
         'Restrict SKYTPU_CKPT_HOLD_FILE parking to one step.'),
    # -- agent / multi-host gang --------------------------------------
    Flag('SKYTPU_AGENT_DIAL', 'str', 'tunnel',
         "How clients dial cluster agents: 'tunnel' (SSH) or 'direct'."),
    Flag('SKYTPU_WORKER_RANK', 'int', None,
         'Global host rank, exported to gang job processes.'),
    Flag('SKYTPU_NUM_WORKERS', 'int', None,
         'Global host count, exported to gang job processes.'),
    Flag('SKYTPU_WORKER_IPS', 'csv', None,
         'All worker IPs, exported to gang job processes.'),
    Flag('SKYTPU_NUM_SLICES', 'int', None,
         'Slice count, exported to multislice gang jobs.'),
    Flag('SKYTPU_SLICE_ID', 'int', None,
         'This host\'s slice id in a multislice gang.'),
    Flag('SKYTPU_CHIPS_PER_HOST', 'int', None,
         'Accelerator chips per host, exported to gang jobs.'),
    Flag('SKYTPU_NATIVE_GANG', 'bool', '1',
         'Use the native gangd coordinator (0 = pure-python fallback).'),
    Flag('SKYTPU_GANGD_BIN', 'path', None,
         'Prebuilt skytpu_gangd binary override (sanitizer builds, '
         'deploys without a toolchain).'),
    Flag('SKYTPU_FUSE_PROXY_BIN', 'path', None,
         'Prebuilt skytpu_fuse_proxy binary override.'),
    Flag('SKYTPU_FUSE_PROXY_SOCKET', 'path', None,
         'Control socket of a running fuse proxy (set for mounted '
         'storage jobs).'),
    Flag('SKYTPU_TERM_GRACE_S', 'float', '10',
         'SIGTERM-to-SIGKILL grace when stopping job processes.'),
    Flag('SKYTPU_REMOTE_PYTHON', 'str', 'python3',
         'Python interpreter used on provisioned hosts.'),
    # -- provisioning / clouds ----------------------------------------
    Flag('SKYTPU_ENABLE_FAKE_CLOUD', 'bool', None,
         'Enable the in-process fake cloud (tests, local dev).'),
    Flag('SKYTPU_CONTROLLER_CLOUD', 'str', 'local',
         'Cloud the managed-jobs/serve controller launches into.'),
    Flag('SKYTPU_CONTROLLER_MAX_RESTARTS', 'int', '3',
         'Controller HA restart budget before a service is marked '
         'failed.'),
    Flag('SKYTPU_ADOPTION_RETRY_S', 'float', '600',
         'HA controller retry period for adopting orphaned services.'),
    Flag('SKYTPU_SERVE_CLAIM_GRACE_S', 'float', '300',
         'Grace before a dead controller\'s service claim may be '
         'adopted.'),
    Flag('SKYTPU_SSH_USER', 'str', '$USER',
         'SSH user for the ssh_pool provisioner.'),
    Flag('SKYTPU_LOCAL_BUCKET_ROOT', 'path', None,
         'Root dir backing the local:// storage scheme.'),
    Flag('SKYTPU_GCP_ZONE', 'str', None,
         'Default GCP zone for provisioning.'),
    Flag('SKYTPU_AWS_REGION', 'str', None,
         'Default AWS region for provisioning.'),
    Flag('SKYTPU_AWS_DEFAULT_AMI', 'str', None,
         'AMI override for AWS instances.'),
    Flag('SKYTPU_AWS_SSH_USER', 'str', 'ubuntu',
         'SSH user on AWS instances.'),
    Flag('SKYTPU_AZURE_REGION', 'str', None,
         'Default Azure region for provisioning.'),
    Flag('SKYTPU_AZURE_SSH_USER', 'str', 'azureuser',
         'SSH user on Azure instances.'),
    Flag('SKYTPU_DO_SSH_USER', 'str', 'root',
         'SSH user on DigitalOcean instances.'),
    Flag('SKYTPU_GKE_NAMESPACE', 'str', None,
         'Kubernetes namespace for GKE provisioning.'),
    Flag('SKYTPU_GKE_SERVICE_TYPE', 'str', None,
         'Service type exposing GKE-provisioned endpoints.'),
    Flag('SKYTPU_K8S_NAMESPACE', 'str', None,
         'Kubernetes namespace for generic k8s provisioning.'),
    Flag('SKYTPU_K8S_SERVICE_TYPE', 'str', None,
         'Service type exposing k8s-provisioned endpoints.'),
    Flag('SKYTPU_SLURM_ALLOC_WAIT_S', 'float', '300',
         'Max wait for a Slurm allocation before giving up.'),
    # -- server metrics history ---------------------------------------
    Flag('SKYTPU_METRICS_SAMPLE_S', 'float', '15',
         'Fleet metrics-history sampling period.'),
    Flag('SKYTPU_METRICS_HISTORY_SAMPLES', 'int', '960',
         'Ring size of retained fleet metrics samples.'),
    Flag('SKYTPU_METRICS_SPOOL', 'bool', '1',
         'Persist the metrics-history ring to a JSONL spool under '
         'SKYTPU_STATE_DIR and reload it at server start (keeps the '
         'SLO slow burn-rate window across restarts).'),
    # -- runtime profiler (observability/profiler.py) -----------------
    Flag('SKYTPU_PROFILE', 'bool', '0',
         'Enable the runtime profiler: compile ledger, device-memory '
         'accounting, cold-start phase ledger (byte-parity gated).'),
    Flag('SKYTPU_PROFILE_MEM_S', 'float', '15',
         'Device-memory sampling period (daemon cadence on the API '
         'server; /health-probe rate limit on replicas).'),
    Flag('SKYTPU_PROFILE_BUDGETS', 'map', None,
         "Per-program shape-budget overrides, e.g. "
         "'generate.prefill=1,engine.paged_chunk=2' — the recompile-storm "
         'injection lever for probes/tests.'),
    # -- cold-start collapse (compile cache / AOT warm-up / restore) --
    Flag('SKYTPU_COMPILE_CACHE', 'path', None,
         'Persistent XLA compilation-cache directory (per model '
         'version, provisioned by instance_setup). A replacement '
         'replica reuses its predecessor\'s lowered programs instead '
         'of recompiling every PROGRAMS entry. '
         'JAX_COMPILATION_CACHE_DIR wins when set; unset, the cache is '
         '<checkout>/.jax_cache (utils/jax_env.py).'),
    Flag('SKYTPU_COMPILE_CACHE_MIN_S', 'float', '0',
         'Minimum compile seconds before a program is persisted to '
         'the compile cache (0 caches everything — required for the '
         'CPU-backend coldstart probe; raise on real fleets to skip '
         'trivial programs).'),
    Flag('SKYTPU_WARMUP', 'bool', '0',
         'AOT warm-up before traffic: during the dark-launch window '
         'the replica drives the steady-state shape set through every '
         'configured jit program and only starts serving once a '
         'replay round compiles nothing new (zero post-READY '
         'compiles becomes the gate).'),
    Flag('SKYTPU_WARMUP_BUCKETS', 'int', '0',
         'Cap on the number of prompt-length shape buckets warm-up '
         'drives (smallest first); 0 = every power-of-two bucket that '
         'fits max_len, bounded by the programs\' declared compile '
         'budgets.'),
    Flag('SKYTPU_WARMUP_ROUNDS', 'int', '4',
         'Max warm-up replay rounds before the replica serves anyway '
         '(coverage is then reported incomplete, not fatal).'),
    Flag('SKYTPU_CKPT_READERS', 'int', '8',
         'Reader-pool width for shard-parallel checkpoint range reads '
         '(restore streaming + deep verify).'),
    Flag('SKYTPU_SCALE_LEAD_SLOW_S', 'float', '60',
         'Spin-up lead-time estimate at or above which the request-'
         'rate autoscalers drop their upscale hysteresis to one tick '
         '(waiting compounds the unserved-demand cost of a slow cold '
         'boot).'),
    # -- SLO engine (observability/slo.py) ----------------------------
    Flag('SKYTPU_SLO', 'bool', '0',
         'Enable the SLO burn-rate alert evaluator on the API server.'),
    Flag('SKYTPU_SLO_EVAL_S', 'float', None,
         'Evaluator cadence override (default: the metrics-history '
         'sampler cadence).'),
    Flag('SKYTPU_SLO_DUMP', 'bool', '1',
         'Auto-capture black-box incident bundles (trigger slo_breach) '
         'on page-severity firing transitions.'),
    Flag('SKYTPU_SLO_HISTORY', 'int', '256',
         'Max resolved alerts kept in the persisted history.'),
    # -- serving: self-healing remediation (serve/remediation.py) -----
    Flag('SKYTPU_REMEDIATE', 'str', 'off',
         "Remediation engine mode: 'off' (default), 'observe' (decide "
         "and record without acting — dry run), 'act' (run the full "
         'migration playbooks).'),
    Flag('SKYTPU_REMEDIATE_MAX_PER_H', 'int', '6',
         'Per-service remediation budget: token bucket of actions per '
         'hour; an exhausted budget downgrades every decision to '
         'noop_observe.'),
    Flag('SKYTPU_REMEDIATE_COOLDOWN_S', 'float', '30',
         'Cooldown after each executed action before the engine will '
         'act again (observe-only decisions are exempt).'),
    Flag('SKYTPU_REMEDIATE_HYSTERESIS_S', 'float', '120',
         'Per-(rule,target) hysteresis: a trigger that already drove '
         'an action is ignored for this long — a flapping alert '
         'cannot thrash replacements.'),
    Flag('SKYTPU_REMEDIATE_PREWARM_CHAINS', 'int', '8',
         "Max hot trie chains replayed victim→successor in a "
         'drain-migrate pre-warm (0 disables the BlockTrie handoff).'),
    Flag('SKYTPU_REMEDIATE_DRAIN_TIMEOUT_S', 'float', '120',
         'Max seconds a migration waits for the LB to confirm the '
         "victim's in-flight streams drained before terminating "
         'anyway.'),
    Flag('SKYTPU_REMEDIATE_ZONE_BLOCK_S', 'float', '900',
         'TTL of a zone_blocklist action: how long successor placement '
         'avoids a preemption-stormy zone.'),
    # -- bench / probe / test harness ---------------------------------
    Flag('SKYTPU_BENCH_SWEEP_BUDGET_S', 'float', '600',
         'Wall-clock budget for one bench sweep phase.'),
    Flag('SKYTPU_LIVE_KIND', 'bool', None,
         'Opt into the live kind-cluster integration test.'),
)

NAMES = frozenset(f.name for f in FLAGS)
_BY_NAME: Dict[str, Flag] = {f.name: f for f in FLAGS}
assert len(_BY_NAME) == len(FLAGS), 'duplicate flag declaration'


def get(name: str) -> Flag:
    return _BY_NAME[name]
