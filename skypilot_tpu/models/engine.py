"""Continuous-batching decode engine (the JetStream-analog serving core).

Reference analog: the reference's headline TPU serving recipe runs
Google's JetStream (``/root/reference/examples/tpu/v6e/README.md:112-118``,
2500 tok/s baseline), whose defining design is SLOT-BASED CONTINUOUS
BATCHING: one persistent decode batch of B slots over a single resident
PAGED KV pool (models/paged.py: fixed-size blocks, a block table a
slot); arriving requests are PREFILLED in small padded groups, their
cache rows INSERTED into the blocks they reserved, and one jitted decode
step advances all slots together. Short requests drain and their slots
refill from the queue while long ones keep streaming — unlike window batching
(``serve/llm_server.py``'s legacy path), where the whole batch waits for
its slowest member before the next batch starts.

TPU-first shape discipline (everything compiles exactly once per shape):

* the slot count B and cache ``max_len`` are fixed at construction — the
  decode step is ONE compiled program for the engine's whole lifetime;
* prompts are right-padded to power-of-two buckets, bounding prefill to
  ~log2(max_len) compiled shapes;
* decode runs in K-step ``lax.scan`` chunks, amortizing the host→device
  dispatch round trip (the dominant per-step cost on a remote-attached
  chip); K=1 recovers per-token latency;
* chunks are PIPELINED one deep (``SKYTPU_LLM_PIPELINE``, default on):
  chunk N+1 is dispatched against the current slot snapshot BEFORE
  chunk N's tokens are fetched, so ``jax.device_get``, stop-token
  truncation, callback firing, slot freeing, admission, and chunked
  prefill all run while the device computes the next chunk. Safe
  because slots are static and junk rows are masked: a slot that
  finished in chunk N just decodes one discardable chunk more (the
  stale-snapshot guard drops its tokens), and reuse overwrites
  ``lengths`` at insert exactly as speculative rollback does. Depth is
  capped at ONE so a paged slot's stale-active writes always precede
  (in device program order) any insert that re-populates its released
  blocks — see ``_dispatch_chunk``. N+1 is dispatched LATE, when N is
  on its last step (``_hold_chunk``): the loop sleeps before the
  dispatch, a submit() ends the sleep, and the arrival's prefill runs
  behind N alone instead of behind an N+1 queued a chunk too soon;
* inserts scatter a group's rows into the blocks it reserved and the
  pool is donated, so steady state allocates nothing.

Freed slots keep decoding junk until reused (static shapes forbid
shrinking the batch); junk rows are masked out of MoE expert routing via
``forward_paged``'s ``active_rows`` — attention is per-row, so expert
capacity is the only cross-row coupling.

PREFIX REUSE is the pool's own (``SKYTPU_LLM_PREFIX_SHARE``, default
on; see ``__init__``): committed full prompt blocks are indexed in a
refcounted trie, and a hit is a block-table write. For models whose
rows are independent causality makes reuse exact: a prompt's first p
cache positions depend only on its first p tokens.

Sampling: per-slot temperature rides the decode step (greedy rows take
``argmax``, sampled rows ``categorical`` with a fresh per-step key).
Per-request SEEDED determinism is impossible under continuous batching
(noise depends on arrival order), so the serving layer routes seeded
requests to the window-batched path instead.

SPECULATIVE DECODING (``draft_params``/``draft_cfg`` set): each engine
iteration becomes a draft-propose / target-verify ROUND over all slots
(JetStream/vLLM-class engines run draft/verify per-slot inside the
continuous batch — r4 verdict Next #2). A parallel draft KV cache
tracks the same committed stream; per round the draft proposes
``spec_k`` greedy tokens per slot (one ``lax.scan``), the target scores
the whole window in ONE k+1-token forward (its existing multi-token
path), and acceptance is decided host-side PER SLOT — rollback is a
per-row ``lengths`` rewrite, the same never-attended-past-length
invariant decode already relies on. Greedy slots emit their accepted
prefix + the target's correction (byte-identical to the plain engine /
solo generation — the draft only changes speed); SAMPLED slots advance
exactly one token per round, drawn from the verify's position-0 logits
(= the plain decode step's logits), so temperature/top-k/top-p traffic
shares the engine instead of forcing it off. Dense targets only: MoE
expert capacity is per forward CALL, so a k+1-token verify routes
differently than sequential decode and would break greedy exactness
(same capacity-coupling reason as chunked prefill / block sharing).
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import os
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import generate as gen_lib
from skypilot_tpu.models import llama
from skypilot_tpu.models import model_ops
from skypilot_tpu.models import paged as paged_lib
from skypilot_tpu.models import sampling
# Flight recorder (observability/blackbox.py): record() is one deque
# append under its own lock — no I/O, no host sync — so the engine
# thread's admit/retire/dispatch edges are legal recording sites, and
# _fail_everything can dump the ring as an incident bundle.
from skypilot_tpu.observability import blackbox
# Compile ledger (observability/profiler.py): every jit program
# registers by name against the bounded PROGRAMS registry, making the
# compile-once-per-shape contract above machine-observable (and
# machine-gated by perf_probe --profile). With SKYTPU_PROFILE off the
# wrappers are passthroughs; on, the steady-state cost is two
# thread-local writes per dispatch — skylint host-sync stays clean.
from skypilot_tpu.observability import profiler
from skypilot_tpu.observability.profiler import profiled_jit
# Tier promote/demote spans for the trace waterfall; add_span is a
# retroactive ring append — no I/O on the engine thread.
from skypilot_tpu.observability import trace as trace_lib
from skypilot_tpu.utils import prefix_affinity as affinity_lib


class RequestTimeline:
    """Where one request's time went inside the engine: stamps on
    ``time.perf_counter()``, each ``None`` until the request reaches
    it, taken where the request changes hands.

    ``submit`` (``_build_request``) -> ``admit`` (it leaves the queue
    for good, holding its slot and blocks, or starts its chunked
    prefill) -> ``prefill`` (the dispatch of the program that computes
    its last prompt token has returned) -> ``first`` (its first token
    is handed over: on the host, just before callbacks fire) ->
    ``last`` (retirement).
    The phases between them are ``engine.queue``, ``engine.prep``,
    ``engine.first_wait`` and ``engine.decode``; the first three
    telescope to ``first - submit``. ``path`` is ``group`` / ``shared``
    / ``long`` / ``import``, ``group`` the requests in its prefill
    group, ``saved_tokens`` the prompt tokens a prefix cache served.

    The engine thread alone writes it (``submit`` excepted, stamped
    before the request is queued). Others read it once the future has
    resolved; a streaming callback may read ``first`` when called. A
    request that fails keeps the stamps it had reached."""
    __slots__ = ('submit', 'admit', 'prefill', 'first', 'last', 'path',
                 'group', 'saved_tokens')
    PHASES = (('engine.queue', 'submit', 'admit'),
              ('engine.prep', 'admit', 'prefill'),
              ('engine.first_wait', 'prefill', 'first'),
              ('engine.decode', 'first', 'last'))

    def __init__(self, submit: float):
        self.submit: float = submit
        self.admit: Optional[float] = None
        self.prefill: Optional[float] = None
        self.first: Optional[float] = None
        self.last: Optional[float] = None
        self.path: Optional[str] = None
        self.group = 0
        self.saved_tokens = 0

    def phases(self) -> List[tuple]:
        """``(span name, start, end)`` of every phase the request has
        closed, in order."""
        out = []
        for name, a, b in self.PHASES:
            t0, t1 = getattr(self, a), getattr(self, b)
            if t0 is None or t1 is None:
                break
            out.append((name, t0, t1))
        return out

    def admitted_at(self, now: float, path: str, group: int = 1) -> None:
        """The ``admit`` stamp, with the way the request went."""
        self.admit, self.path, self.group = now, path, group


class EngineFuture(concurrent.futures.Future):
    """What ``submit()`` / ``submit_prefill()`` / ``submit_import()``
    return: a ``Future`` with one attribute more, ``timeline``, the
    request's :class:`RequestTimeline`."""

    def __init__(self, timeline: RequestTimeline):
        super().__init__()
        self.timeline = timeline


@dataclasses.dataclass
class _Request:
    """Host-side bookkeeping for one prompt row occupying (at most) one
    slot. ``tokens`` accumulates emitted ids; the future resolves with
    the full list once ``max_new`` have been produced. ``on_tokens``
    (optional) is called from the ENGINE thread with each newly emitted
    batch of ids as it lands (streaming) — it must not block."""
    row: List[int]
    max_new: int
    temperature: float
    future: EngineFuture
    tokens: List[int] = dataclasses.field(default_factory=list)
    on_tokens: Optional[object] = None
    top_k: int = 0        # 0 = off
    top_p: float = 1.0    # >= 1 = off
    eos: Optional[frozenset] = None  # stop ids; None = run to max_new
    # Disaggregated serving (serve/disagg.py): an EXPORT request is the
    # prefill-role admission — it prefills normally (block reservation
    # sized to the prompt only), then retires at its first sampled
    # token with the future resolving to a PrefillHandoff instead of
    # ever decoding; the drain gathers its blocks out of the pool.
    export: bool = False
    # Hierarchical KV tiers (serve/kv_tiers.py): how many times this
    # request has parked on a background spill fetch — bounded so a
    # pathological spill state degrades to recompute, never a loop.
    tier_parks: int = 0

    @property
    def timeline(self) -> RequestTimeline:
        return self.future.timeline


@dataclasses.dataclass
class PrefillHandoff:
    """One prompt's computed KV state, host-side, ready to transfer to
    a decode-role engine (the disaggregated-serving handoff unit).

    ``k``/``v`` are [L, nb, Hkv, P, D] in pool block layout (block i
    covers prompt positions [i*P, (i+1)*P)); the last block may be
    partial — positions past ``prompt_len`` carry junk that is never
    attended. The full-block CHAIN (the trie keys) is derivable from
    ``row`` + ``block``, which is what lets shared prefixes transfer
    as references instead of bytes.
    Scale planes (``k_s``/``v_s``) present iff the KV cache is int8."""
    row: List[int]
    first: int
    max_new: int
    temperature: float
    top_k: int
    top_p: float
    eos: Optional[frozenset]
    prompt_len: int
    block: int
    n_blocks: int
    k: Optional[np.ndarray] = None
    v: Optional[np.ndarray] = None
    k_s: Optional[np.ndarray] = None
    v_s: Optional[np.ndarray] = None

    @property
    def full_blocks(self) -> int:
        """Blocks fully covered by the prompt — the shareable chain."""
        return self.prompt_len // self.block


@dataclasses.dataclass
class _ImportEntry:
    """A decode-role admission waiting for a slot + blocks: the
    imported prompt KV plus the mid-flight request state (first token
    already sampled by the prefill side). ``block_start`` is the index
    of the first prompt block present in the data arrays — earlier
    blocks were negotiated away as local trie references."""
    req: _Request
    first: int
    block_start: int = 0
    k: Optional[np.ndarray] = None
    v: Optional[np.ndarray] = None
    k_s: Optional[np.ndarray] = None
    v_s: Optional[np.ndarray] = None


@dataclasses.dataclass
class _Prefilling:
    """An in-flight incremental (chunked) long prefill. ``first`` is set
    once the final chunk has sampled the request's first token; the
    entry may then PARK awaiting a free slot."""
    req: _Request
    cache: Optional[gen_lib.KVCache] = None  # target scratch row
    consumed: int = 0                        # target tokens prefilled
    d_cache: Optional[gen_lib.KVCache] = None  # draft scratch (spec mode)
    d_consumed: int = 0
    first: Optional[jax.Array] = None
    first_host: Optional[int] = None

    @property
    def parked(self) -> bool:
        return self.first is not None


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-unfetched decode chunk: the slot snapshot it
    was dispatched against plus the device handle for its tokens. The
    snapshot is what retirement emits against — a slot freed (or
    reused) after dispatch fails the ``_slot_req[i] is req`` identity
    check and its tokens are dropped as junk."""
    reqs: List[Optional[_Request]]
    toks: jax.Array
    steps: int
    # The experts' token counts [E] of the chunk (drop-free expert
    # models; None otherwise): fetched with ``toks``, no sync of its own.
    counts: Optional[jax.Array] = None
    # What the hold of the NEXT chunk reads (``_hold_chunk``). ``start``:
    # when the host saw what precedes this chunk on the device finish
    # (the retirement before it blocked until then), or its own dispatch
    # if nothing preceded it; None when the host was behind the device
    # and cannot know. ``exact``: ``start`` is of the first kind, so the
    # chunk's length may teach the step time. ``followed``: a prefill,
    # piece or import was queued behind it, so its retirement ends later
    # than the chunk. ``held``: its successor's dispatch was held back.
    start: Optional[float] = None
    exact: bool = False
    followed: bool = False
    held: bool = False


class KVImportError(RuntimeError):
    """A transferred handoff could not be installed (e.g. blocks
    negotiated away as shared references were evicted between the
    prepare round trip and the import). The serving layer maps this to
    a 409 and falls back to colocated serving."""


# Idle engine pacing: the loop parks in _wake.wait(_IDLE_WAIT_S) when no
# slot is active — submit() sets the event, so the wait length only
# bounds how often an IDLE replica spins, not admission latency.
_IDLE_WAIT_S = 1.0

# The hold of the pipelined chunk (``ContinuousEngine._hold_chunk``).
# The chunk after the one in flight is dispatched ``lead`` before the
# one in flight should end: its last decode step's time, at least
# _HOLD_LEAD_S (the host time of ``_dispatch_chunk`` plus a late timer).
# An admission is not STARTED inside a hold with less than
# _HOLD_ADMIT_S left before the chunk's end (an admission's host time:
# its prefill has to be on the device's queue when the chunk ends, and
# it then covers the dispatch of the held chunk). A retirement's waits
# count as having blocked past _BLOCKED_S: less is the fetch of a result
# that was ready, and the host is behind the device.
_HOLD_LEAD_S = 0.004
_HOLD_ADMIT_S = 0.006
_BLOCKED_S = 0.0005


def hold_plan(start: Optional[float], step_s: Optional[float],
              steps: int) -> Optional[tuple]:
    """``(dispatch_at, admit_until)`` for the successor of a chunk of
    ``steps`` decode steps that began at ``start``, each step taking
    ``step_s``: dispatch it one ``lead`` before the chunk's estimated
    end, and start no admission later than ``admit_until``. None (no
    hold) while either is unknown: the host has to have SEEN the chunk
    begin, and a chunk end. Errs early by construction (work queued
    behind the chunk is ignored): dispatching too soon is the loop
    without the hold."""
    if start is None or step_s is None:
        return None
    end = start + steps * step_s
    dispatch_at = end - max(_HOLD_LEAD_S, step_s)
    return dispatch_at, min(dispatch_at, end - _HOLD_ADMIT_S)


def prompt_bucket(n: int, lo: int = 16) -> int:
    """Smallest power-of-two >= n (>= lo): the padded prefill width."""
    b = lo
    while b < n:
        b *= 2
    return b


_jit_sample = profiled_jit('engine.sample', sampling.sample)


def _paged_chunk_impl(cfg: llama.LlamaConfig, k_steps: int, params,
                      cache, last: jax.Array, temps: jax.Array,
                      top_ks, top_ps, active: jax.Array, key: jax.Array,
                      shard_ctx=None):
    """K decode steps over ALL slots of the paged pool
    (models/paged.py): returns (cache, last, toks[K, B]). Per-slot
    sampling params ride as data (temps 0 = greedy, top_ks 0 /
    top_ps 1 = filters off) — no recompile per request mix."""

    def step(carry, key_t):
        cache, last = carry
        logits, cache = paged_lib.forward_paged(params, last[:, None],
                                                cache, cfg, active,
                                                shard_ctx=shard_ctx)
        nxt = sampling.sample(logits, temps, key_t, top_ks, top_ps)
        return (cache, nxt), nxt

    keys = jax.random.split(key, k_steps)
    (cache, last), toks = jax.lax.scan(step, (cache, last), keys)
    return cache, last, toks


_jit_paged_chunk = profiled_jit('engine.paged_chunk', _paged_chunk_impl,
                                static_argnums=(0, 1, 10),
                                donate_argnums=(3, 4))


# skylint: allow-host-sync(top_ks/top_ps arrive as host np arrays built
# from request fields — asarray is host-to-host normalization, no device
# transfer)
def _filters_or_none(top_ks: np.ndarray, top_ps: np.ndarray):
    """None when every row's filters are off — filter_logits then skips
    the full-vocab sort on the hot decode loop entirely (the None/array
    pytree difference gives two cached jit variants)."""
    if bool(top_ks.any()) or bool((top_ps < 1.0).any()):
        return np.asarray(top_ks), np.asarray(top_ps)
    return None, None


def _insert_cache_impl(cache: gen_lib.KVCache, cache_n: gen_lib.KVCache,
                       slots: jax.Array) -> gen_lib.KVCache:
    """Scatter a prefilled N-row DRAFT cache into slots ``slots`` [N]
    (the draft cache is dense rows; the committed token stream ``last``
    is the target's). The prefill cache is only ``width`` (prompt
    bucket) positions long; only [0, width) is written — whatever the
    slot's previous occupant left beyond that is never attended
    (valid-length masking) and is progressively overwritten by decode
    writes."""
    width = cache_n.k.shape[3]
    k = cache.k.at[:, slots, :, :width].set(cache_n.k)
    v = cache.v.at[:, slots, :, :width].set(cache_n.v)
    lengths = cache.lengths.at[slots].set(cache_n.lengths)
    k_s, v_s = cache.k_s, cache.v_s
    if cache.quantized:
        k_s = k_s.at[:, slots, :, :width].set(cache_n.k_s)
        v_s = v_s.at[:, slots, :, :width].set(cache_n.v_s)
    return gen_lib.KVCache(k=k, v=v, lengths=lengths, k_s=k_s, v_s=v_s)


_jit_insert_cache = profiled_jit('engine.insert_cache',
                                 _insert_cache_impl, donate_argnums=(0,))


def _rewind_impl(cache, adj: jax.Array):
    """Per-row rollback: positions past a row's valid length are never
    attended and get overwritten, so rejecting proposals is just a
    lengths subtraction (models/speculative.py's invariant, per row).
    Works for the draft's dense KVCache and the paged pool alike."""
    return dataclasses.replace(cache, lengths=cache.lengths - adj)


_jit_rewind = profiled_jit('engine.rewind', _rewind_impl,
                           donate_argnums=(0,))


def _spec_impl(t_cfg: llama.LlamaConfig, d_cfg: llama.LlamaConfig,
               k: int, t_params, d_params, t_cache,
               d_cache: gen_lib.KVCache, last: jax.Array,
               temps: jax.Array, top_ks, top_ps, active: jax.Array,
               key: jax.Array, shard_ctx=None):
    """One speculative round over ALL slots. Returns (t_cache, d_cache,
    props [B, k+1], tgt [B, k+1], samp [B]) with BOTH caches advanced
    k+1 positions (the host rolls back per row by rewriting lengths).

    The draft runs k+1 proposal steps (the surplus step writes p_k's KV
    so a fully-accepted window leaves the draft cache complete —
    models/speculative.py's trade); the target scores the whole window
    [last, p_1..p_k] in one forward over the paged pool (multi-token
    block writes) with per-position logits. ``samp``
    is drawn from the verify's position-0 logits with each row's
    sampling params — for sampled rows one round == one plain decode
    step on exactly the logits that step would have produced."""
    b = last.shape[0]
    ones = jnp.ones((b,), jnp.int32)

    def dstep(carry, _):
        dc, tok = carry
        logits, dc = gen_lib.forward_cached(d_params, tok[:, None], dc,
                                            d_cfg, ones, active)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (dc, nxt), nxt

    (d_cache, _), props = jax.lax.scan(dstep, (d_cache, last), None,
                                       length=k + 1)
    props = props.transpose(1, 0)  # [B, k+1]
    window = jnp.concatenate([last[:, None], props[:, :k]], axis=1)
    logits_all, t_cache = paged_lib.forward_paged(
        t_params, window, t_cache, t_cfg, active,
        shard_ctx=shard_ctx, all_logits=True)
    tgt = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)  # [B, k+1]
    samp = sampling.sample(logits_all[:, 0].astype(jnp.float32), temps,
                           key, top_ks, top_ps)
    return t_cache, d_cache, props, tgt, samp


_jit_spec = profiled_jit('engine.spec_round', _spec_impl,
                         static_argnums=(0, 1, 2, 13),
                         donate_argnums=(5, 6))


class ContinuousEngine:
    """Slot server: submit() rows from any thread; a dedicated engine
    thread owns the device state and loops admit -> decode-chunk ->
    emit. See module docstring for the design."""

    # Cross-thread state: submitters append to the queues and stats()
    # (the /health endpoint) snapshots queues + counters, while the
    # engine thread mutates both. Counter bumps are grouped under the
    # lock at the few emission/retire points; engine-thread-only reads
    # carry per-line locked(...) annotations.
    _GUARDED_BY = {
        '_pending': '_lock', '_pending_imports': '_lock',
        '_admitting': '_lock', '_prefilling': '_lock',
        '_unfetched': '_lock', '_slot_req': '_lock',
        '_tier_waiting': '_lock',
        'prefills': '_lock', 'failures': '_lock',
        'prefill_chunks': '_lock',
        'share_hits': '_lock', 'share_hit_tokens': '_lock',
        'share_misses': '_lock', 'share_commits': '_lock',
        'share_evictions': '_lock', 'cow_forks': '_lock',
        'prefill_tokens': '_lock', 'prefill_tokens_saved': '_lock',
        'prefill_ms': '_lock', 'prefill_bubble_ms': '_lock',
        'chunks_run': '_lock', 'tokens_emitted': '_lock',
        'peak_active': '_lock', 'spec_rounds': '_lock',
        'spec_proposals': '_lock', 'spec_accepted': '_lock',
        'exports': '_lock', 'imports': '_lock',
        'import_errors': '_lock', 'dispatches': '_lock',
        'decode_steps': '_lock',
        'holds': '_lock', 'hold_ms': '_lock', 'hold_overruns': '_lock',
        'admits': '_lock', 'early_admits': '_lock',
        'host_overlap_ms': '_lock', 'bubble_ms': '_lock',
        '_gap_ms_total': '_lock', '_gap_count': '_lock',
        '_moe_load': '_lock',
    }

    def __init__(self, params, cfg: llama.LlamaConfig, *,
                 slots: Optional[int] = None, max_len: int = 1024,
                 chunk_steps: Optional[int] = None,
                 prefill_batch: Optional[int] = None, seed: int = 0,
                 mesh=None, rules=None,
                 kv_quantize: Optional[bool] = None,
                 prefix_slots: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 draft_params=None,
                 draft_cfg: Optional[llama.LlamaConfig] = None,
                 spec_k: Optional[int] = None,
                 kv_layout: Optional[str] = None,
                 kv_blocks: Optional[int] = None,
                 kv_block: Optional[int] = None,
                 pipeline: Optional[bool] = None,
                 prefix_share: Optional[bool] = None,
                 kv_tiers: Optional[bool] = None,
                 role: Optional[str] = None):
        self.params = params
        self.cfg = cfg
        # Everything that depends on WHICH model this is goes through
        # this row of models/model_ops.py: cache construction, the
        # jitted programs, and which features compose.
        self._ops = model_ops.ops_for(cfg)
        # Capacity-dropping experts couple co-batched rows (expert
        # capacity is per forward CALL); drop-free routing does not.
        rows_couple = self._ops.rows_couple(cfg)
        # Disaggregated serving role (serve/disagg.py): 'prefill'
        # engines mostly see export admissions (submit_prefill — retire
        # at first token with a handoff), 'decode' engines mostly see
        # imported tables (submit_import). The role is advisory — every
        # engine keeps the full capability set so the LB's colocated
        # fallback can route /generate at ANY surviving replica.
        self.role = role or os.environ.get('SKYTPU_LLM_ROLE',
                                           'colocated')
        if self.role not in ('colocated', 'prefill', 'decode'):
            raise ValueError(f'Unknown engine role {self.role!r}; '
                             "'colocated', 'prefill' or 'decode'")
        # Speculative mode (see module docstring): draft proposes,
        # target verifies, per slot, inside the continuous batch.
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError('draft_params and draft_cfg go together')
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        self.spec_k = (spec_k if spec_k is not None
                       else int(os.environ.get('SKYTPU_LLM_SPEC_K', '4')))
        if draft_cfg is not None:
            if self.spec_k < 1:
                raise ValueError(f'spec_k must be >= 1, got {self.spec_k}')
            self._ops.refuse('speculative decoding')
            if rows_couple:
                # Expert capacity is per forward CALL: a k+1-token verify
                # routes (and drops) differently than sequential decode,
                # breaking the byte-identical greedy-exactness contract
                # (same capacity coupling that disables chunked prefill
                # and block sharing for MoE).
                raise ValueError('speculative decoding requires a dense '
                                 'target (MoE expert capacity is per '
                                 'forward call; a k+1-token verify would '
                                 'break greedy exactness)')
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    'draft and target must share a vocabulary '
                    f'({draft_cfg.vocab_size} vs {cfg.vocab_size})')
        self.slots = slots or int(os.environ.get('SKYTPU_LLM_SLOTS', '16'))
        self.max_len = min(max_len, cfg.max_seq_len)
        self.chunk_steps = chunk_steps or int(
            os.environ.get('SKYTPU_LLM_CHUNK_STEPS', '8'))
        self.prefill_batch = min(
            prefill_batch or int(os.environ.get('SKYTPU_LLM_PREFILL_BATCH',
                                                '8')), self.slots)
        if kv_quantize is None:
            kv_quantize = os.environ.get('SKYTPU_LLM_KV_CACHE') == 'int8'
        self.kv_quantize = bool(kv_quantize)
        if self.kv_quantize:
            self._ops.refuse('kv_quantize')
        # ONE KV layout: slots share fixed-size blocks from a pool that
        # may be sized below slots*max_len (models/paged.py). Requests
        # reserve ceil((prompt+max_new)/block) blocks at admission and
        # QUEUE when the pool is exhausted (natural backpressure).
        # ``kv_layout`` and ``prefix_slots`` select nothing: they are
        # kept only to tell a caller that still passes the slot layout
        # or the dense prefix pool what took their place.
        if kv_layout not in (None, 'paged'):
            raise ValueError(
                f'kv_layout={kv_layout!r}: the engine keeps one KV '
                "layout, the paged pool ('paged'); size it with "
                'kv_blocks / kv_block')
        if prefix_slots not in (None, 0):
            raise ValueError(
                f'prefix_slots={prefix_slots!r}: the dense prefix pool '
                'is gone; the block trie (prefix_share, default on) is '
                'the prefix cache')
        self.kv_block = kv_block or int(
            os.environ.get('SKYTPU_LLM_KV_BLOCK', '16'))
        # Pipelined dispatch (default ON): keep one decode chunk in
        # flight so all host bookkeeping overlaps device compute (see
        # module docstring / _run_chunk). Depth 0 = the serial engine.
        if pipeline is None:
            pipeline = os.environ.get('SKYTPU_LLM_PIPELINE', '1') != '0'
        self.pipeline_depth = 1 if pipeline else 0
        if rows_couple:
            # Expert capacity is per forward CALL and couples co-batched
            # rows: an in-flight chunk runs with a slot-snapshot active
            # mask one retirement stale, so a row freed meanwhile would
            # still consume capacity and change LIVE rows' routing vs
            # the serial oracle — the same coupling that disables
            # chunked prefill and block sharing for MoE.
            self.pipeline_depth = 0
        if draft_cfg is not None:
            # Speculative rounds are host-synchronous by construction:
            # acceptance decides the rollback that shapes the next
            # round's inputs, so there is nothing to keep in flight.
            self.pipeline_depth = 0
        # Chunked prefill: prompts longer than this advance in
        # prefill_chunk-token pieces interleaved with decode chunks, so
        # long admissions don't stall every active slot's stream. Each
        # in-flight long prefill holds one scratch max_len cache row
        # (capped at 2 concurrent). Opt-in, except where the family
        # names a piece of its own (model_ops: a model that carries a
        # state between the pieces); 0 turns it off.
        if prefill_chunk is None:
            prefill_chunk = int(
                os.environ.get('SKYTPU_LLM_PREFILL_CHUNK')
                or self._ops.prefill_chunk(cfg))
        self.prefill_chunk = max(int(prefill_chunk), 0)
        if self.prefill_chunk:
            self._ops.refuse('prefill_chunk')
        if rows_couple:
            # Expert capacity is per forward CALL (token count of the
            # call), so a chunked prefill routes/drops differently than
            # the monolithic prefill the greedy-exactness oracle uses —
            # same reason block sharing is disabled for MoE below.
            self.prefill_chunk = 0
        # Where pieces go between the chunks (and the family has the
        # program for it), a chunk ENDS WITH ITS FIRST ROW TO FINISH
        # and a piece follows at least chunk_steps decode steps: a row
        # gets its last tokens at the step that makes them instead of
        # at the end of a chunk of junk steps plus a piece, and no row
        # sees more than one piece per chunk_steps of its own tokens.
        # Without pieces a chunk's tail costs a row only junk steps,
        # and the chunk stays whole (one dispatch per chunk_steps).
        self._trim_chunks = bool(self.prefill_chunk
                                 and self._ops.paged_chunk_n is not None
                                 and draft_cfg is None)
        self._steps_since_piece = self.chunk_steps
        # COPY-ON-WRITE BLOCK SHARING, the prefix cache (default ON):
        # committed full prompt blocks are indexed in a host-side trie
        # (models/paged.py BlockTrie) with per-block refcounts; a
        # matching request points its block table at the shared blocks
        # — a hit is a table write, not a KV copy — and prefills only
        # its unshared tail directly over the pool. A partially-matched
        # tail block copy-on-write-forks; eviction is refcount-aware
        # LRU over idle blocks. Independent rows only: expert capacity
        # couples co-batched rows (a busy prefill group can drop a
        # prefix token's expert routing), so shared prefix KV would
        # replay its commit-time batchmates' contention. Spec mode
        # keeps its own dense draft-cache prefill path and opts out.
        if prefix_share:
            self._ops.refuse('prefix sharing')   # asked for by name
        if prefix_share is None:
            # default ON only where the family has it
            prefix_share = (os.environ.get('SKYTPU_LLM_PREFIX_SHARE',
                                           '1') != '0'
                            and 'prefix sharing' not in self._ops.refuses)
        self.prefix_share = (bool(prefix_share)
                             and not rows_couple
                             and draft_cfg is None)
        # Fleet prefix-affinity advert (utils/prefix_affinity.py): hard
        # entry bound on the trie summary /health ships — the replica
        # probe stores health bodies whole-or-nothing under a 16 KiB
        # cap, so an unbounded advert would blank the ENTIRE health
        # snapshot exactly on the warmed replicas affinity needs.
        self._summary_max = max(
            int(os.environ.get('SKYTPU_PREFIX_SUMMARY_MAX', '64')), 0)
        # Sharded serving (JetStream serves 8B+ models sharded the same
        # way): with a mesh, weights are placed by the training stack's
        # logical rules (tensor axis -> heads/mlp/vocab, i.e. classic TP)
        # and the KV pool shards its kv_heads; every jitted engine fn
        # then compiles to an SPMD program — XLA inserts the collectives.
        self.mesh = mesh
        self.rules = rules
        self._shard_ctx = None
        if mesh is not None:
            self._ops.refuse('tensor parallelism')
            from skypilot_tpu.models import quantization as quant_lib
            from skypilot_tpu.parallel import sharding as sharding_lib
            self.rules = rules or sharding_lib.ShardingRules()
            self.params = quant_lib.shard_params(params, cfg, mesh,
                                                 self.rules)
            if self.draft_params is not None:
                # Draft rides the same TP mesh (its kv_heads must divide
                # the tensor axis like the target's do).
                self.draft_params = quant_lib.shard_params(
                    self.draft_params, self.draft_cfg, mesh, self.rules)
            self._kv_sharding = sharding_lib.logical_sharding(
                mesh, self.rules,
                ('layers', 'batch', 'kv_heads', None, 'head_dim'))
            self._kv_scale_sharding = sharding_lib.logical_sharding(
                mesh, self.rules, ('layers', 'batch', 'kv_heads', None))
            self._vec_sharding = sharding_lib.logical_sharding(
                mesh, self.rules, ('batch',))
            # The pool's writes, reads and decode kernel run per head
            # shard under TP via shard_map (generate.kernel_shard_ctx).
            self._shard_ctx = gen_lib.kernel_shard_ctx(mesh, self.rules)
        # Pool size (INCLUDING the junk-sink block 0): default is full
        # capacity — no saving, always safe; deployments size it down
        # (that's the point) and admission backpressures.
        self.kv_blocks = kv_blocks or (
            self.slots * (self.max_len // self.kv_block) + 1)
        # Spec mode reserves window overhang below max_len: a verify may
        # write k+1 positions past the last committed one before its
        # tail rolls back, and a clamped out-of-range write would smear
        # junk over real KV (same clamping hazard as chunked prefill).
        self._submit_max = self.max_len - (
            self.spec_k + 1 if self.draft_cfg is not None else 0)
        # HIERARCHICAL KV TIERS (serve/kv_tiers.py; default ON where
        # the share trie runs): evicted refcount-zero chains DEMOTE to
        # a bounded host-DRAM pool instead of being discarded, cold
        # host entries SPILL to SKYTPU_KV_SPILL_DIR segment files, and
        # _admit consults the tier index before declaring a miss — a
        # demoted chain re-imports (jit_import_blocks) instead of
        # recomputing its prefill. Host/spill state lives entirely off
        # device; a corrupt entry quarantines and the request
        # recomputes, so tiering can never fail a request.
        if kv_tiers:
            self._ops.refuse('kv_tiers')     # asked for by name
        if kv_tiers is None:
            # default ON only where the family has it
            kv_tiers = (os.environ.get('SKYTPU_KV_TIERS', '1') != '0'
                        and 'kv_tiers' not in self._ops.refuses)
        self._kv_tiers = None
        if kv_tiers and self.prefix_share:
            from skypilot_tpu.serve import kv_tiers as kv_tiers_lib
            self._kv_tiers = kv_tiers_lib.KVTiers.from_env(
                cfg, self.kv_block, quantized=self.kv_quantize)
        # Requests parked on a background spill->host fetch; the fetch
        # completion re-queues them at the head of _pending.
        self._tier_waiting: List[_Request] = []
        self._init_device_state()
        self._slot_req: List[Optional[_Request]] = [None] * self.slots
        self._pending: collections.deque = collections.deque()
        self._pending_imports: collections.deque = collections.deque()
        self._unfetched: List[tuple] = []  # [(reqs, firsts-device-array)]
        self._admitting: List[_Request] = []  # mid-prefill group
        self._prefilling: List[_Prefilling] = []
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._key = jax.random.PRNGKey(seed)
        # Pipeline state: at most ONE dispatched-but-unfetched chunk.
        self._inflight: Optional[_Inflight] = None
        self._last_dispatch_t: Optional[float] = None
        self._no_flight_since: Optional[float] = None
        # The hold of the next chunk (_hold_chunk): what a decode step
        # took in the last chunks whose start and end the host both saw
        # (the estimate is the least of them: early, never late), and
        # whether the loop is running its top again from inside a hold.
        self._step_s: collections.deque = collections.deque(maxlen=3)
        self._early = False
        # Stats (read by /health).
        self.prefills = 0
        self.failures = 0  # _fail_everything trips
        self.prefill_chunks = 0
        # Block-share accounting (prefix_share; see stats()).
        self.share_hits = 0
        self.share_hit_tokens = 0
        self.share_misses = 0
        self.share_commits = 0
        self.share_evictions = 0
        self.cow_forks = 0
        # Prefill cost counters: real prompt tokens the prefill
        # actually computed vs tokens skipped via shared prefix KV —
        # the probe's >= 40% savings gate reads these.
        self.prefill_tokens = 0
        self.prefill_tokens_saved = 0
        self.prefill_ms = 0.0
        self.prefill_bubble_ms = 0.0  # prefill host time decode waited on
        self.chunks_run = 0
        self.tokens_emitted = 0
        self.peak_active = 0
        self.spec_rounds = 0
        self.spec_proposals = 0
        self.spec_accepted = 0
        # KV handoff accounting (disaggregated serving).
        self.exports = 0
        self.imports = 0
        self.import_errors = 0
        # Overlap observability (see stats()['pipeline']): host work
        # done while a chunk computes vs host time the device provably
        # idled with work waiting (the serial-mode bubble).
        self.dispatches = 0
        self.decode_steps = 0  # chunk_steps a dispatch, fewer if trimmed
        # The hold (stats()['pipeline']): chunks whose successor was
        # held back, the time slept so, the holds after which the chunk
        # had already ended (the device may have idled); requests
        # admitted, and those admitted from inside a hold.
        self.holds = 0
        self.hold_ms = 0.0
        self.hold_overruns = 0
        self.admits = 0
        self.early_admits = 0
        self.host_overlap_ms = 0.0
        self.bubble_ms = 0.0
        self._gap_ms_total = 0.0
        self._gap_count = 0
        # Tokens each expert took in decode chunks (drop-free expert
        # models; stays None otherwise), summed from what the chunks
        # return.
        self._moe_load: Optional[np.ndarray] = None

    # -- public API (any thread) ------------------------------------------

    def submit(self, row: List[int], max_new: int,
               temperature: float = 0.0, on_tokens=None,
               top_k: int = 0, top_p: float = 1.0,
               eos=None) -> EngineFuture:
        req = self._build_request(row, max_new, temperature, on_tokens,
                                  top_k, top_p, eos)
        with self._lock:
            self._pending.append(req)
        self.start()  # idempotent; revives a stop()ped engine
        self._wake.set()
        return req.future

    def submit_prefill(self, row: List[int], max_new: int,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0,
                       eos=None) -> EngineFuture:
        """Prefill-role admission: compute the prompt's KV, sample the
        first token, and RETIRE — the future resolves with a
        ``PrefillHandoff`` a decode-role engine can import
        (``submit_import``). ``max_new`` is the downstream ask and only
        rides the handoff; this engine reserves blocks for the prompt
        alone. Dense targets only in the exactness sense that matters:
        MoE expert capacity couples co-batched rows, so exported KV
        would replay its batchmates' contention on a different replica
        — same reason block sharing refuses MoE."""
        self._ops.refuse('KV handoff')
        if self._ops.rows_couple(self.cfg):
            raise ValueError('KV handoff requires a dense model (MoE '
                             'expert capacity is per forward call, so '
                             'exported prompt KV is not batch-'
                             'independent)')
        if self.draft_cfg is not None:
            raise ValueError('KV handoff does not compose with '
                             'speculative decoding (the draft cache '
                             'does not transfer)')
        req = self._build_request(row, max_new, temperature, None,
                                  top_k, top_p, eos, export=True)
        with self._lock:
            self._pending.append(req)
        self.start()
        self._wake.set()
        return req.future

    def submit_import(self, row: List[int], max_new: int, first: int,
                      *, temperature: float = 0.0, top_k: int = 0,
                      top_p: float = 1.0, eos=None, on_tokens=None,
                      block_start: int = 0, k=None, v=None, k_s=None,
                      v_s=None) -> EngineFuture:
        """Decode-role admission of an imported prompt: install the
        transferred KV (block scatter + table install), emit ``first``
        as the request's first token, and resume continuous decode.
        Backpressures exactly like local admission — entries queue
        until a slot and the full block reservation are allocatable."""
        self._ops.refuse('KV handoff')
        if self._ops.rows_couple(self.cfg) or self.draft_cfg is not None:
            raise ValueError('KV handoff requires a dense, '
                             'non-speculative engine')
        if ((k_s is not None) != self.kv_quantize) and k is not None:
            raise ValueError('handoff KV quantization does not match '
                             'the engine kv_cache mode')
        # Plane-shape validation HERE, synchronously: an install that
        # raises on the engine thread fails every in-flight request
        # (_fail_everything blast radius), so a shape-skewed payload —
        # header corruption survives crc32, which covers plane bytes
        # only — must be rejected before it is ever enqueued.
        cfg = self.cfg
        p = self.kv_block
        nb_prompt = -(-len(row) // p)
        nb_present = nb_prompt - int(block_start)
        if nb_present < 0:
            raise ValueError(
                f'handoff block_start {block_start} exceeds the '
                f'prompt chain ({nb_prompt} blocks)')
        want = (cfg.n_layers, nb_present, cfg.n_kv_heads, p,
                cfg.head_dim)
        if nb_present > 0:  # == 0: full local prefix share, no planes
            if k is None or v is None \
                    or tuple(k.shape) != want or tuple(v.shape) != want:
                raise ValueError(
                    f'handoff k/v planes must be {want}, got '
                    f'{None if k is None else tuple(k.shape)} / '
                    f'{None if v is None else tuple(v.shape)}')
            if self.kv_quantize and (
                    k_s is None or v_s is None
                    or tuple(k_s.shape) != want[:-1]
                    or tuple(v_s.shape) != want[:-1]):
                raise ValueError(
                    f'handoff k_s/v_s scale planes must be {want[:-1]}')
        req = self._build_request(row, max_new, temperature, on_tokens,
                                  top_k, top_p, eos)
        entry = _ImportEntry(req=req, first=int(first),
                             block_start=int(block_start),
                             k=k, v=v, k_s=k_s, v_s=v_s)
        with self._lock:
            self._pending_imports.append(entry)
        self.start()
        self._wake.set()
        return req.future

    def probe_chain(self, row: List[int]) -> int:
        """How many leading FULL prompt blocks of ``row`` this engine's
        share trie already holds — the handoff negotiation answer that
        lets the transfer skip those blocks' bytes. Touches the matched
        nodes (LRU refresh) so eviction is unlikely to race the import
        that follows; a race that still loses simply fails the import
        and falls back."""
        if self._trie is None:
            return 0
        p = self.kv_block
        with self._lock:
            nodes, _, _ = self._trie.match(row, limit=(len(row) // p) * p)
            for nd in nodes:
                self._trie.touch(nd)
        return len(nodes)

    def resolve_chains(self, digests: List[bytes]) -> List[List[int]]:
        """Token rows for the advert chain digests this engine's trie
        still holds (``BlockTrie.resolve_chains``), longest first. The
        remediation pre-warm path asks the VICTIM to resolve its own
        last affinity advert back to concrete prompts, then replays
        them through the skytpu-kv/1 export/import path so the
        successor's trie starts hot. Empty when sharing is off. With
        hierarchical tiers on, digests the trie no longer holds resolve
        from the host/spill index too — a drain-migrate carries the
        long tail, not just the HBM-hot head."""
        if self._trie is None:
            return []
        with self._lock:
            rows = self._trie.resolve_chains(digests)
        if self._kv_tiers is not None:
            missing = [d for d in digests if d not in rows]
            if missing:
                rows.update(self._kv_tiers.resolve_rows(missing))
        return sorted(rows.values(), key=len, reverse=True)

    def prefix_summary(self) -> Optional[dict]:
        """Bounded resident-chain summary for fleet prefix-affinity
        routing (``BlockTrie.summary``), or None when sharing is off.
        Shipped in the /health body (serve/llm_server.py) and pushed by
        the controller into the LB's ``PrefixAffinityPolicy`` the same
        way queue pressure is. Tier-resident chains ride along as
        3-element ``[chain_hex, depth, tier]`` rows (1 = host, 2 =
        spilled; plain 2-element rows stay HBM) so the LB can prefer
        HBM over host over bucket over recompute."""
        if self._trie is None:
            return None
        with self._lock:
            summ = self._trie.summary(self._summary_max)
        if self._kv_tiers is not None:
            have = {e[0] for e in summ['entries']}
            room = self._summary_max - len(summ['entries'])
            extra, trunc = self._kv_tiers.advert_entries(room, have)
            summ['entries'].extend(extra)
            summ['truncated'] = bool(summ['truncated'] or trunc)
            summ['tiers'] = True
        return summ

    def _build_request(self, row, max_new, temperature, on_tokens,
                       top_k, top_p, eos, export: bool = False
                       ) -> _Request:
        """Validation + construction shared by submit() and the SPMD
        engine's collective-arrival path (serve/spmd.py). Export
        requests validate against the PROMPT footprint only (they
        retire at the first token; max_new is spent downstream)."""
        budget = 1 if export else max_new
        if len(row) + budget > self._submit_max:
            extra = ('' if self._submit_max == self.max_len else
                     f' (max_len {self.max_len} minus the speculative '
                     f'verify window overhang {self.spec_k + 1})')
            raise ValueError(
                f'prompt ({len(row)}) + max_new ({budget}) exceeds '
                f'engine max_len limit {self._submit_max}{extra}')
        if max_new > 1 or export:
            need = self._blocks_for(len(row), budget)
            if need > self.kv_blocks - 1:
                # Bigger than the WHOLE pool: admission could never
                # succeed — the request would stall itself and starve
                # everything queued behind it (review finding).
                raise ValueError(
                    f'request needs {need} KV blocks but the pool has '
                    f'only {self.kv_blocks - 1}; raise kv_blocks or '
                    'shrink prompt+max_new')
        if top_k < 0 or not 0.0 < top_p <= 1.0:
            # top_p <= 0 would mask EVERY token and degenerate to
            # uniform-random ids — reject like the HTTP layer does.
            raise ValueError('top_k must be >= 0 and top_p in (0, 1]')
        if eos is not None and not isinstance(eos, frozenset):
            # (the HTTP layer already normalizes; don't re-build)
            eos = frozenset([eos] if isinstance(eos, int) else
                            (int(t) for t in eos))
        fut = EngineFuture(RequestTimeline(time.perf_counter()))
        # Engine futures are UNCANCELLABLE (state RUNNING from birth): a
        # client disconnect cancelling a PENDING future would flip it
        # done, making the emission loop skip the slot forever (slot +
        # paged-block leak) — and on a multi-host replica only the
        # head's future would cancel, desynchronizing the ranks'
        # slot state (review finding). The request simply runs to
        # completion with nobody reading the result.
        fut.set_running_or_notify_cancel()
        return _Request(list(row), max_new, float(temperature), fut,
                        on_tokens=on_tokens, top_k=int(top_k),
                        top_p=float(top_p), eos=eos, export=export)

    def start(self) -> None:
        # Under the lock: two first-submitters racing here must not both
        # spawn a loop thread (two loops would mutate the one donated
        # device cache concurrently).
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop = False
                self._thread = threading.Thread(
                    target=self._loop, daemon=True,
                    name='skytpu-decode-engine')
                self._thread.start()

    def stop(self) -> None:
        self._stop = True
        self._wake.set()
        if self._kv_tiers is not None:
            # Tier worker first: a fetch completing after the loop
            # thread dies would re-queue its parked requests into a
            # _pending nobody drains — stopping the worker makes the
            # _tier_waiting sweep below authoritative.
            self._kv_tiers.stop()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                return  # wedged mid-chunk; don't race its state
        # The loop thread is gone: anything still queued or occupying a
        # slot would otherwise wait FOREVER — the HTTP streaming handler
        # blocks on these futures, so a decode replica killed mid-stream
        # must fail fast for the LB to resume the request on a survivor.
        with self._lock:
            live = bool(self._pending or self._pending_imports
                        or self._admitting or self._prefilling
                        or self._unfetched or self._tier_waiting
                        or any(r is not None for r in self._slot_req))
        if live:
            self._fail_everything(RuntimeError('engine stopped'))

    def stats(self) -> dict:
        with self._lock:
            active = sum(r is not None for r in self._slot_req)
            queued = len(self._pending)
            queued_imports = len(self._pending_imports)
            # ONE read: the block states must agree within a snapshot
            # (free + owned + shared + cached == usable), or the
            # dashboard can render an impossible state mid-admission.
            free_blocks = len(self._free_blocks)
            owned_blocks = sum(len(b) for b in self._slot_blocks)
            shared_blocks = cached_blocks = 0
            if self._trie is not None:
                shared_blocks = self._trie.referenced
                cached_blocks = self._trie.reclaimable
            # Tier snapshot in the SAME critical section as the pool
            # states (lock order engine -> tiers): host/spilled must
            # agree with the kv_tiers block they summarize.
            tier_stats = None
            if self._kv_tiers is not None:
                tier_stats = self._kv_tiers.stats()
                tier_stats['waiting'] = len(self._tier_waiting)
            # skylint finding (guarded-by): this return used to sit
            # OUTSIDE the with-block — every counter below was read
            # unlocked while the engine thread bumps them, so /health
            # could see a snapshot where e.g. queue state and the
            # token/prefill counters disagree mid-emission. The whole
            # snapshot now builds under the lock.
            load = (None if self._moe_load is None
                    else self._moe_load.tolist())
            return {'slots': self.slots, 'active_slots': active,
                'kv_cache': 'int8' if self.kv_quantize else 'bf16',
                # What one token costs the cache over all layers, by
                # the family's own count (a latent cache: c_kv | k_rope).
                'kv_bytes_per_token': self._ops.kv_bytes_per_token(
                    self.cfg),
                # What one SEQUENCE costs beside its tokens, whatever
                # its length: a recurrent state a slot (0: none).
                'state_bytes_per_slot': self._ops.state_bytes_per_slot(
                    self.cfg),
                # Drop-free expert models: (token, choice) pairs the
                # decode chunks routed, and the busiest and the mean
                # expert's share of them (None: no such experts).
                'moe_tokens_routed': load and sum(load),
                'moe_expert_load_max': load and max(load),
                'moe_expert_load_mean': load and sum(load) / len(load),
                'moe_expert_load': load,
                'kv_layout': 'paged',  # the one there is
                # How the decode step reads K/V: 'paged_kernel'
                # (through the block table, by length) or 'gather'
                # (every slot's whole max_len into a dense view).
                'decode_attention': self.decode_attention,
                # The family's further rules for its decode program,
                # each under its own key (a recurrent state's one-token
                # step: 'kernel' or 'xla'); none for most.
                **self._step_paths,
                # Handoff accounting (serve/disagg.py): exports are
                # prefill-role retirements, imports are decode-role
                # admissions of transferred tables; queued_imports is
                # the decode pool's admission backpressure signal.
                'disagg': {'exports': self.exports,
                           'imports': self.imports,
                           'import_errors': self.import_errors,
                           'queued_imports': queued_imports},
                'kv_blocks': {
                    'total': self.kv_blocks, 'block': self.kv_block,
                    'free': free_blocks,
                    # used/usable are authoritative here (block 0 is
                    # the junk sink): consumers must not re-derive the
                    # convention (review finding). With block sharing,
                    # physical non-free blocks split into owned
                    # (slot-exclusive), shared (trie-committed,
                    # refcounted by >= 1 live slot), and cached (idle
                    # refs-0, reclaimable by LRU eviction); the states
                    # partition exactly — the old used = total-1-free
                    # would double-count a block every time two slots
                    # reference it.
                    'usable': self.kv_blocks - 1,
                    'used': self.kv_blocks - 1 - free_blocks,
                    'owned': owned_blocks,
                    'shared': shared_blocks,
                    'cached': cached_blocks,
                    # Hierarchical tiers (serve/kv_tiers.py): block
                    # counts held OFF-DEVICE per tier. These are NOT
                    # part of the device-pool partition (a demoted
                    # block's device id is back on the free list) —
                    # free+owned+shared+cached still sums to usable
                    # exactly, and host/spilled must reconcile with
                    # the kv_tiers stats block below.
                    'host': (tier_stats['host_blocks']
                             if tier_stats else 0),
                    'spilled': (tier_stats['spilled_blocks']
                                if tier_stats else 0)},
                'kv_tiers': tier_stats,
                'queued': queued, 'prefills': self.prefills,
                'failures': self.failures,
                'prefill_chunks': self.prefill_chunks,
                'prefilling': len(self._prefilling),
                'chunks_run': self.chunks_run,
                'tokens_emitted': self.tokens_emitted,
                'peak_active_slots': self.peak_active,
                # Decode-dispatch pipeline: depth 1 = one chunk kept in
                # flight (host bookkeeping overlaps device compute);
                # depth 0 = serial (MoE / speculative / opted out).
                # host_overlap_ms and bubble_ms are CUMULATIVE;
                # dispatch_gap_ms is the mean host-side gap between
                # consecutive chunk dispatches.
                'pipeline': {
                    'pipeline_depth': self.pipeline_depth,
                    'dispatches': self.dispatches,
                    'decode_steps': self.decode_steps,
                    'dispatch_gap_ms': round(
                        self._gap_ms_total / max(self._gap_count, 1),
                        3),
                    'host_overlap_ms': round(self.host_overlap_ms, 3),
                    'bubble_ms': round(self.bubble_ms, 3),
                    # The hold of the next chunk (_hold_chunk): chunks
                    # whose successor's dispatch was held back, the
                    # time slept so (cumulative), admissions made from
                    # inside a hold (their prefill runs before the
                    # held chunk), and holds that ended after their
                    # chunk had (the device may have idled).
                    'holds': self.holds,
                    'hold_ms': round(self.hold_ms, 3),
                    'early_admits': self.early_admits,
                    'hold_overruns': self.hold_overruns},
                # Of all admissions since start, the share made from
                # inside a hold, in %.
                'early_admit_share': round(
                    100.0 * self.early_admits / max(self.admits, 1), 2),
                'speculative': None if self.draft_cfg is None else {
                    'rounds': self.spec_rounds,
                    'proposals': self.spec_proposals,
                    'acceptance_rate': (
                        self.spec_accepted / self.spec_proposals
                        if self.spec_proposals else 0.0)},
                # Copy-on-write block sharing (see the ctor comment).
                # prefill_tokens is the prompt tokens prefill actually
                # COMPUTED across all paths; prefill_tokens_saved is
                # what shared prefix KV skipped — the pair the
                # perf_probe --prefix savings gate reads. prefill_bubble_ms is cumulative prefill
                # host time decode provably waited on.
                'prefix_share': {
                    'enabled': self.prefix_share,
                    'hits': self.share_hits,
                    'hit_tokens': self.share_hit_tokens,
                    'misses': self.share_misses,
                    'hit_rate': round(
                        self.share_hits
                        / max(self.share_hits + self.share_misses, 1), 4),
                    'commits': self.share_commits,
                    'evictions': self.share_evictions,
                    'cow_forks': self.cow_forks},
                'prefill_tokens': self.prefill_tokens,
                'prefill_tokens_saved': self.prefill_tokens_saved,
                'prefill_ms': round(self.prefill_ms, 3),
                'prefill_bubble_ms': round(self.prefill_bubble_ms, 3)}

    # -- engine thread -----------------------------------------------------

    # skylint: engine-thread, hot-path
    def _loop(self) -> None:
        while not self._stop:
            try:
                # Prefill advance BEFORE admission: a parked finished
                # prefill must win a freed slot over younger shorts.
                t0 = time.perf_counter()
                self._advance_prefill()
                # Imported prompts admit FIRST: their prefill compute
                # is already spent on the prefill pool — parking them
                # behind younger local admissions would strand paid-for
                # work (they do NOT block local admission when parked:
                # the colocated-fallback traffic a decode replica also
                # serves must keep flowing).
                self._admit_imports()
                self._admit()
                if self._inflight is not None:
                    # Prefill/admission dispatches issued while a chunk
                    # computes are pure overlap — the host work this
                    # pipeline exists to hide.
                    with self._lock:
                        self.host_overlap_ms += \
                            (time.perf_counter() - t0) * 1e3
                # skylint: locked(engine thread is the sole slot-table
                # mutator; a stale read here only delays one loop turn)
                if not any(r is not None for r in self._slot_req):
                    # Every request in a still-in-flight chunk's
                    # snapshot is done by now (a live one would occupy
                    # its slot), so the flush just drops junk tokens.
                    self._flush_pipeline(quiet=True)
                    self._drain_firsts()  # e.g. all-max_new==1 traffic
                    self._note_decode_quiet()
                    # skylint: locked(only the engine thread appends or
                    # retires _prefilling entries; emptiness is stable)
                    if self._prefilling:
                        continue  # keep chunking the long prompt
                    # Long wait, event-paced: submit() sets _wake, and
                    # the loop re-checks _pending at the top either
                    # way, so a sleeping replica admits immediately
                    # instead of burning a core on a 50 ms poll.
                    self._idle_wait(_IDLE_WAIT_S)
                    continue
                with self._lock:
                    only_exports = all(r is None or r.export
                                       for r in self._slot_req)
                if only_exports:
                    # Prefill-role steady state: every occupied slot is
                    # an export awaiting its drain — a decode chunk
                    # over them would be pure junk compute. Drain (which
                    # serializes + retires them) and admit again.
                    self._flush_pipeline(quiet=True)
                    self._drain_firsts()
                    continue
                if self.draft_cfg is not None:
                    self._run_spec_round()
                    continue
                # With a chunk in flight, sleep HERE until it is nearly
                # done, not in its retirement after the next one is
                # already queued behind it: a submit() that ends the
                # sleep is admitted by the top of the loop now, and its
                # prefill runs before the chunk still held back.
                self._early = self._hold_chunk()
                if not self._early:
                    self._run_chunk()
            except Exception as exc:  # noqa: BLE001 — fail all waiters
                # Fail in-flight work, rebuild device state, KEEP LOOPING:
                # the failed call may have consumed the donated cache
                # ("Array has been deleted" on reuse), and exiting the
                # thread would strand any request submitted between the
                # doomed-snapshot and the thread's death (its submitter
                # saw a live thread, so never revived one).
                self._fail_everything(exc)
                self._wake.wait(0.1)
                self._wake.clear()

    def _idle_wait(self, timeout: float) -> bool:
        """Nothing to do until a submit() or ``timeout``; whether it was
        a submit()."""
        with profiler.span('engine.idle'):
            woke = self._wake.wait(timeout)
        self._wake.clear()
        return woke

    # skylint: engine-thread
    def _hold_chunk(self) -> bool:
        """Hold the next decode chunk back until the one in flight (k)
        is nearly done. True: a submit() ended the sleep with time left
        (or a long prompt just admitted has its first piece due), and
        the caller runs the top of the loop again with k+1 still held
        back, so what it admits is dispatched behind k alone (device
        order k, prefill, k+1). False: dispatch k+1 now.

        Admissions always happened in this interval (after k-1's
        retirement, before k+1's dispatch, with k dispatched): the hold
        only stretches it in host time, so ``_dispatch_chunk``'s safety
        argument stands as written and the programs and their order per
        request are the loop's without it. No hold where it could not
        help or the loop cannot tell when k ends: nothing in flight
        (always so at depth 0 and with a draft), a retirement before k
        that did not block (``flight.start`` None: the host is behind
        the device), no chunk timed yet, no free slot or no block to
        allocate (nothing could be admitted). The SPMD lockstep loop
        (serve/spmd.py) drives ``_run_chunk`` itself and never holds."""
        flight = self._inflight
        if flight is None:
            return False
        plan = hold_plan(flight.start, min(self._step_s, default=None),
                         flight.steps)
        if plan is None:
            return False
        deadline, admit_until = plan
        with self._lock:
            parked = sum(1 for e in self._prefilling if e.parked)
            free = sum(1 for r in self._slot_req if r is None)
            room = free > parked and self._blocks_avail() > 0
        if not room:
            return False
        while True:
            left = deadline - time.perf_counter()
            if left <= 0:
                return False
            if time.perf_counter() < admit_until and self._piece_due():
                return True  # a long prompt admitted just now
            if not flight.held:
                flight.held = True
                with self._lock:
                    self.holds += 1
            t0 = time.perf_counter()
            woke = self._idle_wait(left)
            ms = (time.perf_counter() - t0) * 1e3
            with self._lock:
                self.hold_ms += ms
            blackbox.record('engine.hold', ms=round(ms, 3), woke=woke)
            if woke and time.perf_counter() < admit_until:
                return True

    # skylint: engine-thread
    def _fail_everything(self, exc: Exception) -> None:
        with self._lock:
            doomed = list(self._pending) + [
                r for r in self._slot_req if r is not None] + [
                r for reqs, _ in self._unfetched for r in reqs] + \
                list(self._admitting) + [p.req for p in self._prefilling] \
                + [e.req for e in self._pending_imports] \
                + list(self._tier_waiting)
            self._pending.clear()
            self._pending_imports.clear()
            self._tier_waiting = []
            self._slot_req = [None] * self.slots
            self._unfetched = []
            self._admitting = []
            self._prefilling = []
            # Drop the in-flight chunk with the device state: its toks
            # handle chains off buffers the failed dispatch may have
            # consumed, and its snapshot requests are all in the doomed
            # list (or already resolved) via _slot_req.
            self._inflight = None
            self._last_dispatch_t = None
            self._no_flight_since = None
            self._early = False
            self.failures += 1
        for req in doomed:  # dupes are safe: first set_exception wins
            if not req.future.done():
                req.future.set_exception(exc)
        # Black box: the failure cause and blast radius go on the ring,
        # then the whole ring (plus stacks/traces/health) freezes into
        # an incident bundle — the post-mortem for every stream this
        # failure just killed. Waiters were failed FIRST (dump does
        # file I/O); device-state rebuild runs after, so a rebuild
        # crash cannot lose the evidence of the original fault.
        blackbox.record('engine.fail', cause=repr(exc)[:200],
                        doomed=len(doomed))
        blackbox.dump('engine_failure', reason=repr(exc)[:200])
        # Fresh device state: the failed dispatch may have already
        # consumed (donation) or half-written the old buffers.
        self._init_device_state()

    def _init_device_state(self) -> None:
        # Born sharded under a mesh: on a replica sized so the cache only
        # fits spread over the slice, a transient single-device
        # allocation would OOM chip 0 — at construction AND at every
        # _fail_everything recovery. (Shardings are None single-device.)
        vec = kv = kv_s = pool_kv = pool_s = None
        if self.mesh is not None:
            vec, kv, kv_s = (self._vec_sharding, self._kv_sharding,
                             self._kv_scale_sharding)
            # The pool shards on kv_heads over the tensor axis (the
            # same plane as the draft's dense cache); block tables stay
            # replicated — scatter/gather index replicated dims only,
            # so the pool ops partition with no collectives.
            from skypilot_tpu.parallel import sharding as sharding_lib
            pool_kv = sharding_lib.logical_sharding(
                self.mesh, self.rules,
                ('layers', None, 'kv_heads', None, 'head_dim'))
            pool_s = sharding_lib.logical_sharding(
                self.mesh, self.rules,
                ('layers', None, 'kv_heads', None))
        self._cache = self._ops.init_pool(
            self.cfg, self.slots, self.max_len, self.kv_blocks,
            self.kv_block, quantize=self.kv_quantize,
            kv_sharding=pool_kv, scale_sharding=pool_s,
            lengths_sharding=vec)
        # Host-side accounting: block 0 is the junk sink, never
        # allocated; per-slot block lists return to the free list when
        # the slot's request completes. With block sharing,
        # _slot_blocks holds only the slot's OWNED blocks; shared
        # (trie-committed, refcounted) blocks live in _slot_shared.
        self._free_blocks = list(range(1, self.kv_blocks))
        self._slot_blocks: List[List[int]] = [
            [] for _ in range(self.slots)]
        self._slot_shared = [[] for _ in range(self.slots)]
        # The slot's INSTALLED table row (host copy): exports
        # reconstruct the exact device table from it — deriving it from
        # the owned/shared lists breaks when a commit deduped against
        # an existing chain node.
        self._slot_table: List[Optional[np.ndarray]] = \
            [None] * self.slots
        self._trie = (paged_lib.BlockTrie(self.kv_block)
                      if self.prefix_share else None)  # None = sharing off
        # Which attention the decode program is built with: the same
        # call _paged_layer branches on when it is traced. Speculative
        # mode has no S = 1 step over the pool (its verify is
        # S = k + 1: the gather).
        self.decode_attention = (
            'gather' if self.draft_cfg is not None
            else self._ops.decode_attention(self._cache,
                                            self.kv_quantize))
        self._step_paths = self._ops.step_paths(self._cache)
        self._last = jnp.zeros((self.slots,), jnp.int32, device=vec)
        self._d_cache = None
        if self.draft_cfg is not None:
            self._d_cache = gen_lib.init_cache(
                self.draft_cfg, self.slots, self.max_len, kv_sharding=kv,
                lengths_sharding=vec, quantize=self.kv_quantize,
                kv_scale_sharding=kv_s)
        # Logical device-memory registration (observability/profiler.py
        # memory accounting): the engine's resident KV footprint by
        # kind, re-registered on every rebuild so the reconciliation
        # residue (allocator in_use minus logical) stays the
        # leak/fragmentation signal. Host-side .nbytes attribute reads
        # over already-allocated buffers — no device sync.
        profiler.register_logical('kv_cache',
                                  profiler.tree_nbytes(self._cache))
        if self._d_cache is not None:
            profiler.register_logical(
                'kv_draft', profiler.tree_nbytes(self._d_cache))

    def _blocks_for(self, row_len: int, max_new: int) -> int:
        """Blocks reserved at admission: the request's actual ask, not
        max_len — the pool's whole point. Spec mode adds the
        k+1 verify-window overhang: a verify may WRITE that far past
        the committed length before rollback, and a write diverted to
        the junk sink would lose KV the round then commits. The ONE
        definition — submit-time feasibility and admission-time
        reservation must never disagree."""
        extra = self.spec_k + 1 if self.draft_cfg is not None else 0
        return -(-(row_len + max_new + extra) // self.kv_block)

    def _blocks_needed(self, req: _Request) -> int:
        # Export requests retire at the first token: the reservation
        # covers the prompt (plus the one junk decode position a
        # pipelined chunk may write before retirement), never max_new.
        budget = 1 if req.export else req.max_new
        return self._blocks_for(len(req.row), budget)

    # skylint: resource-pair=kv_blocks.release
    def _release_blocks(self, slot: int) -> None:
        self._slot_table[slot] = None
        self._free_blocks.extend(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        if self._trie is not None and self._slot_shared[slot]:
            # Shared blocks DECREF instead of freeing: refs-0 blocks
            # park in the trie's idle LRU as reusable cache (a
            # detached node's block frees for real).
            for node in self._slot_shared[slot]:
                freed = self._trie.release(node)
                if freed is not None:
                    self._free_blocks.append(freed)
            self._slot_shared[slot] = []

    def _blocks_avail(self) -> int:
        """Allocatable blocks RIGHT NOW: the free list plus idle
        (refs == 0) trie blocks the allocator may evict. Callers hold
        the lock."""
        avail = len(self._free_blocks)
        if self._trie is not None:
            avail += self._trie.reclaimable
        return avail

    # skylint: locked(every caller holds _lock per the docstring
    # contract below), resource-pair=kv_blocks.acquire
    def _alloc_blocks(self, n: int) -> List[int]:
        """Pop ``n`` blocks, refcount-aware-LRU-evicting idle trie
        blocks when the free list runs short. Callers hold the lock and
        have checked ``_blocks_avail() >= n``. With hierarchical tiers
        on, eviction DEMOTES instead of discarding: the chains' KV is
        gathered off the pool (dispatch only — the tier thread does
        the device_get) before the freed ids can be rescattered."""
        if len(self._free_blocks) < n and self._trie is not None:
            pairs = self._trie.evict_nodes(n - len(self._free_blocks))
            self.share_evictions += len(pairs)
            if self._kv_tiers is not None and pairs:
                self._demote_evicted(pairs)
            self._free_blocks.extend(b for b, _ in pairs)
        return [self._free_blocks.pop() for _ in range(n)]

    # skylint: locked(callers of _alloc_blocks hold _lock), engine-thread
    def _demote_evicted(self, pairs: list) -> None:
        """Queue just-evicted trie chains for host-tier demotion: ONE
        pow2-padded ``jit_export_blocks`` gather over the victim
        blocks, dispatched HERE — before this admission (or any later
        one) can rescatter the freed ids, so device program order
        guarantees the gather reads the pre-eviction KV. The device
        handles go to the tier thread; the engine thread never pays
        the device_get or the serialization."""
        tiers = self._kv_tiers
        items = []
        for blk, node in pairs:
            if not tiers.accepts(node.chain):
                continue
            parts = []
            cur = node
            while cur is not None:
                parts.append(cur.key)
                cur = cur.parent
            row = [t for key in reversed(parts) for t in key]
            items.append((node.chain, row, len(items), blk))
        if not items:
            return
        nbp = 1
        while nbp < len(items):
            nbp *= 2
        tbl = np.zeros((nbp,), np.int32)  # pad -> junk sink block 0
        tbl[:len(items)] = [blk for _, _, _, blk in items]
        handles = paged_lib.jit_export_blocks(self._cache, tbl)
        tiers.offer_demote([(d, row, gi) for d, row, gi, _ in items],
                           handles)

    # skylint: locked(called from _admit under _lock), engine-thread
    def _tier_consult(self, row: List[int], nodes: list) -> tuple:
        """Extend a trie match through the tier index: walk the full
        blocks past the HBM-resident chain, digesting block-by-block
        (utils/prefix_affinity.chain_digest — same chain identity the
        adverts use). Consecutive host-tier hits become the promote
        list (re-import this admission); the first spilled block
        switches to a fetch list (disk -> host warm-up); any gap ends
        the walk — promotion must stay contiguous. The last prompt
        token is never covered (it must compute the first logits)."""
        p = self.kv_block
        tiers = self._kv_tiers
        promote: list = []
        fetch: list = []
        prev = nodes[-1].chain if nodes else None
        pos = len(nodes) * p
        limit = len(row) - 1
        while pos + p <= limit:
            digest = affinity_lib.chain_digest(prev, row[pos:pos + p])
            where = tiers.lookup(digest)
            if where == 'host' and not fetch:
                promote.append(digest)
            elif where == 'spilled':
                fetch.append(digest)
            else:
                break
            prev = digest
            pos += p
        return promote, fetch

    def _tier_fetch_done(self, digests: List[bytes], ok: bool) -> None:
        """Tier-thread callback: a background spill fetch finished
        (fetched blocks are now host-resident, or quarantined on
        corruption — either way re-matching converges). Re-queue every
        parked request at the FRONT of the pending queue, preserving
        their FIFO seniority over requests that arrived while they
        waited."""
        del digests, ok  # re-match consults the index fresh
        with self._lock:
            if not self._tier_waiting:
                return
            for req in reversed(self._tier_waiting):
                self._pending.appendleft(req)
            self._tier_waiting = []
        self._wake.set()

    # skylint: engine-thread
    @staticmethod
    def _fire_callbacks(emitted: List[tuple]) -> None:
        """Run on_tokens callbacks OUTSIDE the lock, each guarded: a
        raising callback (e.g. a streaming client whose event loop died)
        loses ITS stream only — it must not reach _loop's failure path,
        which would fail every other client's in-flight request and
        rebuild the device cache."""
        for req, new in emitted:
            if req.on_tokens is None:
                continue
            try:
                req.on_tokens(new)
            except Exception:  # noqa: BLE001 — isolate per request
                req.on_tokens = None  # stop notifying the dead consumer

    # skylint: engine-thread
    def _emit(self, emitted: List[tuple], done=()) -> None:
        """Hand new tokens to their streams, then resolve the requests
        that retire here (``done``; ``last`` is stamped before either,
        so whoever the future wakes finds the timeline whole).
        ``engine.callbacks`` is user code running on the engine
        thread."""
        if not emitted and not done:
            return
        now = time.perf_counter()
        for req in done:
            req.timeline.last = now
        with profiler.span('engine.callbacks'):
            self._fire_callbacks(emitted)
            for req in done:
                if not req.future.done():
                    req.future.set_result(req.tokens)

    # skylint: locked(every caller holds _lock: the request leaves its
    # queue in the same critical section)
    def _note_admitted(self, reqs: List[_Request], path: str) -> None:
        """The ``admit`` stamp of requests that leave the queue together,
        and the count of them: ``early`` when the loop admits from
        inside a hold (``_hold_chunk``), the next chunk still held back."""
        now = time.perf_counter()
        for r in reqs:
            r.timeline.admitted_at(now, path, len(reqs))
        self.admits += len(reqs)
        if self._early:
            self.early_admits += len(reqs)

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    # skylint: engine-thread
    @profiler.spanned('engine.admit')
    def _admit(self) -> None:
        """Prefill pending requests into free slots, in power-of-two
        GROUPS: one padded [N, S] forward + one scatter insert per group.
        Per-request prefill is the continuous-batching bottleneck on a
        remote-attached chip (each request would cost its own dispatch
        round trips, and batch-1 matmuls starve the MXU); grouping
        collapses N requests to three dispatches while the power-of-two
        group size keeps compiles at log2(prefill_batch) per prompt
        bucket."""
        while True:
            with self._lock:
                # Long prompts (> prefill_chunk) leave the queue for the
                # INCREMENTAL path (_advance_prefill): one bounded chunk
                # per engine iteration, interleaved with decode, so a
                # 4k-token prompt never stalls every active slot for a
                # whole monolithic prefill. FIFO order is preserved: a
                # long head blocks later shorts only while the in-flight
                # prefill capacity is exhausted.
                while (self.prefill_chunk and self._pending
                       and len(self._prefilling) < 2
                       and len(self._pending[0].row) > self.prefill_chunk):
                    long = self._pending.popleft()
                    self._note_admitted([long], 'long')
                    self._prefilling.append(_Prefilling(long))
                if (self.prefill_chunk and self._pending
                        and len(self._pending[0].row) > self.prefill_chunk):
                    return  # long head waiting on prefill capacity
                # Block-share HIT at the queue head: it leaves the
                # grouped path for a pool-direct tail prefill (the
                # shared head is a table write; only the short unshared
                # tail computes). FIFO is preserved — a hit head that
                # cannot admit yet (no slot / no blocks) parks the
                # queue rather than letting younger requests jump it.
                shared = None
                parked_on_fetch = False
                if (self._trie is not None and self._pending
                        and (self._pending[0].max_new > 1
                             or self._pending[0].export)):
                    head = self._pending[0]
                    nodes, partial, plen = self._trie.match(head.row)
                    # Hierarchical tiers: consult the host/spill index
                    # BEFORE declaring a miss — a demoted chain
                    # extending (or replacing) the trie match promotes
                    # via jit_import_blocks instead of recomputing.
                    promote: list = []
                    fetch: list = []
                    if self._kv_tiers is not None:
                        promote, fetch = self._tier_consult(head.row,
                                                            nodes)
                        if fetch and not nodes and not promote \
                                and head.tier_parks < 2:
                            # Whole chain cold on disk: park THIS
                            # request on a bounded background fetch
                            # (younger requests keep admitting, like a
                            # queued disagg import); completion
                            # re-queues it at the head. Saturation or
                            # repeated parks degrade to a plain miss —
                            # recompute, never a stall.
                            if self._kv_tiers.request_fetch(
                                    fetch, self._tier_fetch_done):
                                head.tier_parks += 1
                                self._pending.popleft()
                                self._tier_waiting.append(head)
                                parked_on_fetch = True
                        elif fetch:
                            # Partial warmth: admit with what is HBM/
                            # host-resident now and warm the spilled
                            # tail in the background for next time.
                            self._kv_tiers.request_fetch(
                                fetch, self._tier_fetch_done)
                    if promote:
                        # The promoted chain covers >= one full block
                        # past the trie match — strictly more than any
                        # partial-tail fork donor could.
                        partial, plen = None, 0
                    if not parked_on_fetch and (nodes or promote):
                        free_s = [i for i, r in enumerate(self._slot_req)
                                  if r is None]
                        pk = sum(1 for e in self._prefilling if e.parked)
                        need = self._blocks_needed(head) - len(nodes)
                        # The matched chain's IDLE blocks are about to
                        # be pinned, so they must not count as
                        # allocatable supply for this same admission —
                        # counting them would pass the check, then
                        # _alloc_blocks finds the idle LRU already
                        # drained by acquire() and pops an empty free
                        # list (engine-thread crash).
                        pinned = sum(1 for nd in nodes if nd.refs == 0)
                        p_idle = int(partial is not None
                                     and partial.refs == 0)
                        if (self._blocks_avail() - pinned - p_idle < need
                                and partial is not None):
                            # The fork donor is pure upside — drop it
                            # (full-block hit only) before parking the
                            # whole queue on its pin.
                            partial, plen = None, 0
                            p_idle = 0
                        if (len(free_s) - pk <= 0
                                or self._blocks_avail() - pinned - p_idle
                                < need):
                            return  # backpressure: the head waits
                        # Pin the matched chain (and the CoW fork
                        # donor) BEFORE allocating — eviction must not
                        # reclaim blocks this admission is using.
                        # (LRU recency lands at release() time, when
                        # the node re-enters the idle dict.)
                        for nd in nodes:
                            self._trie.acquire(nd)
                        if partial is not None:
                            self._trie.acquire(partial)
                        # skylint: allow-leak(engine thread: an escape
                        # between alloc and the slot-table install hits
                        # _loop's catch-all -> _fail_everything, which
                        # rebuilds the device state and the block pool)
                        owned = self._alloc_blocks(need)
                        slot = free_s[0]
                        self._pending.popleft()
                        self._note_admitted([head], 'shared')
                        self._slot_req[slot] = head
                        self._slot_blocks[slot] = list(owned)
                        self._slot_shared[slot] = list(nodes)
                        self._admitting = [head]
                        # Claim the host-tier entries LAST (validated
                        # + popped): a backpressure return above must
                        # not have consumed them. Truncation on a
                        # corrupt entry only shrinks the covered head
                        # — the extra owned blocks serve the tail.
                        pro = (self._kv_tiers.take_for_promote(promote)
                               if promote else [])
                        shared = (head, slot, nodes, partial, plen,
                                  owned, pro)
                if parked_on_fetch:
                    continue
                if shared is None:
                    free = [i for i, r in enumerate(self._slot_req)
                            if r is None]
                    # Slots owed to parked finished prefills are
                    # reserved — without this, a sustained short-prompt
                    # stream would starve the long request forever (it
                    # holds a scratch cache row and blocks further long
                    # admissions while parked).
                    parked = sum(1 for e in self._prefilling if e.parked)
                    n = min(max(len(free) - parked, 0),
                            len(self._pending), self.prefill_batch)
                    if self.prefill_chunk:
                        # Only CONSECUTIVE short requests join a group.
                        run = 0
                        for p in self._pending:
                            if len(p.row) > self.prefill_chunk or run >= n:
                                break
                            run += 1
                        n = run
                    # Backpressure: admit only requests whose block
                    # reservation fits the allocatable pool (free +
                    # evictable idle); the rest queue. A later
                    # block-share HIT also ends the group — it becomes
                    # the head next iteration and takes the pool-direct
                    # path instead of re-prefilling its shared head.
                    avail = self._blocks_avail()
                    run = 0
                    for p in self._pending:
                        if run >= n:
                            break
                        if (run > 0 and self._trie is not None
                                and (p.max_new > 1 or p.export)
                                and self._trie.match(p.row)[0]):
                            break
                        nb = (self._blocks_needed(p)
                              if p.max_new > 1 or p.export else 0)
                        if nb > avail:
                            break
                        avail -= nb
                        run += 1
                    n = run
                    if n == 0:
                        return
                    g = 1
                    while g * 2 <= n:
                        g *= 2
                    reqs = [self._pending.popleft() for _ in range(g)]
                    self._note_admitted(reqs, 'group')
                    # Mid-prefill requests live in NO other structure —
                    # a device failure here must still fail their
                    # futures.
                    self._admitting = reqs
            if shared is not None:
                self._admit_shared(*shared)
                with self._lock:
                    self._admitting = []
                blackbox.record('engine.admit', n=1, shared=True,
                                prompt_len=len(shared[0].row))
                continue
            self._prefill_group(reqs, free[:g])
            with self._lock:
                self._admitting = []
            blackbox.record('engine.admit', n=len(reqs), shared=False,
                            prompt_len=max(len(r.row) for r in reqs))

    # skylint: engine-thread
    @profiler.spanned('engine.admit_shared')
    def _admit_shared(self, req: _Request, slot: int, nodes: list,
                      partial, plen: int, owned: List[int],
                      pro: Optional[list] = None) -> None:
        """Admit ONE block-share hit: the table head points at the
        shared blocks (incref'd by _admit), a partially matched tail
        block is copy-on-write-forked into the first owned block, and
        only the unshared tail prefills — directly over the pool
        (models/paged.py jit_prefill_shared), no dense scratch row and
        no insert copy. ``pro`` carries host-tier promote payloads
        (serve/kv_tiers.py, validated plane arrays, one per block):
        they scatter into the leading owned blocks via
        ``jit_import_blocks`` — a re-import instead of a recompute —
        and then commit into the trie like any other prompt block."""
        t0 = time.perf_counter()
        # skylint: locked(engine thread is the sole slot-table mutator;
        # this is a point-in-time bubble-attribution hint only)
        had_active = any(r is not None and r is not req
                         for r in self._slot_req)
        p = self.kv_block
        row = req.row
        pro = pro or []
        covered = (len(nodes) + len(pro)) * p + plen
        mb = self.max_len // p
        table = np.zeros((mb,), np.int32)
        table[:len(nodes)] = [nd.block for nd in nodes]
        table[len(nodes):len(nodes) + len(owned)] = owned
        if pro:
            # Promote: scatter the demoted chain's planes into the
            # first len(pro) owned blocks and install table+covered
            # length in the same dispatch (the disagg-import program —
            # jit_prefill_shared below overwrites both with the final
            # values). Pow2-padded to the junk sink, like every block
            # mover.
            tw0 = time.time()
            nbp = 1
            while nbp < len(pro):
                nbp *= 2
            blocks = np.zeros((nbp,), np.int32)
            blocks[:len(pro)] = owned[:len(pro)]
            cfg = self.cfg
            shp = (cfg.n_layers, nbp, cfg.n_kv_heads, p, cfg.head_dim)
            kdt = self._cache.k.dtype
            k_pad = np.zeros(shp, dtype=kdt)
            v_pad = np.zeros(shp, dtype=kdt)
            for j, planes in enumerate(pro):
                k_pad[:, j] = planes['k']
                v_pad[:, j] = planes['v']
            ks_pad = vs_pad = None
            if self.kv_quantize:
                ks_pad = np.zeros(shp[:-1], np.float32)
                vs_pad = np.zeros(shp[:-1], np.float32)
                for j, planes in enumerate(pro):
                    ks_pad[:, j] = planes['k_s']
                    vs_pad[:, j] = planes['v_s']
            self._cache = paged_lib.jit_import_blocks(
                self._cache, k_pad, v_pad, ks_pad, vs_pad, blocks,
                table, np.int32(slot), np.int32(covered))
            trace_lib.add_span('serve.kv_promote', tw0, time.time(),
                               blocks=len(pro),
                               tokens=len(pro) * p)
        if partial is not None:
            # First append past the shared partial block forks it: copy
            # the donor into our first owned block; the tail prefill
            # then writes from in-block offset ``plen``.
            self._cache = self._ops.fork_block(
                self._cache, jnp.int32(partial.block), jnp.int32(owned[0]))
        suffix = row[covered:]
        # The padded width must not overhang max_len: positions past
        # the table are CLIPPED to its last entry, and with a full
        # reservation that entry is the request's own live block — the
        # padded junk would scribble over real prompt KV. Room always
        # suffices: submit validates row + max_new <= max_len, so
        # max_len - covered >= len(suffix) + max_new.
        w = min(prompt_bucket(len(suffix)), self.max_len - covered)
        padded = np.zeros((1, w), np.int32)
        padded[0, :len(suffix)] = suffix
        logits, self._cache = self._ops.prefill_shared(
            self.cfg, self.params, self._cache, padded, table[None],
            jnp.int32(slot), np.asarray([covered], np.int32),
            np.asarray([len(suffix)], np.int32), self._shard_ctx)
        req.timeline.prefill = time.perf_counter()
        req.timeline.saved_tokens = covered
        first = _jit_sample(
            logits, np.asarray([req.temperature], np.float32),
            self._next_key(),
            *_filters_or_none(np.asarray([req.top_k], np.int32),
                              np.asarray([req.top_p], np.float32)))
        self._last = self._last.at[jnp.asarray([slot], jnp.int32)].set(
            first)
        with self._lock:
            if partial is not None:
                # The fork donor was pinned only across the copy
                # dispatch; it returns to the idle LRU (or frees, if an
                # eviction detached it meanwhile — impossible while
                # pinned, but release() handles it uniformly).
                freed = self._trie.release(partial)
                if freed is not None:
                    self._free_blocks.append(freed)
                self.cow_forks += 1
            self._slot_table[slot] = table.copy()
            self._commit_prompt_blocks(slot, row, nodes)
            self._unfetched.append(([req], first))
            # skylint finding (guarded-by): these bumps sat outside the
            # lock while /health snapshots them — fold into the commit
            # critical section.
            self.prefills += 1
            self.share_hits += 1
            self.share_hit_tokens += covered
            self.prefill_tokens += len(suffix)
            self.prefill_tokens_saved += covered
        self._note_prefill_time(t0, had_active)

    # skylint: locked(every caller holds _lock per the docstring
    # contract below)
    def _commit_prompt_blocks(self, slot: int, row: List[int],
                              shared_nodes: list) -> None:
        """Index the slot's full PROMPT blocks in the share trie.
        Ownership transfers: committed blocks leave ``_slot_blocks``
        for the refcounted ``_slot_shared`` (released as decrefs).
        Duplicate content — a racing identical commit, or a chunked
        long prefill that COPIED its matched head — keeps our copy
        owned and chains deeper commits under the existing node.
        Caller holds the lock."""
        if self._trie is None:
            return
        p = self.kv_block
        nb_commit = len(row) // p  # only blocks fully inside the prompt
        base = len(shared_nodes)
        if nb_commit <= base:
            return
        owned = self._slot_blocks[slot]
        idx_block = {base + j: b for j, b in enumerate(owned)}
        parent = shared_nodes[-1] if shared_nodes else None
        for i in range(base, nb_commit):
            key = tuple(row[i * p:(i + 1) * p])
            existing = self._trie.child(parent, key)
            if existing is not None:
                parent = existing
                continue
            blk = idx_block[i]
            node = self._trie.commit(parent, key, blk)
            owned.remove(blk)
            self._slot_shared[slot].append(node)
            self.share_commits += 1
            parent = node

    # skylint: engine-thread
    def _note_prefill_time(self, t0: float, had_active: bool) -> None:
        """Prefill cost bookkeeping: total host wall time spent
        dispatching prefill work, and the slice of it decode provably
        waited on (active slots, nothing in flight) — the prefill
        bubble sharing and chunking shrink."""
        dt_ms = (time.perf_counter() - t0) * 1e3
        self._note_queued_behind()
        with self._lock:
            self.prefill_ms += dt_ms
            if had_active and self._inflight is None:
                self.prefill_bubble_ms += dt_ms

    # skylint: engine-thread
    def _note_queued_behind(self) -> None:
        """Work was dispatched behind the chunk in flight: its
        retirement will end later than the chunk, and does not time it."""
        if self._inflight is not None:
            self._inflight.followed = True

    # skylint: engine-thread
    def _prefill_one_chunk(self, params, cfg, cache1, row, consumed):
        """One bounded chunk of a single-row incremental prefill.
        Returns (logits, cache, new_consumed). Pad width may not
        overhang max_len: dynamic_update_slice CLAMPS out-of-range
        starts, and a clamped padded tail would smear junk over REAL
        prefix KV. Room always suffices: the prompt is < max_len
        (submit validates row + max_new <= the engine limit)."""
        w = min(self.prefill_chunk, self.max_len - consumed)
        chunk = row[consumed:consumed + w]
        padded = np.zeros((1, w), np.int32)
        padded[0, :len(chunk)] = chunk
        logits, cache1 = model_ops.ops_for(cfg).prefill(
            params, padded, cache1, cfg,
            np.asarray([len(chunk)], np.int32))
        if params is self.params:  # draft-model chunks don't count
            with self._lock:
                self.prefill_tokens += len(chunk)
        return logits, cache1, consumed + len(chunk)

    # skylint: locked(engine thread is the sole mutator of _prefilling
    # and _slot_req; both reads are loop-pacing hints, not invariants)
    def _advance_prefill(self) -> None:
        if not self._prefilling:
            return
        if not (self._prefilling[0].parked or self._piece_due()):
            return  # the live rows' decode steps come first
        had_active = any(r is not None for r in self._slot_req)
        t0 = time.perf_counter()
        with profiler.span('engine.advance_prefill'):
            try:
                self._advance_prefill_impl()
            finally:
                self._note_prefill_time(t0, had_active)

    # skylint: locked(as _advance_prefill: loop-pacing reads of state
    # the engine thread alone mutates)
    def _piece_due(self) -> bool:
        """Whether the oldest long prefill's next piece may go out now.
        With nothing decoding: always. Between trimmed chunks: after at
        least ``chunk_steps`` decode steps since the last piece. Else
        one piece per dispatched chunk (``_steps_since_piece`` is 0
        from a piece to the next dispatch): the hold runs the top of
        the loop more than once a chunk, and the pieces of a long
        prompt must not go out back to back in front of the live rows."""
        if not self._prefilling or self._prefilling[0].parked:
            return False
        if not any(r is not None for r in self._slot_req):
            return True
        if self._trim_chunks:
            return self._steps_since_piece >= self.chunk_steps
        return self._inflight is None or self._steps_since_piece > 0

    # skylint: engine-thread
    def _advance_prefill_impl(self) -> None:
        """Advance the oldest in-flight long prefill by ONE chunk per
        model (the per-iteration budget that bounds how long active
        slots wait between decode chunks). On the target's final chunk:
        sample the first token; insert once the draft cache (spec mode)
        has caught up and a slot frees."""
        # skylint: locked(only the engine thread reorders _prefilling;
        # cross-thread appends go through _admit under the lock)
        entry = self._prefilling[0]
        req = entry.req
        n = len(req.row)
        spec = self.draft_cfg is not None
        # Draft advances first: a parked target must not stall the
        # draft's remaining chunks.
        if spec and entry.cache is not None and entry.d_consumed < n:
            _, entry.d_cache, entry.d_consumed = self._prefill_one_chunk(
                self.draft_params, self.draft_cfg, entry.d_cache,
                req.row, entry.d_consumed)
            with self._lock:
                self.prefill_chunks += 1
        if entry.parked:
            self._finish_long_prefill(entry)
            return
        if entry.cache is None:
            # First chunk: seed from the share trie when the prompt's
            # head is cached — long popular prompts (system preambles)
            # are where prefix reuse pays most.
            cache1, p_hit = None, 0
            if self._trie is not None:
                with self._lock:
                    t_nodes, _, _ = self._trie.match(req.row)
                    t_blocks = [nd.block for nd in t_nodes]
                    for nd in t_nodes:
                        self._trie.touch(nd)
                if t_blocks:
                    # Seed the dense scratch row from the shared blocks
                    # (one gather); the chunked tail then computes only
                    # unshared tokens. The scratch row is inserted
                    # wholesale at finish, so the long-prompt path
                    # shares COMPUTE, not storage — its novel blocks
                    # still commit (duplicates of the matched head
                    # dedup against the existing chain).
                    mb = self.max_len // self.kv_block
                    tbl = np.zeros((mb,), np.int32)
                    tbl[:len(t_blocks)] = t_blocks
                    p_hit = len(t_blocks) * self.kv_block
                    cache1 = paged_lib.jit_gather_blocks(
                        self._cache, tbl, np.asarray([p_hit], np.int32))
                    with self._lock:
                        self.share_hits += 1
                        self.share_hit_tokens += p_hit
                        self.prefill_tokens_saved += p_hit
                else:
                    with self._lock:
                        self.share_misses += 1
            if cache1 is None:
                cache1 = self._ops.init_cache(self.cfg, 1, self.max_len,
                                              quantize=self.kv_quantize)
            entry.cache, entry.consumed = cache1, p_hit
            req.timeline.saved_tokens = p_hit
            if spec:
                entry.d_cache = gen_lib.init_cache(
                    self.draft_cfg, 1, self.max_len,
                    quantize=self.kv_quantize)
        logits, entry.cache, entry.consumed = self._prefill_one_chunk(
            self.params, self.cfg, entry.cache, req.row, entry.consumed)
        self._steps_since_piece = 0
        with self._lock:
            self.prefill_chunks += 1
        if entry.consumed >= n:
            req.timeline.prefill = time.perf_counter()
            # Sample the first token ONCE off the final chunk's logits;
            # the entry may then park for a free slot (or, spec mode,
            # for the draft's remaining chunks).
            first = _jit_sample(
                logits, np.asarray([req.temperature], np.float32),
                self._next_key(),
                *_filters_or_none(np.asarray([req.top_k], np.int32),
                                  np.asarray([req.top_p], np.float32)))
            entry.first = first
            with profiler.span('engine.wait_firsts'):
                # skylint: allow-host-sync(designed fetch point — one
                # scalar first token at long-prefill retirement, the
                # chunked path's only sync; EOS/export routing needs the
                # host value now)
                entry.first_host = int(jax.device_get(first)[0])
            self._finish_long_prefill(entry)

    # skylint: engine-thread
    def _finish_long_prefill(self, entry: _Prefilling) -> None:
        req = entry.req
        if self.draft_cfg is not None and entry.d_consumed < len(req.row):
            return  # draft cache still catching up; retried next iter
        if req.export:
            self._finish_long_export(entry)
            return
        done = (req.max_new == 1
                or gen_lib.truncate_at_stop([entry.first_host],
                                            req.eos)[1])
        slot = None
        table_row = np.zeros((self.max_len // self.kv_block,), np.int32)
        with self._lock:
            free = [i for i, r in enumerate(self._slot_req) if r is None]
            if done and free:
                # As the group prefill does: a request that ends at its
                # first token still goes in, as junk through the junk
                # sink in a still-free slot, so the shape's insert has
                # run (or compiled) once a one-token request has.
                slot = free[0]
            if not done:
                nb = self._blocks_needed(req)
                if not free or self._blocks_avail() < nb:
                    return  # park until a completion frees a slot/blocks
                # skylint: allow-leak(engine thread: an escape here
                # reaches _fail_everything, which rebuilds the device
                # state and the whole block pool)
                blocks = self._alloc_blocks(nb)
                table_row[:nb] = blocks
                slot = free[0]
                self._slot_req[slot] = req
                self._slot_blocks[slot] = list(blocks)
        with self._lock:
            self._prefilling.pop(0)
            self.prefills += 1
            req.tokens.append(entry.first_host)
            req.timeline.first = time.perf_counter()
            self.tokens_emitted += 1
        self._emit([(req, [entry.first_host])], [req] if done else [])
        if done and slot is None:
            return
        self._cache = self._ops.insert_paged(
            self._cache, entry.cache, np.asarray(table_row[None]),
            np.asarray([slot], np.int32))
        if done:
            return
        self._last = self._last.at[
            jnp.asarray([slot], jnp.int32)].set(entry.first)
        if self._trie is not None:
            with self._lock:
                if self._slot_req[slot] is req:
                    self._commit_prompt_blocks(slot, req.row, [])
        if self.draft_cfg is not None:
            self._d_cache = _jit_insert_cache(
                self._d_cache, entry.d_cache,
                jnp.asarray([slot], jnp.int32))

    # skylint: engine-thread
    def _finish_long_export(self, entry: _Prefilling) -> None:
        """Export retirement for a chunked long prefill: insert the
        scratch row into pool blocks — COMMITTING the prompt chain, so
        later sharers and later exports of the same long preamble hit
        the trie — and gather back out. May PARK (return without
        popping) awaiting a slot/blocks like a normal finish."""
        req = entry.req
        with self._lock:
            free = [i for i, r in enumerate(self._slot_req)
                    if r is None]
            nb = self._blocks_needed(req)
            if not free or self._blocks_avail() < nb:
                return  # park; retried next iteration
            # skylint: allow-leak(engine thread: an escape here
            # reaches _fail_everything, which rebuilds the device
            # state and the whole block pool)
            blocks = self._alloc_blocks(nb)
            table_row = np.zeros((self.max_len // self.kv_block,),
                                 np.int32)
            table_row[:nb] = blocks
            slot = free[0]
            self._slot_req[slot] = req
            self._slot_blocks[slot] = list(blocks)
            self._slot_table[slot] = table_row.copy()
        self._cache = self._ops.insert_paged(
            self._cache, entry.cache, np.asarray(table_row[None]),
            np.asarray([slot], np.int32))
        if self._trie is not None:
            with self._lock:
                if self._slot_req[slot] is req:
                    self._commit_prompt_blocks(slot, req.row, [])
        with self._lock:
            self._prefilling.pop(0)
            self.prefills += 1
        self._export_and_retire(req, entry.first_host)

    # skylint: engine-thread
    @profiler.spanned('engine.prefill_group')
    def _prefill_group(self, reqs: List[_Request],
                       slots: List[int]) -> None:
        t0 = time.perf_counter()
        # skylint: locked(engine thread is the sole slot-table mutator;
        # point-in-time bubble-attribution hint only)
        had_active = any(r is not None for r in self._slot_req)
        n = len(reqs)
        rows = [r.row for r in reqs]
        width = min(prompt_bucket(max(len(r) for r in rows)),
                    self.max_len)
        padded = np.zeros((n, width), np.int32)
        lens = np.zeros((n,), np.int32)
        temps = np.zeros((n,), np.float32)
        top_ks = np.zeros((n,), np.int32)
        top_ps = np.ones((n,), np.float32)
        for i, r in enumerate(reqs):
            padded[i, :len(r.row)] = r.row
            lens[i] = len(r.row)
            temps[i] = r.temperature
            top_ks[i] = r.top_k
            top_ps[i] = r.top_p
        cache_n = self._ops.init_cache(self.cfg, n, width,
                                       quantize=self.kv_quantize)
        logits, cache_n = self._ops.prefill(
            self.params, padded, cache_n, self.cfg,
            np.asarray(lens))
        now = time.perf_counter()
        for r in reqs:
            r.timeline.prefill = now
        with self._lock:
            self.prefill_tokens += int(lens.sum())
        tk, tp = _filters_or_none(top_ks, top_ps)
        firsts = _jit_sample(logits, np.asarray(temps), self._next_key(),
                             tk, tp)
        # Insert EVERY row (a single-token request's row becomes harmless
        # junk in a still-free slot). The first-token VALUES are fetched
        # lazily (``_drain_firsts``) — prefill+insert are then pure async
        # dispatches, and the fetch overlaps the next decode chunk's
        # device time instead of paying its own relay round trip.
        mb = self.max_len // self.kv_block
        tables_host = np.zeros((n, mb), np.int32)
        with self._lock:
            for i, r in enumerate(reqs):
                if r.max_new <= 1 and not r.export:
                    continue  # resolves at prefill: junk-sink row
                # Export requests DO take blocks even at
                # max_new == 1: the handoff serializes from the
                # pool, and a junk-sink row would lose the KV.
                nb = self._blocks_needed(r)
                blocks = self._alloc_blocks(nb)  # _admit reserved
                self._slot_blocks[slots[i]] = blocks
                tables_host[i, :nb] = blocks
                self._slot_table[slots[i]] = tables_host[i].copy()
        self._cache = self._ops.insert_paged(
            self._cache, cache_n, tables_host,
            # skylint: allow-host-sync(slots is a host list of slot
            # indices — asarray builds the jit operand, no transfer)
            np.asarray(slots, np.int32))
        self._last = self._last.at[
            jnp.asarray(slots, jnp.int32)].set(firsts)
        if self._trie is not None:
            # Index the group's full prompt blocks for later
            # sharers (the insert above was already dispatched, so
            # any future gather of these blocks is device-ordered
            # after their content lands).
            with self._lock:
                for i, r in enumerate(reqs):
                    if r.max_new > 1 or r.export:
                        self._commit_prompt_blocks(slots[i], rows[i],
                                                   [])
                        self.share_misses += 1
        if self.draft_cfg is not None:
            # The draft tracks the same committed stream: the same
            # padded rows prefill its own dense cache.
            d_cache_n = gen_lib.init_cache(self.draft_cfg, n, width,
                                           quantize=self.kv_quantize)
            _, d_cache_n = gen_lib._jit_prefill(  # noqa: SLF001
                self.draft_params, padded, d_cache_n,
                self.draft_cfg, lens)
            self._d_cache = _jit_insert_cache(
                self._d_cache, d_cache_n,
                # skylint: allow-host-sync(slots is a host list of slot
                # indices — asarray builds the jit operand, no transfer)
                np.asarray(slots, np.int32))
        with self._lock:
            self.prefills += n
            self._unfetched.append((reqs, firsts))
            for i, req in enumerate(reqs):
                if req.max_new > 1 or req.export:
                    # Exports hold their slot (and blocks) until the
                    # drain gathers them out of the pool.
                    self._slot_req[slots[i]] = req
        self._note_prefill_time(t0, had_active)

    # skylint: engine-thread
    @profiler.spanned('engine.drain_firsts')
    def _drain_firsts(self) -> float:
        """Materialize deferred first tokens. MUST run before a chunk's
        emission so every admitted request's token list starts with its
        prefill token; also completes single-token requests. Returns
        the seconds it waited for the device."""
        with self._lock:
            batches = self._unfetched
            self._unfetched = []
        done: List[_Request] = []
        emitted: List[tuple] = []
        exports: List[tuple] = []
        waited = 0.0
        for reqs, firsts in batches:
            t0 = time.perf_counter()
            with profiler.span('engine.wait_firsts'):
                # skylint: allow-host-sync(designed deferred fetch point
                # — first tokens batched per prefill group and fetched
                # while the next chunk runs on-device, per the pipeline
                # contract)
                firsts_host = np.asarray(jax.device_get(firsts))
            waited += time.perf_counter() - t0
            with self._lock:
                for i, req in enumerate(reqs):
                    first = int(firsts_host[i])
                    if req.export:
                        # Prefill-role retirement: the first token rides
                        # the handoff — nothing is emitted here, and the
                        # serialization (device gather + get) must not
                        # run under the lock.
                        exports.append((req, first))
                        continue
                    req.tokens.append(first)
                    self.tokens_emitted += 1
                    if req.on_tokens is not None:
                        emitted.append((req, [first]))
                    first_is_eos = gen_lib.truncate_at_stop(
                        [first], req.eos)[1]
                    if first_is_eos or len(req.tokens) >= req.max_new:
                        done.append(req)
                        if first_is_eos:
                            # The slot was occupied at admission (only
                            # max_new==1 requests skip occupancy).
                            for si, r in enumerate(self._slot_req):
                                if r is req:
                                    self._slot_req[si] = None
                                    self._release_blocks(si)
                                    break
        # One stamp for all of them, here and not at each fetch: a
        # token is handed to its stream only once the LAST batch has
        # been fetched, and the wait for the later batches' prefills
        # is part of the earlier requests' wait for their first token.
        now = time.perf_counter()
        for reqs, _ in batches:
            for req in reqs:
                req.timeline.first = now
        self._emit(emitted, done)
        for req, first in exports:
            self._export_and_retire(req, first)
        return waited

    # -- disaggregated prefill/decode handoff (serve/disagg.py) -----------

    # skylint: engine-thread
    def _export_and_retire(self, req: _Request, first: int) -> None:
        """Resolve an export request with its ``PrefillHandoff`` and
        free its resources (engine thread only). A failed serialization
        fails THIS request alone — the engine keeps serving."""
        t0 = time.perf_counter()
        err = None
        try:
            handoff = self._build_handoff(req, first)
        except Exception as exc:  # noqa: BLE001 — isolate per request
            handoff, err = None, exc
        with self._lock:
            for si, r in enumerate(self._slot_req):
                if r is req:
                    self._slot_req[si] = None
                    self._release_blocks(si)
                    break
        if req.timeline.first is None:  # the chunked long-prefill path
            req.timeline.first = t0
        if handoff is None:
            if not req.future.done():
                req.future.set_exception(err)
            return
        with self._lock:
            self.exports += 1
        req.timeline.last = time.perf_counter()
        if not req.future.done():
            req.future.set_result(handoff)

    # skylint: allow-host-sync(this function IS the designed device-to-
    # host serialization surface — the KV export gathers the prompt's
    # cache planes for the disagg handoff; runs once per export at
    # prefill retirement, never per decode chunk)
    def _build_handoff(self, req: _Request, first: int) -> PrefillHandoff:
        n = len(req.row)
        base = dict(row=list(req.row), first=int(first),
                    max_new=req.max_new, temperature=req.temperature,
                    top_k=req.top_k, top_p=req.top_p, eos=req.eos,
                    prompt_len=n)
        p = self.kv_block
        nb = -(-n // p)
        with self._lock:
            slot = next((si for si, r in enumerate(self._slot_req)
                         if r is req), None)
            table = (self._slot_table[slot]
                     if slot is not None else None)
        if table is None:
            raise RuntimeError('export request lost its slot before '
                               'serialization')
        nbp = 1
        while nbp < nb:
            nbp *= 2  # pow2-padded gather: log2(MB) compiled shapes
        tbl = np.zeros((nbp,), np.int32)
        tbl[:nb] = table[:nb]
        k, v, k_s, v_s = jax.device_get(
            paged_lib.jit_export_blocks(self._cache, tbl))
        k = np.asarray(k)[:, :nb]                 # [L, nb, H, P, D]
        v = np.asarray(v)[:, :nb]
        if k_s is not None:
            k_s = np.asarray(k_s)[:, :nb]
            v_s = np.asarray(v_s)[:, :nb]
        return PrefillHandoff(block=p, n_blocks=nb, k=k, v=v, k_s=k_s,
                              v_s=v_s, **base)

    # skylint: engine-thread
    @profiler.spanned('engine.admit_imports')
    def _admit_imports(self) -> None:
        """Install queued imported prompts (decode-role admission),
        FIFO. Each head needs a free slot plus its FULL block
        reservation (prompt + max_new — the decode side owns the
        generation budget); a head that cannot admit parks the import
        queue, which is the decode pool's backpressure the autoscaler
        watches via ``queued_imports``. The leading locally-shared
        chain installs as table REFERENCES (trie acquire) and only
        genuinely new blocks scatter."""
        while True:
            doomed = None
            with self._lock:
                if not self._pending_imports:
                    return
                entry = self._pending_imports[0]
                req = entry.req
                first_is_eos = gen_lib.truncate_at_stop(
                    [entry.first], req.eos)[1]
                trivial = first_is_eos or req.max_new <= 1
                slot = None
                nodes: list = []
                table_row = None
                if not trivial:
                    free = [i for i, r in enumerate(self._slot_req)
                            if r is None]
                    parked = sum(1 for e in self._prefilling if e.parked)
                    if len(free) - parked <= 0:
                        return  # backpressure: the head waits
                    slot = free[0]
                    n = len(req.row)
                    p = self.kv_block
                    if self._trie is not None:
                        nodes, _, _ = self._trie.match(
                            req.row, limit=(n // p) * p)
                    if len(nodes) < entry.block_start:
                        # Blocks negotiated away as references were
                        # evicted between prepare and import: the
                        # payload cannot be installed — reject, the
                        # serving layer falls back to colocated.
                        self._pending_imports.popleft()
                        self.import_errors += 1
                        doomed = req
                    else:
                        need = (self._blocks_for(n, req.max_new)
                                - len(nodes))
                        pinned = sum(1 for nd in nodes if nd.refs == 0)
                        if self._blocks_avail() - pinned < need:
                            return  # backpressure: the head waits
                        for nd in nodes:
                            self._trie.acquire(nd)
                        # skylint: allow-leak(engine thread: an escape
                        # here reaches _fail_everything, which rebuilds
                        # the device state and the whole block pool)
                        owned = self._alloc_blocks(need)
                        mb = self.max_len // p
                        table_row = np.zeros((mb,), np.int32)
                        table_row[:len(nodes)] = [nd.block
                                                  for nd in nodes]
                        table_row[len(nodes):len(nodes) + len(owned)] \
                            = owned
                        self._slot_blocks[slot] = list(owned)
                        self._slot_shared[slot] = list(nodes)
                        self._slot_table[slot] = table_row.copy()
                    if doomed is None:
                        self._slot_req[slot] = req
                        self._pending_imports.popleft()
                else:
                    self._pending_imports.popleft()
                if doomed is None:
                    # The prefill was another engine's: nothing to prep.
                    self._note_admitted([req], 'import')
                    req.timeline.prefill = req.timeline.admit
                    req.timeline.saved_tokens = len(nodes) * self.kv_block
            if doomed is not None:
                if not doomed.future.done():
                    doomed.future.set_exception(KVImportError(
                        'handoff blocks negotiated as shared references '
                        'were evicted before import'))
                continue
            emitted = [(req, [entry.first])]
            if trivial:
                req.tokens.append(entry.first)
                req.timeline.first = time.perf_counter()
                with self._lock:
                    self.tokens_emitted += 1
                    self.imports += 1
                self._emit(emitted, [req])
                continue
            # Device install (outside the lock: submit() must not wait
            # on a scatter dispatch).
            self._install_import_paged(entry, slot, nodes, table_row)
            with self._lock:
                if self._slot_req[slot] is req:
                    self._commit_prompt_blocks(slot, req.row, nodes)
                if self._trie is not None:
                    if nodes:
                        self.share_hits += 1
                        self.share_hit_tokens += len(nodes) * self.kv_block
                    else:
                        self.share_misses += 1
            req.tokens.append(entry.first)
            req.timeline.first = time.perf_counter()
            with self._lock:
                self.tokens_emitted += 1
                self.imports += 1
            self._emit(emitted)

    # skylint: engine-thread
    def _install_import_paged(self, entry: _ImportEntry, slot: int,
                              nodes: list, table_row: np.ndarray) -> None:
        """Scatter the transferred prompt blocks into the pool and
        install table/length/last at ``slot`` — one jit dispatch plus
        the ``last`` write. Blocks below the local share point install
        as references (their bytes, if transferred, are ignored)."""
        req = entry.req
        n = len(req.row)
        p = self.kv_block
        nb_prompt = -(-n // p)
        start = max(len(nodes), entry.block_start)
        ids = table_row[start:nb_prompt]
        nbp = 1
        while nbp < max(len(ids), 1):
            nbp *= 2
        blocks = np.zeros((nbp,), np.int32)  # pad -> junk sink
        blocks[:len(ids)] = ids
        cfg = self.cfg
        shp = (cfg.n_layers, nbp, cfg.n_kv_heads, p, cfg.head_dim)
        # Pool dtype, not entry dtype: a full-skip handoff (every
        # prompt block negotiated as a trie reference) legitimately
        # carries NO plane bytes — entry.k is None and the install is
        # the documented all-sink scatter plus the table write.
        kdt = self._cache.k.dtype
        k_pad = np.zeros(shp, dtype=kdt)
        v_pad = np.zeros(shp, dtype=kdt)
        lo = start - entry.block_start
        hi = nb_prompt - entry.block_start
        if len(ids):
            k_pad[:, :len(ids)] = entry.k[:, lo:hi]
            v_pad[:, :len(ids)] = entry.v[:, lo:hi]
        ks_pad = vs_pad = None
        if self.kv_quantize:
            ks_pad = np.zeros(shp[:-1], np.float32)
            vs_pad = np.zeros(shp[:-1], np.float32)
            if len(ids):
                ks_pad[:, :len(ids)] = entry.k_s[:, lo:hi]
                vs_pad[:, :len(ids)] = entry.v_s[:, lo:hi]
        self._note_queued_behind()
        self._cache = paged_lib.jit_import_blocks(
            self._cache, k_pad, v_pad, ks_pad, vs_pad, blocks,
            table_row, np.int32(slot), np.int32(n))
        self._last = self._last.at[jnp.asarray([slot], jnp.int32)].set(
            jnp.asarray([entry.first], jnp.int32))

    # skylint: engine-thread
    def _run_spec_round(self) -> None:
        """One draft-propose / target-verify round over all slots (spec
        mode's decode step; see module docstring). Greedy slots commit
        their accepted prefix + the target's correction; sampled slots
        commit one token drawn from the verify's position-0 logits;
        junk slots commit one target token (mimicking a decode step).
        Both caches then roll back per row to their committed lengths."""
        with self._lock:
            reqs = list(self._slot_req)
        k = self.spec_k
        temps = np.zeros((self.slots,), np.float32)
        top_ks = np.zeros((self.slots,), np.int32)
        top_ps = np.ones((self.slots,), np.float32)
        active = np.zeros((self.slots,), bool)
        for i, r in enumerate(reqs):
            if r is not None:
                temps[i] = r.temperature
                top_ks[i] = r.top_k
                top_ps[i] = r.top_p
                active[i] = True
        with self._lock:
            self.peak_active = max(self.peak_active, int(active.sum()))
        tk, tp = _filters_or_none(top_ks, top_ps)
        t_cache, d_cache, props, tgt, samp = _jit_spec(
            self.cfg, self.draft_cfg, k, self.params, self.draft_params,
            self._cache, self._d_cache, self._last, np.asarray(temps),
            tk, tp, np.asarray(active), self._next_key(),
            self._shard_ctx)
        # Fetch deferred first tokens while the round runs on-device —
        # emission counts on every admitted request's token list already
        # holding its prefill token.
        self._drain_firsts()
        # ONE fused fetch: three sequential device_gets would pay three
        # host↔device relay round trips per round; the tuple transfer
        # pays one.
        with profiler.span('engine.wait_chunk'):
            # skylint: allow-host-sync(designed fetch point — the spec
            # round's single fused result transfer; acceptance
            # bookkeeping needs host values before the next round can
            # be shaped)
            props_h, tgt_h, samp_h = [
                np.asarray(a)
                for a in jax.device_get((props, tgt, samp))]
        # props_h/tgt_h: [B, k+1]; samp_h: [B]
        with self._lock:
            self.spec_rounds += 1
            self.chunks_run += 1
        committed = np.ones((self.slots,), np.int32)
        new_last = tgt_h[:, 0].astype(np.int32).copy()  # junk-slot default
        done: List[_Request] = []
        emitted: List[tuple] = []
        with self._lock:
            for i, req in enumerate(reqs):
                if req is None or self._slot_req[i] is not req \
                        or req.future.done() or req.export:
                    continue  # junk slot (see _run_chunk's rationale)
                if req.temperature == 0.0:
                    a = 0
                    while a < k and props_h[i, a] == tgt_h[i, a]:
                        a += 1
                    new = [int(t) for t in props_h[i, :a]]
                    new.append(int(tgt_h[i, a]))
                    self.spec_proposals += k
                    self.spec_accepted += a
                    committed[i] = a + 1
                    new_last[i] = int(tgt_h[i, a])
                else:
                    # Sampled rows: exactly one plain decode step per
                    # round (greedy acceptance would skew the sampling
                    # distribution; the verify's position-0 logits ARE
                    # that step's logits).
                    new = [int(samp_h[i])]
                    committed[i] = 1
                    new_last[i] = int(samp_h[i])
                need = req.max_new - len(req.tokens)
                new = new[:need]
                new, hit_eos = gen_lib.truncate_at_stop(new, req.eos)
                req.tokens.extend(new)
                self.tokens_emitted += len(new)
                if req.on_tokens is not None and new:
                    emitted.append((req, new))
                if hit_eos or len(req.tokens) >= req.max_new:
                    self._slot_req[i] = None  # slot -> junk; committed
                    self._release_blocks(i)   # value no longer matters
                    done.append(req)
        # Rollback: both models advanced exactly k+1; keep committed.
        adj = np.int32(k + 1) - committed
        if self.mesh is not None:
            adj_dev = jax.device_put(jnp.asarray(adj), self._vec_sharding)
            last_dev = jax.device_put(jnp.asarray(new_last),
                                      self._vec_sharding)
        else:
            adj_dev = jnp.asarray(adj)
            last_dev = jnp.asarray(new_last)
        self._cache = _jit_rewind(t_cache, adj_dev)
        self._d_cache = _jit_rewind(d_cache, adj_dev)
        self._last = last_dev
        self._emit(emitted, done)

    # skylint: engine-thread
    def _run_chunk(self) -> None:
        """Dispatch one decode chunk and retire its predecessor.

        Pipelined (``pipeline_depth == 1``, the default): chunk N+1 is
        dispatched against the current slot snapshot BEFORE chunk N's
        tokens are fetched, so N's ``device_get``, stop-token
        truncation, callback firing, slot freeing — and the admission /
        prefill work at the top of the next loop iteration — all run
        while the device computes N+1. Greedy output is byte-identical
        to the serial engine: rows are attention-independent, and a
        slot that finished in N just decodes one discardable chunk more
        (the retirement guard drops it; the reuse insert overwrites
        ``lengths``). Serial (depth 0): dispatch, fetch, bookkeep — the
        device idles through all host work (the measured bubble).

        Where the loop sleeps: the plain loop calls this only when N is
        nearly done (``_hold_chunk`` sleeps before it, under
        ``engine.idle``, and admits what arrives meanwhile), so the
        fetch of N's tokens below blocks for about a decode step, not
        for a chunk. A caller that does not hold (the SPMD lockstep
        loop) dispatches N+1 a whole chunk early and sleeps in that
        fetch: the loop as it was, same programs, same order."""
        prev, self._inflight = self._inflight, self._dispatch_chunk()
        if prev is not None:
            self._retire_chunk(prev)
        if self.pipeline_depth == 0:
            self._flush_pipeline()

    # skylint: engine-thread
    @profiler.spanned('engine.dispatch_chunk')
    def _dispatch_chunk(self) -> _Inflight:
        """Issue (async) one K-step decode chunk over ALL slots against
        the current slot snapshot. Dispatch and retirement strictly
        alternate (one of each per _run_chunk), which is exactly the
        pool's safety boundary: a slot is freed (blocks
        released) during retirement of chunk N, so exactly ONE chunk —
        N+1, dispatched just before that retirement — runs with the
        slot stale-active, writing junk through its own still-current
        device-side block table; any insert reusing the released
        blocks is dispatched at a LATER admission, after N+1, so the
        donated-pool dependency chain orders the junk writes before
        the insert that overwrites them. A deeper pipeline would let a
        chunk dispatched with a stale snapshot land AFTER such an
        insert and corrupt the new owner's KV — do not raise the depth
        without revisiting this argument. The hold (``_hold_chunk``)
        leaves it as it stands: admissions always ran in the interval
        after N's retirement and before N+2's dispatch with N+1 on the
        device; dispatching N+2 later only stretches that interval in
        host time, and dispatch and retirement still alternate."""
        with self._lock:
            reqs = list(self._slot_req)
        temps = np.zeros((self.slots,), np.float32)
        top_ks = np.zeros((self.slots,), np.int32)
        top_ps = np.ones((self.slots,), np.float32)
        active = np.zeros((self.slots,), bool)
        for i, r in enumerate(reqs):
            if r is not None:
                temps[i] = r.temperature
                top_ks[i] = r.top_k
                top_ps[i] = r.top_p
                active[i] = True
        now = time.perf_counter()
        bubble_closed_ms = None
        with self._lock:
            self.peak_active = max(self.peak_active, int(active.sum()))
            if self._last_dispatch_t is not None:
                # Gaps across quiet stretches are excluded (the
                # baseline is nulled in _note_decode_quiet), so the
                # mean divides by the gaps actually recorded, not
                # dispatches - 1.
                self._gap_ms_total += (now - self._last_dispatch_t) \
                    * 1e3
                self._gap_count += 1
            self._last_dispatch_t = now
            if self._no_flight_since is not None:
                # Host time spent with slots waiting and nothing on the
                # device: the serial-mode bubble pipelining closes.
                bubble_closed_ms = (now - self._no_flight_since) * 1e3
                self.bubble_ms += bubble_closed_ms
                self._no_flight_since = None
            self.dispatches += 1
        blackbox.record('engine.dispatch', active=int(active.sum()))
        if bubble_closed_ms is not None:
            blackbox.record('engine.bubble',
                            ms=round(bubble_closed_ms, 3),
                            edge='dispatch')
        tk, tp = _filters_or_none(top_ks, top_ps)
        if self._trim_chunks:
            steps = self._steps_to_first_finish(reqs)
            chunk, tail = self._ops.paged_chunk_n, np.int32(steps)
        else:
            steps = self.chunk_steps
            chunk, tail = self._ops.paged_chunk, self._shard_ctx
        self._steps_since_piece += steps
        with self._lock:
            self.decode_steps += steps
        self._cache, self._last, toks, counts = chunk(
            self.cfg, self.chunk_steps, self.params, self._cache,
            self._last, np.asarray(temps), tk, tp,
            np.asarray(active), self._next_key(), tail)
        # With a predecessor in flight this chunk begins when that one's
        # retirement sees it end (_note_flight_end); with none, now.
        return _Inflight(reqs=reqs, toks=toks, steps=steps, counts=counts,
                         start=now if self._inflight is None else None)

    # skylint: engine-thread
    def _steps_to_first_finish(self, reqs: List[Optional[_Request]]) -> int:
        """Steps until the first of ``reqs`` reaches its ``max_new``,
        counting what the chunk in flight will have given it; at most
        ``chunk_steps``. A row that ends in the chunk in flight decodes
        junk here and does not count; with no other row, one junk step
        (dispatch and retirement alternate). A stop id can only end a
        row sooner: its slot then frees at the retirement, as ever."""
        flight = self._inflight
        with self._lock:
            # a first token sampled at the prefill and not yet fetched
            unfetched = {id(r) for rs, _ in self._unfetched for r in rs}
            owed = [r.max_new - len(r.tokens) - (id(r) in unfetched)
                    - (flight.steps if flight is not None
                       and flight.reqs[i] is r else 0)
                    for i, r in enumerate(reqs) if r is not None]
        owed = [n for n in owed if n > 0]
        return min(min(owed), self.chunk_steps) if owed else 1

    # skylint: engine-thread
    def _note_flight_end(self, flight: _Inflight, now: float,
                         blocked: bool) -> None:
        """The host has ``flight``'s tokens, and the first tokens of
        what was queued behind it. ``blocked``: it had to wait for them,
        so the device came to the end of that work just now and is
        starting the chunk dispatched behind it (``self._inflight``),
        whose ``start`` this is; if not, the host is behind the device
        and that chunk's start is not known. A whole chunk seen at both
        ends with nothing behind it teaches the step time; one that had
        ended before the hold of its successor did is an overrun (the
        device may have idled from its end to the next dispatch)."""
        nxt = self._inflight
        if nxt is not None and nxt is not flight:
            nxt.start = now if blocked else None
            nxt.exact = blocked
        if (blocked and flight.exact and not flight.followed
                and flight.steps == self.chunk_steps):
            # (a whole chunk: a trimmed one's fixed cost would read as
            # step time, and a whole chunk after it would be held late)
            self._step_s.append((now - flight.start) / flight.steps)
        if flight.held and not blocked:
            # The estimate was late, and a late one keeps itself: no
            # chunk after an overrun is seen at both ends. Forget it;
            # the next chunks run unheld and teach a fresh one.
            self._step_s.clear()
            with self._lock:
                self.hold_overruns += 1

    # skylint: engine-thread
    def _note_decode_quiet(self) -> None:
        """The decode pipeline went quiet (no active slot): stop the
        bubble clock — idle waiting and prefill-only compute are not
        device-idle-with-decode-waiting — and the dispatch-gap baseline
        (the gap across a quiet stretch is not chunk cadence). Called
        by both the plain and the SPMD lockstep loop's idle branch."""
        self._no_flight_since = None
        self._last_dispatch_t = None

    # skylint: engine-thread
    def _flush_pipeline(self, quiet: bool = False) -> None:
        """Retire the in-flight chunk (if any) and mark the device
        idle-with-host-working so time until the next dispatch counts
        as bubble (cleared again when the loop goes truly idle).
        ``quiet``: this is the idle branch draining a junk-only chunk —
        no decode work is waiting, so its bookkeeping time counts
        toward neither overlap nor bubble."""
        flight, self._inflight = self._inflight, None
        if flight is not None:
            self._retire_chunk(flight, quiet=quiet)
        if self._no_flight_since is None:
            self._no_flight_since = time.perf_counter()

    # skylint: engine-thread
    @profiler.spanned('engine.retire_chunk')
    def _retire_chunk(self, flight: _Inflight,
                      quiet: bool = False) -> None:
        """Fetch a dispatched chunk's tokens and run all host-side
        bookkeeping: EOS truncation, streaming callbacks, slot freeing,
        future resolution. Under pipelining this runs while the NEXT
        chunk computes on-device."""
        # Fetch deferred first tokens first — emission counts on every
        # admitted request's token list already holding its prefill
        # token (and a first-token-eos resolved here frees its slot
        # before this chunk's junk for it could be appended).
        waited = self._drain_firsts()
        t0 = time.perf_counter()
        with profiler.span('engine.wait_chunk'):
            # skylint: allow-host-sync(designed fetch point — THE chunk
            # result transfer; under pipelining it lands while the next
            # chunk computes, which is the whole overlap design)
            toks_host, counts = jax.device_get((flight.toks, flight.counts))
            toks_host = np.asarray(toks_host)  # [K, B]
        now = time.perf_counter()
        self._note_flight_end(flight, now, waited + now - t0 > _BLOCKED_S)
        t0 = now
        with self._lock:
            self.chunks_run += 1
            if counts is not None:
                self._moe_load = (counts.astype(np.int64)
                                  if self._moe_load is None
                                  else self._moe_load + counts)
        done: List[_Request] = []
        emitted: List[tuple] = []
        with self._lock:
            for i, req in enumerate(flight.reqs):
                if req is None or self._slot_req[i] is not req \
                        or req.future.done() or req.export:
                    # Stale snapshot entry: between this chunk's
                    # dispatch and its retirement, _drain_firsts may
                    # have resolved a first-token-eos request, or the
                    # PREVIOUS retirement freed the slot (possibly
                    # already reused by a younger admission) —
                    # appending this chunk's tokens would mutate a list
                    # already handed to the future and leak post-eos
                    # junk to streaming clients.
                    continue
                need = req.max_new - len(req.tokens)
                take = min(need, flight.steps)
                new = [int(t) for t in toks_host[:take, i]]
                # Stop at the first stop id; the slot frees now instead
                # of burning max_new's tail.
                new, hit_eos = gen_lib.truncate_at_stop(new, req.eos)
                req.tokens.extend(new)
                self.tokens_emitted += len(new)
                if req.on_tokens is not None and new:
                    emitted.append((req, new))
                if hit_eos or len(req.tokens) >= req.max_new:
                    self._slot_req[i] = None
                    self._release_blocks(i)
                    done.append(req)
        self._emit(emitted, done)
        for req in done:
            # Counts only — token ids/prompt text never enter the ring
            # (the bundle redaction contract).
            blackbox.record('engine.retire', emitted=len(req.tokens),
                            max_new=req.max_new)
        dt_ms = (time.perf_counter() - t0) * 1e3
        was_bubble = False
        with self._lock:
            if self._inflight is not None:
                # a chunk computed meanwhile
                self.host_overlap_ms += dt_ms
            elif not quiet:
                self.bubble_ms += dt_ms  # serial: the device sat idle
                was_bubble = True
        if was_bubble:
            # Captured under the lock above so the ring event can never
            # disagree with the bubble_ms counter it mirrors.
            blackbox.record('engine.bubble', ms=round(dt_ms, 3),
                            edge='retire')
        # quiet flush: junk-only drop with no decode work waiting —
        # neither overlap nor bubble.
