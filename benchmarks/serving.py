"""A serving cell: build the engine the way the replica does, warm the
cell's own shapes, drive the window from the mix, and hand back what
the metrics and the comparison need.

The engine is ``models.engine.ContinuousEngine`` constructed with the
arguments ``serve.llm_server.LlmServer.__init__`` passes (for several
chips, on the mesh ``--tp N`` makes), driven through ``submit`` with a
streaming callback. Deployment choices (slots, ``max_len``,
``kv_blocks``, ...) come from the mix's ``engine`` block.
"""
from __future__ import annotations

import heapq
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmarks import trace as trace_lib
from benchmarks import traffic_gen as tg
from benchmarks.timeline import Record

# Engine methods the traced run wraps in profiler annotations, so that
# idle gaps on the device can be laid to what the host was doing. The
# program has no such spans of its own yet (PERF.md, note for tracing).
SPAN_METHODS = {'_admit': 'engine.admit',
                '_admit_shared': 'engine.admit_shared',
                '_prefill_group': 'engine.prefill_group',
                '_dispatch_chunk': 'engine.dispatch_chunk',
                '_drain_firsts': 'engine.drain_firsts',
                '_retire_chunk': 'engine.retire_chunk'}


def llama_config(cfg: Dict[str, Any]):
    """The program's config object from the published keys."""
    import jax.numpy as jnp
    from skypilot_tpu.models import llama
    d, hq = cfg['hidden_size'], cfg['num_attention_heads']
    return llama.LlamaConfig(
        vocab_size=cfg['vocab_size'], d_model=d,
        n_layers=cfg['num_hidden_layers'], n_heads=hq,
        n_kv_heads=cfg['num_key_value_heads'],
        d_ff=cfg['intermediate_size'],
        head_dim=cfg.get('head_dim') or d // hq,
        rope_theta=float(cfg['rope_theta']),
        norm_eps=float(cfg['rms_norm_eps']),
        max_seq_len=int(cfg['max_position_embeddings']),
        dtype=jnp.bfloat16)


class Cell:
    """One run of one serving cell. ``parts`` collects the set-up's
    parts in seconds for the human-readable lines."""

    def __init__(self, cfg: Dict, mix: Dict, settings: Dict, seed: int,
                 seconds: float, trace: bool, chips: int,
                 trace_dir: Optional[str] = None):
        self.cfg, self.mix, self.settings = cfg, mix, settings
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.chips = chips
        self.trace_dir = trace_dir
        self.parts: Dict[str, float] = {}
        self.records: List[Record] = []
        self.engine = None
        self.params = None
        self.mesh = None
        self.t0 = self.t1 = 0.0           # the window, host clock
        self.trace_t0 = self.trace_t1 = 0.0
        self.samples: List[Dict[str, float]] = []
        self.stats0: Dict = {}
        self.stats1: Dict = {}
        self.trace_stats0: Dict = {}
        self.trace_stats1: Dict = {}
        self.compiles0: Dict[str, int] = {}
        self.compiles1: Dict[str, int] = {}
        self._monitor: Optional[threading.Thread] = None
        self._monitor_stop = threading.Event()

    # -- build ---------------------------------------------------------------

    def build(self) -> None:
        import jax
        from skypilot_tpu.models import llama
        from skypilot_tpu.models.engine import ContinuousEngine
        from benchmarks import weights
        eng = self.mix['engine']
        self.lcfg = llama_config(self.cfg)
        t = time.perf_counter()
        shardings = None
        tp = int(eng.get('tp', 1))
        if tp > 1:
            from skypilot_tpu.parallel import mesh as mesh_lib
            from skypilot_tpu.parallel import sharding as sharding_lib
            self.mesh = mesh_lib.build_mesh(
                mesh_lib.MeshSpec(fsdp=1, tensor=tp),
                devices=jax.devices()[:tp])
            shardings = sharding_lib.sharding_tree(
                llama.param_logical_axes(self.lcfg), self.mesh,
                sharding_lib.ShardingRules())
        self.params = weights.make_params(self.cfg, self.seed, shardings)
        jax.block_until_ready(self.params)
        self.parts['weights_s'] = time.perf_counter() - t
        t = time.perf_counter()
        self.engine = ContinuousEngine(
            self.params, self.lcfg, slots=int(eng['slots']),
            max_len=int(eng['max_len']), mesh=self.mesh,
            kv_quantize=False, prefix_slots=0,
            kv_layout=eng.get('kv_layout', 'paged'),
            kv_blocks=int(eng['kv_blocks']),
            kv_block=int(eng.get('kv_block', 16)),
            prefill_batch=int(eng.get('prefill_batch', 8)),
            chunk_steps=int(eng.get('chunk_steps', 8)),
            prefix_share=bool(eng.get('prefix_share', True)),
            kv_tiers=bool(eng.get('kv_tiers', False)),
            seed=self.seed % (2**31 - 1), role='colocated')
        self.params = self.engine.params
        if self.trace:
            self._wrap_spans()
        self.engine.start()
        self.parts['engine_s'] = time.perf_counter() - t

    def _wrap_spans(self) -> None:
        import jax
        eng = self.engine

        def wrap(name: str, label: str, stamp: bool = False):
            inner = getattr(eng, name)

            def wrapped(*a, **kw):
                if stamp:
                    now = time.perf_counter()
                    reqs = a[0] if isinstance(a[0], list) else [a[0]]
                    for r in reqs:
                        rec = getattr(r.on_tokens, '__self__', None)
                        if isinstance(rec, Record) and rec.admitted is None:
                            rec.admitted = now
                with jax.profiler.TraceAnnotation(label):
                    return inner(*a, **kw)
            setattr(eng, name, wrapped)

        for name, label in SPAN_METHODS.items():
            wrap(name, label,
                 stamp=name in ('_admit_shared', '_prefill_group'))

    # -- submitting ----------------------------------------------------------

    def _submit(self, req: tg.Request, due: Optional[float],
                on_done: Optional[Callable] = None) -> Record:
        rec = Record(rid=req.rid, prompt_len=len(req.prompt),
                     max_new=req.max_new, counted=req.counted, due=due,
                     prompt=req.prompt)
        rec.sent = time.perf_counter()
        try:
            fut = self.engine.submit(req.prompt, req.max_new,
                                     temperature=0.0,
                                     on_tokens=rec.on_tokens)
        except Exception:  # noqa: BLE001 - a refused request is a failure
            rec.failed = True
            self.records.append(rec)
            if on_done is not None:
                on_done(rec)
            return rec

        def done(f, rec=rec):
            if f.exception() is not None:
                rec.failed = True
            if on_done is not None:
                on_done(rec)
        fut.add_done_callback(done)
        rec.future = fut
        self.records.append(rec)
        return rec

    def _submit_group(self, rows: List[List[int]], max_new: int) -> List:
        """Warm-up only: put ``len(rows)`` requests into the queue in
        one step so that the engine prefills them as ONE group (the
        shape being warmed). The engine has no public call for this."""
        eng = self.engine
        reqs = [eng._build_request(row, max_new, 0.0, None, 0, 1.0, None)
                for row in rows]
        with eng._lock:
            eng._pending.extend(reqs)
        eng.start()
        eng._wake.set()
        return [r.future for r in reqs]

    # -- warm-up -------------------------------------------------------------

    def warm(self) -> None:
        """Every shape the cell's traffic will use, and no other."""
        t = time.perf_counter()
        loop = self.mix['loop']
        rng = np.random.default_rng([self.seed, 77])
        vocab = self.cfg['vocab_size']
        eng = self.mix['engine']
        max_len = int(eng['max_len'])

        def row(n: int) -> List[int]:
            return rng.integers(0, vocab, size=n).tolist()

        if loop in ('open', 'backlog'):
            lengths = tg.quantile_lengths(self.mix['prompt'], 512)
            buckets = sorted({tg.pad_width(int(n)) for n in lengths})
            groups, g = [], 1
            while g <= min(int(eng.get('prefill_batch', 8)),
                           int(eng['slots'])):
                groups.append(g)
                g *= 2
            # One-token requests resolve at their prefill: each group
            # runs (and loads or compiles) the prefill, the sampling and
            # the insert of its shape, and no decode chunk.
            for b in buckets:
                n = min(b, max_len - 8)
                for g in groups:
                    futs = self._submit_group([row(n) for _ in range(g)], 1)
                    for f in futs:
                        f.result(timeout=1200)
            # The decode chunk, once.
            self._submit_group([row(buckets[0])], 4)[0].result(timeout=1200)
        elif loop == 'sessions':
            script = tg.SessionScript(self.mix, vocab, self.seed)
            sys_len = int(self.mix['system_prompt'])
            # Each tenant's system prompt goes in cold (one full-width
            # prefill each, as a deployment's first request would) and
            # stays in the trie for the run.
            for sp in script._system:
                self._submit_group([sp + row(8)], 4)[0].result(timeout=1200)
            # Suffix widths: a turn's new tokens (message, or history
            # and message for a session that starts mid-way).
            widths, w = [], 16
            while w <= max_len - sys_len:
                widths.append(w)
                w *= 2
            for w in widths:
                n = min(w, max_len - sys_len - 8)   # room for the answer
                self._submit_group([script._system[0] + row(n)], 4)[
                    0].result(timeout=1200)
            # A copy-on-write fork: share a committed block's head.
            base = script._system[0] + row(16)
            self._submit_group([base + row(4)], 4)[0].result(timeout=1200)
            self._submit_group([base[:-8] + row(12)], 4)[0].result(
                timeout=1200)
        else:
            raise ValueError(f'unknown loop {loop!r}')
        self.parts['warmup_s'] = time.perf_counter() - t

    # -- monitor (traced runs): trace window + stats samples -----------------

    def _start_monitor(self) -> None:
        if not self.trace:
            return
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name='bench-monitor', daemon=True)
        self._monitor.start()

    def _monitor_loop(self) -> None:
        st = self.settings
        trace_s = min(float(st['trace_s']), self.seconds * 0.5)
        start = self.t0 + float(st['trace_start_share']) * self.seconds
        every = float(st['stats_sample_s'])
        tracing = done = False
        nxt = self.t0
        while not self._monitor_stop.is_set():
            now = time.perf_counter()
            if not tracing and not done and now >= start:
                self._window_span = trace_lib.start(self.trace_dir)
                self.trace_t0 = time.perf_counter()
                self.trace_stats0 = self.engine.stats()
                tracing = True
            if tracing and now >= self.trace_t0 + trace_s:
                self.trace_stats1 = self.engine.stats()
                self.trace_t1 = time.perf_counter()
                trace_lib.stop(self._window_span)
                tracing, done = False, True
            if now >= nxt and self.t0 <= now:
                s = self.engine.stats()
                kb = s.get('kv_blocks') or {}
                self.samples.append({
                    't': now, 'active': s['active_slots'],
                    'queued': s['queued'],
                    'live_blocks': kb.get('owned', 0) + kb.get('shared', 0),
                    'usable': kb.get('usable', 1)})
                nxt = now + every
            self._monitor_stop.wait(0.02)
        if tracing:
            self.trace_stats1 = self.engine.stats()
            self.trace_t1 = time.perf_counter()
            trace_lib.stop(self._window_span)

    def _compile_sizes(self) -> Dict[str, int]:
        from skypilot_tpu.observability import profiler
        return dict(profiler.jit_cache_sizes())

    # -- the window ----------------------------------------------------------

    def run_window(self, process_t0: float) -> None:
        loop = self.mix['loop']
        vocab = self.cfg['vocab_size']
        if loop == 'open':
            self._run_open(vocab, process_t0)
        elif loop == 'sessions':
            self._run_sessions(vocab, process_t0)
        elif loop == 'backlog':
            self._run_backlog(vocab, process_t0)
        else:
            raise ValueError(f'unknown loop {loop!r}')

    def _open_window(self, process_t0: float) -> None:
        """Called at the instant the window opens."""
        self.parts['setup_s'] = self.t0 - process_t0
        self.stats0 = self.engine.stats()
        self.compiles0 = self._compile_sizes()
        self._start_monitor()

    def _close_window(self) -> None:
        self.stats1 = self.engine.stats()
        self.compiles1 = self._compile_sizes()

    def _sleep_until(self, t: float) -> None:
        while True:
            dt = t - time.perf_counter()
            if dt <= 0:
                return
            time.sleep(min(dt, 0.05))

    def _run_open(self, vocab: int, process_t0: float) -> None:
        sched = tg.open_schedule(self.mix, self.seconds, vocab, self.seed)
        ramp_s = float(self.mix.get('ramp_s', 0.0))
        base = time.perf_counter() + ramp_s + 0.05   # window opens here
        self.t0, self.t1 = base, base + self.seconds
        opened = closed = False
        for req in sched:
            due = base + req.due_s
            if not opened and due >= self.t0:
                self._sleep_until(self.t0)
                self._open_window(process_t0)
                opened = True
            if not closed and due >= self.t1:
                self._sleep_until(self.t1)
                self._close_window()
                closed = True
            self._sleep_until(due)
            self._submit(req, due)
        if not opened:
            self._sleep_until(self.t0)
            self._open_window(process_t0)
        if not closed:
            self._sleep_until(self.t1)
            self._close_window()
        self._wait_all(float(self.settings['drain_limit_s']))

    def _wait_all(self, limit_s: float) -> None:
        end = time.perf_counter() + limit_s
        for rec in list(self.records):
            fut = getattr(rec, 'future', None)
            if fut is None:
                continue
            try:
                fut.result(timeout=max(end - time.perf_counter(), 0.0))
            except Exception:  # noqa: BLE001 - late or failed: not finished
                pass

    def _run_sessions(self, vocab: int, process_t0: float) -> None:
        mix = self.mix
        script = tg.SessionScript(mix, vocab, self.seed)
        clients = int(mix['clients'])
        n_turns = int(mix['turns'])
        ramp_s = float(mix.get('ramp_s', 0.0))
        done_q: 'queue.Queue' = queue.Queue()
        now = time.perf_counter()
        self.t0 = now + ramp_s
        self.t1 = self.t0 + self.seconds
        # (send time, tiebreak, client, session, turn index)
        heap: List = []
        next_session = clients
        think = mix['think_s']
        spread = float(think['max'])
        plans: Dict[int, List[tg.Request]] = {}
        for c in range(clients):
            # Sessions start at mixed ages: client c opens at turn
            # c mod turns (scripted history), staggered over one think.
            plans[c] = script.turns(c)
            start_turn = min(c % n_turns, len(plans[c]) - 1)
            heapq.heappush(heap, (now + spread * (c + 0.5) / clients, c, c,
                                  c, start_turn))
        opened = closed = False
        inflight = 0
        rec_client: Dict[int, tuple] = {}
        while True:
            now = time.perf_counter()
            if not opened and now >= self.t0:
                self._open_window(process_t0)
                opened = True
            if not closed and now >= self.t1:
                self._close_window()
                closed = True
            if closed and inflight == 0:
                break
            while heap and heap[0][0] <= now and not closed:
                _, _, c, sess, turn = heapq.heappop(heap)
                req = plans[c][turn]
                req.counted = opened
                rec = self._submit(req, None, on_done=done_q.put)
                rec_client[id(rec)] = (c, sess, turn)
                inflight += 1
            nxt = heap[0][0] if heap and not closed else now + 0.05
            edge = self.t0 if not opened else (
                self.t1 if not closed else now + 0.05)
            wait = max(min(nxt, edge, now + 0.05) - now, 0.0)
            try:
                rec = done_q.get(timeout=wait) if wait > 0 else \
                    done_q.get_nowait()
            except queue.Empty:
                continue
            inflight -= 1
            c, sess, turn = rec_client.pop(id(rec))
            t_done = time.perf_counter()
            if turn + 1 < len(plans[c]):
                heapq.heappush(heap, (t_done + plans[c][turn].think_s, c,
                                      c, sess, turn + 1))
            else:
                plans[c] = script.turns(next_session)
                heapq.heappush(heap, (t_done + plans[c][0].think_s
                                      if plans[c] else t_done, c, c,
                                      next_session, 0))
                next_session += 1

    def _run_backlog(self, vocab: int, process_t0: float) -> None:
        mix = self.mix
        depth = int(mix['depth'])
        ramp_s = float(mix.get('ramp_s', 0.0))
        stream = tg.backlog_requests(mix, vocab, self.seed)
        done_q: 'queue.Queue' = queue.Queue()
        now = time.perf_counter()
        self.t0 = now + ramp_s
        self.t1 = self.t0 + self.seconds
        for _ in range(depth):
            self._submit(next(stream), None, on_done=done_q.put)
        opened = False
        while True:
            now = time.perf_counter()
            if not opened and now >= self.t0:
                self._open_window(process_t0)
                opened = True
            if now >= self.t1:
                self._close_window()
                break
            edge = self.t0 if not opened else self.t1
            try:
                done_q.get(timeout=max(min(edge - now, 0.05), 0.0))
            except queue.Empty:
                continue
            self._submit(next(stream), None, on_done=done_q.put)
        # An offline batch has no last request: what is still queued or
        # decoding when the window closes is abandoned, and only
        # requests that finished inside the window (or failed) count.
        for rec in self.records:
            rec.counted = bool(
                rec.failed or (rec.finished and rec.last is not None
                               and self.t0 <= rec.last < self.t1))

    # -- after the window ----------------------------------------------------

    def stop(self) -> None:
        """Stop the engine thread and free the program's device state.
        A backlog's abandoned requests are dropped without the engine's
        failure path (which would write an incident bundle)."""
        self._monitor_stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=120)
        eng = self.engine
        if eng is None:
            return
        eng._stop = True
        eng._wake.set()
        if eng._thread is not None:
            eng._thread.join(timeout=60)
        for attr in ('_cache', '_last', '_inflight', '_unfetched'):
            if hasattr(eng, attr):
                setattr(eng, attr, None)
        self.engine = None
        import gc
        gc.collect()

    def finished_sample(self, k: int) -> List[Record]:
        """``k`` finished counted requests drawn from the seed, the
        longest (prompt + answer) always among them."""
        pool = [r for r in self.records if r.counted and r.finished]
        if not pool:
            return []
        longest = max(pool, key=lambda r: r.prompt_len + r.max_new)
        rng = np.random.default_rng([self.seed, 88])
        rest = [r for r in pool if r is not longest]
        pick = rng.permutation(len(rest))[:max(k - 1, 0)]
        return [longest] + [rest[i] for i in pick]


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get('peak_bytes_in_use', 0)))
    return peak
