"""A training cell: ONE compiled step with its state, built in set-up,
driven from the seed through its first steps (which the reference
follows), and handed — the same object — to the window.

The step is ``train.trainer.Trainer.compiled_step()`` on batches
built from the mix, fed as ``train/run.py`` feeds them
(``jnp.asarray(batch)`` then the step, no sync but the loss read).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import trace as trace_lib
from benchmarks import traffic_gen as tg


class Cell:
    def __init__(self, cfg: Dict, mix: Dict, settings: Dict, seed: int,
                 seconds: float, trace: bool, chips: int,
                 trace_dir: Optional[str] = None):
        self.cfg, self.mix, self.settings = cfg, mix, settings
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.chips = chips
        self.trace_dir = trace_dir
        self.parts: Dict[str, float] = {}
        self.state = None
        self.step_fn = None
        self.trainer = None
        self.t0 = self.t1 = 0.0
        self.trace_t0 = self.trace_t1 = 0.0
        self.step_ends: List[float] = []
        self.compiles0 = self.compiles1 = 0
        self.prog: Dict[str, Any] = {}   # the program's first-steps readings
        self.follow = int(mix.get('reference_steps', 3))

    @property
    def tokens_per_step(self) -> int:
        return int(self.mix['batch']) * int(self.mix['seq_len'])

    def batch(self, step: int) -> np.ndarray:
        return tg.train_batch(self.mix, self.cfg['vocab_size'], self.seed,
                              step)

    def trainer_config(self):
        from benchmarks.serving import llama_config
        from skypilot_tpu.train.trainer import TrainerConfig
        t = self.mix['trainer']
        return TrainerConfig(
            model=llama_config(self.cfg),
            global_batch_size=int(self.mix['batch']),
            seq_len=int(self.mix['seq_len']),
            learning_rate=float(t['learning_rate']),
            warmup_steps=int(t['warmup_steps']),
            total_steps=int(t['total_steps']),
            grad_clip_norm=float(t['grad_clip_norm']),
            optimizer=t['optimizer'], accum_steps=1, remat=True,
            remat_policy=t['remat_policy'])

    def _make_params(self):
        from benchmarks import weights
        return weights.make_params(self.cfg, self.seed,
                                   self.trainer.param_shardings)

    def build(self) -> None:
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.train.trainer import Trainer
        t = time.perf_counter()
        self.trainer = Trainer(self.trainer_config())
        params = self._make_params()
        # skylint: allow-jit(benchmark-side program: the reference and the
        # harness are outside the serving compile ledger by design)
        opt_state = jax.jit(self.trainer.optimizer.init)(params)
        self.state = {'step': jnp.zeros((), jnp.int32), 'params': params,
                      'opt_state': opt_state}
        jax.block_until_ready(self.state)
        self.parts['weights_s'] = time.perf_counter() - t
        self.step_fn = self.trainer.compiled_step()

    def _cache_size(self) -> int:
        try:
            return int(self.step_fn._cache_size())
        except Exception:  # noqa: BLE001 - a wrapped step has no counter
            return 0

    def first_steps(self) -> None:
        """Steps 1..``follow`` through the window's own call and feed;
        they compile the step (twice: PERF.md) and give the program's
        side of the comparison."""
        import jax
        import jax.numpy as jnp
        from benchmarks import correct
        from benchmarks.reference import train as ref_train
        t = time.perf_counter()
        losses: List[float] = []
        clip = float(self.mix['trainer']['grad_clip_norm'])
        for i in range(self.follow):
            self.state, metrics = self.step_fn(self.state,
                                               jnp.asarray(self.batch(i)))
            m = jax.device_get(metrics)
            losses.append(float(m['loss']))
            if i == 0:
                self.prog['grad_global'] = float(m['grad_norm'])
                self.prog['grad'] = correct.grad_norms_from_adafactor(
                    self.state['opt_state'], self.state['params'], clip,
                    self.prog['grad_global'])
        self.prog['losses'] = losses
        # The parameters' change over the steps followed, against the
        # seed's weights made anew (step 4 donates this state away).
        p0 = self._make_params()
        self.prog['change'] = {
            k: v ** 0.5 for k, v in ref_train.change_sq_norms(
                self.state['params'], p0).items()}
        del p0
        self.parts['warmup_s'] = time.perf_counter() - t

    def run_window(self, process_t0: float) -> None:
        import jax
        import jax.numpy as jnp
        st = self.settings
        trace_s = min(float(st['trace_s']), self.seconds * 0.5)
        step = self.follow
        pending: List = []
        span = jax.profiler.TraceAnnotation
        self.compiles0 = self._cache_size()
        self.t0 = time.perf_counter()
        self.parts['setup_s'] = self.t0 - process_t0
        target = self.t0 + self.seconds
        trace_at = self.t0 + float(st['trace_start_share']) * self.seconds
        tracing = traced = False
        window_span = None
        while True:
            now = time.perf_counter()
            if self.trace and not tracing and not traced and now >= trace_at:
                window_span = trace_lib.start(self.trace_dir)
                self.trace_t0 = time.perf_counter()
                tracing = True
            if tracing and now >= self.trace_t0 + trace_s:
                self.trace_t1 = time.perf_counter()
                trace_lib.stop(window_span)
                tracing, traced = False, True
            if now >= target:
                break
            with span('train.batch_build'):
                batch = jnp.asarray(self.batch(step))
            with span('train.step_call'):
                self.state, metrics = self.step_fn(self.state, batch)
            pending.append(metrics['loss'])
            step += 1
            if len(pending) > 2:
                with span('train.device_get'):
                    float(jax.device_get(pending.pop(0)))
                self.step_ends.append(time.perf_counter())
        for loss in pending:
            with span('train.device_get'):
                float(jax.device_get(loss))
            self.step_ends.append(time.perf_counter())
        self.t1 = time.perf_counter()
        if tracing:
            self.trace_t1 = self.t1
            trace_lib.stop(window_span)
        self.compiles1 = self._cache_size()

    def stop(self) -> None:
        self.state = None
        import gc
        gc.collect()

    # -- the reference's side ------------------------------------------------

    def reference_readings(self, quant: Optional[str] = None,
                           rows: Optional[slice] = None) -> Dict[str, Any]:
        """The plain reference through the same first steps, from the
        seed's weights made anew. ``quant`` reads the control,
        ``rows`` plants the half-batch fault in the reference."""
        from benchmarks import weights
        from benchmarks.reference import train as ref_train
        tcfg = self.mix['trainer']
        params = weights.make_params(self.cfg, self.seed)
        opt = ref_train.init_opt_state(params)
        grad_fn = ref_train.make_grad_fn(self.cfg, quant, rows)
        out: Dict[str, Any] = {'losses': []}
        for i in range(self.follow):
            params, opt, loss, gnorms, gglobal = ref_train.train_step(
                params, opt, self.batch(i), self.cfg, tcfg, quant, grad_fn)
            out['losses'].append(loss)
            if i == 0:
                out['grad'], out['grad_global'] = gnorms, gglobal
        p0 = weights.make_params(self.cfg, self.seed)
        out['change'] = {k: v ** 0.5 for k, v in
                         ref_train.change_sq_norms(params, p0).items()}
        return out
