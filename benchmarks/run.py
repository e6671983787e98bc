"""``python -m benchmarks.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell in a new process.

Refuses to run without the chips the cell asks for, builds the cell,
warms only its own shapes (set-up), measures for ``--seconds``, then
frees the program's state and runs the plain reference over what the
timed path produced. Earlier lines are for people; the LAST line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
and ``compared`` last: each number ``correct`` rests on beside its
limit.
"""
from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

from benchmarks import manifest  # noqa: E402


@dataclasses.dataclass
class Context:
    """What the readers read. One per run."""
    cell: Dict[str, Any]
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    chips: int
    parts: Dict[str, float]
    t0: float = 0.0
    t1: float = 0.0
    trace_t0: float = 0.0
    trace_t1: float = 0.0
    records: List = dataclasses.field(default_factory=list)
    samples: List = dataclasses.field(default_factory=list)
    stats0: Dict = dataclasses.field(default_factory=dict)
    stats1: Dict = dataclasses.field(default_factory=dict)
    trace_stats0: Dict = dataclasses.field(default_factory=dict)
    trace_stats1: Dict = dataclasses.field(default_factory=dict)
    compiles0: Any = 0
    compiles1: Any = 0
    step_ends: List[float] = dataclasses.field(default_factory=list)
    tokens_per_step: int = 0
    memory_peak: int = 0
    peaks: Dict[str, float] = dataclasses.field(default_factory=dict)
    programs: List[Dict[str, str]] = dataclasses.field(default_factory=list)
    trace: Any = None


def say(msg: str) -> None:
    print(msg, flush=True)


def device_block(memory_peak: int) -> Dict[str, Any]:
    import jax
    d = jax.devices()
    return {'platform': d[0].platform, 'kind': d[0].device_kind,
            'count': len(d), 'memory_peak_bytes': int(memory_peak)}


def check_chips(chips: int) -> None:
    """Exit non-zero, printing no result, unless JAX sees a TPU with
    at least the chips the cell asks for."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f'benchmarks.run: no backend: {e}', file=sys.stderr)
        sys.exit(3)
    if devs[0].platform != 'tpu' or len(devs) < chips:
        print(f'benchmarks.run: needs {chips} TPU chip(s); JAX reports '
              f'{len(devs)} x {devs[0].platform}', file=sys.stderr)
        sys.exit(3)


def metric_values(ctx: Context, entries: List[Dict]) -> Dict[str, Dict]:
    """Each metric through its own reader; a reader that finds nothing
    to read returns None and the metric is left out of the line."""
    out: Dict[str, Dict] = {}
    for m in entries:
        spec = manifest.metric_file(m['name'])
        val = manifest.reader(spec['reader'])(ctx, **spec.get('args', {}))
        if val is None:
            continue
        out[m['name']] = {'value': float(val), 'unit': m['unit']}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, bench: Optional[Dict] = None,
             cfg: Optional[Dict] = None, mix: Optional[Dict] = None,
             limits: Optional[Dict] = None, hook=None
             ) -> Dict[str, Any]:
    """One run; returns the result object. ``require_chip=False`` and
    the explicit ``cfg``/``mix``/``limits`` are for the CPU tests, which
    drive everything else of a run at a tiny size; ``hook(run, stage)``
    (stages ``built`` and ``warmed``) is where they break the timed path
    underneath."""
    bench = bench or manifest.benchmark()
    cell = manifest.cell(workload, bench)
    cfg = cfg or manifest.config_of(cell, bench)
    mix = mix or manifest.traffic_of(cell)
    limits = limits if limits is not None else manifest.limits_of(workload)
    settings = manifest.settings()
    chips = int(cell['chips'])
    if require_chip:
        check_chips(chips)
    from skypilot_tpu.utils import jax_env
    cache = jax_env.enable_compile_cache()
    say(f'[bench] cell {workload} seed {seed} seconds {seconds} trace '
        f'{int(trace)} compile-cache {json.dumps(cache)}')
    trace_dir = os.path.join(manifest.ROOT, '.scratch', 'bench_trace',
                             f'{workload}-{os.getpid()}')
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    kind = mix.get('kind', 'serve')
    if kind == 'train':
        from benchmarks import training
        run = training.Cell(cfg, mix, settings, seed, seconds, trace, chips,
                            trace_dir)
        run.build()
        if hook is not None:
            hook(run, 'built')
        run.first_steps()
    else:
        from benchmarks import serving
        run = serving.Cell(cfg, mix, settings, seed, seconds, trace, chips,
                           trace_dir)
        run.build()
        if hook is not None:
            hook(run, 'built')
        run.warm()
    if hook is not None:
        hook(run, 'warmed')
    run.run_window(_PROCESS_T0)
    from benchmarks.serving import memory_peak_bytes
    memory_peak = memory_peak_bytes()
    ctx = _context(cell, cfg, mix, chips, run, memory_peak)
    say('[bench] set-up parts (s): ' + json.dumps(
        {k: round(v, 3) for k, v in run.parts.items()}))
    result = _finish(ctx, run, kind, bench, workload, trace, trace_dir,
                     limits, settings)
    return result


def _context(cell, cfg, mix, chips, run, memory_peak) -> Context:
    import jax
    from benchmarks.roofline import peaks_for
    kind = jax.devices()[0].device_kind
    try:
        peaks = peaks_for(kind)
    except KeyError:
        if jax.devices()[0].platform == 'tpu':
            raise
        peaks = {}   # a CPU test run: no device metric is computed
    ctx = Context(cell=cell, cfg=cfg, mix=mix, chips=chips, parts=run.parts,
                  t0=run.t0, t1=run.t1, trace_t0=run.trace_t0,
                  trace_t1=run.trace_t1, compiles0=run.compiles0,
                  compiles1=run.compiles1, memory_peak=memory_peak,
                  peaks=peaks, programs=manifest.programs())
    for name in ('records', 'samples', 'stats0', 'stats1', 'trace_stats0',
                 'trace_stats1', 'step_ends'):
        if hasattr(run, name):
            setattr(ctx, name, getattr(run, name))
    ctx.tokens_per_step = getattr(run, 'tokens_per_step', 0)
    return ctx


def _finish(ctx: Context, run, kind: str, bench: Dict, workload: str,
            trace: bool, trace_dir: str, limits: Dict, settings: Dict
            ) -> Dict[str, Any]:
    from benchmarks import correct
    from benchmarks import timeline as tl
    from benchmarks import trace as tr
    numbers: Dict[str, float] = {}
    t_ref = time.perf_counter()
    if kind == 'train':
        attempted = len(ctx.step_ends)
        failed = 0
        prog = run.prog
        run.stop()
        ref = run.reference_readings()
        numbers = correct.train_numbers(prog, ref)
        say('[bench] losses program ' + json.dumps(prog['losses'])
            + ' reference ' + json.dumps(ref['losses']))
        say(f'[bench] worst leaves: grad {numbers["_grad_leaf"]} '
            f'change {numbers["_change_leaf"]}')
    else:
        attempted, failed = tl.attempted_failed(ctx.records)
        sample = run.finished_sample(int(settings['reference_requests']))
        params = run.params
        run.stop()
        numbers = correct.serving_gaps(params, ctx.cfg, sample, None,
                                       int(settings['reference_pad']))
        numbers['missing'] = float(correct.missing_answers(ctx.records))
        say(f'[bench] reference over {len(sample)} requests, '
            f'{numbers["tokens"]} served tokens: gap_max '
            f'{numbers["gap_max"]:.5f} gap_mean {numbers["gap_mean"]:.5f}')
    say(f'[bench] reference took {time.perf_counter() - t_ref:.1f} s')
    ok, table = correct.verdict(numbers, limits)
    result: Dict[str, Any] = {'correct': ok, 'attempted': attempted,
                              'failed': failed}
    device = device_block(ctx.memory_peak)
    if trace:
        ctx.trace = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result['metrics'] = metric_values(
            ctx, manifest.per_layer_for(workload, bench))
        if ctx.trace is not None and ctx.trace.devices:
            busy, _, window = tr.busy_and_window(ctx.trace)
            device['busy_s'] = busy
            device['window_s'] = window
            result['breakdown'] = {
                'device_ops': tr.per_op_seconds(ctx.trace, ctx.programs),
                'idle_gaps': tr.idle_by_span(ctx.trace)}
    else:
        result['metrics'] = metric_values(
            ctx, manifest.end_to_end_for(workload, bench))
    result['device'] = device
    result['compared'] = table
    correct.print_table(table, ok)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog='benchmarks.run')
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
