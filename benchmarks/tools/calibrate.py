"""Read, on the chip and at the cell's own size, the numbers a cell's
limits are set from: the program's over many seeds (the lower
reading), the control's — the reference in int8 put in the program's
place — and, for training, the half-batch fault planted in the
reference (the upper readings). One process, one JSON line per seed.

    python -m benchmarks.tools.calibrate --workload chat-steady \\
        --seeds 101,102,... --control-seeds 3 --seconds 15

Not part of a run: ``benchmarks/limits/<cell>.json`` holds the limits
that were set from these lines, and PERF.md the readings.
"""
from __future__ import annotations

import argparse
import json
import time

from benchmarks import correct, manifest, run


def _serve(cell, cfg, mix, settings, seed, seconds, control, t0):
    from benchmarks import serving
    c = serving.Cell(cfg, mix, settings, seed, seconds, False,
                     int(cell['chips']))
    c.build()
    c.warm()
    c.run_window(t0)
    sample = c.finished_sample(int(settings['reference_requests']))
    params = c.params
    records = c.records
    c.stop()
    pad = int(settings['reference_pad'])
    out = {'seed': seed, 'program': correct.serving_gaps(
        params, cfg, sample, None, pad)}
    out['program']['missing'] = correct.missing_answers(records)
    out['attempted'] = sum(1 for r in records if r.counted)
    if control:
        out['control_int8'] = correct.serving_gaps(params, cfg, sample,
                                                   'int8', pad)
    return out


def _train(cell, cfg, mix, settings, seed, control):
    from benchmarks import training
    c = training.Cell(cfg, mix, settings, seed, 0.0, False,
                      int(cell['chips']))
    c.build()
    c.first_steps()
    prog = c.prog
    c.stop()
    del c.trainer, c.step_fn
    t = time.perf_counter()
    ref = c.reference_readings()
    out = {'seed': seed, 'reference_s': time.perf_counter() - t,
           'program': correct.train_numbers(prog, ref),
           'losses': [prog['losses'], ref['losses']]}
    if control:
        ctrl = c.reference_readings('int8')
        out['control_int8'] = correct.train_numbers(ctrl, ref)
        out['control_losses'] = ctrl['losses']
        half = int(mix['batch']) // 2
        out['fault_half_batch'] = correct.train_numbers(
            c.reference_readings(None, slice(0, half)), ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--control-seeds', type=int, default=3)
    ap.add_argument('--seconds', type=float, default=15.0)
    args = ap.parse_args()
    t0 = time.perf_counter()
    cell = manifest.cell(args.workload)
    cfg, mix = manifest.config_of(cell), manifest.traffic_of(cell)
    settings = manifest.settings()
    run.check_chips(int(cell['chips']))
    from skypilot_tpu.utils import jax_env
    jax_env.enable_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(',')):
        control = i < args.control_seeds
        t = time.perf_counter()
        if mix.get('kind') == 'train':
            line = _train(cell, cfg, mix, settings, seed, control)
        else:
            line = _serve(cell, cfg, mix, settings, seed, args.seconds,
                          control, t0)
        line['took_s'] = time.perf_counter() - t
        print('[calibrate] ' + json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
