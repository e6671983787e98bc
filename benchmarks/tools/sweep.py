"""Find the knee of an open-loop mix, once, on the chip: one engine,
a ladder of fixed rates, each offered for ``--seconds`` and drained.

    python -m benchmarks.tools.sweep --workload chat-steady \\
        --rates 3,4,5,6,7 --seconds 20 [--slots 32]

Prints one JSON line per rate: offered and completed tokens/s, the
tails, the queue at the window's close. The knee is the highest rate
whose completed tokens/s still follows the offered and whose queue
does not grow; the cell's ``rate_rps`` is then a stated share of it.
Not part of a run: the benchmark never searches for a rate.
"""
from __future__ import annotations

import argparse
import copy
import json
import time

from benchmarks import manifest, run, serving
from benchmarks import timeline as tl


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seconds', type=float, default=20.0)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--slots', type=int, default=0)
    ap.add_argument('--kv-blocks', type=int, default=0)
    args = ap.parse_args()
    t_start = time.perf_counter()
    cell = manifest.cell(args.workload)
    cfg = manifest.config_of(cell)
    mix = manifest.traffic_of(cell)
    if args.slots:
        mix['engine']['slots'] = args.slots
    if args.kv_blocks:
        mix['engine']['kv_blocks'] = args.kv_blocks
    run.check_chips(int(cell['chips']))
    from skypilot_tpu.utils import jax_env
    jax_env.enable_compile_cache()
    c = serving.Cell(cfg, mix, manifest.settings(), args.seed, args.seconds,
                     False, int(cell['chips']))
    c.build()
    c.warm()
    print('[sweep] set-up parts', json.dumps(c.parts), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(',')):
        c.mix = copy.deepcopy(mix)
        c.mix['rate_rps'] = rate
        c.seed = args.seed + i
        c.records = []
        c._run_open(cfg['vocab_size'], t_start)
        lat = tl.latency_metrics(c.records)
        attempted, failed = tl.attempted_failed(c.records)
        offered = sum(r.max_new for r in tl.counted(c.records)) / args.seconds
        print(json.dumps({
            'rate_rps': rate, 'slots': mix['engine']['slots'],
            'attempted': attempted, 'failed': failed,
            'offered_tok_s': offered,
            'out_tok_s': tl.out_tok_s(c.records, c.t0, c.t1),
            'queued_at_close': c.stats1['queued'],
            'active_at_close': c.stats1['active_slots'],
            **{k: round(v, 2) for k, v in lat.items()},
            'memory_peak_gb': serving.memory_peak_bytes() / 1e9}),
            flush=True)
    c.stop()
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
