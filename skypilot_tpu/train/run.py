"""Training entrypoint for recipes: ``python -m skypilot_tpu.train.run``.

The runnable half of the flagship recipe
(``examples/llama_finetune.yaml``) — the reference counterpart is the HF
``run_clm.py`` invocation in ``examples/tpu/v6e/train-llama3-8b.yaml`` and
the checkpoint-bucket resume contract of
``llm/llama-3_1-finetuning/lora.yaml:24-31``: mount/point ``--ckpt-dir`` at
a bucket, run N steps, save every K; on relaunch (spot recovery) training
resumes from the newest durable step automatically.

Exit code 0 only when the requested number of steps is complete — a
preempted run relaunched by the managed-jobs controller picks up where the
checkpoint left off.
"""
from __future__ import annotations

import argparse
import os
import time


def make_sigterm_handler(mgr):
    """The preemption SIGTERM handler, factored for tests: emergency-
    persist FIRST (durability beats forensics — the ckpt write races
    the SIGKILL escalation deadline and must not wait on a bundle),
    THEN freeze the flight-recorder ring into an incident bundle, THEN
    exit 143. The bundle answers the fleet-scale question the ledger
    alone cannot: where exactly was this trainer when the preemption
    landed (ckpt.snapshot/commit/mirror edges + thread stacks)."""
    from skypilot_tpu.observability import blackbox

    def _on_sigterm(signum, frame):
        del signum, frame
        mgr.emergency_persist()
        blackbox.dump('sigterm', reason='trainer preemption')
        raise SystemExit(143)

    return _on_sigterm


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='tiny',
                        help='preset name (models/llama.py PRESETS)')
    parser.add_argument('--steps', type=int, default=100)
    parser.add_argument('--global-batch-size', type=int, default=2)
    parser.add_argument('--seq-len', type=int, default=128)
    parser.add_argument('--optimizer', default='adafactor')
    parser.add_argument('--accum-steps', type=int, default=1,
                        help='gradient accumulation: microbatches per '
                             'optimizer step (global batch must divide)')
    parser.add_argument('--total-steps', type=int, default=10_000,
                        help='LR cosine-decay horizon')
    parser.add_argument('--data', default=None,
                        help='pretokenized token file (train/data.py '
                             'TokenDataset); synthetic stream when unset')
    parser.add_argument('--ckpt-dir', default=None,
                        help='checkpoint dir (mounted bucket for recovery)')
    parser.add_argument('--ckpt-local-dir', default=None,
                        help='fast local staging dir: saves commit here '
                             'and mirror to --ckpt-dir in the background '
                             '(restore prefers local, falls back to the '
                             'bucket)')
    parser.add_argument('--ckpt-sync', action='store_true',
                        help='persist synchronously (stalls the step '
                             'loop for the full write; default is async '
                             '— the loop blocks only for the '
                             'device->host snapshot)')
    parser.add_argument('--save-every', type=int, default=20)
    parser.add_argument('--log-every', type=int, default=10)
    parser.add_argument('--step-time-floor', type=float, default=0.0,
                        help='min seconds per step (tests use it to make '
                             'preemption windows deterministic)')
    parser.add_argument('--mesh', default=None,
                        help='logical mesh axes, e.g. "data=2,fsdp=-1,'
                             'tensor=4" (parallel/mesh.py MeshSpec; '
                             'single-device when unset)')
    parser.add_argument('--num-slices', type=int, default=None,
                        help='TPU slices in the hybrid ICI/DCN mesh; '
                             'defaults to MEGASCALE_NUM_SLICES (set by '
                             'the gang driver on multislice clusters), '
                             'else 1')
    parser.add_argument('--remat-policy', default='full',
                        help='remat policy (models/llama.py '
                             'REMAT_POLICIES); "dots" is the v5e bench '
                             'default where memory allows')
    parser.add_argument('--lora-rank', type=int, default=0,
                        help='LoRA adapter rank; 0 = full finetune '
                             '(models/lora.py)')
    parser.add_argument('--lora-alpha', type=float, default=32.0)
    parser.add_argument('--lora-targets', default='wq,wk,wv,wo',
                        help='comma-separated weight names to adapt '
                             '(also: w_gate,w_up,w_down)')
    return parser


def trainer_from_args(args):
    """(TrainerConfig, Trainer) exactly as this entry point builds them
    — chip_smoke.py lowers the same step to look for the Pallas call."""
    from skypilot_tpu.models import llama
    from skypilot_tpu.train import Trainer, TrainerConfig

    lora_cfg = None
    if args.lora_rank > 0:
        from skypilot_tpu.models import lora as lora_lib
        lora_cfg = lora_lib.LoraConfig(
            rank=args.lora_rank, alpha=args.lora_alpha,
            targets=tuple(t.strip()
                          for t in args.lora_targets.split(',') if t.strip()))
    cfg = TrainerConfig(model=llama.PRESETS[args.model],
                        global_batch_size=args.global_batch_size,
                        seq_len=args.seq_len, optimizer=args.optimizer,
                        accum_steps=args.accum_steps,
                        total_steps=args.total_steps,
                        remat=True, remat_policy=args.remat_policy,
                        lora=lora_cfg)

    mesh = None
    num_slices = args.num_slices
    if num_slices is None:
        num_slices = int(os.environ.get('MEGASCALE_NUM_SLICES', '1'))
    if args.mesh or num_slices > 1:
        from skypilot_tpu.parallel import mesh as mesh_lib
        # Default spec: data-parallel across slices (the DCN-tolerant
        # axis — build_mesh requires data % num_slices == 0), FSDP over
        # the rest of each slice's ICI domain.
        spec = args.mesh or f'data={num_slices},fsdp=-1'
        axes = {}
        for part in spec.split(','):
            k, v = part.split('=')
            axes[k.strip()] = int(v)
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(**axes),
                                   num_slices=num_slices)
        print(f'[train] mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}'
              f' over {num_slices} slice(s)', flush=True)
    return cfg, Trainer(cfg, mesh=mesh)


def main() -> None:
    args = build_parser().parse_args()

    # Compile cache placed, backend up, an un-asked-for CPU refused,
    # the device line printed — before the first lowering.
    from skypilot_tpu.utils import jax_env
    jax_env.init_backend()

    import jax

    from skypilot_tpu.train import data as data_lib

    cfg, trainer = trainer_from_args(args)
    state = trainer.init_state(seed=0)

    # Step/ckpt telemetry (observability/train_telemetry.py): created
    # before the checkpoint manager so restore/save events ride the same
    # spool as the loss windows. Writer is None (and the loop
    # byte-identical) unless the spool dir env var is set — the gang
    # driver exports it per worker.
    from skypilot_tpu.observability import train_telemetry
    telem = train_telemetry.TelemetryWriter.from_env()

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        from skypilot_tpu.train import checkpoint as ckpt_lib
        mgr = ckpt_lib.CheckpointManager(
            args.ckpt_dir, save_interval_steps=args.save_every,
            async_save=not args.ckpt_sync,
            local_dir=args.ckpt_local_dir, telemetry=telem)
        restored = mgr.restore_latest(state)
        if restored is not None:
            state = restored
            start_step = int(jax.device_get(state['step']))
            print(f'[train] resumed from checkpoint step {start_step}',
                  flush=True)

        # Preemption hook: the agent driver's cancel path SIGTERMs the
        # gang (then escalates after a grace window) — persist the
        # freshest host-side snapshot before dying. Never touches the
        # device: safe even mid-step (ckpt.manager.emergency_persist).
        import signal as signal_lib

        signal_lib.signal(signal_lib.SIGTERM, make_sigterm_handler(mgr))

    dataset = None
    if args.data:
        # batch(step) is pure in step: resume replays the exact data
        # trajectory the checkpoint was trained on.
        dataset = data_lib.TokenDataset(
            args.data, seq_len=cfg.seq_len,
            batch_size=cfg.global_batch_size)

    step_fn = trainer.compiled_step()
    try:
        state = _train_loop(args, cfg, state, step_fn, dataset, mgr,
                            telem, start_step)
    finally:
        if mgr is not None:
            mgr.close()  # flushes any in-flight async persist
    # Closing device line, with the trained state still alive:
    # bytes_in_use per device shows where it landed (a quarter per chip
    # under --mesh fsdp=4, not all on chip 0).
    jax_env.print_device_line()
    del state
    print('[train] done', flush=True)


def _train_loop(args, cfg, state, step_fn, dataset, mgr, telem,
                start_step):
    """Runs steps [start_step, args.steps); returns the final state."""
    from skypilot_tpu.observability import train_telemetry
    from skypilot_tpu.train import data as data_lib
    from skypilot_tpu.train import trainer as trainer_lib

    import jax
    import jax.numpy as jnp

    window_t0 = time.time()
    window_steps = 0
    for i in range(start_step, args.steps):
        if dataset is not None:
            batch = jnp.asarray(dataset.batch(i))
        else:
            batch = jnp.asarray(next(iter(data_lib.synthetic_batches(
                cfg.global_batch_size, cfg.seq_len, cfg.model.vocab_size,
                seed=i, num_batches=1))))
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        step = i + 1
        window_steps += 1
        if step % args.log_every == 0 or step == args.steps:
            loss = float(jax.device_get(metrics['loss']))
            now = time.time()
            # The device_get above waited for the step, so the window
            # is device time (the first one includes the compile).
            print(f'[train] step {step}/{args.steps} loss={loss:.4f} '
                  f'step_s={(now - window_t0) / window_steps:.3f}',
                  flush=True)
            if telem is not None:
                telem.emit(train_telemetry.window_record(
                    step=step, steps=window_steps,
                    window_s=now - window_t0,
                    tokens_per_step=trainer_lib.tokens_per_step(cfg),
                    model_flops_per_step=trainer_lib.model_flops_per_step(
                        cfg),
                    loss=loss, ts=now))
            window_t0 = now
            window_steps = 0
        if mgr is not None:
            mgr.save(step, state)
        dt = time.time() - t0
        if args.step_time_floor > dt:
            time.sleep(args.step_time_floor - dt)
    if mgr is not None and mgr.latest_step() != args.steps:
        mgr.save(args.steps, state, force=True)
    return state


if __name__ == '__main__':
    main()
