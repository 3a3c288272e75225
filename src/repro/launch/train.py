"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

End-to-end production loop wiring every substrate layer together:
data pipeline (prefetch) -> sharded train step (mesh + rules) -> telemetry
(straggler detector) -> atomic async checkpoints -> crash/restart recovery
(failure injection for drills). On CPU it runs reduced configs; on a real
slice the same driver runs the full configs (mesh size via --mesh).
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.distributed import sharding as shd
from repro.launch.device import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.train import optimizer as opt_mod
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import FailureInjector, StragglerDetector
from repro.train.loop import TrainConfig, make_train_step


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2.5-3b")
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a real slice); default reduced")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--quantized-moments", action="store_true")
    ap.add_argument("--mesh", default="1x1",
                    help="data x model, e.g. 16x16 (device count must match)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="failure-injection drill: crash at this step")
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_reduced(args.arch)
    model = build_model(cfg)
    d_mesh, m_mesh = (int(x) for x in args.mesh.split("x"))
    mesh = make_host_mesh(d_mesh, m_mesh)

    tcfg = TrainConfig(
        opt=opt_mod.OptConfig(peak_lr=args.lr, warmup_steps=10,
                              decay_steps=max(args.steps, 100),
                              quantized_moments=args.quantized_moments),
        n_microbatches=args.microbatches)
    data = SyntheticPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=0,
        frontend_len=cfg.frontend_len, d_model=cfg.d_model,
        encdec=cfg.family == "encdec"))

    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    injector = FailureInjector(fail_at_steps=(args.fail_at,)
                               if args.fail_at is not None else ())
    detector = StragglerDetector()

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, shd.named_shardings(params, mesh))
        state = opt_mod.init_opt_state(params, tcfg.opt)
        start_step = 0
        if mgr and args.resume and mgr.latest_step() is not None:
            tmpl = jax.eval_shape(lambda: {"params": params, "opt": state})
            shardings = {"params": shd.named_shardings(params, mesh),
                         "opt": jax.tree.map(
                             lambda _: None, jax.eval_shape(lambda: state))}
            start_step, restored = mgr.restore(tmpl)
            params, state = restored["params"], restored["opt"]
            print(f"resumed from step {start_step}", flush=True)

        step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))
        it = data.iterator(start_step=start_step, depth=2)
        t_tokens = args.global_batch * args.seq_len
        for step in range(start_step, args.steps):
            if injector.check(step):
                print(f"[drill] injected crash at step {step}", flush=True)
                if mgr:
                    mgr.wait()
                return 17    # distinct exit code: restart me with --resume
            batch = jax.tree.map(jnp.asarray, next(it))
            t0 = time.monotonic()
            params, state, metrics = step_fn(params, state, batch)
            loss = float(metrics["total_loss"])   # sync point
            dt = time.monotonic() - t0
            detector.record(jax.process_index(), dt)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"gnorm {float(metrics['grad_norm']):.2f}  "
                      f"{t_tokens/dt:.0f} tok/s", flush=True)
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, {"params": params, "opt": state},
                         extra={"loss": loss})
        if mgr:
            mgr.save(args.steps, {"params": params, "opt": state},
                     blocking=True)
        stragglers = detector.stragglers()
        if stragglers:
            print(f"[telemetry] straggler hosts: {stragglers}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
