"""Fault-tolerance drill: train, crash (injected), restart from the atomic
checkpoint on a DIFFERENT mesh shape, and verify the loss trajectory
continues — the elastic-restart contract at example scale.

    PYTHONPATH=src python examples/elastic_restart.py

On a real cluster the same flow is driven by launch/train.py --fail-at /
--resume with the RestartPolicy deciding restart-vs-reslice.
"""

import sys
import tempfile

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.train import optimizer as opt_mod
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import RestartPolicy, elastic_mesh_shape
from repro.train.loop import TrainConfig, make_train_step

CFG = ModelConfig(name="elastic-demo", family="dense", n_layers=2,
                  d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                  d_ff=128, vocab_size=256)


def run_segment(mesh, params, state, data, start, stop, step_fn):
    losses = []
    for i in range(start, stop):
        batch = jax.tree.map(jnp.asarray, data.batch_at(i))
        params, state, m = step_fn(params, state, batch)
        losses.append(float(m["total_loss"]))
    return params, state, losses


def main() -> int:
    model = build_model(CFG)
    tcfg = TrainConfig(opt=opt_mod.OptConfig(peak_lr=3e-3, warmup_steps=5,
                                             decay_steps=100,
                                             weight_decay=0.0))
    data = SyntheticPipeline(DataConfig(vocab_size=CFG.vocab_size, seq_len=64,
                                        global_batch=8, seed=7, branching=2))
    ckpt = tempfile.mkdtemp(prefix="elastic_ckpt_")
    mgr = CheckpointManager(ckpt, keep=2)

    # ---- phase 1: "16 hosts" (here: 1x1 mesh stands in) -------------------
    mesh_a = make_host_mesh(1, 1)
    with jax.set_mesh(mesh_a):
        params = model.init(jax.random.PRNGKey(0))
        state = opt_mod.init_opt_state(params, tcfg.opt)
        step_fn = jax.jit(make_train_step(model, tcfg))
        params, state, l1 = run_segment(mesh_a, params, state, data, 0, 30,
                                        step_fn)
        mgr.save(30, {"params": params, "opt": state}, blocking=True)
    print(f"phase 1 (mesh {dict(mesh_a.shape)}): loss "
          f"{l1[0]:.3f} -> {l1[-1]:.3f}; checkpoint @30 saved")
    print("=== simulated hard failure: 1 of 16 hosts lost ===")

    # ---- recovery decision -------------------------------------------------
    policy = RestartPolicy()
    action, backoff = policy.next_action(0, dead_hosts=[5], n_hosts=16)
    new_shape = elastic_mesh_shape(n_devices=240, model_parallel=16)
    print(f"RestartPolicy -> {action} (backoff {backoff:.0f}s); "
          f"elastic mesh for 240 surviving chips: {new_shape}")

    # ---- phase 2: restart on the new mesh ---------------------------------
    mesh_b = make_host_mesh(1, 1)   # stands in for the re-sliced (15,16)
    with jax.set_mesh(mesh_b):
        tmpl = jax.eval_shape(
            lambda: {"params": model.init(jax.random.PRNGKey(0)),
                     "opt": opt_mod.init_opt_state(
                         jax.eval_shape(lambda: model.init(
                             jax.random.PRNGKey(0))), tcfg.opt)})
        step0, restored = mgr.restore(tmpl)
        params, state = restored["params"], restored["opt"]
        params = jax.device_put(params, shd.named_shardings(params, mesh_b))
        step_fn = jax.jit(make_train_step(model, tcfg))
        params, state, l2 = run_segment(mesh_b, params, state, data, step0,
                                        step0 + 30, step_fn)
    print(f"phase 2 (restored @ step {step0}, new mesh): loss "
          f"{l2[0]:.3f} -> {l2[-1]:.3f}")
    ok = l2[0] < l1[0] and l2[-1] <= l2[0] + 0.05
    print("continuity check:", "OK — trajectory resumed, no loss spike"
          if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
