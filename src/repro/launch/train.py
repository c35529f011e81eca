"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

End-to-end driver: config -> mesh -> meshplan shardings -> data pipeline ->
pjit'd train step under the fault supervisor (checkpoint/restart +
straggler watch).  On this CPU container it runs the smoke-scale configs;
on a real pod the same driver runs the full configs (the mesh and
shardings come from the same meshplan the dry-run exercised).
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import registry
from repro.core import meshplan
from repro.data.pipeline import DataConfig, Pipeline
from repro.fault.supervisor import Supervisor, SupervisorConfig
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.api import get_model
from repro.optim import adamw
from repro.train.step import make_train_step


def train(arch: str, steps: int = 50, batch: int = 8, seq: int = 128,
          smoke: bool = True, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 20, microbatches: int = 1,
          log_every: int = 10, seed: int = 0,
          num_docs: int = 0) -> Dict[str, Any]:
    cfg = registry.get_smoke_config(arch) if smoke \
        else registry.get_config(arch)
    model = get_model(cfg)
    mesh = make_host_mesh()
    plan = meshplan.plan_model(cfg, mesh, "train", batch, seq)

    params = model.init(jax.random.PRNGKey(seed), cfg)
    opt_cfg = adamw.AdamWConfig(total_steps=steps, warmup_steps=steps // 10)
    opt_state = adamw.init(params)
    step_fn = make_train_step(cfg, opt_cfg, remat=True,
                              microbatches=microbatches)
    p_shard = meshplan.tree_shardings(plan, mesh, params)
    params = jax.device_put(params, p_shard)

    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    data = Pipeline(DataConfig(
        vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed,
        embed_dim=cfg.d_model if cfg.input_kind == "embeds" else 0,
        num_docs=num_docs))

    losses = []
    state = {"params": params, "opt": opt_state}

    def one_step(state, step_idx):
        batch_np = next(data)
        b = {"x": jnp.asarray(batch_np["x"]),
             "labels": jnp.asarray(batch_np["labels"])}
        params, opt, metrics = jit_step(state["params"], state["opt"], b)
        losses.append(float(metrics["loss"]))
        if step_idx % log_every == 0:
            print(f"  step {step_idx:4d}  loss {losses[-1]:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
        return {"params": params, "opt": opt}

    if ckpt_dir:
        ckpt = CheckpointManager(ckpt_dir)
        sup = Supervisor(SupervisorConfig(total_steps=steps,
                                          ckpt_every=ckpt_every), ckpt)
        report = sup.run(state, one_step, state_like=state)
        state = report.final_state
    else:
        for i in range(steps):
            state = one_step(state, i)
    return {"losses": losses, "state": state, "config": cfg}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full-size config (pod-scale; default is smoke)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    out = train(args.arch, steps=args.steps, batch=args.batch,
                seq=args.seq, smoke=not args.full,
                ckpt_dir=args.ckpt_dir, microbatches=args.microbatches)
    losses = out["losses"]
    print(f"done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
