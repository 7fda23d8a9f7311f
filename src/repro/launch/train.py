"""Training entry point — the fault-tolerant loop.

Composes every substrate piece: synthetic pipeline (deterministic,
resumable), jit'd train_step with sharded state, atomic checkpointing with
lazy DualView staging, straggler watermarks, preemption handling, and
restore-and-retry supervision.  Runs on CPU with a reduced config
(exercised by tests/examples) and is mesh-agnostic — the same loop drives
the 512-chip configuration.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --steps 50 --ckpt-dir /tmp/ckpt --ckpt-every 20
"""
from __future__ import annotations

import argparse
import math
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLMDataset
from repro.dist import sharding as shd
from repro.launch import env
from repro.launch import steps as steps_mod
from repro.models.model import build_model
from repro.optim import OptimizerConfig
from repro.runtime import PreemptionHandler, Retrier, StragglerDetector


def build_trainer(cfg, hp: steps_mod.TrainHParams, mesh=None):
    """→ (model, jitted step, state shardings or None)."""
    model = build_model(cfg)
    step_fn = steps_mod.make_train_step(model, hp)
    if mesh is not None:
        state_sh = steps_mod.train_state_shardings(mesh, model, hp)
        jitted = jax.jit(step_fn, in_shardings=(state_sh, None),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        return model, jitted, state_sh
    return model, jax.jit(step_fn, donate_argnums=(0,)), None


def train_loop(cfg, *, steps: int, batch: int, seq: int,
               hp: Optional[steps_mod.TrainHParams] = None,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
               mesh=None, seed: int = 0, log_every: int = 10,
               inject_failure_at: Optional[int] = None) -> dict:
    """Returns {"losses": [...], "restarts": n, "stragglers": [...]}."""
    hp = hp or steps_mod.TrainHParams(
        optimizer=OptimizerConfig(total_steps=steps, warmup_steps=max(
            steps // 20, 1)))
    model, jitted, state_sh = build_trainer(cfg, hp, mesh)
    data = SyntheticLMDataset(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        seed=seed))
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None

    # --- restore or init ----------------------------------------------------
    start_step = 0
    if mgr is not None and mgr.latest() is not None:
        state, start_step = mgr.restore(shardings=None)
        print(f"[train] restored step {start_step} from {ckpt_dir}")
    else:
        state = steps_mod.init_train_state(model, hp, seed)
    if mesh is not None:
        state = jax.device_put(state, state_sh)

    straggler = StragglerDetector()
    preempt = PreemptionHandler(install=ckpt_dir is not None)
    retrier = Retrier(max_retries=2)
    losses = []
    restarts = [0]

    def on_failure(e, attempt):
        """Node-failure model: restore last checkpoint and continue."""
        nonlocal state
        restarts[0] += 1
        if mgr is None or mgr.latest() is None:
            raise e
        state, _ = mgr.restore()
        print(f"[train] step failed ({e!r}); restored ckpt, retry "
              f"{attempt}")

    step = start_step
    while step < steps:
        b = data.batch_np(step)
        batch_dev = {k: jnp.asarray(v) for k, v in b.items()}
        fail_once = [inject_failure_at is not None and
                     step == inject_failure_at]
        if fail_once[0]:
            inject_failure_at = None

        def do_step():
            if fail_once[0]:
                fail_once[0] = False       # fail the first attempt only
                raise RuntimeError("injected node failure")
            return jitted(state, batch_dev)

        straggler.start_step()
        state, metrics = retrier.run(do_step, on_failure)
        slow = straggler.end_step(step)
        if slow:
            print(f"[train] straggler: step {step} {slow:.1f}x watermark")
        loss = float(metrics["loss"])
        losses.append(loss)
        if log_every and step % log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f}")
        step += 1
        if mgr is not None and ckpt_every and step % ckpt_every == 0:
            mgr.save(step, state)
        if preempt.requested:
            print("[train] preemption requested — checkpoint and exit")
            if mgr is not None:
                mgr.save(step, state)
            break
    if mgr is not None and step >= steps:
        mgr.save(step, state)
    preempt.uninstall()
    return {"losses": losses, "restarts": restarts[0],
            "stragglers": straggler.flagged}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen2-1.5b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=25)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--remat", default="none")
    args = p.parse_args(argv)
    print(env.device_line())
    env.enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    hp = steps_mod.TrainHParams(
        optimizer=OptimizerConfig(total_steps=args.steps,
                                  warmup_steps=max(args.steps // 20, 1)),
        remat_policy=args.remat, microbatches=args.microbatches)
    out = train_loop(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                     hp=hp, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every)
    l = out["losses"]
    print(f"[train] done. loss {l[0]:.4f} → {l[-1]:.4f} "
          f"(restarts={out['restarts']})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
