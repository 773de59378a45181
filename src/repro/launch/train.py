"""End-to-end training driver (runnable on this CPU container with smoke
configs; the same code path the dry-run lowers at production scale).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b --smoke \
      --steps 200 --batch 8 --seq 128 --ckpt /tmp/ckpt

Features exercised: sharded synthetic data, jitted train step (donated
state), atomic async checkpointing with auto-resume, step retry on transient
faults, loss logging.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLMDataset
from repro.flags import override_flags
from repro.launch.mesh import host_device_mesh
from repro.launch.steps import make_train_step
from repro.models.api import make_model
from repro.obs.clock import monotonic
from repro.optim import adamw_init
from repro.runtime import FaultConfig, retry_step
from repro.sharding import use_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh-model", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = make_model(cfg)
    n_dev = len(jax.devices())
    mesh = host_device_mesh(model=args.mesh_model, data=max(1, n_dev // args.mesh_model))

    ds = SyntheticLMDataset(DataConfig(cfg.vocab_size, args.seq, args.batch, seed=0))
    step_fn = make_train_step(cfg, model, peak_lr=args.lr, warmup_steps=20,
                              total_steps=args.steps)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    with use_mesh(mesh), override_flags(remat="none"):
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)

        start = 0
        cm = None
        if args.ckpt:
            cm = CheckpointManager(args.ckpt, keep=2)
            s, restored = cm.restore_latest((params, opt))
            if s is not None:
                start, (params, opt) = s + 1, restored
                print(f"resumed from step {s}")

        losses = []
        t0 = monotonic()
        for step in range(start, args.steps):
            host = ds.batch(step)
            feed = {"tokens": jnp.asarray(host["tokens"])}
            if cfg.n_enc_tokens:
                feed["enc"] = jnp.zeros((args.batch, cfg.n_enc_tokens, cfg.d_model), jnp.float32)
            if not cfg.embed_inputs:
                toks = host["tokens"]
                emb = jax.random.normal(jax.random.PRNGKey(1), (cfg.vocab_size, cfg.d_model)) * 0.02
                feed = {"embeds": jnp.asarray(emb)[toks[:, :-1]], "labels": jnp.asarray(toks[:, 1:])}

            def one():
                return jit_step(params, opt, feed)

            params, opt, loss = retry_step(one, FaultConfig())
            losses.append(float(loss))
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = monotonic() - t0
                print(f"step {step:5d} loss {losses[-1]:.4f} ({dt:.1f}s)", flush=True)
            if cm and step and step % args.ckpt_every == 0:
                cm.save(step, (params, opt))
        if cm:
            cm.save(args.steps - 1, (params, opt), blocking=True)

    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f"loss {first:.4f} -> {last:.4f} ({'improved' if last < first else 'NOT improved'})")
    return first, last


if __name__ == "__main__":
    main()
