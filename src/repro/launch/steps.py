"""Production step functions (what the dry-run lowers and the drivers run).

All three are pure functions of (params, state, batch) suitable for
``jax.jit(..., donate_argnums=...)`` under a mesh; model-internal sharding
constraints (sharding/rules.py) plus the input shardings riding on the avals
fully determine the SPMD partitioning.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import cross_entropy_loss
from repro.optim import adamw_update, warmup_cosine


def make_train_step(cfg, model, *, peak_lr=3e-4, warmup_steps=100, total_steps=10_000,
                    grad_compress_pod: bool = False):
    """fwd + CE loss + bwd + AdamW.  Batch: {"tokens": [B, S+1]} or the stub-
    frontend form {"embeds": [B, S, d], "labels": [B, S]} (+ optional "enc")."""

    def train_step(params, opt_state, batch):
        if "tokens" in batch:
            inputs, labels = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
            feed = dict(tokens=inputs)
        else:
            labels = batch["labels"]
            feed = dict(embeds=batch["embeds"])
        if "enc" in batch:
            feed["enc"] = batch["enc"]

        def loss_fn(p):
            logits = model.forward_train(p, **feed)
            return cross_entropy_loss(logits, labels)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        if grad_compress_pod:
            from repro.optim.compression import pod_allreduce_compressed
            from repro.sharding import get_mesh
            from jax.sharding import PartitionSpec as P

            mesh = get_mesh()
            if mesh is not None and "pod" in mesh.axis_names:
                # int8-compressed DCN gradient exchange (optim/compression.py)
                grads = jax.tree.map(
                    lambda g: jax.shard_map(
                        lambda x: pod_allreduce_compressed(x, "pod"),
                        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
                    )(g),
                    grads,
                )
        lr = warmup_cosine(opt_state.step, peak_lr=peak_lr,
                           warmup_steps=warmup_steps, total_steps=total_steps)
        new_params, new_opt = adamw_update(grads, opt_state, params, lr)
        return new_params, new_opt, loss

    return train_step


def make_prefill_step(cfg, model, *, S_max: int):
    """Full forward populating the KV cache; emits (next-token ids, cache)."""

    def prefill_step(params, batch):
        feed = {}
        if "tokens" in batch:
            feed["tokens"] = batch["tokens"]
        else:
            feed["embeds"] = batch["embeds"]
        if "enc" in batch:
            feed["enc"] = batch["enc"]
        logits, cache = model.prefill(params, S_max=S_max, **feed)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache

    return prefill_step


def make_decode_step(cfg, model, *, S_max: int):
    """One new token against a cache of S_max rows (decode_* / long_* cells)."""

    def serve_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens, S_max)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache

    return serve_step


def make_spec_verify_step(cfg, model, *, S_max: int, bs: int):
    """The paper's target-side verification forward: ``bs`` tree nodes under a
    non-square mask (used by the spec-decoding benchmark cells, beyond the
    assignment's required decode shape)."""

    def verify_step(params, cache, tokens, positions, rows, mask):
        logits, cache = model.spec_forward(params, cache, tokens, positions, rows, mask)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return verify_step
