"""Uniform Model API over every architecture in the zoo.

Entry points (all functional, all jittable):
  forward_train  — full causal forward -> logits (train_4k cells)
  prefill        — full forward + cache population (prefill_32k cells)
  decode_step    — one token against the cache (decode_32k / long_500k cells)
  spec_forward   — n tokens with an explicit NON-SQUARE tree mask (the paper's
                   draft-expansion / target-verification forward)
  chain_forward  — n chain tokens with masked state commit (SSM/hybrid
                   speculation; DESIGN.md §6)
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.transformer import (
    Ctx,
    apply_model,
    build_plan,
    embed_tokens,
    init_cache,
    init_model,
    logits_from_hidden,
)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---- construction ----------------------------------------------------
    def init(self, key):
        return init_model(self.cfg, key)

    def init_cache(self, B, S_max, dtype=None):
        dt = jnp.dtype(dtype or self.cfg.dtype)
        return init_cache(self.cfg, B, S_max, dt)

    # ---- embedding helpers -------------------------------------------------
    def _embed(self, params, tokens=None, embeds=None):
        if embeds is not None:
            return embeds.astype(jnp.dtype(self.cfg.dtype))
        return embed_tokens(self.cfg, params, tokens)

    # ---- training ----------------------------------------------------------
    def forward_train(self, params, tokens=None, embeds=None, enc=None):
        h = self._embed(params, tokens, embeds)
        B, S, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        ctx = Ctx(mode="full", positions=positions, enc=enc)
        h, _, _ = apply_model(self.cfg, params, h, ctx, cache=None)
        return logits_from_hidden(self.cfg, params, h)

    # ---- serving -----------------------------------------------------------
    def prefill(self, params, tokens=None, embeds=None, enc=None, S_max=None):
        """Returns (logits [B,S,V], cache with len=S)."""
        h = self._embed(params, tokens, embeds)
        B, S, _ = h.shape
        S_max = S_max or S
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        ctx = Ctx(mode="full", make_cache=S_max, positions=positions, enc=enc)
        h, cache, _ = apply_model(self.cfg, params, h, ctx, cache=None)
        cache["len"] = jnp.full((), S, jnp.int32)
        return logits_from_hidden(self.cfg, params, h), cache

    def spec_forward(self, params, cache, tokens, positions, row_idx, attn_mask, *,
                     count_experts=False):
        """Tree-structured forward: K/V written at ``row_idx``, attention under
        the non-square ``attn_mask`` [B,n,S_max]. ``cache['len']`` unchanged —
        the engine owns length bookkeeping (core/kv.py).  Returns (logits,
        cache'), and with ``count_experts`` also the routed experts that the
        live rows (``row_idx >= 0``) reached, summed over the MoE layers."""
        h = self._embed(params, tokens)
        ctx = Ctx(mode="cached", positions=positions, row_idx=row_idx, attn_mask=attn_mask)
        h, nc, touched = apply_model(self.cfg, params, h, ctx, cache=cache)
        nc["len"] = cache["len"]
        logits = logits_from_hidden(self.cfg, params, h)
        if count_experts:
            return logits, nc, jnp.asarray(touched, jnp.int32)
        return logits, nc

    @property
    def n_moe_layers(self) -> int:
        return self.cfg.layer_kinds.count("moe")

    def chain_forward(self, params, cache, tokens, n_commit, S_max):
        """Chain-mode forward of n tokens starting at cache['len'].

        State blocks commit exactly ``n_commit`` steps (masked recurrence);
        attention blocks write rows [len, len+n) (rows beyond the committed
        point are dead and overwritten next round).  Returns (logits, cache')
        with cache'.len = len + n_commit.
        """
        B, n = tokens.shape
        start = cache["len"]
        positions = start + jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
        row_idx = positions
        cols = jnp.arange(S_max, dtype=jnp.int32)
        attn_mask = cols[None, None, :] <= positions[:, :, None]
        if self.cfg.sliding_window:
            attn_mask &= cols[None, None, :] > positions[:, :, None] - self.cfg.sliding_window
        commit = jnp.broadcast_to(jnp.arange(n) < n_commit, (B, n))
        h = self._embed(params, tokens)
        ctx = Ctx(
            mode="cached",
            positions=positions,
            row_idx=row_idx,
            attn_mask=attn_mask,
            commit_mask=commit,
            row_start=start,  # contiguous rows: dynamic_update_slice fast path
        )
        h, nc, _ = apply_model(self.cfg, params, h, ctx, cache=cache)
        nc["len"] = start + jnp.asarray(n_commit, jnp.int32)
        return logits_from_hidden(self.cfg, params, h), nc

    def decode_step(self, params, cache, tokens, S_max):
        """tokens [B,1] -> (logits [B,1,V], cache')."""
        return self.chain_forward(params, cache, tokens, 1, S_max)

    # ---- misc ----------------------------------------------------------------
    @property
    def uses_chain_spec(self) -> bool:
        return self.cfg.sub_quadratic  # SSM/hybrid: tree spec inapplicable

    def needs_enc(self) -> bool:
        return any("cross" in unit for unit, _ in build_plan(self.cfg))


def make_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
