"""Seeded weights of a Llama-architecture decoder, with a planted next-token
map, made on the device in one jitted call per model.

Greedy verification pins the served tokens to target-only greedy decoding,
so the draft's weights change no output; they only set how often the draft
guesses right.  Independent random draws would make the two models agree on
almost nothing, and a speculative round would then measure pure overhead.  So
both ``lm_head`` matrices are built from the model's own embedding:

  target   lm_head[:, j] = a * embed[pi^-1(j)]   (greedy maps token x to pi(x))
  draft    lm_head[:, j] = a * embed[pd^-1(j)]   with pd = pi on all tokens
                                                 but a seeded disagreement set D

The residual stream after the last layer still points mostly along the
current token's embedding (drawn at unit scale, ``EMBED_STD``), so the
planted logit wins by a wide margin.  On a seeded ``free`` subset F of D the target's
planted column is zeroed: after such a token the target's choice falls to the
rest of the vocabulary and depends on the context through attention; these
are the positions where rounding can change a token.  The draft then agrees
with the target on ``1 - |D|/V`` of the steps, spread evenly along every
chain (``plant_maps``).

The weights are a plain dict with the layer weights stacked on a leading axis:
  embed [V, d], final_norm [d], lm_head [d, V],
  layers: ln1 [L, d], wq [L, d, Hq, hd], wk/wv [L, d, Hkv, hd],
          wo [L, Hq, hd, d], ln2 [L, d], wg/wu [L, d, F], wd [L, F, d]
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic.generate import rng

EMBED_STD = 1.0  # of the embedding's entries; the other layers' scales follow from it

@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        """From a Hugging Face style configuration."""
        return cls(vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
                   n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
                   n_kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
                   d_ff=cfg["intermediate_size"])


def plant_maps(vocab: int, seed: int, disagree: float, free: float, block: int = 10):
    """(pi^-1, pd^-1, free_column_mask) from the seed, all on the host.

    pi is one cycle through the whole vocabulary in a seeded order, so a
    greedy chain never repeats within a request.  Along that cycle every
    block of ``block`` consecutive tokens holds exactly ``disagree * block``
    tokens of D, at seeded places, and the first of them in each block is in
    F (``free * block`` of them): the draft's agreement is the same share on
    every stretch of every chain, whatever the seed.  pd equals pi outside D
    and maps D by pi shifted one place along D, so it is a permutation that
    differs from pi on every token of D."""
    g = rng(seed, 2)
    n_d, n_f = int(round(disagree * block)), int(round(free * block))
    if not (0 <= n_f <= n_d <= block):
        raise ValueError(f"disagree {disagree} / free {free} do not fit blocks of {block}")
    order = g.permutation(vocab)
    pi = np.empty(vocab, np.int64)
    pi[order] = np.roll(order, -1)  # order[i] -> order[i + 1]
    places = [b + np.sort(g.choice(block, size=n_d, replace=False))
              for b in range(0, vocab - block + 1, block)]
    D = order[np.concatenate(places)] if places else np.zeros(0, np.int64)
    F = order[np.concatenate([p[:n_f] for p in places])] if places else D
    pd = pi.copy()
    pd[D] = pi[np.roll(D, -1)]
    keep = np.ones(vocab, np.float32)
    keep[pi[F]] = 0.0  # column pi(x) of every free token x
    return np.argsort(pi).astype(np.int32), np.argsort(pd).astype(np.int32), keep


def make_fn(dims: Dims, dtype, logit_scale: float, out_shardings=None):
    """The jitted ``f(key, inv_perm, keep) -> weights`` of one model."""
    V, d, L = dims.vocab, dims.d_model, dims.n_layers
    hq, hkv, hd, ff = dims.n_heads, dims.n_kv_heads, dims.head_dim, dims.d_ff
    dt = jnp.dtype(dtype)

    def make(key, inv_perm, keep):
        k = jax.random.split(key, 8)
        normal = lambda kk, shape, std: (jax.random.normal(kk, shape, dt) * jnp.asarray(std, dt))  # noqa: E731
        embed = normal(k[0], (V, d), EMBED_STD)
        # unit-scale columns: the planted logit is a * |h| * cos(h, embed[x])
        a = logit_scale / (EMBED_STD * np.sqrt(d))
        lm_head = (embed[inv_perm].T.astype(jnp.float32) * (a * keep)[None, :]).astype(dt)
        layers = {
            "ln1": jnp.ones((L, d), dt),
            "wq": normal(k[1], (L, d, hq, hd), d ** -0.5),
            "wk": normal(k[2], (L, d, hkv, hd), d ** -0.5),
            "wv": normal(k[3], (L, d, hkv, hd), d ** -0.5),
            "wo": normal(k[4], (L, hq, hd, d), (hq * hd) ** -0.5),
            "ln2": jnp.ones((L, d), dt),
            "wg": normal(k[5], (L, d, ff), d ** -0.5),
            "wu": normal(k[6], (L, d, ff), d ** -0.5),
            "wd": normal(k[7], (L, ff, d), ff ** -0.5),
        }
        return {"embed": embed, "final_norm": jnp.ones((d,), dt), "lm_head": lm_head,
                "layers": layers}

    return jax.jit(make, out_shardings=out_shardings)


def key_for(seed: int, stream: int):
    """A JAX key for one model of one run seed (any whole number)."""
    return jax.random.PRNGKey(int(rng(seed, stream).integers(0, 2**31 - 1)))
