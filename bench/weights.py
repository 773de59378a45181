"""Seeded weights of a decoder, with a planted next-token map, made on the
device in one jitted call per model.

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

What is shared by every architecture is made here: embed [V, d],
final_norm [d] and lm_head [d, V].  The model's layout module
(``bench/layouts/<name>.py``) makes what sits between them, ``layers``, in
the layout its reference reads, from ``KEYS`` seeded keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic.generate import rng

EMBED_STD = 1.0  # of the embedding's entries; the other layers' scales follow from it


def normal(key, shape, std, dt):
    """Entries drawn from N(0, std^2) in the weights' dtype."""
    return jax.random.normal(key, shape, dt) * jnp.asarray(std, dt)


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


def make_fn(layout, dims, dtype, logit_scale: float, out_shardings=None):
    """The jitted ``f(key, inv_perm, keep) -> weights`` of one model of the
    layout module ``layout``, whose ``dims`` give ``vocab`` and ``d_model``."""
    V, d = dims.vocab, dims.d_model
    dt = jnp.dtype(dtype)

    def make(key, inv_perm, keep):
        k = jax.random.split(key, 1 + layout.KEYS)
        embed = normal(k[0], (V, d), EMBED_STD, dt)
        # unit-scale columns: the planted logit is a * |h| * cos(h, embed[x])
        a = logit_scale / (EMBED_STD * np.sqrt(d))
        lm_head = (embed[inv_perm].T.astype(jnp.float32) * (a * keep)[None, :]).astype(dt)
        return {"embed": embed, "final_norm": jnp.ones((d,), dt), "lm_head": lm_head,
                "layers": layout.layer_weights(k[1:], dims, dt)}

    return jax.jit(make, out_shardings=out_shardings)


def key_for(seed: int, stream: int):
    """A JAX key for one model of one run seed (any whole number)."""
    return jax.random.PRNGKey(int(rng(seed, stream).integers(0, 2**31 - 1)))
