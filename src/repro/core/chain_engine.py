"""Chain-mode speculative decoding for recurrent-state architectures
(SSM / hybrid: rwkv6, zamba2) — DESIGN.md §6.

Tree speculation is inapplicable to a recurrent state: the tree's branches
cannot share one sequential state, and forking it per node costs
O(nodes × state).  We therefore speculate on *chains* (the paper's
sequence-based degenerate case, PEARL/AMUSD-style) while keeping the paper's
actual contribution — asynchronous, disaggregated draft/target execution —
fully intact:

  * the draft group autoregressively proposes k tokens from a snapshot of its
    recurrent state (the generation-time state advance is throwaway);
  * the target group verifies the whole chain in ONE chunked forward
    (``chain_forward`` with n_commit=0: logits are teacher-forced, the
    recurrent state is untouched), then commits exactly the accepted prefix —
    pure-attention targets commit for free (rows are already written; only
    ``len`` moves), state-bearing targets recompute from the pre-round cache;
  * draft-state consistency after partial acceptance is restored by
    *recompute-from-pre-state*: one chain forward of the accepted tokens on
    the snapshot;
  * in parallel mode the draft's next chain is generated concurrently with
    verification under the all-accepted assumption and is kept when the
    assumption holds (PEARL's reuse condition), else discarded.

Greedy-equality invariant: emitted tokens equal target-only greedy decoding
exactly (tests/test_chain_engine.py).  Single-request engine (B = 1), the
paper's latency regime; batch > 1 is served by replication.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import EngineSession
from repro.obs.clock import monotonic
from repro.obs.trace import NULL_TRACER
from repro.sharding import use_mesh


@dataclasses.dataclass(frozen=True)
class ChainConfig:
    k: int = 6  # draft chain length per round
    mode: str = "parallel"  # "parallel" | "serial"
    max_new: int = 64
    eos_id: int = -1


@dataclasses.dataclass
class ChainStats:
    rounds: int = 0
    emitted: int = 0
    accepted: int = 0
    reused_chains: int = 0
    draft_chains: int = 0
    wall_s: float = 0.0
    sync_s: float = 0.0  # host seconds blocked in the rounds' designated sync

    @property
    def compression_ratio(self) -> float:
        return self.emitted / max(self.rounds, 1)


def _has_state(model) -> bool:
    return any(k in ("mamba2", "rwkv6") for k in model.cfg.layer_kinds)


class ChainSpecEngine:
    def __init__(self, target, draft, cfg: ChainConfig, S_max_t: int, S_max_d: int,
                 mesh_target=None, mesh_draft=None):
        self.target, self.draft, self.cfg = target, draft, cfg
        self.S_max_t, self.S_max_d = S_max_t, S_max_d
        self.mesh_target, self.mesh_draft = mesh_target, mesh_draft
        k = cfg.k

        def draft_chain(dparams, dcache, first_tok):
            """k greedy draft tokens; the advanced cache is returned for the
            full-acceptance reuse path (otherwise discarded)."""

            def step(carry, _):
                cache, tok = carry
                logits, cache = draft.decode_step(dparams, cache, tok, S_max_d)
                nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
                return (cache, nxt), nxt[:, 0]

            (dcache, _), toks = jax.lax.scan(step, (dcache, first_tok), None, length=k)
            return jnp.moveaxis(toks, 0, 1), dcache  # [B, k]

        def verify(tparams, tcache, u):
            """One target forward over the chain; no state commitment."""
            logits, tcache_rows = target.chain_forward(tparams, tcache, u, 0, S_max_t)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), tcache_rows

        self._draft_chain = jax.jit(draft_chain)
        self._verify = jax.jit(verify)
        self._tcommit = jax.jit(
            lambda tp, tc, u, n: target.chain_forward(tp, tc, u, n, S_max_t)[1]
        )
        self._dcommit = jax.jit(
            lambda dp, dc, u, n: draft.chain_forward(dp, dc, u, n, S_max_d)[1]
        )
        self._dprefill = jax.jit(lambda p, t, S: draft.prefill(p, tokens=t, S_max=S), static_argnums=(2,))
        self._tprefill = jax.jit(lambda p, t, S: target.prefill(p, tokens=t, S_max=S), static_argnums=(2,))

    # ------------------------------------------------------------------
    def session(self, tparams, dparams, *, tracer=None, track="chain") -> "ChainSession":
        """Bind params (+ optional tracer) into a ChainSession — the round
        API surface; mirrors ``SpecEngine.session``."""
        return ChainSession(self, tparams, dparams,
                            tracer=tracer or NULL_TRACER, track=track)


@dataclasses.dataclass
class ChainSession:
    """Params bound to a ChainSpecEngine — the chain-mode analogue of
    ``EngineSession``.  ``generate`` emits the same phase-span vocabulary as
    the tree engine (``verify_dispatch`` held open across the concurrent
    next-chain speculation, ``draft_lookahead``, ONE fused ``sync_emitted``
    host transfer per round, ``reroot_grow`` for the state commit), so chain
    rounds land in the same ``phase_breakdown`` and the same HOTSYNC budget:
    one designated sync point per round."""

    engine: ChainSpecEngine
    tparams: Any
    dparams: Any
    tracer: Any = NULL_TRACER
    track: str = "chain"

    def generate(self, prompt, max_new=None):
        eng = self.engine
        tparams, dparams = self.tparams, self.dparams
        c = eng.cfg
        k = c.k
        max_new = max_new or c.max_new
        B, P = prompt.shape
        assert B == 1, "chain engine is per-request (paper's latency regime)"
        t0 = monotonic()

        with use_mesh(eng.mesh_target):
            tlogits, tcache = eng._tprefill(tparams, jnp.asarray(prompt), eng.S_max_t)
        with use_mesh(eng.mesh_draft):
            _, dcache = eng._dprefill(dparams, jnp.asarray(prompt), eng.S_max_d)

        pending = jnp.argmax(tlogits[:, -1, :], -1).astype(jnp.int32)[:, None]  # [1,1]
        out = [int(pending[0, 0])]
        stats = ChainStats(emitted=1)
        t_state = _has_state(eng.target)
        pre_drafts = None  # speculated next chain (parallel reuse)
        done = (c.eos_id >= 0 and out[0] == c.eos_id) or len(out) >= max_new

        while not done:
            if (P + stats.emitted + 2 * k + 2) >= min(eng.S_max_t, eng.S_max_d):
                break
            rspan = self.tracer.begin("round", self.track)
            dsnap = dcache  # pre-round draft state (functional: snapshot is free)

            # --- draft chain -------------------------------------------------
            with self.tracer.span("draft_expand", self.track):
                with use_mesh(eng.mesh_draft):
                    if pre_drafts is not None:
                        drafts, dfull_cache = pre_drafts
                        stats.reused_chains += 1
                    else:
                        drafts, _ = eng._draft_chain(dparams, dcache, pending)
                        dfull_cache = None
                        stats.draft_chains += 1
                u = jnp.concatenate([pending, drafts[:, : k - 1]], axis=1)  # [1,k]

            # --- target verification: the span stays open until the verified
            # tokens land at the sync point — it IS the verify window the
            # concurrent speculation below overlaps with
            vspan = self.tracer.begin("verify_dispatch", self.track)
            with use_mesh(eng.mesh_target):
                argmax, tcache_rows = eng._verify(tparams, tcache, u)

            # --- concurrently: speculate the next chain ----------------------
            next_pre = None
            if c.mode == "parallel":
                with self.tracer.span("draft_lookahead", self.track):
                    with use_mesh(eng.mesh_draft):
                        dfull = eng._dcommit(dparams, dsnap, u, jnp.asarray(k))
                        nxt_drafts, nxt_cache = eng._draft_chain(
                            dparams, dfull, drafts[:, k - 1:])
                        next_pre = (nxt_drafts, None)
                        stats.draft_chains += 1

            # --- sync point: ONE fused transfer of the round's verdict ------
            argmax_h, drafts_h = EngineSession.sync_emitted(
                self.tracer, self.track, stats, (argmax, drafts))
            vspan.end()
            argmax_h = np.asarray(argmax_h)[0]  # [k]
            drafts_h = np.asarray(drafts_h)[0]  # [k]
            n_acc = 0
            while n_acc < k - 1 and drafts_h[n_acc] == argmax_h[n_acc]:
                n_acc += 1
            n_emit = n_acc + 1

            for t in argmax_h[:n_emit].tolist():
                out.append(int(t))
                if (c.eos_id >= 0 and t == c.eos_id) or len(out) >= max_new:
                    done = True
                    break
            stats.rounds += 1
            stats.accepted += n_acc
            stats.emitted += n_emit

            full = (n_acc == k - 1) and (argmax_h[k - 1] == drafts_h[k - 1])
            pending = jnp.asarray([[int(argmax_h[n_emit - 1])]], jnp.int32)

            # --- commit accepted prefix ---------------------------------------
            with self.tracer.span("reroot_grow", self.track):
                n = jnp.asarray(n_emit)
                with use_mesh(eng.mesh_target):
                    if t_state:
                        tcache = eng._tcommit(tparams, tcache, u, n)
                    else:  # attention-only: rows already written, just move len
                        tcache = {**tcache_rows, "len": tcache_rows["len"] + n}
                with use_mesh(eng.mesh_draft):
                    if full and c.mode == "parallel":
                        dcache = dfull  # chain fully accepted: snapshot+u == truth
                        pre_drafts = (nxt_drafts, None)
                    else:
                        dcache = eng._dcommit(dparams, dsnap, u, n)
                        pre_drafts = None
            rspan.end()

        stats.wall_s = monotonic() - t0
        return [out[:max_new]], stats
