"""SpecEngine — asynchronous, disaggregated speculative decoding (paper §3.1,
Algorithm 1, Figure 3).

The draft model lives on one device group (submesh), the target on another.
JAX's asynchronous dispatch makes the two jitted programs run concurrently on
disjoint device sets: the verify step for round n is enqueued first, then the
d draft-tree expansions for round n+1 are enqueued on the draft group; the
host blocks only on the tiny verified-token transfer (the paper's NCCL
exchange).  ``mode="serial"`` is the SwiftSpec-base baseline (expand, then
verify, no overlap).

Greedy-verification invariant: the emitted stream equals target-only greedy
decoding token-for-token (tests/test_engine.py).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import kv as kvm
from repro.core import tree as T
from repro.obs.clock import monotonic
from repro.obs.trace import NOOP_SPAN, NULL_TRACER
from repro.sharding import SERVING_RULES, use_mesh


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    bs: int = 8  # target verification batch (paper §5.5: 8)
    w: int = 4  # draft leaves expanded per step (paper §5.5: 8)
    c: int = 2  # children proposed per expanded leaf
    d: int = 3  # tree expansions per round (profiled: ~t_target/t_draft)
    n_cap: int = 64  # tree node capacity
    mode: str = "parallel"  # "parallel" | "serial"
    max_new: int = 64
    eos_id: int = -1  # -1: never stop early
    draft_bypass: bool = False  # straggler mitigation: verify root-only chain
    async_rounds: bool = False  # pipeline rounds: draft N+1's tree while N verifies


@dataclasses.dataclass
class SpecStats:
    """Per-row exact accounting: ``emitted_rows``/``accepted_rows`` hold one
    running total per batch row, accumulated round by round; the scalar
    ``emitted``/``accepted`` views are per-row means derived at read time
    (the old per-round ``sum // B`` floor silently dropped tokens whenever
    rows emitted unequal counts)."""

    rounds: int = 0
    draft_steps: int = 0
    wall_s: float = 0.0
    emitted_rows: np.ndarray | None = None  # i64[B] per-row emitted totals
    accepted_rows: np.ndarray | None = None  # i64[B] per-row accepted totals
    spec_rounds: int = 0  # rounds run through the async lookahead path
    spec_commits: int = 0  # of those, rounds whose lookahead tree was adopted
    # always-on round accounting (docs/observability.md), the same whether a
    # tracer is on or not: host seconds blocked in the round's one designated
    # sync (``sync_emitted``), and dispatches per jitted program, keyed by the
    # name the device trace gives the program
    sync_s: float = 0.0
    dispatches: dict = dataclasses.field(default_factory=dict)
    # MoE targets: the MoE layers the verify calls ran (``moe_layer_calls``),
    # and, summed over them, the routed experts that received at least one
    # live tree token, held on the device by the verify program itself
    # (``touched``) and fetched only when ``experts_touched`` is read
    moe_layer_calls: int = 0
    touched: Any = None

    def add_round(self, n_emitted, n_accepted):
        n_emitted = np.asarray(n_emitted, np.int64)
        if self.emitted_rows is None:
            self.emitted_rows = np.zeros_like(n_emitted)
            self.accepted_rows = np.zeros_like(n_emitted)
        self.emitted_rows += n_emitted
        self.accepted_rows += np.asarray(n_accepted, np.int64)
        self.rounds += 1

    def ran(self, program: str, n: int = 1) -> None:
        """Count ``n`` dispatches of the jitted ``program``."""
        self.dispatches[program] = self.dispatches.get(program, 0) + n

    @property
    def experts_touched(self) -> int:
        return 0 if self.touched is None else int(jax.device_get(self.touched))

    @property
    def emitted(self) -> float:
        return 0.0 if self.emitted_rows is None else float(self.emitted_rows.mean())

    @property
    def accepted(self) -> float:
        return 0.0 if self.accepted_rows is None else float(self.accepted_rows.mean())

    @property
    def total_emitted(self) -> int:
        return 0 if self.emitted_rows is None else int(self.emitted_rows.sum())

    @property
    def tokens_per_round(self) -> float:
        return self.emitted / max(self.rounds, 1)

    @property
    def compression_ratio(self) -> float:
        """Paper's metric: tokens per target-model inference."""
        return self.tokens_per_round


@dataclasses.dataclass
class EngineState:
    """Device-side state of one decode batch, advanced by ``SpecEngine.step``.

    Treat it linearly: the jitted steps donate their cache/tree buffers, so a
    state consumed by step()/admit_slot()/release_slot() must not be reused —
    always thread the returned state forward (generate() and the serving
    runtime both do)."""

    tcache: Any  # target KV cache [U, B, S_max_t, ...]
    dcache: Any  # draft KV cache [U, B, S_max_d, ...]
    tr: Any  # stacked Tree, leaves [B, ...]
    plan: Any  # BatchPlan for the NEXT verification, leaves [B, ...]


@dataclasses.dataclass(frozen=True)
class StepResult:
    """Host-side outcome of one round, per batch row."""

    emitted: np.ndarray  # i32[B, bs+1] verified tokens (accepted + bonus)
    n_emitted: np.ndarray  # i32[B]
    n_accepted: np.ndarray  # i32[B]


@dataclasses.dataclass
class RoundInFlight:
    """One dispatched-but-unreconciled speculative round.

    Created by ``EngineSession.dispatch_verify`` + ``draft_next_tree``,
    consumed exactly once by ``EngineSession.reconcile``.  Everything here is
    a device future except ``draft_steps``; nothing has crossed to the host
    yet.  The owning session's ``state`` is consumed (its buffers donated)
    while a round is in flight — the fresh state is reassembled from these
    fields at reconcile time.
    """

    plan: Any  # BatchPlan actually submitted to verify (post-bypass)
    tcache: Any  # verify-updated target cache (correct regardless of outcome)
    verify: tuple  # (acc_pos, n_acc, bonus, emitted, n_emitted) device futures
    snapshot: tuple | None = None  # (tr, dcache) post-expansion, pre-reroot
    lookahead: tuple | None = None  # (tr, dcache, plan) drafted for round N+1
    pred: tuple | None = None  # (acc_pos, n_acc, bonus) predicted outcome
    draft_steps: int = 0
    verify_span: Any = NOOP_SPAN  # open until the reconcile sync (verify window)


def _effective_depth(depth: int | None, default: int) -> int:
    """Resolve a round's draft depth: a concrete Python int (a host-side
    loop trip count — never traced) with ``None`` meaning the config's
    global ``d``."""
    if depth is None:
        return default
    d = int(depth)
    if d < 1:
        raise ValueError(f"draft depth must be >= 1, got {depth}")
    return d


def greedy_decode(model, params, prompt, n: int, S_max: int):
    """Target-only greedy decoding: the oracle the speculative stream must
    equal token for token.  prompt: i32 [B, P].  Returns (tokens i32 [B, n],
    top2 f32 [B, n, 2]): each step's argmax and the two largest logits it was
    picked from, whose gap says how close to a tie that step was."""

    def pick(logits):
        last = logits[:, -1, :].astype(jnp.float32)
        return jnp.argmax(last, -1)[:, None].astype(jnp.int32), jax.lax.top_k(last, 2)[0]

    @jax.jit
    def prefill_pick(p, t):
        logits, cache = model.prefill(p, tokens=t, S_max=S_max)
        return pick(logits), cache

    @jax.jit
    def decode_pick(p, c, t):
        logits, cache = model.decode_step(p, c, t, S_max)
        return pick(logits), cache

    (cur, top), cache = prefill_pick(params, jnp.asarray(prompt))
    toks, tops = [cur], [top]
    for _ in range(n - 1):
        (cur, top), cache = decode_pick(params, cache, cur)
        toks.append(cur)
        tops.append(top)
    toks, tops = jax.device_get((toks, tops))
    return np.concatenate(toks, axis=1), np.stack(tops, axis=1)


def absorb_emitted(out: list, emitted_row, n_emitted: int, max_new: int, eos_id: int):
    """Append one row's verified tokens to ``out`` until EOS or ``max_new``.

    The single definition of truncation semantics (token appended first, then
    tested) shared by generate() and the serving runtime — the byte-identical
    serving contract depends on both paths stopping on exactly the same token.
    Returns (new_tokens, done)."""
    new = []
    for t in emitted_row[:n_emitted].tolist():
        out.append(int(t))
        new.append(int(t))
        if (eos_id >= 0 and t == eos_id) or len(out) >= max_new:
            return new, True
    return new, False


def _serving(mesh):
    """Mesh context of one serving role: the engine's programs trace under
    the serving rules (KV cache sharded over kv heads)."""
    return use_mesh(mesh, SERVING_RULES)


def _to(mesh, tree):
    """Replicate ``tree`` onto ``mesh`` — the explicit hop of small per-round
    values (plans, verify outcomes) between the draft and target groups, the
    paper's verified-token exchange.  A no-op without a mesh."""
    if mesh is None:
        return tree
    return jax.device_put(tree, NamedSharding(mesh, PartitionSpec()))


class SpecEngine:
    """Tree-based speculative decoding for attention architectures."""

    def __init__(self, target, draft, cfg: SpecConfig, S_max_t: int, S_max_d: int,
                 mesh_target=None, mesh_draft=None):
        self.target, self.draft, self.cfg = target, draft, cfg
        self.S_max_t, self.S_max_d = S_max_t, S_max_d
        self.mesh_target, self.mesh_draft = mesh_target, mesh_draft
        window = target.cfg.sliding_window
        c = cfg
        if c.async_rounds and c.mode != "parallel":
            raise ValueError(
                f"async_rounds requires mode='parallel' (got mode={c.mode!r}): "
                "the lookahead pipeline IS the parallel overlap")

        # ----- jitted draft-side steps ------------------------------------
        def leaves(tr):
            leaf_ids, leaf_valid = jax.vmap(lambda t: T.select_leaves(t, c.w))(tr)
            tokens, rows, positions, mask, _ = jax.vmap(
                lambda t, li, lv: T.leaf_inputs(t, li, lv, S_max_d, draft.cfg.sliding_window)
            )(tr, leaf_ids, leaf_valid)
            return leaf_ids, leaf_valid, tokens, rows, positions, mask

        def grow(tr, leaf_ids, leaf_valid, rows, logits):
            lp = jax.nn.log_softmax(logits.astype(jnp.float32))
            top_lp, top_tok = jax.lax.top_k(lp, c.c)  # [B,w,c]
            return jax.vmap(T.insert_children)(tr, leaf_ids, leaf_valid, rows, top_tok, top_lp)

        def expand(dparams, tr, dcache):
            leaf_ids, leaf_valid, tokens, rows, positions, mask = leaves(tr)
            logits, dcache = draft.spec_forward(dparams, dcache, tokens, positions, rows, mask)
            return grow(tr, leaf_ids, leaf_valid, rows, logits), dcache

        def select_plan(tr):
            return jax.vmap(lambda t: T.select_batch(t, c.bs, S_max_t, window))(tr)

        # the re-root is three separately-dispatched programs so the host can
        # put a `kv_move` tracer span around exactly the cache-reorganization
        # dispatch (the cost the fused kernels attack):
        #   reroot       — tree bookkeeping; emits the MovePlan + FillPlan
        #   kv_move      — apply the MovePlan to the draft cache (donating on
        #                  the committed path, snapshot-preserving on the
        #                  lookahead)
        #   fill_prefix  — prefix fill and the first regrowth expansion
        def reroot(tr, node_ids, acc_pos, n_acc, bonus):
            return jax.vmap(T.reroot)(tr, node_ids, acc_pos, n_acc, bonus)

        def kv_move(dcache, src, dst, mask, *, donate):
            dcache = kvm.apply_moves(dcache, src, dst, mask, donate=donate)
            return kvm.set_length(dcache, 0)  # length bookkeeping via tree.plen

        def fill_prefix(dparams, tr, dcache, fill):
            """The re-rooted tree's prefix fill AND its first growth expansion,
            in one draft forward over the w leaf slots followed by the F fill
            slots (accepted-but-unexpanded tokens, causal over the prefix).
            The forward writes every new K/V row before it attends, and the
            fill rows lie in [plen_old, plen-1), inside the leaves' prefix
            mask, so the leaves see them as they would after a separate
            fill; fill, root and fresh tree rows never overlap.  Draft
            weights are read once per re-root instead of twice.  A MoE
            draft's dispatch is dropless and the padding fill slots route
            nowhere, so the leaves' logits are what a plain expansion
            gives."""
            leaf_ids, leaf_valid, tokens, rows, positions, mask = leaves(tr)
            cols = jnp.arange(S_max_d, dtype=jnp.int32)
            fmask = (cols[None, None, :] <= fill.rows[:, :, None]) & fill.mask[:, :, None]

            def cat(x, f):
                return jnp.concatenate([x, f], axis=1)

            logits, dcache = draft.spec_forward(
                dparams, dcache, cat(tokens, fill.tokens), cat(positions, fill.positions),
                cat(rows, fill.rows), cat(mask, fmask))
            return grow(tr, leaf_ids, leaf_valid, rows, logits[:, :c.w]), dcache

        def seed(tr, root_tok, plen, root_logits):
            return jax.vmap(lambda t, tok, lg: T.seed_root(t, tok, plen, lg, c.c))(
                tr, root_tok, root_logits
            )

        # ----- jitted target-side steps -------------------------------------
        def verify_walk(logits, tokens, rows, parent_pos, valid):
            argmax = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            acc_pos, n_acc, bonus, emitted, n_emitted = jax.vmap(T.verify_walk)(
                tokens, parent_pos, valid, argmax
            )
            # compaction plan: accepted rows -> prefix  (target Fig.5
            # analogue); applied by the separately-dispatched _compact so the
            # reorganization cost is visible under its own kv_move span
            bs = tokens.shape[1]
            plen = rows[:, 0] + 1  # root row = plen-1
            src = jnp.where(acc_pos >= 0, jnp.take_along_axis(rows, jnp.maximum(acc_pos, 0), axis=1), -1)
            dst = plen[:, None] + jnp.arange(bs, dtype=jnp.int32)[None, :]
            mmask = (jnp.arange(bs)[None, :] < n_acc[:, None]) & (src >= 0)
            return acc_pos, n_acc, bonus, emitted, n_emitted, (src, dst, mmask)

        if target.n_moe_layers:
            def verify(tparams, tcache, tokens, positions, rows, mask, parent_pos, valid,
                       touched):
                """Also advances ``touched``, the running count of routed
                experts that live tree tokens reached (``SpecStats.touched``)."""
                logits, tcache, n = self.target.spec_forward(
                    tparams, tcache, tokens, positions, rows, mask, count_experts=True)
                *out, mv = verify_walk(logits, tokens, rows, parent_pos, valid)
                return (*out, tcache, mv, touched + n)
        else:
            def verify(tparams, tcache, tokens, positions, rows, mask, parent_pos, valid):
                logits, tcache = self.target.spec_forward(
                    tparams, tcache, tokens, positions, rows, mask)
                *out, mv = verify_walk(logits, tokens, rows, parent_pos, valid)
                return (*out, tcache, mv)

        def compact(tcache, src, dst, mask):
            return kvm.apply_moves(tcache, src, dst, mask, donate=True)

        self._expand = jax.jit(expand, donate_argnums=(1, 2))
        self._select_plan = jax.jit(select_plan)
        self._reroot = jax.jit(reroot, donate_argnums=(0,))
        self._kv_move = jax.jit(functools.partial(kv_move, donate=True), donate_argnums=(0,))
        # async lookahead twins: the speculative re-root must NOT donate —
        # the pre-reroot (tr, dcache) snapshot stays alive as the reconcile
        # fallback basis until the verify outcome lands on the host (and the
        # non-donating kv_move routes to the snapshot-preserving kernel)
        self._spec_reroot = jax.jit(reroot)
        self._spec_kv_move = jax.jit(functools.partial(kv_move, donate=False))
        self._fill_grow = jax.jit(fill_prefix, donate_argnums=(1, 2))
        self._predict = jax.jit(jax.vmap(T.predict_accept))
        self._seed = jax.jit(seed, static_argnums=(2,))
        self._verify = jax.jit(verify, donate_argnums=(1,))
        self._compact = jax.jit(compact, donate_argnums=(0,))
        # every program is a named function, so the device trace names it
        # (``jit_<name>``; a lambda would show as ``jit__lambda``)
        def draft_prefill(p, t, S):
            return draft.prefill(p, tokens=t, S_max=S)

        def target_prefill(p, t, S):
            return target.prefill(p, tokens=t, S_max=S)

        # per-slot lifecycle (continuous batching); slot/plen are traced so
        # one compile covers every slot index and prompt length.  The cache
        # writes go through per-engine closures: jit caches traces by the
        # wrapped function, and a module function would share one trace (and
        # one kernel choice) across engines built under different flags.
        def install_slot(cache, donor, slot):
            return kvm.install_slot(cache, donor, slot)

        def zero_slot(cache, slot):
            return kvm.zero_slot(cache, slot)

        def seed_slot(tr, slot, tok, plen, lg):
            return T.seed_slot(tr, slot, tok, plen, lg, c.c)

        self._dprefill = jax.jit(draft_prefill, static_argnums=(2,))
        self._tprefill = jax.jit(target_prefill, static_argnums=(2,))
        self._install = jax.jit(install_slot, donate_argnums=(0,))
        self._zero_slot = jax.jit(zero_slot, donate_argnums=(0,))
        self._reset_slot = jax.jit(T.reset_slot, donate_argnums=(0,))
        self._seed_slot = jax.jit(seed_slot, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # state lifecycle (used by generate() below and by serving/runtime.py)
    # ------------------------------------------------------------------
    @property
    def grow_per_round(self) -> int:
        """Expansions needed to refill a re-rooted tree to >= bs nodes."""
        c = self.cfg
        return max(1, -(-(c.bs) // (c.w * c.c)))

    @property
    def plen_budget(self) -> int:
        """Largest per-row prefix length the caches can safely carry into one
        more round: verify rows reach plen-1+bs and the re-rooted tree needs
        another bs of headroom, so stop ``2*bs`` short of the tighter cache.

        The single definition of the KV-budget bound, shared by ``generate()``
        and the serving runtimes — if the two ever drift, a request near the
        budget stops at different tokens solo vs served, silently breaking the
        byte-identical contract."""
        return min(self.S_max_t, self.S_max_d) - 2 * self.cfg.bs

    def init_state(self, B: int) -> EngineState:
        """Empty B-slot serving state: zero caches, parked (invalid) trees.

        Parked slots are inert: their plans carry no valid node, so verify
        writes nothing and expand skips them; the runtime discards whatever
        they "emit"."""
        tcache = self._new_cache(self.target, B, self.S_max_t, self.mesh_target)
        dcache = self._new_cache(self.draft, B, self.S_max_d, self.mesh_draft)
        tr = self._empty_trees(B)
        with _serving(self.mesh_draft):
            plan = self._select_plan(tr)
        return EngineState(tcache, dcache, tr, plan)

    def _empty_trees(self, B: int):
        """B stacked empty trees, on the draft group (where trees live)."""
        return _to(self.mesh_draft,
                   jax.tree.map(lambda x: jnp.stack([x] * B), T.init_tree(self.cfg.n_cap)))

    @staticmethod
    def _new_cache(model, B: int, S_max: int, mesh):
        """Zero cache (every cache starts as zeros) built directly in its
        serving layout on ``mesh``."""
        if mesh is None:
            return model.init_cache(B, S_max)
        shapes = jax.eval_shape(functools.partial(model.init_cache, B, S_max))
        return jax.tree.map(lambda s, sh: jnp.zeros(s.shape, s.dtype, device=sh),
                            shapes, kvm.cache_shardings(mesh, shapes, SERVING_RULES))

    def _prefill_state(self, tparams, dparams, prompt,
                       stats: SpecStats | None = None) -> EngineState:
        """Whole-batch prefill + tree seed + initial growth (all rows start
        together — the generate() path); its dispatches are counted in
        ``stats``."""
        B, P = prompt.shape
        with _serving(self.mesh_draft):
            dlogits, dcache = self._dprefill(dparams, jnp.asarray(prompt), self.S_max_d)
        with _serving(self.mesh_target):
            _, tcache = self._tprefill(tparams, jnp.asarray(prompt), self.S_max_t)
        tr = self._empty_trees(B)
        root_tok = jnp.asarray(prompt[:, -1], jnp.int32)
        with _serving(self.mesh_draft):
            tr = self._seed(tr, root_tok, P, dlogits[:, -1, :])
            for _ in range(self.grow_per_round):
                tr, dcache = self._expand(dparams, tr, dcache)
            plan = self._select_plan(tr)
        if stats is not None:
            for program in ("jit_draft_prefill", "jit_target_prefill", "jit_seed",
                            "jit_select_plan"):
                stats.ran(program)
            stats.ran("jit_expand", self.grow_per_round)
        return EngineState(tcache, dcache, tr, plan)

    def session(self, tparams, dparams, *, state: EngineState | None = None,
                n_slots: int | None = None, tracer=None, track: str = "engine",
                stats: SpecStats | None = None) -> "EngineSession":
        """Bind params (+ optional state, tracer and stats) into an
        ``EngineSession`` — the round API: ``session.step()`` / ``admit_slot``
        / ``release_slot`` / ``generate``, plus the async phase methods
        ``dispatch_verify`` / ``draft_next_tree`` / ``reconcile``.  Pass
        ``n_slots`` to start from an empty parked serving state."""
        if state is None and n_slots is not None:
            state = self.init_state(n_slots)
        return EngineSession(
            engine=self, tparams=tparams, dparams=dparams, state=state,
            tracer=tracer if tracer is not None else NULL_TRACER, track=track,
            stats=stats if stats is not None else SpecStats())

    # --- one-release deprecation shims over the session API ---------------
    def admit_slot(self, tparams, dparams, state: EngineState, slot: int, prompt) -> EngineState:
        """Deprecated: use ``session(tparams, dparams, state=...).admit_slot``."""
        warnings.warn(
            "SpecEngine.admit_slot(tparams, dparams, state, ...) is deprecated; "
            "bind an EngineSession via SpecEngine.session(...) instead",
            DeprecationWarning, stacklevel=2)
        s = self.session(tparams, dparams, state=state)
        s.admit_slot(slot, prompt)
        return s.state

    def release_slot(self, state: EngineState, slot: int) -> EngineState:
        """Deprecated: use ``EngineSession.release_slot``.

        The old positional form never carried params, so the shim binds None —
        release touches no model weights."""
        warnings.warn(
            "SpecEngine.release_slot(state, slot) is deprecated; "
            "bind an EngineSession via SpecEngine.session(...) instead",
            DeprecationWarning, stacklevel=2)
        s = self.session(None, None, state=state)
        s.release_slot(slot)
        return s.state

    def step(self, tparams, dparams, state: EngineState, stats: SpecStats | None = None,
             tracer=None, trace_track: str = "engine"):
        """Deprecated: use ``EngineSession.step``.  Returns (state', StepResult)."""
        warnings.warn(
            "SpecEngine.step(tparams, dparams, state, ...) is deprecated; "
            "bind an EngineSession via SpecEngine.session(...) instead",
            DeprecationWarning, stacklevel=2)
        s = self.session(tparams, dparams, state=state, tracer=tracer, track=trace_track,
                         stats=stats)
        res = s.step(stats=stats)
        return s.state, res

    def generate(self, tparams, dparams, prompt, max_new=None):
        """Deprecated: use ``session(tparams, dparams).generate(prompt)``."""
        warnings.warn(
            "SpecEngine.generate(tparams, dparams, prompt) is deprecated; "
            "use SpecEngine.session(tparams, dparams).generate(prompt)",
            DeprecationWarning, stacklevel=2)
        return self.session(tparams, dparams).generate(prompt, max_new=max_new)

    def profile(self, tparams, dparams, prompt, iters: int = 3):
        """Paper §5.5 profile pass: wall-time one draft expansion and one
        target verification (jits warmed first).  Returns ProfileResult."""
        from repro.core.scheduler import ProfileResult

        B, P = prompt.shape
        with _serving(self.mesh_draft):
            dlogits, dcache = self._dprefill(dparams, jnp.asarray(prompt), self.S_max_d)
        with _serving(self.mesh_target):
            _, tcache = self._tprefill(tparams, jnp.asarray(prompt), self.S_max_t)
        tr = self._empty_trees(B)
        with _serving(self.mesh_draft):
            tr = self._seed(tr, jnp.asarray(prompt[:, -1], jnp.int32), P, dlogits[:, -1, :])
            tr, dcache = self._expand(dparams, tr, dcache)  # warm
            plan = _to(self.mesh_target, self._select_plan(tr))

        def draft_once():
            nonlocal tr, dcache
            with _serving(self.mesh_draft):
                tr, dcache = self._expand(dparams, tr, dcache)
                jax.block_until_ready(tr.tokens)

        def target_once():
            nonlocal tcache
            with _serving(self.mesh_target):
                out = self._verify(tparams, tcache, plan.tokens, plan.positions,
                                   plan.rows, plan.mask, plan.parent_pos, plan.valid,
                                   *self._touched0())
                tcache = self._compact(out[5], *out[6])
                jax.block_until_ready(out[0])

        target_once()  # warm
        t0 = monotonic()
        for _ in range(iters):
            draft_once()
        t_d = (monotonic() - t0) / iters
        t0 = monotonic()
        for _ in range(iters):
            target_once()
        t_t = (monotonic() - t0) / iters
        return ProfileResult(t_draft_s=t_d, t_target_s=t_t)

    def _touched0(self) -> tuple:
        """The verify program's expert-count argument before its first call:
        a zero for a target with MoE layers, else none (its verify counts
        nothing)."""
        return (np.zeros((), np.int32),) if self.target.n_moe_layers else ()

    def _bypass(self, plan):
        """Straggler mitigation: degenerate to root-only verification."""
        keep = jnp.arange(plan.tokens.shape[1]) == 0
        return T.BatchPlan(
            node_ids=plan.node_ids,
            tokens=plan.tokens,
            rows=jnp.where(keep[None, :], plan.rows, -1),
            positions=plan.positions,
            mask=plan.mask & keep[None, :, None],
            parent_pos=plan.parent_pos,
            valid=plan.valid & keep[None, :],
        )


@dataclasses.dataclass
class EngineSession:
    """Params + state + tracer bound into one decode session — the round API.

    Replaces the positional ``(tparams, dparams, state)`` threading: the
    session owns the linear ``EngineState`` and advances it in place.  One
    session per serving replica (``EngineStepper``) or per solo ``generate``.

    Lockstep round (``async_rounds=False``)::

        res = session.step()          # verify → expand → sync → reroot/grow

    Pipelined round (``async_rounds=True``) — the paper's headline overlap::

        rif = session.begin_round()   # dispatch_verify + draft_next_tree
        ...                           # other replicas dispatch here
        res = session.reconcile(rif)  # sync, adopt lookahead or roll back

    Between ``begin_round`` and ``reconcile`` the session state is consumed
    (buffers donated into the round) — ``admit_slot``/``release_slot``/
    ``step`` must not run until the in-flight round reconciles.

    ``stats`` takes the always-on counters, whatever the tracer: every
    program the session dispatches (``SpecStats.dispatches``) and the host
    seconds blocked in each round's sync (``SpecStats.sync_s``).  ``step``
    and ``reconcile`` add the round itself to the ``stats`` they are handed.
    """

    engine: SpecEngine
    tparams: Any
    dparams: Any
    state: EngineState | None = None
    tracer: Any = NULL_TRACER
    track: str = "engine"
    stats: SpecStats = dataclasses.field(default_factory=SpecStats)
    _inflight: RoundInFlight | None = dataclasses.field(default=None, repr=False)

    @staticmethod
    def sync_emitted(tracer, track: str, stats, tree):
        """The round's ONE designated host sync, shared by the tree and chain
        sessions: ``jax.device_get`` of ``tree`` under the ``sync_emitted``
        span, the seconds the host blocked in it added to ``stats.sync_s``."""
        with tracer.span("sync_emitted", track):
            t0 = monotonic()
            host = jax.device_get(tree)  # repro: disable=HOTSYNC — designated sync point
            stats.sync_s += monotonic() - t0
        return host

    # ------------------------------------------------------------------
    # slot lifecycle
    # ------------------------------------------------------------------
    def admit_slot(self, slot: int, prompt) -> None:
        """Admit one request into batch row ``slot`` of the session state.

        The request is prefilled solo ([1, P] — byte-identical numerics to a
        solo generate() start), its cache rows installed into row ``slot`` of
        both serving caches, its tree re-seeded with its own prefix length,
        and the batch grown/re-planned so the next verify covers it.
        Neighboring rows' caches and trees are untouched (they only gain
        extra draft expansions, which never changes emitted tokens — the
        greedy-verification invariant)."""
        self._check_quiescent("admit_slot")
        eng, state = self.engine, self.state
        prompt = np.asarray(prompt, np.int32).reshape(1, -1)
        P = prompt.shape[1]
        with _serving(eng.mesh_draft):
            dlogits, dcache1 = eng._dprefill(self.dparams, jnp.asarray(prompt), eng.S_max_d)
        with _serving(eng.mesh_target):
            _, tcache1 = eng._tprefill(self.tparams, jnp.asarray(prompt), eng.S_max_t)
            tcache = eng._install(state.tcache, tcache1, slot)
        with _serving(eng.mesh_draft):
            dcache = eng._install(state.dcache, dcache1, slot)
            tr = eng._seed_slot(
                state.tr, slot, jnp.asarray(prompt[0, -1], jnp.int32),
                jnp.asarray(P, jnp.int32), dlogits[0, -1, :],
            )
            for _ in range(eng.grow_per_round):
                tr, dcache = eng._expand(self.dparams, tr, dcache)
            plan = eng._select_plan(tr)
        self.state = EngineState(tcache, dcache, tr, plan)
        st = self.stats
        for program in ("jit_draft_prefill", "jit_target_prefill", "jit_seed_slot",
                        "jit_select_plan"):
            st.ran(program)
        st.ran("jit_install_slot", 2)
        st.ran("jit_expand", eng.grow_per_round)

    def release_slot(self, slot: int) -> None:
        """Retire batch row ``slot``: park its tree and physically zero its
        KV rows in both caches, so no state can leak into the next occupant."""
        self._check_quiescent("release_slot")
        eng, state = self.engine, self.state
        with _serving(eng.mesh_target):
            tcache = eng._zero_slot(state.tcache, slot)
        with _serving(eng.mesh_draft):
            dcache = eng._zero_slot(state.dcache, slot)
            tr = eng._reset_slot(state.tr, slot)
            plan = eng._select_plan(tr)
        self.state = EngineState(tcache, dcache, tr, plan)
        self.stats.ran("jit_zero_slot", 2)
        self.stats.ran("jit_reset_slot")
        self.stats.ran("jit_select_plan")

    def _dispatch(self, plan, tcache):
        """Enqueue verification of ``plan`` on the target group, then the
        target cache compaction; returns (acc_pos, n_acc, bonus, emitted,
        n_emitted, tcache') as target-side device futures."""
        eng, st = self.engine, self.stats
        plan = _to(eng.mesh_target, plan)
        touched = (st.touched,) if st.touched is not None else eng._touched0()
        with _serving(eng.mesh_target):
            acc_pos, n_acc, bonus, emitted, n_emitted, tcache, mv, *touched = eng._verify(
                self.tparams, tcache, plan.tokens, plan.positions, plan.rows,
                plan.mask, plan.parent_pos, plan.valid, *touched,
            )
            with self.tracer.span("kv_move", self.track):
                tcache = eng._compact(tcache, *mv)
        st.ran("jit_verify")
        st.ran("jit_compact")
        if touched:
            st.touched = touched[0]
            st.moe_layer_calls += eng.target.n_moe_layers
        return acc_pos, n_acc, bonus, emitted, n_emitted, tcache

    def _reroot_grow(self, programs, tr, dcache, node_ids, outcome, n_grow: int):
        """The re-root tail of every round, on the draft group: re-root
        ``tr`` on the verify ``outcome`` (acc_pos, n_acc, bonus, already on
        the draft group) of the batch ``node_ids``, move the draft KV under
        a ``kv_move`` span, fill the prefix and grow the first level in one
        program (``_fill_grow``), grow ``n_grow - 1`` more levels and select
        the next plan.  ``programs`` is the (reroot, kv_move) pair: the
        donating one on the committed path, the snapshot-preserving one on
        the lookahead.  Returns (tr', dcache', plan') and counts the
        dispatches: one ``jit_fill_prefix`` per re-root and ``n_grow - 1``
        ``jit_expand`` (the KV move, a ``functools.partial``, is
        ``jit__unknown``)."""
        eng = self.engine
        reroot, kv_move = programs
        with _serving(eng.mesh_draft):
            tr, move, fillp = reroot(tr, node_ids, *outcome)
            with self.tracer.span("kv_move", self.track):
                dcache = kv_move(dcache, move.src, move.dst, move.mask)
            tr, dcache = eng._fill_grow(self.dparams, tr, dcache, fillp)
            for _ in range(n_grow - 1):
                tr, dcache = eng._expand(self.dparams, tr, dcache)
            plan = eng._select_plan(tr)
        st = self.stats
        for program in ("jit_reroot", "jit__unknown", "jit_fill_prefix", "jit_select_plan"):
            st.ran(program)
        st.ran("jit_expand", n_grow - 1)
        return tr, dcache, plan

    # ------------------------------------------------------------------
    # the round, lockstep
    # ------------------------------------------------------------------
    def step(self, stats: SpecStats | None = None,
             depth: int | None = None) -> StepResult:
        """One round for every slot.  With ``async_rounds`` this is the
        degenerate pipeline (begin + reconcile back-to-back — same tokens,
        no cross-replica overlap); the serving runtime splits the two calls
        to keep one verify and one draft outstanding per replica.

        ``depth`` is the round's effective draft depth — how many tree
        expansions this round runs — as a plain Python int (None: the
        config's global ``d``).  It is a loop trip count on the host, never
        a traced value, so varying it round to round compiles nothing new:
        the jitted ``_expand`` program is shared by every depth.  Depth only
        changes how much of the greedy continuation each round verifies
        (the adaptive-depth scheduler's lever); the emitted stream itself is
        depth-invariant — greedy verification pins it to target-only greedy
        decoding (tests/test_scheduler.py asserts byte-identity under
        arbitrary per-round depth schedules).

        Rows at different decode depths coexist: all per-row quantities
        (prefix length, masks, acceptance) live in the vmapped tree, so the
        serving runtime can drive rows with mixed progress through the same
        jitted round.

        The session ``tracer`` records the round's host-side phase spans —
        verify_dispatch / draft_expand / sync_emitted / reroot_grow (plus
        draft_lookahead / reconcile on the async path) on ``track`` (one
        track per serving replica); the default NULL_TRACER path is free."""
        if self.engine.cfg.async_rounds:
            return self.reconcile(self.begin_round(depth=depth), stats=stats)
        self._check_quiescent("step")
        eng, obs, track = self.engine, self.tracer, self.track
        c, state = eng.cfg, self.state
        d_eff = _effective_depth(depth, c.d)
        plan = eng._bypass(state.plan) if c.draft_bypass else state.plan
        tr, dcache = state.tr, state.dcache
        draft_steps = 0
        # --- dispatch verification on the target group (async) -------------
        with obs.span("verify_dispatch", track):
            acc_pos, n_acc, bonus, emitted, n_emitted, tcache = self._dispatch(plan, state.tcache)
        # --- concurrently: d tree expansions on the draft group ------------
        if c.mode == "parallel":
            with obs.span("draft_expand", track):
                with _serving(eng.mesh_draft):
                    for _ in range(d_eff):
                        tr, dcache = eng._expand(self.dparams, tr, dcache)
                    draft_steps += d_eff
                    self.stats.ran("jit_expand", d_eff)
        # --- sync point: verified tokens cross groups (host-mediated) ------
        # the verified-token transfer (paper's NCCL exchange), fused —
        # everything else async
        emitted_h, n_emitted_h, n_acc_h = self.sync_emitted(
            obs, track, self.stats, (emitted, n_emitted, n_acc))
        # --- re-root, fill, grow, select next batch (draft group) ----------
        with obs.span("reroot_grow", track):
            n_grow = d_eff if c.mode == "serial" else eng.grow_per_round
            tr, dcache, new_plan = self._reroot_grow(
                (eng._reroot, eng._kv_move), tr, dcache, plan.node_ids,
                _to(eng.mesh_draft, (acc_pos, n_acc, bonus)), n_grow)
            draft_steps += n_grow
        self.state = EngineState(tcache, dcache, tr, new_plan)
        if stats is not None:
            stats.add_round(n_emitted_h, n_acc_h)
            stats.draft_steps += draft_steps
        return StepResult(np.asarray(emitted_h), np.asarray(n_emitted_h), np.asarray(n_acc_h))

    # ------------------------------------------------------------------
    # the round, disaggregated (async_rounds)
    # ------------------------------------------------------------------
    def begin_round(self, depth: int | None = None) -> RoundInFlight:
        """Dispatch one full round without syncing: verify on the target
        group, then the speculative next-round draft on the draft group.
        ``depth``: this round's effective draft depth (see ``step``)."""
        rif = self.dispatch_verify()
        return self.draft_next_tree(rif, depth=depth)

    def dispatch_verify(self) -> RoundInFlight:
        """Enqueue this round's target verification; return the in-flight
        round handle.  No host sync — results stay device futures.  The
        ``verify_dispatch`` span is left OPEN until the reconcile sync, so
        on the trace it is the round's verify window and the overlap with
        ``draft_lookahead`` is directly measurable."""
        self._check_quiescent("dispatch_verify")
        eng, state = self.engine, self.state
        plan = eng._bypass(state.plan) if eng.cfg.draft_bypass else state.plan
        span = self.tracer.begin("verify_dispatch", self.track)
        acc_pos, n_acc, bonus, emitted, n_emitted, tcache = self._dispatch(plan, state.tcache)
        rif = RoundInFlight(
            plan=plan, tcache=tcache,
            verify=(acc_pos, n_acc, bonus, emitted, n_emitted),
            verify_span=span,
        )
        self._inflight = rif
        return rif

    def draft_next_tree(self, rif: RoundInFlight,
                        depth: int | None = None) -> RoundInFlight:
        """While verify runs: finish this round's expansions (``depth`` of
        them — the round's effective draft depth, a host loop count; None
        means the config's global ``d``), predict the accept path
        (``tree.predict_accept``), and draft round N+1's tree on the
        predicted-accept seed — the paper's draft-ahead.  The pre-reroot
        (tr, dcache) snapshot is retained (the speculative re-root does not
        donate), so ``reconcile`` can roll back a rejected seed exactly."""
        eng, c = self.engine, self.engine.cfg
        d_eff = _effective_depth(depth, c.d)
        tr, dcache = self.state.tr, self.state.dcache
        with self.tracer.span("draft_lookahead", self.track):
            with _serving(eng.mesh_draft):
                for _ in range(d_eff):
                    tr, dcache = eng._expand(self.dparams, tr, dcache)
                rif.draft_steps += d_eff
                # post-expansion, pre-reroot: the rollback point
                rif.snapshot = (tr, dcache)
                rif.pred = eng._predict(
                    tr, rif.plan.node_ids, rif.plan.parent_pos, rif.plan.valid)
            # snapshot-preserving re-root and move: (tr, dcache) stays alive
            # for rollback
            rif.lookahead = self._reroot_grow(
                (eng._spec_reroot, eng._spec_kv_move), tr, dcache, rif.plan.node_ids,
                rif.pred, eng.grow_per_round)
            rif.draft_steps += eng.grow_per_round
            self.stats.ran("jit_expand", d_eff)
            self.stats.ran("jit_predict_accept")
        return rif

    def reconcile(self, rif: RoundInFlight, stats: SpecStats | None = None,
                  live=None) -> StepResult:
        """Sync the verify outcome and resolve the speculation: adopt the
        lookahead tree when the predicted accept path held, else roll back
        to the retained snapshot and re-root on the actual path (the exact
        lockstep tail, one round late).

        ``live``: optional bool[B] row occupancy mask — prediction mismatches
        on parked rows are ignored (their trees never reach verification and
        admission fully overwrites the row).  Emitted tokens always come from
        the actual verify, so outputs are byte-identical to lockstep on both
        branches."""
        eng, obs, track = self.engine, self.tracer, self.track
        acc_pos, n_acc, bonus, emitted, n_emitted = rif.verify
        pred_acc, pred_n, pred_bonus = rif.pred
        # verified tokens and the prediction verdict cross in one fused transfer
        (emitted_h, n_emitted_h, n_acc_h, acc_h, bonus_h, pred_acc_h, pred_n_h,
         pred_bonus_h) = self.sync_emitted(
            obs, track, self.stats,
            (emitted, n_emitted, n_acc, acc_pos, bonus, pred_acc, pred_n, pred_bonus))
        rif.verify_span.end()
        ok = ((pred_n_h == n_acc_h) & (pred_bonus_h == bonus_h)
              & (pred_acc_h == acc_h).all(axis=1))
        if live is not None:
            ok = ok | ~np.asarray(live, bool)
        draft_steps = rif.draft_steps
        if ok.all():
            # seed held for every live row: round N+1's tree is already drafted
            tr, dcache, new_plan = rif.lookahead
            if stats is not None:
                stats.spec_commits += 1
        else:
            with obs.span("reconcile", track):
                # the actual-path re-root consumes the snapshot (donating)
                tr, dcache, new_plan = self._reroot_grow(
                    (eng._reroot, eng._kv_move), *rif.snapshot, rif.plan.node_ids,
                    _to(eng.mesh_draft, (acc_pos, n_acc, bonus)), eng.grow_per_round)
                draft_steps += eng.grow_per_round
        self.state = EngineState(rif.tcache, dcache, tr, new_plan)
        self._inflight = None
        if stats is not None:
            stats.spec_rounds += 1
            stats.add_round(n_emitted_h, n_acc_h)
            stats.draft_steps += draft_steps
        return StepResult(np.asarray(emitted_h), np.asarray(n_emitted_h), np.asarray(n_acc_h))

    # ------------------------------------------------------------------
    def generate(self, prompt, max_new=None):
        """prompt: np.ndarray [B, P] int32. Returns (tokens [B, <=max_new] list, stats).

        Rebuilds the session state from a whole-batch prefill of ``prompt``
        (any prior state and counters are discarded: ``stats`` is the
        session's new ``stats``), then loops rounds."""
        eng, c = self.engine, self.engine.cfg
        max_new = max_new or c.max_new
        B, P = prompt.shape
        t0 = monotonic()

        stats = self.stats = SpecStats()
        self.state = eng._prefill_state(self.tparams, self.dparams, prompt, stats=stats)
        out = [[] for _ in range(B)]
        done = np.zeros(B, bool)
        rounds_cap = max_new + 2  # greedy emits >=1 token/round

        for _ in range(rounds_cap):
            longest = 0 if stats.emitted_rows is None else int(stats.emitted_rows.max())
            if done.all() or (P + longest) >= eng.plen_budget:
                break
            res = self.step(stats=stats)
            for b in range(B):
                if not done[b]:
                    _, done[b] = absorb_emitted(
                        out[b], res.emitted[b], res.n_emitted[b], max_new, c.eos_id)

        stats.wall_s = monotonic() - t0
        return out, stats

    @property
    def plen_budget(self) -> int:
        return self.engine.plen_budget

    def _check_quiescent(self, what: str) -> None:
        if self._inflight is not None:
            raise RuntimeError(
                f"EngineSession.{what} called with a round in flight; "
                "reconcile() the outstanding RoundInFlight first — the state's "
                "buffers are donated into the round")
