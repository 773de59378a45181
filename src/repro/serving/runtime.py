"""Continuous-batching serving: per-slot request lifecycles over SpecEngine.

The engine's round (``EngineSession.begin_round``/``reconcile``) always
advances all B batch rows; ``EngineStepper`` gives each row (a *slot*) its
own request lifecycle:

  admit   — install an arrived request into a free slot (solo prefill into
            the slot's cache rows, per-slot tree re-seed) — neighbors keep
            decoding untouched;
  decode  — mixed-progress rounds: every occupied slot emits its verified
            tokens each round, streamed to the caller as they land;
  retire  — on EOS / max_new / cache budget the slot is released (tree
            parked, KV rows zeroed) and immediately backfilled from the
            queue on the next loop turn.

``ContinuousBatchingRuntime`` drives ONE stepper over a ``RequestQueue``;
``ShardedServingRuntime`` (repro.serving.router) drives N of them over one
global queue with depth-aware routing.  Both share the same stepper, so the
slot lifecycle — and therefore the correctness contract — has exactly one
implementation: because greedy verification makes each row's emitted stream
equal target-only greedy decoding regardless of what the other rows are
doing, a request's output is byte-identical to a solo ``generate()`` run no
matter when it was admitted or which replica served it (tests/test_serving.py
and tests/test_router.py assert this).

The clock is injectable: ``WallClock`` replays a trace against real time
(sleeping until the next arrival when idle); ``VirtualClock`` advances a
deterministic amount per engine round, so tests and benchmarks get
reproducible admission schedules independent of host speed.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable

from repro.core.engine import RoundInFlight, SpecStats, absorb_emitted
from repro.obs.clock import monotonic
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NOOP_SPAN, NULL_TRACER
from repro.serving.queue import Request, RequestQueue
from repro.serving.scheduler import (
    AdaptiveDepthController,
    SchedulerConfig,
    deadline_slack,
)
from repro.serving.stats import ServerStats

# accepted-depth histogram bucket for "replica admitted/finished" style
# counters is per-engine (0..bs); TTFT spans queueing so it gets the wide
# latency buckets below (virtual and wall clocks both land inside them)
TTFT_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)


class WallClock:
    def __init__(self):
        self._t0 = monotonic()

    def now(self) -> float:
        return monotonic() - self._t0

    def reset(self) -> None:
        """Re-zero the serving timeline (run() calls this so construction-time
        jit compiles don't consume the trace's arrival schedule)."""
        self._t0 = monotonic()

    def on_round(self, depth: int | None = None) -> None:
        pass  # real time advances by itself

    def wait_until(self, t: float) -> None:
        d = t - self.now()
        if d > 0:
            time.sleep(d)


class VirtualClock:
    """Deterministic clock: ``round_dt`` virtual seconds per engine round,
    plus ``expand_dt`` per draft-tree expansion the round actually ran —
    the cost model that makes adaptive draft depth *measurable* on the
    virtual timeline (a depth-1 round is cheaper than a depth-4 round, as
    on hardware where each expansion is a serialized draft forward pass).
    ``expand_dt=0`` (the default) keeps the legacy fixed-cost rounds."""

    def __init__(self, round_dt: float = 1.0, expand_dt: float = 0.0):
        self._t = 0.0
        self.round_dt = round_dt
        self.expand_dt = expand_dt

    def now(self) -> float:
        return self._t

    def reset(self) -> None:
        self._t = 0.0

    def on_round(self, depth: int | None = None) -> None:
        self._t += self.round_dt + (self.expand_dt * depth if depth else 0.0)

    def wait_until(self, t: float) -> None:
        self._t = max(self._t, t)


@dataclasses.dataclass
class _Active:
    """Host-side bookkeeping for one occupied slot."""

    req: Request
    plen: int  # host mirror of the slot's device prefix length
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False


class EngineStepper:
    """The per-engine admit/absorb/retire loop over one SpecEngine state.

    One stepper owns one ``EngineState`` of ``n_slots`` rows plus the
    host-side slot bookkeeping; the serving runtimes own the queue, the
    clock, and the decision of WHICH stepper a request lands on.  All
    device work (``admit`` prefills, ``step`` rounds) dispatches onto this
    engine's own mesh pair, so in a sharded fleet one replica's admission
    prefill is enqueued asynchronously on its device groups and never
    blocks another replica's decode round (the host only syncs inside
    ``EngineSession.reconcile``'s verified-token transfer).
    """

    def __init__(self, engine, tparams, dparams, n_slots: int, *,
                 stats: ServerStats | None = None,
                 stream: Callable[[int, list, bool], None] | None = None,
                 results: dict | None = None,
                 replica: int = 0,
                 tracer=None,
                 metrics: MetricsRegistry | None = None,
                 scheduler: SchedulerConfig | None = None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.engine, self.tparams, self.dparams = engine, tparams, dparams
        self.n_slots = n_slots
        self.replica = replica
        self.stats = stats if stats is not None else ServerStats()
        self.stream = stream
        self.results = results if results is not None else {}
        self.slots: list[_Active | None] = [None] * n_slots
        # the engine's KV-budget bound (shared with generate(), so serving
        # truncates at exactly the same token as a solo run)
        self.plen_limit = engine.plen_budget
        # ---- observability (repro.obs): spans on this replica's track, and
        # cached metric handles so the hot path touches one object each
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.track = f"replica{replica}"
        self._round_span = NOOP_SPAN
        # the open round's start and the session's sync seconds at that start
        self._round_t0 = self._round_sync0 = 0.0
        # engine-level round accounting, and the session's always-on counters
        self.spec_stats = SpecStats()
        # the bound round API: params + EngineState + tracer, one per replica
        self.session = engine.session(
            tparams, dparams, n_slots=n_slots, tracer=self.tracer,
            track=self.track, stats=self.spec_stats)
        rep = str(replica)
        m = self.metrics
        self._m_rounds = m.counter("serving_rounds_total", replica=rep)
        self._m_admitted = m.counter("serving_admitted_total", replica=rep)
        self._m_finished = m.counter("serving_finished_total", replica=rep)
        self._m_truncated = m.counter("serving_kv_truncations_total", replica=rep)
        self._m_tokens = m.counter("serving_tokens_total", replica=rep)
        # exact per-depth distribution: one bucket per possible accepted
        # count (0..bs) — ROADMAP #2's adaptive-depth signal
        self._m_accept = m.histogram(
            "serving_accept_depth", buckets=tuple(range(engine.cfg.bs + 1)),
            replica=rep)
        self._m_ttft = m.histogram("serving_ttft_seconds", buckets=TTFT_BUCKETS,
                                   replica=rep)
        self._m_occupancy = m.series("serving_occupancy", replica=rep)
        self._m_spec_commits = m.counter("serving_spec_commits_total", replica=rep)
        self._m_depth = m.series("serving_round_depth", replica=rep)
        # ---- adaptive draft depth (repro.serving.scheduler): per-slot
        # acceptance EMAs seeded from the accept-depth histogram above; None
        # keeps the engine's fixed global d (the pre-scheduler behavior)
        self.depth_ctl = None
        if scheduler is not None:
            self.depth_ctl = AdaptiveDepthController(
                scheduler, n_slots, default_depth=engine.cfg.d,
                seed_hist=self._m_accept)
        # the depth the most recent step() ran at (the round's cost driver,
        # read by the fleet loop's clock and the round-depth series)
        self.last_round_depth = engine.cfg.d

    # ------------------------------------------------------------------
    @property
    def state(self):
        """The session's EngineState (back-compat view; tests poke at it)."""
        return self.session.state

    @state.setter
    def state(self, s):
        self.session.state = s

    @property
    def occupied(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def has_free_slot(self) -> bool:
        return any(s is None for s in self.slots)

    @property
    def load(self) -> float:
        """Occupancy fraction in [0, 1] — the routing signal."""
        return self.occupied / self.n_slots

    def deadline_slack(self, now: float) -> float:
        """Tightest remaining deadline slack across this replica's occupied
        slots (+inf when none is deadlined) — the router's SLO-pressure
        tie-break (see ``ServingRuntimeBase._route``)."""
        return deadline_slack(self.slots, now)

    # ------------------------------------------------------------------
    def admit(self, req: Request, now: float) -> int:
        """Install ``req`` into the first free slot; returns the slot.  The
        caller supplies ONE timestamp used for both the arrival gate and the
        ``on_admit`` stamp, so ``queue_s``/TTFT cannot be skewed by clock
        reads straddling the prefill dispatch."""
        slot = self.slots.index(None)
        with self.tracer.span("admit_prefill", self.track,
                              args={"rid": req.rid, "slot": slot,
                                    "plen": int(req.prompt.size)}):
            self.session.admit_slot(slot, req.prompt)
        self.slots[slot] = _Active(req=req, plen=int(req.prompt.size))
        self.stats.on_admit(req.rid, slot, req.arrival_s, now, replica=self.replica,
                            deadline_s=req.deadline_s, priority=req.priority)
        if self.depth_ctl is not None:
            self.depth_ctl.seed_slot(slot)
        self._m_admitted.inc()
        return slot

    def step(self) -> RoundInFlight:
        """Dispatch one engine round for every slot: verify + the
        speculative next-round draft (``EngineSession.begin_round``).
        Returns the ``RoundInFlight`` WITHOUT syncing — the host is free to
        step the other replicas (the two-stage pipeline: one verify and one
        draft outstanding per replica) until ``absorb_round`` reconciles it.

        With an adaptive-depth scheduler bound, the round's effective depth
        is the controller's decision for the CURRENT occupancy (max depth
        bucket over occupied slots' acceptance EMAs); otherwise the engine's
        fixed global ``d``.  Either way ``last_round_depth`` records it for
        the fleet clock's cost model and the round-depth series.

        Opens this replica's ``round`` span; ``absorb_round`` closes it (or
        ``abort_round`` on a failed fleet turn), so the span brackets
        dispatch through absorption — the engine's phase spans
        (verify_dispatch/draft_lookahead/sync_emitted/reconcile) plus
        ``absorb`` are its children.  The same interval is timed, tracer or
        not, into ``ServerStats.round_s``."""
        self._round_t0 = monotonic()
        self._round_sync0 = self.spec_stats.sync_s
        self._round_span = self.tracer.begin("round", self.track)
        try:
            depth = None
            if self.depth_ctl is not None:
                depth = self.depth_ctl.round_depth(
                    [s is not None for s in self.slots])
            self.last_round_depth = self.engine.cfg.d if depth is None else depth
            self._round_span.set("depth", self.last_round_depth)
            return self.session.begin_round(depth=depth)
        except BaseException:
            # a failed dispatch must not leak the open round span
            self._round_span.end()
            self._round_span = NOOP_SPAN
            raise

    def absorb_round(self, rif: RoundInFlight, now: float) -> None:
        """Reconcile the in-flight round and fold its outcome into every
        occupied slot, retiring the rows that finished (EOS / max_new /
        cache budget).  Prediction mismatches on unoccupied rows are
        ignored (``live`` mask), since parked trees never reach
        verification and admission overwrites the row.

        The round span closes via try/finally: an absorb that raises (a
        failing stream callback, a poisoned record) must leave the tracer
        balanced, not with this replica's round span open forever."""
        try:
            pre = self.spec_stats.spec_commits
            res = self.session.reconcile(
                rif, stats=self.spec_stats, live=[s is not None for s in self.slots])
            if self.spec_stats.spec_commits > pre:
                self._m_spec_commits.inc()
            self._m_occupancy.append(now, self.occupied)  # pre-retire, as stats does
            self._m_depth.append(now, self.last_round_depth)
            with self.tracer.span("absorb", self.track):
                for slot, act in enumerate(self.slots):
                    if act is None:
                        continue
                    self._absorb(slot, act, res, now)
                    if act.done:
                        self._retire(slot, act, now)
            self._m_rounds.inc()
            self.stats.on_round_time(monotonic() - self._round_t0,
                                     self.spec_stats.sync_s - self._round_sync0)
        finally:
            self._round_span.end()
            self._round_span = NOOP_SPAN

    def abort_round(self, rif: RoundInFlight) -> None:
        """Abandon a dispatched round whose ``absorb_round`` will never run
        (another replica's absorb raised and the fleet loop is unwinding).
        The round is reconciled and its result discarded — the session's
        buffers were donated into the round, so dropping the
        ``RoundInFlight`` on the floor would orphan the session — and the
        open round span is closed so the tracer stays balanced."""
        try:
            self.session.reconcile(rif, live=[s is not None for s in self.slots])
        finally:
            self._round_span.end()
            self._round_span = NOOP_SPAN

    def _absorb(self, slot: int, act: _Active, res, now: float) -> None:
        """Append one StepResult row's verified tokens up to EOS/max_new,
        stream them, update the plen mirror."""
        # per-request eos/max_new fall back to the engine's, so the
        # byte-identical contract vs solo generate() holds for any SpecConfig
        eos = act.req.eos_id if act.req.eos_id is not None else self.engine.cfg.eos_id
        max_new = act.req.max_new if act.req.max_new is not None else self.engine.cfg.max_new
        new, act.done = absorb_emitted(
            act.out, res.emitted[slot], res.n_emitted[slot], max_new, eos)
        act.plen += int(res.n_emitted[slot])
        if act.plen >= self.plen_limit and not act.done:  # cache budget
            act.done = act.truncated = True
        first = self.stats.records[act.req.rid].first_token_s is None
        self.stats.on_tokens(act.req.rid, len(new), int(res.n_accepted[slot]), now)
        self._m_accept.observe(int(res.n_accepted[slot]))
        if self.depth_ctl is not None:  # the same measurement feeds the EMA
            self.depth_ctl.observe(slot, int(res.n_accepted[slot]))
        if new:
            self._m_tokens.inc(len(new))
            if first:
                self._m_ttft.observe(now - act.req.arrival_s)
        if self.stream is not None and (new or act.done):
            self.stream(act.req.rid, new, act.done)

    def _retire(self, slot: int, act: _Active, now: float) -> None:
        self.results[act.req.rid] = act.out
        with self.tracer.span("retire", self.track, args={"rid": act.req.rid,
                                                          "slot": slot}):
            self.session.release_slot(slot)
        self.slots[slot] = None
        if self.depth_ctl is not None:  # acceptance history dies with the request
            self.depth_ctl.clear_slot(slot)
        self.stats.on_finish(act.req.rid, now, truncated=act.truncated)
        self._m_finished.inc()
        if act.truncated:
            self._m_truncated.inc()


class ServingRuntimeBase:
    """The serve loop over a fleet of steppers: trace submission, arrival
    feeding, routed admission, the round loop, and idle handling — shared by
    the single-engine runtime (a 1-stepper fleet) and the sharded runtime
    (N steppers), so both admission semantics and the round schedule have
    exactly one implementation.

    Subclasses call ``_init_admission`` then ``_init_fleet`` from their
    constructors.
    """

    def _init_admission(self, queue: RequestQueue | None, clock,
                        tracer=None, metrics: MetricsRegistry | None = None) -> None:
        self.queue = queue if queue is not None else RequestQueue()
        self.clock = clock if clock is not None else WallClock()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._m_queue_depth = self.metrics.series("serving_queue_depth")
        self.results: dict[int, list] = {}
        # trace entries whose arrival time is still in the future; they join
        # the queue when the clock reaches them, so BOTH admission gates (the
        # queue cap and the prompt-length bound) shed on ARRIVED traffic —
        # live semantics — not at trace-submission time
        self._pending: collections.deque[Request] = collections.deque()
        self._started = False  # pre-run submissions gate against t=0

    def submit(self, req: Request) -> bool:
        """Queue a request.  A request with a future ``arrival_s`` is held
        outside the queue and faces BOTH admission gates — the queue cap and
        the engine's prompt-length bound — when its arrival time comes, so
        ``RequestQueue.submitted``/``rejected`` count live traffic, not trace
        length.  An already-arrived request is adjudicated immediately:
        rejected (False) when its prompt cannot fit the cache budget or the
        queue is full."""
        # before run() the serving timeline hasn't started: arrivals compare
        # against t=0, not against however long engine construction took
        now = self.clock.now() if self._started else 0.0
        if req.arrival_s > now:
            if self._pending and req.arrival_s < self._pending[-1].arrival_s:
                raise ValueError("submissions must be ordered by arrival_s")
            self._pending.append(req)
            return True
        # a live submit after its arrival time arrives NOW on the serving
        # timeline, keeping queue ordering intact (a copy, so the caller's
        # Request is not mutated); trace entries fed by _feed_arrived keep
        # their true arrival_s — queueing delay belongs in their TTFT
        if req.arrival_s < now:
            req = dataclasses.replace(req, arrival_s=now)
        return self._arrive(req)

    def _arrive(self, req: Request) -> bool:
        """Run the arrival-time admission gates for one request."""
        if req.prompt.size >= self._plen_limit:
            return self.queue.reject(req)
        return self.queue.submit(req)

    def _feed_arrived(self) -> None:
        """Move trace entries whose arrival time has passed through the
        arrival gates (where the cap / prompt bound may shed them)."""
        now = self.clock.now()
        while self._pending and self._pending[0].arrival_s <= now:
            self._arrive(self._pending.popleft())

    def submit_trace(self, requests) -> int:
        """Submit an iterable of Requests (arrival-ordered); returns #accepted
        (future arrivals count as accepted here and are adjudicated on
        arrival)."""
        return sum(1 for r in requests if self.submit(r))

    def _next_arrival(self) -> float | None:
        nxt = self.queue.next_arrival()
        if nxt is None and self._pending:
            nxt = self._pending[0].arrival_s
        return nxt

    def _start_run(self) -> bool:
        """First run() call re-zeros the clock (construction-time jit
        compiles must not consume the trace's arrival schedule); later runs
        keep the original timeline.  Returns True on the first start."""
        if self._started:
            return False
        self._started = True
        self.clock.reset()
        return True

    # ---- the fleet loop ----------------------------------------------
    def _init_fleet(self, steppers: list[EngineStepper]) -> None:
        self.steppers = steppers
        # replicas could in principle differ; admission must fit the tightest
        self._plen_limit = min(s.plen_limit for s in steppers)
        self._seq = 0
        self._last_dispatch = [-1] * len(steppers)

    @property
    def occupied(self) -> int:
        return sum(s.occupied for s in self.steppers)

    def _route(self, now: float) -> int | None:
        """Pick the admission target: least-loaded stepper (occupancy
        fraction) among those with a free slot.  Equal load breaks on
        deadline slack — the replica whose in-flight work has the MOST
        remaining slack wins, so a new admission (whose rounds every
        co-resident request shares) is steered away from the replica that
        must finish something soonest.  Replicas with no deadlined work
        have infinite slack and tie, falling through to the FIFO tie-break
        — the stepper whose last admission is oldest — so deadline-free
        fleets keep the round-robin spread exactly.  None when the fleet is
        full.  (With one stepper this degenerates to "is a slot free".)"""
        best_key, best = None, None
        for i, st in enumerate(self.steppers):
            if not st.has_free_slot:
                continue
            key = (st.load, -st.deadline_slack(now), self._last_dispatch[i])
            if best_key is None or key < best_key:
                best_key, best = key, i
        return best

    def _admit_ready(self) -> None:
        """Drain arrived requests into free slots fleet-wide, one routing
        decision per request (the queue's deadline-aware pop picks WHICH
        request, ``_route`` picks WHERE); each admission reads the clock
        ONCE — the same timestamp keys the routing slack, gates the pop,
        and stamps ``on_admit``."""
        while True:
            now = self.clock.now()
            route_span = self.tracer.begin("route", "router")
            target = self._route(now)
            if target is None:
                route_span.end()
                return
            with self.tracer.span("queue_pop", "router"):
                req = self.queue.pop_ready(now)
            if req is None:
                route_span.end()
                return
            route_span.set("replica", target)
            route_span.set("rid", req.rid)
            route_span.end()
            self.steppers[target].admit(req, now)
            self._seq += 1
            self._last_dispatch[target] = self._seq

    def run(self) -> dict[int, list]:
        """Serve until the queue drains and every slot retires.  Returns the
        merged {rid: emitted tokens}; telemetry accumulates in each stepper's
        ServerStats."""
        if self._start_run():
            t0 = self.clock.now()
            for st in self.steppers:
                st.stats.started_s = t0  # later runs keep the original
                # start so summary() throughput spans all serving
        while self._pending or self.queue.pending or self.occupied:
            self._feed_arrived()
            self._admit_ready()
            busy = [st for st in self.steppers if st.occupied]
            if not busy:
                nxt = self._next_arrival()
                if nxt is None:
                    break
                with self.tracer.span("idle", "router"):
                    self.clock.wait_until(nxt)  # idle: jump to the next arrival
                continue
            # one global round: every busy stepper steps (concurrent across
            # disjoint device groups on real hardware), the clock ticks once,
            # then every stepper absorbs and retires.  If any dispatch or
            # absorb raises, every other dispatched round is aborted on the
            # way out — no open round span, no orphaned RoundInFlight.
            stepped: list = []
            try:
                for st in busy:
                    stepped.append((st, st.step()))
                # the global round costs what the deepest replica round cost
                # (replicas run concurrently on disjoint device groups)
                self.clock.on_round(max(st.last_round_depth for st in busy))
                now = self.clock.now()
                qdepth = self.queue.depth(now)
                self._m_queue_depth.append(now, qdepth)
                self.tracer.counter("queue_depth", qdepth)
                self.tracer.counter("occupied", self.occupied)
                while stepped:
                    st, rif = stepped.pop(0)
                    st.stats.on_round(st.occupied, qdepth)
                    st.absorb_round(rif, now)
            except BaseException:
                for st, rif in stepped:
                    st.abort_round(rif)
                raise
        t1 = self.clock.now()
        for st in self.steppers:
            st.stats.finished_s = t1
        return self.results


class ContinuousBatchingRuntime(ServingRuntimeBase):
    """Drives one SpecEngine state of ``n_slots`` batch rows over a request
    queue.  ``stream(rid, new_tokens, done)`` is called once per round per
    occupied slot with that round's freshly verified tokens."""

    def __init__(self, engine, tparams, dparams, n_slots: int, *,
                 queue: RequestQueue | None = None,
                 clock=None,
                 stats: ServerStats | None = None,
                 stream: Callable[[int, list, bool], None] | None = None,
                 tracer=None,
                 metrics: MetricsRegistry | None = None,
                 scheduler: SchedulerConfig | None = None):
        self._init_admission(queue, clock, tracer, metrics)
        self.stats = stats if stats is not None else ServerStats()
        self.stepper = EngineStepper(
            engine, tparams, dparams, n_slots,
            stats=self.stats, stream=stream, results=self.results,
            tracer=self.tracer, metrics=self.metrics, scheduler=scheduler)
        self._init_fleet([self.stepper])
        self.engine, self.n_slots = engine, n_slots

    # back-compat views (tests and callers poke at the engine state directly)
    @property
    def state(self):
        return self.stepper.state

    @property
    def slots(self):
        return self.stepper.slots
