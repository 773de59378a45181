"""repro.serving — continuous-batching serving runtimes for the async
speculative engine, from one engine to a sharded fleet.

The paper's headline number is an end-to-end *serving* result: the
disaggregated draft/target pipeline only pays off when it is kept full —
and at scale, when many such pipelines are kept full at once.  This package
turns the repo's one-shot ``EngineSession.generate()`` into request runtimes
that multiplex many independent requests through per-slot lifecycles, on
one engine or across N engine replicas on disjoint device groups.

Modules
-------
``queue``
    ``Request`` and ``RequestQueue`` — admission control plus a deadline-
    aware pop: a hard queue cap (load shedding), arrival-time gating so a
    seeded Poisson trace (``repro.data.make_request_trace``) replays like
    live traffic, and EDF selection among arrived requests — ``(priority,
    deadline, FIFO)`` with a ``starvation_s`` bound — that degenerates to
    exact FIFO when nothing carries a deadline.  Both admission gates (cap
    and prompt-length bound) adjudicate at ARRIVAL time; ``depth()`` is
    O(1) via an arrived/future split.
``scheduler``
    ``SchedulerConfig`` / ``AdaptiveDepthController`` — per-slot adaptive
    draft depth: each slot's measured-acceptance EMA maps to a depth bucket
    (one host loop count over the single jitted expand program — no new jit
    traces), and the round runs at the max bucket over occupied slots.
    Adaptation changes when tokens verify, never which tokens.
``runtime``
    ``EngineStepper`` — the per-engine admit/absorb/retire loop over one
    ``SpecEngine`` state: solo prefill installed into a free slot's KV rows
    + per-slot tree re-seed on admit, mixed-progress pipelined decode
    rounds (``EngineSession.begin_round``/``reconcile``) with streaming,
    slot release + backfill on retire.
    ``ContinuousBatchingRuntime`` — one stepper over one queue (the single-
    engine serving loop).  ``WallClock`` / ``VirtualClock`` make trace
    replay real or deterministic.
``router``
    ``ShardedServingRuntime`` — N steppers (one per SpecEngine replica,
    each on its own disjoint device-group pair from
    ``repro.launch.mesh.make_serving_mesh(..., replicas=N)``) fed from ONE
    global queue with depth/occupancy-aware routing: least-loaded replica
    wins, FIFO tie-break, per-replica admission so a long prefill on one
    replica never stalls decode rounds on another.
``stats``
    ``ServerStats`` — per-request TTFT, decode tok/s, acceptance rate, slot
    and round lifetimes (overlapping round intervals are the evidence of
    continuous batching), plus per-round occupancy and queue-depth samples.
    ``merge_summary`` / ``fleet_report`` fold N per-replica ServerStats
    into one aggregate (global TTFT/throughput, per-replica occupancy).

Correctness contract: greedy verification makes every row's emitted stream
equal target-only greedy decoding, independent of its neighbors — so each
request's output is byte-identical to a solo ``generate()`` run regardless
of when it was admitted, which slot it recycled, or which replica served it
(tests/test_serving.py, tests/test_router.py).

Quick start::

    from repro.serving import ContinuousBatchingRuntime, Request

    rt = ContinuousBatchingRuntime(engine, tparams, dparams, n_slots=4)
    for i, prompt in enumerate(prompts):
        rt.submit(Request(rid=i, prompt=prompt, max_new=64))
    outputs = rt.run()          # {rid: [tokens]}
    print(rt.stats.report())    # TTFT / tok-s / occupancy / acceptance

Sharded::

    from repro.serving import ShardedServingRuntime

    rt = ShardedServingRuntime([engine_a, engine_b], tparams, dparams, n_slots=4)
    rt.submit_trace(requests)
    outputs = rt.run()
    print(rt.report())          # per-replica occupancy + fleet aggregate

See also ``examples/continuous_serving.py`` and
``python -m repro.launch.serve --continuous [--replicas N]``.
"""

from repro.serving.queue import Request, RequestQueue
from repro.serving.router import ShardedServingRuntime
from repro.serving.runtime import (
    ContinuousBatchingRuntime,
    EngineStepper,
    VirtualClock,
    WallClock,
)
from repro.serving.scheduler import AdaptiveDepthController, SchedulerConfig
from repro.serving.stats import (
    RequestRecord,
    ServerStats,
    fleet_report,
    merge_summary,
    percentile,
)

__all__ = [
    "AdaptiveDepthController",
    "ContinuousBatchingRuntime",
    "EngineStepper",
    "Request",
    "SchedulerConfig",
    "RequestQueue",
    "RequestRecord",
    "ServerStats",
    "ShardedServingRuntime",
    "VirtualClock",
    "WallClock",
    "fleet_report",
    "merge_summary",
    "percentile",
]
