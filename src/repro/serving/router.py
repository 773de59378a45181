"""ShardedServingRuntime — one global request queue dispatched over N
SpecEngine replicas with depth-aware routing.

SwiftSpec's headline number comes from scaling the disaggregated pipeline
across device groups; this is the serving-side half of that scaling: each
replica is a full (draft group, target group) pair carved out of the slice
by ``repro.launch.mesh.make_serving_mesh(..., replicas=N)``, driven by its
own ``EngineStepper`` — the same per-slot admit/absorb/retire lifecycle and
the same fleet loop (``ServingRuntimeBase``) the single-engine runtime
uses, so the byte-identical contract holds per request regardless of which
replica served it, and the single-engine runtime is literally the N=1 case.

Routing policy (``ServingRuntimeBase._route``): a popped request lands on
the replica with the lowest occupancy fraction among those with a free
slot; ties break FIFO — the replica that has gone longest since its last
admission wins — so equal load spreads round-robin instead of piling onto
replica 0.

Per-replica admission: ``EngineStepper.admit`` dispatches the solo prefill
onto the OWNING replica's device groups only.  JAX's asynchronous dispatch
means the host enqueues replica A's (possibly long) prefill and moves
straight on to replica B's decode round — the only host sync is each
replica's own verified-token transfer inside ``EngineSession.reconcile`` — so a
long prompt admitted on A never stalls decode progress on B.

One global round of the fleet loop = every busy replica steps once (those
rounds run concurrently across disjoint device groups in a real
deployment), then the clock advances once, then every replica
absorbs/retires/backfills.  Telemetry is one ``ServerStats`` per replica,
merged by ``repro.serving.stats.merge_summary`` / ``fleet_report`` into
global TTFT and throughput plus the per-replica occupancy breakdown.
"""

from __future__ import annotations

from typing import Callable

from repro.serving.queue import RequestQueue
from repro.serving.runtime import EngineStepper, ServingRuntimeBase
from repro.serving.stats import ServerStats, fleet_report, merge_summary


class ShardedServingRuntime(ServingRuntimeBase):
    """N-replica continuous batching over one global ``RequestQueue``.

    ``engines`` is a list of SpecEngine replicas (each typically on its own
    disjoint mesh pair; passing the same engine object N times is valid —
    states are separate — and is what the CPU fallback does to share one jit
    cache).  ``tparams``/``dparams`` are either a single pytree shared by
    every replica or a list with one entry per replica (params resident on
    that replica's device groups).
    """

    def __init__(self, engines, tparams, dparams, n_slots: int, *,
                 queue: RequestQueue | None = None,
                 clock=None,
                 stream: Callable[[int, list, bool], None] | None = None,
                 tracer=None,
                 metrics=None,
                 scheduler=None):
        if not engines:
            raise ValueError("need at least one engine replica")
        self._init_admission(queue, clock, tracer, metrics)
        tps = tparams if isinstance(tparams, list) else [tparams] * len(engines)
        dps = dparams if isinstance(dparams, list) else [dparams] * len(engines)
        if not (len(tps) == len(dps) == len(engines)):
            raise ValueError("per-replica params must match the engine count")
        self._init_fleet([
            EngineStepper(eng, tp, dp, n_slots,
                          stats=ServerStats(), stream=stream,
                          results=self.results, replica=i,
                          tracer=self.tracer, metrics=self.metrics,
                          scheduler=scheduler)
            for i, (eng, tp, dp) in enumerate(zip(engines, tps, dps))
        ])

    # ------------------------------------------------------------------
    @property
    def n_replicas(self) -> int:
        return len(self.steppers)

    @property
    def stats(self) -> list[ServerStats]:
        """Per-replica telemetry (merge with ``summary()``/``report()``)."""
        return [s.stats for s in self.steppers]

    def summary(self) -> dict:
        # per-replica accept-depth histograms may have different bucket
        # edges (replicas can run different draft depths) — merge_summary
        # unions the edges instead of summing counts positionally
        hists = [h for _, h in self.metrics.histogram_family("serving_accept_depth")]
        return merge_summary(self.stats, accept_hists=hists or None)

    def report(self) -> str:
        return fleet_report(self.stats)

    def replica_of(self, rid: int) -> int | None:
        """Which replica served (or is serving) a request, None if unknown."""
        for i, st in enumerate(self.steppers):
            if rid in st.stats.records:
                return i
        return None
