"""JHost — the one-sweep entry to the exploration loop (paper §III,
Algorithm 1).

``JHost.explore`` runs one search algorithm over N clients: it builds an
``ExploreService`` (``repro.core.tenancy``, the one host loop) on its
transport with the scheduler knobs it is given, submits the sweep as that
service's only tenant, runs it to completion and returns the ResultStore.
Dispatch policy (``dispatch="eager"`` barrier or ``"pipelined"``
double-buffering, ``pipeline_depth``), adaptive chunk sizing
(``chunk_budget_ms``), deadlines and retries, speculation and
compile-affinity placement (``affinity`` + ``fingerprint_fn``, normally
``JConfig.cache_key``) are the scheduler's (``repro.core.scheduler``); the
fleet artifact store (``fleet_store``) is ``repro.core.fleet``'s; the
checkpoint + write-ahead log (``checkpoint_dir``, ``resume``) is
``repro.core.durable``'s.

Scalar mode (``batch_size=None``, eager) is the degenerate chunk-of-1 case
and keeps the original one-testConfig-per-message wire format.
"""
from __future__ import annotations

from typing import Optional, Sequence

from repro.core.fleet import FleetArtifactStore
from repro.core.results import ResultStore
from repro.core.scheduler import DispatchScheduler
from repro.core.search.base import SearchAlgorithm
from repro.core.tenancy import SOLO_TENANT, ExploreService, stop_clients
from repro.core.transport import HostTransport


class JHost:
    def __init__(self, transport: HostTransport,
                 store: Optional[ResultStore] = None,
                 timeout_s: float = 600.0,
                 max_retries: int = 2,
                 poll_s: float = 0.05):
        self.transport = transport
        self.store = store if store is not None else ResultStore()
        self.timeout_s = timeout_s
        self.max_retries = max_retries
        self.poll_s = poll_s
        # the running sweep's scheduler and its quarantine set (live)
        self.quarantined: set = set()
        self.scheduler: Optional[DispatchScheduler] = None

    # -- Algorithm 1, JHOST procedure -----------------------------------------
    def explore(self, search: SearchAlgorithm, arch: str, shape: str,
                n_samples: int,
                objectives: Sequence[str] = ("time_s", "power_w"),
                progress: bool = False,
                batch_size: Optional[int] = None,
                dispatch: str = "eager",
                chunk_budget_ms: Optional[float] = None,
                affinity: str = "off",
                fingerprint_fn=None,
                client_cache_size: int = 64,
                speculate_frac: Optional[float] = None,
                speculate_slow_mult: Optional[float] = None,
                pipeline_depth: Optional[int] = None,
                fleet_store: Optional[FleetArtifactStore] = None,
                checkpoint_dir: Optional[str] = None,
                checkpoint_every: int = 25,
                resume: bool = False) -> ResultStore:
        svc = ExploreService(
            self.transport, timeout_s=self.timeout_s,
            max_retries=self.max_retries, poll_s=self.poll_s,
            batch_size=batch_size, dispatch=dispatch,
            chunk_budget_ms=chunk_budget_ms, affinity=affinity,
            client_cache_size=client_cache_size,
            speculate_frac=speculate_frac,
            speculate_slow_mult=speculate_slow_mult,
            pipeline_depth=pipeline_depth, fleet_store=fleet_store,
            progress=progress)
        self.scheduler = svc.scheduler
        self.quarantined = svc.scheduler.quarantined   # shared live set
        sweep = svc.submit_sweep(
            SOLO_TENANT, search, arch, shape, n_samples, objectives,
            store=self.store, fingerprint_fn=fingerprint_fn,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume)
        svc.run()
        if sweep.state == "cancelled":
            # a wire CANCEL named the solo sweep: its store is partial
            raise RuntimeError(f"sweep cancelled at {sweep.completed}/"
                               f"{n_samples}")
        return self.store

    def stop_clients(self) -> None:
        stop_clients(self.transport)
