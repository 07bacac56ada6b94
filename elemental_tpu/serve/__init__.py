"""Solver service: deadline-bounded, batched, fault-isolated serving.

The ISSUE-9 front-end that turns eight PRs of single-solve machinery
into a system that survives production traffic -- the ROADMAP's
"millions of users" workload of many small-to-medium solves (arXiv
2112.09017):

  :mod:`.admission`  shape-bucketing into the tuner's pow2 buckets,
                     per-request :class:`Deadline` objects threaded
                     through dispatch, and load shedding that
                     rejects-fast with ``serve_reject/v1``
  :mod:`.executor`   padded ``vmap``'d Cholesky/LU batch solves with a
                     persistent AOT-compiled executable cache (no
                     request pays compile)
  :mod:`.policy`     deadline-aware retry with seeded backoff+jitter,
                     the per-bucket circuit breaker (trip / half-open
                     probe / close), and the load-aware degradation
                     ladder (quant-first under pressure)
  :mod:`.service`    :class:`SolverService` -- submit/drain, trusted
                     per-request certification, bisect fault isolation,
                     escalation through ``certified_solve(deadline=)``
  :mod:`.async_front` :class:`AsyncSolverService` -- the ISSUE-14
                     pipelined front: one worker thread double-buffers
                     host staging against device execution (donated
                     batch buffers), completions stream as
                     :class:`ServeFuture` resolutions
  :mod:`.chaos`      the acceptance-matrix harness over the ISSUE-7
                     ``FaultPlan`` machinery, grown a fleet column
                     (saturation + grid loss, ISSUE 19)
  :mod:`.scheduler`  :class:`FairScheduler` -- per-tenant deficit-round-
                     robin queues and :class:`TenantQuota` outstanding
                     caps (ISSUE 19)
  :mod:`.fleet`      :class:`SolverFleet` -- the ISSUE-19 tentpole:
                     devices partitioned into independent solver grids
                     (own executor cache / breakers / tuner namespace /
                     EWMA each), depth-k pipelined workers, and
                     tenant-aware routing by measured per-grid latency

CLI: ``python -m perf.serve {run,smoke,chaos,fleet-smoke}``; gates:
``tools/check.sh serve`` and ``tools/check.sh fleet``.  No benchmark cell
drives the service yet (``PERF.md`` 7).
"""
from .admission import (REJECT_SCHEMA, AdmissionController, Bucket,
                        Deadline, SolveRequest, make_bucket, reject_doc,
                        validate_problem)
from .executor import (EXEC_SCHEMA, ExecutableCache, Executor, batch_slots,
                       ls_residual, pad_problem, pad_problem_ls, residual,
                       route_for, tune_token)
from .policy import (CLOSED, HALF_OPEN, OPEN, CircuitBreaker, RetryPolicy,
                     select_ladder)
from .service import RESULT_SCHEMA, SolverService
from .async_front import (AsyncSolverService, ServeFuture,
                          donation_safe, serve_async)
from .chaos import (CHAOS_SCHEMA, build_workload, chaos_matrix,
                    fleet_replay_identical, replay_identical,
                    run_async_cell, run_async_shutdown_cell, run_cell,
                    run_fleet_grid_loss_cell, run_fleet_saturation_cell,
                    run_qr_cell)
from .scheduler import DEFAULT_TENANT, FairScheduler, TenantQuota
from .fleet import (FleetFuture, GridWorker, SolverFleet,
                    partition_devices)

__all__ = [
    "REJECT_SCHEMA", "AdmissionController", "Bucket", "Deadline",
    "SolveRequest", "make_bucket", "reject_doc",
    "EXEC_SCHEMA", "ExecutableCache", "Executor", "batch_slots",
    "ls_residual", "pad_problem", "pad_problem_ls", "residual",
    "route_for", "tune_token",
    "CLOSED", "HALF_OPEN", "OPEN", "CircuitBreaker", "RetryPolicy",
    "select_ladder",
    "RESULT_SCHEMA", "SolverService",
    "AsyncSolverService", "ServeFuture", "serve_async",
    "donation_safe",
    "CHAOS_SCHEMA", "build_workload", "chaos_matrix", "replay_identical",
    "run_async_cell", "run_async_shutdown_cell", "run_cell", "run_qr_cell",
    "fleet_replay_identical", "run_fleet_grid_loss_cell",
    "run_fleet_saturation_cell",
    "DEFAULT_TENANT", "FairScheduler", "TenantQuota",
    "FleetFuture", "GridWorker", "SolverFleet", "partition_devices",
    "validate_problem",
]
