"""repro.serving -- batched multi-tenant PCA/SVD serving.

The paper's S-arrays-plus-Matrix-Padding-Unit scalability story as a service:
heterogeneous requests are padded into T-multiple shape buckets
(``batching``), up to S same-bucket requests stack into one vmapped device
batch (``solver``), and ``engine.PCAServer`` runs the queue with
deadline-aware microbatching, a compiled-executable cache, and full
telemetry (``stats``).  Flush placement is an executor (``sharded``): the
default ``LocalExecutor`` runs on one device; ``MeshExecutor`` shards the
batch axis across a named device mesh so one flush retires S x n_devices
requests.  Flush *timing* is a pipeline (``inflight``): executors launch
without blocking, a bounded in-flight queue holds launched flushes, and
retirement unpacks them into tickets -- ``PCAServer(max_inflight=N)``
overlaps host-side batching with device execution (N=1 is the synchronous
engine).  The whole (policy, T, pow2 cap, S, inflight, executor) tuple is a
``ServingPlan`` the traffic-driven autotuner (``autotune``) searches from a
captured ``TrafficProfile`` and hot-swaps onto a live server via
``PCAServer.apply_plan``.  Executables live in a two-tier cache
(``cache``): a bounded in-memory LRU plus an optional persistent
disk tier of serialized AOT executables, so a fresh replica pointed at a
warm ``cache_dir`` -- or pre-built via ``PCAServer.warmup(profile)`` --
serves its first request without ever touching XLA.

Configuration is one frozen ``spec.ServerSpec`` (scheduling / execution /
cache / obs / controller sub-specs): ``PCAServer.from_spec(spec)`` builds
the whole stack, ``ServerSpec.from_args`` maps the CLI onto it, and
``to_json``/``from_json`` round-trip it for config files.  The
``controller.ServingController`` closes the autotune loop autonomously:
re-profile a sliding telemetry window, bandit-search the plan grid, and
hot-swap behind hysteresis + dwell guards.
"""
from .autotune import (AutotuneResult, CostModel, ServingPlan,
                       TrafficProfile, TRACE_KINDS, autotune, bandit_search,
                       plan_grid, replay, server_for_plan, solve_work,
                       subsample, synthetic_trace, trace_dims)
from .batching import (BucketPolicy, POLICIES, pad_to_bucket, padding_waste,
                       stack_requests)
from .cache import (DiskCache, ExecutableCache, LRUCache, SolverKey,
                    content_hash, environment_fingerprint)
from .controller import ServingController
from .engine import (BackendRouter, OPS, PCAServer, ServedEigh, ServedPCA,
                     ServedSVD, Ticket, threshold_router)
from .frontend import (ADMISSION_MODES, ARRIVALS, AdmissionController,
                       AdmissionDecision, Arrival, FairQueue,
                       FrontendReport, SCHEDULERS, TenantSpec, TokenBucket,
                       TrafficFrontend, VirtualClock, arrival_times,
                       generate, materialize, merge, parse_tenants,
                       profile_of)
from .inflight import InFlightFlush, InFlightQueue
from .sharded import LocalExecutor, MeshExecutor, host_mesh, mesh_executor
from .spec import (CacheSpec, ControllerSpec, ExecutionSpec, ObsSpec,
                   SchedulingSpec, ServerSpec, SpecConflictError,
                   build_server, resolve_spec, validate_args)
from .solver import (BatchedEighResult, BatchedPCAResult, BatchedSVDResult,
                     build_solver_fn, jacobi_eigh_batched,
                     jacobi_svd_batched, pca_fit_batched,
                     pca_transform_batched)
from .stats import FlushRecord, RequestRecord, ServingStats, percentile

__all__ = [
    "ADMISSION_MODES", "ARRIVALS", "AdmissionController",
    "AdmissionDecision", "Arrival", "FairQueue", "FrontendReport",
    "SCHEDULERS", "TenantSpec", "TokenBucket", "TrafficFrontend",
    "VirtualClock", "arrival_times", "generate", "materialize", "merge",
    "parse_tenants", "profile_of",
    "AutotuneResult", "BackendRouter", "BatchedEighResult",
    "BatchedPCAResult", "BatchedSVDResult", "BucketPolicy", "CacheSpec",
    "ControllerSpec", "CostModel", "DiskCache", "ExecutableCache",
    "ExecutionSpec", "FlushRecord", "InFlightFlush", "InFlightQueue",
    "LRUCache", "LocalExecutor", "MeshExecutor", "OPS", "ObsSpec",
    "PCAServer", "POLICIES", "RequestRecord", "SchedulingSpec",
    "ServedEigh", "ServedPCA", "ServedSVD", "ServerSpec",
    "ServingController", "ServingPlan", "ServingStats", "SolverKey",
    "SpecConflictError", "Ticket", "TrafficProfile", "TRACE_KINDS",
    "autotune", "bandit_search", "build_server",
    "build_solver_fn", "content_hash", "environment_fingerprint",
    "host_mesh", "jacobi_eigh_batched", "jacobi_svd_batched",
    "mesh_executor", "pad_to_bucket", "padding_waste", "pca_fit_batched",
    "pca_transform_batched", "percentile", "plan_grid", "replay",
    "resolve_spec", "server_for_plan", "solve_work", "stack_requests",
    "subsample", "synthetic_trace", "threshold_router", "trace_dims",
    "validate_args",
]
