"""Hoard core: distributed, dataset-granular data cache for DL training.

Public API surface (see DESIGN.md for the paper mapping):

* ``SimClock`` / ``Resource``             — discrete-event fabric
* ``Topology`` / ``TopologyConfig``        — nodes, racks, links, remote store
* ``StripeStore``                          — chunked, striped, replicated store
* ``CacheManager`` / ``DatasetSpec``       — dataset-granularity lifecycle
* ``PlacementEngine`` / ``JobSpec``        — data/compute co-scheduling
* ``Rebalancer`` / ``MembershipEpoch``     — elastic membership + online re-striping
* ``HoardLoader`` + backends               — transparent iterators (R4)
* ``Telemetry`` / ``Tracer``               — flow spans, timelines, stall classes
* ``HostSpans`` / ``hostspans``            — wall-clock spans + counters of real reads
* ``run_scenario`` / ``build_cluster``     — one-call experiment harness

The simulated paths need only numpy.  The real read path (``read_item`` on a
materialized dataset, and so ``TokenLoader`` and HoardFS) also imports jax:
each of its ``hostspans`` spans is a ``jax.profiler.TraceAnnotation``.
"""

from .cache import (
    CacheEntry,
    CacheEvent,
    CacheFullError,
    CacheManager,
    CacheState,
    DatasetSpec,
    DatasetStat,
    EvictionPolicy,
)
from .calibration import (
    PAPER,
    ComputeModel,
    ConstantCompute,
    RooflineCompute,
    WorkloadCalibration,
)
from .cluster import ScenarioConfig, ScenarioResult, build_cluster, run_scenario
from .loader import (
    HoardBackend,
    HoardLoader,
    JobResult,
    LocalCopyBackend,
    RemoteBackend,
    StripeDataPlane,
    TrainingJob,
)
from . import hostspans
from .hostspans import HostSpans
from .metrics import ClusterMetrics, JobMetrics
from .placement import JobSpec, Placement, PlacementEngine
from .prefetch import FillTracker, PrefetchScheduler
from .readsched import ReadScheduler
from .rebalance import (
    ChunkMove,
    MembershipEpoch,
    RebalanceError,
    RebalancePlan,
    Rebalancer,
)
from .simclock import AllOf, Event, Resource, SimClock
from .telemetry import (
    STALL_CLASSES,
    FlowTag,
    ResourceSampler,
    Telemetry,
    Tracer,
    rollup_stalls,
)
from .stripestore import (
    MANIFEST_SCHEMA_VERSION,
    ChunkCorruption,
    StripeError,
    StripeManifest,
    StripeStore,
)
from .tiers import LRUCache, LRUStackModel, PagePool, buffer_cache_items
from .topology import Node, Topology, TopologyConfig
from .workload import (
    ClusterScheduler,
    JobRecord,
    WorkloadJob,
    WorkloadResult,
    stable_seed,
)
from .writeplane import (
    WRITE_BACK,
    WRITE_POLICIES,
    WRITE_THROUGH,
    ChunkCodec,
    WritePlane,
)

__all__ = [
    "AllOf", "CacheEntry", "CacheEvent", "CacheFullError", "CacheManager",
    "CacheState", "ChunkCodec", "ChunkCorruption", "ChunkMove", "ClusterMetrics",
    "ClusterScheduler", "ComputeModel", "ConstantCompute",
    "DatasetSpec", "DatasetStat", "Event", "EvictionPolicy",
    "FillTracker",
    "FlowTag",
    "HoardBackend", "HoardLoader", "HostSpans", "JobMetrics", "JobRecord", "JobResult",
    "JobSpec", "LRUCache", "LRUStackModel", "LocalCopyBackend",
    "MANIFEST_SCHEMA_VERSION", "MembershipEpoch", "Node", "PAPER", "PagePool",
    "Placement", "PlacementEngine", "PrefetchScheduler", "ReadScheduler",
    "RebalanceError",
    "RebalancePlan", "Rebalancer", "RemoteBackend", "Resource", "ResourceSampler",
    "RooflineCompute",
    "STALL_CLASSES", "ScenarioConfig", "ScenarioResult",
    "SimClock", "StripeDataPlane", "StripeError", "StripeManifest", "StripeStore",
    "Telemetry", "Topology", "TopologyConfig", "Tracer", "TrainingJob",
    "WRITE_BACK", "WRITE_POLICIES",
    "WRITE_THROUGH", "WorkloadCalibration",
    "WorkloadJob", "WorkloadResult", "WritePlane", "buffer_cache_items",
    "build_cluster", "hostspans", "rollup_stalls", "run_scenario", "stable_seed",
]
