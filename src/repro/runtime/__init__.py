"""Distributed runtime: coordinator, executor fleet, cache-aware scheduler.

In-process simulation of the paper's FlockDB deployment shape: one
coordinator, N stateless executors with local SSD caches, a shared object
store, and an Iceberg REST catalog as the source of truth.  Executors run on
their own threads; the scheduler provides cache-aware placement, heartbeat
failure detection with task reassignment, and speculative backup tasks for
straggler mitigation (DESIGN.md §6).
"""

from repro.runtime.fragments import (  # noqa: F401
    IndexBuildResult,
    IndexBuildTaskInfo,
    RefreshResult,
    RefreshTaskInfo,
    RerankResult,
    RerankTaskInfo,
)
from repro.runtime.executor import Executor, ExecutorDead  # noqa: F401
from repro.runtime.scheduler import ExecutorPool, Scheduler  # noqa: F401
from repro.runtime.coordinator import Coordinator, IndexConfig  # noqa: F401
from repro.runtime.predicates import (  # noqa: F401
    And,
    Eq,
    In,
    Or,
    Predicate,
    Range,
    parse_predicate,
)
