from repro.runtime.heartbeat import HeartbeatMonitor, WorkerState  # noqa: F401
from repro.runtime.elastic import (  # noqa: F401
    AllWorkersDead,
    ElasticBlockExecutor,
    ElasticPermutationRunner,
    ExecReport,
)
from repro.runtime.faultinject import (  # noqa: F401
    FaultInjector,
    SimulatedOOM,
    VirtualClock,
)
