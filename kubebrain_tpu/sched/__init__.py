"""Device-aware request scheduler (the service→storage admission layer).

The serving path used to issue one blocking device scan per Range RPC,
although JAX dispatches asynchronously and the device can hold several
scans in flight. This package pipelines them at the serving layer:
concurrent Range/Count requests are queued into APF-style priority lanes,
coalesced when identical, and dispatched with a bounded in-flight depth so
the device pipeline stays full while the host overlays deltas for earlier
requests. Overload is handled by bounded queues + deadline shedding (etcd
``ResourceExhausted`` on the wire).

See docs/scheduler.md for the queue model, lanes, and shedding policy.
"""

from .lanes import Lane, classify, classify_write
from .scheduler import (
    RequestScheduler,
    SchedConfig,
    SchedClosedError,
    SchedOverloadError,
    SchedResultTimeoutError,
    client_of,
    ensure_scheduler,
)

__all__ = [
    "Lane",
    "classify",
    "classify_write",
    "client_of",
    "RequestScheduler",
    "SchedConfig",
    "SchedClosedError",
    "SchedOverloadError",
    "SchedResultTimeoutError",
    "ensure_scheduler",
]
