from .disagg import DisaggExecutor, RoleStats, TTFTSplit
from .engine import DecodeEngine, KVHandoff, Request
from .executor import EngineExecutor
from .fleet import (
    BundleStats,
    FleetReport,
    FleetServer,
    LatencyStats,
    Replica,
    RequestTrace,
    StreamReport,
)

__all__ = [
    "DisaggExecutor",
    "Replica",
    "RoleStats",
    "TTFTSplit",
    "DecodeEngine",
    "KVHandoff",
    "Request",
    "EngineExecutor",
    "BundleStats",
    "FleetReport",
    "FleetServer",
    "LatencyStats",
    "RequestTrace",
    "StreamReport",
]
