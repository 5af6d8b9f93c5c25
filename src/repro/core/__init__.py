"""Vertica-in-JAX core: projections, encodings, storage, MVCC, K-safety.

The paper's §3-§5 as a library: see DESIGN.md for the architecture map.
"""
from .block_cache import BlockCache, CacheStats
from .catalog import Catalog
from .database import (AvailabilityError, LoadStats, NodeState,
                       QueryRejectedError, RecoverySourceLostError,
                       SegmentUnavailableError, Txn, VerticaDB)
from .encodings import (EncodedColumn, Encoding, decode_jnp, device_bytes,
                        encode, upload_jnp)
from .epochs import EpochManager
from .faults import (INJECTION_POINTS, CrashNode, FaultError,
                     FaultInjector, FaultTimeout, Hang, NodeCrashError,
                     NullInjector, Transient, TransientFaultError,
                     fire_with_retries, with_retries)
from .locks import COMPATIBLE, CONVERT, MODES, LockError, LockManager
from .partitioning import partition_keys
from .projection import (PrejoinSpec, ProjectionDef, super_projection)
from .segmentation import SegmentationSpec, hash_columns, rebalance_plan
from .sma import ColumnSMA
from .storage import DeleteVector, ROSContainer, WOS
from .tuple_mover import ProjectionStore, mergeout, moveout, run_tuple_mover
from .types import BLOCK_ROWS, ColumnDef, SQLType, TableSchema

__all__ = [
    "AvailabilityError", "BLOCK_ROWS", "BlockCache", "COMPATIBLE",
    "CONVERT", "CacheStats", "Catalog",
    "ColumnDef", "ColumnSMA", "CrashNode", "DeleteVector", "EncodedColumn",
    "Encoding", "EpochManager", "FaultError", "FaultInjector",
    "INJECTION_POINTS",
    "FaultTimeout", "Hang", "LoadStats", "LockError", "LockManager",
    "MODES",
    "NodeCrashError", "NodeState", "NullInjector", "PrejoinSpec",
    "ProjectionDef", "ProjectionStore", "QueryRejectedError",
    "ROSContainer", "RecoverySourceLostError", "SQLType",
    "SegmentUnavailableError", "SegmentationSpec", "TableSchema",
    "Transient", "TransientFaultError", "Txn", "VerticaDB", "WOS",
    "decode_jnp", "device_bytes", "encode", "fire_with_retries",
    "hash_columns", "mergeout", "moveout", "partition_keys",
    "rebalance_plan", "run_tuple_mover", "super_projection", "upload_jnp",
    "with_retries",
]
