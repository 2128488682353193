"""Physical operators — ≙ reference crate ``datafusion-ext-plans``.

Every operator is an :class:`ExecNode` producing a stream of device
RecordBatches per partition.  Kernels are jitted per (schema, capacity)
bucket; blocking operators (sort, agg, join build) register as
MemConsumers and spill through the runtime memory manager.
"""

from .base import ExecNode
from .memory_scan import MemoryScanExec
from .project import ProjectExec
from .filter import FilterExec
from .agg import AggExec, AggFunction, AggMode, GroupingExpr
from .sort import SortExec, SortField
from .limit import LimitExec
from .union import UnionExec
from .rename import RenameColumnsExec
from .empty import EmptyPartitionsExec
from .debug import DebugExec
from .coalesce import CoalesceBatchesExec
from .joins import BroadcastJoinExec, HashJoinExec, SortMergeJoinExec
from .window import WindowExec, WindowFunction
from .expand import ExpandExec
from .generate import GenerateExec
from .object_agg import ObjectAggExec, Udaf
from .udafs import approx_count_distinct, approx_percentile
from .orc_scan import OrcScanExec
from .parquet_scan import FileSplit, ParquetScanExec
from .parquet_sink import ParquetSinkExec

__all__ = [
    "ExecNode", "MemoryScanExec", "ProjectExec", "FilterExec", "AggExec",
    "AggFunction", "AggMode", "GroupingExpr", "SortExec", "SortField",
    "LimitExec", "UnionExec", "RenameColumnsExec", "EmptyPartitionsExec",
    "DebugExec", "CoalesceBatchesExec", "BroadcastJoinExec", "HashJoinExec",
    "SortMergeJoinExec", "WindowExec", "WindowFunction", "ExpandExec",
    "ObjectAggExec", "Udaf", "approx_count_distinct", "approx_percentile",
    "GenerateExec", "OrcScanExec", "ParquetScanExec", "ParquetSinkExec",
    "FileSplit",
]
