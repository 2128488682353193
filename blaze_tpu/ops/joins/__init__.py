"""Joins — ≙ reference ``joins/`` (join_hash_map.rs, bhj/, smj/,
broadcast_join_exec.rs:76-567, sort_merge_join_exec.rs:58-309).

TPU design (joins/core.py): the "hash map" is a **sorted key table** —
build keys reduce to 64-bit hashes, sorted on device with their row
indices, each key's run length and the offsets of its hash-prefix
buckets; probes binary-search the sorted table once a batch, inside the
bucket of each probe key's prefix (a match range is the found key's
run), expand match ranges with the two-phase
count/cumsum/gather pattern, then **verify** candidate pairs against
the real key columns (so 64-bit collisions and null keys can never
produce wrong matches — exactness does not rest on the hash).
"""

from .core import JoinMap, JoinType
from .broadcast import BroadcastJoinBuildHashMapExec, BroadcastJoinExec, clear_join_map_cache
from .hash_join import HashJoinExec
from .smj import SortMergeJoinExec

__all__ = [
    "JoinMap",
    "JoinType",
    "BroadcastJoinBuildHashMapExec",
    "BroadcastJoinExec",
    "HashJoinExec",
    "SortMergeJoinExec",
    "clear_join_map_cache",
]
