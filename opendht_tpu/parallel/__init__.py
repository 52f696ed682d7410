"""Multi-chip scale-out: sharded node tables + collective top-k merge,
placed by the declarative partition-rule layer (partition.py)."""

from .partition import (  # noqa: F401
    match_partition_rules,
    make_shard_and_gather_fns,
    shard_put,
    constrain,
    shard_table_state,
    TableState,
    TABLE_AXIS_RULES,
    DP_AXIS_RULES,
)
from .sharded import (  # noqa: F401
    make_mesh,
    pad_to_multiple,
    sharded_xor_topk,
    sharded_sort_table,
    sharded_expand_table,
    sharded_window_lookup,
    sharded_lookup,
    sharded_maintenance_sweep,
    dp_simulate_lookups,
    tp_simulate_lookups,
    build_tp_lookup,
)
from .global_sort import sharded_global_sort  # noqa: F401
from .churn import ShardedChurnTable  # noqa: F401
