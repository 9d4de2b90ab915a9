from .mesh import (MeshContext, allreduce_metric_pairs, force_cpu_devices,
                   make_mesh_context, maybe_distributed_init,
                   parse_device_spec)
from .rules import (UnmatchedLeafError, add_fsdp, make_shard_and_gather_fns,
                    match_partition_rules, parse_rule_string, rule_coverage)

__all__ = ["MeshContext", "make_mesh_context", "parse_device_spec",
           "maybe_distributed_init", "allreduce_metric_pairs",
           "force_cpu_devices",
           "match_partition_rules", "make_shard_and_gather_fns",
           "parse_rule_string", "rule_coverage", "add_fsdp",
           "UnmatchedLeafError"]
