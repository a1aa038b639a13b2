from .schedule import (CongestionPlan, FleetPlan, ReduceProgram, TenantPlan,
                       build_program, plan, plan_batch, plan_congestion,
                       plan_fleet)
from .topology import (ClusterTopology, Fleet, build_fleet, chip_level_tree,
                       degrade_links, degrade_switches, fail_devices,
                       fail_switches, fat_tree_fleet, fleet_tree)
from .tree_allreduce import tree_allreduce, tree_allreduce_tree

__all__ = [
    "CongestionPlan", "FleetPlan", "ReduceProgram", "TenantPlan",
    "build_program", "plan", "plan_batch", "plan_congestion", "plan_fleet",
    "ClusterTopology", "Fleet", "build_fleet", "chip_level_tree",
    "fat_tree_fleet",
    "fleet_tree", "fail_devices", "fail_switches", "degrade_links",
    "degrade_switches",
    "tree_allreduce", "tree_allreduce_tree",
]
