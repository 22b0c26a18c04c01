"""The Venice-scheduled conflict-free parallel shard-read planner."""
from repro.data.venice_io import IOPlan, plan_reads

__all__ = ["IOPlan", "plan_reads"]
