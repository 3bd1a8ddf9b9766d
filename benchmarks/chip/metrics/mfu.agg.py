"""The aggregation round's FLOPs utilization: statistics and extraction
products a round requires (``kinds/agg.Runner.flops_per_unit``), times
rounds per second, over chips times the bf16 peak."""
from metrics._shared import flops_share as read  # noqa: F401
