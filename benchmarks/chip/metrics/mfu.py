"""Model FLOPs utilization of the robust step: the FLOPs n workers'
forward and backward passes require (``families/<family>.step_flops``),
times steps per second, over chips times the bf16 peak."""
from metrics._shared import flops_share as read  # noqa: F401
