"""Share of the HBM roofline that the step's Pallas kernels reach."""
from metrics._shared import pallas_roofline as read  # noqa: F401
