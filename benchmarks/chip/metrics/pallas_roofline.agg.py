"""Share of the HBM roofline that the round's Pallas kernels reach."""
from metrics._shared import pallas_roofline as read  # noqa: F401
