"""Share of the traced window in which no operation ran on the chip,
during aggregation rounds."""
from metrics._shared import idle_share as read  # noqa: F401
