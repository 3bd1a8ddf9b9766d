"""Share of the traced window in which no operation ran on the chip,
during robust steps."""
from metrics._shared import idle_share as read  # noqa: F401
