"""Share of the traced window in which the device ran no operation."""
from bench.metrics._common import idle_percent as read  # noqa: F401
