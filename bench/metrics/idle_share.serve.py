"""Share of the traced window in which the chip ran no op (open-loop
serving through ServeFrontend)."""
from bench.metrics_common import idle_share as read  # noqa: F401
