"""Share of the traced window in which the chip ran no op (whole-graph
forwards back to back)."""
from bench.metrics_common import idle_share as read  # noqa: F401
