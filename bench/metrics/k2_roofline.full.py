"""K2 (fused_prune_aggregate's gather-aggregate kernel) against its
roofline: T·K kept h' rows read and T rows written (bench/costs.py) over
its device time in the trace."""
from bench.metrics_common import kernel_roofline

# On the chip a device op is named by its HLO instruction. Both kernels of a
# launch carry the name of their jitted wrapper; K1 returns the tuple (α,
# ids), K2 one float32 array of rows.
PATTERN = r"^%fused_prune_aggregate_grouped_pallas[.\d]* = f32\["


def read(ctx):
    return kernel_roofline(ctx, PATTERN, "k2_ops", "k2_bytes")
