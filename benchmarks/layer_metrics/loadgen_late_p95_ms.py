"""How late the load generator ran: sent minus due, 95th percentile (ms).
A starved generator must not be read as a fast server."""
from benchmarks.lib.harness import percentile


def read(ctx):
    late = ctx.get("late_ms")
    return percentile(late, 95) if late else None
