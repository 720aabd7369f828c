"""95th percentile of how late the open-loop generator sent a request
(sent - due), on its own clock. A starved generator must not read as a
fast server."""

from benchmarks.harness import percentile


def read(ctx):
    late = ctx["client"]["late_ms"]
    return percentile(late, 95) if late else None
