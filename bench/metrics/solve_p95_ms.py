"""solve_p95_ms: the 95th percentile of the latency of every solve the
window completed (host clock, from when it was due to its answer)."""
import statistics


def read(run):
    if run.family != "solve" or len(run.records) < 2:
        return {}
    lat = [1e3 * r["latency_s"] for r in run.records]
    return {"solve_p95_ms": statistics.quantiles(lat, n=20)[18]}
