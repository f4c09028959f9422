"""tier1_roofline.<family>: the tier-1 EC kernel's share of its roofline,
in %, from the device trace.

Kernel time is the sum of the durations of the kernel's events in the
traced window.  The least time of one call is the larger of the bytes the
configuration requires (the resident image, A_tilde and dA at their stated
dtype, plus the input, its DAC image and the output) over the published HBM
bandwidth, and 4 n^2 cols flop over the published bf16 peak; where a mix
has several sizes, its mean over the window's MVMs.  Nothing is read where
the trace holds no such kernel (a path with no tier-1 kernel)."""

# The kernel's op in the trace: ``%ec_matmul.<k> custom-call`` (the
# ``pallas_call`` of ``kernels/rram_mvm.py: ec_matmul``).
KERNEL = r"^%ec_matmul(\.\d+)? custom-call$"


def least_seconds(work: dict, peaks: dict) -> tuple:
    """(seconds, bound) of one call at the chip's published peaks."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_flop = work["flop"] / peaks["flops_bf16"]
    return (by_bytes, "bytes") if by_bytes >= by_flop else (by_flop, "flop")


def read(run):
    if (run.trace is None or "flops_bf16" not in run.peaks
            or not run.records):
        return {}
    events = [e for evs in run.reduce.kernel_events(run.trace, KERNEL).values()
              for e in evs]
    if not events:
        return {}
    mvms = sum(r["mvms"] for r in run.records)
    least = sum(r["mvms"] * least_seconds(run.work(r["cols"]), run.peaks)[0]
                for r in run.records) / mvms
    kernel_s = sum(e.seconds for e in events)
    return {f"tier1_roofline.{run.family}": 100.0 * len(events) * least
            / kernel_s}
