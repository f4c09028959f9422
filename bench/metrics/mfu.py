"""mfu.<family>: the flop that the window's corrected MVMs require by the
configuration (4 n^2 cols each: the two n x n products of the fused tier-1
EC), over the window's wall time x chips x the chip's published bf16 peak,
in %.  The same work whatever implements it."""


def read(run):
    if not run.records or "flops_bf16" not in run.peaks:
        return {}
    flop = sum(r["mvms"] * run.work(r["cols"])["flop"] for r in run.records)
    peak = run.window_s * run.chips * run.peaks["flops_bf16"]
    return {f"mfu.{run.family}": 100.0 * flop / peak}
