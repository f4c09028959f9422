"""idle_share.<family>: the share of the traced window, in %, in which no
operation ran on the device (averaged over the chips used)."""


def read(run):
    if run.trace is None:
        return {}
    share = run.reduce.idle_share(run.trace)
    return {f"idle_share.{run.family}": 100.0 * share}
