"""Device: share of the traced slice in which no operation ran on the chip
(1 - busy union of the `XLA Ops` line over the slice), averaged over chips."""


def read(run):
    if not run.trace or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
