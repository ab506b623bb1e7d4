"""outside_kernel_ms: device milliseconds per iteration of XLA ops, neither
a Pallas kernel nor a collective (layout and padding around the kernel,
partial-sum reductions, the centroid update), the mean over the chips."""


def read(run):
    t = run.trace
    if t is None or not t.busy_s:
        return None
    return 1e3 * t.kind_seconds("xla") / run.iters
