"""collective_ms: device milliseconds per iteration of exchanges between
chips (collective-permute, all-gather, all-reduce and the like) on chip 0.
One chip has none."""


def read(run):
    t = run.trace
    if t is None or run.chips < 2:
        return None
    s = t.kind_seconds("collective", device=0)
    return 1e3 * s / run.iters if s > 0 else None
