"""step_mfu: percent of the chip's roofline the whole iteration reaches:
the least time one chip needs for its share of an iteration's work (FLOPs
at peak or bytes at HBM bandwidth, whichever is more) over the traced
run's time per iteration."""


def read(run):
    return 100.0 * run.roof_s("step") / run.iter_s
