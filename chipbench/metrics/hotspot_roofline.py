"""hotspot_roofline: percent of its roofline the HotSpot stencil kernel
reaches: the least time one chip needs for the kernel's work over the
kernel's device time per iteration.  Read where the configuration's kernel
is hotspot."""


def read(run):
    return run.kernel_roofline("hotspot")
