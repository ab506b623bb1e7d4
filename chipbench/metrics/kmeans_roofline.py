"""kmeans_roofline: percent of its roofline the k-means kernel reaches: the
least time one chip needs for the kernel's work over the kernel's device
time per iteration.  Read where the configuration's kernel is kmeans."""


def read(run):
    return run.kernel_roofline("kmeans")
