"""dispatch_ms: host milliseconds a launch takes to lower and enqueue its
work, the mean of the Context's own ``launch:<kernel>`` spans inside the
window.  Cells that do not launch through Context have none."""


def read(run):
    d = [e["dur"] for e in run.spans if e["name"].startswith("launch:")]
    return 1e3 * sum(d) / len(d) if d else None
