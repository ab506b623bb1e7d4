"""plan_ms: host milliseconds the planner takes per launch, the mean of
the Context's own ``plan:<kernel>`` spans (perf_counter clock) inside the
window.  Cells that do not launch through Context have none."""


def read(run):
    d = [e["dur"] for e in run.spans if e["name"].startswith("plan:")]
    return 1e3 * sum(d) / len(d) if d else None
