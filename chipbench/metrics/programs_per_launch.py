"""programs_per_launch: programs JAX compiled or loaded from its persistent
cache per launch, the mean of the ``programs`` the Context records on its
``launch:<kernel>`` spans inside the window.  A launch that compiles
nothing reads 0.  A program that records no such count, or a cell that
does not launch through Context, has none."""


def read(run):
    n = [e["args"]["programs"] for e in run.spans
         if e["name"].startswith("launch:") and "programs" in e["args"]]
    return sum(n) / len(n) if n else None
