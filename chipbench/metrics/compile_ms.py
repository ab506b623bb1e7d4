"""compile_ms: host milliseconds per launch that JAX spends tracing,
lowering and compiling the launch's programs (a load from the persistent
cache counts as a compile): the mean of the ``compile_s`` the Context
records on its ``launch:<kernel>`` spans inside the window, a part of
``dispatch_ms``.  A program that records no such count, or a cell that
does not launch through Context, has none."""


def read(run):
    s = [e["args"]["compile_s"] for e in run.spans
         if e["name"].startswith("launch:") and "compile_s" in e["args"]]
    return 1e3 * sum(s) / len(s) if s else None
