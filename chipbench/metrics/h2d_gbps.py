"""h2d_gbps: gigabytes a second that the window's streamed launches move
from host to device: the ``bytes`` of the Context's own ``stream:<kernel>``
spans inside the window, over the window's seconds.  Cells whose launches
stream nothing have none."""


def read(run):
    b = [e["args"]["bytes"] for e in run.spans
         if e["name"].startswith("stream:")]
    return sum(b) / run.window_s / 1e9 if b else None
