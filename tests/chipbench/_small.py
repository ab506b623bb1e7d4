"""Cells of the chip benchmark cut to a size the CPU runs in seconds, and a
run of one with the harness's look for a chip skipped."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench  # noqa: E402

#: Sizes per configuration: the widths stay, the counts shrink.
SMALL = {"kmeans": {"points": 1 << 14, "block_rows": 1 << 12},
         "hotspot": {"rows_per_chip": 64, "cols": 256}}


def small_cell(name: str, module=bench) -> "bench.Cell":
    cell = module.load_cell(name)
    if cell.cfg["name"] == "hotspot":
        cell.cfg.update(SMALL["hotspot"])
    else:
        cell.workload["traffic"].update(SMALL["kmeans"])
    return cell


def run_on_cpu(cell, seed: int, seconds: float = 0.3, trace: bool = False,
               module=bench) -> dict:
    """``run.run`` on the CPU's devices, with the v5e's peaks and without
    the persistent cache."""
    import jax

    saved = (module.find_devices, module.setup_jax, module.peaks_for)
    peaks = module.peaks_for("TPU v5 lite")
    module.find_devices = lambda chips: jax.devices()[:chips]
    module.setup_jax = lambda: None
    module.peaks_for = lambda kind: peaks
    try:
        return module.run(cell, seed, seconds, trace)
    finally:
        module.find_devices, module.setup_jax, module.peaks_for = saved


def run_with_four_devices(code: str, timeout: int = 600) -> list[dict]:
    """Run ``code`` (which may use this module as ``small``) in a child with
    four CPU devices; every line it prints that starts with ``{`` is
    returned as JSON."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    prelude = (f"import sys; sys.path.insert(0, {str(Path(__file__).parent)!r})"
               "\nimport _small as small\n")
    out = subprocess.run([sys.executable, "-c", prelude + code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise AssertionError(f"child failed:\n{out.stdout}\n{out.stderr}")
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
