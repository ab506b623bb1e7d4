#!/usr/bin/env python3
"""Readings that a cell's limits are set from, at the cell's own size.

    python3 chipbench/control.py --workload kmeans.hbm --seeds 1-12 \
        --control-seeds 101-103 --fault-seeds 201-203

For each of ``--seeds`` the program runs the cell's checked iterations, as a
benchmark run's set-up does, and its numbers are compared with the
reference.  For each of ``--control-seeds`` the control, the reference at
the next lower precision than the configuration states, takes the
program's place; for each of ``--fault-seeds``, each fault that the
configuration plants in the reference (its ``FAULTS``) does.  One JSON line
per side and seed, with the run's own verdict (``correct``), then a summary
line: for each number, the largest reading of the program (the lower
reading), the smallest of the control (the upper reading), and the
smallest of each fault.  Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench  # noqa: E402


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        if "-" in part[1:]:
            lo, hi = part.split("-", 1)
            out.extend(range(int(lo), int(hi) + 1))
        elif part:
            out.append(int(part))
    return out


def program_readings(cell, seed: int, devices) -> dict:
    traffic = cell.workload["traffic"]
    prog = cell.config.Program(cell.cfg, traffic, seed, devices)
    got = prog.check(int(cell.cfg["check_steps"]))
    prog.free()
    del prog
    gc.collect()
    return cell.config.readings(cell.cfg, traffic, seed, devices, got)


def in_place(make):
    """Readings of what ``make`` computes, put in the program's place."""
    def read(cell, seed: int, devices) -> dict:
        traffic = cell.workload["traffic"]
        got = make(cell.cfg, traffic, seed, devices)
        return cell.config.readings(cell.cfg, traffic, seed, devices, got)
    return read


def collect(cell, program_seeds, control_seeds, devices, out=print,
            fault_seeds=()) -> dict:
    """Run every side; returns the summary (also printed last)."""
    import jax

    mod = cell.config
    sides = [("program", program_seeds, program_readings),
             ("control", control_seeds, in_place(mod.control))]
    sides += [(f"fault:{name}", fault_seeds, in_place(
        lambda *a, name=name: mod.fault(name, *a)))
              for name in getattr(mod, "FAULTS", ())]
    limits = cell.workload["limits"]
    summary = {"workload": cell.name, "lower": {}, "upper": {}, "faults": {},
               "limits": limits}
    precision = cell.cfg.get("matmul_precision", "default")
    with jax.default_matmul_precision(precision):
        for side, chosen, read in sides:
            if side == "program":
                acc, fold = summary["lower"], max
            elif side == "control":
                acc, fold = summary["upper"], min
            else:
                acc, fold = summary["faults"].setdefault(side[6:], {}), min
            for s in chosen:
                t0 = time.perf_counter()
                r = read(cell, s, devices)
                _, correct = bench.verdict(r, limits)
                out(json.dumps({"side": side, "seed": s, "readings": r,
                                "correct": correct,
                                "seconds": time.perf_counter() - t0}))
                for k, v in r.items():
                    acc[k] = fold(acc[k], v) if k in acc else v
    out(json.dumps(summary))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(bench.ROOT / "src"))
    cell = bench.load_cell(args.workload)
    bench.setup_jax()
    try:
        devices = bench.find_devices(int(cell.workload["chips"]))
    except bench.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    collect(cell, args.seeds, args.control_seeds, devices,
            out=lambda line: print(line, flush=True),
            fault_seeds=args.fault_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
