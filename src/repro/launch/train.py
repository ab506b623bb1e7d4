"""End-to-end training driver.

Runs real training on whatever devices exist (CPU smoke scale here, the
production mesh on a pod): config → data pipeline → jitted train step →
checkpoint manager → supervisor loop with heartbeat/straggler monitoring.

    PYTHONPATH=src python -m repro.launch.train \
        --arch gemma-2b --smoke --steps 50 --batch 8 --seq 128

``--smoke`` selects the reduced config (CPU-sized); omit it on real
hardware to train the full architecture.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core.faults import FaultInjector
from repro.data.pipeline import DataConfig, TokenStream
from repro.dist.fault import HeartbeatMonitor, StragglerMonitor, TrainSupervisor
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.trace import NULL_TRACER
from repro.train.train_loop import init_train_state, make_train_step
from repro.utils.compile_cache import enable_compile_cache


def run_training(
    arch: str,
    *,
    smoke: bool = True,
    steps: int = 50,
    batch: int = 8,
    seq: int = 128,
    microbatches: int = 1,
    ckpt_dir: str | None = None,
    ckpt_every: int = 20,
    seed: int = 0,
    log_every: int = 10,
    fail_at_step: int | None = None,  # legacy one-shot fault injection
    fault_injector: FaultInjector | None = None,  # general fault schedule
    supervisor_backoff: float = 0.0,
    jitter_seed: int | None = None,  # decorrelated restart jitter
    clock=time.monotonic,
    sleep=time.sleep,
    registry: MetricsRegistry | None = None,
    tracer=None,
) -> dict:
    reg = registry if registry is not None else default_registry()
    tracer = tracer or NULL_TRACER
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    data = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                      seed=seed)
    stream = TokenStream(data)
    step_fn = make_train_step(cfg, microbatches=microbatches)
    ckpt = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
    monitor = HeartbeatMonitor(num_hosts=1)
    stragglers = StragglerMonitor(monitor)
    losses: list[float] = []

    def make_batch(step: int) -> dict:
        b = stream.batch_at(step)
        out = {"tokens": jnp.asarray(b["tokens"])}
        if cfg.family == "encdec":
            rng = np.random.default_rng(seed + step)
            out["frames"] = jnp.asarray(
                rng.standard_normal(
                    (batch, cfg.enc_frames, cfg.d_model), np.float32
                ),
                cfg.jdtype,
            )
        if cfg.family == "vlm":
            rng = np.random.default_rng(seed + step)
            out["patch_embeds"] = jnp.asarray(
                rng.standard_normal(
                    (batch, cfg.n_patches, cfg.d_model), np.float32
                ),
                cfg.jdtype,
            )
        return out

    armed = {"fail": fail_at_step is not None}

    def run_from(start: int) -> int:
        state = init_train_state(jax.random.key(seed), cfg)
        if ckpt is not None and ckpt.latest_step() is not None:
            state, meta = ckpt.restore(state)
            start = meta["step"]
        step = start
        while step < steps:
            t0 = clock()
            batch_data = make_batch(step)
            state, metrics = step_fn(state, batch_data)
            loss = float(metrics["loss"])
            losses.append(loss)
            step += 1
            dt = clock() - t0
            reg.counter("train.steps").inc()
            reg.histogram("train.step_s").observe(dt)
            if dt > 0:
                reg.gauge("train.tokens_per_s").set(batch * seq / dt)
            if tracer.enabled:
                # t0/dt come from the injected ``clock`` so the trace is
                # self-consistent (and deterministic when tests fake it).
                tracer.complete("train.step", t0, dt, stream="train",
                                cat="compute",
                                args={"step": step, "loss": loss})
            monitor.beat(0, dt)
            stragglers.evaluate()
            if armed["fail"] and step == fail_at_step:
                armed["fail"] = False  # one-shot fault injection
                raise RuntimeError(f"injected worker failure at {step}")
            if fault_injector is not None and fault_injector.probe(
                "step", task=step, site="train_step"
            ):
                raise RuntimeError(f"injected step failure at {step}")
            if ckpt is not None and step % ckpt_every == 0:
                ckpt.save(step, state)
            if step % log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"({clock() - t0:.2f}s/step)")
        if ckpt is not None:
            ckpt.save(steps, state, blocking=True)
        return step

    if ckpt is not None:
        sup = TrainSupervisor(ckpt, backoff=supervisor_backoff,
                              sleep=sleep, clock=clock,
                              jitter_seed=jitter_seed)
        last = sup.run(run_from, steps)
        events = [dataclass_event(e) for e in sup.events]
    else:
        last = run_from(0)
        events = []
    if ckpt is not None:
        ckpt.wait()
    return {
        "arch": cfg.name,
        "steps": last,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "losses": losses,
        "events": events,
    }


def dataclass_event(e) -> dict:
    return {"kind": e.kind, "step": e.step, "detail": e.detail}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    result = run_training(
        args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
        seq=args.seq, microbatches=args.microbatches,
        ckpt_dir=args.ckpt_dir, seed=args.seed,
    )
    print(json.dumps({k: v for k, v in result.items() if k != "losses"},
                     indent=2))


if __name__ == "__main__":
    main()
