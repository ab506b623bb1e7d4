"""Serving driver: batched requests through the ServeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --requests 8 --prompt-len 32 --max-new 16
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import init_params
from repro.serve.engine import Request, ServeEngine
from repro.utils.compile_cache import enable_compile_cache


def run_serving(
    arch: str,
    *,
    smoke: bool = True,
    requests: int = 8,
    prompt_len: int = 32,
    max_new: int = 16,
    slots: int = 4,
    seed: int = 0,
) -> dict:
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    params = init_params(jax.random.key(seed), cfg)
    max_len = prompt_len + max_new + 8
    engine = ServeEngine(params, cfg, slots=slots, max_len=max_len,
                         seed=seed)
    rng = np.random.default_rng(seed)
    t0 = time.time()
    for rid in range(requests):
        engine.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab, prompt_len).astype(np.int32),
            max_new_tokens=max_new,
        ))
    done = engine.run()
    dt = time.time() - t0
    statuses: dict[str, int] = {}
    for req in done:
        statuses[req.status] = statuses.get(req.status, 0) + 1
    return {
        "arch": cfg.name,
        "completed": len(done),
        "statuses": statuses,
        "errors": engine.stats["errors"],
        "error_messages": sorted({r.error for r in done if r.error}),
        "decode_tokens": engine.stats["decode_tokens"],
        "prefill_tokens": engine.stats["prefill_tokens"],
        "wall_s": round(dt, 3),
        "tokens_per_s": round(
            (engine.stats["decode_tokens"] + engine.stats["prefill_tokens"])
            / max(dt, 1e-9), 1,
        ),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    print(json.dumps(run_serving(
        args.arch, smoke=args.smoke, requests=args.requests,
        prompt_len=args.prompt_len, max_new=args.max_new, slots=args.slots,
    ), indent=2))


if __name__ == "__main__":
    main()
