"""Batched serving engine: request queue → slot-based continuous batching.

Production loop: a fixed decode batch of ``slots``; finished/empty slots are
refilled from the queue by running a prefill for the incoming prompt and
splicing its cache into the slot (cache surgery = per-slot
dynamic_update_slice on the batch axis).  Prefill and decode are separate
jitted programs (the two compiled artifacts the ``prefill_*`` / ``decode_*``
dry-run shapes correspond to).

Sampling: greedy or temperature; deterministic per (seed, slot, step).

Robustness: each request carries a ``deadline_steps`` budget — one that
decodes past it is evicted with status ``timed_out`` instead of occupying a
decode slot forever.  A :class:`~repro.core.faults.FaultInjector` can be
threaded in to fail prefills/decodes deterministically; failed work retries
under the :class:`~repro.core.faults.RecoveryPolicy` and a request whose
retries exhaust completes with status ``error`` — the batch loop never
stalls on one bad request.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.faults import FaultInjector, RecoveryPolicy
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.trace import NULL_TRACER
from repro.dist.sharding import ShardingRules
from repro.models import api as model_api
from repro.models.config import ModelConfig


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    deadline_steps: int | None = None  # decode-step budget (None = engine's)
    # filled by the engine:
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "pending"  # -> "ok" | "timed_out" | "error"
    error: str | None = None  # repr of the exception behind "error"


class ServeEngine:
    def __init__(
        self,
        params: Any,
        cfg: ModelConfig,
        *,
        slots: int = 4,
        max_len: int = 512,
        rules: ShardingRules | None = None,
        seed: int = 0,
        deadline_steps: int | None = None,
        fault_injector: FaultInjector | None = None,
        recovery: RecoveryPolicy | None = None,
        registry: MetricsRegistry | None = None,
        tracer=None,
        clock: Callable[[], float] | None = None,
    ):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.rules = rules
        self.rng = np.random.default_rng(seed)
        self.deadline_steps = deadline_steps
        self.fault_injector = fault_injector
        self.recovery = recovery or RecoveryPolicy()
        # Observability: terminal-status request counts, queue depth, and
        # TTFT / per-decode-step latency histograms.  ``clock`` is injected
        # for determinism in tests; with a live tracer it defaults to the
        # tracer's clock so latencies and spans share a timeline.
        self.tracer = tracer or NULL_TRACER
        self._registry = registry
        if clock is not None:
            self.clock = clock
        elif self.tracer.enabled:
            self.clock = self.tracer.now
        else:
            self.clock = time.perf_counter
        self._submit_ts: dict[int, float] = {}

        self._decode = jax.jit(
            lambda p, tok, st: model_api.decode_step(p, tok, cfg, st, rules)
        )
        self._prefill = jax.jit(
            lambda p, batch, st: model_api.prefill(p, batch, cfg, st, rules)
        )
        self.state = model_api.init_decode_state(cfg, slots, max_len)
        self.slot_req: list[Request | None] = [None] * slots
        self.slot_tokens = np.zeros((slots,), np.int32)
        self.slot_age = np.zeros((slots,), np.int64)  # decode steps in slot
        self.queue: list[Request] = []
        self.completed: list[Request] = []
        self.stats = {"prefill_tokens": 0, "decode_tokens": 0, "steps": 0,
                      "timed_out": 0, "errors": 0, "retries": 0}

    # -- API --------------------------------------------------------------------

    @property
    def registry(self) -> MetricsRegistry:
        return self._registry if self._registry is not None \
            else default_registry()

    def submit(self, req: Request) -> None:
        self.queue.append(req)
        self._submit_ts[req.rid] = self.clock()
        self.registry.gauge("serve.queue_depth").set(len(self.queue))

    def run(self, max_steps: int = 1000) -> list[Request]:
        """Drive until queue + slots drain (or step budget)."""
        for _ in range(max_steps):
            self._fill_slots()
            if all(r is None for r in self.slot_req):
                break
            self._decode_once()
        return self.completed

    # -- internals ----------------------------------------------------------------

    _TERMINAL_STATUS = {"ok": "completed", "timed_out": "timed_out",
                        "error": "error"}

    def _finish(self, slot: int, req: Request, status: str) -> None:
        req.status = status
        req.done = True
        self.completed.append(req)
        self.slot_req[slot] = None
        self._submit_ts.pop(req.rid, None)
        self.registry.counter("serve.requests").labels(
            status=self._TERMINAL_STATUS.get(status, status)).inc()

    def _fill_slots(self) -> None:
        for s in range(self.slots):
            while self.slot_req[s] is None and self.queue:
                req = self.queue.pop(0)
                self.registry.gauge("serve.queue_depth").set(len(self.queue))
                try:
                    with self.tracer.span(f"prefill:r{req.rid}",
                                          stream="serve", cat="compute",
                                          rid=req.rid, slot=s,
                                          prompt_len=len(req.prompt)):
                        logits, pstate = self._prefill_with_retry(req)
                except Exception as exc:  # noqa: BLE001 — retries exhausted
                    req.error = repr(exc)
                    self.stats["errors"] += 1
                    self._finish(s, req, "error")  # slot stays free
                    continue
                self.state = _splice_state(self.state, pstate, s)
                tok = self._sample(logits[0, -1], req)
                req.output.append(int(tok))
                # First token out: time-to-first-token for this request.
                t_submit = self._submit_ts.get(req.rid)
                if t_submit is not None:
                    self.registry.histogram("serve.ttft_s").observe(
                        self.clock() - t_submit)
                self.slot_req[s] = req
                self.slot_tokens[s] = int(tok)
                self.slot_age[s] = 0
                self.stats["prefill_tokens"] += len(req.prompt)

    def _prefill_with_retry(self, req: Request):
        """Prefill this prompt alone (batch=1, spliced into the slot),
        retrying injected/transient failures under the recovery policy."""
        pcfg_state = model_api.init_decode_state(self.cfg, 1, self.max_len)
        batch = {
            "tokens": jnp.asarray(req.prompt[None, :], jnp.int32)
        }
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (1, self.cfg.enc_frames, self.cfg.d_model),
                self.cfg.jdtype,
            )
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (1, self.cfg.n_patches, self.cfg.d_model),
                self.cfg.jdtype,
            )
        attempt = 0
        while True:
            try:
                if (self.fault_injector is not None
                        and self.fault_injector.probe(
                            "request", task=req.rid, site="prefill")):
                    raise RuntimeError(
                        f"injected prefill failure: request {req.rid}"
                    )
                return self._prefill(self.params, batch, pcfg_state)
            except Exception:  # noqa: BLE001 — bounded retry
                attempt += 1
                if attempt > self.recovery.max_attempts:
                    raise
                self.stats["retries"] += 1

    def _decode_once(self) -> None:
        toks = jnp.asarray(self.slot_tokens[:, None], jnp.int32)
        attempt = 0
        t0 = self.clock()
        with self.tracer.span("decode_step", stream="serve", cat="compute",
                              step=self.stats["steps"]):
            while True:
                try:
                    if (self.fault_injector is not None
                            and self.fault_injector.probe(
                                "decode", site="decode_step")):
                        raise RuntimeError("injected decode-batch failure")
                    logits, state = self._decode(self.params, toks,
                                                 self.state)
                    break
                except Exception:  # noqa: BLE001 — bounded retry
                    attempt += 1
                    if attempt > self.recovery.max_attempts:
                        raise
                    self.stats["retries"] += 1
        self.registry.histogram("serve.decode_step_s").observe(
            self.clock() - t0)
        self.state = state
        self.stats["steps"] += 1
        for s in range(self.slots):
            req = self.slot_req[s]
            if req is None:
                continue
            tok = self._sample(logits[s, -1], req)
            req.output.append(int(tok))
            self.slot_tokens[s] = int(tok)
            self.slot_age[s] += 1
            self.stats["decode_tokens"] += 1
            if len(req.output) >= req.max_new_tokens:
                self._finish(s, req, "ok")
                continue
            deadline = (req.deadline_steps if req.deadline_steps is not None
                        else self.deadline_steps)
            if deadline is not None and self.slot_age[s] >= deadline:
                # Past its budget: return what we have instead of holding
                # the slot (and the rest of the queue) hostage.
                self.stats["timed_out"] += 1
                self._finish(s, req, "timed_out")

    def _sample(self, logits: jax.Array, req: Request) -> int:
        logits = np.asarray(logits, np.float32)
        if req.temperature <= 0.0:
            return int(logits.argmax())
        p = np.exp((logits - logits.max()) / req.temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))


def _splice_state(state: Any, single: Any, slot: int) -> Any:
    """Copy a batch-1 prefill state into batch slot ``slot``.

    Every leaf whose batch axis we know (dense/MoE caches: axis 1 with
    leading layer axis; ``pos``: axis 0) gets a dynamic-slice update.  For
    pytrees with other layouts (rwkv/hybrid states) the structure matches
    leafwise, so we splice on the axis whose size differs.
    """

    def splice(dst, src):
        if dst.ndim == 0:
            return dst
        # find the batch axis: the one where dst is larger and src == 1
        for ax in range(dst.ndim):
            if src.shape[ax] == 1 and dst.shape[ax] != src.shape[ax]:
                idx = [0] * dst.ndim
                idx[ax] = slot
                return jax.lax.dynamic_update_slice(
                    dst, src.astype(dst.dtype), tuple(idx)
                )
        return dst

    return jax.tree.map(splice, state, single)
