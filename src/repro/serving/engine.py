"""Serving engine: batched prefill + decode with KV caches.

A deliberately compact continuous-batching engine ("batching-lite"): requests
are admitted into fixed-capacity decode slots; each engine tick runs one
decode step for every active slot; finished sequences free their slot for the
admission queue. Prefill runs per-request (batch=1) and writes the slot's
cache region.

The engine is the paper's "accelerator": its measured service times feed the
queueing models, and the gateway (serving/gateway.py) applies Algorithm 1 to
route between a device-tier engine and edge-tier engines. Timing is
measurement-grade (repro.measure relies on it):

  * every service stamp is taken AFTER ``jax.block_until_ready`` on the op's
    outputs — JAX dispatch is asynchronous, so a bare ``time.*`` pair around
    a jitted call measures dispatch latency, not device compute;
  * JIT compile time is kept out of steady-state service: :meth:`warmup`
    compiles the prefill/decode executables up front, and any cold call that
    does slip through is flagged ``compile=True`` in the service log and
    excluded from :meth:`observed_service_stats`;
  * a pluggable ``timer`` lets the measurement harness substitute a seeded,
    deterministic service-time model for the wall clock (the "simulated
    clock" mode of ``repro.measure.harness``) while the engine still runs the
    real model for token-level correctness.

The served loop's phases are ``jax.profiler.TraceAnnotation`` spans, so a
profile of a served run shows them beside the device's trace (always on;
without a profiler session each costs under a microsecond):

  * ``engine.admit`` — one admitted request, from popping it off the queue
    to setting its slot state;
  * ``engine.decode`` — one decode step, from building its inputs to the
    end of the per-slot bookkeeping;
  * inside either, in order: ``engine.launch`` (host-to-device uploads and
    the dispatch of the jitted call, plus the eager slot write on
    admission), ``engine.wait`` (``block_until_ready``) and
    ``engine.sample`` (the host copy of the token ids the program picked,
    and their conversion to ``int``; no device op runs in it).

The jitted programs are named ``engine_prefill`` and ``engine_decode``
(``jit_engine_prefill(...)`` and ``jit_engine_decode(...)`` in a profile).
Each picks the greedy next token itself: it returns the ``argmax`` of its
logits as int32 ids, never the logits, so sampling dispatches nothing on
the host. The decode program also returns, per expert layer, how many of
the step's (token, choice) pairs went to each held expert, read from the
caches the step wrote (``lm.expert_tokens``); the engine keeps that device
array as ``Engine.expert_tokens`` and never copies it to the host (shape
(0, 0) for a model without experts). The decode program donates the slot
caches: each step writes one K/V row per layer into them in place, and
``tick`` replaces ``Engine.caches`` with the program's output.

KV caches and recurrent state (Mamba's ``conv`` and ``h``) live side by
side in the slot caches: ``_write_slot`` copies a prompt's keys and values
into its slot's first positions, and state leaves (and an expert layer's
``routed`` counts) whole.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as _span

from repro.configs.base import ModelConfig
from repro.models import lm

__all__ = ["Request", "ServeConfig", "ServiceEvent", "Engine", "jit_programs"]


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    arrival_s: float = 0.0
    # filled by the engine:
    tokens_out: list = field(default_factory=list)
    t_admit: float | None = None  # prefill start (queue wait ends here)
    t_first_token: float | None = None
    t_done: float | None = None

    @property
    def latency_s(self) -> float | None:
        return None if self.t_done is None else self.t_done - self.arrival_s

    @property
    def queue_wait_s(self) -> float | None:
        return None if self.t_admit is None else self.t_admit - self.arrival_s


@dataclass(frozen=True)
class ServeConfig:
    slots: int = 4  # concurrent decode slots
    max_seq: int = 512  # cache capacity per slot
    greedy: bool = True


class ServiceEvent(NamedTuple):
    """One timed engine operation in the service log.

    ``t`` is the operation's start on the engine clock (simulated or wall);
    ``occupancy`` is the compute batch the accelerator saw (1 for per-request
    prefill, the number of active slots for a decode step). ``compile=True``
    marks a wall-clocked call whose executable was cold (JIT compile included
    in ``duration_s``) — excluded from steady-state statistics.
    """

    t: float
    phase: str  # "prefill" | "decode"
    duration_s: float
    occupancy: int
    rid: int  # request id for prefill; -1 for batched decode steps
    tokens: int  # prompt tokens (prefill) / tokens emitted (decode)
    compile: bool = False


# timer(phase, run, tokens=..., occupancy=...) -> (run's result, seconds)
Timer = Callable[..., tuple[Any, float]]


def jit_programs(cfg: ModelConfig) -> tuple[Callable, Callable]:
    """The engine's jitted ``(decode, prefill)`` programs.

    ``lm.decode_step`` returns (logits (slots, 1, V), new caches) and
    ``lm.prefill`` (logits (1, 1, V) of the last position, caches); each
    program keeps only the greedy ids: (slots,) and (1,) int32. The decode
    donates its caches (argument 3), so the step writes its K/V rows and
    states into the same buffers and allocates no second cache.
    """

    def engine_decode(p, tok, pos, caches):
        logits, caches = lm.decode_step(p, cfg, tok, pos, caches)
        ids = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return ids, caches, lm.expert_tokens(cfg, caches)

    def engine_prefill(p, tokens):
        logits, caches = lm.prefill(p, cfg, tokens)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), caches

    return jax.jit(engine_decode, donate_argnums=(3,)), jax.jit(engine_prefill)


def _stamp(now: float | None, charged: float = 0.0) -> float:
    """An event's time: the wall clock as it happens when the engine owns
    the clock (``now is None``), else the caller's ``now`` plus the service
    charged before the event."""
    return time.time() if now is None else now + charged


class Engine:
    """Single-model serving engine over the lm prefill/decode steps.

    ``timer`` (optional) replaces the wall clock for service durations: the
    engine still executes the real jitted ops, but charges each one the
    seconds the timer returns. ``repro.measure.harness.SimulatedTimer`` uses
    this for seeded, replayable profiling runs.
    """

    def __init__(self, cfg: ModelConfig, params: Any, sc: ServeConfig,
                 timer: Timer | None = None, tracer=None):
        self.cfg = cfg
        self.sc = sc
        self.params = params
        self.timer = timer
        # repro.obs request tracing (duck-typed; serving never imports obs).
        # _trace is the single predicate every hot-path emission site checks:
        # tracer=None and Tracer(enabled=False) cost exactly one bool test.
        self.tracer = tracer
        self._trace = tracer is not None and getattr(tracer, "enabled", True)

        self._decode, self._prefill = jit_programs(cfg)
        # slot state
        B, S = sc.slots, sc.max_seq
        self.caches = self._zero_caches(B, S)
        self.expert_tokens = lm.expert_tokens(cfg, self.caches)
        self.positions = np.zeros(B, np.int32)  # next position per slot
        self.active: list[Request | None] = [None] * B
        self.remaining = np.zeros(B, np.int32)
        self.queue: list[Request] = []
        self.completed: list[Request] = []
        self.service_log: list[ServiceEvent] = []
        # executables already compiled (prefill by prompt length; one decode
        # shape total) — cold wall-clocked calls are flagged in the log
        self._warm_prefill: set[int] = set()
        self._warm_decode = False

    def _zero_caches(self, batch: int, seq: int):
        from repro.models.params import init_params
        from repro.models.lm import cache_template

        tpl = cache_template(self.cfg, batch, seq, enc_len=seq if self.cfg.is_encdec else 0)
        return init_params(tpl, jax.random.PRNGKey(0), jnp.dtype(self.cfg.dtype))

    # ------------------------------------------------------------------
    def _timed(self, phase: str, run: Callable[[], Any], *,
               tokens: int, occupancy: int) -> tuple[Any, float]:
        """Run ``run`` and return (result, service seconds). Wall mode blocks
        on the result BEFORE the closing stamp (async dispatch otherwise makes
        the measurement a dispatch time, not a service time)."""
        if self.timer is not None:
            out, dt = self.timer(phase, run, tokens=tokens, occupancy=occupancy)
            return out, float(dt)
        t0 = time.perf_counter()
        with _span("engine.launch"):
            out = run()
        with _span("engine.wait"):
            jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    def warmup(self, prompt_lens: Iterable[int] = (), *, decode: bool = True) -> None:
        """Compile the jitted executables outside the measured path.

        JAX specialises ``prefill`` per prompt length, so pass every length
        the workload can draw. Compile-time is the dominant first-call cost
        (seconds vs millisecond service times) and would otherwise pollute
        any measured mean. Runs on scratch inputs; engine state is untouched
        (the decode donates its caches, so it runs on scratch zero caches).
        """
        for L in sorted({int(x) for x in prompt_lens}):
            if L in self._warm_prefill:
                continue
            jax.block_until_ready(
                self._prefill(self.params, jnp.zeros((1, L), jnp.int32)))
            self._warm_prefill.add(L)
        if decode and not self._warm_decode:
            tok = jnp.zeros((self.sc.slots, 1), jnp.int32)
            scratch = self._zero_caches(self.sc.slots, self.sc.max_seq)
            jax.block_until_ready(self._decode(self.params, tok, jnp.int32(0), scratch))
            self._warm_decode = True

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self, now: float | None) -> float | None:
        """Admit queued requests into free slots; returns the advanced clock
        (each prefill occupies the accelerator, so admissions serialise).
        ``now`` is ``None`` when the engine stamps events with the wall clock
        as they happen (see :meth:`tick`)."""
        for slot in range(self.sc.slots):
            if self.active[slot] is not None or not self.queue:
                continue
            with _span("engine.admit"):
                dt = self._admit_one(self.queue.pop(0), slot, now)
            if now is not None:
                now += dt
        return now

    def _admit_one(self, req: Request, slot: int, now: float | None) -> float:
        """Prefill ``req`` into ``slot``; returns its service seconds."""
        L = len(req.prompt)
        cold = self.timer is None and L not in self._warm_prefill

        def run():
            prompt = jnp.asarray(req.prompt[None], jnp.int32)
            first, caches = self._prefill(self.params, prompt)
            # write this request's cache into the slot (batch index
            # `slot`) inside the timed region — the copy is device work
            # the request's service genuinely includes
            new = jax.tree.map(
                lambda full, one: self._write_slot(full, one, slot, L),
                self.caches,
                caches,
            )
            return first, new

        start = _stamp(now)
        req.t_admit = start
        (first, new_caches), dt = self._timed(
            "prefill", run, tokens=L, occupancy=1)
        self.caches = new_caches
        self._warm_prefill.add(L)
        with _span("engine.sample"):
            next_tok = int(np.asarray(first)[0])
        self.positions[slot] = L
        self.remaining[slot] = req.max_new_tokens - 1
        req.tokens_out.append(next_tok)
        req.t_first_token = _stamp(now, dt)
        self.service_log.append(
            ServiceEvent(start, "prefill", dt, 1, req.rid, L, cold))
        if self._trace:
            track = f"req[{req.rid}]"
            self.tracer.span(
                t=req.arrival_s, dur=max(0.0, start - req.arrival_s),
                name="queue", cat="queue", track=track, rid=req.rid)
            self.tracer.span(
                t=start, dur=dt, name="prefill", cat="prefill", track=track,
                rid=req.rid, tokens=L, compile=cold)
        if self.remaining[slot] <= 0:
            # single-token request: prefill IS the whole service
            req.t_done = req.t_first_token
            self.completed.append(req)
            if self._trace:
                self.tracer.instant(
                    t=req.t_done, name="respond", cat="respond",
                    track=f"req[{req.rid}]", rid=req.rid,
                    tokens=len(req.tokens_out), latency_s=req.latency_s)
        else:
            self.active[slot] = req
        return dt

    @staticmethod
    def _write_slot(full, one, slot: int, prompt_len: int):
        """Place a single-request cache (leading batch 1) into slot `slot`.

        Sequence-bearing leaves (dim2 = cache capacity) copy the prompt
        prefix; state leaves (mamba/xLSTM) copy wholesale."""
        if full.ndim >= 3 and one.ndim == full.ndim and full.shape[2] != one.shape[2]:
            # kv-style cache: (n_sb, B, S_cap, ...) vs prefill (n_sb, 1, S_p, ...)
            s = min(one.shape[2], full.shape[2])
            return full.at[:, slot : slot + 1, :s].set(one[:, :, :s].astype(full.dtype))
        return full.at[:, slot : slot + 1].set(one.astype(full.dtype))

    # ------------------------------------------------------------------
    def tick(self, now: float | None = None) -> int:
        """Admit + one decode step for all active slots. Returns #active.

        ``now`` is the caller's engine clock at tick start; events then land
        at ``now`` plus the service charged before them, so request
        timestamps are event times, not tick-start times. When ``now`` is
        omitted and no ``timer`` is set, the engine stamps each event
        (service starts, first tokens, completions, ``repro.obs`` spans)
        with ``time.time()`` at the moment it happens: the profiler's host
        clock, host time between calls included.
        """
        if now is None and self.timer is not None:
            now = time.time()
        now = self._admit(now)
        if not any(r is not None for r in self.active):
            return 0
        cold = self.timer is None and not self._warm_decode

        with _span("engine.decode"):
            last = np.zeros((self.sc.slots, 1), np.int32)
            for slot, req in enumerate(self.active):
                if req is not None:
                    last[slot, 0] = req.tokens_out[-1]
            pos = int(max(self.positions[s] for s, r in enumerate(self.active)
                          if r is not None))
            n_active = sum(r is not None for r in self.active)

            def run():
                return self._decode(self.params, jnp.asarray(last), jnp.int32(pos),
                                    self.caches)

            start = _stamp(now)
            (next_ids, new_caches, self.expert_tokens), dt = self._timed(
                "decode", run, tokens=n_active, occupancy=n_active)
            self.caches = new_caches
            self._warm_decode = True
            with _span("engine.sample"):
                nxt = np.asarray(next_ids).tolist()
            end = _stamp(now, dt)
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                req.tokens_out.append(nxt[slot])
                self.positions[slot] += 1
                self.remaining[slot] -= 1
                if self.remaining[slot] <= 0 or self.positions[slot] >= self.sc.max_seq - 1:
                    req.t_done = end
                    self.completed.append(req)
                    self.active[slot] = None
                    if self._trace:
                        self.tracer.instant(
                            t=req.t_done, name="respond", cat="respond",
                            track=f"req[{req.rid}]", rid=req.rid,
                            tokens=len(req.tokens_out), latency_s=req.latency_s)
        self.service_log.append(
            ServiceEvent(start, "decode", dt, n_active, -1, n_active, cold))
        if self._trace:
            self.tracer.span(
                t=start, dur=dt, name="decode", cat="decode", track="engine",
                occupancy=n_active, compile=cold)
        return n_active

    def drain(self) -> None:
        while self.queue or any(r is not None for r in self.active):
            self.tick()

    # ------------------------------------------------------------------
    def observed_service_stats(self) -> tuple[float, float]:
        """(mean, var) of measured per-op service times — the paper's
        profiled service-time input (§4.2). Cold (compile-bearing) calls are
        excluded; they measure the XLA compiler, not the accelerator."""
        durs = [ev.duration_s for ev in self.service_log if not ev.compile]
        if not durs:
            return 0.0, 0.0
        arr = np.array(durs)
        return float(arr.mean()), float(arr.var())
