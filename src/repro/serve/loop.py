"""Batched serving loop: slot-based continuous batching.

A fixed decode batch of ``slots``; finished sequences free their slot and
the next queued request is prefilled into it.  Greedy sampling (argmax);
the decode step is a single compiled function over the whole slot batch,
caches donated in place — the production shape of vLLM-style serving,
scaled to run on this host with reduced configs.

Pass ``pim_offload=DecodeOffload(cfg, ...)`` to mirror every decode
step's matmuls onto a resident-weight PIM runtime (balanced placement,
weights uploaded once): the sidecar accumulates a per-step PIM-vs-host
roofline without touching the serving numerics — see
:mod:`repro.serve.offload`.  With ``DecodeOffload(cfg, numeric=True)``
(small configs) the sidecar additionally executes each step's matmul set
on the per-channel engines and cross-checks every output — lm_head
logits included — against an XLA reference within FP16 accumulation
tolerance, while charging the same ledgers as the analytic sidecar.

Graceful degradation (:mod:`repro.faults`): ``Server(faults=...)``
accepts a :class:`~repro.faults.plan.FaultPlan` (or DSL string) and
consumes its :class:`~repro.faults.plan.ServeFault` entries — the
request decoding in the named slot at the named iteration is knocked
out and requeued with per-request exponential backoff
(``retry_backoff_steps`` doubling per retry, capped), failing
permanently after ``max_retries``.  ``step_deadline_s`` counts
over-deadline serving iterations; ``max_queue`` turns :meth:`submit`
into admission control that sheds load (:class:`AdmissionError`) when
the queue exceeds the cap *scaled by surviving PIM capacity* — a
half-dead offload cluster halves what the server accepts.

Virtual time (:mod:`repro.serve.traffic`): request timestamps
(``submitted_at`` / ``admitted_at`` / ``first_token_at`` /
``finished_at``) are stamped from a :class:`~repro.serve.traffic.
SimClock` by default — admission advances it by the host-prefill
roofline, each decode iteration by the offload's ``StepRecord.pim_s``
(or the host decode roofline without a sidecar) — so
:meth:`Server.latency_summary` percentiles are deterministic and
machine-independent.  ``Server(wall=True)`` restores wall-clock
stamping for live measurement.

:class:`TrafficServer` is the load-study twin: it drives a
:class:`~repro.serve.offload.DecodeOffload` under a stochastic arrival
:class:`~repro.serve.traffic.Trace` entirely in virtual time, with
prefill/decode **disaggregation** — prefill batches priced on the host
XLA roofline while decode steps stay PIM-resident, the prefilled KV
handed off across the shared :class:`~repro.runtime.cluster.
HostLinkLedger` as clocked ``"prefill"`` busy windows (decode's
activations as ``"acts"``), chunked-prefill interleaving, admission
control, slot autoscaling policies, and TTFT/TPOT/goodput SLO
accounting.  ``disaggregate=False`` is the colocated baseline: the
same chunks serialize on the decode lane.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.isa import PIM_FREQ_HZ
from repro.models import model as lm
from repro.models.attention import decode_kv_positions
from repro.obs.metrics import Histogram
from repro.obs.spans import span
from repro.runtime.cluster import HostLinkLedger
from repro.serve.offload import DecodeOffload
from repro.serve.traffic import (SLO, HostCostModel, SimClock, Trace,
                                 TraceRequest, WallClock)


class AdmissionError(RuntimeError):
    """Admission control shed this request (queue over the surviving-
    capacity-scaled cap).  Callers should back off and resubmit."""


# eq=False: the generated __eq__ would compare the ndarray prompt field
# and raise "truth value is ambiguous" on membership tests (req in
# queue); identity is the right request equality anyway — uid is the
# stable name across retries
@dataclasses.dataclass(eq=False)
class Request:
    uid: int
    prompt: np.ndarray              # (Tp,) int32
    max_new: int = 16
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    submitted_at: float = 0.0
    admitted_at: float = 0.0        # left the queue (prefill started)
    first_token_at: float = 0.0     # prefill produced the first token
    finished_at: float = 0.0
    retries: int = 0                # fault knock-outs survived so far
    not_before: int = 0             # earliest serving iteration to re-admit


class Server:
    def __init__(self, cfg: ArchConfig, params, slots: int = 4,
                 cache_len: int = 128, eos_id: Optional[int] = None,
                 pim_offload: Optional[DecodeOffload] = None,
                 metrics=None, faults=None,
                 step_deadline_s: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 retry_backoff_steps: int = 2,
                 retry_backoff_cap: int = 16,
                 max_retries: int = 2,
                 wall: bool = False, clock=None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.eos_id = eos_id
        self.pim_offload = pim_offload
        # virtual-time stamping (deterministic latency percentiles) by
        # default; wall=True keeps the old time.time() stamps for live
        # measurement, and an explicit clock= shares one SimClock
        # across several servers
        self.clock = clock if clock is not None \
            else (WallClock() if wall else SimClock())
        self.cost = HostCostModel(cfg)
        # repro.obs registry for serve.* latency metrics (TTFT/TPOT per
        # request, step wall time); pass the same registry to the
        # offload sidecar to merge runtime streams into one snapshot
        self.metrics = metrics
        self.active: List[Optional[Request]] = [None] * slots
        self.pos = np.zeros((slots,), np.int32)
        self.caches = lm.make_caches(cfg, slots, cache_len)
        self.queue: List[Request] = []
        self.completed: List[Request] = []
        # -- graceful degradation state (all zero / empty without faults)
        self.step_deadline_s = step_deadline_s
        self.max_queue = max_queue
        self.retry_backoff_steps = retry_backoff_steps
        self.retry_backoff_cap = retry_backoff_cap
        self.max_retries = max_retries
        self.failed_requests: List[Request] = []
        self.shed = 0                   # submissions refused at admission
        self.deadline_misses = 0        # serving iterations over deadline
        self.retries_total = 0          # fault knock-outs requeued
        self.undrained = 0              # left pending by run_until_drained
        self._iter = 0                  # serving-iteration counter (1-based)
        self._serve_faults: List = []
        if faults is not None:
            from repro.faults.plan import as_plan
            self._serve_faults = sorted(
                as_plan(faults).serve_faults,
                key=lambda f: (f.at_iter, f.slot))

        # named functions, so that the device trace shows the programs as
        # jit_server_decode(<hash>) and jit_server_prefill(<hash>)
        def server_decode(p, t, ps, c):
            return lm.decode_step(p, t, ps, c, cfg)

        def server_prefill(p, toks):
            return lm.prefill(p, {"tokens": toks}, cfg, cache_len=cache_len)

        self._decode = jax.jit(server_decode, donate_argnums=(3,))
        self._prefill_one = jax.jit(server_prefill)

    def _check_prompt(self, req: Request) -> None:
        """A prompt must leave at least one cache position for decode —
        longer ones would silently corrupt the slot cache at prefill."""
        if len(req.prompt) >= self.cache_len:
            raise ValueError(
                f"prompt of request uid={req.uid} has {len(req.prompt)} "
                f"tokens but cache_len={self.cache_len} leaves no room "
                f"to decode — truncate the prompt or grow cache_len")

    @property
    def _kv(self):
        """The offload sidecar's KV manager when KV-resident attention
        is on (``DecodeOffload(kv_offload=True)``), else None — every
        hook below is a no-op without it."""
        off = self.pim_offload
        return off.kv if off is not None else None

    @property
    def surviving_fraction(self) -> float:
        """Fraction of PIM decode capacity still alive (1.0 without an
        offload sidecar or without faults) — scales the admission cap."""
        off = self.pim_offload
        return off.surviving_fraction if off is not None else 1.0

    def submit(self, req: Request):
        self._check_prompt(req)
        if self.max_queue is not None:
            cap = max(1, int(self.max_queue * self.surviving_fraction))
            if len(self.queue) >= cap:
                self.shed += 1
                if self.metrics is not None:
                    self.metrics.counter(
                        "serve.shed", unit="requests",
                        help="submissions shed by admission control").inc()
                raise AdmissionError(
                    f"queue at {len(self.queue)} >= cap {cap} "
                    f"(max_queue={self.max_queue}, surviving="
                    f"{self.surviving_fraction:.2f}); shedding "
                    f"request uid={req.uid}")
        req.submitted_at = self.clock.now
        self.queue.append(req)

    def _apply_serve_faults(self):
        """Fire ServeFaults due this iteration: knock out the slot's
        request and requeue it with exponential backoff (or fail it
        permanently past max_retries)."""
        due = [f for f in self._serve_faults if f.at_iter == self._iter]
        if not due:
            return
        self._serve_faults = [f for f in self._serve_faults
                              if f.at_iter != self._iter]
        for f in due:
            if f.slot >= self.slots or self.active[f.slot] is None:
                continue
            req = self.active[f.slot]
            self.active[f.slot] = None
            # the slot's cache is considered poisoned: restart the
            # request from its prompt (prefill re-runs on re-admission);
            # its PIM-resident KV drops with it
            if self._kv is not None:
                self.pim_offload.kv_release(req.uid)
            req.out_tokens = []
            req.first_token_at = 0.0
            req.retries += 1
            if req.retries > self.max_retries:
                req.done = True
                req.finished_at = self.clock.now
                self.failed_requests.append(req)
                if self.metrics is not None:
                    self.metrics.counter(
                        "serve.failed", unit="requests",
                        help="requests failed past max_retries").inc()
                continue
            backoff = min(
                self.retry_backoff_steps * 2 ** (req.retries - 1),
                self.retry_backoff_cap)
            req.not_before = self._iter + backoff
            self.queue.append(req)
            self.retries_total += 1
            if self.metrics is not None:
                self.metrics.counter(
                    "serve.retries", unit="requests",
                    help="fault knock-outs requeued with backoff").inc()

    def _admit(self):
        """Prefill queued requests into free slots (FIFO among requests
        whose retry backoff has elapsed)."""
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                idx = next((j for j, r in enumerate(self.queue)
                            if r.not_before <= self._iter), None)
                if idx is None:
                    return           # everything queued is backing off
                req = self.queue.pop(idx)
                with span("server.admit", uid=req.uid,
                          prompt_len=len(req.prompt)):
                    self._admit_one(i, req)

    def _admit_one(self, i: int, req: Request):
        """Prefill ``req`` and splice its cache into slot ``i``."""
        self._check_prompt(req)
        req.admitted_at = self.clock.now
        if self.metrics is not None:
            m = self.metrics
            m.histogram(
                "serve.queue_delay_s", unit="s",
                help="queue wait (submit -> prefill start)"
            ).record(req.admitted_at - req.submitted_at)
            m.counter("serve.admissions", unit="requests",
                      help="requests prefilled into a slot").inc()
            m.counter("serve.prefill_tokens", unit="tokens",
                      help="prompt tokens prefilled").inc(len(req.prompt))
        with span("server.prefill"):
            logits, fresh = self._prefill_one(
                self.params, jnp.asarray(req.prompt[None, :]))
        # splice slot i's cache from the single-seq prefill cache
        with span("server.splice"):
            self.caches = jax.tree.map(
                lambda full, one: _splice(full, one, i, self.cfg),
                self.caches, fresh)
        # the host waits here for the prefill and the splice
        with span("server.first_token"):
            tok = int(jnp.argmax(logits[0]))
        req.out_tokens.append(tok)
        # the prefill's argmax IS the request's first token:
        # TTFT closes here, before any decode step runs.  The
        # virtual clock charges the host-prefill roofline (a
        # WallClock ignores the advance and reads real time)
        self.clock.advance(self.cost.prefill_s(len(req.prompt)))
        req.first_token_at = self.clock.now
        if self.metrics is not None:
            self.metrics.histogram(
                "serve.ttft_s", unit="s",
                help="time to first token (submit -> prefill "
                     "argmax)").record(
                req.first_token_at - req.submitted_at)
        self.active[i] = req
        self.pos[i] = len(req.prompt)
        # host prefill produced the prompt's KV: ship it onto
        # the sidecar's PIM pages once, decode grows it in place
        if self._kv is not None:
            self.pim_offload.kv_prefill(req.uid, len(req.prompt))

    def _retire(self, i: int):
        req = self.active[i]
        req.done = True
        req.finished_at = self.clock.now
        self.completed.append(req)
        self.active[i] = None
        if self._kv is not None:
            self.pim_offload.kv_release(req.uid)
        if self.metrics is not None:
            m = self.metrics
            m.counter("serve.requests", unit="requests",
                      help="requests completed").inc()
            m.counter("serve.tokens", unit="tokens",
                      help="tokens generated (first token included)").inc(
                len(req.out_tokens))
            if len(req.out_tokens) >= 2:      # TPOT needs a decode tail
                m.histogram(
                    "serve.tpot_s", unit="s",
                    help="time per output token after the first").record(
                    (req.finished_at - req.first_token_at)
                    / (len(req.out_tokens) - 1))

    def _decode_positions(self, live: List[int]) -> np.ndarray:
        """Each slot's position for the decode program: ``pos`` for the
        ``live`` slots, 0 for free ones, whose token and cache row nothing
        reads (the next admission splices the whole row)."""
        pos = np.zeros_like(self.pos)
        pos[live] = self.pos[live]
        return pos

    def _kv_positions(self, live: List[int],
                      platform: str) -> Tuple[int, int]:
        """Cache positions of one decode step on ``platform``: (live,
        scanned), as ``attention.decode_kv_positions`` counts them at the
        positions the decode program gets."""
        return decode_kv_positions(self.cfg, self.cache_len,
                                   self._decode_positions(live), live,
                                   platform)

    def step(self):
        """One serving iteration: fire serve faults, admit, batched
        decode, retire; count the iteration against the step deadline."""
        with span("server.step", iter=self._iter + 1):
            return self._step()

    def _step(self):
        track_wall = self.metrics is not None \
            or self.step_deadline_s is not None
        t0 = time.time() if track_wall else 0.0
        self._iter += 1
        self._apply_serve_faults()
        self._admit()
        live = [i for i in range(self.slots) if self.active[i] is not None]
        if not live:
            # backing-off requests still count as pending work: report
            # True so run_until_drained keeps iterating toward their
            # re-admission instead of spinning the caller's loop exit
            return bool(self.queue)
        toks = np.zeros((self.slots, 1), np.int32)
        for i in live:
            toks[i, 0] = self.active[i].out_tokens[-1]
        kv_live, kv_scanned = self._kv_positions(live, jax.default_backend())
        with span("server.decode", live=len(live), kv_live=kv_live,
                  kv_scanned=kv_scanned):
            logits, self.caches = self._decode(
                self.params, jnp.asarray(toks),
                jnp.asarray(self._decode_positions(live)), self.caches)
        if self.metrics is not None:
            m = self.metrics
            m.counter("serve.decode_steps", unit="steps",
                      help="batched decode steps").inc()
            m.counter("serve.kv_live_positions", unit="positions",
                      help="cache positions live slots had written, "
                           "summed over decode steps").inc(kv_live)
            m.counter("serve.kv_scanned_positions", unit="positions",
                      help="cache positions decode attended over, "
                           "summed over decode steps").inc(kv_scanned)
        rec = None
        if self.pim_offload is not None:
            rec = self.pim_offload.step(
                len(live),
                request_ids=[self.active[i].uid for i in live])
        # the decode iteration's virtual duration: the PIM step's clocked
        # makespan when a sidecar ran it, else the host decode roofline
        self.clock.advance(rec.pim_s if rec is not None
                           else self.cost.decode_step_s(len(live)))
        # the host waits here for the decode
        with span("server.sample"):
            nxt = np.asarray(jnp.argmax(logits, -1))
        for i in live:
            req = self.active[i]
            req.out_tokens.append(int(nxt[i]))
            self.pos[i] += 1
            hit_eos = self.eos_id is not None and int(nxt[i]) == self.eos_id
            if (len(req.out_tokens) >= req.max_new or hit_eos
                    or int(self.pos[i]) >= self.cache_len - 1):
                self._retire(i)
        if track_wall:
            wall = time.time() - t0
            if self.step_deadline_s is not None \
                    and wall > self.step_deadline_s:
                self.deadline_misses += 1
                if self.metrics is not None:
                    self.metrics.counter(
                        "serve.deadline_misses", unit="steps",
                        help="serving iterations over step_deadline_s"
                    ).inc()
            if self.metrics is not None:
                self.metrics.histogram(
                    "serve.step_s", unit="s",
                    help="serving-iteration wall time").record(wall)
        return True

    def run_until_drained(self, max_iters: int = 10_000,
                          on_undrained: str = "raise"):
        """Step until every request completes (or fails permanently).

        If ``max_iters`` exhausts with requests still queued or active,
        the default ``on_undrained="raise"`` raises ``RuntimeError`` —
        a hung serving loop must not masquerade as success.
        ``on_undrained="warn"`` downgrades to a ``RuntimeWarning`` and
        returns the partial results; either way the pending count is
        recorded in :attr:`undrained` / :meth:`latency_summary`.
        """
        if on_undrained not in ("raise", "warn"):
            raise ValueError(
                f"on_undrained must be 'raise' or 'warn', "
                f"got {on_undrained!r}")
        it = 0
        while (self.queue or any(a is not None for a in self.active)) \
                and it < max_iters:
            self.step()
            it += 1
        self.undrained = len(self.queue) \
            + sum(a is not None for a in self.active)
        if self.undrained:
            msg = (f"run_until_drained exhausted max_iters={max_iters} "
                   f"with {self.undrained} request(s) still "
                   f"queued/active ({len(self.completed)} completed)")
            if on_undrained == "raise":
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return self.completed

    def latency_summary(self) -> Dict:
        """TTFT/TPOT/queue-delay percentile summary over completed
        requests (p50/p90/p99/p99.9 — virtual seconds by default, so
        identical across runs and machines; wall seconds with
        ``wall=True``).

        Computed from the request timestamps directly, so it works with
        or without an attached metrics registry.  TTFT is submit ->
        prefill argmax; TPOT divides the decode tail by the tokens after
        the first (requests with a single token report no TPOT sample);
        queue delay is submit -> prefill start.
        """
        ttft = Histogram("serve.ttft_s", unit="s")
        tpot = Histogram("serve.tpot_s", unit="s")
        qdel = Histogram("serve.queue_delay_s", unit="s")
        for req in self.completed:
            if req.first_token_at:
                ttft.record(req.first_token_at - req.submitted_at)
                qdel.record(req.admitted_at - req.submitted_at)
                if req.finished_at and len(req.out_tokens) >= 2:
                    tpot.record((req.finished_at - req.first_token_at)
                                / (len(req.out_tokens) - 1))
        return {
            "requests": len(self.completed),
            "tokens": sum(len(r.out_tokens) for r in self.completed),
            "ttft_s": _pct_summary(ttft),
            "tpot_s": _pct_summary(tpot),
            "queue_delay_s": _pct_summary(qdel),
            # degradation accounting (all zero on a fault-free run)
            "undrained": self.undrained,
            "failed": len(self.failed_requests),
            "shed": self.shed,
            "deadline_misses": self.deadline_misses,
            "retries": self.retries_total,
        }


def _pct_summary(h: Histogram) -> Dict:
    """``Histogram.summary()`` plus the serving tail the SLO studies
    read (p99.9)."""
    s = h.summary()
    s["p99.9"] = h.percentile(99.9)
    return s


# ---------------------------------------------------------------------------
# Traffic-driven virtual-time serving: prefill/decode disaggregation
# ---------------------------------------------------------------------------


class _BusyLane:
    """One contended resource as a set of reserved busy intervals.

    A scalar "free after the last reservation" clock is wrong for the
    host link: prefill KV handoffs are reserved *into the future* (each
    chunk ships only after its compute lands), and a decode step's tiny
    activation window arriving *now* must be allowed to use the idle
    gap in front of them instead of queueing behind the whole prefill
    pipeline.  ``reserve`` places a duration at the earliest gap at or
    after ``ready`` — first-fit, which is exactly link arbitration with
    no preemption.
    """

    def __init__(self):
        self._busy: List[Tuple[float, float]] = []   # sorted, disjoint

    def prune(self, now: float) -> None:
        """Drop intervals that ended before ``now`` — reservations are
        never placed in the past, so they can no longer collide."""
        self._busy = [iv for iv in self._busy if iv[1] > now]

    def reserve(self, ready: float, dur: float) -> Tuple[float, float]:
        """Occupy the lane for ``dur`` seconds starting at the earliest
        instant >= ``ready`` with no overlap; returns ``(start, end)``."""
        if dur <= 0:
            return ready, ready
        t = ready
        at = 0
        for i, (s, e) in enumerate(self._busy):
            if s - t >= dur:        # fits in the gap before interval i
                at = i
                break
            t = max(t, e)
            at = i + 1
        self._busy.insert(at, (t, t + dur))
        return t, t + dur


class TrafficServer:
    """Virtual-time load simulator: a decode-resident PIM server under a
    stochastic arrival :class:`~repro.serve.traffic.Trace`.

    Where :class:`Server` runs the actual XLA model on reduced configs,
    ``TrafficServer`` *clocks* serving at paper scale: every duration
    comes from the analytic cost substrate (the offload's per-step PIM
    makespan, the :class:`~repro.serve.traffic.HostCostModel` prefill
    roofline, and :func:`~repro.runtime.cluster.host_link_cycles` for
    everything crossing the host link), so hundreds-to-thousands of
    requests simulate in milliseconds and every latency percentile is
    deterministic and machine-independent.

    Three resources contend, each a monotonic "free at" lane in virtual
    seconds:

    * the **host XLA device** (prefill chunks — compute the prompt's KV
      and first token);
    * the **shared host link** (prefilled KV handed off to PIM pages as
      ``"prefill"`` windows, per-decode-step activations as ``"acts"``
      windows — charged on the offload cluster's own
      :class:`~repro.runtime.cluster.HostLinkLedger` when it has one,
      so they land in its trace);
    * the **PIM decode pipeline** (batched decode steps, priced by the
      offload's :class:`~repro.serve.offload.StepRecord`).

    ``disaggregate=True`` (default) lets the host lane prefill ahead
    while PIM decodes — the two phases contend only on the link.
    ``disaggregate=False`` is the **colocated** baseline: prefill
    chunks serialize on the decode lane (one chunk per live prefilling
    request per serving iteration — classic chunked-prefill continuous
    batching), stalling decode exactly as a single-pipeline server
    does.  ``chunk_tokens`` bounds that stall in both modes.

    Admission control (``max_queue``, arrivals shed beyond it), slot
    autoscaling (``autoscale=`` one of the :mod:`repro.serve.traffic`
    policies), and an :class:`~repro.serve.traffic.SLO` for
    goodput/attainment accounting complete the load study.  With
    ``kv_offload`` sidecars the KV lifecycle (``kv_prefill`` at
    handoff, ``kv_release`` at retire) runs for real; analytic decode
    step costs are probed once per distinct batch size
    (``cache_steps``; exact per-iteration stepping is forced when the
    step cost is stateful, i.e. the KV cache grows).

    Strictly additive: constructing one and running an empty trace
    leaves the offload's ledgers ``==``-equal and its trace
    byte-identical — the traffic layer charges nothing until traffic
    exists.
    """

    def __init__(self, offload: DecodeOffload, *, slots: int = 4,
                 disaggregate: bool = True, chunk_tokens: int = 256,
                 max_queue: Optional[int] = None, autoscale=None,
                 slo: Optional[SLO] = None, metrics=None, clock=None,
                 cost: Optional[HostCostModel] = None,
                 cache_steps: Optional[bool] = None,
                 step_costs: Optional[Dict[int, Tuple[float, int]]] = None):
        if offload.async_mode:
            raise ValueError(
                "TrafficServer clocks its own virtual lanes; drive it "
                "with a serialized (async_mode=False) offload")
        self.off = offload
        self.cfg = offload.cfg
        self.cost = cost if cost is not None else HostCostModel(offload.cfg)
        self.slots = slots
        self.disaggregate = disaggregate
        self.chunk_tokens = max(1, chunk_tokens)
        self.max_queue = max_queue
        self.autoscale = autoscale
        self.slo = slo
        self.metrics = metrics
        self.clock = clock if clock is not None else SimClock()
        # analytic StepRecords are pure functions of the batch size, so
        # one probe step per distinct batch prices every iteration; a
        # growing KV cache makes the cost stateful -> step exactly
        self.cache_steps = (offload.kv is None) if cache_steps is None \
            else cache_steps
        self._step_costs: Dict[int, Tuple[float, int]] = \
            step_costs if step_costs is not None else {}
        # the shared host link: the offload cluster's ledger when it has
        # one (multi-stack — handoff windows then land in its trace),
        # else a sim-owned ledger with identical accounting
        stack = offload.rt.stack
        self.link: HostLinkLedger = getattr(stack, "link", None) \
            or HostLinkLedger()
        # -- virtual lanes: host and PIM are monotonic "free at" times
        # (their work is always scheduled at the current sim time); the
        # link takes future reservations, so it books busy intervals --
        self._host_free_s = 0.0         # host XLA prefill lane
        self._pim_free_s = 0.0          # PIM decode lane
        self._link_lane = _BusyLane()
        if self.link.tl_free > 0:       # respect prior async occupancy
            self._link_lane.reserve(0.0, self.link.tl_free / PIM_FREQ_HZ)
        # -- request state --
        self.queue: List[Request] = []
        self.active: List[Request] = []         # decode-resident
        self.prefilling: List[Request] = []     # colocated chunk progress
        self._tokens_left: Dict[int, int] = {}  # colocated prefill tokens
        self._ready_s: Dict[int, float] = {}    # uid -> KV handoff done
        self._last_tok_s: Dict[int, float] = {}
        self.completed: List[Request] = []
        self.shed_requests: List[TraceRequest] = []
        self.shed = 0
        self.iterations = 0
        self.slots_max_seen = slots
        self.max_decode_gap_s = 0.0     # worst inter-token decode stall
        self._recent_ttft: List[float] = []

    # -- resource lanes -------------------------------------------------------

    def _link_window(self, kind: str, nbytes: int,
                     ready_s: float) -> Tuple[float, float]:
        """Charge ``nbytes`` on the shared host link as one ``kind``
        event and occupy the link lane for its clocked duration starting
        no earlier than ``ready_s``; returns ``(start, end)`` seconds."""
        if nbytes <= 0:
            return ready_s, ready_s
        cyc = self.link.charge(kind, nbytes)
        self._link_lane.prune(self.clock.now)
        start, end = self._link_lane.reserve(ready_s, cyc / PIM_FREQ_HZ)
        self.link.tl_free = max(self.link.tl_free, end * PIM_FREQ_HZ)
        return start, end

    def _step_cost(self, batch: int,
                   rids: List[int]) -> Tuple[float, int]:
        """One decode iteration's ``(pim_s, h2d_bytes)`` over ``batch``
        slots — probed once per distinct batch when cacheable."""
        if not self.cache_steps:
            rec = self.off.step(batch, request_ids=rids)
            return rec.pim_s, rec.h2d_bytes
        if batch not in self._step_costs:
            rec = self.off.step(batch)
            self._step_costs[batch] = (rec.pim_s, rec.h2d_bytes)
        return self._step_costs[batch]

    @property
    def routing_observed(self):
        """The offload's observed per-layer expert-selection histogram
        (a :class:`~repro.serve.traffic.RoutingProfile`), or ``None``
        when the offload is not routed (``routing=None``)."""
        return self.off.observed

    # -- request lifecycle ----------------------------------------------------

    def _arrive(self, tr: TraceRequest) -> None:
        if self.max_queue is not None:
            cap = max(1, int(self.max_queue * self.off.surviving_fraction))
            if len(self.queue) >= cap:
                self.shed += 1
                self.shed_requests.append(tr)
                if self.metrics is not None:
                    self.metrics.counter(
                        "serve.shed", unit="requests",
                        help="arrivals shed by admission control").inc()
                return
        req = Request(uid=tr.uid,
                      prompt=np.zeros((tr.prompt_len,), np.int32),
                      max_new=tr.max_new, submitted_at=tr.at_s)
        self.queue.append(req)

    def _admit(self, req: Request) -> None:
        now = self.clock.now
        req.admitted_at = now
        if self.metrics is not None:
            self.metrics.histogram(
                "serve.queue_delay_s", unit="s",
                help="queue wait (arrival -> prefill start)").record(
                now - req.submitted_at)
        if not self.disaggregate:
            # colocated: chunks serialize on the decode lane, one per
            # serving iteration (see _prefill_chunk_colocated)
            self._tokens_left[req.uid] = len(req.prompt)
            self.prefilling.append(req)
            return
        # disaggregated: the whole chunked prefill schedules on the host
        # lane right now; each chunk's KV hands off over the link as
        # soon as its compute lands.  TTFT closes at the last chunk's
        # compute (the prefill argmax); decode may start once the last
        # handoff clears the link.
        tokens, t, ready = len(req.prompt), now, now
        while tokens > 0:
            ct = min(self.chunk_tokens, tokens)
            tokens -= ct
            cs = max(t, self._host_free_s)
            ce = cs + self.cost.prefill_s(ct)
            self._host_free_s = t = ce
            _, ready = self._link_window(
                "prefill", self.cost.kv_ship_bytes(ct), ce)
        req.first_token_at = t
        self._finish_prefill(req, ready)

    def _prefill_chunk_colocated(self, req: Request) -> None:
        """Advance one colocated request's prefill by one chunk *on the
        decode lane* — the serialization that makes colocated serving
        stall, and exactly what ``chunk_tokens`` bounds."""
        ct = min(self.chunk_tokens, self._tokens_left[req.uid])
        cs = max(self.clock.now, self._pim_free_s)
        ce = cs + self.cost.prefill_s(ct)
        self._pim_free_s = ce
        _, ready = self._link_window(
            "prefill", self.cost.kv_ship_bytes(ct), ce)
        self.clock.advance_to(ce)
        self._tokens_left[req.uid] -= ct
        if self._tokens_left[req.uid] <= 0:
            del self._tokens_left[req.uid]
            self.prefilling.remove(req)
            req.first_token_at = ce
            self._finish_prefill(req, ready)

    def _finish_prefill(self, req: Request, ready_s: float) -> None:
        req.out_tokens.append(0)        # the prefill argmax (token 1)
        ttft = req.first_token_at - req.submitted_at
        self._recent_ttft.append(ttft)
        if self.metrics is not None:
            self.metrics.histogram(
                "serve.ttft_s", unit="s",
                help="time to first token (arrival -> prefill argmax)"
            ).record(ttft)
        self._ready_s[req.uid] = ready_s
        self._last_tok_s[req.uid] = req.first_token_at
        self.active.append(req)
        if self.off.kv is not None:
            self.off.kv_prefill(req.uid, len(req.prompt))

    def _decode_step(self) -> bool:
        """One batched decode iteration over every handoff-complete
        active request; returns False when none is eligible yet."""
        now = self.clock.now
        eligible = [r for r in self.active if self._ready_s[r.uid] <= now]
        if not eligible:
            return False
        pim_s, h2d = self._step_cost(len(eligible),
                                     [r.uid for r in eligible])
        # the step's activations cross the link, then PIM computes
        _, le = self._link_window("acts", h2d, now)
        ds = max(le, self._pim_free_s)
        de = ds + pim_s
        self._pim_free_s = de
        self.clock.advance_to(de)
        for req in eligible:
            self.max_decode_gap_s = max(
                self.max_decode_gap_s, de - self._last_tok_s[req.uid])
            self._last_tok_s[req.uid] = de
            req.out_tokens.append(0)
            if len(req.out_tokens) >= req.max_new:
                self._retire(req, de)
        return True

    def _retire(self, req: Request, at_s: float) -> None:
        req.done = True
        req.finished_at = at_s
        self.active.remove(req)
        del self._ready_s[req.uid], self._last_tok_s[req.uid]
        self.completed.append(req)
        if self.off.kv is not None:
            self.off.kv_release(req.uid)
        if self.metrics is not None:
            m = self.metrics
            m.counter("serve.requests", unit="requests",
                      help="requests completed").inc()
            m.counter("serve.tokens", unit="tokens",
                      help="tokens generated (first token included)").inc(
                len(req.out_tokens))
            if len(req.out_tokens) >= 2:
                m.histogram(
                    "serve.tpot_s", unit="s",
                    help="time per output token after the first").record(
                    (req.finished_at - req.first_token_at)
                    / (len(req.out_tokens) - 1))

    # -- the serving loop -----------------------------------------------------

    def run(self, trace: Trace, max_iters: int = 2_000_000
            ) -> List[Request]:
        """Replay ``trace`` to completion; returns the completed
        requests (``latency_summary`` aggregates them)."""
        pending = list(trace)
        pi, n = 0, len(pending)
        while pi < n or self.queue or self.active or self.prefilling:
            self.iterations += 1
            if self.iterations > max_iters:
                raise RuntimeError(
                    f"traffic simulation exceeded max_iters={max_iters} "
                    f"({len(self.completed)} completed, "
                    f"{len(self.queue)} queued)")
            now = self.clock.now
            while pi < n and pending[pi].at_s <= now:
                self._arrive(pending[pi])
                pi += 1
            if self.autoscale is not None:
                live = len(self.active) + len(self.prefilling)
                self.slots = max(1, self.autoscale.target(
                    queue_len=len(self.queue), slots=self.slots,
                    live=live, recent_ttft=self._recent_ttft))
                self.slots_max_seen = max(self.slots_max_seen, self.slots)
            if self.metrics is not None:
                self.metrics.gauge(
                    "serve.queue_depth", unit="requests",
                    help="queued requests at iteration start").set(
                    len(self.queue))
                self.metrics.gauge(
                    "serve.slots", unit="slots",
                    help="decode slot capacity (autoscaled)").set(
                    self.slots)
            while self.queue and \
                    len(self.active) + len(self.prefilling) < self.slots:
                self._admit(self.queue.pop(0))
            for req in list(self.prefilling):
                self._prefill_chunk_colocated(req)
            stepped = self._decode_step() if self.active else False
            if stepped or self.prefilling or self.clock.now > now:
                continue
            # idle: jump to the next event (an arrival, or a pending
            # KV handoff completing)
            horizon = []
            if self.active:
                horizon.append(min(self._ready_s[r.uid]
                                   for r in self.active))
            if pi < n:
                horizon.append(pending[pi].at_s)
            if not horizon:
                raise RuntimeError(
                    "traffic simulation stalled with work pending — "
                    "this is a scheduler bug")
            self.clock.advance_to(min(horizon))
        return self.completed

    # -- reporting ------------------------------------------------------------

    def latency_summary(self) -> Dict:
        """Load-study summary: latency percentiles (virtual seconds),
        throughput, and — with an :class:`~repro.serve.traffic.SLO`
        attached — attainment and goodput.

        Attainment counts shed arrivals as SLO misses (shedding is a
        service failure from the client's side); goodput is SLO-met
        completions per second of simulated serving time.
        """
        ttft = Histogram("serve.ttft_s", unit="s")
        tpot = Histogram("serve.tpot_s", unit="s")
        qdel = Histogram("serve.queue_delay_s", unit="s")
        met = 0
        for req in self.completed:
            t = req.first_token_at - req.submitted_at
            ttft.record(t)
            qdel.record(req.admitted_at - req.submitted_at)
            p = None
            if len(req.out_tokens) >= 2:
                p = (req.finished_at - req.first_token_at) \
                    / (len(req.out_tokens) - 1)
                tpot.record(p)
            if self.slo is not None and self.slo.met(t, p):
                met += 1
        span = max((r.finished_at for r in self.completed),
                   default=self.clock.now) or 1e-12
        offered = len(self.completed) + self.shed
        out = {
            "requests": len(self.completed),
            "shed": self.shed,
            "tokens": sum(len(r.out_tokens) for r in self.completed),
            "duration_s": span,
            "throughput_rps": len(self.completed) / span,
            "ttft_s": _pct_summary(ttft),
            "tpot_s": _pct_summary(tpot),
            "queue_delay_s": _pct_summary(qdel),
            "max_decode_gap_s": self.max_decode_gap_s,
            "iterations": self.iterations,
            "slots_max": self.slots_max_seen,
            "link_prefill_bytes": sum(
                b for k, b in self.link.events if k == "prefill"),
            "link_acts_bytes": sum(
                b for k, b in self.link.events if k == "acts"),
        }
        if self.slo is not None:
            out["slo"] = {"ttft_s": self.slo.ttft_s,
                          "tpot_s": self.slo.tpot_s}
            out["slo_met"] = met
            out["slo_attainment"] = met / offered if offered else 0.0
            out["goodput_rps"] = met / span
        return out


def _splice(full, one, slot: int, cfg: ArchConfig):
    """Write the single-sequence prefill cache ``one`` into batch slot
    ``slot`` of the server cache ``full``.  Cache layouts put batch at
    axis 1 (layer-stacked) for every family."""
    # trim/pad the sequence axis if the prefill cache is longer/shorter
    if one.shape != full.shape:
        pads = []
        slc = []
        for a, (fo, oo) in enumerate(zip(full.shape, one.shape)):
            if a == 1:      # batch axis
                pads.append((0, 0))
                slc.append(slice(0, oo))
            else:
                pads.append((0, max(0, fo - oo)))
                slc.append(slice(0, min(fo, oo)))
        one = jnp.pad(one[tuple(slc)], pads)
    return jax.lax.dynamic_update_index_in_dim(full, one[:, :1], slot, 1)
