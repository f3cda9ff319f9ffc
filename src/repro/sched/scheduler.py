"""Energy-SLO admission and billing over a priced request queue.

The scheduling half of the closed loop: where the governor holds a power
cap by actuating the plant, the scheduler decides *which work* runs by
pricing every queued request in joules before it is admitted and
reconciling those predictions against the energy the sensor fleet
actually measured (step-interval / per-wave `EnergyLedger`s from
`repro.attrib`).

The serving substrate is **continuous batching at step granularity**:

* :class:`EnergyPricer` — predicted J/token for an architecture, built
  from per-kernel attribution artifacts (an attributed `EnergyLedger`, a
  `SignatureLibrary` of per-kernel waveforms, or the declared phase
  timeline of the TPU model) and corrected online by an EWMA of the
  measured/predicted ratio;
* :class:`Request` — one queued generation request with its predicted
  and measured energy accounting and its outstanding per-request budget
  commitment;
* :class:`ContinuousBatch` — the slot model: requests :meth:`admit` into
  free slots of a fixed-shape decode batch, every decode step bills real
  tokens per occupied slot (:meth:`step_billing`), completions and
  evictions free slots immediately (:meth:`retire`), and measured energy
  lands per **step interval** (:meth:`settle_interval`), split across the
  requests occupying slots in that interval by token share;
* :class:`EnergySloScheduler` — the wave-granularity compatibility shim
  over the same core (pricing, budget commitments, ledger-splitting):
  `next_wave` / `complete_wave` / `reconcile` admit and settle whole
  waves at once.  A wave is the degenerate one-interval case of the slot
  model; `policies.py` and `compare_policies` run unchanged on either.

Budget accounting is per-request across three pools that always sum
against the budget: ``committed_j`` (admitted but not yet decoded),
``inflight_j`` (decoded but not yet settled step intervals — the wave
shim settles admission-to-reconciliation in one move, so its inflight is
folded into ``committed_j``) and ``spent_j`` (settled, measured or
released-at-prediction).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Sequence

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from .policies import Policy, SchedContext

if TYPE_CHECKING:  # pragma: no cover
    from repro.attrib import EnergyLedger
    from repro.attrib.signatures import SignatureLibrary


@dataclass
class Request:
    """One generation request moving through the scheduler."""

    rid: int
    client: str = "default"
    prompt_len: int = 0
    gen_len: int = 0
    arrival_s: float = 0.0
    payload: object = None  # opaque (e.g. the prompt token array)
    predicted_j: float = 0.0
    measured_j: float = 0.0
    done_tokens: int = 0
    finished: bool = False
    evicted: bool = False
    #: outstanding admission commitment against the joules budget, and the
    #: tokens that commitment still covers (amortised out per decode step)
    committed_j: float = 0.0
    committed_tokens: int = 0

    @property
    def measured_mj_per_token(self) -> float:
        return self.measured_j / self.done_tokens * 1e3 if self.done_tokens else 0.0


@dataclass
class EnergyPricer:
    """Predicted J/token for one architecture, reconciled against reality.

    ``j_per_token`` is the base per-kernel prediction; ``correction`` is
    an EWMA of measured/base ratios fed back from attributed step-interval
    (or wave) ledgers, so systematic model error (the same bias the
    governor's PI integrator absorbs) washes out of admission pricing
    after a few settlements.
    """

    j_per_token: float
    alpha: float = 0.25
    correction: float = 1.0
    n_updates: int = 0

    def price_tokens(self, n_tokens: int) -> float:
        return self.j_per_token * self.correction * max(int(n_tokens), 0)

    def update(self, tokens: int, measured_j: float) -> float:
        """Fold one measured interval in; returns the instantaneous ratio."""
        base = self.j_per_token * tokens
        if base <= 0 or measured_j <= 0:
            return self.correction
        ratio = measured_j / base
        self.correction = (1.0 - self.alpha) * self.correction + self.alpha * ratio
        self.n_updates += 1
        return ratio

    # ------------------------------------------------------------ builders
    @classmethod
    def from_ledger(cls, ledger: "EnergyLedger", tokens: int, **kw) -> "EnergyPricer":
        """Price from an attributed ledger covering ``tokens`` of decode."""
        if tokens <= 0:
            raise ValueError("tokens must be positive")
        return cls(j_per_token=ledger.total_energy_j / tokens, **kw)

    @classmethod
    def from_signatures(
        cls,
        library: "SignatureLibrary",
        tokens_per_step: int,
        kernels: Sequence[str] | None = None,
        **kw,
    ) -> "EnergyPricer":
        """Price from per-kernel power signatures: Σ mean_w · duration per step.

        This is the `attrib.signatures` path: each kernel's signature
        carries its mean occurrence power and duration, so one modelled
        serving step costs the sum over its kernels — no markers needed on
        the pricing side.
        """
        names = list(kernels) if kernels is not None else list(library.signatures)
        step_j = 0.0
        for name in names:
            sig = library.signatures[name]
            step_j += sig.mean_w * sig.duration_s
        if tokens_per_step <= 0:
            raise ValueError("tokens_per_step must be positive")
        return cls(j_per_token=step_j / tokens_per_step, **kw)

    @classmethod
    def from_phases(cls, phases, chip, tokens_per_step: int, dvfs=None, **kw) -> "EnergyPricer":
        """Price from the declared per-kernel phase timeline (model-only)."""
        step_j = sum(p.power(chip, dvfs) * p.duration_s for p in phases)
        if tokens_per_step <= 0:
            raise ValueError("tokens_per_step must be positive")
        return cls(j_per_token=step_j / tokens_per_step, **kw)


# --------------------------------------------------------------------- core
class _SloCore:
    """Shared pricing/budget/settlement machinery under both granularities.

    Owns the queue, the request index, the budget pools, and the exact
    ledger-splitting settlement (`_split_settled`): settled energy is
    divided across requests by share with the last share absorbing the
    float residue, so per-request totals always sum *exactly* to the
    settled total — the SLO invariant every billing test pins.
    """

    def __init__(
        self,
        pricer: EnergyPricer,
        policy: Policy,
        budget_j: float = math.inf,
        cap_w: float | None = None,
        power_of_batch=None,
    ):
        self.pricer = pricer
        self.policy = policy
        self.budget_j = float(budget_j)
        self.cap_w = cap_w
        self.power_of_batch = power_of_batch
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.rejected: list[Request] = []
        self.spent_j = 0.0  # settled energy (measured or released)
        self.committed_j = 0.0  # admitted-but-unsettled predicted energy
        self.inflight_j = 0.0  # decoded-but-unsettled predicted energy
        self.client_energy_j: dict[str, float] = {}
        self._by_rid: dict[int, Request] = {}

    # ---------------------------------------------------------- admission
    @property
    def remaining_budget_j(self) -> float:
        return self.budget_j - self.spent_j - self.committed_j - self.inflight_j

    def submit(self, req: Request) -> None:
        req.predicted_j = self.pricer.price_tokens(req.gen_len)
        self.queue.append(req)
        self._by_rid[req.rid] = req
        self.client_energy_j.setdefault(req.client, 0.0)

    def _context(self, now_s: float) -> SchedContext:
        return SchedContext(
            max_batch=self._admission_bound(),
            remaining_budget_j=self.remaining_budget_j,
            cap_w=self.cap_w,
            power_of_batch=self.power_of_batch,
            client_energy_j=dict(self.client_energy_j),
            now_s=now_s,
        )

    def _admission_bound(self) -> int:  # pragma: no cover - overridden
        raise NotImplementedError

    def _reject_hopeless(self) -> None:
        """Drop queued requests that cannot fit the budget even once every
        in-flight commitment resolves — an SLO decision surfaced in
        ``rejected`` rather than a silent starve."""
        hard_remaining = self.budget_j - self.spent_j
        for req in list(self.queue):
            if self.pricer.price_tokens(req.gen_len - req.done_tokens) > hard_remaining:
                self.queue.remove(req)
                self.rejected.append(req)

    # --------------------------------------------------------- settlement
    def _split_settled(
        self, rids: Sequence[int], shares: Sequence[float], energy_j: float
    ) -> None:
        """Split settled energy across requests by share, exactly."""
        n = len(rids)
        total_share = sum(shares)
        if n == 0 or total_share <= 0:
            return
        handed = 0.0
        for k, (rid, share) in enumerate(zip(rids, shares)):
            req = self._by_rid[rid]
            d = energy_j - handed if k == n - 1 else energy_j * share / total_share
            handed += d
            req.measured_j += d
            self.client_energy_j[req.client] = (
                self.client_energy_j.get(req.client, 0.0) + d
            )

    # ------------------------------------------------------------ reports
    def report_rows(self) -> list[dict]:
        rows = []
        for req in sorted(self._by_rid.values(), key=lambda r: r.rid):
            rows.append(
                {
                    "rid": req.rid,
                    "client": req.client,
                    "tokens": req.done_tokens,
                    "predicted_j": req.predicted_j,
                    "measured_j": req.measured_j,
                    "mj_per_token": req.measured_mj_per_token,
                    "finished": req.finished,
                }
            )
        return rows


# ------------------------------------------------------------ step model
@dataclass
class StepRecord:
    """One decode step over the live batch: who ran, who got billed."""

    index: int
    interval: int  # the settlement interval this step belongs to
    rids: tuple[int, ...]  # requests occupying active slots this step
    tokens: tuple[int, ...]  # real tokens billed per occupying request
    decoded_tokens: int  # tokens the hardware ran, padded slots included

    @property
    def billed_tokens(self) -> int:
        return sum(self.tokens)


@dataclass
class IntervalRecord:
    """One settlement interval: a batch of decode steps bracketed by the
    step clock (markers), with its per-request occupancy matrix collapsed
    to token counts — the generalisation of a wave's token shares."""

    index: int
    steps: int = 0
    #: rid -> real tokens billed inside this interval (insertion-ordered)
    occupancy: dict[int, int] = field(default_factory=dict)
    #: tokens the hardware decoded, padded slots included — the pricer's
    #: correction denominator
    decoded_tokens: int = 0
    predicted_j: float = 0.0  # commitment moved in from the steps billed
    measured_j: float | None = None  # None until settled/released
    released: bool = False  # settled from prediction, not measurement

    @property
    def tokens(self) -> int:
        return sum(self.occupancy.values())


#: slot lifecycle: free -> active (admitted) -> draining (request finished
#: or evicted; the fixed-shape batch still decodes the slot as padding,
#: excluded from billing) -> active/free again at the next admission
SLOT_FREE = "free"
SLOT_ACTIVE = "active"
SLOT_DRAINING = "draining"


class ContinuousBatch(_SloCore):
    """Continuous batching priced in joules, at step granularity.

    The live decode batch is ``n_slots`` fixed slots (the compiled batch
    shape).  Requests join mid-decode (:meth:`admit`), are billed real
    tokens per step (:meth:`step_billing` — padded/draining slots bill
    nothing), and leave the moment they finish or are evicted
    (:meth:`retire`), freeing the slot for the next admission.

    Energy lands per **step interval**: :meth:`seal_interval` closes the
    batch of steps since the last seal (the serve loop brackets each with
    one marker occurrence), and :meth:`settle_interval` splits the
    measured interval energy across the requests that occupied slots in
    it, by real-token share — the same exact-sum ledger splitting the
    wave shim uses, driven by the interval's occupancy matrix instead of
    a per-wave token share.  Settlement may lag by any number of
    intervals; :meth:`release_interval` settles an unmeasurable interval
    at its predicted energy so budget commitments never leak.

    Admission enforces the power cap at step granularity: the policy's
    ``batch_limit`` bounds the number of *live* slots, so a cap-strict
    policy holds the modelled batch power under the cap at every step
    boundary even as completions and arrivals churn the batch.
    """

    def __init__(
        self,
        pricer: EnergyPricer,
        policy: Policy,
        n_slots: int,
        budget_j: float = math.inf,
        cap_w: float | None = None,
        power_of_batch=None,
    ):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        super().__init__(pricer, policy, budget_j, cap_w, power_of_batch)
        self.n_slots = int(n_slots)
        self.slot_rids: list[int | None] = [None] * self.n_slots
        self.slot_states: list[str] = [SLOT_FREE] * self.n_slots
        self.evicted: list[Request] = []
        self.steps: list[StepRecord] = []
        self.intervals: list[IntervalRecord] = []  # sealed intervals
        self.overhead_j = 0.0  # settled energy no live request occupied
        self._cur = IntervalRecord(index=0)

    # ------------------------------------------------------------- state
    @property
    def current_interval(self) -> int:
        """Index the next :meth:`seal_interval` will close (the open one)."""
        return self._cur.index

    @property
    def n_active(self) -> int:
        return sum(1 for s in self.slot_states if s == SLOT_ACTIVE)

    @property
    def live_rids(self) -> list[int]:
        return [
            rid
            for rid, s in zip(self.slot_rids, self.slot_states)
            if s == SLOT_ACTIVE and rid is not None
        ]

    def _admission_bound(self) -> int:
        return self.n_slots

    def _slot_of(self, rid: int) -> int:
        for i, (r, s) in enumerate(zip(self.slot_rids, self.slot_states)):
            if r == rid and s == SLOT_ACTIVE:
                return i
        raise KeyError(f"request {rid} occupies no active slot")

    # ---------------------------------------------------------- admission
    def admit(self, now_s: float = 0.0) -> list[tuple[int, Request]]:
        """Fill reusable slots from the queue; returns (slot, request) pairs.

        The policy orders the queue and bounds the *live* batch (cap
        enforcement at step granularity); the budget walk then admits
        every request whose re-priced remaining cost fits — skipped, not
        blocked, so cheaper requests behind an expensive head keep the
        batch full.  Each admission takes a per-request commitment
        against the budget, amortised back out token-by-token as the
        request decodes.  When nothing fits a free slot *and* no
        commitment is pending resolution, hopeless requests are rejected.
        """
        reusable = [
            i for i, s in enumerate(self.slot_states) if s != SLOT_ACTIVE
        ]
        if not self.queue or not reusable:
            return []
        with obs_trace.span("sched:admit", queued=len(self.queue)) as sp:
            ctx = self._context(now_s)
            order = self.policy.order(self.queue, ctx)
            limit = min(self.policy.batch_limit(self.queue, ctx), self.n_slots)
            room = limit - self.n_active
            admitted: list[tuple[int, Request]] = []
            predicted = 0.0
            remaining = self.remaining_budget_j
            chosen: list[Request] = []
            for qi in order:
                if len(chosen) >= min(room, len(reusable)):
                    break
                req = self.queue[qi]
                price = self.pricer.price_tokens(req.gen_len - req.done_tokens)
                if predicted + price > remaining:
                    continue
                chosen.append(req)
                predicted += price
            for slot, req in zip(reusable, chosen):
                self.queue.remove(req)
                price = self.pricer.price_tokens(req.gen_len - req.done_tokens)
                req.predicted_j = price
                req.committed_j = price
                req.committed_tokens = max(req.gen_len - req.done_tokens, 0)
                self.committed_j += price
                self.slot_rids[slot] = req.rid
                self.slot_states[slot] = SLOT_ACTIVE
                admitted.append((slot, req))
                # the wait ends here, so it travels as metadata of a point span
                with obs_trace.span("sched:queue", rid=req.rid,
                                    wait_ms=(now_s - req.arrival_s) * 1e3,
                                    prompt_len=req.prompt_len):
                    pass
            if not admitted and room > 0 and not (self.committed_j or self.inflight_j):
                self._reject_hopeless()
            sp.set_metadata(admitted=len(admitted))
        if admitted:
            reg = obs_metrics.active()
            if reg is not None:
                reg.counter("sched_admitted_total", "requests admitted").inc(
                    len(admitted)
                )
        return admitted

    # ------------------------------------------------------------ billing
    def step_billing(
        self, slot_tokens: int = 1, decoded_slots: int | None = None
    ) -> StepRecord:
        """Bill one decode step of the live batch.

        Every active slot's request is credited ``slot_tokens`` real
        tokens (clamped at its remaining ``gen_len``); its admission
        commitment moves pro rata into the current interval's predicted
        pool (``inflight_j``), so the budget view is unchanged by the
        move.  Requests that finish retire immediately — their slot
        drains and is reusable at the next :meth:`admit`.  Padded slots
        (free/draining) bill nothing but count in ``decoded_tokens``:
        the fixed compiled batch shape ran them, and the pricer's
        correction must price what the hardware actually did.
        """
        with obs_trace.span("sched:step", live=len(self.live_rids),
                            slots=self.n_slots) as sp:
            rids: list[int] = []
            tokens: list[int] = []
            for slot, (rid, state) in enumerate(
                zip(self.slot_rids, self.slot_states)
            ):
                if state != SLOT_ACTIVE or rid is None:
                    continue
                req = self._by_rid[rid]
                d = min(int(slot_tokens), max(req.gen_len - req.done_tokens, 0))
                if d > 0:
                    move = (
                        req.committed_j * d / req.committed_tokens
                        if req.committed_tokens > 0
                        else 0.0
                    )
                    req.committed_j -= move
                    req.committed_tokens -= d
                    self.committed_j -= move
                    self.inflight_j += move
                    self._cur.predicted_j += move
                    self._cur.occupancy[rid] = self._cur.occupancy.get(rid, 0) + d
                    req.done_tokens += d
                    rids.append(rid)
                    tokens.append(d)
                if req.done_tokens >= req.gen_len:
                    self._finish(req, slot)
            n_decoded = self.n_slots if decoded_slots is None else int(decoded_slots)
            decoded = int(slot_tokens) * n_decoded
            self._cur.steps += 1
            self._cur.decoded_tokens += decoded
            rec = StepRecord(
                index=len(self.steps),
                interval=self._cur.index,
                rids=tuple(rids),
                tokens=tuple(tokens),
                decoded_tokens=decoded,
            )
            self.steps.append(rec)
            sp.set_metadata(billed=rec.billed_tokens)
        return rec

    def _release_commitment(self, req: Request) -> None:
        self.committed_j -= req.committed_j
        req.committed_j = 0.0
        req.committed_tokens = 0

    def _finish(self, req: Request, slot: int) -> None:
        self._release_commitment(req)
        self.slot_states[slot] = SLOT_DRAINING
        if not req.finished:
            req.finished = True
            self.finished.append(req)

    def retire(self, rid: int, requeue: bool = False) -> Request:
        """Evict one live request, freeing its slot immediately.

        Its outstanding commitment is released; tokens already billed
        stay billed (their intervals settle normally — no double billing,
        no leak).  With ``requeue`` the request rejoins the queue to be
        re-admitted (and re-priced) later; otherwise it lands in
        ``evicted``.
        """
        slot = self._slot_of(rid)
        req = self._by_rid[rid]
        self._release_commitment(req)
        self.slot_states[slot] = SLOT_DRAINING
        if requeue:
            self.queue.append(req)
        else:
            req.evicted = True
            self.evicted.append(req)
        rec = obs_trace.active()
        if rec is not None:
            rec.instant(
                "sched:retire:requeue" if requeue else "sched:retire:evict",
                track="sched", value=float(rid),
            )
        reg = obs_metrics.active()
        if reg is not None:
            reg.counter(
                "sched_retired_total", "requests evicted or requeued",
                mode="requeue" if requeue else "evict",
            ).inc()
        return req

    # --------------------------------------------------------- settlement
    def seal_interval(self) -> IntervalRecord | None:
        """Close the current step interval; returns it (None when empty).

        The serve loop calls this once per marker sync: the sealed
        interval's index lines up 1:1 with the marker occurrence that
        opened it, so measured marker-window energy settles by index.
        """
        if self._cur.steps == 0:
            return None
        sealed = self._cur
        with obs_trace.span("sched:seal", interval=sealed.index,
                            decoded=sealed.decoded_tokens):
            self.intervals.append(sealed)
            self._cur = IntervalRecord(index=sealed.index + 1)
        reg = obs_metrics.active()
        if reg is not None:
            reg.counter("sched_intervals_sealed_total", "step intervals sealed").inc()
        return sealed

    def _settle(self, rec: IntervalRecord, energy_j: float, from_measurement: bool) -> None:
        if rec.measured_j is not None:
            raise ValueError(f"interval {rec.index} already settled")
        with obs_trace.span("sched:settle", interval=rec.index,
                            measured=int(from_measurement)):
            rec.measured_j = float(energy_j)
            rec.released = not from_measurement
            self.inflight_j -= rec.predicted_j
            self.spent_j += rec.measured_j
            if rec.occupancy:
                self._split_settled(
                    list(rec.occupancy), list(rec.occupancy.values()), rec.measured_j
                )
            else:
                # the hardware drew power but no live request occupied a slot
                # (all padding): surfaced as overhead, never silently dropped
                self.overhead_j += rec.measured_j
            if from_measurement and rec.decoded_tokens:
                self.pricer.update(rec.decoded_tokens, rec.measured_j)
        reg = obs_metrics.active()
        if reg is not None:
            reg.counter(
                "sched_intervals_settled_total",
                "intervals settled (measured) or released (predicted)",
                mode="measured" if from_measurement else "released",
            ).inc()
            reg.counter(
                "sched_settled_joules_total", "energy landed on intervals",
            ).inc(rec.measured_j)

    def settle_interval(self, index: int, measured_j: float) -> None:
        """Land the attributed energy of one sealed step interval.

        Splits by real-token share across the interval's occupancy matrix
        (per-request totals sum exactly to the settled total), releases
        the interval's predicted pool from the budget, charges the
        measured energy, and feeds the pricer's correction loop.
        """
        self._settle(self.intervals[index], measured_j, from_measurement=True)

    def release_interval(self, index: int) -> None:
        """Settle an interval whose energy could not be measured (ring
        evicted the span, markers lost to a fault): charge its *predicted*
        energy so the budget commitment is not leaked, without feeding the
        pricer."""
        self._settle(self.intervals[index], self.intervals[index].predicted_j,
                     from_measurement=False)

    def unsettled(self) -> list[int]:
        return [r.index for r in self.intervals if r.measured_j is None]

    @property
    def billed_j(self) -> float:
        """Per-request settled energy total (== spent_j − overhead_j)."""
        return float(sum(r.measured_j for r in self._by_rid.values()))


# ------------------------------------------------------- wave compat shim
@dataclass
class WaveRecord:
    """One scheduled wave and its energy accounting."""

    index: int
    rids: list[int]
    tokens: int = 0  # tokens credited to real requests (gen_len-clamped)
    #: tokens the hardware actually decoded, including padded batch slots —
    #: the denominator the pricer's J/token correction must use
    decoded_tokens: int = 0
    request_tokens: list[int] = field(default_factory=list)
    predicted_j: float = 0.0
    measured_j: float | None = None  # None until reconciled/released
    released: bool = False  # settled from prediction, not measurement


class EnergySloScheduler(_SloCore):
    """Wave-granularity compatibility shim over the continuous-batch core.

    Lifecycle per wave: :meth:`next_wave` (policy orders the queue, the
    scheduler admits a budget-feasible prefix), :meth:`complete_wave`
    (tokens decoded), :meth:`reconcile` (attributed wave energy lands,
    split across the wave's requests by token share, budget and pricer
    updated).  Reconciliation is allowed to lag by any number of waves.

    Commitments are per-request (each admitted request carries its own
    ``committed_j``), matching the step-granularity core; a wave's
    commitment is just the sum over its requests.  A wave is the
    degenerate one-interval case of :class:`ContinuousBatch`: one
    admission, one settlement, token shares as the occupancy matrix.
    `compare_policies` and the policy surface run identically on both.
    """

    def __init__(
        self,
        pricer: EnergyPricer,
        policy: Policy,
        max_batch: int,
        budget_j: float = math.inf,
        cap_w: float | None = None,
        power_of_batch=None,
    ):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        super().__init__(pricer, policy, budget_j, cap_w, power_of_batch)
        self.max_batch = int(max_batch)
        self.waves: list[WaveRecord] = []

    def _admission_bound(self) -> int:
        return self.max_batch

    def next_wave(self, now_s: float = 0.0) -> list[Request] | None:
        """Select the next wave, or None when the queue is empty / starved.

        The policy orders the queue and bounds the batch; the scheduler
        then walks that order admitting every request whose *re-priced*
        predicted energy still fits the remaining budget.  Admission is
        deliberately work-conserving: a request too expensive for the
        current remainder is skipped (not a barrier), so cheaper requests
        behind it keep the batch full — an expensive head-of-line request
        waits until commitments resolve or is eventually rejected as
        hopeless (predicted energy above the spent-adjusted budget alone),
        an SLO decision surfaced in ``rejected`` rather than a silent
        starve.
        """
        if not self.queue:
            return None
        ctx = self._context(now_s)
        order = self.policy.order(self.queue, ctx)
        limit = min(self.policy.batch_limit(self.queue, ctx), self.max_batch)
        if limit < 1:
            return None
        chosen: list[Request] = []
        predicted = 0.0
        remaining = self.remaining_budget_j
        for qi in order:
            if len(chosen) >= limit:
                break
            req = self.queue[qi]
            price = self.pricer.price_tokens(req.gen_len - req.done_tokens)
            if predicted + price > remaining:
                continue
            req.predicted_j = price
            chosen.append(req)
            predicted += price
        if not chosen:
            # Nothing fits *right now*.  Only requests that cannot fit the
            # budget even once every in-flight commitment resolves are
            # hopeless and rejected; the rest stay queued — the caller can
            # reconcile pending waves (freeing committed energy) and retry.
            self._reject_hopeless()
            return None
        for req in chosen:
            self.queue.remove(req)
            req.committed_j = req.predicted_j
            req.committed_tokens = max(req.gen_len - req.done_tokens, 0)
        wave = WaveRecord(
            index=len(self.waves), rids=[r.rid for r in chosen], predicted_j=predicted
        )
        self.waves.append(wave)
        self.committed_j += predicted
        return chosen

    # --------------------------------------------------------- completion
    def complete_wave(
        self,
        wave_index: int,
        tokens_per_request: int,
        decoded_tokens: int | None = None,
    ) -> None:
        """Record the tokens a wave decoded.

        Per-request credit is clamped at each request's remaining
        ``gen_len`` (a short request padded into a long wave does not get
        phantom tokens); ``decoded_tokens`` is what the hardware actually
        ran — including padded batch slots — and defaults to
        ``tokens_per_request × n_requests`` when no padding happened.
        """
        wave = self.waves[wave_index]
        wave.request_tokens = []
        for rid in wave.rids:
            req = self._by_rid[rid]
            d = min(tokens_per_request, max(req.gen_len - req.done_tokens, 0))
            req.done_tokens += d
            wave.request_tokens.append(d)
            if req.done_tokens >= req.gen_len and not req.finished:
                req.finished = True
                self.finished.append(req)
        wave.tokens = sum(wave.request_tokens)
        wave.decoded_tokens = (
            decoded_tokens
            if decoded_tokens is not None
            else tokens_per_request * len(wave.rids)
        )

    def _settle(self, wave: WaveRecord, energy_j: float, from_measurement: bool) -> None:
        wave.measured_j = float(energy_j)
        wave.released = not from_measurement
        for rid in wave.rids:
            req = self._by_rid[rid]
            self.committed_j -= req.committed_j
            req.committed_j = 0.0
            req.committed_tokens = 0
        # split by per-request token share; exact-sum residue handling is
        # the shared core's (same machinery as step-interval settlement)
        shares = (
            [float(t) for t in wave.request_tokens]
            if sum(wave.request_tokens)
            else [1.0] * len(wave.rids)
        )
        self._split_settled(wave.rids, shares, wave.measured_j)
        self.spent_j += wave.measured_j
        if from_measurement and wave.decoded_tokens:
            self.pricer.update(wave.decoded_tokens, wave.measured_j)

    def reconcile(self, wave_index: int, measured_j: float) -> None:
        """Land the attributed energy of one wave.

        Splits by token share across the wave's requests (so per-request
        totals sum exactly to the ledger total), releases the wave's
        predicted commitment from the budget, charges the measured energy,
        and feeds the pricer's correction loop.
        """
        wave = self.waves[wave_index]
        if wave.measured_j is not None:
            raise ValueError(f"wave {wave_index} already settled")
        self._settle(wave, measured_j, from_measurement=True)

    def release_wave(self, wave_index: int) -> None:
        """Settle a wave whose energy could not be measured (e.g. the ring
        evicted its span): charge its *predicted* energy so the budget
        commitment is not leaked forever, without feeding the pricer."""
        wave = self.waves[wave_index]
        if wave.measured_j is not None:
            raise ValueError(f"wave {wave_index} already settled")
        self._settle(wave, wave.predicted_j, from_measurement=False)

    # ------------------------------------------------------------ reports
    def unreconciled(self) -> list[int]:
        return [w.index for w in self.waves if w.measured_j is None]


def format_report_rows(rows: Sequence[dict]) -> str:
    """Render `report_rows` output as the per-request SLO accounting table."""
    lines = ["  rid client    tokens  predicted J  measured J  mJ/token"]
    for row in rows:
        lines.append(
            f"  {row['rid']:>3} {row['client']:<9} {row['tokens']:>5}  "
            f"{row['predicted_j']:>11.4f} {row['measured_j']:>11.4f}  "
            f"{row['mj_per_token']:>8.3f}"
        )
    return "\n".join(lines)
