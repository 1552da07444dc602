"""Continuous-batching scheduler over the paged KV pool — ragged edition.

The old TPU-shaped constraint (XLA compiles one executable per input
*shape*) used to force decode batches into a closed set of
(batch, pages) shape buckets plus a separate bucketed prefill ladder —
up to B*P + #prefill_buckets executables. The ragged kernel
(kernels/paged_attention.py) removes the constraint at the source: every
engine step is ONE launch of ONE fixed shape — ``max_num_seqs`` row
slots over a ``step_token_budget``-token query buffer — and everything
request-specific (which row, how many query tokens, which pool pages)
travels as *data* through block tables and (q_start, q_len, kv_len)
metadata. The engine therefore compiles exactly one step executable for
the lifetime of the process (tests/test_serving_compile_gate.py).

A step row is a (sequence, q_len) pair and there is NO prefill/decode
distinction: each sequence has ``cached_len`` tokens committed to the KV
pool out of ``total_len`` known tokens (prompt + generated), and a row
processes the next ``q_len = min(remaining, chunk_size, budget share)``
of them. A fully-caught-up sequence has exactly one uncached token (its
last sampled one) — its row is a decode step, q_len = 1, by the same
formula. A freshly admitted prompt is processed in ``chunk_size``-token
chunks across consecutive steps, INTERLEAVED with every running decode
row in the same launch — long prompts no longer head-of-line-block
decodes behind ``max_prefills_per_step`` whole-prompt prefills; the
budget reserves ``q_block`` tokens per running row first, so decode
progress per step is guaranteed by construction.

Policies (serving study arxiv 2605.25645, RPA arxiv 2604.15464, vLLM):
- admission: FIFO queue; a request is admitted when the pool can hold its
  FIRST chunk and utilization stays under the high watermark (waived when
  nothing is running, so a big request cannot deadlock an empty engine).
  At most ``max_prefills_per_step`` admissions per engine step. An
  optional ``prefix_hook`` (the engine's prefix cache) may fork the
  request onto a live sequence's matching prompt-prefix pages, skipping
  both the re-prefill and the page storage for the shared region.
- deadline load shedding: a *waiting* request whose deadline has passed is
  shed at schedule time. Running requests are never shed.
- preemption-with-requeue: when a sequence cannot grow into its next
  page, victims are preempted latest-arrival-first, their generated
  tokens kept, and they re-enter the *front* of the queue in recompute
  mode (``cached_len`` reset to 0): on re-admission the engine re-chunks
  prompt+generated and decoding resumes — the ragged step computes each
  token's K/V identically regardless of chunk boundaries, so greedy
  outputs are identical with and without preemption.
"""
from __future__ import annotations

import enum
import time
from collections import deque
from dataclasses import dataclass, field

from .kv_cache import PagedKVPool, PoolExhausted


class SequenceStatus(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    PREEMPTED = "preempted"   # transiently, while re-queued
    FINISHED = "finished"
    SHED = "shed"
    CANCELLED = "cancelled"
    ABORTED = "aborted"


def bucket_for(n: int, buckets) -> int:
    """Smallest bucket >= n (buckets need not be sorted). Kept for the
    legacy bucketed callers/tests; the ragged step itself has one shape
    and never buckets."""
    best = None
    for b in buckets:
        if b >= n and (best is None or b < best):
            best = b
    if best is None:
        raise ValueError(f"{n} exceeds the largest bucket in {buckets}")
    return best


@dataclass
class Sequence:
    """Scheduler-side state of one in-flight request."""
    seq_id: str
    prompt_ids: list
    max_new_tokens: int
    arrival: float
    deadline: float | None = None
    #: absolute e2e SLO: a request still unfinished past this instant is
    #: ABORTED at the next step boundary (mid-flight SLO abort — decoding
    #: tokens nobody will read is shed load, not service), unlike
    #: ``deadline`` which only sheds requests still WAITING to start
    abort_deadline: float | None = None
    temperature: float = 0.0
    #: per-request sampling knobs (None/0 = off, engine passes them as
    #: per-row data into the one jitted step — knobs are data, not shape)
    top_k: int | None = None
    top_p: float | None = None
    #: resolved per-request PRNG seed: every random draw this request
    #: consumes is fold_in(base, seed, generation position, tag), so its
    #: sampled tokens are bit-identical across batch compositions
    seed: int = 0
    eos_token_id: int | None = None
    tokens: list = field(default_factory=list)      # generated so far
    status: SequenceStatus = SequenceStatus.WAITING
    num_preemptions: int = 0
    #: tokens whose K/V is committed to the pool (prefix-cache fork sets
    #: it to the shared length at admission; preemption resets it to 0)
    cached_len: int = 0
    #: when the sequence last entered the waiting queue (scheduler
    #: now_fn time base): set at add(), refreshed at preempt() — queue
    #: age = now - enqueued_at feeds the starvation gauges
    enqueued_at: float | None = None
    #: when the FIRST generated token was committed (engine now_fn time
    #: base) — the TTFT numerator; never reset by preemption (the
    #: client saw the token when it streamed, recompute is invisible)
    first_token_at: float | None = None
    #: multi-tenant serving (paddle_tpu.tenancy): the owning tenant
    #: (None = untenanted traffic), the LoRA adapter the request wears
    #: (0 = base model) and its resolved registry slot — the slot rides
    #: the ragged step as per-token DATA, never shape
    tenant_id: str | None = None
    adapter_id: object = 0
    adapter_slot: int = 0
    #: structured shed cause (e.g. "quota_exceeded") — the engine's
    #: finalize turns it into the output's finish_reason
    shed_reason: str | None = None

    @property
    def total_len(self) -> int:
        """Tokens the engine knows (prompt + generated)."""
        return len(self.prompt_ids) + len(self.tokens)

    @property
    def remaining_new_tokens(self) -> int:
        return self.max_new_tokens - len(self.tokens)

    @property
    def uncached_len(self) -> int:
        """Known tokens not yet in the pool — 1 for a caught-up decode
        row, more while the prompt is still being chunked in."""
        return self.total_len - self.cached_len

    @property
    def all_ids(self) -> list:
        return self.prompt_ids + self.tokens


@dataclass
class BurstPlan:
    """One on-device generation burst: every row is a caught-up decode
    row (uncached_len == 1), and the pool already covers ``cap`` more
    tokens per row — the jitted ``lax.while_loop`` runs up to
    ``burst_len`` sample->append->gate iterations with NO host round
    trip, the host re-syncing scheduler state only at the boundary."""
    rows: list                 # [(Sequence, cap)] cap = max tokens this burst
    burst_len: int             # max(cap) — the loop's trip bound
    cow_copies: int = 0        # copy-on-write page dups pre-claimed


@dataclass
class StepPlan:
    """One fixed-shape ragged launch: ``rows`` are (seq, q_start, q_len)
    with slot starts aligned to ``q_block``, packed into a
    ``token_budget``-token query buffer over ``num_slots`` row slots."""
    rows: list                 # [(Sequence, q_start, q_len)]
    num_slots: int             # fixed row-slot count (max_num_seqs)
    token_budget: int          # fixed packed-query length
    cow_copies: int = 0        # copy-on-write page dups this step
    #: speculative rounds only (prepare_spec): per-row draft candidate
    #: count, aligned with ``rows`` (q_len = spec_len + 1); None on
    #: ordinary decode/prefill rounds
    spec_lens: list | None = None

    @property
    def actual_q_tokens(self) -> int:
        return sum(q_len for _, _, q_len in self.rows)

    @property
    def pad_fraction(self) -> float:
        return 1.0 - self.actual_q_tokens / self.token_budget


class SchedulerConfig:
    def __init__(self, *, max_num_seqs=None, chunk_size=32, q_block=8,
                 step_token_budget=None, max_prefills_per_step=4,
                 now_fn=time.monotonic, batch_buckets=None,
                 pages_buckets=None):
        # legacy bucket knobs: max(batch_buckets) used to bound the decode
        # batch — it still sets the row-slot count when max_num_seqs is
        # not given; pages_buckets is obsolete (one launch shape) and
        # accepted only so older callers keep working
        if max_num_seqs is None:
            max_num_seqs = max(batch_buckets) if batch_buckets else 8
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if q_block < 1:
            raise ValueError("q_block must be >= 1")
        self.max_num_seqs = int(max_num_seqs)
        self.q_block = int(q_block)
        self.chunk_size = int(chunk_size)
        if step_token_budget is None:
            step_token_budget = self.max_num_seqs * self.q_block + \
                -(-self.chunk_size // self.q_block) * self.q_block
        if step_token_budget % self.q_block != 0:
            raise ValueError(
                f"step_token_budget {step_token_budget} not a multiple of "
                f"q_block {self.q_block}")
        if step_token_budget < self.max_num_seqs * self.q_block:
            raise ValueError(
                "step_token_budget must reserve q_block tokens per row "
                f"({self.max_num_seqs} rows x q_block {self.q_block})")
        self.step_token_budget = int(step_token_budget)
        self.max_prefills_per_step = max_prefills_per_step
        self.now_fn = now_fn


class Scheduler:
    def __init__(self, pool: PagedKVPool, config: SchedulerConfig,
                 max_pages_per_seq: int, metrics=None):
        self.pool = pool
        self.config = config
        self.max_pages_per_seq = max_pages_per_seq
        self.metrics = metrics
        self.waiting: deque[Sequence] = deque()
        self.running: list[Sequence] = []
        #: sequences preempted during the LAST prepare_step round; the
        #: engine drains this to surface fresh preemptions exactly once
        self.last_preempted: list[Sequence] = []
        #: watermark hysteresis: once admission halts above the HIGH
        #: watermark, it stays halted until utilization falls below LOW —
        #: prevents admit/preempt thrash right at the high line
        self._admission_paused = False
        #: q_len granted to each running seq by the current planning round
        self._granted: dict[str, int] = {}
        #: cluster drain hook (serving/cluster.py): True freezes
        #: admission entirely — running rows finish, waiting rows sit
        #: (or are withdrawn by the cluster for requeue elsewhere)
        self.admission_blocked = False
        #: multi-tenant economy (paddle_tpu.tenancy.TenantPolicy): when
        #: set, admission switches to stride-scheduled weighted-fair
        #: pick over per-tenant queues with token-bucket quota gating;
        #: None (the default) keeps the bare-FIFO path byte-identical
        self.policy = None

    # ---- introspection ----
    @property
    def max_num_seqs(self) -> int:
        return self.config.max_num_seqs

    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running)

    def queue_depth(self) -> int:
        return len(self.waiting)

    def queue_ages(self, now=None) -> list[float]:
        """Seconds each waiting request has sat in the queue since it was
        last (re-)enqueued — the starvation signal behind the
        ``queue_age_p99_s`` / ``max_queue_wait_s`` gauges. Preemption
        refreshes a sequence's enqueue timestamp: its age measures THIS
        wait, not lifetime."""
        now = self.config.now_fn() if now is None else now
        return [now - (s.enqueued_at if s.enqueued_at is not None
                       else s.arrival)
                for s in self.waiting]

    def max_queue_wait(self, now=None) -> float:
        ages = self.queue_ages(now)
        return max(ages) if ages else 0.0

    # ---- admission ----
    def add(self, seq: Sequence):
        total_pages = self.pool.pages_for(
            len(seq.prompt_ids) + seq.max_new_tokens)
        limit = min(self.pool.capacity, self.max_pages_per_seq)
        if total_pages > limit:
            raise ValueError(
                f"request {seq.seq_id}: prompt+max_new_tokens needs "
                f"{total_pages} pages, engine limit is {limit}")
        seq.status = SequenceStatus.WAITING
        seq.enqueued_at = self.config.now_fn()
        self.waiting.append(seq)

    def remove(self, seq_id: str):
        """Drop a sequence wherever it sits (cancellation). Frees pages if
        it was running. Returns the Sequence or None."""
        for s in self.waiting:
            if s.seq_id == seq_id:
                self.waiting.remove(s)
                return s
        for s in self.running:
            if s.seq_id == seq_id:
                self.running.remove(s)
                self.pool.free(seq_id)
                return s
        return None

    def shed_expired(self, now=None) -> list[Sequence]:
        """Deadline-based load shedding over the admission queue.

        The deadline is a waiting-before-START SLO: a request that has
        already produced tokens (i.e. was admitted, then preempted back
        into the queue) is never shed — shedding it would break the
        preemption token-identity guarantee for work already under way.
        """
        now = self.config.now_fn() if now is None else now
        shed, keep = [], deque()
        for s in self.waiting:
            if s.deadline is not None and now > s.deadline \
                    and not s.tokens:
                s.status = SequenceStatus.SHED
                shed.append(s)
            else:
                keep.append(s)
        self.waiting = keep
        if shed and self.metrics is not None:
            self.metrics.shed_requests.inc(len(shed))
        return shed

    def abort_expired(self, now=None) -> list[Sequence]:
        """Mid-flight SLO abort: collect every sequence — RUNNING rows
        included — whose absolute e2e ``abort_deadline`` has passed.
        Shedding only at admission keeps burning steps on requests whose
        client has already timed out; this catches them at the step
        boundary instead. The caller finalizes each one (a structured
        ``RequestOutput`` with reason ``deadline_exceeded``; pages are
        freed through the normal ``finish`` path, so CoW refcounts and
        pinned chains stay intact). This method only COLLECTS — state
        changes stay in one place (``finish``)."""
        now = self.config.now_fn() if now is None else now
        return [s for s in list(self.running) + list(self.waiting)
                if s.abort_deadline is not None and now > s.abort_deadline]

    def admit(self, prefix_hook=None) -> list[Sequence]:
        """Move FIFO-queue heads into the running set. Claims the pages
        of each admission's FIRST chunk (later chunks claim lazily inside
        ``prepare_step``); ``prefix_hook(seq)``, when given, may fork the
        sequence onto cached prompt-prefix pages first and returns the
        shared token count (0 on miss).

        With a :class:`~paddle_tpu.tenancy.TenantPolicy` attached
        (``self.policy``) admission instead stride-picks the next
        fundable tenant's oldest request (weighted-fair + token-bucket
        quotas); without one, this FIFO body runs unchanged."""
        if self.policy is not None:
            return self._admit_weighted(prefix_hook)
        admitted = []
        if self.admission_blocked:
            return admitted
        if self._admission_paused and self.pool.below_low_watermark():
            self._admission_paused = False
        while self.waiting:
            # admitted seqs are already in self.running — count them once
            if len(self.running) >= self.max_num_seqs:
                break
            if len(admitted) >= self.config.max_prefills_per_step:
                break
            seq = self.waiting[0]
            # a PARKED sequence (two-tier pools, serving/kv_tier.py)
            # still owns its table: re-admission must restore its
            # spilled pages — that restore IS its first-chunk cost
            parked = seq.seq_id in self.pool
            if parked:
                # restore cost + the first chunk's growth past the
                # pages the sequence already owns, priced against
                # headroom that EXCLUDES the sequence's own cold pages
                # (spilling the row being restored frees no net HBM)
                first_target = min(seq.cached_len + self.config.chunk_size,
                                   seq.total_len)
                n_pages = self.pool.spilled_page_count(seq.seq_id) \
                    + max(0, self.pool.pages_for(first_target)
                          - len(self.pool.block_table(seq.seq_id)))
                avail = self.pool.restore_headroom(seq.seq_id)
            else:
                first_len = min(self.config.chunk_size, seq.total_len)
                n_pages = self.pool.pages_for(first_len)
                # available = free + reclaimable pinned-exclusive pages
                # (a pool full of evictable prefix cache must still
                # admit)
                avail = self.pool.available_pages
            # a pool with window layers prices the first chunk in its
            # second page group too (kv_cache.py)
            if n_pages > avail or not (
                    parked or self.pool.window_can_hold(first_len)):
                break
            # watermark admission control: above the high watermark stop
            # taking new work (leave headroom for running seqs to grow),
            # and stay stopped until utilization recovers below the low
            # watermark (hysteresis) — unless the engine is idle, where
            # waiting would deadlock
            busy = bool(self.running) or bool(admitted)
            if busy:
                if self.pool.above_high_watermark(extra_pages=n_pages):
                    self._admission_paused = True
                if self._admission_paused:
                    break
            self.waiting.popleft()
            if parked:
                # exact-byte resume: prefetch-hit or counted stall, the
                # restored KV is identical — cached_len survives
                # parking. Restore AND the first chunk's growth can
                # both fall short if headroom moved under us: defer,
                # don't die — the row keeps its queue-front slot and
                # retries next round (a restore that landed stays
                # landed; the retry's restore is then a no-op).
                shared = seq.cached_len
                first_target = min(shared + self.config.chunk_size,
                                   seq.total_len)
                try:
                    self.pool.restore_sequence(seq.seq_id)
                    self.pool.extend(seq.seq_id, first_target)
                except PoolExhausted:
                    self.waiting.appendleft(seq)
                    break
            else:
                shared = 0
                if prefix_hook is not None:
                    shared = int(prefix_hook(seq) or 0)
                if not shared:
                    self.pool.allocate(seq.seq_id, 0)
                seq.cached_len = shared
                # reserve the first chunk's pages now (the watermark
                # math above priced them in) but commit nothing yet —
                # prepare_step owns the committed length
                first_target = min(shared + self.config.chunk_size,
                                   seq.total_len)
                self.pool.extend(seq.seq_id, first_target)
            self.pool.set_seq_len(seq.seq_id, shared)
            seq.status = SequenceStatus.RUNNING
            self.running.append(seq)
            admitted.append(seq)
            if self.metrics is not None:
                self.metrics.prefills.inc()
        return admitted

    def _admit_weighted(self, prefix_hook=None) -> list[Sequence]:
        """Weighted-fair admission (paddle_tpu.tenancy.TenantPolicy):
        each round the policy stride-picks the fundable tenant with the
        lowest virtual pass and admits that tenant's OLDEST waiting
        request — same pool/watermark feasibility gates as the FIFO
        path, but the pick order interleaves tenants by weight and a
        tenant whose token bucket cannot fund its next request simply
        does not compete (its requests sit, or are quota-shed by
        :meth:`shed_quota`)."""
        admitted = []
        if self.admission_blocked:
            return admitted
        if self._admission_paused and self.pool.below_low_watermark():
            self._admission_paused = False
        while self.waiting:
            if len(self.running) >= self.max_num_seqs:
                break
            if len(admitted) >= self.config.max_prefills_per_step:
                break
            now = self.config.now_fn()
            idx = self.policy.pick(self.waiting, now=now)
            if idx is None:
                break                  # no tenant can fund its next ask
            seq = self.waiting[idx]
            parked = seq.seq_id in self.pool
            if parked:
                first_target = min(seq.cached_len + self.config.chunk_size,
                                   seq.total_len)
                n_pages = self.pool.spilled_page_count(seq.seq_id) \
                    + max(0, self.pool.pages_for(first_target)
                          - len(self.pool.block_table(seq.seq_id)))
                avail = self.pool.restore_headroom(seq.seq_id)
            else:
                first_len = min(self.config.chunk_size, seq.total_len)
                n_pages = self.pool.pages_for(first_len)
                avail = self.pool.available_pages
            # a pool with window layers prices the first chunk in its
            # second page group too (kv_cache.py)
            if n_pages > avail or not (
                    parked or self.pool.window_can_hold(first_len)):
                break
            busy = bool(self.running) or bool(admitted)
            if busy:
                if self.pool.above_high_watermark(extra_pages=n_pages):
                    self._admission_paused = True
                if self._admission_paused:
                    break
            del self.waiting[idx]
            if parked:
                shared = seq.cached_len
                first_target = min(shared + self.config.chunk_size,
                                   seq.total_len)
                try:
                    self.pool.restore_sequence(seq.seq_id)
                    self.pool.extend(seq.seq_id, first_target)
                except PoolExhausted:
                    self.waiting.insert(idx, seq)
                    break
            else:
                shared = 0
                if prefix_hook is not None:
                    shared = int(prefix_hook(seq) or 0)
                if not shared:
                    self.pool.allocate(seq.seq_id, 0)
                seq.cached_len = shared
                first_target = min(shared + self.config.chunk_size,
                                   seq.total_len)
                self.pool.extend(seq.seq_id, first_target)
            self.pool.set_seq_len(seq.seq_id, shared)
            seq.status = SequenceStatus.RUNNING
            self.running.append(seq)
            admitted.append(seq)
            self.policy.on_admit(seq, now=now)
            if self.metrics is not None:
                self.metrics.prefills.inc()
        return admitted

    def shed_quota(self, now=None) -> list[Sequence]:
        """Quota-based load shedding (the noisy-neighbor valve): ask
        the policy which waiting requests sit beyond their tenant's
        fundable horizon (current bucket + ``shed_window_s`` of refill)
        and shed them with the structured reason ``"quota_exceeded"``.
        Preempted-back requests (``seq.tokens`` non-empty) are never
        shed — same work-already-under-way rule as
        :meth:`shed_expired`. No-op without a policy."""
        if self.policy is None:
            return []
        now = self.config.now_fn() if now is None else now
        shed = []
        for i in self.policy.shed_candidates(self.waiting, now=now):
            s = self.waiting[i]
            if s.tokens:
                continue
            s.status = SequenceStatus.SHED
            s.shed_reason = "quota_exceeded"
            del self.waiting[i]
            shed.append(s)
        return shed

    def prefetch_candidates(self, limit: int) -> list:
        """Seq ids of the first ``limit`` PARKED sequences in queue
        order — the restores the next admission round will want. The
        engine issues cursor-ahead staging for these at the END of each
        step, so by the time admission claims them the background
        thread has had a full step of compute to overlap."""
        out = []
        for s in self.waiting:
            if len(out) >= limit:
                break
            if s.seq_id in self.pool:
                out.append(s.seq_id)
        return out

    # ---- ragged step assembly ----
    def preempt(self, seq: Sequence):
        """Free the sequence's page mappings and requeue it (recompute
        mode) at the FRONT of the queue; generated tokens are
        preserved, ``cached_len`` resets — re-admission re-chunks
        prompt+generated through the same ragged step."""
        self.running.remove(seq)
        self.pool.free(seq.seq_id)
        seq.cached_len = 0
        seq.status = SequenceStatus.WAITING
        seq.num_preemptions += 1
        seq.enqueued_at = self.config.now_fn()
        self.waiting.appendleft(seq)
        self.last_preempted.append(seq)
        if self.metrics is not None:
            self.metrics.preemptions.inc()

    def park(self, seq: Sequence):
        """Two-tier preemption (serving/kv_tier.py): spill the victim's
        exclusive pages to the host arena and requeue it at the queue
        FRONT with ``cached_len`` INTACT — re-admission restores the
        exact bytes instead of recomputing the prefix. Everything else
        mirrors :meth:`preempt` (same counters, same requeue position),
        so the client-visible lifecycle is identical and greedy tokens
        stay bit-identical either way."""
        self.running.remove(seq)
        self.pool.park(seq.seq_id)
        seq.status = SequenceStatus.WAITING
        seq.num_preemptions += 1
        seq.enqueued_at = self.config.now_fn()
        self.waiting.appendleft(seq)
        self.last_preempted.append(seq)
        if self.metrics is not None:
            self.metrics.preemptions.inc()

    def _relieve_pressure(self, seq: Sequence) -> bool:
        """One pressure-relief move after :class:`PoolExhausted`, in
        cost order: deepen the cold spill of already-parked sequences
        (costs nothing semantically) -> park the victim into the host
        tier (exact-byte restore later) -> classic recompute preemption
        (the arena is full or the victim has nothing spillable).
        Returns True to retry the claim, False when ``seq`` itself was
        evicted (the caller's planning loop drops the row)."""
        pool = self.pool
        if hasattr(pool, "spill_cold") and pool.spill_cold() > 0:
            return True
        victim = self._pick_victim(exclude=seq)
        target = victim if victim is not None else seq
        if hasattr(pool, "can_park") and pool.can_park(target.seq_id):
            self.park(target)
        else:
            self.preempt(target)
        return target is not seq

    def finish(self, seq: Sequence, status=SequenceStatus.FINISHED):
        seq.status = status
        if seq in self.running:
            self.running.remove(seq)
        elif any(s is seq for s in self.waiting):
            # mid-flight aborts can finalize a WAITING sequence (e.g. a
            # preempted-back row whose e2e deadline passed in the queue)
            self.waiting = deque(s for s in self.waiting if s is not seq)
        if seq.seq_id in self.pool:
            self.pool.free(seq.seq_id)

    def prepare_burst(self, burst_tokens: int) -> BurstPlan | None:
        """Plan an on-device generation burst, or None when ineligible.

        Eligible only when EVERY running sequence is a caught-up decode
        row (its whole prompt committed, exactly one uncached token):
        prefill chunks need per-chunk host packing, so any in-flight
        prompt falls back to the per-step ragged path. Claims (and
        CoWs) each row's pages for up to ``min(burst_tokens,
        remaining_new_tokens)`` appends up front — the burst loop never
        crosses into an unowned page — preempting latest arrivals when
        the pool runs dry, exactly like :meth:`prepare_step`. Rows the
        planning itself preempts drop out of the burst (they re-chunk
        through per-step on re-admission)."""
        self.last_preempted = []
        if burst_tokens <= 1 or not self.running:
            return None
        for s in self.running:
            if s.uncached_len != 1 or s.cached_len < len(s.prompt_ids):
                return None
        rows, cow = [], 0
        for seq in list(self.running):
            if seq.status is not SequenceStatus.RUNNING:
                continue                      # preempted by an earlier row
            cap = min(burst_tokens, seq.remaining_new_tokens)
            while True:
                try:
                    cow += self.pool.prepare_append(
                        seq.seq_id, seq.cached_len + cap)
                    break
                except PoolExhausted:
                    # shrink before shooting: a shorter burst that fits
                    # the row's already-owned pages beats preempting a
                    # neighbor into a full re-prefill (the per-step
                    # path's 1-token grant, generalized)
                    fit = len(self.pool.block_table(seq.seq_id)) \
                        * self.pool.page_size - seq.cached_len
                    if 1 <= fit < cap:
                        cap = fit
                        continue
                    if not self._relieve_pressure(seq):
                        break
            if seq.status is SequenceStatus.RUNNING:
                rows.append((seq, cap))
        # a LATER row's PoolExhausted retry can pick an already-planned
        # row as its preemption victim — drop stale rows (their pool
        # entries are freed) instead of handing _launch_burst a
        # sequence with no block table (prepare_step's rebuild-from-
        # running discipline)
        rows = [(s, c) for s, c in rows
                if s.status is SequenceStatus.RUNNING]
        if not rows:
            return None
        return BurstPlan(rows, burst_len=max(cap for _, cap in rows),
                         cow_copies=cow)

    def prepare_spec(self, k: int) -> StepPlan | None:
        """Plan a speculative-verification round, or None when ineligible.

        Eligible only when EVERY running sequence is a caught-up decode
        row (like :meth:`prepare_burst`): each row gets ``q_len =
        spec_len + 1`` query tokens — its one uncached token plus
        ``spec_len = min(k, remaining - 1)`` draft candidates — so the
        whole round is one prefill-shaped launch of the SAME ragged
        executable. Pages are claimed (and CoW'd) for the full ``k+1``
        appends up front; the engine rolls the committed length back to
        what verification actually accepted.

        ``spec_len`` deliberately depends ONLY on the request's own
        state (k and remaining_new_tokens), never on pool pressure or
        co-scheduling — shrinking it under pressure would change which
        PRNG stream positions get drafted vs directly sampled and break
        the bit-reproducibility contract. Pressure is answered the
        per-step way: preempt latest arrivals (recompute replays the
        same streams)."""
        self.last_preempted = []
        if k < 1 or not self.running:
            return None
        for s in self.running:
            if s.uncached_len != 1 or s.cached_len < len(s.prompt_ids):
                return None
        cfg = self.config
        qb = cfg.q_block
        rows, cow = [], 0
        for seq in list(self.running):
            if seq.status is not SequenceStatus.RUNNING:
                continue                  # preempted by an earlier row
            spec = min(k, seq.remaining_new_tokens - 1)
            while True:
                try:
                    cow += self.pool.prepare_append(
                        seq.seq_id, seq.cached_len + spec + 1)
                    break
                except PoolExhausted:
                    if not self._relieve_pressure(seq):
                        break
            if seq.status is SequenceStatus.RUNNING:
                rows.append((seq, spec))
        rows = [(s, c) for s, c in rows
                if s.status is SequenceStatus.RUNNING]
        if not rows:
            return None
        plan_rows, spec_lens, cursor = [], [], 0
        for seq, spec in rows:
            plan_rows.append((seq, cursor, spec + 1))
            spec_lens.append(spec)
            cursor += -(-(spec + 1) // qb) * qb
        assert cursor <= cfg.step_token_budget, \
            "spec round overflows the step token budget (engine init " \
            "must size the budget for max_num_seqs x (k+1))"
        return StepPlan(plan_rows, num_slots=self.max_num_seqs,
                        token_budget=cfg.step_token_budget,
                        cow_copies=cow, spec_lens=spec_lens)

    def prepare_step(self) -> StepPlan | None:
        """Grant each running sequence its step-token share, grow/CoW its
        pages to cover the granted tokens (preempting latest arrivals
        when the pool runs dry), then pack the fixed-shape ragged plan."""
        cfg = self.config
        qb = cfg.q_block
        self.last_preempted = []
        self._granted = {}
        cow = 0
        budget_left = cfg.step_token_budget
        pending = list(self.running)
        for idx, seq in enumerate(pending):
            # preemption flips status to WAITING immediately, so a status
            # check is an O(1) liveness test (no dataclass-__eq__ list
            # membership scans in the per-step hot path)
            if seq.status is not SequenceStatus.RUNNING:
                continue
            # reserve one q_block for every not-yet-granted row behind us
            # so a fat prefill chunk can never starve their decode slots
            behind = sum(qb for s in pending[idx + 1:]
                         if s.status is SequenceStatus.RUNNING)
            allowed = budget_left - behind
            q_len = min(seq.uncached_len, cfg.chunk_size, allowed)
            assert q_len >= 1, "budget must cover q_block per running row"
            while True:
                try:
                    cow += self.pool.prepare_append(
                        seq.seq_id, seq.cached_len + q_len)
                    break
                except PoolExhausted:
                    # spill-cold -> park victim -> recompute-preempt.
                    # False = THIS sequence was evicted (add()
                    # guaranteed prompt+max_new fits the empty pool, so
                    # its re-admission always converges).
                    if not self._relieve_pressure(seq):
                        break
            if seq.status is SequenceStatus.RUNNING:
                self._granted[seq.seq_id] = q_len
                budget_left -= -(-q_len // qb) * qb
        if not self.running:
            return None
        rows, cursor = [], 0
        for seq in self.running:
            q_len = self._granted[seq.seq_id]
            rows.append((seq, cursor, q_len))
            cursor += -(-q_len // qb) * qb
        assert cursor <= cfg.step_token_budget
        return StepPlan(rows, num_slots=self.max_num_seqs,
                        token_budget=cfg.step_token_budget, cow_copies=cow)

    def _pick_victim(self, exclude: Sequence) -> Sequence | None:
        candidates = [s for s in self.running if s is not exclude]
        if not candidates:
            return None
        return max(candidates, key=lambda s: s.arrival)


__all__ = ["BurstPlan", "Scheduler", "SchedulerConfig", "Sequence",
           "SequenceStatus", "StepPlan", "bucket_for"]
