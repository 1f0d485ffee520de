"""Continuous batching: the host-side admit/evict/pad loop.

The compiled decode step always runs the full ``[max_batch]`` row
block; this scheduler is everything around it — an open-loop request
queue, slot assignment (a finished request's row goes
straight to the next arrival), per-request sequence budgets from
``seq_buckets``, and the pad arrays that keep inactive rows
shape-stable. None of it touches a jit boundary, so admission, buckets
and eviction are recompile-free by construction (and the engine's
compile counters prove it).

Buckets: a request's budget is the smallest ``seq_bucket`` that fits
``prompt + max_new_tokens`` (clamped to the largest). The bucket caps
how far the row may fill — a metadata cap, deliberately NOT a compiled
shape — so short requests get admission-control/accounting granularity
without buying per-bucket XLA programs.

Every decode step emits one ``decode_step`` telemetry event (tokens
produced, live batch, occupancy, queue depth, host wall) through the
session, feeding ``ds_tpu_metrics summary``'s serve mode, and every
completion one ``request_done`` event (queue wait, time to first token,
latency).

**The admission round.** ``step()`` returns whenever it has made a
token a caller can read. A *round* begins at a ``step()`` that finds a
free row and a due request at the head of the queue; its members are
the requests queued at that moment, in order, as far as free rows go.
Each ``step()`` of the round admits ONE member (pages, the whole
prefill, the first token) and returns; when no member is left (or the
pool refuses one, which stays queued) the next ``step()`` is the
round's decode over the live rows, and ends it. A request submitted
after a round began waits for the next one. A ``step()`` with nothing
to admit is a decode step (or an idle tick) and nothing else. The
engine sees the calls it would see if a round were one call: the same
prefills in the same order, then the decode; only the returns between
them are new. ``step_count`` advances at a decode or an idle tick
only, so ``Request.arrival_step``, ``Completion.steps``, the fault
seams' step numbers and ``run(max_steps=...)`` count decode steps, not
returns.

**Stamps and spans** (always on; `telemetry/spans.py` has the clock and
the ring). Every request is stamped on ``telemetry.spans.clock``:
``arrival_t`` (the caller's, else ``submit_t``), ``submit_t``,
``admit_t`` (taken off the queue), ``first_token_t`` (``sample_first``
has returned: the token exists on the host), ``first_return_t`` (the
``step()`` that admitted the request returns, before any other engine
call is launched: the first moment a caller of ``step()`` can read the
token, so ``first_return_t - first_token_t`` is the slot's booking and
the return, not a decode step), ``token_t`` (one per generated token)
and ``finish_t``; they ride on :class:`Completion` and, at finish, on
one ``serve/request`` record in the span ring. Every ``step()`` call
is one ``serve/step`` span whose attrs carry that return's counters,
read at its end (``step``, the decode step count it began at, shared
by the returns of one round; ``admitted``, 0 or 1, the requests this
return admitted; ``round``, the round's ordinal, on the returns of a
round; on a return that decoded or ticked idle ``round_prefills`` and
``round_prefill_s``, the prefills of the round it ended and the
seconds of those that ran with a row waiting in decode, 0 where it
ended none; ``live_rows``, ``batch``, ``max_batch``, ``queue_depth``,
``tokens``, ``pages_live``, ``pages_resident``, ``pages_total``;
``gc_s``, the collector's seconds over the call; and, on a return that
closes 50 ms or more after the last one that had them, ``cpu_s`` /
``cpu_wall_s``, the thread's CPU and wall seconds since then
(``spans.CpuMark``): by these a stall is read afterwards). Its
children, on a round's first return ``expire``, on an admitting return
``admit`` (attrs ``rid`` and ``rows_waiting``, the rows that hold a
request in decode while this one's prompt is prefilled; under it
``pages``, the engine's ``prefill``, which takes both over, and
``sample``), and on a decoding return (``expire`` first where it is no
round's) ``grow``, ``inputs``, the engine's ``decode`` (under
it ``upload``, ``dispatch``, ``wait_tokens``: the step's tokens come
home and its logits stay on the device, which nothing here reads; a
speculative engine has ``draft`` and ``verify`` instead) and ``book``.

The scheduler delegates page mapping to
`inference/paging.py:PagedCacheManager`:
admission walks the radix prefix cache (shared pages mapped, prefill
resumed mid-prompt), each decode step grows rows' mappings page by
page, and a finished request carrying a ``session_id`` parks its pages
(device first, host RAM under pressure) instead of freeing them. All
of it stays host-side: the compiled decode step just receives the
``[max_batch, pages_per_row]`` tables the manager maintains.
"""

import collections
import dataclasses
from typing import List, Optional

import numpy as np

from deepspeed_tpu.runtime.resilience import fault_injection
from deepspeed_tpu.telemetry.spans import (
    CpuMark, Span, clock, collector, record)


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival_step``>0 holds the request
    back until the scheduler's decode step count reaches it: a *test*
    clock (deterministic synthetic streams for tests and smokes), not a
    load model, since a slower server is then offered less load.
    ``arrival_t`` is when the request reached the system on
    ``telemetry.spans.clock``, where the caller knows it (a front end's
    receive time, a load generator's due time); ``submit()`` defaults
    it to ``submit_t``. ``session_id`` (paged engines) parks the
    request's KV pages
    at completion so a follow-up request on the same session resumes
    without re-prefilling its history.

    Robustness knobs (ISSUE 17): ``deadline_s`` bounds the request's
    TOTAL wall clock from first submit to completion, ``queue_timeout_s``
    bounds its wait for a cache row — either expiry finishes it with the
    typed ``timeout`` reason instead of letting it stall the stream.
    ``redispatched``/``restarts`` are stamped by the fleet router when a
    replica death forces a re-prefill elsewhere; ``submit_t`` is
    ``telemetry.spans.clock`` at FIRST submit and survives redispatch,
    so the
    deadline spans retries (exactly-once completion semantics over
    at-least-once execution)."""
    rid: str
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    arrival_step: int = 0
    session_id: Optional[str] = None
    deadline_s: Optional[float] = None
    queue_timeout_s: Optional[float] = None
    redispatched: int = 0       # replica-death redispatches (router)
    restarts: int = 0           # total re-executions (router)
    submit_t: Optional[float] = None
    arrival_t: Optional[float] = None


# a request's stamps, as ``Completion`` and its ``serve/request`` record
# carry them
STAMPS = ("arrival_t", "submit_t", "admit_t", "first_token_t",
          "first_return_t", "token_t", "finish_t")


def _minus(a, b):
    return None if a is None or b is None else a - b


@dataclasses.dataclass
class Completion:
    rid: str
    prompt_len: int
    tokens: List[int]           # generated ids (includes eos when hit)
    finish_reason: str          # "max_new_tokens" | "eos" | "length" |
                                # "timeout" | "incomplete"
    bucket: int
    slot: int                   # -1: never held a row (queued timeout)
    steps: int                  # decode steps this request was live for
    prefix_hit: bool = False    # admitted on shared radix pages
    resumed: bool = False       # admitted by resuming a parked session
    prefill_chunks: int = 0     # prefill chunks actually run
    prefill_chunks_skipped: int = 0
    redispatched: int = 0       # times redispatched across replicas
    restarts: int = 0           # times its execution restarted
    # stamps on telemetry.spans.clock (None: it never got that far)
    arrival_t: Optional[float] = None
    submit_t: Optional[float] = None
    admit_t: Optional[float] = None         # taken off the queue
    first_token_t: Optional[float] = None   # exists on the host
    first_return_t: Optional[float] = None  # its admitting step() returned
    token_t: List[float] = dataclasses.field(default_factory=list)
    finish_t: Optional[float] = None

    # the stamps as durations, seconds (None where a stamp is missing)
    @property
    def queue_wait_s(self):
        return _minus(self.admit_t, self.submit_t)

    @property
    def ttft_s(self):
        """Arrival to the first moment a caller of ``step()`` can read
        the first token."""
        return _minus(self.first_return_t, self.arrival_t)

    @property
    def hold_s(self):
        """How long the finished first token waited inside ``step()``:
        the slot's booking and the return."""
        return _minus(self.first_return_t, self.first_token_t)

    @property
    def latency_s(self):
        return _minus(self.finish_t, self.arrival_t)


@dataclasses.dataclass
class _Slot:
    request: Request
    bucket: int
    next_pos: int               # position the pending token feeds at
    pending: int                # last sampled token (next decode input)
    generated: List[int]
    admitted_step: int
    paging: object = None       # the row's RowPaging
    admit_t: Optional[float] = None
    first_return_t: Optional[float] = None
    # one stamp per generated token; the first is the first token's
    token_t: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Round:
    """An admission round in progress (see the module's head)."""
    ordinal: int
    left: int                   # members not yet admitted
    scan: int = 0               # the row the search for a free one resumes at
    prefills: int = 0           # members admitted so far
    prefill_s: float = 0.0      # their prefills' seconds with a row waiting


class ContinuousBatchingScheduler:
    def __init__(self, engine, session=None):
        self.engine = engine
        self.session = session if session is not None else engine.session
        self.queue = collections.deque()
        self.slots = [None] * engine.max_batch
        self.step_count = 0             # decode steps and idle ticks
        self.rounds = 0                 # admission rounds begun
        self._round = None              # the round in progress
        self.completions = []
        # completions made inside the step() that admitted them: their
        # first token is not out before that call returns
        self._unreturned = []
        self._step_attrs = {}           # the running step's counters
        self._cpu_mark = CpuMark()
        from deepspeed_tpu.inference.paging import PagedCacheManager
        with Span("setup/engine/paging"):
            self.paging = PagedCacheManager(engine, session=self.session)

    # -- request lifecycle --------------------------------------------------

    def submit(self, request):
        if not request.prompt:
            raise ValueError(f"request {request.rid}: empty prompt")
        if len(request.prompt) >= self.engine.max_seq:
            raise ValueError(
                f"request {request.rid}: prompt length "
                f"{len(request.prompt)} does not fit the largest seq "
                f"bucket {self.engine.max_seq}")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"request {request.rid}: max_new_tokens must be >= 1")
        # park/resume knows pages only: a session on a model with a
        # recurrent state is refused here, typed, not served cold
        self.paging._refuse_session(request.session_id)
        if request.submit_t is None:    # survives redispatch resubmits
            request.submit_t = clock()
        if request.arrival_t is None:
            request.arrival_t = request.submit_t
        self.queue.append(request)

    def admit_prefilled(self, request, row, first_token):
        """Seed a slot from ANOTHER tier's finished prefill
        (disaggregated serving, ISSUE 20): the prompt's KV already sits
        in this engine's pool on ``row``'s pages (installed by the KV
        handoff) and ``first_token`` was sampled from the prefill-tier
        logits, so admission here runs NO prefill call — the decode
        tier's prefill program stays at zero jit-cache entries. Returns
        False when no slot is free (the caller keeps the handoff
        queued)."""
        for i in range(len(self.slots)):
            if self.slots[i] is not None:
                continue
            # the token was made on another tier: it is here, and
            # readable by the caller, as of now
            now = clock()
            if request.submit_t is None:
                request.submit_t = now
            if request.arrival_t is None:
                request.arrival_t = request.submit_t
            self.paging.adopt(row)
            self.slots[i] = _Slot(
                request=request, bucket=self._bucket_for(request),
                next_pos=len(request.prompt), pending=first_token,
                generated=[first_token],
                admitted_step=self.step_count, paging=row,
                admit_t=now, first_return_t=now, token_t=[now])
            # eos / single-token budgets can finish right here, exactly
            # where the colocated loop's post-admission check fires.
            self._check_finished(i)
            return True
        return False

    def _bucket_for(self, request):
        need = len(request.prompt) + request.max_new_tokens
        for b in self.engine.seq_buckets:
            if need <= b:
                return b
        return self.engine.max_seq      # clamp: generation truncates

    def _finish(self, i, reason):
        s = self.slots[i]
        comp = Completion(
            rid=s.request.rid, prompt_len=len(s.request.prompt),
            tokens=list(s.generated), finish_reason=reason, bucket=s.bucket,
            slot=i, steps=self.step_count - s.admitted_step,
            redispatched=s.request.redispatched,
            restarts=s.request.restarts,
            arrival_t=s.request.arrival_t, submit_t=s.request.submit_t,
            admit_t=s.admit_t, first_token_t=s.token_t[0],
            first_return_t=s.first_return_t, token_t=s.token_t,
            finish_t=clock())
        comp.prefix_hit = s.paging.prefix_hit
        comp.resumed = s.paging.resumed
        comp.prefill_chunks = s.paging.prefill_chunks
        comp.prefill_chunks_skipped = s.paging.prefill_chunks_skipped
        # KV on the pages covers the prompt plus every generated
        # token that fed a later decode step (the LAST sampled
        # token was never written — nothing attended past it).
        kv_tokens = list(s.request.prompt) + s.generated[:-1]
        self.paging.release(s.paging, kv_tokens=kv_tokens,
                            session_id=s.request.session_id)
        self.completions.append(comp)
        self.slots[i] = None            # row free again
        if comp.first_return_t is None:
            self._unreturned.append(comp)   # step() stamps and records
        else:
            self._record_request(comp)

    def _finish_unstarted(self, request, reason):
        """Record a completion for a request that never held a row
        (queued timeout / max_steps exhaustion)."""
        comp = Completion(
            rid=request.rid, prompt_len=len(request.prompt), tokens=[],
            finish_reason=reason, bucket=self._bucket_for(request),
            slot=-1, steps=0, redispatched=request.redispatched,
            restarts=request.restarts, arrival_t=request.arrival_t,
            submit_t=request.submit_t, finish_t=clock())
        self.completions.append(comp)
        self._record_request(comp)

    def _record_request(self, comp):
        """One ``serve/request`` record in the span ring (``token_t`` by
        reference) and, with a session, one ``request_done`` event."""
        record("serve/request", comp.arrival_t, comp.finish_t, {
            "rid": comp.rid, "prompt_len": comp.prompt_len,
            "finish_reason": comp.finish_reason,
            **{k: getattr(comp, k) for k in STAMPS}})
        if self.session is not None:
            self.session.emit(
                "request_done", rid=comp.rid,
                finish_reason=comp.finish_reason,
                prompt_len=comp.prompt_len, tokens=len(comp.tokens),
                queue_wait_s=comp.queue_wait_s, ttft_s=comp.ttft_s,
                hold_s=comp.hold_s, latency_s=comp.latency_s,
                token_gaps_s=[round(b - a, 6) for a, b in
                              zip(comp.token_t[1:], comp.token_t[2:])])

    def _stamp_returned(self):
        """The last thing ``step()`` does: the first token of the
        request this call admitted can be read from now on, and no
        engine call has been launched since it was sampled."""
        now = clock()
        for s in self.slots:
            if s is not None and s.first_return_t is None:
                s.first_return_t = now
        for comp in self._unreturned:
            # finished on its first token: the caller sees token and
            # completion together, now
            comp.first_return_t = comp.finish_t = now
            self._record_request(comp)
        self._unreturned.clear()

    def _check_finished(self, i):
        s = self.slots[i]
        if s.request.eos_id is not None and \
                s.pending == s.request.eos_id:
            self._finish(i, "eos")
        elif len(s.generated) >= s.request.max_new_tokens:
            self._finish(i, "max_new_tokens")
        elif s.next_pos >= s.bucket:
            # bucket budget exhausted: evict (truncated generation)
            self._finish(i, "length")

    def _expire(self):
        """Typed ``timeout`` finishes: queued requests past their queue
        timeout (or total deadline) drop WITHOUT ever taking a row, and
        live rows past their deadline finish with whatever they
        generated so far."""
        now = clock()

        def _queued_expired(r):
            waited = now - r.submit_t if r.submit_t is not None else 0.0
            return ((r.queue_timeout_s is not None and
                     waited > r.queue_timeout_s) or
                    (r.deadline_s is not None and waited > r.deadline_s))

        expired = [r for r in self.queue if _queued_expired(r)]
        if expired:
            self.queue = collections.deque(
                r for r in self.queue if not _queued_expired(r))
        for r in expired:
            self._finish_unstarted(r, "timeout")
            if self.session is not None:
                self.session.emit("request_timeout", rid=r.rid,
                                  where="queue", step=self.step_count)
        for i, s in enumerate(self.slots):
            if s is None or s.request.deadline_s is None or \
                    s.request.submit_t is None:
                continue
            if now - s.request.submit_t > s.request.deadline_s:
                self._finish(i, "timeout")
                if self.session is not None:
                    self.session.emit("request_timeout",
                                      rid=s.request.rid, where="decode",
                                      step=self.step_count)

    def _begin_round(self):
        """The admission round this ``step()`` begins, or None where no
        row is free or no due request heads the queue. Its members are
        the head of the queue as it stands, as far as free rows go."""
        free = sum(s is None for s in self.slots)
        left = 0
        for req in self.queue:
            if left == free or req.arrival_step > self.step_count:
                break
            left += 1
        if not left:
            return None
        self.rounds += 1
        return _Round(ordinal=self.rounds, left=left)

    def _admit_next(self, rnd):
        """Admit the round's next member into the next free row. False
        where the pool cannot back its prompt (it stays queued and the
        round admits no more)."""
        # the next free row: the round began with one a member, and a
        # row freed by a member that finished on its first token is not
        # taken again in this round
        i = rnd.scan
        while self.slots[i] is not None:
            i += 1
        rnd.scan = i + 1
        rnd.left -= 1
        req = self.queue[0]
        attrs = {"rid": req.rid, "rows_waiting": sum(
            s is not None for s in self.slots)}
        with Span("admit", self.session, attrs):
            prefill_s = self._admit_one(i, req)
            if prefill_s is None:
                # pool can't back the prompt right now even after the
                # eviction ladder — leave the request queued and let
                # running rows finish and free pages.
                attrs["admitted"] = False
                return False
        rnd.prefills += 1
        if attrs["rows_waiting"]:
            rnd.prefill_s += prefill_s
        return True

    def _admit_one(self, i, req):
        """Take ``req`` off the queue into row ``i``: pages, prefill,
        first token. Returns the prefill call's seconds; None (and the
        request stays queued) when the pool cannot back its prompt."""
        session = self.session
        with Span("pages", session):
            row = self.paging.admit(req.prompt, session_id=req.session_id,
                                    slot=i)
        if row is None:
            return None
        self.queue.popleft()
        admit_t = clock()
        last_logits = self.engine.prefill(
            i, req.prompt, page_table=row.table(self.paging.table_width),
            start=row.start)
        prefill_s = clock() - admit_t
        self.paging.after_prefill(row, req.prompt)
        with Span("sample", session):
            first = self.engine.sample_first(last_logits)
        self.slots[i] = _Slot(
            request=req, bucket=self._bucket_for(req),
            next_pos=len(req.prompt), pending=first,
            generated=[first], admitted_step=self.step_count,
            paging=row, admit_t=admit_t, token_t=[clock()])
        self._step_attrs["tokens"] += 1
        self._check_finished(i)
        return prefill_s

    # -- the decode loop ----------------------------------------------------

    def step(self):
        """Make the next tokens a caller can read, and return: admit ONE
        member of the admission round (its prefill and its first token,
        which ``slot.generated`` or its completion holds when this
        returns, before any other engine call is launched), or, with no
        member left to admit, run one compiled decode step over the
        live rows (see the module's head). Returns True while there is
        (or will be) work left. With a speculative engine the decode is
        a whole draft/verify round and rows advance by a VARIABLE number
        of tokens (their accepted length) — see :meth:`_spec_step`."""
        attrs = self._step_attrs = {
            "step": self.step_count, "max_batch": self.engine.max_batch,
            "batch": 0, "tokens": 0, "admitted": 0}
        gc0 = collector.seconds
        try:
            with Span("serve/step", self.session, attrs):
                try:
                    return self._step()
                finally:
                    # the collector's seconds over the step and, every
                    # 50 ms or more, the thread's CPU seconds beside the
                    # wall's: wall without CPU is a blocked or
                    # descheduled thread
                    attrs["gc_s"] = collector.seconds - gc0
                    self._cpu_mark.stamp(attrs)
                    # the call's counters, read where they are true:
                    # at its end, as a caller polling after it would
                    attrs["live_rows"] = sum(
                        s is not None for s in self.slots)
                    attrs["queue_depth"] = len(self.queue)
                    alloc = self.paging.allocator
                    attrs["pages_live"] = self.paging.pages_live
                    attrs["pages_resident"] = alloc.resident_pages
                    attrs["pages_total"] = alloc.n_pages - 1
                    if self.paging.ring_pages:
                        # by group: kv_pages_live_full, kv_bytes_live_window
                        groups = self.paging.group_facts()
                        for name, g in groups.items():
                            attrs[f"kv_pages_live_{name}"] = g["pages_live"]
                            attrs[f"kv_bytes_live_{name}"] = g["bytes_live"]
                        attrs["kv_bytes_live"] = sum(
                            g["bytes_live"] for g in groups.values())
                    if self.paging.recurrent:
                        attrs["state_rows_live"] = \
                            self.paging.state_rows_live
                        attrs["state_rows_total"] = \
                            self.paging.state_rows_total
                        attrs["state_bytes_live"] = \
                            self.paging.state_bytes_live
        finally:
            self._stamp_returned()

    def _inputs(self, active):
        """The decode step's numpy inputs: pending token, position and
        page table of every live row."""
        mb = self.engine.max_batch
        tokens = np.zeros(mb, np.int32)
        positions = np.zeros(mb, np.int32)
        for i in active:
            tokens[i] = self.slots[i].pending
            positions[i] = self.slots[i].next_pos
        page_tables = np.zeros((mb, self.paging.table_width), np.int32)
        for i in active:
            page_tables[i] = self.slots[i].paging.table(
                self.paging.table_width)
        return tokens, positions, page_tables

    def _step(self):
        session = self.session
        attrs = self._step_attrs
        rnd = self._round
        if rnd is None:
            with Span("expire", session):
                self._expire()
            rnd = self._round = self._begin_round()
        if rnd is not None:
            attrs["round"] = rnd.ordinal
            if rnd.left and self._admit_next(rnd):
                attrs["admitted"] = 1
                return True
            # no member left, or one refused: the round's decode
            self._round = None
        attrs["round_prefills"] = rnd.prefills if rnd else 0
        attrs["round_prefill_s"] = rnd.prefill_s if rnd else 0.0
        if getattr(self.engine, "speculative", None) is not None:
            return self._spec_step(self.engine.speculative)
        # grow each live row's page mapping to cover this step's write
        # BEFORE building the tables; a row the pool can't grow even
        # after the eviction ladder is length-finished (same truncation
        # contract as a bucket edge).
        with Span("grow", session):
            for i, s in enumerate(self.slots):
                if s is not None and \
                        not self.paging.ensure_position(s.paging,
                                                        s.next_pos):
                    self._finish(i, "length")
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            self.step_count += 1        # idle tick (open-loop gap)
            return bool(self.queue)
        with Span("inputs", session):
            tokens, positions, page_tables = self._inputs(active)
        # fault-injection seams: a hard kill (SIGKILL — the process just
        # dies with admitted sessions' KV un-drained) and the soft
        # decode exception, both no-ops unless a harness armed them.
        fault_injection.maybe_kill("decode_step", self.step_count)
        fault_injection.maybe_fail_decode(self.step_count)
        t_in = clock()
        next_tokens, _ = self.engine.decode(tokens, positions,
                                            page_tables=page_tables)
        t_tokens = clock()      # the host has this step's tokens
        self.step_count += 1
        with Span("book", session):
            for i in active:
                s = self.slots[i]
                s.next_pos += 1
                s.pending = int(next_tokens[i])
                s.generated.append(s.pending)
                s.token_t.append(t_tokens)
                self._check_finished(i)
            self._step_attrs["batch"] = len(active)
            self._step_attrs["tokens"] += len(active)
            self._emit(len(active), t_tokens - t_in)
        return bool(self.queue) or any(s is not None for s in self.slots)

    def _spec_step(self, spec):
        """One speculative round: j chained draft calls + one
        verify-accept call, then a per-row consume walk over the
        variable-length accepted blocks.

        Row discipline: a row must have ``k + 1`` slots of physical
        headroom before the round (the verify chunk writes positions
        ``next_pos..next_pos+k``; past ``max_seq`` the page-table
        lookup would clamp to the row's last page and overwrite valid
        history) — rows inside that margin length-finish now, the same
        truncation contract as a bucket edge, at most k tokens early.
        Rows also grow their mapping to cover every potentially-
        ACCEPTED write (``next_pos + j``); pad writes past the mapping
        land on the trash page by the PR 16 discipline."""
        k = spec.k
        j = spec.draft_len()
        for i, s in enumerate(self.slots):
            if s is not None and \
                    s.next_pos + k + 1 > self.engine.max_seq:
                self._finish(i, "length")
        with Span("grow", self.session):
            for i, s in enumerate(self.slots):
                if s is not None and not self.paging.ensure_span(
                        s.paging, s.next_pos, s.next_pos + j):
                    self._finish(i, "length")
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            self.step_count += 1        # idle tick (open-loop gap)
            return bool(self.queue)
        session = self.session
        mb = self.engine.max_batch
        with Span("inputs", session):
            tokens, positions, page_tables = self._inputs(active)
        fault_injection.maybe_kill("decode_step", self.step_count)
        fault_injection.maybe_fail_decode(self.step_count)
        # draft: j chained truncated-forward calls of ONE compiled
        # program (tokens/positions are data; j itself never reaches a
        # jit boundary)
        chunk = np.zeros((mb, k + 1), np.int32)
        chunk[:, 0] = tokens
        q_dists = None
        cur, cur_pos = tokens, positions.copy()
        with Span("draft", session) as draft_span:
            for t in range(j):
                cur, q = spec.draft(cur, cur_pos, page_tables=page_tables)
                chunk[:, t + 1] = cur
                if q is not None:
                    if q_dists is None:
                        q_dists = np.zeros((mb, k, q.shape[-1]),
                                           np.float32)
                    q_dists[:, t] = q
                cur_pos = cur_pos + 1
        # verify: one full-depth teacher-forced call over [B, k+1]
        pos_chunk = positions[:, None] + \
            np.arange(k + 1, dtype=np.int32)[None, :]
        draft_len = np.zeros(mb, np.int32)
        draft_len[active] = j
        with Span("verify", session) as verify_span:
            acc, out = spec.verify(chunk, pos_chunk, draft_len,
                                   q_dists=q_dists,
                                   page_tables=page_tables)
        t_tokens = clock()      # the host has this round's tokens
        self.step_count += 1
        # consume: walk each row's accepted block token by token so
        # eos / token budget / bucket edges bind MID-CHUNK exactly
        # where the non-speculative loop would have stopped
        emitted = accepted = 0
        with Span("book", session):
            for i in active:
                s = self.slots[i]
                accepted += int(acc[i])
                for t in range(int(acc[i]) + 1):
                    s.next_pos += 1
                    s.pending = int(out[i, t])
                    s.generated.append(s.pending)
                    s.token_t.append(t_tokens)
                    emitted += 1
                    self._check_finished(i)
                    if self.slots[i] is None:
                        break
            spec.observe(len(active), len(active) * j, accepted, emitted)
            self._step_attrs["batch"] = len(active)
            self._step_attrs["tokens"] += emitted
            self._emit(len(active),
                       draft_span.duration_s + verify_span.duration_s,
                       tokens=emitted,
                       spec_stats={"accepted_tokens": emitted,
                                   "accepted_drafts": accepted,
                                   "draft_tokens": len(active) * j,
                                   "draft_len": j,
                                   "draft_wall_s": draft_span.duration_s,
                                   "verify_wall_s":
                                       verify_span.duration_s})
        return bool(self.queue) or any(s is not None for s in self.slots)

    def run(self, requests=None, max_steps=100000):
        """Drain ``requests`` (plus anything already queued) through the
        decode loop; returns the completions in finish order.
        ``max_steps`` bounds the decode steps (and idle ticks) run
        here, as ``step_count`` counts them, not the returns of
        ``step()``: an admission is not one.

        Exhausting ``max_steps`` with work still in flight no longer
        returns silently: every live row finishes with the typed
        ``incomplete`` reason (keeping its generated-so-far tokens),
        every still-queued request records an empty ``incomplete``
        completion, and one ``scheduler_incomplete`` warning event makes
        the truncation visible in telemetry."""
        for r in requests or ():
            self.submit(r)
        last = self.step_count + max_steps
        while self.step_count < last and self.step():
            pass
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if live or self.queue:
            self._round = None
            for i in live:
                self._finish(i, "incomplete")
            queued = len(self.queue)
            while self.queue:
                self._finish_unstarted(self.queue.popleft(), "incomplete")
            if self.session is not None:
                self.session.emit(
                    "scheduler_incomplete", level="warning",
                    step=self.step_count, max_steps=max_steps,
                    live_rows=len(live), queued=queued)
        return list(self.completions)

    # -- telemetry ----------------------------------------------------------

    def occupancy(self):
        live = sum(1 for s in self.slots if s is not None)
        return live / float(self.engine.max_batch)

    def _emit(self, batch, wall_s, tokens=None, spec_stats=None):
        if self.session is None:
            return
        occ = batch / float(self.engine.max_batch)
        tokens = batch if tokens is None else tokens
        pg = self.paging
        extra = dict(
            spec_stats or {},
            pages_free=pg.allocator.free_pages,
            pages_resident=pg.allocator.resident_pages,
            pages_live=pg.pages_live,
            prefix_hits=pg.prefix_hits,
            prefix_misses=pg.prefix_misses,
            sessions_admitted=pg.sessions_admitted,
            sessions_parked_host=len(pg.host_store),
            cache_bytes=pg.page_bytes() * pg.engine.n_pages)
        self.session.emit(
            "decode_step", step=self.step_count, tokens=tokens,
            batch=batch, occupancy=occ, queue_depth=len(self.queue),
            wall_s=wall_s, **extra)
        self.session.registry.counter(
            "decode_tokens_total",
            help="tokens generated by decode steps").inc(tokens)
