"""Continuous-batching scheduler: mid-flight admission into fixed slots.

ISSUE 9 pillar 2.  Static batching drains to stragglers — a batch is held
open until its LONGEST request finishes, so short requests pay long
requests' latency and the decode batch empties toward 1.  Continuous
batching (Orca lineage; the discipline the Gemma-on-TPU comparison,
arXiv:2605.25645, identifies as the serving-throughput lever) keeps the
decode batch full instead: requests admit the moment a slot AND their
worst-case KV-block budget are free, finished sequences evict immediately,
and their freed blocks refill the pool for the next admission.

All host-side bookkeeping — the device never sees the queue.  Prompt
padding runs through ``NativeBatcher.gather_pad`` (the GIL-free C++ ragged
gather+pad used by the training loader path), so request packing rides the
same native host runtime as training input assembly.

Slot invariants the compiled decode program relies on:

- every slot always has a block-table row (inactive rows are all
  ``SCRATCH_BLOCK``) and a position/token/context entry — decode runs the
  FULL fixed ``max_seqs`` batch every step, no active-mask branching;
- a live slot's blocks are disjoint from every other slot's, so in-batch
  page writes never collide;
- admission reserves ``ceil((prompt_len + max_new_tokens) / block_size)``
  blocks up front, so a mid-flight decode step can never fail on an empty
  pool.

The engine may dispatch a decode step before it has read the one before
(ISSUE 38).  Everything a batch needs but the tokens is a count the host
has before it sees a token, so a slot counts its tokens in flight
(``_Slot.ahead``): :meth:`Scheduler.decode_batch` builds a step with the
dispatched ones counted as done, a row whose token the host has not seen
feeds ``FROM_DEVICE``, and :meth:`Scheduler.commit_decode` matches each row
to the request it was dispatched with.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from stoke_tpu.native import NativeBatcher
from stoke_tpu.serving.kv_cache import SCRATCH_BLOCK, BlockAllocator
from stoke_tpu.serving.sampling import SamplingParams
from stoke_tpu.serving.slo import RequestSLO
from stoke_tpu.serving.speculative import propose_draft

#: a decode row's token when the host has not read it yet: the decode
#: program takes the row from the token vector the device kept
FROM_DEVICE = -1


@dataclass
class Request:
    """One inference request and its lifecycle timestamps.

    ``tokens`` accumulates the generated ids (the first one comes from
    prefill — its wall time IS the TTFT); ``first_token_ts - arrival_ts``
    and the per-token deltas after it feed the TTFT/TPOT histograms.
    ``params``/``seed`` are the resolved sampling knobs (ISSUE 13): the
    engine resolves defaults at submit, so the scheduler only carries
    them.  ``slo`` is the resolved per-request SLO (ISSUE 16), same
    contract: targets already filled from the ServeConfig defaults, the
    scheduler never interprets it.
    """

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: Optional[int] = None
    params: SamplingParams = field(default_factory=SamplingParams)
    seed: int = 0
    slo: Optional[RequestSLO] = None
    arrival_ts: float = field(default_factory=time.perf_counter)
    admit_ts: Optional[float] = None
    first_token_ts: Optional[float] = None
    finish_ts: Optional[float] = None
    tokens: List[int] = field(default_factory=list)

    @property
    def finished(self) -> bool:
        return self.finish_ts is not None

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.arrival_ts

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time-per-output-token over the decode tokens (excludes the
        prefill token the TTFT already accounts)."""
        if self.finish_ts is None or len(self.tokens) < 2:
            return None
        return (self.finish_ts - self.first_token_ts) / (len(self.tokens) - 1)


@dataclass
class _Slot:
    request: Optional[Request] = None
    blocks: List[int] = field(default_factory=list)
    context_len: int = 0       # cached tokens (prompt + committed decode)
    next_token: int = 0        # token the next decode step feeds
    # tokens of this request that dispatched programs will produce and the
    # host has not read: the prefill's first token, a decode step in flight
    ahead: int = 0
    # chunked prefill (ISSUE 13): prompt tokens already written to the
    # cache; None = prefill complete (the slot decodes).  While a slot is
    # prefilling it occupies capacity but is excluded from decode_batch —
    # its rows run against the scratch table like an inactive slot, so
    # in-flight decode writes can never clobber its half-written prompt.
    prefill_pos: Optional[int] = None


class Scheduler:
    """Continuous-batching request scheduler over a block allocator."""

    def __init__(
        self,
        max_seqs: int,
        allocator: BlockAllocator,
        max_blocks_per_seq: int,
        *,
        max_seq_len: int,
        default_max_new_tokens: int,
        eos_id: Optional[int] = None,
        pad_multiple: int = 64,
        prefill_chunk_tokens: Optional[int] = None,
        sampling_seed_base: int = 0,
        batcher: Optional[NativeBatcher] = None,
    ):
        self.max_seqs = int(max_seqs)
        self.allocator = allocator
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self.max_seq_len = int(max_seq_len)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.eos_id = eos_id
        self.pad_multiple = int(pad_multiple)
        self.prefill_chunk_tokens = (
            None if prefill_chunk_tokens is None else int(prefill_chunk_tokens)
        )
        self.sampling_seed_base = int(sampling_seed_base)
        self.batcher = batcher or NativeBatcher()
        self.queue: Deque[Request] = deque()
        self.slots: List[_Slot] = [_Slot() for _ in range(max_seqs)]
        # fixed-shape decode-side state the engine snapshots every step
        self.block_tables = np.full(
            (max_seqs, max_blocks_per_seq), SCRATCH_BLOCK, np.int32
        )
        self.finished: Dict[int, Request] = {}
        # the (slot, rid) rows of each decode step dispatched and not yet
        # committed, oldest first
        self._in_flight: Deque[List[Tuple[int, int]]] = deque()
        self._next_rid = 0
        self.preempt_denials = 0  # admissions deferred on an empty pool

    # ----------------------------- intake ------------------------------ #

    def submit(
        self,
        prompt,
        max_new_tokens: Optional[int] = None,
        eos_id: Optional[int] = None,
        params: Optional[SamplingParams] = None,
        slo: Optional[RequestSLO] = None,
    ) -> int:
        """Enqueue one request; returns its id.  Requests whose worst case
        cannot fit ``max_seq_len`` are rejected here — a cap the paged
        pool could never honor must fail at submit, not mid-decode."""
        prompt = np.ascontiguousarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        cap = (
            self.default_max_new_tokens
            if max_new_tokens is None
            else int(max_new_tokens)
        )
        if cap < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {cap}")
        if prompt.size + cap > self.max_seq_len:
            raise ValueError(
                f"request needs {prompt.size} prompt + {cap} output tokens "
                f"> max_seq_len={self.max_seq_len}"
            )
        rid = self._next_rid
        self._next_rid += 1
        params = params if params is not None else SamplingParams()
        # seed resolution lives HERE, beside rid assignment: an explicit
        # per-request seed wins, else the deterministic per-request
        # default sampling_seed_base + rid — so whole runs replay from
        # the config and the derivation can never desync from the rid
        seed = (
            params.seed
            if params.seed is not None
            else self.sampling_seed_base + rid
        )
        self.queue.append(
            Request(
                rid=rid,
                prompt=prompt,
                max_new_tokens=cap,
                eos_id=self.eos_id if eos_id is None else eos_id,
                params=params,
                seed=int(seed),
                slo=slo,
            )
        )
        return rid

    # ---------------------------- admission ---------------------------- #

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s.request is not None)

    @property
    def decoding(self) -> int:
        """Slots with a fully-prefilled request — the live decode batch
        (a chunk-prefilling slot occupies capacity but does not decode)."""
        return sum(
            1
            for s in self.slots
            if s.request is not None and s.prefill_pos is None
        )

    @property
    def has_prefilling(self) -> bool:
        return any(s.prefill_pos is not None for s in self.slots)

    @property
    def queued(self) -> int:
        return len(self.queue)

    @property
    def in_flight(self) -> int:
        """Decode steps dispatched and not yet committed."""
        return len(self._in_flight)

    @property
    def has_work(self) -> bool:
        return self.active > 0 or self.queued > 0 or self.in_flight > 0

    @property
    def batch_fill(self) -> float:
        return self.active / max(self.max_seqs, 1)

    def admit(self) -> List[Tuple[int, Request, Optional[np.ndarray], int]]:
        """Admit queued requests (FIFO) while a slot and their block
        budget are free.  Returns ``[(slot, request, padded_prompt,
        prompt_len), ...]`` for the engine to prefill; the padded prompt
        comes from the native ``gather_pad`` path (zero-pad to the
        ``pad_multiple`` bucket that keys the compiled prefill program).

        Chunked prefill (ISSUE 13): when ``prefill_chunk_tokens`` is set
        and the prompt is longer, the slot is admitted in the PREFILLING
        state instead (``padded_prompt`` is None) — the engine pulls
        fixed-size chunks via :meth:`next_chunk` across later iterations,
        interleaved with decode steps, so one long prompt cannot stall
        the in-flight batch."""
        admitted = []
        for i, slot in enumerate(self.slots):
            if not self.queue:
                break
            if slot.request is not None:
                continue
            req = self.queue[0]
            need = self.allocator.blocks_for(
                req.prompt.size + req.max_new_tokens
            )
            blocks = self.allocator.alloc(need)
            if blocks is None:
                # head-of-line blocking by design: admitting a smaller
                # later request over the head would starve long prompts
                self.preempt_denials += 1
                break
            self.queue.popleft()
            req.admit_ts = time.perf_counter()
            slot.request = req
            slot.blocks = blocks
            slot.context_len = int(req.prompt.size)
            self.block_tables[i, :] = SCRATCH_BLOCK
            self.block_tables[i, : len(blocks)] = blocks
            chunk = self.prefill_chunk_tokens
            if chunk is not None and req.prompt.size > chunk:
                slot.prefill_pos = 0
                admitted.append((i, req, None, int(req.prompt.size)))
                continue
            padded, _mask = self.batcher.gather_pad(
                req.prompt,
                np.zeros(1, np.int64),
                np.array([req.prompt.size], np.int32),
                [0],
                pad_multiple=self.pad_multiple,
            )
            admitted.append((i, req, padded, int(req.prompt.size)))
        return admitted

    # ------------------------- chunked prefill -------------------------- #

    def next_chunk(self):
        """The next prompt chunk to prefill, or None.  One chunk per
        engine iteration keeps every iteration's prefill work bounded by
        ``prefill_chunk_tokens`` — the TPOT-flatness guarantee.  The
        OLDEST-admitted prefilling request is serviced first (FIFO over
        admit_ts, not slot index): a later long prompt recycling a lower
        slot must never starve one already mid-prefill.  Returns
        ``(slot, request, tokens [C], positions [C], is_final,
        logit_idx)``: tokens zero-padded to the fixed chunk length (ONE
        compiled chunk program), positions the GLOBAL prompt positions
        (padding rows clamped — their writes steer to scratch, their
        outputs are discarded), ``logit_idx`` the in-chunk row of the
        last prompt token (meaningful only when ``is_final``)."""
        C = self.prefill_chunk_tokens
        prefilling = [
            (s.request.admit_ts, i, s)
            for i, s in enumerate(self.slots)
            if s.prefill_pos is not None
        ]
        if not prefilling:
            return None
        _, i, s = min(prefilling)
        req = s.request
        plen = int(req.prompt.size)
        start = s.prefill_pos
        toks = np.zeros(C, np.int32)
        n = min(C, plen - start)
        toks[:n] = req.prompt[start : start + n]
        positions = np.minimum(
            start + np.arange(C, dtype=np.int32), self.max_seq_len - 1
        )
        is_final = start + C >= plen
        logit_idx = plen - 1 - start if is_final else 0
        return i, req, toks, positions, is_final, logit_idx

    def note_chunk(self, slot: int) -> None:
        """One chunk dispatched for ``slot``: advance the prefill cursor;
        the final chunk completes prefill (the engine then records the
        sampled first token via :meth:`note_prefill_token`, arming
        decode)."""
        s = self.slots[slot]
        s.prefill_pos += self.prefill_chunk_tokens
        if s.prefill_pos >= s.request.prompt.size:
            s.prefill_pos = None

    def next_chunks(self):
        """Packed chunk batch (ISSUE 17): ONE dispatch services every
        prefilling slot's next chunk, shaped ``[max_seqs, C]`` like the
        verify program (fixed batch, per-row positions, scratch-steered
        idle rows) instead of :meth:`next_chunk`'s one-slot-per-iteration
        ``[1, C]``.  Per-iteration prefill work is still bounded — C
        tokens per ROW, and rows were already paying the fixed dispatch
        cost as dead decode slots.  Returns ``None`` when nothing is
        prefilling, else ``(tokens [B, C], positions [B, C], tables
        [B, MB], lengths [B], logit_idx [B], rows)`` — ``lengths`` the
        per-row prompt length (the chunk-mode write predicate; idle rows
        0 so every write steers to scratch), ``rows`` a list of
        ``(slot, request, is_final)`` for the serviced slots."""
        C = self.prefill_chunk_tokens
        B = self.max_seqs
        if not self.has_prefilling:
            return None
        tokens = np.zeros((B, C), np.int32)
        positions = np.tile(np.arange(C, dtype=np.int32), (B, 1))
        lengths = np.zeros(B, np.int32)
        logit_idx = np.zeros(B, np.int32)
        tables = self.block_tables.copy()
        rows = []
        for i, s in enumerate(self.slots):
            if s.prefill_pos is None:
                # idle or decoding row: all-scratch table (a decoding
                # slot's real cache must be unreachable from this
                # dispatch's padding writes), zero length, discarded out
                tables[i, :] = SCRATCH_BLOCK
                continue
            req = s.request
            plen = int(req.prompt.size)
            start = s.prefill_pos
            n = min(C, plen - start)
            tokens[i, :n] = req.prompt[start : start + n]
            positions[i, :] = np.minimum(
                start + np.arange(C, dtype=np.int32), self.max_seq_len - 1
            )
            lengths[i] = plen
            is_final = start + C >= plen
            logit_idx[i] = plen - 1 - start if is_final else 0
            rows.append((i, req, is_final))
        return tokens, positions, tables, lengths, logit_idx, rows

    # ------------------------ speculative decode ------------------------ #

    def verify_batch(self, k: int, *, ngram_max: int, ngram_min: int):
        """Fixed-shape speculative verify inputs (ISSUE 17): each decoding
        slot's pending token plus up to ``k`` drafts from the host-side
        prompt-lookup drafter, as S = k+1 query rows.

        Drafts are truncated to ``remaining - 1`` (cap minus the pending
        token) so a fully-accepted dispatch can never overshoot the
        request's token budget or its admission-reserved blocks.  Idle
        and still-prefilling rows ride along scratch-steered exactly like
        :meth:`decode_batch`'s — zero write budget, all-scratch tables,
        outputs discarded.

        Returns ``(tokens [B, S], positions [B, S], tables [B, MB],
        lengths [B], draft_lens [B])`` — ``lengths`` the verify write
        budget (context + draft + 1), ``draft_lens`` the per-slot valid
        draft counts the accept rule masks with.
        """
        B = self.max_seqs
        S = k + 1
        tokens = np.zeros((B, S), np.int32)
        positions = np.tile(np.arange(S, dtype=np.int32), (B, 1))
        lengths = np.zeros(B, np.int32)
        draft_lens = np.zeros(B, np.int32)
        tables = self.block_tables.copy()
        for i, s in enumerate(self.slots):
            if s.request is None:
                continue
            if s.prefill_pos is not None:
                tables[i, :] = SCRATCH_BLOCK
                continue
            req = s.request
            remaining = req.max_new_tokens - len(req.tokens)
            budget = max(0, min(k, remaining - 1))
            draft = propose_draft(
                np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)]),
                budget,
                ngram_max=ngram_max,
                ngram_min=ngram_min,
            )[:budget]
            tokens[i, 0] = s.next_token
            if draft:
                tokens[i, 1 : 1 + len(draft)] = draft
            positions[i, :] = np.minimum(
                s.context_len + np.arange(S, dtype=np.int32),
                self.max_seq_len - 1,
            )
            lengths[i] = s.context_len + len(draft) + 1
            draft_lens[i] = len(draft)
        return tokens, positions, tables, lengths, draft_lens

    def commit_verify(
        self, targets: np.ndarray, n_emit: np.ndarray, now: float
    ) -> Tuple[np.ndarray, int]:
        """Fold one verify dispatch's outputs into the slots: each live
        slot emits its first ``n_emit[i]`` target tokens (the accepted
        run plus the correction/bonus draw), stopping early at eos —
        eviction frees the whole slot, so over-accepted cache rows past
        an eos die with it.  Returns ``(committed [B], accepted)`` —
        per-slot tokens actually committed (0 for idle rows) and the
        total draft tokens that became output (``committed - 1`` per
        live slot); with :meth:`verify_batch`'s ``draft_lens`` these
        feed the ``serve/spec_*`` counters."""
        committed = np.zeros(self.max_seqs, np.int32)
        accepted = 0
        for i, s in enumerate(self.slots):
            if s.request is None or s.prefill_pos is not None:
                continue
            req = s.request
            for j in range(int(n_emit[i])):
                tok = int(targets[i, j])
                s.context_len += 1  # query row j's K/V is now cached
                req.tokens.append(tok)
                s.next_token = tok
                committed[i] += 1
                if self._done(req):
                    self._finish(i, now)
                    break
            accepted += max(int(committed[i]) - 1, 0)
        return committed, accepted

    # --------------------------- decode state -------------------------- #

    def _rides(self, s: _Slot) -> bool:
        """Whether the slot's request has a row in the next decode step: it
        is fully prefilled and, with everything in flight counted as done,
        still short of its ``max_new_tokens``.  (A request that will end on
        ``eos_id`` is not known to yet: it rides one step too many, and
        :meth:`commit_decode` drops that row.)"""
        req = s.request
        return (
            req is not None
            and s.prefill_pos is None
            and len(req.tokens) + s.ahead < req.max_new_tokens
        )

    @property
    def riding(self) -> int:
        """Rows the next decode step would carry (:meth:`decode_batch`)."""
        return sum(1 for s in self.slots if self._rides(s))

    def decode_batch(self):
        """Fixed-shape decode inputs: ``(tokens [B], positions [B],
        block_tables [B, MB], context_lens [B])``, with every program in
        flight counted as done.  Inactive slots feed token 0 at position 0
        against an all-scratch table; slots still chunk-prefilling get the
        SAME treatment (their real table is swapped for scratch here) so
        the decode step's position-0 write can never clobber their
        half-written prompt K/V, and so does a request whose last token is
        in flight.  A row whose token the host has not read feeds
        ``FROM_DEVICE``.  Changes nothing: the engine calls
        :meth:`note_decode_dispatched` once the step is on its way."""
        B = self.max_seqs
        tokens = np.zeros(B, np.int32)
        positions = np.zeros(B, np.int32)
        context = np.ones(B, np.int32)  # inactive: attend self-only
        tables = self.block_tables.copy()
        for i, s in enumerate(self.slots):
            if s.request is None:
                continue
            if not self._rides(s):
                tables[i, :] = SCRATCH_BLOCK
                continue
            tokens[i] = FROM_DEVICE if s.ahead else s.next_token
            # the fed token is the request's newest: its position follows
            # the prompt and the tokens before it
            positions[i] = (
                s.request.prompt.size + len(s.request.tokens) + s.ahead - 1
            )
            context[i] = positions[i] + 1
        return tokens, positions, tables, context

    def note_decode_dispatched(self) -> List[Tuple[int, int]]:
        """The step :meth:`decode_batch` built is dispatched: its rows'
        tokens are in flight until :meth:`commit_decode` folds them in.
        Returns the rows, ``(slot, rid)`` each."""
        rows = [
            (i, s.request.rid)
            for i, s in enumerate(self.slots)
            if self._rides(s)
        ]
        for i, _ in rows:
            self.slots[i].ahead += 1
        self._in_flight.append(rows)
        return rows

    def sampling_batch(self):
        """Fixed-shape per-slot sampling knobs aligned with
        :meth:`decode_batch`: ``(temperature [B] f32, top_k [B] i32,
        top_p [B] f32)`` — inactive/prefilling slots greedy-encoded."""
        B = self.max_seqs
        temps = np.zeros(B, np.float32)
        ks = np.zeros(B, np.int32)
        ps = np.ones(B, np.float32)
        for i, s in enumerate(self.slots):
            if s.request is None or s.prefill_pos is not None:
                continue
            temps[i], ks[i], ps[i] = s.request.params.as_arrays()
        return temps, ks, ps

    # --------------------------- commit/evict --------------------------- #

    def note_prefill_dispatched(self, slot: int) -> None:
        """The program that produces the slot's first token is dispatched:
        the slot rides the next decode step on that count."""
        self.slots[slot].ahead += 1

    def note_prefill_token(self, slot: int, token: int, now: float) -> None:
        """Record the prefill-produced first token (the TTFT point) and
        arm the slot for decode (or finish immediately at cap 1/eos)."""
        s = self.slots[slot]
        req = s.request
        req.first_token_ts = now
        req.tokens.append(int(token))
        s.next_token = int(token)
        s.ahead -= 1
        if self._done(req):
            self._finish(slot, now)

    def commit_decode(self, next_tokens: np.ndarray, now: float) -> int:
        """Fold the oldest decode step in flight into the slots; evict
        finished requests (blocks freed back to the pool).  A row is its
        request's only while the slot still holds the rid it was dispatched
        with: a request that ended on ``eos_id`` a step ago rode this one
        too, and that output is dropped.  Returns the number of LIVE tokens
        committed (inactive-slot outputs are discarded)."""
        live = 0
        for i, rid in self._in_flight.popleft():
            s = self.slots[i]
            if s.request is None or s.request.rid != rid:
                continue
            tok = int(next_tokens[i])
            s.context_len += 1  # the token we fed is cached
            s.request.tokens.append(tok)
            s.next_token = tok
            s.ahead -= 1
            live += 1
            if self._done(s.request):
                self._finish(i, now)
        return live

    def _done(self, req: Request) -> bool:
        if len(req.tokens) >= req.max_new_tokens:
            return True
        return req.eos_id is not None and req.tokens[-1] == req.eos_id

    def _finish(self, slot: int, now: float) -> None:
        s = self.slots[slot]
        s.request.finish_ts = now
        self.finished[s.request.rid] = s.request
        self.allocator.free(s.blocks)
        self.slots[slot] = _Slot()
        self.block_tables[slot, :] = SCRATCH_BLOCK
