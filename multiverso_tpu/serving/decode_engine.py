"""Continuous-batching decode engine: paged KV cache + iteration scheduling.

The micro-batcher's ``lm_decode`` workload locks B requests together
through a full ``greedy_decode`` to ``max_new``: one long generation
holds every short one hostage, and an arriving request waits for the
whole batch to drain before it can even prefill (head-of-line blocking
at completion AND admission). This engine removes both stalls with the
Orca design — iteration-level scheduling over a persistent KV cache
paged as in vLLM's PagedAttention. There is ONE cache layout (the block
pool) and ONE admission path (chunked prefill):

* **slots and the paged KV cache** — a slot is one in-flight sequence;
  the set of live slots is an ``active`` lanes vector. The engine owns
  one block pool ``[L, n_blocks + 1, block_size, D]``
  plus a host-side allocator (``serving/block_pool.py``) and per-slot
  block tables ``[S, max_blocks_per_seq]`` handed to the jitted
  programs as traced data — a sequence reserves
  ``ceil((prompt + max_new) / block_size)`` blocks at admission and
  frees them at eos/completion, so CAPACITY (KV bytes), not slot
  geometry, bounds concurrency: slots can outnumber what worst-case
  strips would fit, short sequences hold only the blocks they need,
  and a submit whose ``prompt + max_new`` can never fit the pool sheds
  with :class:`OverloadedError`. Pools are jit-donated so XLA updates
  them in place off-CPU.
* **content-addressed prefix caching** (``-prefix_cache``, default on)
  — every FULL block a prefill writes is registered under a
  hash-chained identity (``block_pool.chain_hashes``
  seeded by the pinned snapshot version); admission looks up the
  longest cached prefix of an arriving prompt, splices the matched
  blocks into the new slot's table with a refcount bump, and starts
  chunked prefill at the first uncached token. A fully cached prompt
  skips prefill entirely: its slot goes live at ``P - 1`` and the first
  token falls out of the next fused step (one copy-on-write of the last
  matched block first — writes never land in shared blocks). Completed
  sequences ``decref``; refcount-0 content-addressed blocks park in a
  cached-LRU tier that allocation pressure evicts, so shared system
  prompts/templates prefill once and multiply both effective KV
  capacity and TTFT (vLLM automatic prefix caching / SGLang
  RadixAttention). All placement still rides the block tables as traced
  data — one compiled trace per program, cache hits or not.
* **one fused step per iteration** — every iteration runs ONE jitted
  :func:`models.transformer.decode_step_paged` over all S slots, live
  or dead. Shapes never depend on the request mix, so the step compiles
  exactly once per engine config.
* **tensor-parallel decode mesh** (``-decode_tp``, default 1) — with
  ``decode_tp > 1`` the engine owns a decode-SPECIFIC mesh over the
  first ``tp`` devices: attention heads and the MLP hidden dim shard
  Megatron-style, the paged K/V pools shard over the head slice of
  ``D``, and every serving program is built ONCE at construction with
  matched ``in/out_shardings``
  (:func:`models.transformer.make_sharded_decode_programs`) so the spmd
  partitioner runs at compile time and never in the hot loop. Snapshot
  pins reshard the params onto the mesh
  (:func:`snapshot.shard_for_decode`) instead of replicating them onto
  one device — models whose params + KV pool exceed a single device's
  memory serve by splitting over the mesh. Block tables / tokens /
  positions stay replicated traced-as-data, so the one-trace invariant
  holds per mesh, and outputs are token-identical to the replicated
  path.
* **chunked, budget-bounded admission** — an arriving prompt prefills
  in fixed-size chunks (:func:`models.transformer.prefill_chunk_paged`,
  K/V written straight into its reserved blocks), AT MOST ONE chunk per
  iteration interleaved with the fused decode step, and the loop at
  most one iteration ahead of the device. Inter-token latency for
  in-flight generations is therefore bounded by two budget-sized chunks
  of work regardless of the arriving prompt's length (the
  Sarathi-Serve stall-free schedule), and a long prompt's TTFT
  amortizes across iterations instead of blocking the world. The chunk
  size is the ``prefill_token_budget`` config knob; its fixed shape
  adds exactly ONE compiled trace per engine config; a budget of
  ``max_prompt`` or more prefills every prompt in one chunk. The first
  token falls out of the last chunk of the prefill, so TTFT is one
  prefill — not one full batch drain.
* **speculative decoding** (``-spec_k``, default 0 = off) — the engine
  emits up to ``spec_k + 1`` tokens per iteration: a host-side n-gram
  **prompt-lookup** drafter (Saxena; no draft model) proposes up to K
  continuation guesses per live slot from the sequence's own history
  (prompt + emitted tokens, indexed incrementally per accept), and ONE
  fused :func:`models.transformer.verify_step_paged` scores all K + 1
  positions against the paged pool in a single forward. Greedy
  verification accepts the longest drafted prefix matching the model's
  own argmax chain plus one correction token, so outputs are
  **token-identical to plain greedy decode** — speculation changes the
  schedule, never the tokens. K is fixed per engine config (the
  ``[S, K + 1]`` window is the only new static shape; drafts, valid
  counts and the accepted length are traced data), so the feature adds
  exactly ONE compiled verify trace next to the one fused step. Drafts
  clamp to the request's remaining budget, so speculative writes never
  escape the admission-time block reservation (rejected positions need
  no device rollback — the next window rewrites them before any mask
  can reach them), and a full-hit shared block is CoW'd at admission
  *before* speculation, preserving the prefix-cache one-write-site
  contract. ``spec_k=0`` is today's one-token path, bit-for-bit.
* **iteration-granular completion** — a slot frees the moment its
  sequence's ``eos_id`` or its per-request ``max_new``-th token is
  booked; the finished tokens resolve the caller's Future immediately
  and the slot is reusable on the next iteration.
* **one pass ahead of the host** — a loop pass dispatches its step and
  its chunk FIRST and only then syncs and books what the pass before
  dispatched: the step takes its tokens from the last step's output on
  the device, positions advance at dispatch, and the host's late syncs,
  bookings and records run under device work. The depth (one, or zero
  behind a drain) is decided each pass from state the loop holds;
  outputs are token-identical to a drained loop (docs/SERVING.md "The
  order inside one iteration").
* **overload-graceful scheduling** (``-preempt``, default on) —
  requests carry a tenant ``priority`` class and an
  optional ``deadline_s``. The queue is a set of per-priority FIFO
  lanes under a stride (weighted-fair) scheduler with bounded
  lookahead past a block-starved head, and expired-deadline requests
  are dropped at POP time (:class:`DeadlineExceededError`) before any
  prefill is burned on them. Admission is OPTIMISTIC: a
  sequence reserves its PROMPT's blocks only and grows the reservation
  block-by-block at decode time; on pool exhaustion the lowest-
  priority/youngest victim is **preempted** — its blocks decref
  (tail-first, so its prefix-cache chain stays hittable), it re-enters
  the front of its lane, and on re-admission it recomputes from
  ``prompt + emitted tokens``, making the final output bit-identical
  to an un-preempted run (greedy decode is a deterministic function of
  the token prefix + pinned params, and the recompute is nearly free
  under the prefix cache). Anti-livelock: a per-request preemption
  budget (past it the request re-admits pessimistically with its full
  worst-case reservation) and a guaranteed-progress floor (the OLDEST
  live sequence is never preempted). Preemption is host-side
  scheduling only — block tables stay traced data, one compiled trace
  per program (docs/SERVING.md "Overload and preemption").

Snapshot pinning: an admission pins the engine's current params
snapshot for the whole generation. The pinned snapshot only moves when
the engine is EMPTY (no live slots), so a generation never spans two
parameter versions — concurrent ``train_batch`` calls can't tear an
in-flight sequence (the copy-on-publish guarantee extended from one
flush to one generation). The trade is surfaced, not hidden: replies
carry the pinned ``snapshot_version``/``staleness_s``, and a saturated
engine serves the admission-time version until it next drains.

Metrics: decode tokens/sec and slot occupancy land in Dashboard gauges
(``DECODE_TPS[name]``, ``SLOT_OCC[name]``); time-to-first-token and
inter-token latency land in histograms (``SERVE_TTFT[name]``,
``SERVE_ITL[name]``) next to the micro-batcher's ``SERVE_LAT``.
"""

from __future__ import annotations

import collections
import itertools
import threading
from ..analysis import lockwatch
import time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import (Deque, Dict, List, NamedTuple, Optional,
                    Sequence)

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace
from ..dashboard import Dashboard
from ..log import Log
from .batcher import DeadlineExceededError, OverloadedError
from . import accounting
from . import kv_transfer
from .block_pool import SCRATCH_BLOCK, BlockPool, chain_hashes
from .flight_recorder import FlightRecorder
from .programs import EngineSpec
from .snapshot import SnapshotManager
from .watchdog import EngineWatchdog, WatchdogConfig
from .workloads import _jit_cache_size


@dataclass
class DecodeEngineConfig:
    slots: int = 8              # S: concurrent sequences (fused-step width)
    max_prompt: int = 64        # longest admissible prompt
    max_new: int = 32           # per-request cap AND default generation length
    eos_id: Optional[int] = None
    max_queue: int = 256        # admission queue depth before shedding
    max_staleness_s: float = 0.05
    # per-iteration chunked-prefill token budget, > 0 (None = the
    # -prefill_token_budget flag); a budget past max_prompt means every
    # prompt prefills in one chunk
    prefill_token_budget: Optional[int] = None
    # paged KV cache: block size in token positions, > 0 (None = the
    # -kv_block_size flag) and usable pool blocks (None = the
    # -kv_pool_blocks flag, <= 0 = auto-size to every slot's worst case,
    # slots * ceil(T / block_size))
    kv_block_size: Optional[int] = None
    kv_pool_blocks: Optional[int] = None
    # tensor-parallel decode mesh width (None = the -decode_tp flag).
    # 1 reduces exactly to the single-device replicated path; > 1 builds
    # a decode-specific mesh over the first decode_tp devices, shards
    # attention heads / the MLP hidden dim / the head slice of the paged
    # K/V pools over a "tp" axis, and compiles every serving program
    # once against matched in/out_shardings
    decode_tp: Optional[int] = None
    # content-addressed prefix caching over the paged pool (None = the
    # -prefix_cache flag). False is the A/B baseline: same pool bytes,
    # every prompt prefills from token zero.
    prefix_cache: Optional[bool] = None
    # sequence-parallel long-prompt prefill over the decode mesh (None =
    # the matching -prefill_sp* flags): prompts at/above the threshold
    # prefill in budget * tp token chunks with the chunk's rows sharded
    # over the decode mesh's tp axis ("ring" ppermute rotations or
    # "ulysses" all_to_all head resharding); shorter prompts keep the
    # single-lane chunk program bit-for-bit. Incompatible with
    # kv_quant=int8.
    prefill_sp: Optional[bool] = None
    prefill_sp_backend: Optional[str] = None
    prefill_sp_threshold: Optional[int] = None
    # speculative decoding draft length (None = the -spec_k flag).
    # 0 = off (today's one-token path, bit-for-bit); > 0 drafts up to
    # spec_k tokens per live slot via n-gram prompt lookup and verifies
    # them in one fused fixed-K step
    spec_k: Optional[int] = None
    # int8 per-block-scaled paged KV pools (None = the -kv_quant flag).
    # "none" is today's fp pools bit-for-bit; "int8" stores the pools
    # as int8 with per-(layer, block) fp32 scales riding every program
    # as traced data — ~4x KV capacity at equal bytes, lossy (the bench
    # archives the argmax-match rate against the fp32 oracle).
    kv_quant: Optional[str] = None
    # int8 decode param snapshot pins (None = the -decode_param_quant
    # flag): pins quantize host-side once per version (~4x smaller
    # replica copies) and the compiled programs fold the dequant in
    decode_param_quant: Optional[str] = None
    # overload-graceful serving (None = the matching flags): optimistic
    # prompt-only reservation + grow-at-decode + preemption-with-
    # recompute (False = worst-case up-front reservation, the A/B
    # baseline), the per-request preemption
    # budget, and the bounded admission lookahead past a block-starved
    # queue head (0 = strict FIFO within a priority class)
    preempt: Optional[bool] = None
    preempt_budget: Optional[int] = None
    sched_lookahead: Optional[int] = None
    # black-box layer (None = the matching flag): always-on flight
    # recorder ring, stall/leak watchdog, trip-bundle target, and the
    # rolling-window latency SLOs registered in the Dashboard
    flight_recorder: Optional[bool] = None
    flight_recorder_capacity: Optional[int] = None
    watchdog: Optional[bool] = None
    watchdog_interval_s: Optional[float] = None
    watchdog_stall_s: Optional[float] = None
    watchdog_queue_age_s: Optional[float] = None
    debug_dump_dir: Optional[str] = None
    slo_ttft_ms: Optional[float] = None
    slo_itl_ms: Optional[float] = None
    # per-tenant cost attribution (None = the -cost_ledger flag): a
    # host-only CostLedger accumulating each request's resource vector
    # at the existing instrumentation sites (serving/accounting.py;
    # False = today's metrics surface byte-for-byte)
    cost_ledger: Optional[bool] = None

    def _resolved(self, field: str, flag: Optional[str] = None):
        value = getattr(self, field)
        if value is None:
            from ..config import get_flag

            value = get_flag(flag or field)
        return value

    def resolved_prefill_budget(self) -> int:
        if self.prefill_token_budget is not None:
            return int(self.prefill_token_budget)
        from ..config import get_flag

        return int(get_flag("prefill_token_budget"))

    def resolved_kv_block_size(self) -> int:
        if self.kv_block_size is not None:
            return int(self.kv_block_size)
        from ..config import get_flag

        return int(get_flag("kv_block_size"))

    def resolved_kv_pool_blocks(self, blocks_per_seq: int) -> int:
        n = self.kv_pool_blocks
        if n is None:
            from ..config import get_flag

            n = int(get_flag("kv_pool_blocks"))
        if n <= 0:                   # auto: every slot's worst case
            n = self.slots * blocks_per_seq
        return int(n)

    def resolved_watchdog_config(self) -> WatchdogConfig:
        return WatchdogConfig(
            interval_s=float(self._resolved("watchdog_interval_s")),
            stall_s=float(self._resolved("watchdog_stall_s")),
            queue_age_s=float(self._resolved("watchdog_queue_age_s")),
            dump_dir=str(self._resolved("debug_dump_dir")))


# process-unique small request ids: the flight recorder's admitted/
# completed columns join ring records to requests without holding refs
_RIDS = itertools.count(1)

# tenant priority classes: small ints, higher = more important. The
# admission scheduler weights class p by 2**p, so under contention
# class p receives 2**p admissions for every one class 0 gets — and
# every non-empty class keeps a POSITIVE share (the starvation bound
# the tests assert; strict priority would starve class 0 forever).
MAX_PRIORITY = 7
DEFAULT_PRIORITY = 1

# disaggregated serving: cap on the chain hashes health() advertises
# (the decode side's dedup advertisement rides replica heartbeats — at
# 16 bytes/hash this bounds the heartbeat cost to ~8 KB of hex). A
# capped advertisement is weaker, never wrong: an unadvertised cached
# block crosses the wire and dedups on arrival instead.
_CHAIN_ADVERT_CAP = 256

# prompt-lookup n-gram width: the drafter keys on the sequence's last
# _SPEC_NGRAM tokens. 2 is the sweet spot for the repetitive tails
# speculation targets (templated/looping continuations re-enter their
# cycle within a couple of tokens); a larger n only delays the first
# match without improving the greedy-verified acceptance contract.
_SPEC_NGRAM = 2

# The loop's phases (docs/OBSERVABILITY.md "Engine phases"): every
# instant of the loop thread is in ``engine.wait``, in ``engine.iter``
# (one pass) or in the loop gap between two passes, and a pass is
# tiled by these eight leaves, entered one after another. The flight
# recorder's ``phases`` column and ``stats()["slowest_pass"]`` keep a
# pass's row in this order.
_PASS_PHASES = ("engine.step", "engine.admit", "engine.prefill_chunk",
                "engine.step.sync", "engine.step.book",
                "engine.prefill_chunk.sync", "engine.prefill_chunk.book",
                "engine.record")
# the flight recorder's step_ms: the launch of the step a pass
# dispatched, the wait for and the booking of the one it retired
_STEP_PHASES = tuple(_PASS_PHASES.index(name) for name in (
    "engine.step", "engine.step.sync", "engine.step.book"))


class _PromptLookup:
    """Per-slot n-gram prompt-lookup index (Saxena, "Prompt Lookup
    Decoding"): maps every :data:`_SPEC_NGRAM`-gram of the sequence so
    far (prompt + emitted tokens) to the position right after its most
    recent earlier occurrence. A proposal reads the continuation that
    followed the last time the sequence's current tail was seen — free
    drafts with high acceptance on the repetitive tails of real traffic
    (templates, code, multi-turn echoes), and by construction the tail
    n-gram itself is never indexed until a later token gives it a
    continuation, so a proposal never self-matches. Pure host state,
    O(1) amortized per token (the index extends incrementally with each
    accepted token), so drafting can never add a compiled trace."""

    __slots__ = ("toks", "index")

    def __init__(self) -> None:
        self.toks: List[int] = []
        self.index: dict = {}

    def extend(self, tokens) -> None:
        """Append tokens; each one gives the n-gram ENDING just before
        it a continuation, which is when that n-gram becomes usable."""
        for t in tokens:
            p = len(self.toks)
            self.toks.append(int(t))
            if p >= _SPEC_NGRAM:
                self.index[tuple(self.toks[p - _SPEC_NGRAM: p])] = p

    def propose(self, limit: int) -> List[int]:
        """Up to ``limit`` draft tokens continuing the current tail, or
        ``[]`` when the tail n-gram has no earlier occurrence.

        The lookup FOLLOWS THROUGH its own extension: when the matched
        continuation runs out before ``limit`` (a tight cycle whose
        period is shorter than the draft window), the tail of (sequence
        + draft-so-far) is looked up again — so a period-2 loop still
        fills a K=4 window instead of stalling at the match boundary,
        which is exactly where greedy generations spend their
        repetitive tails."""
        if limit <= 0 or len(self.toks) < _SPEC_NGRAM:
            return []
        out: List[int] = []
        key = tuple(self.toks[-_SPEC_NGRAM:])
        while len(out) < limit:
            start = self.index.get(key)
            if start is None:
                break
            take = self.toks[start: start + (limit - len(out))]
            if not take:
                break
            out.extend(take)
            key = tuple((list(key) + take)[-_SPEC_NGRAM:])
        return out


class _PrioQueue:
    """Per-priority FIFO lanes under a stride (weighted-fair) scheduler.

    Each admission decision picks the non-empty lane with the smallest
    *pass* value, then advances that lane's pass by ``1 / 2**p``
    (stride scheduling): class ``p`` receives a ``2**p`` share of
    admissions under contention, ties break toward the higher class,
    and an idle lane re-activates at the current pass frontier so it
    cannot hoard credit and burst. Within a lane order is FIFO, with
    two exceptions the overload design needs:

    * **bounded lookahead** — when the lane head's block reservation
      does not fit the pool right now, up to ``lookahead`` younger
      requests of the SAME lane are scanned for one that does (a huge
      request at the head must not starve small admissible ones). The
      bypass bound is GLOBAL: the head accumulates one skip per
      admission that jumps it — same-lane candidates and other lanes'
      requests alike — and at ``lookahead`` skips ALL admission
      freezes until the head fits (see :meth:`pop_admissible`), which
      keeps every head's wait finite.
    * **preempted re-enqueue** (:meth:`appendleft`) — a preempted
      sequence returns to the FRONT of its lane: it is the oldest
      work its class has, and re-admitting it first is what makes the
      preemption budget a real churn bound.

    Expired-deadline requests are dropped AT POP TIME, whenever the
    scheduler's scan touches them — the caller receives them in the
    second return slot and fails their futures before any prefill
    runs. Per-lane depth rides ``QUEUE_DEPTH[name.pN]`` gauges.
    Callers hold the engine lock; this class does no locking itself.
    """

    def __init__(self, name: str, lookahead: int) -> None:
        self._name = name
        self._lookahead = int(lookahead)
        self._lanes: Dict[int, Deque["_Request"]] = {}
        self._passes: Dict[int, float] = {}
        self._gauges: Dict[int, object] = {}
        self._n = 0
        # queued requests that were preempted mid-generation and await
        # resume: while any exist the engine HOLDS its snapshot pin
        # (a pin move between preemption and resume would recompute
        # the tail under different params and break the bit-identical
        # contract) — maintained by _add and every removal path
        self.n_resumed = 0

    def __len__(self) -> int:
        return self._n

    def _gauge(self, p: int):
        g = self._gauges.get(p)
        if g is None:
            g = Dashboard.get_or_create_gauge(
                f"QUEUE_DEPTH[{self._name}.p{p}]")
            self._gauges[p] = g
        return g

    def _min_pass(self) -> float:
        active = [self._passes[p] for p, lane in self._lanes.items()
                  if lane]
        return min(active) if active else 0.0

    def _charge(self, p: int) -> None:
        self._passes[p] += 1.0 / (1 << min(p, MAX_PRIORITY))

    def _add(self, req: "_Request", front: bool) -> None:
        lane = self._lanes.get(req.priority)
        if lane is None:
            lane = self._lanes[req.priority] = collections.deque()
            self._passes.setdefault(req.priority, 0.0)
        if not lane:
            self._passes[req.priority] = max(
                self._passes[req.priority], self._min_pass())
        (lane.appendleft if front else lane.append)(req)
        self._n += 1
        if req.resumed:
            self.n_resumed += 1
        self._gauge(req.priority).set(float(len(lane)))

    def _removed(self, req: "_Request") -> "_Request":
        self._n -= 1
        if req.resumed:
            self.n_resumed -= 1
        return req

    def append(self, req: "_Request") -> None:
        self._add(req, front=False)

    def appendleft(self, req: "_Request") -> None:
        """Preempted re-enqueue: the front of the request's lane."""
        self._add(req, front=True)

    def oldest_t_enq(self) -> Optional[float]:
        heads = [lane[0].t_enq for lane in self._lanes.values() if lane]
        return min(heads) if heads else None

    def lowest_priority(self) -> Optional[int]:
        lanes = [p for p, lane in self._lanes.items() if lane]
        return min(lanes) if lanes else None

    def pop_admissible(self, now: float, covers):
        """One scheduling decision: ``(request or None, expired)``.

        ``covers(req)`` is the admission gate (block coverage); every
        queued request the scan touches is first deadline-checked and
        dropped into ``expired`` when past it — fail-fast BEFORE any
        prefill, the pop-time contract.

        The bypass bound is GLOBAL: a block-starved head accumulates
        one skip per admission that jumps it — same-lane lookahead
        candidates AND other lanes' requests alike — and once any head
        reaches the bound, admission freezes fleet-wide until that
        head fits (only bound-reaching heads may admit). Per-lane-only
        accounting would let the other lanes' small optimistic
        admissions re-consume every block a completion frees, starving
        a pessimistic (budget-exhausted worst-case) waiter forever;
        freezing lets freed blocks ACCUMULATE for it, so its wait is
        bounded by the live sequences' drain."""
        expired: List["_Request"] = []

        def dead(r: "_Request") -> bool:
            return r.deadline is not None and r.deadline <= now

        def sweep(p) -> None:
            lane = self._lanes[p]
            while lane and dead(lane[0]):
                expired.append(self._removed(lane.popleft()))

        thresh = self._lookahead if self._lookahead > 0 else 1
        order = sorted((p for p, lane in self._lanes.items() if lane),
                       key=lambda p: (self._passes[p], -p))
        # starved heads first: one at its bypass bound freezes every
        # other admission until it goes through
        for p in list(order):
            sweep(p)
        starved = [p for p in order
                   if self._lanes[p] and self._lanes[p][0].skips >= thresh]
        scan = starved or [p for p in order if self._lanes[p]]
        frozen = bool(starved)
        checked: List["_Request"] = []   # heads found non-coverable
        try:
            for p in scan:
                lane = self._lanes[p]
                head = lane[0]
                if covers(head):
                    self._removed(lane.popleft())
                    self._charge(p)
                    for h in checked:
                        h.skips += 1
                    return head, expired
                checked.append(head)
                if frozen or self._lookahead <= 0 \
                        or head.skips >= self._lookahead:
                    continue
                i, scanned = 1, 0
                while i < len(lane) and scanned < self._lookahead:
                    cand = lane[i]
                    if dead(cand):
                        del lane[i]
                        expired.append(self._removed(cand))
                        continue
                    scanned += 1
                    if covers(cand):
                        del lane[i]
                        self._removed(cand)
                        self._charge(p)
                        for h in checked:
                            h.skips += 1
                        return cand, expired
                    i += 1
            return None, expired
        finally:
            for p in order:
                self._gauge(p).set(float(len(self._lanes[p])))

    def drain(self) -> List["_Request"]:
        """Remove and return everything (the failure path)."""
        out: List["_Request"] = []
        for p, lane in self._lanes.items():
            out.extend(lane)
            lane.clear()
            self._gauge(p).set(0.0)
        self._n = 0
        self.n_resumed = 0
        return out


class _Request:
    __slots__ = ("prompt", "max_new", "future", "t_enq", "t_last",
                 "slot", "out", "version", "ctx", "pf_off", "pf_chunks",
                 "t_admit", "blocks", "rid", "hashes", "hash_seed",
                 "n_hit", "full_hit", "saved", "pf_reg", "ttft_pending",
                 "drafter", "priority", "deadline", "preempts",
                 "resumed", "skips", "prompt0", "pf_only", "known",
                 "xfer", "tenant", "usage", "sp")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 ctx: Optional[trace.SpanContext] = None,
                 priority: int = DEFAULT_PRIORITY,
                 deadline: Optional[float] = None,
                 tenant: Optional[str] = None) -> None:
        self.rid = next(_RIDS)
        self.prompt = prompt
        self.max_new = max_new
        self.future: Future = Future()
        self.t_enq = time.monotonic()
        self.t_last = self.t_enq     # last token emission (ITL base)
        self.slot = -1
        self.out: List[int] = []
        self.version = -1
        self.blocks: List[int] = []  # paged KV: the admission's reservation
        # trace handoff token (the submitter's root-span context): the
        # engine thread parents admission/iteration spans under it
        self.ctx = ctx
        # chunked-prefill progress: next chunk's prompt offset, chunks
        # run so far, and when admission began (queue.wait boundary)
        self.pf_off = 0
        self.pf_chunks = 0
        self.t_admit = 0.0
        # sequence-parallel prefill routing (set at _begin_prefill on
        # -prefill_sp engines: prompt length >= the threshold)
        self.sp = False
        # prefix caching: the prompt's full-block hash chain (memoized
        # per seed), blocks matched at admission, whether the WHOLE
        # prompt was cached, prefill tokens skipped, how many prompt
        # blocks are registered so far, and whether the next fused-step
        # token is this request's FIRST (full hit: TTFT lands on the
        # first decode step, not on a prefill chunk)
        self.hashes: Optional[List[bytes]] = None
        self.hash_seed: Optional[bytes] = None
        self.n_hit = 0
        self.full_hit = False
        self.saved = 0
        self.pf_reg = 0
        self.ttft_pending = False
        # speculative decoding: the slot's prompt-lookup draft index
        # (None on spec_k=0 engines — created at admission)
        self.drafter: Optional[_PromptLookup] = None
        # overload-graceful scheduling: tenant class, absolute
        # monotonic deadline (None = none), times preempted (the
        # budget), whether a preemption already interrupted emitted
        # output (resume recomputes, TTFT never re-records), times the
        # admission lookahead bypassed this request at the lane head,
        # and the ORIGINAL prompt (the resume base — ``prompt`` grows
        # to prompt0 + emitted tokens across preemptions)
        self.priority = int(priority)
        self.deadline = deadline
        self.preempts = 0
        self.resumed = False
        self.skips = 0
        self.prompt0 = prompt
        # disaggregated serving (kv_transfer): prefill-only admissions
        # resolve with a transfer payload instead of tokens; ``known``
        # holds the hex chain hashes the receiver advertised (skip
        # shipping those); ``xfer`` carries the splice accounting of the
        # transfer that warmed this request's prefix (decode side) so
        # the admit span can attribute the hit to the wire
        self.pf_only = False
        self.known: frozenset = frozenset()
        self.xfer: Optional[Dict[str, int]] = None
        # per-tenant cost attribution: the submitted tenant id (None =
        # the ledger's default tenant) and the request's host-only
        # resource vector — None on ledger-off engines, so every
        # attribution site is a single is-None check there
        self.tenant = tenant
        self.usage: Optional[accounting.ResourceUsage] = None


class _StepInFlight(NamedTuple):
    """A dispatched step (or verify window) until its booking."""
    nxt: object                     # the tokens, still on the device
    reqs: List[Optional[_Request]]  # each slot's request AT DISPATCH
    spec_toks: Optional[np.ndarray]
    n_valid: Optional[np.ndarray]
    t0: float                       # monotonic, before growth and drafts


class _ChunkInFlight(NamedTuple):
    """A dispatched prefill chunk until it is retired."""
    req: _Request
    logits: object                  # the last real row's, on the device
    off: int
    n: int                          # real tokens of the chunk
    size: int                       # the program's chunk shape
    index: int                      # which chunk of the prompt
    final: bool                     # the prompt's last: the slot lands
    t0: float                       # monotonic at dispatch (tracing only)


class DecodeEngine:
    """One LM's continuous-batching decode loop.

    ``lm`` is a :class:`models.transformer.TransformerLM` (the snapshot
    contract source); ``submit`` enqueues a prompt and returns a Future
    resolving to the reply dict ``{"result", "snapshot_version",
    "staleness_s"}`` where ``result`` is the generated id array
    (truncated at eos, so its length is request-dependent).
    """

    def __init__(self, name: str, lm, config: Optional[DecodeEngineConfig]
                 = None) -> None:
        self.name = name
        self.config = config or DecodeEngineConfig()
        ec = self.config
        S = ec.slots
        self._cache_len = ec.max_prompt + ec.max_new
        T = self._cache_len

        # -- paged KV cache geometry ----------------------------------------
        # one block pool [L, n_blocks + 1, block_size, D] (physical
        # block 0 is the scratch/sentinel block) + per-slot block tables
        # [S, M]
        self._block_size = ec.resolved_kv_block_size()
        if self._block_size <= 0:
            Log.fatal(f"DecodeEngine {name!r}: kv_block_size must be "
                      f"> 0 (token positions a block), got "
                      f"{self._block_size}")
        self._blocks_per_seq = -(-T // self._block_size)  # M = ceil(T / Bs)
        self._pool = BlockPool(
            ec.resolved_kv_pool_blocks(self._blocks_per_seq),
            self._block_size, name=name)
        # all-sentinel rows: every position maps to scratch until an
        # admission installs its reservation
        self._block_tables = np.full(
            (S, self._blocks_per_seq), SCRATCH_BLOCK, np.int32)

        # -- quantized serving knobs ----------------------------------------
        # int8 per-(layer, block)-scaled KV pools: the pools store int8
        # and a pair of [L, n_blocks + 1] fp32 scale arrays rides every
        # program call as TRACED data — same one-trace accounting as the
        # block tables. kv_quant="none" (default) keeps the fp pools and
        # is bit-identical to the pre-quant engine.
        self._kv_quant_mode = str(ec._resolved("kv_quant"))
        if self._kv_quant_mode not in ("none", "int8"):
            Log.fatal(f"DecodeEngine {name!r}: kv_quant must be 'none' or "
                      f"'int8', got {self._kv_quant_mode!r}")
        self._kv_quant = self._kv_quant_mode == "int8"
        # int8 decode param pins: the pin quantizes host-side ONCE per
        # snapshot version (snapshot.quantize_decode_params) and the
        # compiled programs fold the dequant in — pin device_put bytes
        # drop ~4x, per-token traces stay 1.
        self._param_quant = str(ec._resolved("decode_param_quant"))
        if self._param_quant not in ("none", "int8"):
            Log.fatal(f"DecodeEngine {name!r}: decode_param_quant must be "
                      f"'none' or 'int8', got {self._param_quant!r}")

        # -- decode mesh (tensor-parallel serving) --------------------------
        # decode_tp=1 (default) reduces exactly to the single-device
        # replicated path; > 1 builds a decode-SPECIFIC mesh over the
        # first tp devices — NOT the train mesh, whose NamedShardings
        # dragged per-token programs through the spmd partitioner
        # (~10x step wall, the PR 2 gate this replaces)
        self._tp = int(ec._resolved("decode_tp"))
        self._decode_mesh = None
        if self._tp < 1:
            Log.fatal(f"DecodeEngine {name!r}: decode_tp must be >= 1, "
                      f"got {self._tp}")
        if self._tp > 1:
            from ..models.transformer import DECODE_TP_AXIS
            from ..topology import make_mesh

            ndev = len(jax.devices())
            if self._tp > ndev:
                Log.fatal(f"DecodeEngine {name!r}: decode_tp {self._tp} "
                          f"exceeds the {ndev} visible device(s)")
            if jax.process_count() > 1:
                # fail at construction, not at pin time on the loop
                # thread: in a multi-process mesh jax.devices()[:tp]
                # includes devices this host cannot address, and the
                # pin's cross-mesh device_put would raise mid-serving
                # (replicate_for_decode has the same single-process
                # scope; multi-process decode meshes are the
                # serving-fleet item, not this knob)
                Log.fatal(f"DecodeEngine {name!r}: decode_tp > 1 is "
                          f"single-process only — a multi-process mesh "
                          f"cannot address jax.devices()[:{self._tp}] "
                          f"from one host")
            self._decode_mesh = make_mesh(
                (self._tp,), axis_names=(DECODE_TP_AXIS,),
                devices=jax.devices()[: self._tp])

        self._manager = SnapshotManager.of(lm, name=name)
        self._snap = None            # pinned while any slot is live
        self._pinned = None          # the pinned snapshot's DECODE params
        self._pinned_version: Optional[int] = None
        # replica/reshard copies actually taken: the pin memoizes on
        # snapshot VERSION, so a drain/re-pin cycle (or a forced
        # re-publish) without a version move is copy-free (tested)
        self.pin_copies = 0

        # -- jitted programs ------------------------------------------------
        # chunked admission budget: a fixed-size chunk prefilled straight
        # into the slot's blocks at a traced (slot, offset, length) — the
        # chunk shape is the ONLY static, so it is exactly one extra
        # compiled trace per engine config (asserted in the tests)
        self._budget = ec.resolved_prefill_budget()
        if self._budget <= 0:
            Log.fatal(f"DecodeEngine {name!r}: prefill_token_budget must "
                      f"be > 0 (tokens a prefill chunk), got "
                      f"{self._budget}")
        # a chunk never needs more tokens than the longest admissible
        # prompt (and must fit the [.., T, ..] cache): clamp the chunk
        # shape — budgets past max_prompt just mean one-chunk admission
        self._budget = min(self._budget, ec.max_prompt)
        # content-addressed prefix caching over the block pool
        self._prefix = bool(ec._resolved("prefix_cache"))
        self._hash_seed = b""        # pinned-version scope for the chain
        # sequence-parallel prefill: prompts at/above the threshold chunk
        # at budget * tp tokens with the rows sharded over the decode
        # mesh — a long prompt admits in tp x fewer iterations while each
        # device still runs one budget's worth of rows per iteration
        # (the ITL bound the budget exists for). Short prompts keep the
        # single-lane chunk program bit-for-bit.
        self._sp = bool(ec._resolved("prefill_sp"))
        self._sp_backend = str(ec._resolved("prefill_sp_backend"))
        self._sp_threshold = int(ec._resolved("prefill_sp_threshold"))
        self._chunk_sp_fn = None
        if self._sp:
            if self._kv_quant:
                Log.fatal(f"DecodeEngine {name!r}: prefill_sp is "
                          f"incompatible with kv_quant=int8 — the "
                          f"seqpar entry points reproduce the fp chunk "
                          f"math exactly and have no quantized variant")
            if self._sp_backend not in ("ring", "ulysses"):
                Log.fatal(f"DecodeEngine {name!r}: prefill_sp_backend "
                          f"must be 'ring' or 'ulysses', got "
                          f"{self._sp_backend!r}")
            if self._sp_threshold < 0:
                Log.fatal(f"DecodeEngine {name!r}: negative "
                          f"prefill_sp_threshold {self._sp_threshold}")
            if self._sp_backend == "ring" and T % self._tp != 0:
                # the ring rotates the slot's gathered [T, D] view in
                # T/tp-row shards; ulysses keeps T whole (head shards)
                Log.fatal(f"DecodeEngine {name!r}: ring prefill_sp "
                          f"needs the logical cache length {T} "
                          f"divisible by decode_tp {self._tp} — use "
                          f"the ulysses backend or adjust "
                          f"max_prompt/max_new")
        # the seqpar chunk's global size: one budget of rows per DEVICE
        self._sp_chunk = self._budget * self._tp if self._sp else 0
        # speculative decoding: up to spec_k prompt-lookup drafts per
        # live slot, verified by one fused fixed-K step per iteration
        # (the verify window parks rejected/pad writes in the scratch
        # block)
        self._spec = int(ec._resolved("spec_k"))
        if self._spec < 0:
            Log.fatal(f"DecodeEngine {name!r}: negative spec_k "
                      f"{self._spec}")
        # overload-graceful serving: optimistic prompt-only reservation
        # + grow-at-decode + preemption-with-recompute. preempt=False
        # keeps the worst-case prompt+max_new up-front reservation (the
        # A/B baseline).
        self._preempt_on = bool(ec._resolved("preempt"))
        self._preempt_budget = int(ec._resolved("preempt_budget"))
        if self._preempt_budget < 0:
            Log.fatal(f"DecodeEngine {name!r}: negative preempt_budget "
                      f"{self._preempt_budget}")
        self._lookahead = int(ec._resolved("sched_lookahead"))
        if self._lookahead < 0:
            Log.fatal(f"DecodeEngine {name!r}: negative sched_lookahead "
                      f"{self._lookahead}")

        # -- the model's side: cache layout and programs -------------------
        # the engine resolved ITS knobs above; what a cache row is and
        # how a token is computed against it is the model's
        # (serving/programs.py). Every program is jitted there, ONCE,
        # inside this constructor (the RT106 contract); a feature the
        # model lacks is refused there, by name. Cache donation is real
        # only where XLA implements input aliasing (TPU/GPU): on CPU a
        # donated arg forces a defensive copy AND a second compiled
        # trace (measured 2.4 ms -> 22 ms per fused step)
        progs = lm.serving_programs(EngineSpec(
            name=name, slots=S, max_prompt=ec.max_prompt,
            max_new=ec.max_new, cache_len=T, block_size=self._block_size,
            blocks_per_seq=self._blocks_per_seq,
            pool_blocks=self._pool.capacity,
            budget=self._budget, prefix=self._prefix, tp=self._tp,
            mesh=self._decode_mesh, kv_quant=self._kv_quant_mode,
            param_quant=self._param_quant, spec_k=self._spec,
            prefill_sp=self._sp_backend if self._sp else "none",
            donate=jax.default_backend() != "cpu"))
        self._progs = progs
        self._chunk_fn = progs.chunk
        self._chunk_sp_fn = progs.chunk_sp
        self._step_fn = progs.step
        self._cow_fn = progs.cow
        self._verify_fn = progs.verify
        # the KV transfer plane's two programs (prefix-cache engines of
        # a model that has them): one compiled trace each
        self._fetch_fn = progs.fetch
        self._splice_fn = progs.splice
        if self._chunk_fn is None:
            Log.fatal(f"DecodeEngine {name!r}: the model has no prefill "
                      f"chunk program")
        # the engine's own program: a step's input tokens, merged on the
        # device from the last step's output and the host's tokens of
        # the slots that went live since (-1 elsewhere). Its output and
        # a step's are the ONE kind of ``tok`` the step ever sees
        # (warm-up included), so the step keeps one compiled trace
        tok_target = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        if self._decode_mesh is not None:
            tok_target = jax.sharding.NamedSharding(
                self._decode_mesh, jax.sharding.PartitionSpec())
        def merge_tokens(prev, host):
            return jnp.where(host >= 0, host, prev)

        self._merge_fn = jax.jit(merge_tokens, out_shardings=tok_target)

        # -- device state (owned by the loop thread after start) -------------
        # committed placement from birth: the programs' traces are
        # compiled against operands that carry it (an uncommitted zeros
        # here would retrace on the first live call). Sharded engines
        # commit each pool to the placement the model names (matching
        # the programs' in_shardings); others to the first device
        self._pool_targets = (progs.pool_targets
                              or (jax.devices()[0],) * len(progs.pools))
        self._pools = tuple(
            jax.device_put(jnp.zeros(shape, dtype), target)
            for (shape, dtype), target in zip(progs.pools,
                                              self._pool_targets))
        # window base of the model's own counters (reset_stats)
        self._counters_base = None
        # -- host state -----------------------------------------------------
        self._slot_req: List[Optional[_Request]] = [None] * S
        # explicit free-slot set, maintained at admit/complete (the loop
        # used to rebuild it by scanning all S slots every iteration)
        self._free_q: Deque[int] = collections.deque(range(S))
        self._tok = np.zeros(S, np.int32)
        self._pos = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        # the last step's tokens, on the device, and the slots whose
        # input token is newer on the host (``_tok``): those that went
        # live since that step was dispatched
        self._dev_tok = jax.device_put(jnp.zeros(S, jnp.int32), tok_target)
        self._fresh = np.zeros(S, bool)
        # steps a live slot may still be dispatched in: with spec_k == 0
        # a step emits one token whatever it is, so a request that
        # reaches max_new with steps in flight is known by count and
        # left out of the next step without reading anything
        self._left = np.zeros(S, np.int32)
        # programs dispatched and not yet retired, oldest first: at most
        # one step and one chunk between passes (the pass before's),
        # two of each inside a pass
        self._flight: Deque = collections.deque()
        # the one admission currently prefilling in chunks (its slot is
        # reserved — excluded from the free pool — but not yet live)
        self._pf: Optional[_Request] = None
        # per-priority weighted-fair admission lanes (a plain FIFO when
        # every submit uses the default class)
        self._q = _PrioQueue(name, self._lookahead)
        # chaos/test hook (faultinject pool_squeeze=): block ids held
        # hostage to force pool pressure; excluded from the watchdog's
        # leaked-reservation heuristic
        self._squeezed: List[int] = []
        # work awaiting the loop thread: the pools are loop-thread-owned
        # (donation reassigns them per dispatch), so splice() and
        # warmup() park (work, done-event, out-dict) triples here and
        # the loop runs them between iterations
        self._loop_work: Deque = collections.deque()
        self._lock = lockwatch.lock("serving.DecodeEngine._lock")
        self._cv = threading.Condition(self._lock)
        self._stop = threading.Event()
        # -- stats ----------------------------------------------------------
        self.ttft_hist = Dashboard.get_or_create_histogram(
            f"SERVE_TTFT[{name}]")
        self.itl_hist = Dashboard.get_or_create_histogram(
            f"SERVE_ITL[{name}]")
        # enqueue -> admission, one sample a request admitted: TTFT is
        # this wait plus admission-to-first-token
        self.qwait_hist = Dashboard.get_or_create_histogram(
            f"SERVE_QWAIT[{name}]")
        self.tps_gauge = Dashboard.get_or_create_gauge(f"DECODE_TPS[{name}]")
        self.occ_gauge = Dashboard.get_or_create_gauge(f"SLOT_OCC[{name}]")
        # staleness-aware serving: seconds since the served source last
        # moved (SnapshotManager.params_age_s), refreshed on health()
        # polls — the publish-stream-went-silent signal the obs plane
        # ships and -params_stale_after_s turns into a STALE verdict
        self.params_age_gauge = Dashboard.get_or_create_gauge(
            f"SERVE_PARAMS_AGE[{name}]")
        self.shed_counter = Dashboard.get_or_create_counter(
            f"SERVE_SHED[{name}]")
        # overload-graceful instruments: preemption events, expired-
        # deadline drops, and per-class shed counters (created lazily —
        # one per priority class actually shed)
        self.preempt_counter = Dashboard.get_or_create_counter(
            f"PREEMPTIONS[{name}]")
        self.deadline_counter = Dashboard.get_or_create_counter(
            f"DEADLINE_DROPS[{name}]")
        self._shed_class_counters: Dict[int, object] = {}
        self.steps_counter = Dashboard.get_or_create_counter(
            f"DECODE_STEPS[{name}]")
        # token-accounting split: prompt tokens prefilled vs tokens
        # emitted — interval-deltas (MetricsExporter) become the two
        # rates whose ratio says where the engine's FLOPs are going.
        # DECODE_TOKENS counts every EMITTED token (a speculative
        # iteration emits up to spec_k + 1), so DECODE_TPS and the
        # exporter's token rate stay honest under speculation
        self.prefill_tok_counter = Dashboard.get_or_create_counter(
            f"PREFILL_TOKENS[{name}]")
        self.decode_tok_counter = Dashboard.get_or_create_counter(
            f"DECODE_TOKENS[{name}]")
        # speculative decoding instruments, created only on spec engines
        # so a spec_k=0 engine's dashboard/stats surface is byte-for-
        # byte today's (the metrics regression contract)
        self.spec_prop_counter = self.spec_acc_counter = None
        if self._spec:
            self.spec_prop_counter = Dashboard.get_or_create_counter(
                f"SPEC_PROPOSED[{name}]")
            self.spec_acc_counter = Dashboard.get_or_create_counter(
                f"SPEC_ACCEPTED[{name}]")
        # KV-transfer instruments, created only on prefix-cache engines
        # (the transfer plane's gate) so a prefix_cache=off engine's
        # dashboard/stats surface stays byte-for-byte (the metrics
        # regression contract). Bytes are RAW K/V bytes moved — the
        # kv_transfer.payload_bytes unit, not wire encoding.
        self.xfer_bytes_counter = self.xfer_blocks_counter = None
        self.xfer_dedup_counter = None
        if self._prefix:
            self.xfer_bytes_counter = Dashboard.get_or_create_counter(
                f"KV_XFER_BYTES[{name}]")
            self.xfer_blocks_counter = Dashboard.get_or_create_counter(
                f"KV_XFER_BLOCKS[{name}]")
            self.xfer_dedup_counter = Dashboard.get_or_create_counter(
                f"KV_XFER_DEDUP[{name}]")
        # iteration progress: the counter for dashboards/rates, the local
        # mirror + monotonic age for stats()/the watchdog's stall check
        self.iters_counter = Dashboard.get_or_create_counter(
            f"ENGINE_ITERS[{name}]")
        self.iters_total = 0
        self._last_progress = time.monotonic()
        # rolling-window latency SLOs (burn status in every snapshot())
        slo_ttft = float(ec._resolved("slo_ttft_ms"))
        if slo_ttft > 0:
            Dashboard.set_slo(f"SERVE_TTFT[{name}]", slo_ttft)
        slo_itl = float(ec._resolved("slo_itl_ms"))
        if slo_itl > 0:
            Dashboard.set_slo(f"SERVE_ITL[{name}]", slo_itl)
        # always-on flight recorder (the loop writes one record per
        # iteration; pure host state, so it can never add a compiled
        # trace — the one-trace assertions below it stay at 1)
        self.recorder: Optional[FlightRecorder] = None
        if bool(ec._resolved("flight_recorder")):
            self.recorder = FlightRecorder(
                int(ec._resolved("flight_recorder_capacity")), name=name)
            # static mesh facts ride the black box: a post-mortem dump
            # must say which tensor-parallel config produced its records
            self.recorder.meta.update(
                decode_tp=self._tp,
                mesh_devices=(self._decode_mesh.size
                              if self._decode_mesh is not None else 1))
            if self._spec:
                self.recorder.meta["spec_k"] = self._spec
            if self._kv_quant:
                self.recorder.meta["kv_quant"] = self._kv_quant_mode
            if self._sp:
                self.recorder.meta["prefill_sp"] = self._sp_backend
            self.recorder.meta["phases"] = list(_PASS_PHASES)
        # the loop's phases on the always-on host clock (trace.PhaseClock:
        # the loop thread is its only writer); under a profiler session
        # the same sites write the bench.engine.* annotations
        self._clock = trace.PhaseClock(_PASS_PHASES, "engine.iter",
                                       "engine.wait")
        self._phase = self._clock.phase
        # the longest pass since reset_stats(), kept outside the ring so
        # that a wrap cannot lose it: (it, end, ns, gap before, live,
        # queue, row)
        self._slowest_ns = 0
        self._slowest: Optional[tuple] = None
        # admit-span mesh annotation (trace_summary ships the column):
        # only sharded engines carry it, so replicated reports stay flat
        self._mesh_attrs = ({"decode_tp": self._tp} if self._tp > 1
                            else {})
        if self._kv_quant:
            # quant engines annotate every admit span too (the
            # trace_summary quant column; off-quant spans stay flat —
            # the metrics-regression byte-identity contract)
            self._mesh_attrs["kv_quant"] = self._kv_quant_mode
        # per-tenant cost attribution (the -cost_ledger gate): pure
        # host state on the loop thread — attaching it can never add a
        # compiled trace (step/prefill traces stay 1, retraces 0) and
        # off-ledger engines keep today's metrics surface byte-for-byte
        self.ledger: Optional[accounting.CostLedger] = None
        if bool(ec._resolved("cost_ledger")):
            self.ledger = accounting.CostLedger(
                name,
                block_bytes=progs.bytes_per_block)
        # per-iteration scratch the recorder drains (reused, not realloc'd)
        self._it_admitted: List[int] = []
        self._it_completed: List[int] = []
        self._it_prefill = 0
        self._it_decode = 0
        self._it_spec_proposed = 0
        self._it_spec_accepted = 0
        self._it_sp_chunks = 0
        self._it_live_blocks = -1
        self._it_behind = 0
        self._it_ahead = 0
        self.completed = 0
        self.shed = 0
        self.tokens = 0
        # peak concurrent sequences (live slots + the mid-prefill
        # admission): what the pool's KV bytes, not the slot count, bound
        self.peak_live = 0
        # engine-local prefill-token count: the PREFILL_TOKENS Counter is
        # monotonic by contract (MetricsExporter rates), so stats() and
        # reset_stats() read/zero this mirror instead
        self.prefill_tokens = 0
        # prefix-cache mirrors (the pool's PREFIX_* counters stay
        # monotonic; these reset with the bench window)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefill_tokens_saved = 0
        self.cow_copies = 0
        # KV-transfer mirrors (the KV_XFER_* counters stay monotonic;
        # these reset with the bench window): blocks whose bytes crossed
        # this engine's boundary (fetched out OR spliced in), the raw
        # K/V bytes they carried, and blocks deduped away (source-side
        # skip on this engine's fetch, or arrival-side index hit)
        self.xfer_blocks = 0
        self.xfer_bytes = 0
        self.xfer_dedup = 0
        # speculative-decoding mirrors (the SPEC_* counters stay
        # monotonic; these reset with the bench window): drafts
        # proposed/accepted and verify-step dispatches
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        # sequence-parallel prefill mirror (resets with the bench
        # window): chunks dispatched through the seqpar program
        self.seqpar_chunks = 0
        # prefill chunks dispatched, and those of them dispatched while
        # a step of the same pass was in flight (queued behind it on the
        # device): with something live every chunk is one; resets with
        # the bench window
        self.prefill_chunks = 0
        self.chunks_behind_step = 0
        # over the prefill chunks dispatched, the positions each chunk's
        # last row can see (offset + length), summed: over chunks x the
        # programs' logical cache length a slot, the share of the keys a
        # chunk program is compiled for that chunks could reach; resets
        # with the bench window
        self.chunk_reach = 0
        # steps dispatched, those of them dispatched with the step
        # before still unread (its tokens taken from the device), and
        # the times the loop retired what was in flight ahead of its
        # turn, by cause; reset with the bench window
        self.steps = 0
        self.steps_ahead = 0
        self.drains: Dict[str, int] = {}
        # when the last step was booked: a step's ledger charge starts
        # there or at its own dispatch, whichever is later
        self._t_booked = 0.0
        # overload mirrors (the PREEMPTIONS/DEADLINE_DROPS counters
        # stay monotonic; these reset with the bench window):
        # preemption EVENTS, distinct requests preempted at least
        # once, and expired-deadline queue drops
        self.preemptions = 0
        self.preempted = 0
        self.deadline_drops = 0
        # quant quality headline: argmax-match rate vs an fp32 oracle,
        # measured and recorded by the harness/bench (the engine cannot
        # compute it alone — it needs the oracle's outputs); -1 = never
        # measured. Quant engines surface it in stats() as _info-grade
        # data, off-quant engines' stats stay byte-identical
        self._argmax_match = -1.0
        # window base for the pool's monotonic eviction counter, so
        # stats()["prefix_evictions"] resets with its sibling mirrors
        self._evictions_base = 0
        self.t_first: Optional[float] = None
        self._occ_sum = 0.0          # mean occupancy over iterations
        self._occ_n = 0
        # KV blocks the steps' attention had to read:
        # what the view path's gather over all slots x M is wasted on,
        # and what the paged kernel's copies scale with
        self._live_blocks_sum = 0
        self._thread = threading.Thread(
            target=self._loop, name=f"serve-decode-{name}", daemon=True)
        self._thread.start()
        # the watchdog watches the PUBLIC health surface (health() /
        # pool_drift()), so it starts after the loop thread exists
        self.watchdog: Optional[EngineWatchdog] = None
        if bool(ec._resolved("watchdog")):
            self.watchdog = EngineWatchdog(
                self, ec.resolved_watchdog_config())

    # -- client side --------------------------------------------------------
    def validate(self, prompt: np.ndarray, max_new: Optional[int]) -> None:
        p = np.asarray(prompt, np.int32).ravel()
        if not 1 <= p.shape[0] <= self.config.max_prompt:
            raise ValueError(f"prompt length {p.shape[0]} outside "
                             f"[1, {self.config.max_prompt}]")
        if max_new is not None and not 1 <= int(max_new) <= self.config.max_new:
            raise ValueError(f"max_new {max_new} outside "
                             f"[1, {self.config.max_new}]")

    def _shed_class(self, priority: int) -> None:
        counter = self._shed_class_counters.get(priority)
        if counter is None:
            counter = Dashboard.get_or_create_counter(
                f"SHED_BY_CLASS[{self.name}.p{priority}]")
            self._shed_class_counters[priority] = counter
        counter.inc()

    def submit(self, prompt: np.ndarray, max_new: Optional[int] = None,
               ctx: Optional[trace.SpanContext] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None,
               xfer_info: Optional[Dict[str, int]] = None,
               tenant: Optional[str] = None) -> Future:
        """Enqueue one prompt; fast-rejects at the admission-queue cap,
        and when ``prompt + max_new`` needs more blocks than
        the whole pool holds — such a request could NEVER be admitted
        (``retriable=False``: no amount of retrying changes that), so
        queueing it would deadlock the admission head. ``ctx`` is the
        request's trace handoff token (or None). ``priority`` is the
        tenant class (0..7, higher = more important; None = class 1 —
        admission shares are weighted-fair, docs/SERVING.md "Overload
        and preemption"). ``deadline_s`` (None = none) is seconds from
        now past which the answer is worthless: an expired request is
        dropped at queue-POP time with :class:`DeadlineExceededError`
        before any prefill runs. ``xfer_info`` (disaggregated serving)
        is the :meth:`splice` accounting of the KV transfer that warmed
        this prompt's prefix, threaded onto the admit span so the trace
        attributes the cache hit to the wire. ``tenant`` (None = the
        ``-default_tenant`` fallback) names who pays: on a
        ``-cost_ledger`` engine the request carries a resource vector
        finalized into that tenant's aggregates
        (docs/OBSERVABILITY.md "Tenant accounting")."""
        self.validate(prompt, max_new)
        prio = DEFAULT_PRIORITY if priority is None else int(priority)
        if not 0 <= prio <= MAX_PRIORITY:
            raise ValueError(f"priority {prio} outside "
                             f"[0, {MAX_PRIORITY}]")
        deadline = None
        if deadline_s is not None:
            if float(deadline_s) <= 0:
                raise ValueError(f"deadline_s must be > 0, "
                                 f"got {deadline_s}")
            deadline = time.monotonic() + float(deadline_s)
        p = np.asarray(prompt, np.int32).ravel()
        req = _Request(p, int(max_new or self.config.max_new), ctx,
                       priority=prio, deadline=deadline, tenant=tenant)
        if xfer_info:
            req.xfer = dict(xfer_info)
        if self.ledger is not None:
            req.usage = self.ledger.usage(tenant)
        with self._cv:
            if self._stop.is_set():
                raise RuntimeError(f"decode engine {self.name!r} is stopped")
            need = self._pool.blocks_needed(p.shape[0] + req.max_new)
            if need > self._pool.capacity:
                self.shed += 1
                self.shed_counter.inc()
                self._shed_class(prio)
                if req.usage is not None:
                    self.ledger.finalize(req.usage, "shed")
                raise OverloadedError(self.name, need,
                                      self._pool.capacity,
                                      what="kv block pool",
                                      retriable=False)
            if len(self._q) >= self.config.max_queue:
                self.shed += 1
                self.shed_counter.inc()
                self._shed_class(prio)
                if req.usage is not None:
                    self.ledger.finalize(req.usage, "shed")
                raise OverloadedError(self.name, len(self._q),
                                      self.config.max_queue)
            if self.t_first is None:
                self.t_first = req.t_enq
            self._q.append(req)
            self._cv.notify()
        return req.future

    # -- disaggregated prefill/decode (kv_transfer) -------------------------
    @property
    def supports_transfer(self) -> bool:
        """Whether this engine can be a disaggregation endpoint. The
        transfer plane moves chain-addressed FULL blocks, so it rides
        exactly the prefix-cache gate: without the content index there
        is nothing to splice INTO."""
        return self._prefix and self._fetch_fn is not None

    def submit_prefill(self, prompt: np.ndarray,
                       known_hashes: Sequence[str] = (),
                       ctx: Optional[trace.SpanContext] = None,
                       tenant: Optional[str] = None) -> Future:
        """Enqueue a PREFILL-ONLY admission (the disaggregated fleet's
        stage 1): the prompt chunk-prefills into paged blocks exactly
        like a normal admission, but instead of going live the request
        resolves with ``{"xfer": payload, "snapshot_version",
        "staleness_s"}`` — the prompt's finished full blocks fetched to
        the host as a :mod:`kv_transfer` payload — and releases its
        reservation (the prefilled blocks stay behind in the CACHED
        tier, so a repeat prompt full-hits locally). ``known_hashes``
        are hex chain hashes the receiver already holds (router-tracked
        shipped set + heartbeat advertisement): those blocks ride as
        metadata only. Sheds like :func:`submit`; fails fast on engines
        without :attr:`supports_transfer`."""
        if not self.supports_transfer:
            raise RuntimeError(
                f"decode engine {self.name!r} cannot serve prefill-only "
                f"admissions (needs prefix_cache and a model with KV "
                f"transfer programs — the transfer plane's gate)")
        self.validate(prompt, None)
        p = np.asarray(prompt, np.int32).ravel()
        # max_new=1 keeps the reservation arithmetic in-range; the
        # pf_only reservation is prompt-only regardless (nothing decodes)
        req = _Request(p, 1, ctx, tenant=tenant)
        req.pf_only = True
        req.known = frozenset(str(h) for h in known_hashes)
        if self.ledger is not None:
            req.usage = self.ledger.usage(tenant)
        with self._cv:
            if self._stop.is_set():
                raise RuntimeError(f"decode engine {self.name!r} is stopped")
            need = self._pool.blocks_needed(p.shape[0])
            if need > self._pool.capacity:
                self.shed += 1
                self.shed_counter.inc()
                self._shed_class(req.priority)
                if req.usage is not None:
                    self.ledger.finalize(req.usage, "shed")
                raise OverloadedError(self.name, need,
                                      self._pool.capacity,
                                      what="kv block pool",
                                      retriable=False)
            if len(self._q) >= self.config.max_queue:
                self.shed += 1
                self.shed_counter.inc()
                self._shed_class(req.priority)
                if req.usage is not None:
                    self.ledger.finalize(req.usage, "shed")
                raise OverloadedError(self.name, len(self._q),
                                      self.config.max_queue)
            if self.t_first is None:
                self.t_first = req.t_enq
            self._q.append(req)
            self._cv.notify()
        return req.future

    def splice(self, payload: dict, timeout_s: float = 30.0) -> Dict:
        """Splice a :mod:`kv_transfer` payload into this engine's block
        pool (the disaggregated fleet's arrival side) and return the
        accounting ``{"xfer_blocks", "xfer_bytes", "dedup_blocks"}``
        (plus ``"skipped"`` when nothing could apply). BLOCKING and
        thread-safe: the caches are loop-thread-owned, so the payload
        parks on ``_loop_work`` and the loop applies it between
        iterations — callers (the replica's drain thread) wait so the
        follow-up ``submit`` of the same prompt is guaranteed to see
        the warm prefix. Degrades, never raises: an unsupported engine,
        stopped loop, or timeout returns a zero accounting and the
        caller's submit re-prefills locally (correctness by
        construction — the full prompt always rides stage 2)."""
        zero = {"xfer_blocks": 0, "xfer_bytes": 0, "dedup_blocks": 0}
        if not self.supports_transfer:
            return dict(zero, skipped="unsupported")
        done = threading.Event()
        info: Dict = {}
        with self._cv:
            if self._stop.is_set():
                return dict(zero, skipped="stopped")
            self._loop_work.append(
                (lambda: self._apply_splice(payload), done, info))
            self._cv.notify()
        if not done.wait(timeout_s):
            return dict(zero, skipped="timeout")
        out = dict(zero)
        out.update((k, v) for k, v in info.items() if k != "error")
        return out

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._q)

    def health(self) -> dict:
        """The watchdog's poll surface: progress, liveness, and queue
        age WITHOUT the histogram sorts ``stats()`` pays — cheap enough
        to read several times a second against a saturated engine."""
        now = time.monotonic()
        with self._lock:
            depth = len(self._q)
            oldest = self._q.oldest_t_enq()
            age = (now - oldest) if oldest is not None else 0.0
            pinned = self._pinned_version
            snap = self._snap
        from .. import config

        # params staleness: how long since the SERVED source last moved
        # (the trainer's publish stream going silent). The verdict is
        # advisory — the engine keeps serving its frozen snapshot — and
        # clears automatically when a fenced restart republishes.
        params_age = self._manager.params_age_s()
        stale_after = float(config.get_flag("params_stale_after_s"))
        self.params_age_gauge.set(params_age)
        out = {
            "iters_total": self.iters_total,
            "last_iter_age_s": now - self._last_progress,
            "snapshot_version": (-1 if pinned is None else int(pinned)),
            "snapshot_epoch": (0 if snap is None
                               else int(getattr(snap, "epoch", 0))),
            "params_age_s": round(params_age, 4),
            "params_stale": self._manager.params_stale(
                stale_after, age_s=params_age),
            "live_seqs": int(self._active.sum())
            + (1 if self._pf is not None else 0) + len(self._landing()),
            "active_slots": int(self._active.sum()),
            "queue_depth": depth,
            "queue_age_s": age,
            # rides replica heartbeats -> the router's FLEET_PREEMPTS
            # gauge -> the opscenter replica rows
            "preemptions": self.preemptions,
            "stopped": self._stop.is_set(),
        }
        if self._prefix:
            # dedup ADVERTISEMENT (disaggregated serving): the chain
            # hashes content-addressed here, riding replica heartbeats
            # so the router's prefill stage skips shipping blocks this
            # engine already holds. Capped — a truncated advertisement
            # is weaker (those blocks cross the wire and dedup on
            # arrival), never wrong.
            out["cached_chains"] = [
                h.hex() for h in self._pool.indexed_hashes(
                    limit=_CHAIN_ADVERT_CAP)]
        if self.ledger is not None:
            # per-tenant cost, top-N bounded: rides replica heartbeats
            # so the router (and its replica_rows surface) can see who
            # is burning a replica without an obs-plane round trip
            out["tenants"] = self.ledger.heartbeat_rows()
        return out

    def pool_drift(self) -> Optional[str]:
        """Paged-KV accounting sanity: allocator invariant violations,
        or live blocks held while NOTHING is alive to hold them (no
        active slot, no admission mid-prefill, nothing queued, no
        program in flight).
        Refcounted sharing
        is NOT a leak: ``n_live`` counts blocks with holders exactly
        once however many sequences share them, and prefix-cached
        blocks whose refcount hit zero sit in the pool's CACHED tier,
        outside ``n_live`` entirely. Sampled racily — the watchdog
        requires the verdict to persist across two polls before
        tripping."""
        msg = self._pool.drift()
        if msg is not None:
            return msg
        # chaos-squeezed blocks are live-with-no-sequence BY DESIGN —
        # the leak heuristic must not read a staged pool squeeze as a
        # lost reservation
        live_blocks = self._pool.n_live - len(self._squeezed)
        if (live_blocks > 0 and not self._active.any()
                and self._pf is None and not self._q and not self._flight):
            return (f"{live_blocks} live block(s) with zero live "
                    f"sequences (leaked reservation)")
        return None

    # -- engine loop --------------------------------------------------------
    def _req_hashes(self, req: _Request) -> List[bytes]:
        """The prompt's full-block hash chain, memoized per seed (the
        admission gate polls it every loop pass while a request waits
        for blocks; a pin move invalidates the memo)."""
        if req.hashes is None or req.hash_seed != self._hash_seed:
            req.hashes = chain_hashes(req.prompt, self._block_size,
                                      self._hash_seed)
            req.hash_seed = self._hash_seed
        return req.hashes

    def _prefix_usable_hits(self, req: _Request) -> int:
        """Net blocks the prefix cache saves ``req`` against the
        RECLAIMABLE supply (free + cached) the gate checks — a peek, no
        refcounts move. A live-shared hit is a pure saving; a hit on a
        CACHED block saves the prefill but still consumes one unit of
        that supply when lookup reactivates it, so it cancels out of
        the arithmetic (counting it double let an admission pass the
        gate and then run the allocator dry mid-reservation). A FULLY
        cached prompt costs one more fresh block: its last block gets
        copy-on-written so the first decode step can land P-1's K/V.
        Floored at ZERO: the CoW dup's cost is offset by its decref'd
        source returning to the reclaimable pool before the fresh
        allocation runs, so the true supply draw never exceeds the
        plain uncached reservation — without the floor, a block-aligned
        max-context prompt re-hitting its own cached blocks computed
        need = capacity + 1 and deadlocked the FIFO head forever
        (regression-tested)."""
        m, cached = self._pool.peek_counts(self._req_hashes(req))
        usable = m - 1 if (m and m * self._block_size == len(req.prompt)) \
            else m
        return max(0, usable - cached)

    def _reservation_blocks(self, req: _Request) -> int:
        """The admission's reservation size. Worst case by default:
        ``prompt + remaining generation`` worth of blocks (``prompt``
        already folds in any pre-preemption emitted tokens, which
        ``max_new`` also counts — hence the subtraction). With
        ``-preempt`` (optimistic admission) it is the PROMPT's blocks
        only — the generation grows block-by-block at decode time and
        preemption supplies blocks under pressure — EXCEPT for a
        request whose preemption budget is already spent: that one
        re-admits pessimistically, so it can never need growth, never
        be preempted again, and never churn (the anti-livelock
        backstop)."""
        if req.pf_only:
            # prefill-only admissions never decode: the prompt's blocks
            # are the whole reservation (no growth, no CoW headroom)
            return self._pool.blocks_needed(len(req.prompt))
        if self._preempt_on and req.preempts < self._preempt_budget:
            return self._pool.blocks_needed(len(req.prompt))
        return self._pool.blocks_needed(
            len(req.prompt) + req.max_new - len(req.out))

    def _blocks_cover(self, req: _Request) -> bool:
        """The admission gate: a request admits only when its
        reservation (:meth:`_reservation_blocks` — worst-case by
        default, prompt-only under ``-preempt`` — and, with prefix
        caching, less the cached blocks it will share instead of
        allocate) fits the reclaimable pool (free list + evictable
        cached blocks). A false verdict leaves it QUEUED — completions
        free blocks at iteration granularity, so it admits as soon as
        enough return; only a request larger than the entire pool could
        wait forever, and ``submit`` shed that case up front (no
        admission deadlock, tested)."""
        need = self._reservation_blocks(req)
        if self._prefix:
            need -= self._prefix_usable_hits(req)
        return need <= self._pool.n_free + self._pool.n_cached

    def _drop_expired(self, dropped: List[_Request]) -> None:
        """Deadline enforcement lands at queue-POP time: the scheduler
        hands back every expired request its scan touched, and the
        engine fails them HERE — before a single prefill FLOP is spent
        on an answer whose requester stopped waiting (the pre-PR
        behaviour ran the full prefill first). Futures resolve outside
        the engine lock: their done-callbacks are user code."""
        now = time.monotonic()
        for req in dropped:
            self.deadline_drops += 1
            self.deadline_counter.inc()
            if req.usage is not None:
                # the whole life was queue wait; attribution closes here
                req.usage.queue_wait_ms += (now - req.usage.t_wait0) * 1e3
                self._finalize_usage(req, "deadline", now)
            if trace.enabled() and req.ctx is not None:
                trace.record_span("queue.wait", req.ctx, req.t_enq, now,
                                  cause="deadline")
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(DeadlineExceededError(
                    f"decode request rid {req.rid} missed its deadline "
                    f"after {now - req.t_enq:.3f}s queued "
                    f"(engine {self.name!r})"))

    def _pop_admissible(self):
        """One pop through the weighted-fair lane scheduler (expired
        deadlines dropped at pop, bounded lookahead past a block-starved
        head), gated on the block pool covering the arrival's
        reservation: ``(request or None, expired)``. The pop decision
        and the queue mutation are one step under the engine lock."""
        with self._cv:
            if not self._q:
                return None, []
            return self._q.pop_admissible(time.monotonic(),
                                          self._blocks_cover)

    def _loop(self) -> None:
        """The loop thread: wait for work, then one :meth:`_iteration`
        a pass. The loop keeps the device ONE PASS AHEAD: a pass
        dispatches its step and its chunk first and only then retires
        (syncs and books) what the pass before left in flight, so the
        device has its next program queued when one ends, and the
        host's syncs, bookings and records run under device work.
        Between passes at most one step and one chunk are in flight
        (``_flight``), inside a pass at most two of each. Wherever
        ``stop()``, ``_maybe_refresh``, ``_preempt``, a KV splice and
        the idle wait run, nothing a pass before dispatched is in
        flight: :meth:`_drain` retired it first."""
        while True:
            splices: List[tuple] = []
            with self._cv:
                while (not self._q and self._pf is None
                       and not self._active.any() and not self._flight
                       and not self._loop_work
                       and not self._stop.is_set()):
                    with self._phase("engine.wait"):
                        self._cv.wait()
                if (self._stop.is_set() and not self._q
                        and self._pf is None and not self._active.any()
                        and not self._flight):
                    # release any splice waiters before the loop dies —
                    # a blocked replica drain thread must not hang on a
                    # transfer the loop will never apply
                    while self._loop_work:
                        _, done, info = self._loop_work.popleft()
                        info["skipped"] = "stopped"
                        done.set()
                    return
                if self._loop_work:
                    splices = list(self._loop_work)
                    self._loop_work.clear()
            # Phases (trace.PhaseClock: the host's clock always, the
            # profiler's too under a session). With something live,
            # prefilling, in flight or to splice the pass is sure of
            # work and is ONE engine.iter, to the end of
            # _record_iteration. From idle the queue decides: an arrival
            # makes it an iteration (the pop itself, microseconds, is in
            # neither), and a pass that finds only block-starved or
            # expired waiters is an engine.wait
            arrival, expired = None, []
            if not (splices or self._pf is not None or self._active.any()
                    or self._flight):
                if self._free_q:
                    arrival, expired = self._pop_admissible()
                if arrival is None:
                    # nothing live and nothing admissible: the queue
                    # holds only block-starved waiters (a budget-
                    # exhausted pessimistic re-admission, or a chaos-
                    # squeezed pool) or expired ones: yield briefly
                    # instead of hot-spinning until blocks free
                    with self._phase("engine.wait"):
                        self._drop_expired(expired)
                        time.sleep(0.0005)
                    continue
            with self._phase("engine.iter"):
                if expired:
                    with self._phase("engine.admit"):
                        self._drop_expired(expired)
                alive = self._iteration(splices, arrival)
            if self._clock.pass_ns > self._slowest_ns:
                self._keep_slowest()
            if not alive:
                return

    def _keep_slowest(self) -> None:
        """The pass just left is the longest since ``reset_stats()``:
        keep its row, the gap before it and what the engine held."""
        clock = self._clock
        self._slowest_ns = clock.pass_ns
        self._slowest = (self.iters_total, clock.pass_end_ns / 1e9,
                         clock.pass_ns, clock.gap_ns,
                         int(self._active.sum()), len(self._q),
                         tuple(clock.row_ns))

    def _iteration(self, splices: List[tuple],
                   arrival: Optional[_Request]) -> bool:
        """One pass that does work: it hands the device THIS pass's
        programs and then retires the LAST pass's, so everything the
        host does between two dispatches runs under programs already
        queued:

        1. ``engine.step``: grow reservations, propose drafts, dispatch
           the step (or verify window) over the slots live NOW that
           their ``max_new`` has not counted out. The step's tokens are
           the last step's output, still on the device, merged with the
           host's for the slots that went live since. Positions and the
           by-count budget advance here. No wait.
        2. ``engine.admit``: pop and reserve: full prefix hits go live
           (they join the NEXT pass's step), the first admission that
           needs prefill becomes ``_pf``. (Work parked for the loop
           thread, a KV splice or a warm-up, has an ``engine.admit`` of
           its own BEFORE the step, behind a drain.)
        3. ``engine.prefill_chunk``: build and dispatch ONE budget-sized
           chunk of ``_pf``, queued on the device behind the step; the
           prompt's offset advances here, and after its last chunk
           ``_pf`` is free for the next admission. No wait.
        4. ``engine.step.sync`` then ``engine.step.book``: fetch the
           tokens of the step the pass BEFORE dispatched and book them
           against the slots it was dispatched with.
        5. ``engine.prefill_chunk.sync`` then
           ``engine.prefill_chunk.book``: wait for the chunk the pass
           before dispatched and book it: prefix registration and,
           after a prompt's last chunk, the first token and the slot
           going live (it joins the next pass's step).
        6. ``engine.record``.

        The depth is decided each pass from what the loop holds. A pass
        starts with a :meth:`_drain` (4 and 5 come first, nothing of an
        earlier pass is in flight from there) when
        :meth:`_drain_cause` names a cause, and again when growth
        would have to preempt; a ``spec_k`` engine, where acceptance
        decides the positions, retires its own programs in 4 and 5 of
        the same pass. Returns False when the pass failed and the loop
        must die."""
        # the progress clock restarts when the loop picks work up:
        # last_iter_age_s then measures how long THIS pass has been
        # stuck, not how long the engine idled beforehand (an idle
        # engine is not a stalled one — the watchdog's distinction)
        self._last_progress = time.monotonic()
        self._it_admitted.clear()
        self._it_completed.clear()
        self._it_prefill = self._it_decode = 0
        self._it_spec_proposed = self._it_spec_accepted = 0
        self._it_sp_chunks = 0
        self._it_live_blocks = -1
        self._it_behind = self._it_ahead = 0
        arrivals = [] if arrival is None else [arrival]
        worked = bool(self._flight or splices)
        try:
            cause = self._drain_cause(splices) if self._flight else None
            if cause is not None:
                self._drain(cause)
            if splices:
                # inbound KV transfers (and a warm-up) apply OUTSIDE the
                # engine lock on this (loop) thread — the only thread
                # allowed to reassign the donated caches — with nothing
                # in flight. A bad payload degrades (accounting says
                # so); the waiter is released either way
                with self._phase("engine.admit"):
                    for work, done, info in splices:
                        try:
                            info.update(work())
                        except Exception as exc:    # pragma: no cover
                            info["skipped"] = (f"failed on the loop "
                                               f"thread: {exc}")
                            info["error"] = exc
                        finally:
                            done.set()
                    # a splice's own program has no step or chunk behind
                    # it to be waited by: retired here (microseconds of
                    # device work), so that an empty ``_flight`` means an
                    # idle device
                    jax.block_until_ready(self._pools)
            step = chunk = None
            if self._running().any():
                with self._phase("engine.step"):
                    step = self._dispatch_step()
                if step is None and self._flight:
                    # growth met a dry pool with programs in flight:
                    # their bookings may free blocks, and a victim's
                    # emitted tokens are booked before it is requeued
                    self._drain("preempt")
                    with self._phase("engine.step"):
                        step = self._dispatch_step()
            with self._phase("engine.admit"):
                self._admit(arrivals)
                # the sequences held at once: a prompt whose last chunk
                # goes out below moves from _pf to landing, so the count
                # stands from here to the end of the pass
                live = (int(self._active.sum()) + (self._pf is not None)
                        + len(self._landing()))
                if live > self.peak_live:
                    self.peak_live = live
            if self._pf is not None:
                # AT MOST one budget-sized chunk per iteration, and at
                # most one pass of programs ahead: what an admission can
                # add to a live generation's next token is two chunks
                # and two steps of device work, whatever the arrivals
                with self._phase("engine.prefill_chunk"):
                    chunk = self._dispatch_chunk()
                if step is not None:
                    # queued on the device behind the step in flight:
                    # its launch falls under the step's run
                    self.chunks_behind_step += 1
                    self._it_behind = 1
            # what earlier passes left in flight, oldest first, under
            # this pass's programs
            self._retire((step is not None) + (chunk is not None))
            if self._spec and self._flight:
                self._drain("spec")
        except Exception as exc:          # pragma: no cover - defensive
            # arrivals are already popped from the queue but may not
            # be slotted yet — include them so their futures fail too
            self._fail_all(exc, arrivals)
            return False
        if (worked or step is not None or chunk is not None
                or self._it_admitted):
            with self._phase("engine.record"):
                self._record_iteration()
        return True

    def _drain_cause(self, splices: List[tuple]) -> Optional[str]:
        """Why this pass may not dispatch ahead of what is in flight
        (None: it may), from state the loop holds: work parked for the
        loop thread (a KV splice, a warm-up), or nothing live and
        nothing prefilling, so that what is in flight (a prompt's last
        chunk, a step that ran its slots past an eos) is all the engine
        has: it is retired before the loop stops, goes idle or admits
        into an empty engine, where ``_maybe_refresh`` may move the
        pin."""
        if splices:
            return "splice"
        if not self._active.any() and self._pf is None:
            return "empty"
        return None

    def _drain(self, cause: str) -> None:
        """Retire everything in flight, oldest first: depth 0 from here
        to the next dispatch."""
        self.drains[cause] = self.drains.get(cause, 0) + 1
        self._retire(0)

    def _retire(self, keep: int) -> None:
        """Retire the oldest programs in flight down to the ``keep``
        newest. An item leaves ``_flight`` only once it is booked: a
        device error surfaces at its sync, and a prompt's last chunk is
        all that still names its request (off ``_pf``, slot not live),
        so ``_fail_all`` must find it there."""
        while len(self._flight) > keep:
            item = self._flight[0]
            if isinstance(item, _StepInFlight):
                self._retire_step(item)
            else:
                self._retire_chunk(item)
            self._flight.popleft()

    def _running(self) -> np.ndarray:
        """The slots the next step runs: live, and not counted out by
        the steps already dispatched for them."""
        return self._active & (self._left > 0)

    def _landing(self) -> List[_Request]:
        """Requests whose prompt's last chunk is in flight: off ``_pf``
        already, their slot not live yet."""
        return [f.req for f in list(self._flight)
                if isinstance(f, _ChunkInFlight) and f.final]

    def _admit(self, arrivals: List[_Request]) -> None:
        """Admission onto the explicit free-slot set. One admission
        prefills at a time: the NEXT request is only picked up once the
        current one goes live. Zero-cost admissions (a full prefix hit
        goes live without a single prefill chunk) must not consume the
        iteration's one admission slot: keep admitting until a chunk is
        actually pending or nothing is admissible, so a full-hit-heavy
        trace admits at slot rate instead of one request per iteration
        (the per-iteration chunk budget is what bounds ITL, and these
        admissions cost no chunk). ``arrivals`` holds the request a pass
        from idle already popped, and collects every later pop, so the
        failure path reaches requests that are popped and not yet
        slotted."""
        if arrivals:
            self._begin_prefill(arrivals[0], self._free_q.popleft())
        while self._pf is None and self._free_q:
            req, expired = self._pop_admissible()
            self._drop_expired(expired)
            if req is None:
                break
            arrivals.append(req)
            self._begin_prefill(req, self._free_q.popleft())
            if req.slot == -1:
                # the reservation raced a pool claimant and the request
                # was requeued — retry next iteration rather than
                # spinning here
                break

    def _record_iteration(self) -> None:
        """One iteration retired: bump the progress clock/counters and
        append the flight-recorder record. Reads of queue/pool state are
        intentionally lock-light — these are gauge samples for the black
        box, not accounting. The pass's times are reads of the phase
        clock: ``busy_ms`` from the pass's start to here, ``step_ms``
        the row's three step phases."""
        now = time.monotonic()
        clock = self._clock
        row = clock.row_ns
        busy_ms = (time.perf_counter_ns()
                   - self._phase("engine.iter").t0_ns) / 1e6
        self.iters_total += 1
        self.iters_counter.inc()
        self._last_progress = now
        it_block_s = 0.0
        if self.ledger is not None:
            # KV residency integrates here: every admitted sequence is
            # charged reserved-blocks x this iteration's wall (host
            # floats only — same cost posture as the recorder itself)
            dt = busy_ms / 1e3
            reqs = self._admitted_requests()
            self.ledger.charge_iteration(reqs, dt)
            it_block_s = dt * sum(len(r.blocks) for r in reqs)
        recorder = self.recorder
        if recorder is None:
            return
        # the pass's row in ms; engine.record's entry (the last) is this
        # call's time up to here
        phases = [ns / 1e6 for ns in row]
        phases[-1] = (time.perf_counter_ns()
                      - self._phase("engine.record").t0_ns) / 1e6
        try:
            oldest = self._q.oldest_t_enq()
        except (IndexError, RuntimeError):   # racing a concurrent submit
            oldest = None
        recorder.record((
            self.iters_total, now, busy_ms,
            sum(row[i] for i in _STEP_PHASES) / 1e6,
            int(self._active.sum()), 1 if self._pf is not None else 0,
            len(self._q),
            0.0 if oldest is None else (now - oldest) * 1e3,
            self._it_prefill, self._it_decode,
            self._pool.n_free, self._pool.n_live, self._pool.n_shared,
            self._snap.version if self._snap is not None else -1,
            tuple(self._it_admitted), tuple(self._it_completed),
            self._it_spec_proposed if self._spec else -1,
            self._it_spec_accepted if self._spec else -1,
            1 if self._kv_quant else 0,
            # written-block occupancy PROXY (live + cached pool blocks)
            # — the real nonzero-scale count lives on the device, and
            # the recorder's cost posture forbids a per-iteration sync
            (self._pool.n_live + self._pool.n_cached)
            if self._kv_quant else -1,
            # tenant accounting tail (FIELDS append at the END; -1 =
            # ledger off): this iteration's KV block-seconds charge and
            # the live tenant cardinality
            round(it_block_s, 6) if self.ledger is not None else -1.0,
            (self.ledger.tenant_count() if self.ledger is not None
             else -1),
            # seqpar tail (FIELDS append at the END; -1 = prefill_sp
            # off): chunks this iteration dispatched through the
            # sequence-parallel program
            self._it_sp_chunks if self._sp else -1,
            # live-block tail (FIELDS append at the END; -1 = a pass
            # that ran no step): the share of the slots x M table
            # entries this pass's step had to read
            (self._it_live_blocks
             / (self.config.slots * self._blocks_per_seq))
            if self._it_live_blocks >= 0 else -1.0,
            # overlap tail (FIELDS append at the END): 1 when this
            # pass's chunk was dispatched behind its in-flight step
            self._it_behind,
            # run-ahead tail (FIELDS append at the END): 1 when this
            # pass's step was dispatched with the step before unread
            self._it_ahead,
            # phase tail (FIELDS append at the END): the pass's row, in
            # the order of meta["phases"], and the loop gap between the
            # pass before and this one
            phases, clock.gap_ns / 1e6))

    def _xfer_block_shape(self) -> tuple:
        """One block of the first pool as the transfer plane ships it:
        ``(layers, block_size, width)``."""
        shape = self._pools[0].shape
        return (int(shape[0]), self._block_size, int(shape[3]))

    def _seed_for(self, version: int) -> bytes:
        """Hash-chain seed for a pinned snapshot version. kv_quant tags
        the seed: cached K/V bytes are a function of (token prefix,
        params version, POOL ENCODING) — an int8 block and an fp block
        for the same prefix hold different bytes, so their chain
        identities must differ. This is also what makes cross-mode KV
        transfer degrade cleanly: a quant payload arriving at a
        kv_quant=none replica fails the seed/dtype checks and the
        receiver re-prefills locally (chaos-tested)."""
        if self._kv_quant:
            return f"{int(version)}/int8".encode()
        return str(int(version)).encode()

    def _maybe_refresh(self, hold: bool = False) -> None:
        """Move the pinned snapshot only while NO generation is in
        flight — neither live slots, nor a mid-prefill admission, nor
        (``-preempt``) a PREEMPTED request awaiting resume anywhere in
        the queue (``hold`` covers the one being re-admitted right
        now, already popped). A resume recomputes its tail from
        prompt + emitted tokens, and that recompute is only
        bit-identical under the SAME params the first life pinned — so
        preemption extends the pin's lifetime across the eviction gap,
        and the surfaced trade is staleness, never a mixed-version
        generation. Nor while a program is in flight: a pass that
        could move the pin starts with a drain (:meth:`_drain_cause`)."""
        snap = self._snap
        if snap is None:
            snap = self._manager.current()
        elif (not hold and not self._active.any() and self._pf is None
                and self._q.n_resumed == 0 and not self._flight):
            snap = self._manager.ensure_fresh(self.config.max_staleness_s)
        if self._snap is not snap or self._pinned is None:
            # the decode copy memoizes on snapshot VERSION: a drain/
            # re-pin cycle (or a forced re-publish) without an
            # intervening version move reuses the existing replica —
            # the full-tree copy only happens when training actually
            # produced new params
            if self._pinned is None or snap.version != self._pinned_version:
                # one copy per pinned VERSION, amortized over the whole
                # generation stream the pin serves: tp=1 replicates onto
                # one device (snapshot.replicate_for_decode — ~10x
                # per-step wall through the partitioner otherwise,
                # sharded fallback multi-process); tp>1 reshards onto
                # the decode mesh (snapshot.shard_for_decode), matching
                # the pre-partitioned programs' in_shardings exactly
                with trace.span("snapshot.pin", engine=self.name,
                                version=snap.version):
                    # the model's pin (serving/programs.py): a replica
                    # on one device, a reshard onto the decode mesh
                    # (host-quantized first under decode_param_quant=
                    # int8: host numpy on purpose, building a jit on the
                    # loop thread would be an RT106 hazard), or, for a
                    # serve-only model, the weights themselves
                    self._pinned = self._progs.pin(snap.value)
                self._pinned_version = snap.version
                self.pin_copies += 1
            self._snap = snap
            if self._prefix:
                # the hash chain is scoped to the params the K/V was
                # computed under: when the pin moves, cached blocks are
                # garbage to the new version — flush them (the version
                # seed alone would keep them resident but unreachable,
                # silently shrinking effective capacity)
                seed = self._seed_for(snap.version)
                if seed != self._hash_seed:
                    self._hash_seed = seed
                    self._pool.flush_cache()

    def _reserve_blocks(self, req: _Request, slot: int) -> None:
        """Build the admission's reservation
        (:meth:`_reservation_blocks` — ``prompt + max_new`` positions
        worst-case, the prompt's positions only under optimistic
        ``-preempt`` admission) and install it in the slot's block
        table row — the loop's ``_blocks_cover`` gate guaranteed
        coverage, so this cannot fail (a racing chaos pool squeeze is
        the one exception; ``_begin_prefill`` requeues on it).

        With prefix caching the reservation SPLICES: the longest cached
        prefix of the prompt is claimed from the content index (those
        blocks gain a holder instead of being allocated) and only the
        remainder comes off the free list. A fully cached prompt
        additionally copy-on-writes its LAST matched block: the first
        decode step recomputes position ``P - 1`` and writes its K/V
        there, and a write must never land in a shared block — the copy
        happens here, host-dispatched, before the table is ever handed
        to the jitted step."""
        total = self._reservation_blocks(req)
        matched: List[int] = []
        hashes: List[bytes] = []
        full_hit_cow = False
        if self._prefix:
            hashes = self._req_hashes(req)
            matched = self._pool.lookup(hashes)
            req.n_hit = len(matched)
            req.full_hit = bool(matched) and (
                len(matched) * self._block_size == len(req.prompt))
            # claimed blocks land on the request IMMEDIATELY: if an
            # alloc below races a concurrent pool claimant and raises,
            # the requeue path can decref exactly what was taken
            req.blocks = matched
            # a prefill-only full hit skips the CoW: nothing will ever
            # WRITE this sequence (no decode step recomputes P-1), so
            # the last matched block stays shared and the payload
            # fetches straight from the cached blocks
            if req.full_hit and not req.pf_only:
                shared_last = matched[-1]
                dup = self._pool.alloc(1)[0]
                self._pools = tuple(self._cow_fn(
                    *self._pools, np.int32(shared_last), np.int32(dup)))
                self._pool.decref([shared_last])
                matched[-1] = dup
                full_hit_cow = True
            req.saved = (len(req.prompt) if req.full_hit
                         else req.n_hit * self._block_size)
        req.blocks = matched + self._pool.alloc(total - len(matched))
        # stats commit only once the WHOLE reservation stands: a
        # squeeze-raced alloc raise requeues the request, and its
        # re-admission must not count the same hits/saves twice
        if self._prefix:
            if full_hit_cow:
                self.cow_copies += 1
            self.prefix_hits += req.n_hit
            self.prefix_misses += len(hashes) - req.n_hit
            self.prefill_tokens_saved += req.saved
            if req.usage is not None:
                # same commit point as the engine mirror, so the
                # per-tenant saved sum reconciles exactly (requeue-on-
                # race never reaches here; a preempted resume recommits
                # on both sides alike)
                req.usage.prefill_tokens_saved += req.saved
        row = self._block_tables[slot]
        row[:] = SCRATCH_BLOCK
        row[: total] = req.blocks

    def _release_seq(self, req: _Request) -> None:
        """Completion (eos / max_new / eos-at-first-token): the slot
        returns to the free set and the reservation's blocks
        drop this holder — at iteration granularity, so a same-
        iteration queued admission can reuse them on the very next
        loop pass (tested). ``decref``, not ``free``: a block shared
        with a live sequence stays live under its remaining holders,
        and a content-addressed block parks in the pool's cached-LRU
        tier instead of losing its identity — the next shared-prefix
        arrival reactivates it without re-prefilling. Decref TAIL
        first: release order is LRU order, and peek/lookup walk the
        hash chain head-first, so eviction must shrink a chain from
        its END — a head-first release would have pressure evict the
        chain's first block and strand every cached suffix block as
        unreachable dead weight (the vLLM eviction convention)."""
        if req.blocks:
            self._pool.decref(reversed(req.blocks))
            req.blocks = []
            self._block_tables[req.slot][:] = SCRATCH_BLOCK
        self._free_q.append(req.slot)

    def _begin_prefill(self, req: _Request, slot: int) -> None:
        """Reserve ``slot`` (and its KV blocks) and pin the snapshot for
        one admission; its prompt then prefills one chunk per iteration.
        The reserved-not-live admission keeps its blocks for its whole
        lifetime — a concurrent wave cannot steal a mid-prefill
        sequence's cache out from under it."""
        self._maybe_refresh(hold=req.resumed)
        req.version = self._snap.version
        req.slot = slot
        try:
            self._reserve_blocks(req, slot)
        except RuntimeError:
            # a concurrent pool claimant (the chaos pool squeeze is the
            # one in-contract case) raced the admission gate: requeue
            # the request instead of killing the loop thread — exactly
            # a preemption-before-any-work, minus the accounting
            if req.blocks:
                self._pool.decref(reversed(req.blocks))
                req.blocks = []
            self._block_tables[slot][:] = SCRATCH_BLOCK
            self._free_q.append(slot)
            req.slot = -1
            req.hashes = None
            req.n_hit = 0
            req.full_hit = False
            with self._cv:
                self._q.appendleft(req)
            return
        req.pf_chunks = 0
        req.t_admit = time.monotonic()   # queue.wait ends here
        if not req.resumed:
            # a resumed request waited in its first life; t_enq is that
            # life's, and the sample was taken there
            self.qwait_hist.record((req.t_admit - req.t_enq) * 1e3)
        if req.usage is not None:
            req.usage.queue_wait_ms += (req.t_admit
                                        - req.usage.t_wait0) * 1e3
        if self._spec:
            # prompt-lookup drafting indexes the prompt up front; every
            # emitted token extends the index incrementally from here
            req.drafter = _PromptLookup()
            req.drafter.extend(req.prompt)
        self._it_admitted.append(req.rid)
        if self._prefix and req.full_hit and req.pf_only:
            # prefill-only admission of a fully cached prompt: every
            # block is already resident (and stays shared — reservation
            # skipped the CoW), so the payload fetches immediately and
            # the slot never goes live
            self._pf = None
            self._finish_prefill_only(req, chunks=0)
            return
        if self._prefix and req.full_hit:
            # the WHOLE prompt was cached: no prefill at all. The slot
            # goes live at position P-1 with the prompt's last token as
            # input — the next fused step recomputes that position's
            # K/V (into the block CoW'd at reservation), and its output
            # IS the request's first token (TTFT = one decode step).
            if trace.enabled() and req.ctx is not None:
                now = time.monotonic()
                extra = dict(self._mesh_attrs)
                if req.preempts:
                    extra["preempted"] = req.preempts
                if req.xfer:
                    # the splice that warmed this prefix (disaggregated
                    # stage 2): the trace links the hit to the wire
                    extra["xfer_blocks"] = req.xfer.get("xfer_blocks", 0)
                    extra["xfer_bytes"] = req.xfer.get("xfer_bytes", 0)
                    extra["dedup_blocks"] = req.xfer.get(
                        "dedup_blocks", 0)
                trace.record_span("queue.wait", req.ctx, req.t_enq,
                                  req.t_admit, cause="admission")
                trace.record_span(
                    "decode.admit", req.ctx, req.t_admit, now,
                    slot=slot, prompt_len=len(req.prompt), chunks=0,
                    budget=self._budget, snapshot_version=req.version,
                    blocks=len(req.blocks), pool_free=self._pool.n_free,
                    prefix_hit_blocks=req.n_hit,
                    prefill_tokens_saved=req.saved, **extra)
            # a RESUMED full hit already recorded its TTFT in its first
            # life: the next fused-step token is an inter-token gap
            req.ttft_pending = not req.resumed
            # the ITL base moves to ADMISSION: the next step's first
            # token records TTFT, but a speculative window's extra
            # tokens divide (now - t_last) as ITL samples — left at
            # t_enq, a queued full hit would bleed its whole queue wait
            # into the ITL histogram (review-found, regression-tested)
            req.t_last = req.t_admit
            self._go_live(req, int(req.prompt[-1]), len(req.prompt) - 1)
            self._pf = None
            return
        # chunked prefill starts at the first UNCACHED token (block-
        # aligned); the matched prefix blocks are already in the table
        req.pf_off = req.n_hit * self._block_size if self._prefix else 0
        req.pf_reg = req.n_hit
        # seqpar routing decides per REQUEST, once: prompts at/above the
        # threshold take the budget * tp sequence-parallel chunks, the
        # rest keep the single-lane program bit-for-bit
        req.sp = self._sp and len(req.prompt) >= self._sp_threshold
        self._pf = req

    def _go_live(self, req: _Request, tok: int, pos: int) -> None:
        """``req``'s slot joins the next step dispatched: its input
        token is the host's (``_fresh``), and it may be dispatched in
        as many steps as it has tokens left to emit."""
        slot = req.slot
        self._slot_req[slot] = req
        self._tok[slot] = tok
        self._fresh[slot] = True
        self._pos[slot] = pos
        self._left[slot] = req.max_new - len(req.out)
        self._active[slot] = True

    def _dispatch_chunk(self) -> _ChunkInFlight:
        """Build and dispatch ONE budget-sized chunk of the in-flight
        admission's prefill; no wait. The prompt's offset and the
        prefill accounting advance here; after the prompt's last chunk
        ``_pf`` is free for the next admission, and the request lands
        (first token, slot live) when the chunk is retired."""
        req = self._pf
        sp = req.sp
        C = self._sp_chunk if sp else self._budget
        off = req.pf_off
        n = min(C, len(req.prompt) - off)
        toks = np.zeros(C, np.int32)
        toks[: n] = req.prompt[off: off + n]
        t0 = time.monotonic() if trace.enabled() else 0.0
        chunk_fn = self._chunk_sp_fn if sp else self._chunk_fn
        # the program gets its OWN block tables: bookings run under the
        # chunk and reset the rows of the slots they free, and a
        # dispatched program may read a host array late
        *pools, logits = chunk_fn(
            self._pinned, *self._pools, self._block_tables.copy(),
            np.int32(req.slot), toks, np.int32(off), np.int32(n))
        self._pools = tuple(pools)
        self.prefill_chunks += 1
        self.chunk_reach += off + n
        req.pf_off = off + n
        req.pf_chunks += 1
        if sp:
            self.seqpar_chunks += 1
            self._it_sp_chunks += 1
        self.prefill_tokens += n
        self.prefill_tok_counter.inc(n)
        self._it_prefill += n
        if req.usage is not None:
            req.usage.prefill_tokens += n
            if req.resumed:
                # preemption-with-recompute: a resume life's prefill
                # re-computes work a first life already paid for — the
                # vector carries it separately so showback can see the
                # preemption tax (still counted in prefill_tokens: the
                # conservation identity tracks FLOPs actually spent)
                req.usage.recompute_tokens += n
        final = req.pf_off >= len(req.prompt)
        if final:
            self._pf = None
        chunk = _ChunkInFlight(req, logits, off, n, C, req.pf_chunks - 1,
                               final, t0)
        self._flight.append(chunk)
        return chunk

    def _retire_chunk(self, chunk: _ChunkInFlight) -> None:
        """Wait for a chunk dispatched a pass ago (by its own logits:
        the pools have later programs queued on them) and book it: its
        completed blocks gain their content identity, and after the
        prompt's last chunk the first token falls out and the slot goes
        live (or resolves immediately on eos-at-first-token, never
        occupying the slot). Waiting for EVERY chunk, one pass after
        its dispatch, is the in-flight bound: letting chunk dispatches
        run on asynchronously looks free, but an idle->busy transition
        can queue several chunks on the device and the next step's sync
        pays for all of them at once — exactly the unbounded ITL spike
        the budget exists to prevent (measured: p99 went from ~1
        chunk+step to >100 ms under ramp). With at most one pass of
        programs ahead, a live generation's next token waits for at
        most two steps and two chunks."""
        with self._phase("engine.prefill_chunk.sync"):
            # final chunk: the prompt's last real position's logits are
            # the first generated token (exactly a whole-prompt
            # prefill's gather); a pf_only prompt's fall on the floor
            logits = (np.asarray(chunk.logits)
                      if chunk.final and not chunk.req.pf_only
                      else jax.block_until_ready(chunk.logits))
        with self._phase("engine.prefill_chunk.book"):
            self._book_chunk(chunk, logits)

    def _book_chunk(self, chunk: _ChunkInFlight, logits) -> None:
        """What a synced chunk means on the host: its completed blocks'
        content identity and, after a prompt's last chunk, the first
        token, the histograms and the slot going live."""
        req, _, off, n, C, index, final, t0 = chunk
        tracing = trace.enabled()
        if self._prefix:
            # every prompt block this chunk COMPLETED gains its content
            # identity now, not at release: a concurrent same-prefix
            # arrival can share a still-prefilling sequence's blocks
            # (register no-ops when an identical block beat us to it)
            hashes = self._req_hashes(req)
            while (req.pf_reg < len(hashes)
                   and (req.pf_reg + 1) * self._block_size <= off + n):
                self._pool.register(req.blocks[req.pf_reg],
                                    hashes[req.pf_reg])
                req.pf_reg += 1
        if tracing and req.ctx is not None:
            # seqpar ENGINES annotate every chunk span (sp=0 marks a
            # below-threshold prompt on the single-lane program); off-sp
            # engines' spans stay flat — the metrics regression contract
            sp_attrs = ({"sp": int(req.sp), "sp_backend": self._sp_backend}
                        if self._sp else {})
            trace.record_span(
                "decode.prefill_chunk", req.ctx, t0, time.monotonic(),
                slot=req.slot, offset=off, chunk=index,
                tokens=n, budget=C, **sp_attrs)
        if not final:
            return
        if req.pf_only:
            # prefill-only admission (disaggregated stage 1): no first
            # token — the prompt's finished blocks ARE the result. The
            # logits fall on the floor by design: the decode side
            # recomputes P-1 through its own full-hit CoW step, which
            # is what keeps disaggregated output bit-identical.
            self._finish_prefill_only(req, chunks=req.pf_chunks)
            return
        tok0 = int(np.argmax(logits))
        now = time.monotonic()
        if req.resumed:
            # preemption recompute: TTFT already happened in the first
            # life — this token is an inter-token gap, and the sample
            # honestly carries the whole preemption stall (t_last is
            # the last PRE-preemption emission)
            self.itl_hist.record((now - req.t_last) * 1e3)
        else:
            self.ttft_hist.record((now - req.t_enq) * 1e3)
        req.t_last = now
        self.tokens += 1
        self.decode_tok_counter.inc()
        self._it_decode += 1
        if req.usage is not None:
            req.usage.decode_tokens += 1
        req.out.append(tok0)
        if req.drafter is not None:
            req.drafter.extend((tok0,))
        if tracing and req.ctx is not None:
            trace.record_span("queue.wait", req.ctx, req.t_enq,
                              req.t_admit, cause="admission")
            extra = {"blocks": len(req.blocks),
                     "pool_free": self._pool.n_free}
            if self._prefix:
                extra["prefix_hit_blocks"] = req.n_hit
                extra["prefill_tokens_saved"] = req.saved
            if req.preempts:
                extra["preempted"] = req.preempts
            if req.xfer:
                extra["xfer_blocks"] = req.xfer.get("xfer_blocks", 0)
                extra["xfer_bytes"] = req.xfer.get("xfer_bytes", 0)
                extra["dedup_blocks"] = req.xfer.get("dedup_blocks", 0)
            extra.update(self._mesh_attrs)
            trace.record_span(
                "decode.admit", req.ctx, req.t_admit, now, slot=req.slot,
                prompt_len=len(req.prompt), chunks=req.pf_chunks,
                budget=C, snapshot_version=req.version, **extra)
        if self._finished(req, tok0):
            # slot never goes live; the inserted K/V is dead weight a
            # later admission overwrites (tested) — slot and blocks
            # return to the free sets immediately
            self._release_seq(req)
            self._resolve(req)
            return
        self._go_live(req, tok0, len(req.prompt))

    def _finish_prefill_only(self, req: _Request, chunks: int) -> None:
        """Prefill-only admission complete (disaggregated stage 1): the
        prompt's full blocks are prefilled (or cache-resident), so fetch
        the ones the receiver did NOT advertise to the host, build the
        :mod:`kv_transfer` payload, release the reservation (the blocks
        park in the CACHED tier — a repeat prompt full-hits locally),
        and resolve the future with the payload instead of tokens. Runs
        on the loop thread: the caches are loop-thread-owned."""
        hashes = self._req_hashes(req)
        # a quantized source ships the pool's native int8 bytes + each
        # block's per-layer scale columns; the payload dtype tells the
        # receiver which splice contract applies (the seed check already
        # scoped the hashes to the same encoding)
        payload = kv_transfer.new_payload(
            len(req.prompt), self._block_size, req.version,
            self._xfer_block_shape(), self._pools[0].dtype)
        if req.tenant:
            # the receiving engine's ledger charges the splice-in bytes
            # to the originating tenant; absent key = default tenant
            payload["tenant"] = req.tenant
        shipped = 0
        for i, h in enumerate(hashes):
            hx = h.hex()
            if hx in req.known:
                # source-side dedup: the receiver advertised this chain
                # prefix — the hash rides, the bytes stay home
                kv_transfer.add_block(payload, hx)
                continue
            # K and V slices (and, quantized, their per-layer scale
            # columns), in the pools' order
            kv_transfer.add_block(payload, hx, *(
                np.asarray(piece) for piece in self._fetch_fn(
                    *self._pools, np.int32(req.blocks[i]))))
            shipped += 1
        nbytes = kv_transfer.payload_bytes(payload)
        dedup = int(payload["dedup_blocks"])
        self.xfer_blocks += shipped
        self.xfer_bytes += nbytes
        self.xfer_dedup += dedup
        self.xfer_blocks_counter.inc(shipped)
        self.xfer_bytes_counter.inc(nbytes)
        if dedup:
            self.xfer_dedup_counter.inc(dedup)
        if req.usage is not None:
            req.usage.xfer_bytes += nbytes
        now = time.monotonic()
        if trace.enabled() and req.ctx is not None:
            trace.record_span("queue.wait", req.ctx, req.t_enq,
                              req.t_admit, cause="admission")
            trace.record_span(
                "decode.admit", req.ctx, req.t_admit, now,
                slot=req.slot, prompt_len=len(req.prompt), chunks=chunks,
                budget=self._budget, snapshot_version=req.version,
                blocks=len(req.blocks), pool_free=self._pool.n_free,
                prefix_hit_blocks=req.n_hit,
                prefill_tokens_saved=req.saved, prefill_only=True,
                xfer_blocks=shipped, xfer_bytes=nbytes,
                dedup_blocks=dedup, **self._mesh_attrs)
        self._finalize_usage(req, "completed", now)
        self._release_seq(req)
        self.completed += 1
        self._it_completed.append(req.rid)
        if req.future.set_running_or_notify_cancel():
            req.future.set_result({
                "xfer": payload,
                "snapshot_version": req.version,
                "staleness_s": self._manager.staleness_s(self._snap)})

    def _apply_splice(self, payload: dict) -> Dict:
        """Splice one received payload into the pool (loop thread).
        Walks the hash chain head-first: an already-indexed hash is an
        arrival-side dedup hit; a hash with shipped bytes allocates one
        block, writes the K/V via the jitted splice program, registers
        the content identity, and decrefs straight into the CACHED tier
        (claimable by the follow-up admission's lookup, evictable under
        pressure). The walk STOPS at the first gap — chain hashes only
        have meaning as prefixes — so a chaos-dropped payload or a full
        pool degrades to a shorter warm prefix, never a wrong one. A
        payload whose pinned-version seed disagrees is skipped whole
        (splicing stale-params K/V would poison the content index)."""
        info: Dict = {"xfer_blocks": 0, "xfer_bytes": 0,
                      "dedup_blocks": 0}
        why = kv_transfer.validate(payload)
        if why is not None:
            info["skipped"] = why
            return info
        # pin a snapshot if nothing has yet (a fresh decode replica may
        # see its first transfer before its first request), then check
        # the payload's version against OUR hash-chain seed
        self._maybe_refresh()
        if self._seed_for(int(payload["snapshot_version"])) != \
                self._hash_seed:
            info["skipped"] = (
                f"snapshot version {payload['snapshot_version']} != "
                f"pinned {self._pinned_version}")
            return info
        if int(payload["block_size"]) != self._block_size:
            info["skipped"] = (f"block size {payload['block_size']} != "
                               f"{self._block_size}")
            return info
        shape = tuple(int(d) for d in payload["shape"])
        if shape != self._xfer_block_shape():
            info["skipped"] = f"block shape {shape} mismatch"
            return info
        dtype = np.dtype(payload["dtype"])
        # the pool's NATIVE dtype, not the model's: an int8 engine
        # splices int8 bytes. The encoding-tagged hash seed means a
        # cross-mode payload normally fails the seed check above; this
        # check is the belt to that suspender (same-version payloads
        # from a differently-configured fleet must still degrade to a
        # local re-prefill, never splice mis-typed bytes)
        expect = np.dtype(self._pools[0].dtype)
        if dtype != expect:
            info["skipped"] = f"dtype {dtype} != {expect}"
            return info
        per_block = kv_transfer.block_nbytes(shape, dtype)
        blocks = payload.get("blocks") or {}
        for hx in payload["hashes"]:
            h = bytes.fromhex(hx)
            if self._pool.peek([h]):
                info["dedup_blocks"] += 1
                continue
            rec = blocks.get(hx)
            if rec is None or not self._pool.can_alloc(1):
                break
            try:
                k, v = kv_transfer.unpack_block(rec, shape, dtype)
                scales = (kv_transfer.unpack_scales(rec, shape[0])
                          if self._kv_quant else None)
            except ValueError:
                break
            if self._kv_quant and scales is None:
                # int8 bytes without their scales are undecodable —
                # stop the walk (prefix semantics) and re-prefill
                break
            blk = self._pool.alloc(1)[0]
            self._pools = tuple(self._splice_fn(
                *self._pools, np.int32(blk), k, v, *(scales or ())))
            self._pool.register(blk, h)
            self._pool.decref([blk])
            info["xfer_blocks"] += 1
            info["xfer_bytes"] += per_block
        self.xfer_blocks += info["xfer_blocks"]
        self.xfer_bytes += info["xfer_bytes"]
        self.xfer_dedup += info["dedup_blocks"]
        if info["xfer_blocks"]:
            self.xfer_blocks_counter.inc(info["xfer_blocks"])
            self.xfer_bytes_counter.inc(info["xfer_bytes"])
        if info["dedup_blocks"]:
            self.xfer_dedup_counter.inc(info["dedup_blocks"])
        if self.ledger is not None and info["xfer_bytes"]:
            # splice-in bytes charge directly (no request exists yet to
            # carry them): the payload's optional "tenant" tag names
            # who pays, a legacy payload bills the default tenant —
            # same site, same amount as the engine mirror above, so
            # the per-tenant xfer sum reconciles exactly
            self.ledger.charge(payload.get("tenant"),
                               xfer_bytes=info["xfer_bytes"])
        return info

    def _propose_drafts(self):
        """Gather this iteration's verification window: up to ``spec_k``
        prompt-lookup drafts per live slot. Drafts clamp to the
        request's REMAINING budget minus one (the correction token
        always fills the final emission), so a valid window write never
        passes position ``prompt + max_new - 2`` — strictly inside the
        worst-case block reservation, which is how the K-token
        overhang is accounted for without reserving a single extra
        block (under optimistic ``-preempt`` admission the same bound
        is what ``_ensure_growth`` sizes each slot's growth to: the
        window length rides ``n_valid``, so speculative writes land in
        grown-and-owned blocks exactly like plain steps' writes do).
        Returns ``(None, None)`` when no slot drafted: the
        iteration then runs the plain fused step, so a spec engine's
        draft-less iterations (and the whole life of a ``spec_k=0``
        engine) stay on today's path bit-for-bit."""
        K = self._spec
        toks = n_valid = None
        for s in range(self.config.slots):
            req = self._slot_req[s]
            if req is None:
                continue
            limit = min(K, req.max_new - len(req.out) - 1)
            if limit <= 0:
                continue
            drafts = req.drafter.propose(limit)
            if not drafts:
                continue
            if toks is None:
                toks = np.zeros((self.config.slots, K + 1), np.int32)
                toks[:, 0] = self._tok
                n_valid = np.ones(self.config.slots, np.int32)
            toks[s, 1: 1 + len(drafts)] = drafts
            n_valid[s] = 1 + len(drafts)
        return toks, n_valid

    def _admitted_requests(self) -> List[_Request]:
        reqs = [r for r in self._slot_req if r is not None]
        if self._pf is not None:
            reqs.append(self._pf)
        return reqs + self._landing()

    def _pick_victim(self, grower: _Request) -> Optional[_Request]:
        """Preemption victim policy: among admitted sequences (live
        slots plus the reserved-not-live mid-prefill admission), pick
        the LOWEST-priority then YOUNGEST one — never the grower
        itself, and NEVER the overall-oldest sequence (the
        guaranteed-progress floor: whatever the churn, the oldest
        admission runs to completion, which is what makes preemption
        terminate). A victim must additionally have preemption budget
        left and rank below the grower (strictly lower class, or the
        same class but younger) — EXCEPT when the grower IS the
        oldest: the floor outranks budget and class, because the
        submit-time shed gate guarantees the oldest's worst case fits
        once every other holder is evicted, and the whole design
        hinges on the oldest always completing."""
        cands = [r for r in self._admitted_requests() if r is not grower]
        if not cands:
            return None
        oldest = min(cands + [grower], key=lambda r: r.t_enq)
        cands = [r for r in cands if r is not oldest]
        if not cands:
            return None
        if oldest is not grower:
            cands = [r for r in cands
                     if r.preempts < self._preempt_budget
                     and (r.priority < grower.priority
                          or (r.priority == grower.priority
                              and r.t_enq > grower.t_enq))]
            if not cands:
                return None
        return min(cands, key=lambda r: (r.priority, -r.t_enq))

    def _preempt(self, req: _Request, why: str = "") -> None:
        """Evict one admitted sequence and free its blocks — host-side
        scheduling only (the block tables are traced DATA; no compiled
        program ever notices). The victim re-enters the FRONT of its
        priority lane and, on re-admission, recomputes from
        ``prompt + emitted tokens``: greedy decode is a deterministic
        function of the token prefix and the pinned params, and the
        paged kernels' attention operand is bit-identical across the
        prefill/decode layouts, so the resumed generation's remaining
        tokens equal the un-preempted run's exactly (oracle-tested).
        Blocks decref TAIL-first (the ``_release_seq`` LRU
        convention), so under the prefix cache the victim's registered
        blocks park in the cached tier and splice straight back at
        resume — recompute is then nearly free."""
        t0 = time.monotonic()
        slot = req.slot
        freed = len(req.blocks)
        if req is self._pf:
            self._pf = None
        else:
            self._active[slot] = False
            self._slot_req[slot] = None
        if req.blocks:
            self._pool.decref(reversed(req.blocks))
            req.blocks = []
        self._block_tables[slot][:] = SCRATCH_BLOCK
        self._free_q.append(slot)
        req.slot = -1
        if req.preempts == 0:
            self.preempted += 1
        req.preempts += 1
        self.preemptions += 1
        self.preempt_counter.inc()
        # resume state: the working prompt becomes the ORIGINAL prompt
        # plus everything emitted so far; prefill-progress/prefix/spec
        # state resets (the drafter rebuilds at re-admission from the
        # same token sequence, so its proposals are identical)
        if req.out:
            req.prompt = np.concatenate(
                [req.prompt0, np.asarray(req.out, np.int32)])
            req.resumed = True
        req.hashes = None
        req.n_hit = 0
        req.full_hit = False
        req.saved = 0
        req.pf_off = req.pf_chunks = req.pf_reg = 0
        req.ttft_pending = False
        req.drafter = None
        if req.usage is not None:
            # a fresh queue-wait interval opens: the victim re-enters
            # its lane and the next admission closes the clock again
            req.usage.t_wait0 = time.monotonic()
        if trace.enabled() and req.ctx is not None:
            trace.record_span(
                "decode.preempt", req.ctx, t0, time.monotonic(),
                victim=req.rid, slot=slot, blocks_freed=freed,
                preempts=req.preempts, priority=req.priority, why=why)
        with self._cv:
            self._q.appendleft(req)

    def _ensure_growth(self, n_valid) -> bool:
        """Optimistic admission's decode-time half: before the fused
        step (or verify window) dispatches, every slot it will run has
        a reservation that covers the positions THIS step writes —
        ``pos .. pos + window - 1``. Growth is allocator work plus a
        block-table row append (traced data, never a shape). On pool
        exhaustion it preempts via :meth:`_pick_victim`; when no
        admissible victim exists (everyone shielded by the floor/
        budget/class rules, or a chaos squeeze holds the pool) the
        grower itself yields and recomputes later — in normal
        operation that is never the oldest, whose growth the floor
        guarantees. Growers run highest-class-oldest-first, so the
        important/old sequences claim blocks before the preemptible
        ones. Returns False, with nobody preempted, when it would have
        to preempt while programs are in flight: the caller retires
        them and calls again (what was grown stays grown)."""
        order = [s for s in range(self.config.slots)
                 if self._slot_req[s] is not None and self._left[s] > 0]
        order.sort(key=lambda s: (-self._slot_req[s].priority,
                                  self._slot_req[s].t_enq))
        for s in order:
            req = self._slot_req[s]
            if req is None:          # victimized by an earlier grower
                continue
            win = 1 if n_valid is None else max(1, int(n_valid[s]))
            need = self._pool.blocks_needed(int(self._pos[s]) + win)
            grow = need - len(req.blocks)
            if grow <= 0:
                continue
            while self._slot_req[s] is req:
                if self._pool.can_alloc(grow):
                    try:
                        blocks = self._pool.alloc(grow)
                    except RuntimeError:
                        # a concurrent claimant (chaos pool squeeze)
                        # raced the check: fall through to preemption
                        continue
                    base = len(req.blocks)
                    req.blocks.extend(blocks)
                    self._block_tables[s][base: base + grow] = blocks
                    break
                if self._flight:
                    return False
                victim = self._pick_victim(req)
                if victim is None:
                    self._preempt(req, why="yield: no admissible victim")
                    break
                self._preempt(victim, why=f"growth for rid {req.rid}")
        return True

    def _dispatch_step(self) -> Optional[_StepInFlight]:
        """Grow every reservation the step needs, propose drafts and
        dispatch the fused step (or the verify window) over the slots
        live NOW whose ``max_new`` has not counted out; no wait. None
        when growth preempted every such slot, or when it would have to
        preempt with programs in flight (they are still in flight then:
        the caller drains and calls again). The program gets COPIES of
        the host arrays and the booking gets the slots' requests as
        they stand here: admission and the bookings of earlier steps
        run while this one is in flight and write block tables,
        ``_tok``, ``_pos`` and ``_active``, and a dispatched program may
        read a host array late. The input tokens never come to the
        host first: they are the last step's output on the device, but
        for the slots that went live since. With ``spec_k == 0`` a slot
        moves one position a step whatever its token, so positions and
        the by-count budget advance HERE; a slot may so run one step
        past an eos the host has not read yet: that step's write falls
        in the request's own reservation (grown above), dead weight a
        later admission overwrites behind it in device order, and its
        token is dropped at the booking."""
        t0 = time.monotonic()
        spec_toks = n_valid = None
        if self._spec:
            spec_toks, n_valid = self._propose_drafts()
        # grow every reservation to cover this step's writes, preempting
        # under pool pressure; a yield can deactivate slots (incl. every
        # drafted one), so the slots are read after it
        if self._preempt_on and not self._ensure_growth(
                n_valid if spec_toks is not None else None):
            return None
        running = self._running()
        if not running.any():
            return None
        # blocks this step's attention reads: every running slot's
        # positions <= pos (host state the loop already holds)
        self._it_live_blocks = int(np.sum(
            self._pos[running] // self._block_size + 1))
        self._live_blocks_sum += self._it_live_blocks
        tables, pos = self._block_tables.copy(), self._pos.copy()
        if spec_toks is not None:
            # fused verify: ONE forward scores every window position;
            # acceptance is decided at the booking on the host from the
            # argmax chain (traced data in, plain ints out — never a
            # shape)
            self.spec_steps += 1
            *pools, nxt = self._verify_fn(
                self._pinned, *self._pools, tables, spec_toks, pos,
                running, n_valid)
        else:
            tok = self._dev_tok
            if self._fresh.any():
                tok = self._merge_fn(
                    tok, np.where(self._fresh, self._tok, np.int32(-1)))
                self._fresh[:] = False
            *pools, nxt, _ = self._step_fn(
                self._pinned, *self._pools, tables, tok, pos, running)
            self._dev_tok = nxt
        self._pools = tuple(pools)
        if not self._spec:
            self._pos[running] += 1
            self._left[running] -= 1
        self.steps += 1
        if any(isinstance(f, _StepInFlight) for f in self._flight):
            self.steps_ahead += 1
            self._it_ahead = 1
        step = _StepInFlight(
            nxt, [r if on else None
                  for r, on in zip(self._slot_req, running)],
            spec_toks, n_valid, t0)
        self._flight.append(step)
        return step

    def _retire_step(self, step: _StepInFlight) -> None:
        """Fetch an in-flight step's tokens and book them. The flight
        recorder's ``step_ms`` is the host's milliseconds on steps in a
        pass: the launch of the one it dispatched (``engine.step``), the
        wait and the booking of the one it retired (these two
        phases)."""
        with self._phase("engine.step.sync"):
            nxt = np.array(step.nxt)   # [S] or [S, K+1]; the host sync point
        with self._phase("engine.step.book"):
            self._book_step(step, nxt)

    def _book_step(self, step: _StepInFlight, nxt) -> None:
        """What a step's synced tokens mean on the host: each slot that
        ran IN IT has its emissions booked, histograms and ledger
        charged, finished requests released and resolved. A slot that
        went live since (a full hit admitted under the step) was not
        computed and is not booked; a request that left its slot since
        (resolved at the step before: this one ran it one past its eos)
        has this step's token dropped, never appended, never counted."""
        spec_toks, n_valid, t_it0 = step.spec_toks, step.n_valid, step.t0
        # ONE branch decides all per-iteration trace work: when tracing
        # is off this loop allocates nothing trace-related (guarded by
        # test_observability's overhead test)
        tracing = trace.enabled()
        ledger_on = self.ledger is not None
        now = time.monotonic()
        self.steps_counter.inc()
        if ledger_on:
            # device time attributed by active-lane share: the step's
            # wall (dispatch to sync, growth/drafting included; from
            # the booking before where the step was dispatched ahead of
            # it, so that no interval is charged twice) divides evenly
            # over the sequences it served — charged BEFORE the
            # per-slot loop so a sequence completing this very step
            # still pays for it
            self.ledger.charge_step(
                [r for r in step.reqs if r is not None],
                (now - max(t_it0, self._t_booked)) * 1e3)
        self._t_booked = now
        n_active = 0
        for s, req in enumerate(step.reqs):
            if req is None or self._slot_req[s] is not req:
                continue
            n_active += 1
            if spec_toks is None:
                emitted = [int(nxt[s])]
                accepted = 0
            else:
                # greedy verification: drafts are accepted while they
                # match the model's own argmax chain; entry ``accepted``
                # of the window's outputs is the correction token, so
                # at least the plain step's one token always emits and
                # every emission equals sequential greedy decode
                nv = int(n_valid[s])
                accepted = 0
                while (accepted + 1 < nv
                       and int(spec_toks[s, accepted + 1])
                       == int(nxt[s, accepted])):
                    accepted += 1
                emitted = [int(nxt[s, j]) for j in range(accepted + 1)]
                eos = self.config.eos_id
                if eos is not None and eos in emitted:
                    # an in-window eos truncates the window HERE, so
                    # the accounting credits only REALIZED drafts —
                    # matches accepted past the eos were never emitted,
                    # and accepted_per_step is documented (and gated)
                    # as extra tokens actually bought per dispatch
                    emitted = emitted[: emitted.index(eos) + 1]
                    accepted = len(emitted) - 1
                proposed = nv - 1
                self.spec_proposed += proposed
                self.spec_accepted += accepted
                self._it_spec_proposed += proposed
                self._it_spec_accepted += accepted
                if proposed:
                    self.spec_prop_counter.inc(proposed)
                if accepted:
                    self.spec_acc_counter.inc(accepted)
            if self._spec:
                # acceptance decides how far the slot moved, and the
                # next window starts from the host's token (consumed
                # inputs advance the position; rejected window positions
                # are simply never consumed — the next window starts at
                # the first unverified position and rewrites them before
                # any mask reaches them)
                self._pos[s] += len(emitted)
                self._tok[s] = emitted[-1]
                # a pass in which no slot drafts runs the plain step,
                # whose tokens are the device's but for the fresh slots
                self._fresh[s] = True
            # ITL is per EMITTED token: the step interval divides across
            # this iteration's emissions (spec_k=0 emits one token, so
            # the sample is exactly today's now - t_last)
            share = (now - req.t_last) * 1e3 / len(emitted)
            done = False
            for tok in emitted:
                req.out.append(tok)
                self.tokens += 1
                self.decode_tok_counter.inc()
                self._it_decode += 1
                if req.usage is not None:
                    req.usage.decode_tokens += 1
                if req.ttft_pending:
                    # fully-cached admission: THIS is the request's
                    # first token — it belongs in TTFT, not ITL
                    req.ttft_pending = False
                    self.ttft_hist.record((now - req.t_enq) * 1e3)
                else:
                    self.itl_hist.record(share)
                if self._finished(req, tok):
                    # eos inside the window truncates it: emissions past
                    # eos are dropped exactly as sequential decode would
                    # never have produced them
                    done = True
                    break
            req.t_last = now
            if req.drafter is not None and not done:
                req.drafter.extend(emitted)
            if tracing and req.ctx is not None:
                # one fused step serves every live slot; each request
                # gets the iteration as ITS child span (same interval),
                # so a slow request's trace shows every co-batched
                # iteration it sat through and on which slot. Spec
                # engines annotate how many drafts the window kept
                # (spec_k=0 spans stay flat — today's attrs exactly)
                extra = {"accepted": accepted} if self._spec else {}
                trace.record_span("decode.iter", req.ctx, t_it0, now,
                                  slot=s, token_index=len(req.out),
                                  **extra)
            if done:
                self._active[s] = False
                self._slot_req[s] = None
                self._release_seq(req)
                self._resolve(req)
        self._occ_sum += n_active / self.config.slots
        self._occ_n += 1
        self.occ_gauge.set(int(self._active.sum()) / self.config.slots)
        t_first = self.t_first        # local read: reset_stats() may race
        if t_first is not None and now > t_first:
            self.tps_gauge.set(self.tokens / (now - t_first))

    def _finished(self, req: _Request, tok: int) -> bool:
        eos = self.config.eos_id
        return (eos is not None and tok == eos) or len(req.out) >= req.max_new

    def _finalize_usage(self, req: _Request, outcome: str,
                        now: Optional[float] = None) -> None:
        """Fold one finished request's resource vector into its
        tenant's aggregates, exactly once (the vector detaches here —
        overlapping failure paths cannot double-fold), and record the
        post-hoc ``acct.request`` span carrying tenant + cost + the
        vector: the source of trace_summary's tenant/cost columns."""
        usage = req.usage
        if usage is None:
            return
        req.usage = None
        if now is None:
            now = time.monotonic()
        usage.preemptions = req.preempts
        lat_ms = ((now - req.t_enq) * 1e3 if outcome == "completed"
                  else None)
        cost = self.ledger.finalize(usage, outcome, lat_ms)
        if trace.enabled() and req.ctx is not None:
            trace.record_span(
                "acct.request", req.ctx, req.t_enq, now,
                tenant=usage.tenant, cost=round(cost, 6),
                outcome=outcome,
                prefill_tokens=usage.prefill_tokens,
                prefill_tokens_saved=usage.prefill_tokens_saved,
                decode_tokens=usage.decode_tokens,
                kv_block_s=round(usage.kv_block_s, 6),
                device_step_ms=round(usage.device_step_ms, 3),
                queue_wait_ms=round(usage.queue_wait_ms, 3),
                xfer_bytes=usage.xfer_bytes,
                recompute_tokens=usage.recompute_tokens,
                preemptions=usage.preemptions)

    def _resolve(self, req: _Request) -> None:
        self._finalize_usage(req, "completed")
        self.completed += 1
        self._it_completed.append(req.rid)
        if req.future.set_running_or_notify_cancel():
            # staleness measured at REPLY time (the PR 1 contract): the
            # pin can't move while this request is in flight, so _snap IS
            # the request's snapshot here
            req.future.set_result({
                "result": np.asarray(req.out, np.int32),
                "snapshot_version": req.version,
                "staleness_s": self._manager.staleness_s(self._snap),
            })

    def _fail_all(self, exc: Exception,
                  in_flight: Optional[List[_Request]] = None) -> None:
        with self._cv:
            # the loop thread is dying: flag stop so later submits
            # fast-fail instead of enqueueing futures nobody will drain
            self._stop.set()
            pending = self._q.drain()
            # release splice waiters: the loop will never apply these
            while self._loop_work:
                _, done, info = self._loop_work.popleft()
                info["skipped"] = "engine failed"
                done.set()
        # every pass in flight dies with this one: the live slots'
        # requests, the mid-prefill admission and those whose last
        # chunk was in flight
        live = self._admitted_requests()
        self._pf = None
        self._flight.clear()
        # the dying requests' reservations go back too — including
        # arrivals popped but not yet slotted. The engine is stopped,
        # but stats()/gauges must not report phantom live blocks (the
        # pool's leak invariant must hold). decref, not free:
        # prefix-shared blocks carry one holder per dying request, and
        # each drops exactly its own
        for req in live + (in_flight or []):
            if req.blocks:
                self._pool.decref(req.blocks)
                req.blocks = []
        if self._squeezed:       # staged chaos squeeze dies too
            self._pool.decref(self._squeezed)
            self._squeezed = []
        self._block_tables[:] = SCRATCH_BLOCK
        self._active[:] = False
        self._slot_req = [None] * self.config.slots
        self._free_q = collections.deque(range(self.config.slots))
        seen = set()
        for req in pending + live + (in_flight or []):
            if id(req) in seen or req.future.done():
                continue            # e.g. an arrival already resolved
            seen.add(id(req))
            # whatever this request consumed before the engine died is
            # still attributed (outcome "failed") — the conservation
            # identity survives an engine failure by construction
            self._finalize_usage(req, "failed")
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(exc)

    # -- chaos hooks --------------------------------------------------------
    def squeeze_pool(self, frac: float) -> int:
        """Chaos/test hook (the ``-chaos`` ``pool_squeeze=`` fault):
        take up to ``frac`` of the paged pool's capacity hostage —
        blocks allocate and are simply HELD, so live traffic sees a
        shrunken pool and the preemption machinery gets exercised
        under real pressure. Returns the blocks actually held (capped
        to what is reclaimable right now). The watchdog's
        leaked-reservation heuristic excludes squeezed blocks; release
        with :meth:`unsqueeze_pool` (``stop()``/the failure path
        release automatically)."""
        want = int(self._pool.capacity * float(frac))
        take = min(want, self._pool.n_free + self._pool.n_cached)
        if take <= 0:
            return 0
        try:
            self._squeezed.extend(self._pool.alloc(take))
        except RuntimeError:             # raced a concurrent admission
            return 0
        return take

    def unsqueeze_pool(self) -> int:
        """Release a staged :meth:`squeeze_pool`; returns blocks freed."""
        n = len(self._squeezed)
        if n:
            self._pool.decref(self._squeezed)
            self._squeezed = []
        return n

    # -- introspection ------------------------------------------------------
    def step_cache_size(self) -> int:
        """Compiled-trace count of the fused step (1 after warmup: the
        whole point of fixed slots + active-lane masking)."""
        return _jit_cache_size(self._step_fn)

    def prefill_cache_size(self) -> int:
        """Compiled-trace count of the admission path: the single
        fixed-shape chunk program."""
        return _jit_cache_size(self._chunk_fn)

    def verify_cache_size(self) -> int:
        """Compiled-trace count of the speculative verify step (1 after
        warmup on a spec engine: the fixed-K window is the whole
        signature; 0 when ``spec_k=0`` — the program doesn't exist)."""
        if self._verify_fn is None:
            return 0
        return _jit_cache_size(self._verify_fn)

    def seqpar_cache_size(self) -> int:
        """Compiled-trace count of the sequence-parallel chunk program
        (1 after warmup on a ``-prefill_sp`` engine — the budget * tp
        token shape is the whole signature; 0 when off — the program
        doesn't exist)."""
        if self._chunk_sp_fn is None:
            return 0
        return _jit_cache_size(self._chunk_sp_fn)

    def transfer_cache_size(self) -> int:
        """Compiled-trace count of the KV transfer plane (2 after
        warmup on a prefix-cache engine — one fetch, one splice; the
        block id is traced, so pool position never recompiles; 0 when
        the plane doesn't exist)."""
        if self._fetch_fn is None:
            return 0
        return (_jit_cache_size(self._fetch_fn)
                + _jit_cache_size(self._splice_fn))

    def warmup(self) -> None:
        """Compile the ONE chunk program, the copy-on-write and transfer
        programs and the fused step before taking traffic —
        deadline-sensitive deployments call this BEFORE submitting so no
        live request ever pays a compile. Pins the snapshot through the
        serving path itself, so the warmup params copy (and placement,
        hence the compiled traces) IS the one the first admission
        serves.

        The engine warms up against its LIVE pools, on the loop thread
        (the only thread that may hand the donated pools to a program):
        every table row names the scratch block and no lane is active,
        so all writes park in scratch, which no live mask reaches. No
        second copy of the pools ever exists (a model's weights and
        pools may fill the chip).
        """
        done = threading.Event()
        info: Dict = {}

        def warm() -> dict:
            self._pools = self._warm(self._pools)
            return {}

        with self._cv:
            if self._stop.is_set():
                return
            self._loop_work.append((warm, done, info))
            self._cv.notify()
        while not done.wait(0.5):
            if not self._thread.is_alive():
                raise RuntimeError(
                    f"decode engine {self.name!r}: loop thread died "
                    f"during warmup")
        if "error" in info:
            raise info["error"]

    def _warm(self, pools: tuple) -> tuple:
        """Dispatch every serving program once with the serving avals
        (numpy host state, the pools' committed placement), threading
        ``pools`` through the donations; returns the pools."""
        self._maybe_refresh()
        params = self._pinned
        S = self.config.slots
        # all-scratch block tables: placement is data, so these ARE the
        # serving traces for any block assignment
        tables = np.full((S, self._blocks_per_seq), SCRATCH_BLOCK, np.int32)
        zeros = np.zeros(S, np.int32)
        *pools, _ = self._chunk_fn(
            params, *pools, tables, np.int32(0),
            np.ones(self._budget, np.int32), np.int32(0), np.int32(1))
        if self._chunk_sp_fn is not None:
            # the seqpar chunk program compiles here too (its
            # budget * tp token shape is the only static), so no
            # long prompt ever pays the trace — and the partitioner
            # runs now, not mid-traffic
            *pools, _ = self._chunk_sp_fn(
                params, *pools, tables, np.int32(0),
                np.ones(self._sp_chunk, np.int32), np.int32(0),
                np.int32(1))
        if self._cow_fn is not None:
            # the CoW block copy is part of the serving path (a
            # full-prompt cache hit dispatches it at admission)
            pools = self._cow_fn(*pools, np.int32(0), np.int32(0))
        if self._fetch_fn is not None:
            # the KV transfer plane's two programs likewise. The host
            # round-trip mirrors serving: fetch materializes before
            # splice donates the pools away
            pieces = [np.asarray(x)
                      for x in self._fetch_fn(*pools, np.int32(0))]
            pools = self._splice_fn(*pools, np.int32(0), *pieces)
        if self._verify_fn is not None:
            # the [S, K + 1] window shape is the whole signature
            *pools, _ = self._verify_fn(
                params, *pools, tables,
                np.zeros((S, self._spec + 1), np.int32), zeros,
                np.zeros(S, bool), np.ones(S, np.int32))
        # the step's tokens are the merge's output or the last step's
        tok = self._merge_fn(self._dev_tok, zeros)
        *pools, nxt, _ = self._step_fn(params, *pools, tables, tok,
                                       zeros, np.zeros(S, bool))
        jax.block_until_ready(nxt)
        return tuple(pools)

    def _model_counters(self):
        """The newest value of the model's own counter pool (never
        donated, so any thread may read it), or None."""
        i = self._progs.counter_pool
        return None if i is None else np.asarray(self._pools[i])

    def reset_stats(self) -> None:
        """Zero counters/histograms (benches: measure past jit warmup)."""
        self._counters_base = self._model_counters()
        self.ttft_hist.reset()
        self.itl_hist.reset()
        self.qwait_hist.reset()
        self._clock.reset()
        self._slowest_ns = 0
        self._slowest = None
        self.completed = 0
        self.shed = 0
        self.tokens = 0
        self.peak_live = 0
        self.prefill_tokens = 0
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefill_tokens_saved = 0
        self.cow_copies = 0
        self.xfer_blocks = 0
        self.xfer_bytes = 0
        self.xfer_dedup = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        self.seqpar_chunks = 0
        self.prefill_chunks = 0
        self.chunks_behind_step = 0
        self.chunk_reach = 0
        self.steps = 0
        self.steps_ahead = 0
        self.drains = {}
        self.preemptions = 0
        self.preempted = 0
        self.deadline_drops = 0
        self._argmax_match = -1.0
        self._evictions_base = self._pool.evictions
        if self.ledger is not None:
            self.ledger.reset()
        self.t_first = None
        self._occ_sum = 0.0
        self._occ_n = 0
        self._live_blocks_sum = 0

    def record_argmax_match(self, rate: float) -> None:
        """Attach an externally measured argmax-match rate (quant output
        vs an fp32 oracle on the same prompts) to this engine's stats
        surface — the quant quality headline the bench archives. The
        harness computes it because only the harness holds both
        engines' outputs."""
        self._argmax_match = float(rate)

    def stats(self) -> dict:
        t_first = self.t_first
        elapsed = (time.monotonic() - t_first) if t_first else 0.0
        ttft = self.ttft_hist.percentiles((50, 99))
        itl = self.itl_hist.percentiles((50, 99))
        qwait = self.qwait_hist.percentiles((50, 99))
        slowest = self._slowest
        issued = self.completed + self.shed
        # KV pool occupancy: capacity is what bounds concurrency, so
        # the pool's free/live split (and the peak sequence count it
        # allowed) belongs next to slot occupancy
        lookups = self.prefix_hits + self.prefix_misses
        pool = {"kv_block_size": self._block_size,
                "kv_pool_blocks": self._pool.capacity,
                # mesh-aware capacity: the pools (scratch included)
                # shard over the head slice of D, so each device holds
                # 1/tp of the KV bytes — the number that decides
                # whether a model + pool fits the hardware
                # quant-aware: an int8 pool's per-block cost counts its
                # int8 K/V bytes PLUS the per-(layer, block) fp32
                # scales — the footprint must not flatter quantization
                "kv_bytes_per_device": (
                    (self._pool.capacity + 1)
                    * self._progs.bytes_per_block // self._tp),
                "kv_blocks_free": self._pool.n_free,
                "kv_blocks_live": self._pool.n_live,
                "kv_blocks_cached": self._pool.n_cached,
                "blocks_shared": self._pool.n_shared,
                "block_allocs": self._pool.allocs,
                "block_frees": self._pool.frees,
                "prefix_cache": int(self._prefix),
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefix_hit_rate": (self.prefix_hits / lookups
                                    if lookups else 0.0),
                "prefill_tokens_saved": self.prefill_tokens_saved,
                "prefix_evictions": self._pool.evictions
                - self._evictions_base,
                "cow_copies": self.cow_copies}
        if self._progs.bytes_per_slot:
            # what the model keeps per SEQUENCE beside the blocks (a
            # recurrent state): fixed bytes a slot, live or not
            pool["slot_state_bytes_per_device"] = (
                self.config.slots * self._progs.bytes_per_slot // self._tp)
        if self._kv_quant:
            # quant surface, present only on kv_quant=int8 engines (an
            # off-quant engine's stats dict stays byte-for-byte — the
            # metrics regression contract). quant_scale_blocks here IS
            # the real device count (one sync, stats are not the hot
            # loop); the per-iteration recorder uses the pool proxy
            try:
                nz = int((np.maximum.reduce(
                    [np.asarray(self._pools[i])
                     for i in self._progs.scale_pools]).max(axis=0)
                    > 0).sum())
            except RuntimeError:
                # donated-away buffer (stats raced a dispatch): the
                # count is a diagnostic, not an invariant — degrade
                nz = -1
            pool.update({
                "kv_quant": self._kv_quant_mode,
                "quant_scale_blocks": nz,
                "argmax_match_rate": self._argmax_match,
            })
        if self._param_quant == "int8":
            pool["decode_param_quant"] = self._param_quant
        if self.ledger is not None:
            # tenant-accounting surface, present only on -cost_ledger
            # engines (off-ledger stats stay byte-for-byte — the
            # metrics regression contract). accounting_drift is the
            # conservation residual |sum over tenants - engine mirror|
            # over the integer fields: exactly zero at quiescence, and
            # the bench's zero-baseline gate holds it there
            pool.update({
                **self.ledger.stats(),
                "accounting_drift": self.ledger.drift(
                    self.prefill_tokens, self.tokens, self.xfer_bytes),
            })
        if self._prefix:
            # KV transfer plane (disaggregated serving), prefix-cache
            # engines only — the plane's gate, so a prefix_cache=off
            # engine's stats surface stays byte-for-byte today's.
            # kv_bytes_moved is RAW K/V bytes that crossed this
            # engine's boundary (fetched out or spliced in); the dedup
            # hit rate is blocks-deduped over blocks-considered
            moved = self.xfer_blocks + self.xfer_dedup
            pool.update({
                "kv_bytes_moved": self.xfer_bytes,
                "xfer_blocks": self.xfer_blocks,
                "xfer_dedup_blocks": self.xfer_dedup,
                "xfer_dedup_hit_rate": (self.xfer_dedup / moved
                                        if moved else 0.0),
            })
        if self._sp:
            # sequence-parallel prefill surface, present only on
            # -prefill_sp engines (an off-sp engine's stats dict stays
            # byte-for-byte today's — the metrics regression contract).
            # seqpar_traces is the one-trace gate for the sp chunk
            # program, exactly like step_traces/prefill_traces
            pool.update({
                "prefill_sp": self._sp_backend,
                "prefill_sp_threshold": self._sp_threshold,
                "prefill_sp_chunk": self._sp_chunk,
                "seqpar_chunks": self.seqpar_chunks,
                "seqpar_traces": self.seqpar_cache_size(),
            })
        if self._spec:
            # speculative-decoding surface, present only on spec
            # engines (a spec_k=0 engine's stats dict stays byte-for-
            # byte today's — the metrics regression contract).
            # accepted_per_step is the amortization headline: mean
            # EXTRA tokens each verify dispatch bought; acceptance_rate
            # is the drafter-quality diagnostic (archived _info in the
            # bench — trace-dependent, so it never gates)
            pool.update({
                "spec_k": self._spec,
                "spec_steps": self.spec_steps,
                "spec_proposed": self.spec_proposed,
                "spec_accepted": self.spec_accepted,
                "acceptance_rate": (self.spec_accepted
                                    / self.spec_proposed
                                    if self.spec_proposed else 0.0),
                "accepted_per_step": (self.spec_accepted
                                      / self.spec_steps
                                      if self.spec_steps else 0.0),
                "verify_traces": self.verify_cache_size(),
            })
        health = self.health()
        counts = self._model_counters()
        if counts is not None:
            # the model's own counters (LongCat: routing picks and the
            # held experts' load), accumulated on the device by its
            # programs and read here, never a sync a step; a window's
            # delta since reset_stats()
            if self._counters_base is not None:
                counts = counts - self._counters_base
            pool.update(self._progs.counters(counts))
        return {
            **pool,
            "decode_tp": self._tp,
            "mesh_devices": (self._decode_mesh.size
                             if self._decode_mesh is not None else 1),
            # the zero-baseline hot-loop gate: any repartition/retrace
            # of the fused step past warmup shows up here (the PR 2
            # ~10x partitioner drag, now asserted gone)
            "decode_step_retraces": max(0, self.step_cache_size() - 1),
            "pin_copies": self.pin_copies,
            "iters_total": health["iters_total"],
            "last_iter_age_s": health["last_iter_age_s"],
            "live_seqs": health["live_seqs"],
            "watchdog_trips": (self.watchdog.trip_count
                               if self.watchdog is not None else 0),
            "flight_records": (self.recorder.total
                               if self.recorder is not None else 0),
            "peak_live_seqs": self.peak_live,
            # overload-graceful scheduling: preemption EVENTS, distinct
            # requests preempted at least once, and expired-deadline
            # queue drops (docs/SERVING.md "Overload and preemption")
            "preempt": int(self._preempt_on),
            "preemptions": self.preemptions,
            "preempted": self.preempted,
            "deadline_drops": self.deadline_drops,
            "completed": self.completed,
            "shed": self.shed,
            "shed_rate": self.shed / issued if issued else 0.0,
            "tokens": self.tokens,
            "tokens_per_s": self.tokens / elapsed if elapsed > 0 else 0.0,
            "ttft_p50_ms": ttft[50],
            "ttft_p99_ms": ttft[99],
            "itl_p50_ms": itl[50],
            "itl_p99_ms": itl[99],
            # TTFT = queue wait + admission-to-first-token, each readable
            "queue_wait_p50_ms": qwait[50],
            "queue_wait_p99_ms": qwait[99],
            # the loop's phases on the always-on host clock, since
            # reset_stats() (docs/OBSERVABILITY.md "Engine phases"): the
            # time under each phase, the loop gap between passes, the
            # longest single engine.wait (a stalled CLIENT shows there,
            # not in a pass) and the longest pass with its row
            "phase_ms": self._clock.totals(),
            "loop_gap_ms": self._clock.gap(),
            "wait_ms_max": self._clock.wait_max_ns / 1e6,
            "slowest_pass": None if slowest is None else {
                "it": slowest[0], "ts": slowest[1],
                "busy_ms": slowest[2] / 1e6,
                "gap_before_ms": slowest[3] / 1e6,
                "live": slowest[4], "queue": slowest[5],
                "phases": self._clock.row_ms(slowest[6])},
            "slot_occupancy": (self._occ_sum / self._occ_n
                               if self._occ_n else 0.0),
            "kv_live_block_share": (
                self._live_blocks_sum
                / (self._occ_n * self.config.slots * self._blocks_per_seq)
                if self._occ_n else 0.0),
            "active_slots": int(self._active.sum()),
            "queue_depth": self.queue_depth(),
            "snapshot_publishes": self._manager.publishes,
            "step_traces": self.step_cache_size(),
            "prefill_traces": self.prefill_cache_size(),
            "prefill_token_budget": self._budget,
            "prefill_tokens": self.prefill_tokens,
            "prefill_chunks": self.prefill_chunks,
            "chunk_context_share": (
                self.chunk_reach / (self.prefill_chunks
                                    * self._blocks_per_seq * self._block_size)
                if self.prefill_chunks else 0.0),
            "chunks_behind_step": self.chunks_behind_step,
            "steps": self.steps,
            "steps_ahead": self.steps_ahead,
            "drains": dict(self.drains),
        }

    # -- lifecycle ----------------------------------------------------------
    def stop(self) -> None:
        """Drain queued + in-flight generations, then retire the loop
        (and its watchdog — a watchdog outliving its engine would keep
        polling a corpse)."""
        with self._cv:
            self._stop.set()
            self._cv.notify_all()
        self._thread.join(timeout=60)
        # a staged chaos squeeze must not outlive the engine (the
        # pool's books would report phantom live blocks forever)
        self.unsqueeze_pool()
        if self.watchdog is not None:
            self.watchdog.stop()
