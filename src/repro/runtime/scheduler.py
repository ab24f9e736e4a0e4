"""Continuous-batching scheduler on the paged KV substrate.

The seed served one robot at a time; PR 1 added continuous batching over a
*fixed pool of slots*, each backed by a dense per-slot KV slab sized to the
longest request — so slot count, not memory, bounded resident sequences.
This scheduler drops the slot array: sequences are backed by page tables
over one shared KV page pool (``Model``'s paged decode mode), and

  * **admission** is bounded only by free pages — pending requests are
    prefillled in one batched jitted call and their prompt KV is scattered
    straight into the pool pages they were allocated (``Model.
    merge_prefill_into_paged``);
  * **batch rows** carry only O(1) per-sequence state (last logits, page
    table row, recurrent block state); when more sequences are resident
    than rows, the row arrays double — at most log2 jitted decode variants;
  * **decode rounds** advance every active row by ``decode_block`` greedy
    action tokens through one fused ``Model.decode_chunk`` (paged mode —
    attention reads/writes go through ``ops.paged_decode_attention``);
  * **page accounting** is a single ``PageAllocator`` shared by cloud-only
    sequences *and* (when a ``PartitionExecutor`` is attached) the cloud
    suffixes of partitioned robots, so both kinds of robot share the same
    decode rounds and the same admission currency: free pages.

**Scan windows — the device-resident steady state.**  ``scan_rounds=R``
lifts the per-round host round-trip out of the hot loop: one ``step()``
call per window dispatches a single jitted ``lax.scan`` over R decode
rounds (the logits rows and the paged pools are *donated*, so XLA updates
the KV pool in place), the next R-1 calls return immediately, and the
window-closing call performs the window's only host sync, harvesting every
finished chunk at once.  Admission, completion, and page release happen
only at these boundaries; a ``cancel`` landing mid-window marks the
sequence dead and the boundary frees its pages — never while a donated
in-flight buffer might still write them.  A released row's capacity is
zeroed on the device by one program (``zero_caps``) for every row released
since the last boundary, at the start of the next admission, before any
program can read it.  ``scan_rounds=1`` degenerates to the classic
one-round-per-call loop (dispatch + harvest in the same call).

A split lane runs (argmax → edge prefix → merged suffix) for a whole
window inside one jitted scan — ascending-cut lanes join a progressively
concatenated row batch at their cut layer, so shared tail layers run once
over the combined rows and every lane's suffix KV lives in one
scheduler-owned pool per model layer (pages are globally unique, so
cross-lane batching needs no per-lane pool copies).

Every ``ChunkResult`` carries a pool-utilization snapshot (pages in use /
free / high-water) so serving telemetry sees KV pressure directly.

**Observability.**  Pass ``obs=Observability()`` to record the full
request lifecycle: submission, queue wait, admission, per-window decode
spans and completion/cancel are stamped with the monotonic ``obs.clock``
— but ONLY at the host-owned boundaries above (submit, admit, window
close), so instrumentation adds no host↔device syncs and the decoded
tokens are byte-identical to an uninstrumented run.  Each stamp feeds
the metrics registry (``serve.chunk_latency_ms``, ``serve.queue_wait_ms``,
``sched.*`` counters, ``pool.*`` gauges) and, when tracing, spans on one
track per robot (chunk ⊃ queue ⊃ decode) and one per lane (windows).
Every completion harvested at a boundary shares that boundary's single
clock read, so request spans align exactly with their window's close.
Each window boundary runs as four host spans on the profiler's clock
(``Observability.span``): ``sched.admit`` (``_try_admit``),
``sched.dispatch`` (the decode-window and fused split calls),
``sched.sync`` (the host waiting on the device for the window's tokens)
and ``sched.harvest`` (the rest of the close).  ``sched.row_tokens``
counts every decoded row-token by what its row held (``ROW_STATES``),
from host integers tallied at dispatch; ``sched.admitted_requests`` counts
the requests the admission program (``_admit_for``) takes in, padded rows
left out; ``sched.release_flushes`` counts the ``zero_caps`` programs and
``sched.released_rows`` the rows they zeroed.
"""

from __future__ import annotations

import contextlib
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.data.pipeline import EpisodeTokenizer
from repro.launch.sharding import (
    no_sharding,
    shard as logical_shard,
    sharding_rules,
)
from repro.models.model import Model
from repro.obs.clock import clock
from repro.obs.span import NULL_SPAN
from repro.runtime.kv_cache import PageAllocator, PagedSpec, donating_jit

DEFAULT_PAGE_SIZE = 16
# what a decode row's token held, for ``sched.row_tokens{state=...}``
ROW_STATES = ("live", "past", "idle", "cancelled")


def zero_caps(cap, mask):
    """Capacity 0 on every row ``mask`` holds, the others unchanged."""

    return jnp.where(mask, 0, cap)


# ``cap`` is donated: the update is in place and keeps the array's layout
_zero_caps = donating_jit(zero_caps, donate_argnums=(0,))


def decode_window(model, n_steps: int, rounds: int, token_floor: int):
    """The scan window ``window(params, logits_rows, pcache) -> (tokens
    [B, rounds * n_steps], logits_rows, pcache)``: ``rounds`` chained
    ``model.decode_chunk`` calls of ``n_steps`` tokens each in one
    ``lax.scan``."""

    def window(params, logits_rows, pcache):
        def body(carry, _):
            lg, pc = carry
            toks, lg, pc = model.decode_chunk(
                params, lg[:, None], pc, n_steps, token_floor
            )
            return (lg[:, -1], pc), toks

        (lg, pc), toks = jax.lax.scan(
            body, (logits_rows, pcache), None, length=rounds
        )
        toks = jnp.swapaxes(toks, 0, 1).reshape(
            logits_rows.shape[0], rounds * n_steps
        )
        return toks, lg, pc

    return window


def _bucket(n: int) -> int:
    """Smallest power of two >= n (jit-variant quantization)."""

    b = 1
    while b < n:
        b *= 2
    return b


def _row_tokens(groups, n_steps: int) -> Dict[str, int]:
    """The tokens one window decodes, ``rows x n_steps`` per decode
    program, split by what each row holds at dispatch: ``live`` (tokens
    its chunk still owes), ``past`` (decoded after the chunk is complete)
    and ``idle`` (no sequence, or one whose prefill is still pending).
    ``groups`` pairs each program's dispatched sequences with its row
    count.  Host integers only: nothing here reads the device."""

    t = dict.fromkeys(ROW_STATES, 0)
    for seqs, rows in groups:
        for seq in seqs:
            take = min(seq.remaining, n_steps)
            t["live"] += take
            t["past"] += n_steps - take
        t["idle"] += (rows - len(seqs)) * n_steps
    return t


def _lane_order(key) -> Tuple[int, tuple]:
    """Total order over lane keys: plain int cuts sort with ``(cut,
    offload)`` expert-offload keys at the same boundary (plain first)."""

    return (key, ()) if isinstance(key, int) else (key[0], tuple(key[1]))


@dataclass
class ChunkRequest:
    robot_id: int
    obs: np.ndarray          # [S_obs] observation token ids
    submitted_round: int
    order: int = 0           # global FIFO position across all lanes
    earliest_round: int = 0  # admission deferral (cancellation-aware)
    submit_ts: float = 0.0   # obs.clock at submission (0 when obs is off)


@dataclass(frozen=True)
class PoolStats:
    """KV page-pool utilization snapshot.

    In mesh-sharded mode the per-shard tuples report each data shard's
    occupancy/high-water alongside the global aggregate (all plain host
    counters — no device syncs); ``None`` on a single-shard pool.
    """

    pages_in_use: int
    pages_free: int
    high_water: int
    shard_in_use: Optional[Tuple[int, ...]] = None
    shard_high_water: Optional[Tuple[int, ...]] = None


@dataclass
class ChunkResult:
    robot_id: int
    tokens: np.ndarray       # [chunk_len * n_joints] greedy action tokens
    submitted_round: int
    admitted_round: int
    completed_round: int
    kind: str = "cloud"      # "cloud" (full stack) | "split" (cloud suffix)
    pool: Optional[PoolStats] = None
    cut: Optional[int] = None  # split kind: the lane's edge layer count
    expert_offload: Tuple[int, ...] = ()  # the lane's cloud-resident experts
    # request-lifecycle wall stamps (obs.clock seconds; 0 when obs is off).
    # ``completed_ts`` is the harvesting boundary's single clock read, so
    # results of one window share it exactly.
    submitted_ts: float = 0.0
    admitted_ts: float = 0.0
    completed_ts: float = 0.0


@dataclass
class _Sequence:
    """One page-table-backed in-flight sequence (replaces the old _Slot)."""

    robot_id: int
    row: int
    remaining: int
    pages: List[int]
    request: ChunkRequest
    admitted_round: int
    tokens: List[int] = field(default_factory=list)
    # cancelled while a scan window was in flight: the donated decode still
    # writes this row's pages, so they are freed at the boundary, not here
    dead: bool = False
    # disaggregated admission: prefill dispatched on the prefill device but
    # not yet merged into the live pool — the row decodes into the trash
    # page (cap 0) and is excluded from harvest until the merge boundary
    pending: bool = False
    admit_ts: float = 0.0    # obs.clock at batched-prefill admission


@dataclass
class _ScanWindow:
    """One dispatched multi-round decode whose results await harvest."""

    steps_left: int
    n_steps: int                         # total tokens decoded per row
    toks: Optional[jax.Array] = None     # cloud tokens [rows, n_steps]
    seqs: List[_Sequence] = field(default_factory=list)
    lane_toks: Dict[object, object] = field(default_factory=dict)  # by lane key
    lane_seqs: Dict[object, list] = field(default_factory=dict)
    t_open: float = 0.0                  # obs.clock at dispatch
    row_tokens: Optional[Dict[str, int]] = None  # obs on: tally at dispatch


class ContinuousBatchingScheduler:
    """Page-bounded continuous batcher over the model's paged decode mode."""

    def __init__(
        self,
        model: Model,
        params,
        tokenizer: EpisodeTokenizer,
        max_slots: int = 8,
        chunk_len: int = 8,
        n_joints: int = 7,
        decode_block: Optional[int] = None,
        adaptive_block: bool = False,
        max_block: Optional[int] = None,
        page_size: int = DEFAULT_PAGE_SIZE,
        num_pages: Optional[int] = None,
        scan_rounds: int = 1,
        obs=None,
        mesh=None,
        prefill_group=None,
    ):
        if model.cfg.encoder_decoder:
            raise NotImplementedError("continuous batching targets decoder-only VLAs")
        self.model = model
        self.tok = tokenizer
        # mesh-sharded mode: the page pools shard over the mesh ``data``
        # axis (global page ids, contiguous per-shard blocks), decode rows
        # and params lay out via the logical sharding rules, and every
        # jitted entry point (admission, scan windows, fused split decode)
        # traces under the mesh context so model-internal ``shard()`` calls
        # take effect — token outputs stay bit-identical to single-device
        # (all pool writes are unique-slot ``.at[].set``; no cross-row or
        # cross-page reductions change order under GSPMD)
        self.mesh = mesh
        self._ndata = int(mesh.shape["data"]) if mesh is not None else 1
        # prefill/decode disaggregation: long-prompt prefill runs on its
        # own device (group) and hands off via the paged cache one window
        # later, so prompt bursts stop serializing with in-flight decode
        self._prefill_device = None
        if prefill_group:
            self._prefill_device = prefill_group[0]
            self._prefill_params = jax.device_put(params, self._prefill_device)
            self._prefill_fns = {}
            self._merge_fns = {}
            self._pending_admit: List[tuple] = []
        self.params = params if mesh is None else model.shard_params(params, mesh)
        # optional Observability handle; every producer site is guarded on
        # ``self.obs is not None`` so a None handle costs nothing.  Swappable
        # between runs (the serving bench attaches a fresh one per run).
        self.obs = obs
        # ``max_slots`` no longer caps residency — it sizes the initial row
        # arrays and the *default* page pool (kept so the default capacity
        # matches the old fixed-slot engine); pass ``num_pages`` to admit
        # more sequences than rows, which then double on demand.
        self.max_slots = max_slots
        self.chunk_len = chunk_len
        self.n_joints = n_joints
        self.total_tokens = chunk_len * n_joints
        self.decode_block = decode_block or n_joints
        self.adaptive_block = adaptive_block
        self.max_block = min(max_block or 4 * self.decode_block, self.total_tokens)
        self.prompt_len = 2 * n_joints
        # R decode rounds per host dispatch; 1 == per-round path
        self.scan_rounds = max(int(scan_rounds), 1)
        self.round = 0
        self.peak_active = 0
        self.mixed_rounds = 0        # rounds where both kinds decoded
        self.hetero_rounds = 0       # rounds where >= 2 distinct cuts decoded
        self.decode_rounds = 0       # rounds where any sequence decoded
        self.cancelled = 0           # sequences cancelled mid-flight
        self.deferred = 0            # submissions admitted late on purpose
        self.windows = 0             # dispatched scan windows
        self.window_closes = 0       # harvested (synced) scan windows
        self.last_round_kinds: Tuple[int, int] = (0, 0)  # (cloud, split)

        # KV page accounting: a request needs prompt + chunk tokens resident
        self.page_size = page_size
        self.pages_per_req = -(-(self.prompt_len + self.total_tokens) // page_size)
        pool = num_pages if num_pages is not None else self.pages_per_req * max_slots
        if self._ndata > 1:
            # pool+1 (incl. the trash page) must split evenly over the data
            # axis so the allocator's shard ownership (contiguous id blocks)
            # coincides exactly with the GSPMD layout of the pool arrays
            pool = self._ndata * (-(-(pool + 1) // self._ndata)) - 1
        self.allocator = PageAllocator(
            pool,
            num_shards=self._ndata,
            pages_per_shard=(pool + 1) // self._ndata if self._ndata > 1 else None,
        )
        self.paged_spec = PagedSpec(
            num_pages=pool,
            page_size=page_size,
            max_pages_per_seq=self.pages_per_req,
        )
        self.cap_tokens = self.pages_per_req * page_size

        # decode rows shard over the data axis, so keep the row count a
        # multiple of it (doubling in _grow_rows preserves the property)
        rows0 = max_slots
        if self._ndata > 1:
            rows0 = self._ndata * (-(-rows0 // self._ndata))
        self._queue: Deque[ChunkRequest] = deque()
        self._seqs: Dict[int, _Sequence] = {}    # row -> sequence
        self._free_rows: List[int] = list(range(rows0))
        # rows released since the last ``_flush_releases``: their device
        # capacity is still the old sequence's until the flush zeroes it
        self._release_mask = np.zeros(rows0, bool)
        # lane-key-keyed split-lane registry: plain layer cuts key by their
        # int cut (backwards compatible), expert-offload lanes by
        # ``(cut, offload)`` — so a plain lane and an offload lane may share
        # a cut boundary, all drawing pages from the one allocator above
        self._lanes: Dict[object, "_SplitLane"] = {}
        self._order = 0
        self._window: Optional[_ScanWindow] = None

        self._token_floor = tokenizer.action_base
        self._admit_fns = {}
        self._decode_fns = {}
        # split serving: shared per-MODEL-layer suffix page pools
        # and the fused per-(cuts, n_steps) decode fns over them
        self._suffix_pools: Optional[Dict[int, dict]] = None
        self._fleet_fns = {}

        # live batch state: logits rows + the paged cache (shared pools,
        # per-row page table / length / capacity — zeros mean inactive)
        self.rows = rows0
        logits_shape = jax.eval_shape(
            lambda p, b: self.model.prefill(p, b, extra=0)[0],
            params, {"tokens": jnp.zeros((1, self.prompt_len), jnp.int32)},
        )
        self._vdim = logits_shape.shape[-1]
        with self._ctx():
            self._logits = logical_shard(
                jnp.zeros((self.rows, self._vdim), logits_shape.dtype),
                "batch", None,
            )
            self._pcache = model.init_paged_cache(self.rows, self.paged_spec)

    def _span(self, name: str):
        """``obs.span(name)``, or the shared ``NULL_SPAN`` with obs off."""

        return NULL_SPAN if self.obs is None else self.obs.span(name)

    def _ctx(self):
        """Mesh trace/placement context (identity without a mesh)."""

        if self.mesh is None:
            return contextlib.nullcontext()
        return sharding_rules(self.mesh)

    # ------------------------------------------------------------------
    # request interface
    # ------------------------------------------------------------------

    def attach_partition(self, executor, rows: int = 2) -> None:
        """Serve partitioned robots' cloud suffixes in the same rounds.

        ``executor`` is a ``PartitionExecutor`` over the same model family;
        its suffix KV draws pages from this scheduler's allocator, so cloud-
        only sequences and split suffixes compete for (and are bounded by)
        the same pool.  Call once per DISTINCT cut to serve a heterogeneous
        fleet: each call registers a lane keyed by ``executor.cut_layer``,
        and robots on different cuts still share decode rounds and the one
        page allocator.

        The lane decodes inside one fused jitted scan per window — edge
        stage of token t+1 overlaps the suffix of token t, and compatible
        lanes batch their suffixes into one call.  Heterogeneous lanes must
        share parameter slices — derive siblings with ``executor.with_cut``.

        Expert-offload executors (``executor.expert_offload`` non-empty)
        register under their ``(cut, offload)`` lane key, so an offload
        lane coexists with a plain lane at the same cut; both join the
        same fused decode windows and page pool.
        """

        key = getattr(executor, "lane_key", executor.cut_layer)
        if key in self._lanes:
            raise ValueError(f"lane {key} already attached")
        if self.obs is not None and getattr(executor, "obs", None) is None:
            executor.obs = self.obs  # lane spans share the run's registry
        self._lanes[key] = _SplitLane(self, executor, rows)

    def _lane_for(self, cut) -> "_SplitLane":
        if not self._lanes:
            raise ValueError("no PartitionExecutor attached; call attach_partition")
        if cut is None:
            if len(self._lanes) > 1:
                raise ValueError(
                    "multiple lanes attached "
                    f"{sorted(self._lanes, key=_lane_order)}; pass cut="
                )
            return next(iter(self._lanes.values()))
        if cut not in self._lanes:
            raise ValueError(
                f"no lane for {cut}; attached: "
                f"{sorted(self._lanes, key=_lane_order)}"
            )
        return self._lanes[cut]

    def submit(
        self, robot_id: int, qd: np.ndarray, tau: np.ndarray,
        partitioned: bool = False, cut: Optional[int] = None,
        defer_rounds: int = 0,
    ) -> None:
        """Queue one chunk request for ``robot_id`` (qd/tau [1, N]).

        ``cut`` routes a partitioned robot to its assigned lane (optional
        while a single lane is attached).  ``defer_rounds`` delays admission
        (not submission order): the request keeps its FIFO slot but won't be
        prefilled for that many rounds — cancellation-aware admission uses
        one round, so a robot whose trigger preempts hot pays a queue
        removal, not a wasted batched prefill, when the next fire lands.
        """

        obs = np.concatenate(
            [self.tok.encode_state(qd), self.tok.encode_state(tau)], axis=1
        )[0]
        self._order += 1
        req = ChunkRequest(
            robot_id, obs, self.round, order=self._order,
            earliest_round=self.round + max(defer_rounds, 0) + 1
            if defer_rounds > 0 else 0,
        )
        if defer_rounds > 0:
            self.deferred += 1
        if self.obs is not None:
            req.submit_ts = clock()
            m = self.obs.metrics
            m.counter("sched.submissions").inc()
            if defer_rounds > 0:
                m.counter("sched.deferred").inc()
        if partitioned:
            self._lane_for(cut).queue.append(req)
        else:
            self._queue.append(req)

    def cancel(self, robot_id: int) -> bool:
        """Cancel ``robot_id``'s queued or in-flight chunk request.

        The redundancy-aware fleet loop calls this when a contact-phase
        trigger fires while a previous request is still decoding.  Queued
        requests are plain queue removals.  An in-flight sequence is freed
        immediately (its capacity zeroed at the next admission) — *unless*
        it belongs to the currently dispatched scan window: the donated
        in-flight scan still writes its pages and row, so the sequence is
        only MARKED dead here and the window boundary releases it (without
        emitting a result).  Freeing early would let the next admission
        reuse pages the scan is still writing.  Returns
        ``False`` when nothing was in flight (e.g. the preemption raced the
        chunk's final decode round) — nothing is double-freed.
        """

        for lane_queue in (self._queue, *(l.queue for l in self._lanes.values())):
            for req in lane_queue:
                if req.robot_id == robot_id:
                    lane_queue.remove(req)
                    self.cancelled += 1
                    self._obs_cancel(req.robot_id, req.submit_ts, queued=True)
                    return True
        w = self._window
        for seq in self._seqs.values():
            if seq.robot_id == robot_id and not seq.dead:
                dead = w is not None and any(s is seq for s in w.seqs)
                if dead:
                    seq.dead = True
                else:
                    self._release(seq)
                self.cancelled += 1
                self._obs_cancel(
                    seq.robot_id, seq.request.submit_ts, dead=dead
                )
                return True
        for lane in self._lanes.values():
            for seq in lane.seqs.values():
                if seq.robot_id == robot_id and not seq.dead:
                    dead = w is not None and any(
                        s is seq for s in w.lane_seqs.get(lane.key, ())
                    )
                    if dead:
                        seq.dead = True
                    else:
                        lane.release(seq)
                    self.cancelled += 1
                    self._obs_cancel(
                        seq.robot_id, seq.request.submit_ts,
                        dead=dead, cut=lane.cut,
                    )
                    return True
        return False

    def submit_batch(
        self, robot_ids, qd: np.ndarray, tau: np.ndarray,
        partitioned=None, cuts=None, defer_rounds=None,
    ) -> None:
        """Queue chunk requests for many robots in one call (qd/tau [n, N]).

        Row ``i`` of ``qd``/``tau`` belongs to ``robot_ids[i]``; FIFO order
        follows row order, so the queue state after this call is identical
        to ``n`` serial ``submit`` calls in the same order (same global
        ``order`` stamps, same lanes, same ``earliest_round``).  The state
        encode is one vectorized call over the whole batch instead of one
        per robot — ``EpisodeTokenizer.encode_state`` is elementwise, so
        each row matches the serial encode bit-for-bit.

        ``partitioned`` is an optional [n] bool mask, ``cuts`` an optional
        [n] sequence of lane keys: plain int cuts (entries < 0 or ``None``
        mean "no cut given" — legal only while a single lane is attached)
        or ``(cut, expert_offload)`` tuples routing to expert-offload
        lanes.  ``defer_rounds`` is an optional [n] int array.  Obs
        stamping uses one ``clock()`` read for the whole batch;
        serial submits read it per request (the stamps feed wait
        histograms, not the decode path, so results stay byte-identical).
        """

        robot_ids = np.asarray(robot_ids, np.int64)
        n = int(robot_ids.shape[0])
        if n == 0:
            return
        obs_toks = np.concatenate(
            [self.tok.encode_state(np.asarray(qd)), self.tok.encode_state(np.asarray(tau))],
            axis=1,
        )
        part = (
            np.zeros(n, bool) if partitioned is None
            else np.asarray(partitioned, bool)
        )
        defer = (
            np.zeros(n, np.int64) if defer_rounds is None
            else np.asarray(defer_rounds, np.int64)
        )
        # lane keys may mix ints and (cut, offload) tuples, so keep them as
        # a plain list instead of forcing an int64 array
        cut_seq = None if cuts is None else list(cuts)
        ts = 0.0
        if self.obs is not None:
            ts = clock()
            m = self.obs.metrics
            m.counter("sched.submissions").inc(n)
            n_deferred = int((defer > 0).sum())
            if n_deferred:
                m.counter("sched.deferred").inc(n_deferred)
        for i in range(n):
            self._order += 1
            d = int(defer[i])
            req = ChunkRequest(
                int(robot_ids[i]), obs_toks[i], self.round, order=self._order,
                earliest_round=self.round + d + 1 if d > 0 else 0,
                submit_ts=ts,
            )
            if d > 0:
                self.deferred += 1
            if part[i]:
                cut = None
                if cut_seq is not None:
                    c = cut_seq[i]
                    if isinstance(c, tuple):
                        cut = (int(c[0]), tuple(int(x) for x in c[1]))
                    elif c is not None and int(c) >= 0:
                        cut = int(c)
                self._lane_for(cut).queue.append(req)
            else:
                self._queue.append(req)

    def cancel_batch(self, robot_ids) -> np.ndarray:
        """Cancel many robots' queued/in-flight requests; returns a bool mask.

        Element ``i`` is ``cancel(robot_ids[i])`` — cancellation is inherently
        per-sequence bookkeeping (queue removal or dead-marking inside the
        dispatched window), so this is a batched entry point over the same
        state machine, in ascending-row order.
        """

        return np.fromiter(
            (self.cancel(int(r)) for r in np.asarray(robot_ids)),
            dtype=bool, count=len(np.asarray(robot_ids)),
        )

    @property
    def n_pending(self) -> int:
        return len(self._queue) + sum(len(l.queue) for l in self._lanes.values())

    @property
    def n_active(self) -> int:
        return len(self._seqs) + sum(len(l.seqs) for l in self._lanes.values())

    @property
    def active_cuts(self) -> List[int]:
        """Distinct cuts with in-flight suffixes this instant (ascending).

        Lane keys collapse to their cut layer here: a plain lane and an
        expert-offload lane at the same boundary count as one cut (they
        batch into the same suffix rows); ``active_lanes`` keeps them apart.
        """

        return sorted({l.cut for l in self._lanes.values() if l.seqs})

    @property
    def active_lanes(self) -> List[object]:
        """Lane keys with in-flight suffixes this instant (ascending)."""

        return sorted(
            (k for k, l in self._lanes.items() if l.seqs), key=_lane_order
        )

    def pool_stats(self) -> PoolStats:
        a = self.allocator
        sharded = a.num_shards > 1
        return PoolStats(
            pages_in_use=a.num_in_use,
            pages_free=a.num_free,
            high_water=a.high_water,
            shard_in_use=tuple(a.shard_in_use) if sharded else None,
            shard_high_water=tuple(a.shard_high_water) if sharded else None,
        )

    def reset(self) -> None:
        """Drop all queued/in-flight work; keep compiled fns and buffers."""

        self._queue.clear()
        self._seqs.clear()
        self._free_rows = list(range(self.rows))
        self._release_mask = np.zeros(self.rows, bool)  # every cap zeroed below
        # same allocator object: lifetime alloc/free counters survive the
        # reset while the high-water mark restarts, so per-episode
        # ``PoolStats.high_water`` stays meaningful on a reused scheduler
        self.allocator.reclaim_all()
        self._window = None
        if self._prefill_device is not None:
            self._pending_admit = []
        with self._ctx():
            # fresh zeros lose the mesh layout; re-apply the logical shards
            self._logits = logical_shard(
                jnp.zeros_like(self._logits), "batch", None
            )
            self._pcache["len"] = logical_shard(
                jnp.zeros((self.rows,), jnp.int32), "batch"
            )
            self._pcache["cap"] = logical_shard(
                jnp.zeros((self.rows,), jnp.int32), "batch"
            )
        for lane in self._lanes.values():
            lane.reset()
        self._suffix_pools = None
        self.round = 0
        self.peak_active = 0
        self.mixed_rounds = 0
        self.hetero_rounds = 0
        self.decode_rounds = 0
        self.cancelled = 0
        self.deferred = 0
        self.windows = 0
        self.window_closes = 0
        self.last_round_kinds = (0, 0)

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------

    def _block_for_depth(self, depth: int) -> int:
        """Per-round decode block, monotone non-decreasing in queue depth.

        Fixed-block mode (the default) always returns ``decode_block``.
        Adaptive mode doubles the block each time the pending backlog could
        refill a row-array's worth of sequences, capped at ``max_block``.
        """

        blk = self.decode_block
        if not self.adaptive_block:
            return blk
        while depth >= self.max_slots and blk * 2 <= self.max_block:
            blk *= 2
            depth -= self.max_slots
        return blk

    def _grow_rows(self) -> None:
        """Double the row arrays (page pools are shared and don't grow).

        With a mesh, the doubled row count stays a multiple of the data
        axis; the concatenated row-indexed arrays are re-laid-out under
        the logical rules (concat with unsharded pad zeros would otherwise
        leave XLA's choice of layout).  Values are unaffected either way.
        """

        old, new = self.rows, self.rows * 2
        pad = new - old
        self._logits = jnp.concatenate(
            [self._logits, jnp.zeros((pad, self._vdim), self._logits.dtype)], 0
        )
        unit = []
        for entry, spec in zip(self._pcache["unit"], self.model.unit):
            if spec[0] == "attn":
                unit.append(entry)  # shared pool: no batch dim
            else:
                unit.append(jax.tree.map(
                    lambda a: jnp.concatenate(
                        [a, jnp.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)], 1
                    ),
                    entry,
                ))
        self._pcache = {
            "unit": unit,
            "len": jnp.concatenate(
                [self._pcache["len"], jnp.zeros((pad,), jnp.int32)]
            ),
            "pt": jnp.concatenate(
                [self._pcache["pt"],
                 jnp.zeros((pad, self.pages_per_req), jnp.int32)]
            ),
            "cap": jnp.concatenate(
                [self._pcache["cap"], jnp.zeros((pad,), jnp.int32)]
            ),
        }
        if self.mesh is not None:
            with self._ctx():
                self._logits = logical_shard(self._logits, "batch", None)
                self._pcache["len"] = logical_shard(self._pcache["len"], "batch")
                self._pcache["pt"] = logical_shard(
                    self._pcache["pt"], "batch", None
                )
                self._pcache["cap"] = logical_shard(self._pcache["cap"], "batch")
        self._release_mask = np.concatenate(
            [self._release_mask, np.zeros(pad, bool)]
        )
        self._free_rows.extend(range(old, new))
        self.rows = new

    def _take_row(self) -> int:
        if not self._free_rows:
            self._grow_rows()
        return self._free_rows.pop(0)

    def _admit_for(self, n: int):
        """Jitted admission (batched prefill + paged merge) per (n, rows).

        The live pool/logits buffers are donated — the merge updates them
        in place; the caller rebinds both references to the outputs.
        """

        key = (n, self.rows)
        fn = self._admit_fns.get(key)
        if fn is None:
            def admit(params, pcache, logits_live, obs, pt_new, row_idx, lens, caps):
                new_logits, dcache = self.model.prefill(
                    params, {"tokens": obs}, extra=0
                )
                pcache = self.model.merge_prefill_into_paged(
                    dcache, pcache, pt_new, row_idx, lens, caps
                )
                logits_live = logits_live.at[row_idx].set(
                    new_logits[:, -1], mode="drop"
                )
                return pcache, logits_live

            fn = donating_jit(admit, donate_argnums=(1, 2))
            self._admit_fns[key] = fn
        return fn

    def _decode_for(self, n_steps: int, rounds: int):
        """Jitted decode window per (block, rounds, rows): a ``lax.scan``
        over ``rounds`` chained ``decode_chunk`` calls — identical, token
        for token, to ``rounds`` separate per-round dispatches, but with a
        single host round-trip and the logits/pool buffers donated so the
        paged KV pool updates in place."""

        key = (n_steps, rounds, self.rows)
        fn = self._decode_fns.get(key)
        if fn is None:
            fn = donating_jit(
                decode_window(self.model, n_steps, rounds, self._token_floor),
                donate_argnums=(1, 2),
            )
            self._decode_fns[key] = fn
        return fn

    def _ensure_suffix_pools(self, ex) -> None:
        """Shared cut-suffix K/V page pools, keyed by MODEL layer index.

        Every lane whose cut is <= a layer writes that layer's suffix KV
        into the same physical pool — page ids are globally unique (one
        allocator), so heterogeneous-cut lanes batch their compatible
        suffixes without per-lane pool copies.  Dropped (with the lane row
        arrays) whenever no lane holds buffers.
        """

        if self._suffix_pools is None:
            self._suffix_pools = {}
        for layer in range(ex.cut_layer, self.model.cfg.num_layers):
            if self.model.specs[layer][0] == "attn" and layer not in self._suffix_pools:
                self._suffix_pools[layer] = ex.init_layer_pool(self.paged_spec)

    def _split_fused_step(self, lanes: List["_SplitLane"], n_steps: int) -> None:
        """Dispatch one fused jitted decode over every active split lane.

        Ascending-cut lanes join a progressively concatenated row batch at
        their cut layer, so the shared tail layers run once over the
        combined rows.  The shared pools and every lane's carries (edge
        caches, recurrent state, logits) are donated and rebound here; the
        per-lane tokens/logits stay on device until ``harvest``.
        """

        lanes = sorted(lanes, key=lambda l: _lane_order(l.key))
        ex = lanes[0].ex
        cuts = tuple(l.cut for l in lanes)
        offloads = tuple(l.expert_offload for l in lanes)
        key = (cuts, offloads, n_steps)
        fn = self._fleet_fns.get(key)
        if fn is None:
            fn = ex.build_fleet_decode(
                cuts, n_steps, self._token_floor,
                offloads=offloads if any(offloads) else None,
            )
            self._fleet_fns[key] = fn
        # only the layers the fused call returns may be donated — an entry
        # for a shallower (currently idle) cut must stay alive
        pools = {l: p for l, p in self._suffix_pools.items() if l >= cuts[0]}
        lane_in = tuple(
            {
                "logits": jnp.asarray(l._logits, jnp.float32),
                "edge": l._edge,
                "state": l._state,
                "lens": jnp.asarray(l._len),
            }
            for l in lanes
        )
        pts = tuple(jnp.asarray(l._pt) for l in lanes)
        caps = tuple(jnp.asarray(l._cap) for l in lanes)
        toks, new_lanes, new_pools = fn(
            ex._per_layer, ex._base, pools, lane_in, pts, caps
        )
        self._suffix_pools = {**self._suffix_pools, **new_pools}
        for lane, nl, tk in zip(lanes, new_lanes, toks):
            lane._edge = nl["edge"]
            lane._state = nl["state"]
            lane._pending_logits = nl["logits"]
            lane._pending_toks = tk

    def _reserve(self, req: ChunkRequest) -> _Sequence:
        pages = self.allocator.alloc(self.pages_per_req)
        row = self._take_row()
        seq = _Sequence(
            robot_id=req.robot_id,
            row=row,
            remaining=self.total_tokens,
            pages=pages,
            request=req,
            admitted_round=self.round,
        )
        self._seqs[row] = seq
        return seq

    def _try_admit(self) -> None:
        """Admit pending requests FIFO across ALL lanes — partitioned
        suffixes (any cut) and cloud-only robots compete for the same pages
        in submission order, so no kind can starve another.  A head whose
        ``earliest_round`` lies in the future holds its lane back this round
        (deferred admissions keep their FIFO slot).  The capacity of every
        row released since the last admission is zeroed first, so a row
        re-admitted here keeps its new capacity.  The whole admission is
        the ``sched.admit`` span."""

        with self._span("sched.admit") as span:
            self._flush_releases()
            if self._prefill_device is not None and self._pending_admit:
                # disaggregation phase 2: last boundary's prefill-device
                # results merge into the live pool before any new
                # reservations, so a cancelled pending sequence's recycled
                # pages are never touched
                self._merge_pending()
            new: List[_Sequence] = []
            new_split: Dict[object, list] = {}
            while self.allocator.num_free >= self.pages_per_req:
                heads = []
                if self._queue and self._queue[0].earliest_round <= self.round:
                    heads.append((self._queue[0].order, None))
                for key, lane in self._lanes.items():
                    if lane.queue and lane.queue[0].earliest_round <= self.round:
                        heads.append((lane.queue[0].order, key))
                if not heads:
                    break
                # orders are globally unique, so min() never compares lane keys
                _, key = min(heads, key=lambda h: h[0])
                if key is None:
                    new.append(self._reserve(self._queue.popleft()))
                else:
                    lane = self._lanes[key]
                    new_split.setdefault(key, []).append(
                        lane.reserve(lane.queue.popleft())
                    )
            n = _bucket(len(new)) if new else 0
            if span:
                span.set(admitted=len(new) + sum(map(len, new_split.values())),
                         padded=n)
            if self.obs is not None and (new or new_split):
                # one clock read per admission boundary: every sequence
                # admitted here ends its queue-wait span on the same stamp
                t_adm = clock()
                m = self.obs.metrics
                admitted = new + [s for seqs in new_split.values() for s in seqs]
                m.counter("sched.admissions").inc(len(admitted))
                qw = m.histogram("serve.queue_wait_ms")
                for seq in admitted:
                    seq.admit_ts = t_adm
                    qw.observe((t_adm - seq.request.submit_ts) * 1e3)
            for key, seqs in new_split.items():
                self._lanes[key].flush(seqs)
            if not new:
                return
            if self._prefill_device is not None:
                self._dispatch_prefill(new)
                return
            obs = np.zeros((n, self.prompt_len), np.int64)
            pt_new = np.zeros((n, self.pages_per_req), np.int32)
            row_idx = np.full((n,), self.rows, np.int32)  # OOB rows -> dropped
            lens = np.zeros((n,), np.int32)
            caps = np.zeros((n,), np.int32)
            for i, seq in enumerate(new):
                obs[i] = seq.request.obs
                pt_new[i] = seq.pages
                row_idx[i] = seq.row
                lens[i] = self.prompt_len
                caps[i] = self.cap_tokens
            if self.obs is not None:
                # the requests this admission program serves, padding left out
                self.obs.metrics.counter("sched.admitted_requests").inc(len(new))
            self._pcache, self._logits = self._admit_for(n)(
                self.params, self._pcache, self._logits,
                jnp.asarray(obs), jnp.asarray(pt_new), jnp.asarray(row_idx),
                jnp.asarray(lens), jnp.asarray(caps),
            )

    def _release(self, seq: _Sequence) -> None:
        """Return pages + row, and mark the row for ``_flush_releases``,
        which zeroes its capacity before any program runs again, so the
        (still batched) row can never write into pages a later admission
        reuses."""

        self.allocator.free(seq.pages)
        del self._seqs[seq.row]
        self._free_rows.append(seq.row)
        self._release_mask[seq.row] = True

    def _flush_releases(self) -> None:
        """Zero the capacity of every row released since the last flush,
        in one program; with none released, dispatch nothing."""

        n = int(self._release_mask.sum())
        if not n:
            return
        # a fresh mask, not a cleared one: the dispatched copy may still
        # read the host buffer
        mask, self._release_mask = self._release_mask, np.zeros(self.rows, bool)
        self._pcache["cap"] = _zero_caps(self._pcache["cap"], mask)
        if self.obs is not None:
            m = self.obs.metrics
            m.counter("sched.release_flushes").inc()
            m.counter("sched.released_rows").inc(n)

    # ------------------------------------------------------------------
    # prefill/decode disaggregation (``prefill_group``)
    # ------------------------------------------------------------------

    def _prefill_for(self, n: int):
        """Jitted prompt prefill pinned to the prefill device.

        Traced OUTSIDE any mesh context: the prefill group is its own
        single-device domain; computation follows the device-put params and
        tokens there, overlapping the decode devices' in-flight window.
        """

        fn = self._prefill_fns.get(n)
        if fn is None:
            def pf(params, obs):
                return self.model.prefill(params, {"tokens": obs}, extra=0)

            fn = jax.jit(pf)
            self._prefill_fns[n] = fn
        return fn

    def _merge_for(self, n: int):
        """Donated merge of a transferred prefill into the live pool."""

        key = (n, self.rows)
        fn = self._merge_fns.get(key)
        if fn is None:
            def merge(pcache, logits_live, dcache, new_logits,
                      pt_new, row_idx, lens, caps):
                pcache = self.model.merge_prefill_into_paged(
                    dcache, pcache, pt_new, row_idx, lens, caps
                )
                logits_live = logits_live.at[row_idx].set(
                    new_logits[:, -1], mode="drop"
                )
                return pcache, logits_live

            fn = donating_jit(merge, donate_argnums=(0, 1))
            self._merge_fns[key] = fn
        return fn

    def _dispatch_prefill(self, new: List[_Sequence]) -> None:
        """Disaggregated admission, phase 1 (this boundary): the batched
        prompt prefill runs asynchronously on the prefill device while the
        window just dispatched decodes on the decode devices.  The
        sequences keep their reserved rows/pages but stay ``pending`` —
        cap 0 routes any scan writes on their rows to the trash page and
        they are excluded from harvest — until the NEXT boundary merges
        the prefill KV.  One extra window of admission latency buys prompt
        prefill that no longer serializes with in-flight decode."""

        n = _bucket(len(new))
        obs = np.zeros((n, self.prompt_len), np.int64)
        for i, seq in enumerate(new):
            obs[i] = seq.request.obs
            seq.pending = True
        with no_sharding():
            new_logits, dcache = self._prefill_for(n)(
                self._prefill_params,
                jax.device_put(jnp.asarray(obs), self._prefill_device),
            )
        self._pending_admit.append((new, new_logits, dcache))

    def _merge_pending(self) -> None:
        """Disaggregated admission, phase 2 (next boundary): move the
        prefill device's dense caches to the decode side and install them
        into the live (possibly sharded) pool with the donated merge.
        Sequences cancelled while pending were released at cancel time:
        their merge rows are dropped (out-of-range row index) and their
        prompt KV routes to the trash page (len 0), so pages a later
        admission may have reused are never written."""

        pending, self._pending_admit = self._pending_admit, []
        for new, new_logits, dcache in pending:
            n = new_logits.shape[0]
            pt_new = np.zeros((n, self.pages_per_req), np.int32)
            row_idx = np.full((n,), self.rows, np.int32)
            lens = np.zeros((n,), np.int32)
            caps = np.zeros((n,), np.int32)
            for i, seq in enumerate(new):
                if seq.dead or self._seqs.get(seq.row) is not seq:
                    continue
                pt_new[i] = seq.pages
                row_idx[i] = seq.row
                lens[i] = self.prompt_len
                caps[i] = self.cap_tokens
                seq.pending = False
            # jit refuses mixed committed devices: explicitly move the
            # prefill-device results into the decode domain (replicated
            # over the mesh, or onto the default decode device)
            tgt = (
                NamedSharding(self.mesh, P())
                if self.mesh is not None else jax.devices()[0]
            )
            new_logits, dcache = jax.device_put((new_logits, dcache), tgt)
            self._pcache, self._logits = self._merge_for(n)(
                self._pcache, self._logits, dcache, new_logits,
                jnp.asarray(pt_new), jnp.asarray(row_idx),
                jnp.asarray(lens), jnp.asarray(caps),
            )

    # ------------------------------------------------------------------
    # observability producers (all guarded: no-ops when ``obs`` is None)
    # ------------------------------------------------------------------

    def _obs_cancel(self, robot_id: int, submit_ts: float,
                    queued: bool = False, dead: bool = False,
                    cut: Optional[int] = None) -> None:
        """Stamp a cancellation (queue removal, immediate free, or a
        mid-window dead-mark whose pages the boundary will release)."""

        if self.obs is None:
            return
        t = clock()
        m = self.obs.metrics
        m.counter("sched.cancels").inc()
        if queued:
            m.counter("sched.cancelled_queued").inc()
        if dead:
            m.counter("sched.dead_marked").inc()
        tr = self.obs.trace
        if tr is not None:
            args = {"robot": robot_id, "queued": queued, "dead": dead}
            if cut is not None:
                args["cut"] = cut
            track = f"robot {robot_id}"
            if submit_ts > 0.0:
                tr.complete(track, "cancelled", submit_ts, t, args)
            else:
                tr.instant(track, "cancelled", t, args)

    def _obs_complete(self, results: List[ChunkResult], t_end: float) -> None:
        """Stamp harvested completions with the boundary's single clock
        read ``t_end`` — every result of one window shares it exactly, so
        chunk spans end on their window's close timestamp."""

        if self.obs is None or not results:
            return
        m = self.obs.metrics
        m.counter("sched.completions").inc(len(results))
        h = m.histogram("serve.chunk_latency_ms")
        tr = self.obs.trace
        for r in results:
            r.completed_ts = t_end
            h.observe((t_end - r.submitted_ts) * 1e3)
            if tr is not None:
                track = f"robot {r.robot_id}"
                args = {"robot": r.robot_id, "kind": r.kind,
                        "rounds": r.completed_round - r.submitted_round}
                if r.cut is not None:
                    args["cut"] = r.cut
                # nesting: chunk (lifetime) ⊃ queue wait ⊃ decode
                tr.complete(track, "chunk", r.submitted_ts, t_end, args)
                tr.complete(track, "queue", r.submitted_ts, r.admitted_ts)
                tr.complete(track, "decode", r.admitted_ts, t_end)

    def _obs_window_close(self, w: _ScanWindow, done: List[ChunkResult]) -> None:
        """Window boundary: one clock read covers the window span, every
        completion stamp, and the pool/queue gauge refresh."""

        t_end = clock()
        m = self.obs.metrics
        m.histogram("sched.window_ms").observe((t_end - w.t_open) * 1e3)
        if w.row_tokens is not None:
            # a row cancelled mid-window decoded for nothing: its tokens,
            # counted live or past at dispatch, move to ``cancelled``
            t = w.row_tokens
            for seq in w.seqs + [s for seqs in w.lane_seqs.values() for s in seqs]:
                if seq.dead:
                    take = min(seq.remaining, w.n_steps)
                    t["live"] -= take
                    t["past"] -= w.n_steps - take
                    t["cancelled"] += w.n_steps
            for state, n in t.items():
                m.counter("sched.row_tokens", state=state).inc(n)
        tr = self.obs.trace
        if tr is not None:
            name = f"window {self.windows}"
            if w.toks is not None:
                tr.complete("lane cloud", name, w.t_open, t_end,
                            {"rows": len(w.seqs), "rounds": self.scan_rounds})
            for key, seqs in w.lane_seqs.items():
                tr.complete(f"lane {self._lanes[key].label}", name, w.t_open,
                            t_end,
                            {"rows": len(seqs), "rounds": self.scan_rounds})
        self._obs_complete(done, t_end)
        alloc = self.allocator
        m.gauge("pool.pages_in_use").set(alloc.num_in_use)
        m.gauge("pool.high_water").set(alloc.high_water)
        m.gauge("pool.page_allocs_total").set(alloc.total_allocs)
        m.gauge("pool.page_frees_total").set(alloc.total_frees)
        if alloc.num_shards > 1:
            # per-data-shard pool gauges (host counters — no device syncs)
            m.gauge("pool.num_shards").set(alloc.num_shards)
            for s, (iu, hw) in enumerate(
                zip(alloc.shard_in_use, alloc.shard_high_water)
            ):
                m.gauge("pool.shard_pages_in_use", shard=str(s)).set(iu)
                m.gauge("pool.shard_high_water", shard=str(s)).set(hw)

    def step(self) -> List[ChunkResult]:
        """Advance one decode round.

        ``scan_rounds == 1``: every call admits, runs one jitted round, and
        harvests (the classic per-round loop).  ``scan_rounds == R > 1``:
        one call per window admits and dispatches the async R-round scan,
        the next R-2 calls return [] without touching the device, and the
        R-th call syncs once and emits everything the window finished.
        """

        # every jitted entry point (admission, merge, scan window, fused
        # split) traces inside the mesh context so model-internal shard()
        # calls and the "pages"/"batch" layouts apply; without a mesh this
        # is a nullcontext and nothing changes
        with self._ctx():
            return self._step_impl()

    def _step_impl(self) -> List[ChunkResult]:
        if self._window is not None:
            self.round += 1
            self._window.steps_left -= 1
            if self._window.steps_left <= 0:
                return self._close_window()
            return []
        self.round += 1
        self._try_admit()
        n_cloud = len(self._seqs)
        n_split = sum(len(l.seqs) for l in self._lanes.values())
        self.last_round_kinds = (n_cloud, n_split)
        if n_cloud + n_split == 0:
            return []
        rounds = self.scan_rounds
        self.mixed_rounds += rounds * (n_cloud > 0 and n_split > 0)
        self.hetero_rounds += rounds * (len(self.active_cuts) >= 2)
        self.decode_rounds += rounds
        self.windows += 1
        self.peak_active = max(self.peak_active, n_cloud + n_split)
        block = self._block_for_depth(self.n_pending)
        if self.obs is not None:
            m = self.obs.metrics
            m.counter("sched.windows").inc()
            m.gauge("sched.queue_depth").set(self.n_pending)
        w = _ScanWindow(steps_left=rounds, n_steps=rounds * block)
        if self.obs is not None:
            w.t_open = clock()
        lanes = [l for l in self._lanes.values() if l.seqs]
        with self._span("sched.dispatch") as span:
            if n_cloud:
                w.toks, self._logits, self._pcache = self._decode_for(
                    block, rounds
                )(self.params, self._logits, self._pcache)
                # pending (disaggregated-prefill) rows decode into the trash
                # page this window; they are merged — and harvested — later
                w.seqs = [s for s in self._seqs.values() if not s.pending]
            if lanes:
                self._split_fused_step(lanes, w.n_steps)
                for lane in lanes:
                    w.lane_seqs[lane.key] = list(lane.seqs.values())
                    w.lane_toks[lane.key] = lane._pending_toks
                    lane._pending_toks = None
            if span:
                groups = [(w.seqs, self.rows)] if n_cloud else []
                groups += [(w.lane_seqs[l.key], l.rows) for l in lanes]
                w.row_tokens = _row_tokens(groups, w.n_steps)
                span.set(rows=sum(r for _, r in groups), steps=w.n_steps)
        self._window = w
        self._window.steps_left -= 1
        if self._window.steps_left <= 0:
            return self._close_window()
        return []

    def _close_window(self) -> List[ChunkResult]:
        """Window boundary: the one host sync, then harvest + releases.

        Sequences past their chunk kept decoding inside the scan (their
        writes land in their own spare page slots, then the trash page);
        only the first ``remaining`` tokens are taken, so the harvested
        stream is bit-identical to the per-round path.  Dead (cancelled
        mid-window) sequences release their pages here, emitting nothing.
        The host's wait for the device is the ``sched.sync`` span, the
        rest the ``sched.harvest`` span.
        """

        w, self._window = self._window, None
        self.window_closes += 1
        with self._span("sched.sync"):
            toks = None if w.toks is None else np.asarray(w.toks)
            lane_toks = {key: self._lanes[key].sync(w.lane_toks[key])
                         for key in w.lane_seqs}
        with self._span("sched.harvest"):
            done: List[ChunkResult] = []
            if toks is not None:
                for seq in w.seqs:
                    if seq.dead:
                        continue
                    take = min(seq.remaining, toks.shape[1])
                    seq.tokens.extend(int(t) for t in toks[seq.row, :take])
                    seq.remaining -= take
                    if seq.remaining == 0:
                        self._release(seq)
                        done.append(ChunkResult(
                            robot_id=seq.robot_id,
                            tokens=np.asarray(seq.tokens, np.int64),
                            submitted_round=seq.request.submitted_round,
                            admitted_round=seq.admitted_round,
                            completed_round=self.round,
                            kind="cloud",
                            pool=self.pool_stats(),
                            submitted_ts=seq.request.submit_ts,
                            admitted_ts=seq.admit_ts,
                        ))
                for seq in w.seqs:
                    if seq.dead and self._seqs.get(seq.row) is seq:
                        self._release(seq)
            for key, seqs in w.lane_seqs.items():
                done.extend(self._lanes[key].harvest(seqs, lane_toks[key], self.round))
            if self.obs is not None:
                self._obs_window_close(w, done)
        return done

    def compiled_decode_window(self):
        """The compiled cloud decode-window program at the live row count.

        For inspection (``as_text()``, ``memory_analysis()``): lowering
        neither runs the window nor donates the live buffers.
        """

        with self._ctx():
            fn = self._decode_for(
                self._block_for_depth(self.n_pending), self.scan_rounds
            )
            return fn.lower(self.params, self._logits, self._pcache).compile()

    def drain(self, max_rounds: int = 10_000) -> List[ChunkResult]:
        """Run rounds until queue and batch are empty; return all results."""

        out: List[ChunkResult] = []
        rounds = 0
        while (self.n_pending or self.n_active) and rounds < max_rounds:
            out.extend(self.step())
            rounds += 1
        return out


# ---------------------------------------------------------------------------
# split lane: partitioned robots' cloud suffixes in the shared rounds
# ---------------------------------------------------------------------------


@dataclass
class _SplitSeq:
    robot_id: int
    row: int
    remaining: int
    pages: List[int]
    request: ChunkRequest
    admitted_round: int
    edge_cache: object       # dense per-robot edge-prefix caches (batch 1)
    tokens: List[int] = field(default_factory=list)
    dead: bool = False       # cancelled while its scan window was in flight
    admit_ts: float = 0.0    # obs.clock at batched-prefill admission


class _SplitLane:
    """Batched cloud-suffix decode for partitioned robots.

    The lane's edge prefixes are row-batched device caches, and a whole
    window of (argmax → edge prefix → merged suffix) steps runs inside ONE
    jitted scan (``PartitionExecutor.build_fleet_decode``) with no host
    sync — realizing the planner's pipelined ``max(edge, cloud)`` pricing,
    and batching compatible suffixes across heterogeneous cuts.

    Suffix attention KV lives in the SCHEDULER's shared per-model-layer
    pools (``_ensure_suffix_pools``); the lane holds only per-row state:
    recurrent block state, page table, lengths, logits.  Pages come from
    the scheduler's allocator, so admission of split and cloud-only work
    is fungible.
    """

    def __init__(self, sched: ContinuousBatchingScheduler, executor, rows: int):
        from repro.partition.executor import PartitionExecutor

        assert isinstance(executor, PartitionExecutor)
        self.sched = sched
        self.ex = executor
        self.cut = executor.cut_layer
        self.expert_offload = getattr(executor, "expert_offload", ())
        self.key = getattr(executor, "lane_key", executor.cut_layer)
        self.rows = rows
        self.queue: Deque[ChunkRequest] = deque()
        self.seqs: Dict[int, _SplitSeq] = {}
        self._free_rows: List[int] = list(range(rows))
        # the suffix pools share the scheduler's pool geometry (and pages)
        self.ex.build_suffix_fns(sched.paged_spec, extra=sched.total_tokens)
        # row arrays (edge caches + recurrent state + bookkeeping) are
        # allocated lazily and DROPPED whenever the lane empties — with a
        # frontier of concurrent lanes, an idle cut must not pin row state
        self._state = None       # {model layer idx: per-row recurrent state}
        self._edge = None        # row-batched edge caches
        self._pt = self._len = self._cap = self._logits = None
        self._pending_logits = None   # device logits of an in-flight window
        self._pending_toks = None

    @property
    def label(self) -> str:
        off = ("+exp" + ",".join(map(str, self.expert_offload))
               if self.expert_offload else "")
        return f"cut={self.cut}{off}"

    @property
    def has_buffers(self) -> bool:
        return self._pt is not None

    def _ensure_buffers(self) -> None:
        if self._pt is not None:
            return
        sched = self.sched
        sched._ensure_suffix_pools(self.ex)
        self._state = self.ex.init_lane_state(sched.paged_spec, self.rows)
        self._edge = self.ex.init_edge_rows(
            self.rows, sched.prompt_len + sched.total_tokens
        )
        # host-side row bookkeeping shipped into every suffix call
        self._pt = np.zeros((self.rows, sched.pages_per_req), np.int32)
        self._len = np.zeros((self.rows,), np.int32)
        self._cap = np.zeros((self.rows,), np.int32)
        self._logits = np.zeros((self.rows, sched._vdim), np.float32)

    def _drop_buffers(self) -> None:
        """Free the lane's device row arrays (nothing in flight refers to
        them); ``_ensure_buffers`` rebuilds zeros on the next admission.
        The scheduler's shared suffix pools go too once NO lane holds
        buffers — an idle fleet pins no split KV at all."""

        self._state = self._edge = None
        self._pt = self._len = self._cap = self._logits = None
        self._pending_logits = self._pending_toks = None
        sched = self.sched
        if sched._suffix_pools is not None and not any(
            l.has_buffers for l in sched._lanes.values()
        ):
            sched._suffix_pools = None

    def reset(self) -> None:
        self.queue.clear()
        self.seqs.clear()
        self._free_rows = list(range(self.rows))
        self._drop_buffers()

    def _grow_rows(self) -> None:
        old, new = self.rows, self.rows * 2
        pad = new - old
        if self._pt is not None:
            self._state = self.ex.pad_lane_state(self._state, pad)
            self._edge = self.ex.pad_edge_rows(self._edge, pad)
            self._pt = np.concatenate(
                [self._pt, np.zeros((pad, self.sched.pages_per_req), np.int32)]
            )
            self._len = np.concatenate([self._len, np.zeros((pad,), np.int32)])
            self._cap = np.concatenate([self._cap, np.zeros((pad,), np.int32)])
            self._logits = np.concatenate(
                [self._logits, np.zeros((pad, self._logits.shape[1]), np.float32)]
            )
        self._free_rows.extend(range(old, new))
        self.rows = new

    def _take_row(self) -> int:
        if not self._free_rows:
            self._grow_rows()
        return self._free_rows.pop(0)

    def release(self, seq: _SplitSeq) -> None:
        """Return pages + row; zero the row's capacity so in-flight batches
        can never write into pages a later admission reuses.  When the last
        member leaves (completion OR cancel), the lane's row arrays are
        released too — not just the row — so an emptied lane holds no
        device memory."""

        self.sched.allocator.free(seq.pages)
        del self.seqs[seq.row]
        self._free_rows.append(seq.row)
        if self.seqs:
            self._cap[seq.row] = 0
        else:
            self._drop_buffers()

    def reserve(self, req: ChunkRequest) -> _SplitSeq:
        sched = self.sched
        pages = sched.allocator.alloc(sched.pages_per_req)
        row = self._take_row()
        # one robot-chunk's modeled channel bytes (per-leg up/down counters)
        self.ex.record_chunk_bytes(sched.prompt_len, sched.total_tokens)
        # edge prefix runs on the robot's own device: batch-1 prefill
        x_cut, edge_cache = self.ex.edge_prefill(req.obs[None])
        seq = _SplitSeq(
            robot_id=req.robot_id,
            row=row,
            remaining=sched.total_tokens,
            pages=pages,
            request=req,
            admitted_round=sched.round,
            edge_cache=edge_cache,
        )
        seq._x_cut = x_cut
        self.seqs[row] = seq
        return seq

    def _layers_view(self) -> list:
        """Assemble the executor's per-cloud-layer list fresh for the
        suffix prefill: attention layers read the scheduler's SHARED pools,
        the rest this lane's per-row state."""

        pools = self.sched._suffix_pools
        out = []
        for j, s in enumerate(self.ex.cloud_specs):
            layer = self.cut + j
            out.append(pools[layer] if s[0] == "attn" else self._state[layer])
        return out

    def _writeback(self, layers: list) -> None:
        pools = dict(self.sched._suffix_pools)
        for j, s in enumerate(self.ex.cloud_specs):
            layer = self.cut + j
            if s[0] == "attn":
                pools[layer] = {"kp": layers[j]["kp"], "vp": layers[j]["vp"]}
            else:
                self._state[layer] = layers[j]
        self.sched._suffix_pools = pools

    def flush(self, new: List[_SplitSeq]) -> None:
        """Batched cloud-suffix prefill over the reserved admissions."""

        sched = self.sched
        self._ensure_buffers()
        n = _bucket(len(new))
        s = sched.prompt_len
        x = np.zeros((n, s, self.ex.cfg.d_model), np.float32)
        pt_new = np.zeros((n, sched.pages_per_req), np.int32)
        row_idx = np.full((n,), self.rows, np.int32)
        lens = np.zeros((n,), np.int32)
        caps = np.zeros((n,), np.int32)
        for i, seq in enumerate(new):
            x[i] = np.asarray(seq._x_cut[0], np.float32)
            pt_new[i] = seq.pages
            row_idx[i] = seq.row
            lens[i] = s
            caps[i] = sched.cap_tokens
            self._pt[seq.row] = seq.pages
            self._len[seq.row] = s
            self._cap[seq.row] = sched.cap_tokens
        layers, logits_new = self.ex.suffix_prefill(
            x, self._layers_view(), pt_new, row_idx, lens, caps
        )
        self._writeback(layers)
        logits_new = np.asarray(logits_new, np.float32)
        for i, seq in enumerate(new):
            self._logits[seq.row] = logits_new[i]
            del seq._x_cut
        # the robots' batch-1 edge prefill caches become rows of the lane's
        # device-resident edge state (full-row overwrite, so a recycled row
        # carries no stale KV)
        self._edge = self.ex.merge_edge_rows(
            self._edge,
            [seq.edge_cache for seq in new],
            [seq.row for seq in new],
        )
        for seq in new:
            seq.edge_cache = None

    def sync(self, toks) -> np.ndarray:
        """Window boundary: read the fused scan's logits
        and tokens back to the host.  The logits are a writable copy: the
        next ``flush`` writes admitted rows into them."""

        self._logits = np.array(self._pending_logits, np.float32)
        self._pending_logits = None
        return np.asarray(toks)

    def harvest(self, seqs: List[_SplitSeq], toks: np.ndarray,
                completed_round: int) -> List[ChunkResult]:
        """After ``sync``: take each live sequence's
        tokens (over-decoded tail discarded), release completions and dead
        (mid-window-cancelled) rows."""

        sched = self.sched
        done: List[ChunkResult] = []
        n_steps = toks.shape[1]
        live = [s for s in seqs if not s.dead]
        if live:
            self._len[[s.row for s in live]] += n_steps
        for seq in live:
            take = min(seq.remaining, n_steps)
            seq.tokens.extend(int(t) for t in toks[seq.row, :take])
            seq.remaining -= take
            if seq.remaining == 0:
                self.release(seq)
                done.append(ChunkResult(
                    robot_id=seq.robot_id,
                    tokens=np.asarray(seq.tokens, np.int64),
                    submitted_round=seq.request.submitted_round,
                    admitted_round=seq.admitted_round,
                    completed_round=completed_round,
                    kind="split",
                    pool=sched.pool_stats(),
                    cut=self.cut,
                    expert_offload=self.expert_offload,
                    submitted_ts=seq.request.submit_ts,
                    admitted_ts=seq.admit_ts,
                ))
        for seq in seqs:
            if seq.dead and self.seqs.get(seq.row) is seq:
                self.release(seq)
        return done
